"""repro.analysis — program analysis over the serving stack.

Two halves share this package:

- **Cost/HLO analysis** (``hlo``, ``roofline``, ``report``): compiled-
  program cost modelling for the QABAS search and launch dry-runs.
- **Serving-invariant analyzer** (``rules``, ``targets``, ``cli``): a
  rule-based static checker with two front ends — a recursive jaxpr
  walker over the REAL traced serving programs (``jaxpr_walk``,
  ``targets``) and an AST linter over ``src/repro`` — plus a runtime
  retrace audit. ``python -m repro.analysis`` runs it; the CI fast
  gate blocks on it. Rules: no-materialization, precision, host-sync,
  trace-stability (see ``repro/serving/__init__.py``,
  "Enforced invariants", for the contracts they pin).

Only stdlib-light names are re-exported here so ``import
repro.analysis.hlo`` keeps working without dragging in the analyzer.
"""
from repro.analysis.findings import (ALLOW_RE, Finding, apply_allowlist,
                                     inline_allowed, is_allowed,
                                     parse_allow_entry)
from repro.analysis.jaxpr_walk import (EqnSite, eqn_provenance, find_eqns,
                                       gather_sizes, iter_eqns, sub_jaxprs)

__all__ = [
    "ALLOW_RE", "Finding", "apply_allowlist", "inline_allowed",
    "is_allowed", "parse_allow_entry",
    "EqnSite", "eqn_provenance", "find_eqns", "gather_sizes",
    "iter_eqns", "sub_jaxprs",
]
