"""``python -m repro.analysis`` — run the serving-invariant rules.

Exit status 0 when every rule passes (after allowlist suppression),
1 when any finding survives, 2 on usage errors. The CI fast gate runs
this as a blocking step; see ``repro/serving/__init__.py`` ("Enforced
invariants") for what each rule guards.

Usage:
    python -m repro.analysis                     # all rules
    python -m repro.analysis --rules precision,host-sync
    python -m repro.analysis --list-rules
    python -m repro.analysis --json              # machine-readable
    python -m repro.analysis --allow 'precision:qmatmul*'
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback
from typing import List, Optional, Sequence

from repro.analysis.allowlist import DEFAULT_ALLOWLIST
from repro.analysis.context import AnalysisContext
from repro.analysis.findings import Finding, apply_allowlist
from repro.analysis.rules import all_rules


def run_rules(ctx: AnalysisContext,
              names: Optional[Sequence[str]] = None) -> List[Finding]:
    """Run the (selected) registered rules; a rule that crashes is
    itself a finding — the gate must not silently skip checks."""
    findings: List[Finding] = []
    for r in all_rules(names):
        try:
            findings.extend(r.check(ctx))
        except Exception:
            tb = traceback.format_exc().strip().splitlines()[-1]
            findings.append(Finding(
                r.id, f"rule:{r.id}",
                f"rule crashed instead of checking: {tb}"))
    return findings


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static + trace analysis of the serving invariants")
    p.add_argument("--rules", default=None,
                   help="comma-separated rule ids (default: all)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the registry and exit")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit findings as JSON")
    p.add_argument("--root", default=None,
                   help="source root to lint (default: src/repro)")
    p.add_argument("--allow", action="append", default=[],
                   metavar="RULE[:GLOB]",
                   help="extra allowlist entry (repeatable)")
    p.add_argument("--no-default-allowlist", action="store_true",
                   help="ignore DEFAULT_ALLOWLIST")
    args = p.parse_args(argv)

    if args.list_rules:
        for r in all_rules():
            print(f"{r.id:20s} [{r.kind:7s}] {r.doc}")
        return 0

    names = ([n.strip() for n in args.rules.split(",") if n.strip()]
             if args.rules else None)
    ctx = (AnalysisContext(src_root=args.root, rel_prefix="")
           if args.root else AnalysisContext())
    try:
        findings = run_rules(ctx, names)
    except ValueError as e:                       # unknown rule name
        print(f"error: {e}", file=sys.stderr)
        return 2

    allowlist = (list(() if args.no_default_allowlist
                      else DEFAULT_ALLOWLIST) + args.allow)
    kept, suppressed = apply_allowlist(findings, allowlist)

    if args.as_json:
        print(json.dumps({
            "findings": [vars(f) for f in kept],
            "suppressed": [vars(f) for f in suppressed]}, indent=2))
    else:
        for f in kept:
            print(f)
        tail = f" ({len(suppressed)} suppressed)" if suppressed else ""
        if kept:
            print(f"repro.analysis: {len(kept)} finding(s){tail}")
        else:
            print(f"repro.analysis: clean{tail}")
    return 1 if kept else 0
