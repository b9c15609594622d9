"""repro.analysis — the serving-invariant analyzer.

Each rule gets a seeded violation (a deliberately-broken program or
source snippet) asserting the finding fires WITH correct provenance,
plus the clean cases that must not fire. The full-repo CLI run (the CI
gate itself) is the slow test at the bottom.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.context import AnalysisContext
from repro.analysis.findings import (Finding, apply_allowlist,
                                     inline_allowed, is_allowed)
from repro.analysis.jaxpr_walk import gather_sizes, iter_eqns
from repro.analysis.rules import all_rules
from repro.analysis.targets import TraceTarget
from repro.analysis.cli import main, run_rules

SRC = Path(__file__).resolve().parents[1] / "src"


# ------------------------------------------------------------- registry


def test_registry_has_the_five_rules():
    ids = [r.id for r in all_rules()]
    assert ids == sorted(["no-materialization", "precision", "host-sync",
                          "trace-stability"])


def test_registry_rejects_unknown_rule():
    with pytest.raises(ValueError, match="unknown rules"):
        all_rules(["no-such-rule"])


# ---------------------------------------------------------- jaxpr walker


def test_walker_descends_into_pjit_and_scan():
    def f(x):
        def body(c, _):
            return c @ jnp.ones((4, 4)), None
        y, _ = jax.lax.scan(body, x, None, length=2)
        return jnp.take(y, jnp.asarray([0, 1]), axis=0)

    jx = jax.make_jaxpr(jax.jit(f))(jnp.zeros((4, 4)))
    names = [s.eqn.primitive.name for s in iter_eqns(jx)]
    assert "scan" in names and "gather" in names
    (gsite,) = [s for s in iter_eqns(jx)
                if s.eqn.primitive.name == "gather"]
    # provenance: jnp.take nests its clipping helper inside the jit
    assert gsite.path[0] == "jit"
    assert gsite.path_str.endswith("/gather")
    assert gather_sizes(jx) == [2 * 4]


# ------------------------------------------------- rule: materialization


def _seeded_target(fn, args, backend, name="seeded", **kw):
    meta = dict(kind="attn-op", quantized=False, n_slots=2, block_len=4,
                arena_sigs={(10, 4): 4})
    meta.update(kw)
    return TraceTarget(name=name, jaxpr=jax.make_jaxpr(fn)(*args),
                       backend=backend, **meta)


def test_materialization_flags_arena_gather_on_pallas():
    from repro.analysis.rules.materialization import check_target
    k = jnp.zeros((10, 4, 2, 16))             # arena-shaped (Nb, bl, ...)
    idx = jnp.zeros((8,), jnp.int32)          # B*T rows -> full view

    tgt = _seeded_target(lambda k, i: jnp.take(k, i, axis=0), (k, idx),
                         "pallas")
    (f,) = check_target(tgt)
    assert f.rule == "no-materialization"
    assert f.where.startswith("seeded::") and "gather" in f.where
    assert "logical KV view" in f.message

    # same program on the xla backend IS the oracle: no finding
    assert check_target(_seeded_target(
        lambda k, i: jnp.take(k, i, axis=0), (k, idx), "xla")) == []


def test_materialization_flags_oracle_drift_on_xla():
    from repro.analysis.rules.materialization import check_target
    k = jnp.zeros((10, 4, 2, 16))
    (f,) = check_target(_seeded_target(lambda k: k * 2.0, (k,), "xla"))
    assert f.where == "seeded::oracle" and "oracle" in f.message


def test_materialization_ignores_non_arena_gathers():
    from repro.analysis.rules.materialization import check_target
    emb = jnp.zeros((256, 64))                # embedding table, not arena
    idx = jnp.zeros((2, 4), jnp.int32)
    assert check_target(_seeded_target(
        lambda e, i: jnp.take(e, i, axis=0), (emb, idx), "pallas")) == []


# ------------------------------------------------------- rule: precision


def test_precision_flags_bf16_accumulator_attention():
    from repro.analysis.rules.precision import check_target
    q = jnp.zeros((2, 8, 16), jnp.bfloat16)
    k = jnp.zeros((2, 8, 16), jnp.bfloat16)

    def bad_attn(q, k):                       # bf16 accumulation
        return jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,))))

    (f,) = check_target(_seeded_target(bad_attn, (q, k), "xla",
                                       arena_sigs={}))
    assert f.rule == "precision"
    assert "low-precision accumulator" in f.message
    assert "dot_general" in f.where


def test_precision_flags_bf16_softmax_stats():
    from repro.analysis.rules.precision import check_target
    s = jnp.zeros((2, 16), jnp.bfloat16)
    found = check_target(_seeded_target(
        lambda s: jax.nn.softmax(s, axis=-1), (s,), "xla", arena_sigs={}))
    assert {f.rule for f in found} == {"precision"}
    assert any("exp over bfloat16" in f.message for f in found)


def test_precision_flags_laundering_downcast_on_quantized_path():
    from repro.analysis.rules.precision import check_target
    s = jnp.zeros((2, 16), jnp.float32)

    def launder(s):                           # fp32 stats -> bf16 exp
        return jnp.exp(s.astype(jnp.bfloat16))

    found = check_target(_seeded_target(launder, (s,), "xla",
                                        quantized=True, arena_sigs={}))
    assert any("downcast" in f.message for f in found)
    # the same downcast is fine when nothing stats-like consumes it
    # (that IS the dequant contract's shape)
    assert check_target(_seeded_target(
        lambda s: s.astype(jnp.bfloat16) * 2, (s,), "xla",
        quantized=True, arena_sigs={})) == []


def test_precision_accepts_the_dequant_contract():
    from repro.analysis.rules.precision import check_target
    from repro.kernels.paged_attention import dequantize_kv
    q = jnp.zeros((10, 4, 16), jnp.int8)
    sc = jnp.zeros((10, 4), jnp.float32)
    w = jnp.zeros((16, 16), jnp.bfloat16)

    def contract(q, sc, w):                   # dequant -> fp32-acc dot
        x = dequantize_kv(q, sc)
        return jnp.einsum("nbd,de->nbe", x, w,
                          preferred_element_type=jnp.float32)

    assert check_target(_seeded_target(contract, (q, sc, w), "xla",
                                       quantized=True, arena_sigs={})) == []


# ------------------------------------------------------- rule: host-sync


_SYNC_SNIPPET = textwrap.dedent("""\
    import numpy as np

    class R:
        def _step_decode_only(self, works):
            toks = self._prog()
            toks = np.asarray(toks){marker}
            return toks

        def helper(self):
            return np.asarray(self.x)     # not a tick function: fine
""")


def test_host_sync_flags_unannotated_tick_sync():
    from repro.analysis.rules.host_sync import check_source
    (f,) = check_source("serving/runner.py",
                        _SYNC_SNIPPET.format(marker=""))
    assert f.rule == "host-sync"
    assert f.where == "serving/runner.py:6"   # provenance: exact line
    assert "np.asarray" in f.message


def test_host_sync_accepts_marker_and_inline_allow():
    from repro.analysis.rules.host_sync import check_source
    ok = _SYNC_SNIPPET.format(marker="  # sync: scheduler needs tokens")
    assert check_source("serving/runner.py", ok) == []
    allowed = _SYNC_SNIPPET.format(marker="  # repro-allow: host-sync")
    assert check_source("serving/runner.py", allowed) == []
    # non-tick files are out of scope entirely
    assert check_source("kernels/ops.py",
                        _SYNC_SNIPPET.format(marker="")) == []


# ------------------------------------------- rule: trace-stability


def test_trace_stability_flags_fresh_static_arg():
    from repro.analysis.rules.trace_stability import audit_program
    jitted = jax.jit(lambda x, tag: x + 1, static_argnums=(1,))
    call = lambda: jitted(jnp.zeros(()), object())   # fresh key per call
    found = audit_program("seeded", jitted, call)
    assert any(f.where == "seeded::retrace" for f in found)


def test_trace_stability_accepts_stable_program():
    from repro.analysis.rules.trace_stability import audit_program
    jitted = jax.jit(lambda x: x + 1)
    x = jnp.zeros(())
    assert audit_program("stable", jitted, lambda: jitted(x)) == []


# ------------------------------------------- allowlist + driver + CLI


def test_allowlist_suppression_globs():
    f = Finding("host-sync", "serving/runner.py:6", "msg")
    assert is_allowed(f, ["host-sync:serving/*"])
    assert is_allowed(f, ["host-sync"])       # bare rule = everywhere
    assert not is_allowed(f, ["precision:serving/*"])
    kept, supp = apply_allowlist([f], ["host-sync:serving/*"])
    assert kept == [] and supp == [f]


def test_inline_allow_matches_rule_list():
    lines = ["x = 1  # repro-allow: trace-stability, host-sync"]
    assert inline_allowed(lines, 1, "trace-stability")
    assert inline_allowed(lines, 1, "host-sync")
    assert not inline_allowed(lines, 1, "precision")


def test_driver_reports_crashed_rule_as_finding(monkeypatch):
    import repro.analysis.rules.host_sync as hs
    monkeypatch.setattr(
        hs, "check_source",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom")))
    ctx = AnalysisContext()
    (f,) = [f for f in run_rules(ctx, ["host-sync"])
            if f.rule == "host-sync"]
    assert f.where == "rule:host-sync" and "crashed" in f.message


def test_cli_nonzero_on_seeded_tree_and_allow_flag(tmp_path, capsys):
    (tmp_path / "serving").mkdir()
    (tmp_path / "serving" / "runner.py").write_text(
        _SYNC_SNIPPET.format(marker=""))
    (tmp_path / "serving" / "engine.py").write_text(
        _SYNC_SNIPPET.format(marker=""))

    rc = main(["--rules", "host-sync", "--root", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "serving/engine.py:6" in out and "serving/runner.py:6" in out

    rc = main(["--rules", "host-sync", "--root", str(tmp_path),
               "--allow", "host-sync:serving/engine.py:*",
               "--allow", "host-sync:serving/runner.py:*"])
    assert rc == 0
    assert "suppressed" in capsys.readouterr().out


def test_cli_ast_rules_clean_on_repo():
    assert main(["--rules", "host-sync"]) == 0


def test_driver_flags_seeded_jaxpr_targets_through_registry():
    """Seeded violations reach the registered rules via an injected
    context — a bf16-accumulator attention program and an arena-view
    gather on the fused path both produce gate-failing findings."""
    q = jnp.zeros((2, 8, 16), jnp.bfloat16)
    bad_acc = _seeded_target(
        lambda q, k: jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (0,)))),
        (q, q), "xla", arena_sigs={})
    k = jnp.zeros((10, 4, 2, 16))
    idx = jnp.zeros((8,), jnp.int32)
    bad_gather = _seeded_target(lambda k, i: jnp.take(k, i, axis=0),
                                (k, idx), "pallas")
    ctx = AnalysisContext(jaxpr_targets=[bad_acc, bad_gather])
    found = run_rules(ctx, ["precision", "no-materialization"])
    assert {f.rule for f in found} == {"precision", "no-materialization"}


@pytest.mark.slow
def test_cli_full_gate_clean_on_repo():
    """The CI gate itself: every rule, real traced programs, exit 0."""
    env = dict(os.environ, PYTHONPATH=str(SRC))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis"], capture_output=True,
        text=True, env=env, cwd=str(SRC.parent))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout
