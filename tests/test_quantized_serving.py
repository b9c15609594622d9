"""Quantized serving end-to-end (fp8/int8 paged KV arena + quantized
decode matmuls).

Gates:
- per-token-per-head scale roundtrip: ``quantize_kv``/``dequantize_kv``
  are exact inverses up to the int8 grid step, and degenerate (all-zero)
  vectors clamp to ``QSCALE_MIN`` instead of dividing by zero.
- ``CacheQuantPolicy`` admission grammar: parse/describe roundtrip,
  unknown-mode and unknown-group rejection, and the fp8 platform
  fallback (a WARNING that swaps fp8 -> bf16, never a crash).
- fused-vs-reference numeric parity for int8 and fp8 arenas, GQA and
  MLA, decode (C == 1) and chunk (C > 1) ticks including the mixed
  chunk+decode row batch — on poisoned arenas where every unwritten
  byte AND every unwritten scale is a stale trap.
- recycled-block stale-scale masking: poisoned scales at unwritten
  positions must be unreachable through the pos row, in both backends.
- end-to-end engine token parity, xla vs pallas(interpret), per cache
  family (dense/GQA, MLA, hybrid SWA ring) under int8/fp8 policies,
  including block recycling on a tight arena.
- pool byte accounting: scale leaves exist exactly for int8 groups and
  are included in ``nbytes`` (no hidden bookkeeping in equal-bytes
  comparisons).
- quantized decode matmuls: ``dense`` routes PackedTensor weights
  through the Pallas ``qmatmul`` kernel exactly when the config carries
  QABAS bit-widths and the tiling contract holds; the basecaller
  ``sep_conv`` fused route agrees with the dequant fallback; packed
  int8 serving of a trained basecaller stays within a bounded read
  identity delta of its fp32 weights (the eval harness).
"""
import warnings
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import QuantPolicy, get_config
from repro.kernels import ops
from repro.kernels.paged_attention import (EMPTY_POS, QSCALE_MIN,
                                           dequantize_kv, quantize_kv)
from repro.models import api
from repro.serving import Request, ServingEngine
from repro.serving.cache import CacheQuantPolicy, fp8_supported
from repro.serving.sampling import SamplingParams

# ------------------------------------------------------------ scale roundtrip


def test_quantize_kv_roundtrip():
    """Symmetric per-vector int8: dequant error bounded by half a grid
    step per element, scale shape drops the feature axis, and the
    roundtrip is exact for values already on the grid."""
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(3, 5, 2, 16) * 4.0, jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]
    y = dequantize_kv(q, s, jnp.float32)
    step = np.broadcast_to(np.expand_dims(np.asarray(s), -1), x.shape)
    np.testing.assert_array_less(np.abs(np.asarray(y - x)),
                                 0.5 * step + 1e-7)   # half a grid step
    # grid-exact values roundtrip bit-exactly
    g = dequantize_kv(*quantize_kv(y), jnp.float32)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(y))


def test_quantize_kv_zero_vector_clamps():
    """An all-zero row (a just-reset slot) must produce QSCALE_MIN, not
    a 0/0 NaN — and dequantize back to exact zeros."""
    q, s = quantize_kv(jnp.zeros((2, 4, 8), jnp.float32))
    assert np.all(np.asarray(s) == QSCALE_MIN)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.asarray(dequantize_kv(q, s, jnp.float32)) == 0.0)


# --------------------------------------------------------- policy admission


def test_cache_quant_policy_grammar():
    p = CacheQuantPolicy.parse("int8")
    assert p.default == "int8" and p.overrides == ()
    p = CacheQuantPolicy.parse("default=bf16, g1_moe=int8")
    assert p.mode_for("g1_moe") == "int8" and p.mode_for("g0_dense") == "bf16"
    # describe() -> parse() roundtrip
    assert CacheQuantPolicy.parse(p.describe()) == p
    assert CacheQuantPolicy.parse(None) == CacheQuantPolicy()
    with pytest.raises(ValueError):
        CacheQuantPolicy.parse("int7")
    with pytest.raises(ValueError):
        CacheQuantPolicy.parse("g0_dense=int7")


def test_cache_quant_policy_unknown_group_rejected():
    p = CacheQuantPolicy.parse("g0_dense=int8,gX_typo=fp8")
    with pytest.raises(ValueError, match="gX_typo"):
        p.validate_groups(["g0_dense", "g1_moe"])
    p.validate_groups(["g0_dense", "gX_typo"])        # all known: fine


def test_cache_quant_policy_fp8_fallback_warns(monkeypatch):
    """On builds without fp8 storage, resolve() warns and serves bf16 —
    admission must never crash on a platform capability."""
    import repro.serving.cache as cache_mod
    monkeypatch.setattr(cache_mod, "fp8_supported", lambda: False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = CacheQuantPolicy.parse("fp8,g1_moe=int8").resolve()
    assert any(issubclass(x.category, RuntimeWarning) for x in w)
    assert r.default == "bf16" and r.mode_for("g1_moe") == "int8"
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        r = CacheQuantPolicy.parse("int8").resolve()  # no fp8: no warning
    assert r.default == "int8" and not w


# ------------------------------------------- quantized kernel numeric parity


def _mk_paged_q(rs, B, Hkv, hd, bl, T, n_blocks, C=1, mode="int8",
                fills=None, scale_poison=1e6):
    """Quantized poisoned arena, mirroring test_paged_attention's
    builders: every unwritten byte is poisoned AND (int8) every
    unwritten scale entry is a huge stale-scale trap. Rows hold
    ``fills[b]`` written positions plus the C in-flight chunk tokens."""
    Leff = T * bl
    kf = np.zeros((n_blocks, bl, Hkv, hd), np.float32)
    vf = np.zeros((n_blocks, bl, Hkv, hd), np.float32)
    written = np.zeros((n_blocks, bl), bool)
    table = np.full((B, T), -1, np.int32)
    pos = np.full((B, Leff), EMPTY_POS, np.int32)
    free = list(range(n_blocks))
    if fills is None:
        # C == 1 rows need >= 1 written key (an all-masked row is garbage
        # in BOTH backends by contract); chunk rows write their own keys
        fills = [Leff - C, Leff // 2, 0 if C > 1 else 1, 1]
    t = np.zeros((B, C), np.int32)
    for b in range(B):
        n = min(fills[b % len(fills)], Leff - C)
        t[b] = np.arange(n, n + C) if C > 1 else n
        top = n + C if C > 1 else n     # C==1: position n not yet written
        for j in range(T):
            if j * bl <= max(top - 1, n):
                table[b, j] = free.pop(rs.randint(len(free)))
        for p in range(top):
            blk, off = table[b, p // bl], p % bl
            kf[blk, off] = rs.randn(Hkv, hd)
            vf[blk, off] = rs.randn(Hkv, hd)
            written[blk, off] = True
            pos[b, p] = p
    if mode == "fp8":
        dt = jnp.float8_e4m3fn
        k = jnp.asarray(kf.transpose(0, 2, 1, 3)).astype(dt)
        v = jnp.asarray(vf.transpose(0, 2, 1, 3)).astype(dt)
        k = jnp.where(jnp.asarray(written)[:, None, :, None], k,
                      jnp.asarray(99.0, dt))
        return (k, v, None, None, jnp.asarray(pos), jnp.asarray(t),
                jnp.asarray(table))
    # heads-major arena layout: (n_blocks, Hkv, block_len, hd)
    kq, ks = quantize_kv(jnp.asarray(kf.transpose(0, 2, 1, 3)))
    vq, vs = quantize_kv(jnp.asarray(vf.transpose(0, 2, 1, 3)))
    w = jnp.asarray(written)[:, None, :]
    kq = jnp.where(w[..., None], kq, jnp.asarray(103, jnp.int8))
    vq = jnp.where(w[..., None], vq, jnp.asarray(-91, jnp.int8))
    ks = jnp.where(w, ks, scale_poison)               # stale-scale traps
    vs = jnp.where(w, vs, scale_poison)
    return kq, vq, ks, vs, jnp.asarray(pos), jnp.asarray(t), jnp.asarray(table)


@pytest.mark.parametrize("group,window,bl,T,C",
                         [(2, 0, 4, 4, 1),    # GQA decode tick
                          (1, 0, 4, 4, 1),    # dense decode
                          (4, 0, 16, 1, 1),   # contiguous-degenerate
                          (2, 7, 4, 4, 1),    # SWA ring window
                          (2, 0, 4, 4, 3),    # chunk crossing blocks
                          (2, 5, 2, 8, 6),    # SWA ring, chunk spans 3+
                          (1, 0, 4, 4, 4)])   # chunk == block_len
def test_gqa_int8_fused_matches_reference(group, window, bl, T, C):
    """int8 arena: the fused kernel's in-register dequant (scales as
    extra VMEM operands) == the reference's gathered ``dequantize_kv``,
    decode and chunk ticks, on poisoned bytes AND poisoned scales."""
    rs = np.random.RandomState(group * 100 + window * 10 + bl + C)
    B, Hkv, hd = 4, 2, 16
    kq, vq, ks, vs, pos, t, table = _mk_paged_q(rs, B, Hkv, hd, bl, T,
                                                B * T + 2, C)
    q = jnp.asarray(rs.randn(B, C, Hkv * group, hd), jnp.float32)
    ref = ops.decode_gqa(q, kq, vq, pos, t, window=window, table=table,
                         k_scale=ks, v_scale=vs, backend="xla")
    fused = ops.decode_gqa(q, kq, vq, pos, t, window=window, table=table,
                           k_scale=ks, v_scale=vs, backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)     # bf16 compute
    assert np.isfinite(np.asarray(fused)).all()


@pytest.mark.parametrize("C", [1, 3])
def test_gqa_fp8_fused_matches_reference(C):
    """fp8 arena (pure storage-dtype change, no scales): both backends
    compute in bf16 off the fp8 bytes and agree."""
    if not fp8_supported():
        pytest.skip("no fp8 storage on this build")
    rs = np.random.RandomState(29 + C)
    B, Hkv, hd, bl, T = 4, 2, 16, 4, 4
    k, v, _, _, pos, t, table = _mk_paged_q(rs, B, Hkv, hd, bl, T,
                                            B * T + 2, C, mode="fp8")
    q = jnp.asarray(rs.randn(B, C, 4, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, table=table, backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, table=table, backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_gqa_int8_mixed_chunk_decode_rows():
    """The mixed-tick shape under int8: a chunk row co-batched with a
    padded decode row and a free slot — live queries match, pad queries
    stay finite (no poison or stale-scale leak)."""
    rs = np.random.RandomState(31)
    B, Hkv, hd, bl, T, C = 4, 2, 16, 4, 4, 3
    kq, vq, ks, vs, pos, t, table = _mk_paged_q(rs, B, Hkv, hd, bl, T,
                                                B * T + 2, C)
    t = np.asarray(t).copy()
    t[1, 1:] = -1                 # decode row padded to C
    t[2, :] = -1                  # free slot
    t = jnp.asarray(t)
    q = jnp.asarray(rs.randn(B, C, 4, hd), jnp.float32)
    ref = ops.decode_gqa(q, kq, vq, pos, t, table=table,
                         k_scale=ks, v_scale=vs, backend="xla")
    fused = ops.decode_gqa(q, kq, vq, pos, t, table=table,
                           k_scale=ks, v_scale=vs, backend="pallas")
    live = np.asarray(t) >= 0
    np.testing.assert_allclose(np.asarray(fused)[live],
                               np.asarray(ref)[live], rtol=2e-2, atol=2e-2)
    assert np.isfinite(np.asarray(fused)).all()


@pytest.mark.parametrize("bl,T,C", [(4, 4, 1), (16, 1, 1), (4, 4, 3)])
def test_mla_int8_fused_matches_reference(bl, T, C):
    """int8 latent arena: per-token c/kr scales through the absorbed-MLA
    fused kernel == the dequantizing gather reference."""
    rs = np.random.RandomState(bl + T + C)
    B, H, kvr, rope_d = 4, 4, 16, 8
    cq, krq, cs, krs, pos, t, table = _mk_paged_q(rs, B, 1, kvr, bl, T,
                                                  B * T + 2, C)
    cq, cs = cq[:, 0], cs[:, 0]
    krq = jnp.asarray(np.asarray(krq)[:, 0, :, :rope_d].copy())
    krs_full = krs[:, 0]
    # kr is quantized over its own rope_d slice in the real cache; re-do
    krq2, krs2 = quantize_kv(dequantize_kv(krq, krs_full, jnp.float32))
    qa = jnp.asarray(rs.randn(B, C, H, kvr), jnp.float32)
    qr = jnp.asarray(rs.randn(B, C, H, rope_d), jnp.float32)
    ref = ops.decode_mla(qa, qr, cq, krq2, pos, t, scale=0.17, table=table,
                         c_scale=cs, kr_scale=krs2, backend="xla")
    fused = ops.decode_mla(qa, qr, cq, krq2, pos, t, scale=0.17,
                           table=table, c_scale=cs, kr_scale=krs2,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    assert np.isfinite(np.asarray(fused)).all()


def test_recycled_block_stale_scales_never_leak():
    """A recycled block's old scales are garbage the moment it leaves
    the free list. Writing the SAME arena with clean (1.0) scales at the
    unwritten positions must not change either backend's output — i.e.
    the pos row alone fences stale scales, in lockstep with stale KV."""
    rs = np.random.RandomState(37)
    B, Hkv, hd, bl, T = 4, 2, 16, 4, 4
    kq, vq, ks, vs, pos, t, table = _mk_paged_q(
        rs, B, Hkv, hd, bl, T, B * T + 2, scale_poison=1e6)
    clean = jnp.where(ks >= 1e6, 1.0, ks), jnp.where(vs >= 1e6, 1.0, vs)
    q = jnp.asarray(rs.randn(B, 1, 4, hd), jnp.float32)
    for backend in ("xla", "pallas"):
        poisoned = ops.decode_gqa(q, kq, vq, pos, t, table=table,
                                  k_scale=ks, v_scale=vs, backend=backend)
        fenced = ops.decode_gqa(q, kq, vq, pos, t, table=table,
                                k_scale=clean[0], v_scale=clean[1],
                                backend=backend)
        np.testing.assert_array_equal(np.asarray(poisoned),
                                      np.asarray(fenced), err_msg=backend)
        assert np.isfinite(np.asarray(poisoned)).all()


# --------------------------------------------------- engine token parity


def _drain(arch, backend, spec, policy, seed=0, **kw):
    cfg = get_config(arch)
    params = api.init_params(jax.random.key(0), cfg)
    rs = np.random.RandomState(seed)
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("block_len", 4)
    eng = ServingEngine(params, cfg, attn_backend=backend,
                        quant_policy=policy, **kw)
    for i, (pl, mn) in enumerate(spec):
        eng.submit(Request(
            rid=i, prompt=rs.randint(1, cfg.vocab_size, size=pl).tolist(),
            sampling=SamplingParams(max_new_tokens=mn)))
    done = eng.run()
    return {i: done[i].out_tokens for i in done}, eng


QUANT_FAMILIES = [("qwen1.5-4b-smoke", "int8"),
                  ("qwen1.5-4b-smoke", "fp8"),
                  ("deepseek-v3-671b-smoke", "int8"),
                  ("hymba-1.5b-smoke", "int8")]


@pytest.mark.parametrize("arch,policy", QUANT_FAMILIES)
def test_engine_quantized_backend_parity(arch, policy):
    """Greedy tokens are identical between the fused and reference
    backends with a quantized arena — GQA, MLA latents, hybrid SWA ring
    — through real mixed chunk+decode engine ticks."""
    if policy == "fp8" and not fp8_supported():
        pytest.skip("no fp8 storage on this build")
    spec = [(6, 8), (10, 5), (3, 6)]
    ref, re = _drain(arch, "xla", spec, policy, cache_len=48)
    fused, fe = _drain(arch, "pallas", spec, policy, cache_len=48)
    assert fused == ref
    assert fe.pool.quant_policy.default == policy
    # mixed ticks really ran
    assert any(k[0] == "mixed" for k in re.runner.plans._warmed)


def test_engine_quantized_recycle_parity():
    """Tight int8 arena: blocks recycle across requests — stale bytes
    AND stale scales from prior tenants must be fenced identically in
    both backends (token equality), and recycling must really happen."""
    spec = [(6, 8), (6, 8), (5, 4)]
    ref, _ = _drain("qwen1.5-4b-smoke", "xla", spec, "int8",
                    cache_len=16, n_blocks=5)
    fused, fe = _drain("qwen1.5-4b-smoke", "pallas", spec, "int8",
                       cache_len=16, n_blocks=5)
    assert fused == ref
    assert fe.pool.alloc_count > 5


def test_engine_per_group_policy_and_bytes():
    """Mixed per-group policy on a tight pool: scale leaves exist for
    exactly the int8 groups, byte accounting sums to nbytes, and the
    int8 arena really shrinks vs bf16 at equal slots."""
    cfg = get_config("qwen1.5-4b-smoke")
    params = api.init_params(jax.random.key(0), cfg)

    def pool_of(policy):
        eng = ServingEngine(params, cfg, n_slots=2, cache_len=32,
                            block_len=4, quant_policy=policy)
        return eng.runner.pool

    base = pool_of("bf16")
    q8 = pool_of("int8")
    by_b, by_q = base.nbytes_by_class(), q8.nbytes_by_class()
    assert by_b["scales"] == 0 and by_q["scales"] > 0
    assert sum(by_b.values()) == base.nbytes()
    assert sum(by_q.values()) == q8.nbytes()
    assert by_q["arena"] * 2 == by_b["arena"]     # int8 halves the bytes
    if fp8_supported():
        f8 = pool_of("fp8").nbytes_by_class()
        assert f8["scales"] == 0 and f8["arena"] * 2 == by_b["arena"]


# ------------------------------------------------ quantized decode matmuls


def test_dense_routes_packed_weight_through_qmatmul(monkeypatch):
    """`dense` takes the Pallas qmatmul route exactly when the config
    carries 8-bit QABAS widths AND the tiling contract holds — and the
    route is numerically the integer matmul (exact vs the fp32 int
    reference), falling back cleanly otherwise."""
    from repro.core.quant.policy import quantize_tensor
    from repro.models.lm import common

    cfg = replace(get_config("qwen1.5-4b-smoke"), dtype="float32",
                  quant=QuantPolicy(weight_bits=8, act_bits=0))
    rs = np.random.RandomState(3)
    w = jnp.asarray(rs.randn(64, 128), jnp.float32)
    w_p = quantize_tensor(w, 8)
    calls = []
    real = ops.qmatmul
    monkeypatch.setattr(ops, "qmatmul",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    x = jnp.asarray(rs.randn(4, 64), jnp.float32)
    y = common.dense({"kernel": w_p}, x, cfg=cfg, tag="mlp/wi")
    assert calls == [1]
    want = (np.asarray(x) @ np.asarray(w_p.data, np.float32)) \
        * np.asarray(w_p.scale)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-6, atol=1e-6)
    # M=130 breaks the tiling contract (130 % 128 != 0) -> dequant
    # fallback, no kernel call, same numbers to rounding
    x130 = jnp.asarray(rs.randn(130, 64), jnp.float32)
    y130 = common.dense({"kernel": w_p}, x130, cfg=cfg, tag="mlp/wi")
    assert calls == [1]
    want130 = (np.asarray(x130) @ np.asarray(w_p.data, np.float32)) \
        * np.asarray(w_p.scale)
    np.testing.assert_allclose(np.asarray(y130), want130,
                               rtol=1e-5, atol=1e-5)
    # a 16-bit layer (QABAS keeps it high-precision) never takes the route
    cfg16 = replace(cfg, quant=QuantPolicy(
        weight_bits=8, act_bits=0, overrides=(("mlp/wi", (16, 16)),)))
    common.dense({"kernel": w_p}, x, cfg=cfg16, tag="mlp/wi")
    assert calls == [1]


def _packed_block(cfg):
    """rubicall-smoke block params packed for serving, under a config
    whose QABAS widths put every block at 8 bits (the smoke truncation
    keeps only the 16-bit head of the real depth profile). min_size=1:
    smoke conv leaves are tiny, but the full-size arch packs them."""
    from repro.core.quant.policy import quantize_tree
    from repro.models.basecaller import model as bc
    cfg8 = replace(cfg, quant=QuantPolicy(weight_bits=8, act_bits=8))
    params = bc.init_params(jax.random.key(1), cfg8)
    state = bc.init_state(cfg8)
    qt = quantize_tree(params, QuantPolicy(weight_bits=8, act_bits=0),
                       min_size=1)
    return cfg8, params, qt, state


def test_sep_conv_fused_route_matches_fallback(monkeypatch):
    """The fused qconv1d block (in-kernel dequant + folded BN) agrees
    with the dequant-on-read fallback within int8 grid tolerance, and
    the fused route really fires for the stride-1 square blocks."""
    from repro.kernels.ops import qconv1d_block
    from repro.models.basecaller import model as bc

    cfg8, params, qt, state = _packed_block(get_config("rubicall-smoke"))
    rs = np.random.RandomState(5)
    x = jnp.asarray(rs.randn(2, 48, 1), jnp.float32)
    fused_calls = []
    real = qconv1d_block
    import repro.kernels.ops as ops_mod
    monkeypatch.setattr(ops_mod, "qconv1d_block",
                        lambda *a, **k: fused_calls.append(1)
                        or real(*a, **k))
    lp_fused, _ = bc.forward(qt, state, x, cfg8, train=False)
    # blocks 1..3 are stride-1 square 32->32: the fused kernel must fire
    assert len(fused_calls) >= 3
    # force the fallback by disabling the QABAS gate (bits 16 everywhere)
    cfg16 = replace(cfg8, quant=QuantPolicy(weight_bits=16, act_bits=0))
    lp_fb, _ = bc.forward(qt, state, x, cfg16, train=False)
    assert len(fused_calls) >= 3                  # unchanged: no new calls
    np.testing.assert_allclose(np.asarray(lp_fused), np.asarray(lp_fb),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_basecaller_packed_int8_identity_delta():
    """Bounded accuracy delta on the eval harness: packed-int8 serving
    weights of a briefly-trained rubicall-smoke stay within 2 points of
    read identity of the fp32 weights (the QAT-trained model should be
    nearly lossless under its own 8-bit grid)."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks.common import eval_identity, train_model
    from repro.core.quant.policy import quantize_tree

    cfg = replace(get_config("rubicall-smoke"),
                  quant=QuantPolicy(weight_bits=8, act_bits=8))
    params, state, _ = train_model(cfg, steps=300)
    ident_fp = eval_identity(cfg, params, state, n_batches=2)
    qt = quantize_tree(params, QuantPolicy(weight_bits=8, act_bits=0),
                       min_size=1)
    ident_q = eval_identity(cfg, qt, state, n_batches=2)
    assert ident_fp > 0.3          # the harness really learned something
    assert abs(ident_fp - ident_q) < 0.02, (ident_fp, ident_q)
