"""Decode-attention backend subsystem (repro.kernels.paged_attention +
the dispatch in repro.kernels.ops).

Gates:
- ``paged_indices`` sweep: block_len x n_blocks x window including ring
  wrap-around, unassigned (-1) blocks, recycled-block stale-KV masking
  via the pos/KV write lockstep, and the exact-fit
  ``prompt + max_new - 1 == cache_len`` boundary.
- fused-vs-reference numeric parity for the GQA and MLA kernels across
  paged configs (small blocks, block_len == cache_len, sliding-window
  ring, GQA grouping, pad rows, poisoned recycled blocks) — for BOTH
  the C == 1 decode tick and the C > 1 chunk variants (chunks crossing
  block boundaries, chunk == block_len exact fit, mixed chunk+decode
  row batches, bf16 arenas).
- the fused path contains NO logical-view gather (jaxpr inspection) —
  the ``(B, T*block_len)`` per-layer materialisation the kernel exists
  to remove; the reference path must still contain it (oracle check).
  Gated at C == 1 AND on a C > 1 mixed tick.
- end-to-end engine token parity, xla vs pallas(interpret), per cache
  family — dense/GQA, MLA, hybrid ring, audio cross-attn — including
  block recycling and preemption/resume; plus co-batched vs split-tick
  vs prefill-budgeted scheduling parity (mixed ticks must be a timing
  change only).
- runtime interpret resolution (the import-time INTERPRET pin fix).

On CPU the fused kernel runs in Pallas interpret mode, so the kernel
body itself is exercised by every tier-1 run.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.kernels import ops
from repro.kernels.paged_attention import (EMPTY_POS, paged_indices,
                                           valid_mask)
from repro.models import api
from repro.serving import Request, ServingEngine
from repro.serving.sampling import SamplingParams


# ------------------------------------------------------------ paged_indices


@pytest.mark.parametrize("block_len,n_blocks,T", [(4, 7, 3), (8, 4, 2),
                                                  (16, 2, 1), (2, 9, 5)])
@pytest.mark.parametrize("window", [0, 6])
def test_paged_indices_sweep(block_len, n_blocks, T, window):
    """Index math vs a literal numpy re-derivation, over positions that
    cover in-block, block-crossing, ring wrap-around (t >= Leff — what a
    sliding-window group does), pad (-1) and the exact last position."""
    rs = np.random.RandomState(block_len * 31 + T)
    B = 4
    Leff = T * block_len
    table = rs.randint(-1, n_blocks, size=(B, T)).astype(np.int32)
    table[0] = -1                                 # fully unassigned row
    # positions: pad, 0, boundary, mid, exact fit (Leff-1), ring wrap
    t = np.array([[-1], [0], [block_len], [Leff - 1]], np.int32)
    t_wrap = np.array([[Leff], [Leff + block_len - 1], [3 * Leff + 1],
                       [2 * Leff - 1]], np.int32)
    for tt in (t, t_wrap):
        wblk, off, lw, gidx, leff = paged_indices(
            jnp.asarray(table), jnp.asarray(tt), n_blocks, block_len)
        assert leff == Leff
        wblk, off, lw, gidx = map(np.asarray, (wblk, off, lw, gidx))
        for b in range(B):
            for c in range(tt.shape[1]):
                tv = int(tt[b, c])
                if tv < 0:                        # pad: all writes drop
                    assert wblk[b, c] == n_blocks
                    assert lw[b, c] == Leff
                    continue
                l = tv % Leff                     # ring wrap
                blk = table[b, l // block_len]
                if blk < 0:                       # unassigned: KV *and*
                    assert wblk[b, c] == n_blocks  # pos writes drop in
                    assert lw[b, c] == Leff        # lockstep
                else:
                    assert wblk[b, c] == blk
                    assert off[b, c] == l % block_len
                    assert lw[b, c] == l
        np.testing.assert_array_equal(gidx, np.maximum(table, 0))
    # the window never changes the indices — it's a read-side mask only
    pos = np.arange(Leff, dtype=np.int32)[None].repeat(B, 0)
    vm = np.asarray(valid_mask(jnp.asarray(pos), jnp.asarray(t), window))
    for b in range(B):
        tv = int(t[b, 0])
        want = (pos[b] >= 0) & (pos[b] <= tv)
        if window > 0:
            want &= pos[b] > tv - window
        np.testing.assert_array_equal(vm[b, 0], want)


def test_paged_indices_recycled_block_lockstep():
    """A recycled arena block (present in the table, but the slot has
    not written it yet) is masked purely by the pos row: the gather
    index DOES address it, so the pos/KV lockstep is the only guard —
    unassigned entries must drop both writes."""
    table = jnp.asarray([[3, -1]], jnp.int32)
    t = jnp.asarray([[5]], jnp.int32)             # lands in block 1: hole
    wblk, off, lw, gidx, Leff = paged_indices(table, t, 6, 4)
    assert int(wblk[0, 0]) == 6 and int(lw[0, 0]) == Leff   # both drop
    assert int(gidx[0, 1]) == 0                   # clamped gather: block 0
    # ... which is why a pos row left valid here would leak block 0's KV


# ------------------------------------------------- kernel numeric parity


def _heads_major(arena):
    """(n_blocks, block_len, Hkv, hd) as built -> the arena layout
    (n_blocks, Hkv, block_len, hd)."""
    return jnp.asarray(arena.transpose(0, 2, 1, 3))


def _mk_paged(rs, B, Hkv, hd, bl, T, n_blocks, poison=99.0):
    """Random arena with poisoned bytes everywhere (every block is
    'recycled'), a random table and per-row fill levels."""
    Leff = T * bl
    k = np.full((n_blocks, bl, Hkv, hd), poison, np.float32)
    v = np.full((n_blocks, bl, Hkv, hd), poison, np.float32)
    table = np.full((B, T), -1, np.int32)
    pos = np.full((B, Leff), EMPTY_POS, np.int32)
    free = list(range(n_blocks))
    fills = [Leff - 1, Leff // 2, 1] + [rs.randint(1, Leff)
                                        for _ in range(B - 3)]
    t = np.zeros((B, 1), np.int32)
    for b in range(B):
        n = fills[b % len(fills)]
        t[b, 0] = n                   # decoding position n; n pos written
        for j in range(-(-(n + 1) // bl)):
            if j * bl <= n:           # blocks covering [0, n]
                table[b, j] = free.pop(rs.randint(len(free)))
        for p in range(n):            # position n itself not yet written
            blk, off = table[b, p // bl], p % bl
            k[blk, off] = rs.randn(Hkv, hd)
            v[blk, off] = rs.randn(Hkv, hd)
            pos[b, p] = p
    return (_heads_major(k), _heads_major(v), jnp.asarray(pos),
            jnp.asarray(t), jnp.asarray(table))


@pytest.mark.parametrize("group,window,bl,T",
                         [(1, 0, 4, 4), (2, 0, 4, 4), (4, 0, 16, 1),
                          (2, 7, 4, 4), (2, 0, 2, 8), (2, 5, 16, 1)])
def test_gqa_fused_matches_reference(group, window, bl, T):
    """Fused kernel == gather reference over dense/GQA/sliding-window
    configs, small blocks and block_len == cache_len (T == 1, the
    contiguous-degenerate layout), on a poisoned arena (every unwritten
    byte is a stale-KV trap)."""
    rs = np.random.RandomState(group * 100 + window * 10 + bl)
    B, Hkv, hd = 4, 2, 16
    H = Hkv * group
    n_blocks = B * T + 2
    k, v, pos, t, table = _mk_paged(rs, B, Hkv, hd, bl, T, n_blocks)
    q = jnp.asarray(rs.randn(B, 1, H, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, window=window, table=table,
                         backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, window=window, table=table,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gqa_fused_pad_rows_and_holes():
    """Pad rows (t < 0) and unassigned mid-table holes: live rows match
    the reference; pad rows are garbage in BOTH backends and simply must
    not poison the live ones (finite output)."""
    rs = np.random.RandomState(7)
    B, Hkv, hd, bl, T = 4, 2, 16, 4, 3
    k, v, pos, t, table = _mk_paged(rs, B, Hkv, hd, bl, T, B * T + 2)
    t = t.at[1, 0].set(-1)                        # row 1 becomes a pad row
    table = table.at[2, T - 1].set(-1)            # row 2: trailing hole
    q = jnp.asarray(rs.randn(B, 1, Hkv * 2, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, table=table, backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, table=table, backend="pallas")
    live = [0, 2, 3]
    np.testing.assert_allclose(np.asarray(fused)[live],
                               np.asarray(ref)[live], rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(fused)).all()


def test_gqa_fused_contiguous_layout():
    """table=None (contiguous slot rows) runs fused as a B-block arena
    with an identity table."""
    rs = np.random.RandomState(11)
    B, L, Hkv, hd = 3, 12, 2, 16
    k = jnp.asarray(rs.randn(B, L, Hkv, hd), jnp.float32)
    v = jnp.asarray(rs.randn(B, L, Hkv, hd), jnp.float32)
    pos = np.full((B, L), EMPTY_POS, np.int32)
    for b, n in enumerate((11, 5, 1)):
        pos[b, :n] = np.arange(n)
    t = jnp.asarray([[11], [5], [1]], jnp.int32)
    q = jnp.asarray(rs.randn(B, 1, 4, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, jnp.asarray(pos), t, backend="xla")
    fused = ops.decode_gqa(q, k, v, jnp.asarray(pos), t, backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gqa_fused_bf16_cache_dtype_alignment():
    """bf16 caches (the serving default dtype off-CPU): the fused
    kernel computes QK/PV in the cache dtype like the reference, so the
    two backends agree to bf16 rounding — not just on the fp32
    parity-suite configs."""
    rs = np.random.RandomState(21)
    B, Hkv, hd, bl, T = 4, 2, 16, 4, 3
    k, v, pos, t, table = _mk_paged(rs, B, Hkv, hd, bl, T, B * T + 2)
    k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    q = jnp.asarray(rs.randn(B, 1, 4, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, table=table, backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, table=table, backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bl,T", [(4, 4), (16, 1)])
def test_mla_fused_matches_reference(bl, T):
    """Absorbed-MLA fused kernel == gather reference (latent + rope
    score halves, probability-weighted latent accumulation)."""
    rs = np.random.RandomState(bl + T)
    B, H, kvr, rope_d = 4, 4, 16, 8
    n_blocks = B * T + 2
    c, kr, pos, t, table = _mk_paged(rs, B, 1, kvr, bl, T, n_blocks)
    c, kr = c[:, 0], jnp.asarray(
        np.asarray(kr)[:, 0, :, :rope_d].copy())
    qa = jnp.asarray(rs.randn(B, 1, H, kvr), jnp.float32)
    qr = jnp.asarray(rs.randn(B, 1, H, rope_d), jnp.float32)
    ref = ops.decode_mla(qa, qr, c, kr, pos, t, scale=0.17, table=table,
                         backend="xla")
    fused = ops.decode_mla(qa, qr, c, kr, pos, t, scale=0.17, table=table,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -------------------------------------- chunk (C > 1) kernel numeric parity


def _mk_paged_chunk(rs, B, Hkv, hd, bl, T, n_blocks, C, fills,
                    poison=99.0):
    """Arena state as ``decode_gqa`` sees it MID-CHUNK: each row's first
    ``fills[b]`` positions written, PLUS the C chunk tokens at
    ``[fills[b], fills[b]+C)`` — the layer scatters the chunk's K/V
    before the attention read, so causality-within-chunk is carried by
    the per-query position mask alone. Everything unwritten is poisoned
    (stale-KV trap). Returns t: (B, C) per-query positions."""
    Leff = T * bl
    k = np.full((n_blocks, bl, Hkv, hd), poison, np.float32)
    v = np.full((n_blocks, bl, Hkv, hd), poison, np.float32)
    table = np.full((B, T), -1, np.int32)
    pos = np.full((B, Leff), EMPTY_POS, np.int32)
    free = list(range(n_blocks))
    t = np.zeros((B, C), np.int32)
    for b in range(B):
        n = fills[b]
        assert n + C <= Leff
        t[b] = np.arange(n, n + C)
        for j in range(T):                # blocks covering [0, n+C)
            if j * bl <= n + C - 1:
                table[b, j] = free.pop(rs.randint(len(free)))
        for p in range(n + C):
            blk, off = table[b, p // bl], p % bl
            k[blk, off] = rs.randn(Hkv, hd)
            v[blk, off] = rs.randn(Hkv, hd)
            pos[b, p] = p
    return (_heads_major(k), _heads_major(v), jnp.asarray(pos),
            jnp.asarray(t), jnp.asarray(table))


def _chunk_fills(bl, T, C):
    """Per-row chunk start positions covering the interesting layouts:
    a chunk CROSSING a block boundary (start bl-1), a block-aligned
    start, prompt-start (0) and a deep row near the end of the ring."""
    Leff = T * bl
    return [min(bl - 1, Leff - C), min(bl, Leff - C), 0, Leff - C]


@pytest.mark.parametrize("group,window,bl,T,C",
                         [(1, 0, 4, 4, 3),     # dense, boundary-crossing
                          (2, 0, 4, 4, 4),     # GQA, chunk == block_len
                          (4, 0, 16, 1, 5),    # contiguous-degenerate
                          (2, 7, 4, 4, 3),     # SWA ring window
                          (2, 5, 2, 8, 6)])    # chunk spans 3+ tiny blocks
def test_gqa_chunk_fused_matches_reference(group, window, bl, T, C):
    """The multi-token fused kernel == gather reference for C > 1 chunk
    prefill: per-query causal masking (query c attends [0, t_c]),
    boundary-crossing chunks, the chunk == block_len exact fit, GQA
    grouping and sliding windows, on a poisoned arena."""
    rs = np.random.RandomState(group * 100 + window * 10 + bl + C)
    B, Hkv, hd = 4, 2, 16
    H = Hkv * group
    k, v, pos, t, table = _mk_paged_chunk(rs, B, Hkv, hd, bl, T,
                                          B * T + 2, C,
                                          _chunk_fills(bl, T, C))
    q = jnp.asarray(rs.randn(B, C, H, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, window=window, table=table,
                         backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, window=window, table=table,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gqa_chunk_mixed_rows_and_pads():
    """The mixed-tick shape: chunk rows co-batched with a decode row
    (single token in column 0, the rest padded ``t < 0``) and a fully
    padded free slot. Live queries match the reference; pad queries are
    finite garbage (the l == 0 guard) and must not leak poison."""
    rs = np.random.RandomState(17)
    B, Hkv, hd, bl, T, C = 4, 2, 16, 4, 4, 3
    k, v, pos, t, table = _mk_paged_chunk(rs, B, Hkv, hd, bl, T,
                                          B * T + 2, C,
                                          _chunk_fills(bl, T, C))
    t = np.asarray(t).copy()
    t[1, 1:] = -1                 # row 1: a decode row padded to C
    t[2, :] = -1                  # row 2: free slot, all pad
    t = jnp.asarray(t)
    q = jnp.asarray(rs.randn(B, C, Hkv * 2, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, table=table, backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, table=table, backend="pallas")
    live = np.asarray(t) >= 0                     # (B, C) query validity
    np.testing.assert_allclose(np.asarray(fused)[live],
                               np.asarray(ref)[live], rtol=1e-5, atol=1e-5)
    assert np.isfinite(np.asarray(fused)).all()


def test_gqa_chunk_bf16_cache_dtype_alignment():
    """bf16 arena through the chunk kernel: both backends compute QK/PV
    in the cache dtype, so they agree to bf16 rounding."""
    rs = np.random.RandomState(23)
    B, Hkv, hd, bl, T, C = 4, 2, 16, 4, 4, 3
    k, v, pos, t, table = _mk_paged_chunk(rs, B, Hkv, hd, bl, T,
                                          B * T + 2, C,
                                          _chunk_fills(bl, T, C))
    k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    q = jnp.asarray(rs.randn(B, C, 4, hd), jnp.float32)
    ref = ops.decode_gqa(q, k, v, pos, t, table=table, backend="xla")
    fused = ops.decode_gqa(q, k, v, pos, t, table=table, backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("bl,T,C", [(4, 4, 3), (16, 1, 4), (4, 4, 4)])
def test_mla_chunk_fused_matches_reference(bl, T, C):
    """Absorbed-MLA chunk kernel == gather reference for C > 1,
    including the chunk == block_len exact fit."""
    rs = np.random.RandomState(bl + T + C)
    B, H, kvr, rope_d = 4, 4, 16, 8
    c, kr, pos, t, table = _mk_paged_chunk(rs, B, 1, kvr, bl, T,
                                           B * T + 2, C,
                                           _chunk_fills(bl, T, C))
    c, kr = c[:, 0], jnp.asarray(
        np.asarray(kr)[:, 0, :, :rope_d].copy())
    qa = jnp.asarray(rs.randn(B, C, H, kvr), jnp.float32)
    qr = jnp.asarray(rs.randn(B, C, H, rope_d), jnp.float32)
    ref = ops.decode_mla(qa, qr, c, kr, pos, t, scale=0.17, table=table,
                         backend="xla")
    fused = ops.decode_mla(qa, qr, c, kr, pos, t, scale=0.17, table=table,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------- no logical-view materialisation


@pytest.mark.parametrize("backend,expect_gather", [("xla", True),
                                                   ("pallas", False)])
@pytest.mark.parametrize("C", [1, 4])
def test_fused_path_has_no_logical_gather(backend, expect_gather, C):
    """The acceptance gate, for BOTH tick shapes: the fused step
    contains NO gather as large as the (B, T*block_len) logical KV view
    (the reference must — that is exactly the copy being eliminated).
    C == 1 is the lockstep decode-only tick; C == 4 is a mixed tick
    with a chunk row co-batched against a padded decode row. The jaxpr
    walk is the analyzer's (repro.analysis.gather_sizes — the same
    walker the no-materialization CI rule runs over the full runner
    programs)."""
    from repro.analysis import gather_sizes
    from repro.models.lm import attention as A
    cfg = get_config("qwen1.5-4b-smoke")
    p = A.make_attn_params(jax.random.key(0), cfg)
    B, bl, T, Nb = 2, 4, 4, 10
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    cache = A.init_attn_cache_paged(cfg, B, bl * T, Nb, bl,
                                    dtype=jnp.float32)
    x = jnp.zeros((B, C, cfg.d_model), jnp.float32)
    if C == 1:
        t = jnp.asarray([[3], [5]], jnp.int32)
    else:                    # mixed tick: chunk row + padded decode row
        t = jnp.asarray([[3, 4, 5, 6], [5, -1, -1, -1]], jnp.int32)
    table = jnp.zeros((B, T), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda *a: A.attn_decode_slots(*a, cfg, table=table,
                                       attn_backend=backend)
    )(p, x, cache, t)
    view_size = B * T * bl * Hkv * hd             # the logical view
    big = [s for s in gather_sizes(jaxpr) if s >= view_size]
    assert bool(big) == expect_gather, (backend, big)


# --------------------------------------------------- engine token parity


def _drain(arch, backend, spec, seed=0, **kw):
    cfg = get_config(arch)
    params = api.init_params(jax.random.key(0), cfg)
    rs = np.random.RandomState(seed)
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 32)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("block_len", 4)
    eng = ServingEngine(params, cfg, cache_dtype=jnp.float32,
                        attn_backend=backend, **kw)
    assert eng.runner.attn_backend == backend     # resolved + threaded
    for i, (pl, mn) in enumerate(spec):
        frames = (rs.randn(cfg.frontend_tokens, cfg.d_model)
                  .astype(np.float32) if cfg.family == "audio" else None)
        eng.submit(Request(
            rid=i, prompt=rs.randint(1, cfg.vocab_size, size=pl).tolist(),
            sampling=SamplingParams(max_new_tokens=mn), frames=frames))
    done = eng.run()
    return {i: done[i].out_tokens for i in done}, eng


def test_engine_backend_parity_dense_gqa():
    """qwen (GQA) through the paged pool: greedy tokens are identical
    between the fused and reference backends, across block crossings."""
    spec = [(6, 10), (10, 7), (3, 5)]
    ref, _ = _drain("qwen1.5-4b-smoke", "xla", spec)
    fused, eng = _drain("qwen1.5-4b-smoke", "pallas", spec)
    assert fused == ref
    assert eng.pool.attn_backend == "pallas"


def test_engine_backend_parity_recycle_and_preempt():
    """Tight arena: blocks recycle across requests and the youngest
    request is preempted and resumed — fused tokens still match the
    reference exactly (stale-KV masking and re-prefill both fused)."""
    spec = [(6, 8), (6, 8), (5, 4)]
    ref, re = _drain("qwen1.5-4b-smoke", "xla", spec, cache_len=16,
                     n_blocks=5)
    fused, fe = _drain("qwen1.5-4b-smoke", "pallas", spec, cache_len=16,
                       n_blocks=5)
    assert fused == ref
    assert fe.pool.alloc_count > 5                # blocks really recycled
    assert fe.metrics.preempts == re.metrics.preempts


@pytest.mark.parametrize("arch", ["deepseek-v3-671b-smoke",
                                  "hymba-1.5b-smoke",
                                  "whisper-tiny-smoke"])
def test_engine_backend_parity_families(arch):
    """MLA (absorbed latent decode), hybrid sliding-window ring, and
    audio enc-dec (fused self- AND cross-attention) — token parity
    through the full engine. hymba's SWA groups ring at min(window,
    cache_len), so this also covers ring wrap through the table."""
    spec = [(6, 8), (10, 5)]
    ref, _ = _drain(arch, "xla", spec, cache_len=48)
    fused, _ = _drain(arch, "pallas", spec, cache_len=48)
    assert fused == ref


@pytest.mark.parametrize("arch", ["qwen1.5-4b-smoke", "mamba2-130m-smoke",
                                  "deepseek-v3-671b-smoke",
                                  "whisper-tiny-smoke"])
def test_engine_cobatch_matches_split_tick(arch):
    """Unified mixed ticks are a SCHEDULING change only: the co-batched
    engine (default), the same engine under a tight per-tick prefill
    budget, and the legacy split-tick schedule (``co_batch=False``)
    produce token-identical outputs for every cache family — the
    pre-refactor-parity acceptance gate."""
    spec = [(6, 8), (10, 5), (3, 6)]
    split, _ = _drain(arch, "xla", spec, cache_len=48, co_batch=False)
    mixed, me = _drain(arch, "xla", spec, cache_len=48)
    assert mixed == split
    assert any(k[0] == "mixed" for k in me.runner.plans._warmed)
    budget, _ = _drain(arch, "xla", spec, cache_len=48,
                       max_prefill_tokens=4)
    assert budget == split


# ------------------------------------------------- runtime interpret pin


def test_interpret_resolved_at_call_time(monkeypatch):
    """The import-time INTERPRET pin is gone: interpret defaults are a
    function of the CURRENT backend/env, and REPRO_PALLAS_INTERPRET
    force-overrides for tests."""
    import repro.kernels.flash_attention as fa
    import repro.kernels.qmatmul as qm
    import repro.kernels.ssd_scan as ss
    import repro.kernels.qconv1d as qc
    for mod in (fa, qm, ss, qc):
        assert not hasattr(mod, "INTERPRET"), mod.__name__
    assert ops.interpret_default() is True        # CPU container
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.interpret_default() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert ops.interpret_default() is True
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET")
    assert ops.resolve_attn_backend(None) == "xla"      # auto on CPU
    assert ops.resolve_attn_backend("pallas") == "pallas"
    with pytest.raises(ValueError):
        ops.resolve_attn_backend("triton")
    # the public kernel wrappers must resolve interpret OUTSIDE the jit
    # boundary (plain functions dispatching to _*_jit) — resolving
    # inside a jitted body freezes the first answer under the `None`
    # static-arg cache key, resurrecting the import-pin bug at trace
    # time
    jitted = type(jax.jit(lambda: 0))
    for fn in (ops.qmatmul, ops.flash_attention, ops.qconv1d_block,
               ops.ssd_chunk_scan):
        assert not isinstance(fn, jitted), fn.__name__
    for fn in (ops._qmatmul_jit, ops._flash_attention_jit,
               ops._qconv1d_block_jit, ops._ssd_chunk_scan_jit):
        assert isinstance(fn, jitted)


def test_interpret_refused_on_tpu(monkeypatch):
    """On a TPU the kernels always compile: REPRO_PALLAS_INTERPRET
    asking for the interpreter is an error, never a silent slow path."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert ops.interpret_default() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert ops.interpret_default() is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(RuntimeError, match="REPRO_PALLAS_INTERPRET"):
        ops.interpret_default()
