"""Decode-attention backends: fused paged-attention Pallas kernels + the
XLA gather reference — one home for every paged-KV read path.

The serving engine pages K/V into shared block arenas (``repro.serving.
cache``): per layer group the cache holds ``(n_blocks, block_len, ...)``
leaves and a host block table ``(n_slots, T)`` maps each slot's logical
block to an arena block. Decode attention then has two ways to read:

``xla`` (reference)
    Gather each row's T blocks into a ``(B, T*block_len)`` logical view
    and run masked-dense attention over it — today's path, kept
    bit-identical as the parity oracle and the GSPMD/multi-chip default.
    The gather MATERIALISES the logical view: ``B * T*block_len``
    positions of K plus V copied per layer per decode tick, even when a
    slot has only a handful of blocks assigned.

``pallas`` (fused)
    The kernels below compute attention DIRECTLY from the arena. The
    block table rides in as a scalar-prefetch operand, so each grid
    step's ``BlockSpec`` index_map resolves ``table[b, j]`` and DMAs
    exactly one arena block into VMEM — unassigned (``-1``) blocks are
    skipped via ``pl.when``, no ``(B, T*block_len)`` copy ever exists.
    Online softmax runs over the blocks with validity (``pos``), ring-
    window and stale-KV masking fused into the score tile. Bytes moved
    per tick drop from ``O(B * T * block_len)`` to ``O(assigned
    blocks * block_len)``.

Both backends share the same masking contract (a position participates
iff ``pos >= 0 and pos <= t`` and, for ring groups, ``pos > t -
window``), so a recycled arena block is invisible to its new owner until
written — exactly the stale-KV story of the XLA path.

Backend selection is dispatched by ``repro.kernels.ops.decode_gqa`` /
``decode_mla`` (layout glue + fallback rules); the model layers
(``models/lm/attention.py`` / ``mla.py``) call those and never touch a
gather themselves. Two fused variants cover both serving shapes: the
lockstep decode tick (``C == 1`` queries — ``gqa_paged_p`` /
``mla_paged_p``) and multi-token chunk prefill (``C > 1`` —
``gqa_paged_chunk_p`` / ``mla_paged_chunk_p``, which fold the chunk
into the query-row axis and carry a PER-QUERY position vector so each
chunk token applies its own causal/ring mask against the same arena
blocks; causal-within-chunk falls out of the position mask because the
chunk's K/V is scattered into the arena before the kernel runs).

Rows with no valid position (pad slots, ``t < 0``) produce garbage in
both backends — the scheduler never reads them.

Layouts follow the TPU rule that a block's last two dims be (8, 128)
multiples or the array's full dims. GQA arenas are heads-major,
``(n_blocks, Hkv, block_len, hd)``, so one head's block is a whole
``(block_len, hd)`` tile; their int8 scales are ``(n_blocks, Hkv,
block_len)`` and DMA whole per block — viewing them as one row per
(block, head) would relayout the whole scale arena on every call when
Hkv is not a multiple of 8 (20 for qwen1.5-4b). ``pos`` and the MLA
latent scales ride as ``(rows, 1, block_len)`` rows and per-query
positions as ``(B, rows, 1)`` columns. A scale row becomes the
``(block_len, 1)`` column the dequant needs by an exact masked-sum
transpose in-register (:func:`_col`, :func:`_head_col`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# Sentinel for "no token cached in this slot" — also what pads per-row
# position vectors for inactive serving slots (any negative works: the
# validity mask is pos >= 0).
EMPTY_POS = -(10 ** 9)


def _interpret(interpret):
    if interpret is None:
        from repro.kernels.ops import interpret_default  # lazy: no cycle
        return interpret_default()
    return interpret


# ---------------------------------------------------------------------------
# Shared index math (paged scatter/gather)


def paged_indices(table: jax.Array, t: jax.Array, n_blocks: int,
                  block_len: int):
    """Block-indirect scatter/gather indices shared by the paged
    attention and MLA decode paths.

    table: (B, T) int32 arena-block table (-1 = unassigned); t: (B, C)
    positions (< 0 = pad). Returns ``(wblk, off, lw, gidx, Leff)``:
    arena block + in-block offset for the KV scatter ((B, C), pushed out
    of bounds — dropped — for pad tokens and unassigned blocks), the pos
    scatter index ``lw`` (kept in LOCKSTEP with the KV write: if the
    mapped block is unassigned the pos write drops too, or a valid pos
    entry would admit another block's garbage through the clamped
    gather), the clamped (B, T) arena gather indices, and the padded
    ring length ``Leff = T * block_len``.
    """
    B, T = table.shape
    Leff = T * block_len
    bidx = jnp.arange(B)[:, None]
    l = jnp.where(t >= 0, t % Leff, Leff)         # Leff is OOB -> drop
    blk = table[bidx, jnp.minimum(l // block_len, T - 1)]
    wblk = jnp.where((t >= 0) & (blk >= 0), blk, n_blocks)
    lw = jnp.where(wblk < n_blocks, l, Leff)
    return wblk, l % block_len, lw, jnp.maximum(table, 0), Leff


def valid_mask(pos: jax.Array, t: jax.Array, window: int = 0) -> jax.Array:
    """(B, C, L) participation mask: cached position ``pos`` is visible
    to query position ``t`` iff it is written (>= 0), causal (<= t) and,
    for ring-buffer groups, inside the sliding window."""
    valid = (pos >= 0)[:, None, :] & (pos[:, None, :] <= t[:, :, None])
    if window > 0:
        valid &= pos[:, None, :] > (t[:, :, None] - window)
    return valid


# ---------------------------------------------------------------------------
# int8 arena quantization (shared by the cache write path, the fused
# kernels, and the XLA gather reference — ONE rounding rule, so fused-vs-
# reference parity holds at every cache dtype)


QSCALE_MIN = 1e-8      # scale floor: an all-zero vector stays exactly 0


def quantize_kv(x: jax.Array, axis: int = -1):
    """Symmetric per-vector int8 quantization over the feature ``axis``
    (per token per KV head for attention, per token for MLA latents).
    Returns ``(q int8, scale fp32)`` with ``axis`` dropped from the
    scale shape. Written at the same scatter indices as the values, so
    scales can never go stale independently of their bytes."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis)
    scale = jnp.maximum(amax / 127.0, QSCALE_MIN)
    q = jnp.clip(jnp.round(xf / jnp.expand_dims(scale, axis)), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16,
                  axis: int = -1) -> jax.Array:
    """Inverse of :func:`quantize_kv` — fp32 multiply, then cast to the
    compute dtype (bf16, matching the 1-byte-cache convention). Both
    backends MUST dequantize through this exact expression."""
    return _dequant(q, jnp.expand_dims(scale, axis), dtype)


def _dequant(q: jax.Array, scale: jax.Array, dtype=jnp.bfloat16):
    """The one dequant expression, ``scale`` already broadcastable."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def _head_col(sc: jax.Array, h) -> jax.Array:
    """Row ``h`` of a ``(rows, n)`` scale tile as an ``(n, 1)`` column,
    selected by an exact masked sum."""
    row = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0) == h, sc, 0.0),
        axis=0, keepdims=True)                              # (1, n)
    return _col(row)


def _col(row: jax.Array) -> jax.Array:
    """A ``(1, n)`` scale row as an ``(n, 1)`` column.

    Mosaic has no cheap lane-to-sublane reshape, so transpose by a
    masked sum: every sum adds one value to zeros, which is exact."""
    n = row.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _pos_rows(pos: jax.Array, T: int) -> jax.Array:
    """(B, T*block_len) positions -> (B*T, 1, block_len): one row per
    (slot, logical block), a block the TPU lowering accepts."""
    B, L = pos.shape
    return pos.reshape(B * T, 1, L // T)


# ---------------------------------------------------------------------------
# XLA reference backend (the pre-fusion gather path, verbatim)


def gqa_reference(q: jax.Array, k_read: jax.Array, v_read: jax.Array,
                  pos: jax.Array, t: jax.Array, *, window: int = 0
                  ) -> jax.Array:
    """Masked-dense GQA decode over a logical (B, L, Hkv, hd) KV view.

    q: (B, C, H, hd); pos: (B, L); t: (B, C). Returns (B, C, H*hd).
    f8 caches compute in bf16 (converts fuse on TPU); otherwise the
    storage dtype, fp32 accumulation — one pass over the view per step.
    """
    B, C, H, hd = q.shape
    Hkv = k_read.shape[2]
    group = H // Hkv
    cdt = jnp.bfloat16 if jnp.dtype(k_read.dtype).itemsize == 1 \
        else k_read.dtype
    qg = q.reshape(B, C, Hkv, group, hd).astype(cdt)
    s = jnp.einsum("bckgd,blkd->bckgl", qg, k_read.astype(cdt),
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    valid = valid_mask(pos, t, window)
    s = jnp.where(valid[:, :, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bckgl,blkd->bckgd", prob.astype(cdt),
                   v_read.astype(cdt),
                   preferred_element_type=jnp.float32).astype(q.dtype)
    return o.reshape(B, C, H * hd)


def mla_reference(q_abs: jax.Array, q_rope: jax.Array, c_read: jax.Array,
                  kr_read: jax.Array, pos: jax.Array, t: jax.Array, *,
                  scale: float, shard_s=None) -> jax.Array:
    """Absorbed-form MLA decode over a logical latent view.

    q_abs: (B, C, H, kvr); q_rope: (B, C, H, rope_d); c_read: (B, L,
    kvr); kr_read: (B, L, rope_d); pos: (B, L); t: (B, C). Returns
    o_lat (B, C, H, kvr), fp32 — the caller applies the absorbed value
    projection. ``shard_s`` is an optional constraint hook on the score
    tensor (the flash-decoding 'model'-axis annotation)."""
    s = jnp.einsum("bchr,blr->bchl", q_abs, c_read,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bchp,blp->bchl", q_rope.astype(kr_read.dtype),
                       kr_read, preferred_element_type=jnp.float32)
    if shard_s is not None:
        s = shard_s(s)
    s = s * scale
    valid = valid_mask(pos, t)
    s = jnp.where(valid[:, :, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bchl,blr->bchr", prob.astype(c_read.dtype), c_read,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Fused Pallas backend — online softmax shared by every kernel below


def _online_softmax_step(s, v, m_ref, l_ref, acc_ref):
    """Fold one block's masked scores ``s`` (rows, bl) and values ``v``
    (bl, d) into the running max / denominator / accumulator."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _stats_scratch(rows: int, d: int):
    return [pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, d), jnp.float32)]


# ---------------------------------------------------------------------------
# Fused Pallas backend — GQA


def _gqa_kernel(tbl_ref, *refs, scale: float, window: int, nT: int,
                quantized: bool, chunk: bool):
    """One (row b, KV head h, logical block j) grid step. Decode ticks
    (``chunk`` False) mask against the scalar-prefetched ``t[b]``; chunk
    ticks carry a per-query position column ``tq`` (rows, 1)."""
    if chunk:
        q_ref, k_ref, v_ref, *rest = refs
    else:
        t_ref, q_ref, k_ref, v_ref, *rest = refs
    ks_ref = vs_ref = None
    if quantized:      # int8 arena rides with per-token-per-head scales
        ks_ref, vs_ref, *rest = rest
    if chunk:
        tq_ref, *rest = rest
    pos_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    # unassigned (-1) logical blocks contribute nothing: skip the whole
    # tile (their pos words are EMPTY_POS anyway — writes drop in
    # lockstep — but skipping also skips the DMA'd garbage compute)
    @pl.when(tbl_ref[b, j] >= 0)
    def _body():
        # mirror the reference's compute dtypes (gqa_reference): QK/PV
        # inputs in the cache dtype (bf16 for 1-byte storage — int8
        # dequantizes in-register through the same rule the reference
        # uses), fp32 scores/stats/accumulation
        cdt = jnp.bfloat16 if jnp.dtype(k_ref.dtype).itemsize == 1 \
            else k_ref.dtype
        q = q_ref[0, 0].astype(cdt)                    # (rows, hd)
        if quantized:
            k = _dequant(k_ref[0, 0], _head_col(ks_ref[0], h))  # (bl, hd)
            v = _dequant(v_ref[0, 0], _head_col(vs_ref[0], h))
        else:
            k = k_ref[0, 0].astype(cdt)                # (bl, hd)
            v = v_ref[0, 0].astype(cdt)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos = pos_ref[0]                               # (1, bl) int32
        tq = tq_ref[0] if chunk else t_ref[b]          # (rows, 1) | scalar
        valid = (pos >= 0) & (pos <= tq)
        if window > 0:
            valid &= pos > tq - window
        _online_softmax_step(jnp.where(valid, s, NEG_INF), v,
                             m_ref, l_ref, acc_ref)

    @pl.when(j == nT - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _gqa_call(qh, k, v, pos, table, *, window, k_scale, v_scale,
              interpret, t=None, tq=None):
    """Shared pallas_call of the GQA kernels. qh: (B, Hkv, rows, hd);
    k/v: (n_blocks, Hkv, block_len, hd); pos: (B, T*block_len); exactly
    one of ``t`` (B,) — decode — or ``tq`` (B, rows) — chunk — given."""
    B, Hkv, rows, hd = qh.shape
    bl = k.shape[2]
    T = table.shape[1]
    quantized = k_scale is not None
    chunk = tq is not None
    kern = functools.partial(_gqa_kernel, scale=hd ** -0.5, window=window,
                             nT=T, quantized=quantized, chunk=chunk)

    def blk(b, h, j, tbl, *_):
        return jnp.maximum(tbl[b, j], 0)

    in_specs = [
        pl.BlockSpec((1, 1, rows, hd), lambda b, h, j, *_: (b, h, 0, 0)),
        *[pl.BlockSpec((1, 1, bl, hd),
                       lambda b, h, j, *r: (blk(b, h, j, *r), h, 0, 0))] * 2,
        *[pl.BlockSpec((1, Hkv, bl),
                       lambda b, h, j, *r: (blk(b, h, j, *r), 0, 0))]
        * (2 if quantized else 0),
        *([pl.BlockSpec((1, rows, 1), lambda b, h, j, *_: (b, 0, 0))]
          if chunk else []),
        pl.BlockSpec((1, 1, bl), lambda b, h, j, *_: (b * T + j, 0, 0)),
    ]
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if chunk else 2,      # table (, t)
        grid=(B, Hkv, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda b, h, j, *_: (b, h, 0, 0)),
        scratch_shapes=_stats_scratch(rows, hd),
    )
    scalars = (table.astype(jnp.int32),) if chunk \
        else (table.astype(jnp.int32), t.astype(jnp.int32))
    args = (qh, k, v) + ((k_scale, v_scale) if quantized else ()) \
        + ((tq.astype(jnp.int32)[..., None],) if chunk else ()) \
        + (_pos_rows(pos, T),)
    return pl.pallas_call(
        kern, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows, hd), qh.dtype),
        interpret=_interpret(interpret),
    )(*scalars, *args)


def gqa_paged_p(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
                t: jax.Array, table: jax.Array, *, window: int = 0,
                k_scale: jax.Array | None = None,
                v_scale: jax.Array | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Fused paged GQA decode. q: (B, Hkv, group, hd); k/v: arenas
    (n_blocks, Hkv, block_len, hd); pos: (B, T*block_len); t: (B,);
    table: (B, T). Returns (B, Hkv, group, hd) in q's dtype.

    Grid (B, Hkv, T), block axis innermost: the table is a scalar-
    prefetch operand, so each step's index_map DMAs head h of arena
    block ``table[b, j]`` straight into VMEM — the logical
    (B, T*block_len) view is never materialised. Rows with no valid
    position produce garbage (the scheduler ignores them).

    ``k_scale``/``v_scale`` (int8 arenas only): fp32 scale arenas
    (n_blocks, Hkv, block_len), DMA'd per grid step alongside their
    value block and dequantized in-register."""
    return _gqa_call(q, k, v, pos, table, window=window, k_scale=k_scale,
                     v_scale=v_scale, interpret=interpret, t=t)


# ---------------------------------------------------------------------------
# Fused Pallas backend — MLA (absorbed latent form)


def _mla_kernel(tbl_ref, *refs, scale: float, nT: int, quantized: bool,
                chunk: bool):
    if chunk:
        qa_ref, qr_ref, c_ref, kr_ref, *rest = refs
    else:
        t_ref, qa_ref, qr_ref, c_ref, kr_ref, *rest = refs
    cs_ref = krs_ref = None
    if quantized:      # int8 latent arena: per-token fp32 scale rows
        cs_ref, krs_ref, *rest = rest
    if chunk:
        tq_ref, *rest = rest
    pos_ref, o_ref, m_ref, l_ref, acc_ref = rest
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    @pl.when(tbl_ref[b, j] >= 0)
    def _body():
        # compute dtypes mirror mla_reference: latent/rope dots take the
        # cache dtype (bf16 once an int8 block is dequantized) with fp32
        # accumulation; softmax stats fp32
        if quantized:
            c = _dequant(c_ref[0], _col(cs_ref[0]))      # (bl, kvr)
            kr = _dequant(kr_ref[0], _col(krs_ref[0]))
        else:
            c = c_ref[0]                               # (bl, kvr)
            kr = kr_ref[0]                             # (bl, rope_d)
        qa = qa_ref[0].astype(c.dtype)                 # (rows, kvr)
        qr = qr_ref[0].astype(kr.dtype)                # (rows, rope_d)
        s = jax.lax.dot_general(qa, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + jax.lax.dot_general(qr, kr, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
        s = s * scale
        pos = pos_ref[0]                               # (1, bl)
        tq = tq_ref[0] if chunk else t_ref[b]
        valid = (pos >= 0) & (pos <= tq)
        _online_softmax_step(jnp.where(valid, s, NEG_INF), c,
                             m_ref, l_ref, acc_ref)

    @pl.when(j == nT - 1)
    def _done():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def _mla_call(qa, qr, c, kr, pos, table, *, scale, c_scale, kr_scale,
              interpret, t=None, tq=None):
    """Shared pallas_call of the MLA kernels. qa: (B, rows, kvr); qr:
    (B, rows, rope_d); c/kr: (n_blocks, block_len, kvr|rope_d)."""
    B, rows, kvr = qa.shape
    rope_d = qr.shape[-1]
    n_blocks, bl = c.shape[:2]
    T = table.shape[1]
    quantized = c_scale is not None
    chunk = tq is not None
    kern = functools.partial(_mla_kernel, scale=scale, nT=T,
                             quantized=quantized, chunk=chunk)

    def blk(b, j, tbl, *_):
        return jnp.maximum(tbl[b, j], 0)

    in_specs = [
        pl.BlockSpec((1, rows, kvr), lambda b, j, *_: (b, 0, 0)),
        pl.BlockSpec((1, rows, rope_d), lambda b, j, *_: (b, 0, 0)),
        pl.BlockSpec((1, bl, kvr), lambda b, j, *r: (blk(b, j, *r), 0, 0)),
        pl.BlockSpec((1, bl, rope_d),
                     lambda b, j, *r: (blk(b, j, *r), 0, 0)),
        *[pl.BlockSpec((1, 1, bl), lambda b, j, *r: (blk(b, j, *r), 0, 0))]
        * (2 if quantized else 0),
        *([pl.BlockSpec((1, rows, 1), lambda b, j, *_: (b, 0, 0))]
          if chunk else []),
        pl.BlockSpec((1, 1, bl), lambda b, j, *_: (b * T + j, 0, 0)),
    ]
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 if chunk else 2,
        grid=(B, T),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, rows, kvr), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=_stats_scratch(rows, kvr),
    )
    scalars = (table.astype(jnp.int32),) if chunk \
        else (table.astype(jnp.int32), t.astype(jnp.int32))
    scales = ((c_scale.reshape(n_blocks, 1, bl),
               kr_scale.reshape(n_blocks, 1, bl)) if quantized else ())
    args = (qa, qr, c, kr) + scales \
        + ((tq.astype(jnp.int32)[..., None],) if chunk else ()) \
        + (_pos_rows(pos, T),)
    return pl.pallas_call(
        kern, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, kvr), jnp.float32),
        interpret=_interpret(interpret),
    )(*scalars, *args)


def mla_paged_p(q_abs: jax.Array, q_rope: jax.Array, c: jax.Array,
                kr: jax.Array, pos: jax.Array, t: jax.Array,
                table: jax.Array, *, scale: float,
                c_scale: jax.Array | None = None,
                kr_scale: jax.Array | None = None,
                interpret: bool | None = None) -> jax.Array:
    """Fused paged absorbed-MLA decode. q_abs: (B, H, kvr); q_rope:
    (B, H, rope_d); c/kr: latent arenas (n_blocks, block_len, kvr|
    rope_d); pos: (B, T*block_len); t: (B,); table: (B, T). Returns
    o_lat (B, H, kvr) fp32 — probability-weighted latent rows; the
    caller applies the absorbed value projection. ``c_scale``/
    ``kr_scale`` (int8 arenas only): per-token fp32 scale arenas
    (n_blocks, block_len) riding the same index_map as their blocks."""
    return _mla_call(q_abs, q_rope, c, kr, pos, table, scale=scale,
                     c_scale=c_scale, kr_scale=kr_scale,
                     interpret=interpret, t=t)


# ---------------------------------------------------------------------------
# Fused Pallas backend — multi-token chunk variants (C > 1)
#
# Chunk prefill runs C query tokens per slot per tick. The C == 1
# kernels key their mask off a scalar per-row position ``t``; here every
# query token has its OWN position, so the chunk folds into the query-
# row axis (C*group rows for GQA, C*H for MLA) and a per-query position
# column ``tq`` rides in as a VMEM operand. The mask
# ``(pos >= 0) & (pos <= tq)`` then gives each chunk token its own
# causal frontier — causal-within-chunk for free, since the chunk's K/V
# is already scattered into the arena when the kernel reads it. Pad
# tokens (t < 0) mask every position and emit garbage rows the
# scheduler never reads (their l stays 0; the output is acc/max(l,eps)).


def gqa_paged_chunk_p(q: jax.Array, k: jax.Array, v: jax.Array,
                      pos: jax.Array, t: jax.Array, table: jax.Array, *,
                      window: int = 0,
                      k_scale: jax.Array | None = None,
                      v_scale: jax.Array | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Fused paged GQA chunk prefill (C > 1 query tokens per row).

    q: (B, C, H, hd); k/v: arenas (n_blocks, Hkv, block_len, hd); pos:
    (B, T*block_len); t: (B, C) per-query positions (< 0 = pad); table:
    (B, T). Returns (B, C, H*hd) in q's dtype.

    Same grid/DMA story as :func:`gqa_paged_p` — the chunk folds into
    the query-row axis (query token c, group member g -> row c*group+g)
    and ``t`` expands to a per-row position column, so each chunk token
    masks against its own causal frontier inside one online-softmax
    pass over the row's arena blocks. ``k_scale``/``v_scale``: int8
    scale arenas as in :func:`gqa_paged_p`."""
    B, C, H, hd = q.shape
    Hkv = k.shape[1]
    group = H // Hkv
    CG = C * group
    qf = (q.reshape(B, C, Hkv, group, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, Hkv, CG, hd))
    tq = jnp.repeat(t, group, axis=1)                        # (B, CG)
    o = _gqa_call(qf, k, v, pos, table, window=window, k_scale=k_scale,
                  v_scale=v_scale, interpret=interpret, tq=tq)
    return (o.reshape(B, Hkv, C, group, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H * hd))


def mla_paged_chunk_p(q_abs: jax.Array, q_rope: jax.Array, c: jax.Array,
                      kr: jax.Array, pos: jax.Array, t: jax.Array,
                      table: jax.Array, *, scale: float,
                      c_scale: jax.Array | None = None,
                      kr_scale: jax.Array | None = None,
                      interpret: bool | None = None) -> jax.Array:
    """Fused paged absorbed-MLA chunk prefill (C > 1).

    q_abs: (B, C, H, kvr); q_rope: (B, C, H, rope_d); c/kr: latent
    arenas (n_blocks, block_len, kvr|rope_d); pos: (B, T*block_len);
    t: (B, C) per-query positions; table: (B, T). Returns o_lat
    (B, C, H, kvr) fp32 — chunk folded into the query-row axis (row
    c*H + h), per-query causal mask, same arena DMA as
    :func:`mla_paged_p`. ``c_scale``/``kr_scale``: int8 scale arenas
    (n_blocks, block_len)."""
    B, C, H, kvr = q_abs.shape
    CH = C * H
    tq = jnp.repeat(t, H, axis=1)                            # (B, CH)
    o = _mla_call(q_abs.reshape(B, CH, kvr),
                  q_rope.reshape(B, CH, q_rope.shape[-1]), c, kr, pos,
                  table, scale=scale, c_scale=c_scale, kr_scale=kr_scale,
                  interpret=interpret, tq=tq)
    return o.reshape(B, C, H, kvr)
