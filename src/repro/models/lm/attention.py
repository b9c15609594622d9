"""GQA attention: blockwise (flash-pattern) prefill + cache-sharded decode.

Memory discipline
-----------------
- Prefill/train never materialises (S x S) scores: an outer ``lax.scan``
  over query chunks and an inner online-softmax scan over KV chunks keep
  the working set at (B, H, Qc, Kc). Sliding-window layers use a
  dynamic-slice KV window instead of the inner scan (O(S*W) flops).
- Decode shards the KV cache over ('data' on batch, 'model' on sequence) —
  flash-decoding across chips: GSPMD turns the softmax & PV reductions into
  small all-reduces over the 'model' axis. This is what lets a 405B-scale
  32k-cache decode fit 16 GB/chip without padding tricks.

The Pallas twin of the prefill path is ``repro.kernels.flash_attention``
(TPU hot-spot; numerically validated against this module in tests).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
# decode-attention backend interface: the paged/contiguous KV read paths
# (XLA gather reference + fused Pallas kernel) live in repro.kernels —
# EMPTY_POS/NEG_INF/paged_indices are re-exported here for callers that
# predate the refactor (repro.models.lm.mla, serving/cache, tests).
from repro.kernels.ops import decode_gqa
from repro.kernels.paged_attention import (EMPTY_POS, NEG_INF,  # noqa: F401
                                           paged_indices, quantize_kv)
from repro.models.lm.common import (BATCH_AXES, Params, constrain, dense,
                                    make_dense_params)
from repro.models.lm.rope import apply_rope


def _score_dtype():
    """Blockwise-attention score/prob dtype. fp32 by default (safe);
    REPRO_ATTN_BF16=1 switches the chunk tensors to bf16 — halves the
    dominant prefill/train memory-roofline term (hillclimb H3; TPU flash
    kernels run bf16 scores natively, m/l stats stay fp32 either way)."""
    import os
    return jnp.bfloat16 if os.environ.get("REPRO_ATTN_BF16") == "1" \
        else jnp.float32


def _chunk(n: int, pref: int) -> int:
    """Largest divisor of n that is <= pref (keeps shapes static & even)."""
    if n <= pref:
        return n
    c = pref
    while n % c:
        c -= 1
    return c


# ---------------------------------------------------------------------------
# Blockwise attention core (shared by prefill & train)


def blockwise_attn(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   q_offset: int = 0, q_chunk: int = 0,
                   kv_chunk: int = 1024) -> jax.Array:
    """q: (B, Sq, H, hd); k, v: (B, Sk, Hkv, hd). Returns (B, Sq, H, hd).

    ``window > 0`` = sliding-window attention (each query sees the previous
    ``window`` positions inclusive of itself). Default chunk sizes come
    from REPRO_ATTN_QCHUNK (512) — larger q chunks amortise the SWA
    window halo reload (hillclimb qc1024).
    """
    import os
    if not q_chunk:
        q_chunk = int(os.environ.get("REPRO_ATTN_QCHUNK", "512"))
    B, Sq, H, hd = q.shape
    _, Sk, Hkv, _ = k.shape
    hd_v = v.shape[-1]          # MLA: value dim may differ from qk dim
    group = H // Hkv
    scale = hd ** -0.5
    Qc = _chunk(Sq, q_chunk)
    Tq = Sq // Qc

    qs = q.reshape(B, Tq, Qc, H, hd).transpose(1, 0, 3, 2, 4)  # (Tq,B,H,Qc,hd)

    if window > 0:
        # -- SWA: static-size KV window per query chunk ------------------
        W = min(window, Sk)
        Wpad = W + Qc if Sk >= W + Qc else Sk

        def q_step(_, iq_q):
            i, qc = iq_q
            qstart = q_offset + i * Qc
            start = jnp.clip(qstart + Qc - Wpad, 0, Sk - Wpad)
            kw = jax.lax.dynamic_slice_in_dim(k, start, Wpad, axis=1)
            vw = jax.lax.dynamic_slice_in_dim(v, start, Wpad, axis=1)
            kw = jnp.repeat(kw, group, axis=2)  # (B,Wpad,H,hd)
            vw = jnp.repeat(vw, group, axis=2)
            qpos = qstart + jnp.arange(Qc)
            kpos = start + jnp.arange(Wpad)
            mask = (kpos[None, :] <= qpos[:, None]) & \
                   (kpos[None, :] > qpos[:, None] - W)
            sdt = _score_dtype()
            s = jnp.einsum("bhqd,bkhd->bhqk", qc.astype(sdt),
                           kw.astype(sdt),
                           preferred_element_type=sdt) * \
                jnp.asarray(scale, sdt)
            s = jnp.where(mask[None, None], s, jnp.asarray(NEG_INF, sdt))
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(sdt),
                           vw.astype(sdt),
                           preferred_element_type=jnp.float32)
            return None, o.astype(q.dtype)

        # remat the chunk step: backward recomputes the (Qc x W) probs
        # instead of saving them — flash-attention memory semantics.
        _, outs = jax.lax.scan(jax.checkpoint(q_step), None,
                               (jnp.arange(Tq), qs))
        return outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd_v)

    # -- full (causal) attention: online softmax over KV chunks ----------
    Kc = _chunk(Sk, kv_chunk)
    Tk = Sk // Kc
    ks = k.reshape(B, Tk, Kc, Hkv, hd).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, Tk, Kc, Hkv, hd_v).transpose(1, 0, 3, 2, 4)

    if causal and os.environ.get("REPRO_ATTN_TRI") == "1" and Sq == Sk:
        # triangular schedule: iterate only the ~T^2/2 (q,kv) block pairs
        # below the causal diagonal (static index lists) instead of
        # masking the full T^2 grid — halves attention flops in the HLO,
        # matching the Pallas kernel's block skipping.
        return _blockwise_tri(q, ks, vs, Qc=Qc, Kc=Kc, group=group,
                              scale=scale, q_offset=q_offset,
                              hd_v=hd_v)

    sdt = _score_dtype()

    def q_step(_, iq_q):
        i, qc = iq_q                                     # qc: (B,H,Qc,hd)
        qpos = q_offset + i * Qc + jnp.arange(Qc)
        qf = qc.astype(sdt)

        def kv_step(carry, jk):
            m, l, acc = carry
            j, kc, vc = jk                               # (B,Hkv,Kc,hd)
            kc = jnp.repeat(kc, group, axis=1)
            vc = jnp.repeat(vc, group, axis=1)
            # scores/probs in sdt (bf16 under REPRO_ATTN_BF16 — the TPU
            # flash-kernel convention); m/l/acc statistics stay fp32.
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(sdt),
                           preferred_element_type=sdt) * \
                jnp.asarray(scale, sdt)
            if causal:
                kpos = j * Kc + jnp.arange(Kc)
                s = jnp.where(kpos[None, None, None, :]
                              <= qpos[None, None, :, None], s,
                              jnp.asarray(NEG_INF, sdt))
            m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
            p = jnp.exp(s - m_new[..., None].astype(sdt))
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p.astype(jnp.float32), axis=-1)
            acc_new = acc * corr[..., None] + \
                jnp.einsum("bhqk,bhkd->bhqd", p, vc.astype(sdt),
                           preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((B, H, Qc), NEG_INF, jnp.float32),
                jnp.zeros((B, H, Qc), jnp.float32),
                jnp.zeros((B, H, Qc, hd_v), jnp.float32))
        # remat the KV step: flash-attention backward (recompute s/p per
        # chunk from q,k,v) instead of materialising (Qc x Kc) per step.
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), init,
                                      (jnp.arange(Tk), ks, vs))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(Tq), qs))
    return outs.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, hd_v)


def _blockwise_tri(q, ks, vs, *, Qc, Kc, group, scale, q_offset, hd_v):
    """Causal blockwise attention over the static lower-triangular list of
    (q-chunk, kv-chunk) pairs. Carries per-q-chunk (m, l, acc) state and
    updates one slot per step (slice-sized traffic; the analyzer's
    DUS-awareness keeps the accounting honest)."""
    import numpy as np
    Tk, B, Hkv, _, hd = ks.shape
    H = Hkv * group
    Tq = q.shape[1] // Qc
    qs = q.reshape(B, Tq, Qc, H, q.shape[-1]).transpose(1, 0, 3, 2, 4)
    sdt = _score_dtype()

    pairs = [(i, j) for i in range(Tq) for j in range(Tk)
             if j * Kc <= q_offset + i * Qc + Qc - 1]
    pi = jnp.asarray(np.array([p[0] for p in pairs], np.int32))
    pj = jnp.asarray(np.array([p[1] for p in pairs], np.int32))

    def step(carry, ij):
        m, l, acc = carry                         # (Tq,B,H,Qc[,hd_v])
        i, j = ij
        qc = jax.lax.dynamic_index_in_dim(qs, i, 0, keepdims=False)
        kc = jax.lax.dynamic_index_in_dim(ks, j, 0, keepdims=False)
        vc = jax.lax.dynamic_index_in_dim(vs, j, 0, keepdims=False)
        kc = jnp.repeat(kc, group, axis=1)
        vc = jnp.repeat(vc, group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qc.astype(sdt), kc.astype(sdt),
                       preferred_element_type=sdt) * jnp.asarray(scale, sdt)
        qpos = q_offset + i * Qc + jnp.arange(Qc)
        kpos = j * Kc + jnp.arange(Kc)
        s = jnp.where(kpos[None, None, None, :]
                      <= qpos[None, None, :, None], s,
                      jnp.asarray(NEG_INF, sdt))
        m_i = jax.lax.dynamic_index_in_dim(m, i, 0, keepdims=False)
        l_i = jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False)
        a_i = jax.lax.dynamic_index_in_dim(acc, i, 0, keepdims=False)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1).astype(jnp.float32))
        p = jnp.exp(s - m_new[..., None].astype(sdt))
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p.astype(jnp.float32), axis=-1)
        a_new = a_i * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(sdt),
            preferred_element_type=jnp.float32)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, i, 0)
        return (m, l, acc), None

    init = (jnp.full((Tq, B, H, Qc), NEG_INF, jnp.float32),
            jnp.zeros((Tq, B, H, Qc), jnp.float32),
            jnp.zeros((Tq, B, H, Qc, hd_v), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(step), init, (pi, pj))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.astype(q.dtype)
    B_, Sq_ = q.shape[0], q.shape[1]
    return out.transpose(1, 0, 3, 2, 4).reshape(B_, Sq_, H, hd_v)


# ---------------------------------------------------------------------------
# GQA layer


def make_attn_params(rng, cfg: ModelConfig) -> Params:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    r = jax.random.split(rng, 4)
    return {
        "wq": make_dense_params(r[0], d, H * hd, bias=cfg.qkv_bias),
        "wk": make_dense_params(r[1], d, Hkv * hd, bias=cfg.qkv_bias),
        "wv": make_dense_params(r[2], d, Hkv * hd, bias=cfg.qkv_bias),
        "wo": make_dense_params(r[3], H * hd, d),
    }


def _project_qkv(p: Params, x: jax.Array, positions, cfg: ModelConfig):
    B, S, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = dense(p["wq"], x, cfg=cfg, tag="attn/wq")
    kk = dense(p["wk"], x, cfg=cfg, tag="attn/wk")
    vv = dense(p["wv"], x, cfg=cfg, tag="attn/wv")
    q = constrain(q, P(BATCH_AXES, None, "model"))
    q = q.reshape(B, S, H, hd)
    kk = kk.reshape(B, S, Hkv, hd)
    vv = vv.reshape(B, S, Hkv, hd)
    q = apply_rope(q, positions, head_dim=hd, theta=cfg.rope_theta,
                   two_d=cfg.rope_2d)
    kk = apply_rope(kk, positions, head_dim=hd, theta=cfg.rope_theta,
                    two_d=cfg.rope_2d)
    return q, kk, vv


def attn_forward(p: Params, x: jax.Array, positions: jax.Array,
                 cfg: ModelConfig, *, window: int = 0,
                 causal: bool = True) -> Tuple[jax.Array, Dict]:
    """Training/prefill attention. Returns (out, kv) — kv feeds the cache."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, positions, cfg)
    # heads sharded over 'model' for the compute; see module docstring.
    q = constrain(q, P(BATCH_AXES, None, "model", None))
    k = constrain(k, P(BATCH_AXES, None, "model", None))
    v = constrain(v, P(BATCH_AXES, None, "model", None))
    o = blockwise_attn(q, k, v, causal=causal, window=window)
    o = o.reshape(B, S, cfg.n_heads * cfg.resolved_head_dim)
    o = constrain(o, P(BATCH_AXES, None, "model"))
    out = dense(p["wo"], o, cfg=cfg, tag="attn/wo")
    return out, {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Decode path (flash-decoding over a sequence-sharded cache)


def init_attn_cache(cfg: ModelConfig, batch: int, cache_len: int,
                    *, window: int = 0, dtype=jnp.bfloat16) -> Dict:
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    L = min(window, cache_len) if window > 0 else cache_len
    return {
        "k": jnp.zeros((batch, L, Hkv, hd), dtype),
        "v": jnp.zeros((batch, L, Hkv, hd), dtype),
        "pos": jnp.full((L,), -(10 ** 9), jnp.int32),
        "window": jnp.asarray(window, jnp.int32),
    }


def cache_specs(window: int = 0):
    """PartitionSpecs matching init_attn_cache layout."""
    seq_ax = None if window > 0 else "model"   # ring buffers are small
    return {"k": P(BATCH_AXES, seq_ax, None, None),
            "v": P(BATCH_AXES, seq_ax, None, None),
            "pos": P(None), "window": P()}


def attn_cache_reset_spec(quantized: bool = False):
    """Per-leaf slot-recycle action (see repro.serving.cache): KV bytes
    stay stale-but-masked; only positions are invalidated (O(L) words).
    int8 scale leaves are ``keep`` like the bytes they scale: a stale
    scale times a stale int8 value is finite garbage the new occupant's
    empty ``pos`` row masks out, and writes land in lockstep anyway."""
    spec = {"k": "keep", "v": "keep", "pos": "empty", "window": "keep"}
    if quantized:
        spec.update({"k_scale": "keep", "v_scale": "keep"})
    return spec


def fill_cache_from_prefill(cache: Dict, kv: Dict, t0: int = 0) -> Dict:
    """Write prefill kv (B,S,Hkv,hd) into the cache (ring-aware)."""
    S = kv["k"].shape[1]
    L = cache["k"].shape[1]
    if S >= L:   # keep last L positions (ring layout = positions mod L)
        ks, vs = kv["k"][:, S - L:], kv["v"][:, S - L:]
        pos = jnp.arange(S - L, S, dtype=jnp.int32) + t0
        slot = pos % L
        k = jnp.zeros_like(cache["k"]).at[:, slot].set(ks)
        v = jnp.zeros_like(cache["v"]).at[:, slot].set(vs)
        parr = jnp.full((L,), -(10 ** 9), jnp.int32).at[slot].set(pos)
    else:
        k = cache["k"].at[:, :S].set(kv["k"].astype(cache["k"].dtype))
        v = cache["v"].at[:, :S].set(kv["v"].astype(cache["v"].dtype))
        parr = cache["pos"].at[:S].set(jnp.arange(S, dtype=jnp.int32) + t0)
    return {"k": k, "v": v, "pos": parr, "window": cache["window"]}


def init_attn_cache_slots(cfg: ModelConfig, batch: int, cache_len: int,
                          *, window: int = 0, dtype=jnp.bfloat16) -> Dict:
    """Slot-pool cache: like :func:`init_attn_cache` but positions are
    tracked per batch row ((B, L) not (L,)) so every row can sit at a
    different decode position — the layout continuous batching needs."""
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    L = min(window, cache_len) if window > 0 else cache_len
    return {
        "k": jnp.zeros((batch, L, Hkv, hd), dtype),
        "v": jnp.zeros((batch, L, Hkv, hd), dtype),
        "pos": jnp.full((batch, L), EMPTY_POS, jnp.int32),
        "window": jnp.asarray(window, jnp.int32),
    }


def attn_ring_len(cfg: ModelConfig, cache_len: int, *, window: int = 0) -> int:
    """Logical (ring) length of this layer kind's KV cache — what the
    paged pool's per-slot block table must be able to address."""
    return min(window, cache_len) if window > 0 else cache_len


def init_attn_cache_paged(cfg: ModelConfig, n_slots: int, cache_len: int,
                          n_blocks: int, block_len: int, *, window: int = 0,
                          dtype=jnp.bfloat16) -> Dict:
    """Paged slot-pool cache: KV bytes live in a shared heads-major block
    arena ``(n_blocks, Hkv, block_len, hd)`` (one head's block is a whole
    TPU tile for the fused kernel) instead of one contiguous row per
    slot. A host-side block table (``(n_slots, T)``, passed into the
    decode program each tick) maps each slot's logical block j to an
    arena block; positions stay PER SLOT (``pos: (n_slots, T*block_len)``
    int32 words) so validity masking and the reset-spec recycle machinery
    are unchanged — a recycled arena block's stale KV is masked because
    the new occupant's ``pos`` row is empty until it writes.

    int8 ``dtype`` stores a QUANTIZED arena: K/V bytes are int8 and two
    fp32 scale arenas (``k_scale``/``v_scale``, ``(n_blocks, Hkv,
    block_len)``: per block per KV head per position) ride alongside,
    written at the same scatter indices as their values."""
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    L = attn_ring_len(cfg, cache_len, window=window)
    T = -(-L // block_len)                     # blocks per slot (ceil)
    cache = {
        "k": jnp.zeros((n_blocks, Hkv, block_len, hd), dtype),
        "v": jnp.zeros((n_blocks, Hkv, block_len, hd), dtype),
        "pos": jnp.full((n_slots, T * block_len), EMPTY_POS, jnp.int32),
        "window": jnp.asarray(window, jnp.int32),
    }
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        cache["k_scale"] = jnp.zeros((n_blocks, Hkv, block_len),
                                     jnp.float32)
        cache["v_scale"] = jnp.zeros((n_blocks, Hkv, block_len),
                                     jnp.float32)
    return cache


def attn_cache_slot_axes(quantized: bool = False) -> Dict:
    """Which leaves of the PAGED cache carry a slot axis (axis 1 once
    layer-stacked). Arena leaves (``False``) are shared across slots: the
    serving pool's row gather passes them through whole and its row
    scatter takes the updated arena back whole. Scale leaves (int8
    arenas) are shared exactly like the bytes they scale."""
    axes = {"k": False, "v": False, "pos": True, "window": False}
    if quantized:
        axes.update({"k_scale": False, "v_scale": False})
    return axes


def attn_decode_slots(p: Params, x: jax.Array, cache: Dict, t: jax.Array,
                      cfg: ModelConfig, *, window: int = 0,
                      table: Optional[jax.Array] = None,
                      attn_backend: Optional[str] = None
                      ) -> Tuple[jax.Array, Dict]:
    """Slot-batched decode: every batch row advances at its OWN position.

    x: (B, C, d); t: (B, C) int32 per-token positions with ``t < 0``
    marking padding (padding tokens write nothing into the cache — their
    scatter index is clamped out of bounds and dropped — and their output
    rows are garbage the caller must ignore). Two call shapes cover the
    serving engine: C == 1 is the lockstep decode-only tick over all
    slots; C > 1 is a MIXED tick — each row carries its own prefill
    chunk (or a single decode token in column 0 with the rest padded
    ``t < 0``), so chunk rows and decode rows advance in one program.
    Causality within a chunk holds because KV is written before
    attending and the mask compares cached positions against each
    query's position — ragged rows need no extra masking.

    ``table`` switches to the PAGED cache layout: ``cache["k"]``/``v``
    are shared block arenas ``(n_blocks, Hkv, block_len, hd)`` and
    ``table: (B, T)`` int32 maps each row's logical block to an arena
    block (-1 = unassigned). Token position t lands in arena block
    ``table[b, (t % (T*block_len)) // block_len]`` at offset
    ``t % block_len``; the reference backend gathers each row's T
    blocks back into a ``(B, T*block_len)`` logical view (the fused
    backend reads arena blocks in place). Unassigned entries gather
    arena block 0, but ``pos`` is per slot, so those logical positions
    still carry the empty sentinel and mask out — which is also why a
    recycled arena block cannot leak its previous owner's KV.

    ``attn_backend`` selects the decode-attention read path
    (``repro.kernels.ops.decode_gqa``): None/"xla" is the gather
    reference; "pallas" computes both the C == 1 tick and the C > 1
    chunk variant directly from the arena (no logical-view
    materialisation in either shape).
    """
    B, C, _ = x.shape
    q, k_new, v_new = _project_qkv(p, x, jnp.maximum(t, 0), cfg)

    bidx = jnp.arange(B)[:, None]
    k_new = constrain(k_new, P(BATCH_AXES, None, None, None))
    v_new = constrain(v_new, P(BATCH_AXES, None, None, None))
    if table is None:
        L = cache["k"].shape[1]
        slot = jnp.where(t >= 0, t % L, L)        # L is OOB -> mode="drop"
        k = cache["k"].at[bidx, slot].set(k_new.astype(cache["k"].dtype),
                                          mode="drop")
        v = cache["v"].at[bidx, slot].set(v_new.astype(cache["v"].dtype),
                                          mode="drop")
        pos = cache["pos"].at[bidx, slot].set(t, mode="drop")
        seq_spec = P(BATCH_AXES, "model", None, None)
        k = constrain(k, seq_spec)
        v = constrain(v, seq_spec)
        o = decode_gqa(q, k, v, pos, t, window=window,
                       backend=attn_backend)
    else:
        Nb, bl = cache["k"].shape[0], cache["k"].shape[2]
        wblk, off, lw, _, _ = paged_indices(table, t, Nb, bl)
        # (B, C) block/offset indices around the head slice: the update
        # operand is (B, C, Hkv, ...), exactly what the projection gives
        quantized = "k_scale" in cache
        if quantized:
            # int8 arena: quantize per token per KV head and scatter the
            # scale at the SAME (wblk, off) as its bytes — lockstep by
            # construction, so a recycled block can never pair fresh
            # bytes with a stale scale (or vice versa)
            kq, ks_new = quantize_kv(k_new)
            vq, vs_new = quantize_kv(v_new)
            k = cache["k"].at[wblk, :, off].set(kq, mode="drop")
            v = cache["v"].at[wblk, :, off].set(vq, mode="drop")
            k_scale = cache["k_scale"].at[wblk, :, off].set(ks_new,
                                                            mode="drop")
            v_scale = cache["v_scale"].at[wblk, :, off].set(vs_new,
                                                            mode="drop")
        else:
            k = cache["k"].at[wblk, :, off].set(
                k_new.astype(cache["k"].dtype), mode="drop")
            v = cache["v"].at[wblk, :, off].set(
                v_new.astype(cache["v"].dtype), mode="drop")
            k_scale = v_scale = None
        pos = cache["pos"].at[bidx, lw].set(t, mode="drop")
        o = decode_gqa(
            q, k, v, pos, t, window=window, table=table,
            backend=attn_backend, k_scale=k_scale, v_scale=v_scale,
            shard_kv=lambda a: constrain(
                a, P(BATCH_AXES, "model", None, None)))
    new_cache = {"k": k, "v": v, "pos": pos, "window": cache["window"]}
    if "k_scale" in cache:
        new_cache["k_scale"] = k_scale
        new_cache["v_scale"] = v_scale
    out = dense(p["wo"], o, cfg=cfg, tag="attn/wo")
    return out, new_cache


def attn_decode(p: Params, x: jax.Array, cache: Dict, t: jax.Array,
                cfg: ModelConfig, *, window: int = 0) -> Tuple[jax.Array, Dict]:
    """One-token decode. x: (B, 1, d); t: current position (scalar int32)."""
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    group = H // Hkv
    q, k_new, v_new = _project_qkv(p, x, t[None, None] if t.ndim == 0 else t, cfg)

    L = cache["k"].shape[1]
    slot = (t % L).astype(jnp.int32)
    # match the cache sharding (batch on dp, seq on 'model') before the
    # in-place update — otherwise GSPMD full-remats the cache per layer.
    k_new = constrain(k_new, P(BATCH_AXES, None, None, None))
    v_new = constrain(v_new, P(BATCH_AXES, None, None, None))
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    pos = cache["pos"].at[slot].set(t.astype(jnp.int32))

    # flash-decoding over the sequence-sharded cache: q replicated across
    # 'model', scores/PV contract the sharded L axis -> two tiny
    # all-reduces per layer instead of resharding the cache. The GQA
    # repeat stays implicit (grouped einsum) and the cache is read in its
    # storage dtype with fp32 accumulation — one bf16 pass over the cache
    # per step, the decode memory-roofline ideal.
    seq_spec = P(BATCH_AXES, "model", None, None)
    k = constrain(k, seq_spec)
    v = constrain(v, seq_spec)
    # f8 caches (kvq8 serving variant) compute in bf16; HBM still reads
    # the 1-byte storage (converts fuse on TPU; the roofline analyzer
    # charges pre-convert bytes).
    cdt = jnp.bfloat16 if jnp.dtype(k.dtype).itemsize == 1 else k.dtype
    qg = constrain(q.reshape(B, Hkv, group, hd),
                   P(BATCH_AXES, None, None, None)).astype(cdt)
    s = jnp.einsum("bkgd,blkd->bkgl", qg, k.astype(cdt),
                   preferred_element_type=jnp.float32) * (hd ** -0.5)
    s = constrain(s, P(BATCH_AXES, None, None, "model"))
    valid = (pos >= 0) & (pos <= t)      # pos < 0 marks empty slots
    if window > 0:
        valid &= pos > t - window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgl,blkd->bkgd", prob.astype(cdt), v.astype(cdt),
                   preferred_element_type=jnp.float32).astype(x.dtype)
    o = o.reshape(B, 1, H * hd)
    out = dense(p["wo"], o, cfg=cfg, tag="attn/wo")
    new_cache = {"k": k, "v": v, "pos": pos, "window": cache["window"]}
    return out, new_cache
