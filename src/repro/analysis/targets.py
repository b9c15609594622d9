"""Trace targets: the REAL serving programs the jaxpr rules inspect.

The analyzer does not check toy re-derivations — it traces the same
jitted programs serving runs:

- ``serving_step_targets``: every cache family the engine serves
  (dense/GQA, hybrid sliding-window ring, absorbed-MLA) x both decode-
  attention backends (``xla`` gather reference, ``pallas`` fused),
  through the actual :class:`~repro.serving.runner.TokenRunner` step
  programs (``_decode_greedy`` for the lockstep C == 1 tick,
  ``_step_greedy`` for the co-batched mixed tick) at smoke scale —
  plus an int8-quantized-arena variant so the dequant paths are
  covered. Each target carries its pool's ARENA SIGNATURES
  (``(n_blocks, block_len) -> T``), which is how the materialization
  rule recognizes a logical-view gather without false-positiving on
  embedding lookups of similar size.
- ``attention_op_targets``: the ``repro.kernels.ops`` decode-attention
  dispatch (GQA + MLA, fp32/bf16/int8 arenas, C == 1 and chunk) and
  the quantized ``qmatmul`` — the jaxprs the precision rule audits for
  fp32 softmax stats / accumulators.
- ``basecaller_stream_targets``: the streaming basecall tick — the
  batched halo-window forward exactly as ``BasecallerRunner.step``
  invokes it, with and without the co-executed read-until classifier
  head. No KV arena (``arena_sigs`` stays empty, so the
  materialization rule skips them); the precision rule walks them and
  the trace-stability audit re-ticks the live runner.

Tracing uses ``jax.make_jaxpr`` only (no compilation, no execution),
so a full target sweep costs seconds on CPU.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Smoke arch per cache family (matches the tier-1 parity suites).
SERVING_FAMILIES: Tuple[Tuple[str, str], ...] = (
    ("gqa", "qwen1.5-4b-smoke"),          # dense/GQA full attention
    ("swa", "hymba-1.5b-smoke"),          # hybrid sliding-window ring
    ("mla", "deepseek-v3-671b-smoke"),    # absorbed-MLA latent cache
)
BACKENDS: Tuple[str, ...] = ("xla", "pallas")

# Smoke-scale pool geometry shared by every serving target.
N_SLOTS, CACHE_LEN, BLOCK_LEN, CHUNK = 2, 16, 4, 4


@dataclasses.dataclass(frozen=True)
class TraceTarget:
    """One traced program + the metadata rules need to judge it."""
    name: str                 # e.g. "step[qwen1.5-4b-smoke/pallas/mixed]"
    jaxpr: Any                # ClosedJaxpr
    kind: str                 # "serving-step" | "attn-op" | "qmatmul"
    backend: Optional[str]    # "xla" | "pallas" | None
    quantized: bool           # int8 arena (scale leaves ride along)
    n_slots: int = 0
    block_len: int = 0
    # (n_blocks, block_len) -> min blocks-per-slot T among matching
    # groups: how a rule recognizes an arena-shaped gather operand.
    arena_sigs: Dict[Tuple[int, int], int] = dataclasses.field(
        default_factory=dict)

    def view_floor(self, operand_shape: Sequence[int]) -> Optional[int]:
        """Size of the ``(B, T*block_len, ...)`` logical view a gather
        from an arena-shaped operand would materialize — None when the
        operand is not arena-shaped for this target. Arenas are
        ``(n_blocks, block_len, ...)`` (MLA latents) or heads-major
        ``(n_blocks, Hkv, block_len, ...)`` (GQA K/V)."""
        if len(operand_shape) < 3:
            return None
        for ax in (1, 2):
            T = self.arena_sigs.get((operand_shape[0], operand_shape[ax]))
            if T is not None:
                feat = math.prod(operand_shape[1:]) // operand_shape[ax]
                return self.n_slots * T * self.block_len * feat
        return None


def _pool_sigs(pool) -> Dict[Tuple[int, int], int]:
    sigs: Dict[Tuple[int, int], int] = {}
    for g, T in pool.layout.items():
        key = (pool.n_blocks[g], pool.block_len)
        sigs[key] = min(T, sigs.get(key, T))
    return sigs


def _build_runner(arch: str, backend: str, quant: Optional[str] = None):
    from repro.config import get_config
    from repro.models import api
    from repro.serving.runner import TokenRunner
    cfg = get_config(arch)
    params = api.init_params(jax.random.key(0), cfg)
    return TokenRunner(params, cfg, n_slots=N_SLOTS, cache_len=CACHE_LEN,
                       prefill_chunk=CHUNK, cache_dtype=jnp.float32,
                       block_len=BLOCK_LEN, attn_backend=backend,
                       quant_policy=quant)


def serving_step_targets(
        families: Sequence[Tuple[str, str]] = SERVING_FAMILIES,
        backends: Sequence[str] = BACKENDS,
        quant_archs: Sequence[str] = ("qwen1.5-4b-smoke",),
) -> List[TraceTarget]:
    """Trace the real runner step programs per family x backend x tick
    shape (plus int8-arena variants of ``quant_archs``)."""
    out: List[TraceTarget] = []
    for _family, arch in families:
        for backend in backends:
            variants = [(None, "")]
            if arch in quant_archs:
                variants.append(("int8", "/int8"))
            for quant, tag in variants:
                runner = _build_runner(arch, backend, quant)
                out.extend(_trace_runner_steps(
                    runner, f"{arch}/{backend}{tag}",
                    quantized=quant == "int8"))
    return out


def _trace_runner_steps(runner, label: str, quantized: bool
                        ) -> List[TraceTarget]:
    """Trace one runner's decode-only and mixed tick programs with the
    exact host-side argument layout ``TokenRunner.step`` builds."""
    B, C = runner.n_slots, runner.chunk_tokens
    pool = runner.pool
    meta = dict(kind="serving-step", backend=pool.attn_backend,
                quantized=quantized, n_slots=B, block_len=pool.block_len,
                arena_sigs=_pool_sigs(pool))
    tables = pool.device_tables()
    chain = np.zeros((B,), np.int32)        # async chained-token args:
    prev = np.zeros((B,), np.int32)         # all-zero = sync semantics
    # decode-only tick: the lockstep (B, 1) greedy program
    tok1 = np.zeros((B, 1), np.int32)
    t1 = np.arange(3, 3 + B, dtype=np.int32).reshape(B, 1)
    jx_decode = jax.make_jaxpr(runner._decode_greedy)(
        runner.params, pool.caches, tok1, t1, chain, prev, tables,
        runner.enc_kv)
    # mixed tick: chunk row co-batched with a padded decode row
    tokC = np.zeros((B, C), np.int32)
    tC = np.full((B, C), -1, np.int32)
    tC[0] = np.arange(C)
    tC[1:, 0] = 5
    fresh = np.zeros((B,), np.int32)
    last = np.zeros((B,), np.int32)
    jx_mixed = jax.make_jaxpr(runner._step_greedy)(
        runner.params, pool.caches, tokC, tC, chain, prev, fresh, last,
        tables, runner.enc_kv)
    return [TraceTarget(name=f"step[{label}/decode]", jaxpr=jx_decode,
                        **meta),
            TraceTarget(name=f"step[{label}/mixed]", jaxpr=jx_mixed,
                        **meta)]


def _build_basecaller_runner(read_until: bool):
    from repro.config import get_config
    from repro.models import api
    from repro.models.basecaller import classifier as rc
    from repro.serving.runner import BasecallerRunner
    from repro.serving.stream import ReadUntil
    cfg = get_config("bonito-smoke")
    params = api.init_params(jax.random.key(0), cfg)
    ru = None
    if read_until:
        # untrained head, threshold -inf: the PROGRAM is what's audited
        ru = ReadUntil(params=rc.init_params(jax.random.key(1)),
                       eject_after_chunks=2, threshold=-1e9)
    return BasecallerRunner(params, cfg, n_slots=N_SLOTS,
                            chunk_samples=300, read_until=ru)


def basecaller_stream_targets() -> List[TraceTarget]:
    """Trace the streaming basecall tick program (batched halo-window
    forward; ``/read_until`` adds the fused classifier head) with the
    exact argument layout ``BasecallerRunner.step`` builds."""
    out: List[TraceTarget] = []
    for read_until, tag in ((False, ""), (True, "/read_until")):
        runner = _build_basecaller_runner(read_until)
        W = runner.core + 2 * runner.halo
        wins = np.zeros((N_SLOTS, W, 1), np.float32)
        start = np.zeros((N_SLOTS,), np.int32)
        read_len = np.full((N_SLOTS,), W, np.int32)
        jx = jax.make_jaxpr(runner._fwd)(runner.params, runner.state,
                                         wins, start, read_len)
        out.append(TraceTarget(
            name=f"step[bonito-smoke/stream{tag}]", jaxpr=jx,
            kind="serving-step", backend=None, quantized=False,
            n_slots=N_SLOTS))
    return out


def attention_op_targets(backends: Sequence[str] = BACKENDS
                         ) -> List[TraceTarget]:
    """Trace the decode-attention dispatch + quantized matmul jaxprs."""
    from repro.kernels import ops
    out: List[TraceTarget] = []
    B, Hkv, hd, bl, T, Nb = 2, 2, 16, 4, 4, 10
    pos = np.full((B, T * bl), -1, np.int32)
    table = np.zeros((B, T), np.int32)
    sigs = {(Nb, bl): T}
    for backend in backends:
        for C, ctag in ((1, "decode"), (4, "chunk")):
            q = jnp.zeros((B, C, 2 * Hkv, hd), jnp.float32)
            t = np.zeros((B, C), np.int32)
            for cdt, scales, qtag in (
                    (jnp.float32, False, "fp32"),
                    (jnp.bfloat16, False, "bf16"),
                    (jnp.int8, True, "int8")):
                k = jnp.zeros((Nb, Hkv, bl, hd), cdt)
                sc = (jnp.zeros((Nb, Hkv, bl), jnp.float32) if scales
                      else None)
                jx = jax.make_jaxpr(
                    lambda q, k, v, pos, t, table, ks, vs:
                    ops.decode_gqa(q, k, v, pos, t, table=table,
                                   backend=backend, k_scale=ks,
                                   v_scale=vs))(
                    q, k, k, pos, t, table, sc, sc)
                out.append(TraceTarget(
                    name=f"decode_gqa[{backend}/{ctag}/{qtag}]", jaxpr=jx,
                    kind="attn-op", backend=backend, quantized=scales,
                    n_slots=B, block_len=bl, arena_sigs=sigs))
        # absorbed-MLA (latent + rope halves), C == 1
        kvr, rope_d = 16, 8
        qa = jnp.zeros((B, 1, 4, kvr), jnp.float32)
        qr = jnp.zeros((B, 1, 4, rope_d), jnp.float32)
        t = np.zeros((B, 1), np.int32)
        for cdt, scales, qtag in ((jnp.float32, False, "fp32"),
                                  (jnp.int8, True, "int8")):
            c = jnp.zeros((Nb, bl, kvr), cdt)
            kr = jnp.zeros((Nb, bl, rope_d), cdt)
            sc = jnp.zeros((Nb, bl), jnp.float32) if scales else None
            jx = jax.make_jaxpr(
                lambda qa, qr, c, kr, pos, t, table, cs, krs:
                ops.decode_mla(qa, qr, c, kr, pos, t, scale=0.17,
                               table=table, backend=backend, c_scale=cs,
                               kr_scale=krs))(
                qa, qr, c, kr, pos, t, table, sc, sc)
            out.append(TraceTarget(
                name=f"decode_mla[{backend}/{qtag}]", jaxpr=jx,
                kind="attn-op", backend=backend, quantized=scales,
                n_slots=B, block_len=bl, arena_sigs=sigs))
    # quantized-weight matmul (int8 weights, fp32 activations/acc)
    x = jnp.zeros((128, 128), jnp.float32)
    w = jnp.zeros((128, 128), jnp.int8)
    s = jnp.zeros((128,), jnp.float32)
    jx = jax.make_jaxpr(lambda x, w, s: ops.qmatmul(x, w, s))(x, w, s)
    out.append(TraceTarget(name="qmatmul[int8]", jaxpr=jx, kind="qmatmul",
                           backend=None, quantized=True))
    return out
