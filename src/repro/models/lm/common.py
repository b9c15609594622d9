"""Shared functional building blocks for the LM zoo.

Conventions
-----------
- Params are nested dicts of ``jnp.ndarray`` (fp32 "master" storage).
- Every forward casts to ``cfg.dtype`` for compute; norms & softmax in fp32.
- Layer stacks carry a leading ``n_layers`` axis (built with vmap'd init,
  consumed with ``lax.scan``) so HLO size is O(1) in depth.
- Matmuls route through :func:`dense` which applies the per-layer
  quantization policy (fake-quant in training, int storage in serving).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig

Params = Dict[str, Any]


def truncated_normal_init(rng, shape, dtype=jnp.float32, stddev=0.02):
    return stddev * jax.random.truncated_normal(rng, -2.0, 2.0, shape, dtype)


def make_dense_params(rng, d_in: int, d_out: int, *, bias: bool = False,
                      stddev: float = 0.02) -> Params:
    kr, _ = jax.random.split(rng)
    p = {"kernel": truncated_normal_init(kr, (d_in, d_out), stddev=stddev)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), jnp.float32)
    return p


def kernel_of(p: Params, dtype) -> jax.Array:
    """Weight leaf, dequantizing PackedTensor (int8/int4 serving storage)
    on the fly — HBM reads the packed bytes; the convert fuses in-register
    (the Pallas ``qmatmul`` kernel is the explicit TPU twin)."""
    w = p["kernel"] if isinstance(p, dict) else p
    from repro.core.quant.policy import PackedTensor, dequantize
    if isinstance(w, PackedTensor):
        return dequantize(w, dtype)
    return w.astype(dtype)


def _qmatmul_tiles(m: int, k: int, n: int, bits: int) -> bool:
    """True when (M, K, N) satisfies ``qmatmul_p``'s tiling contract:
    every dim divides its ``min(128, dim)`` block, and int4 needs an
    even K (two nibbles per byte along the reduction axis) that divides
    its own K tile."""
    ok = all(d > 0 and d % min(128, d) == 0 for d in (m, k, n))
    if bits == 4:
        from repro.kernels.qmatmul import int4_k_block
        ok = ok and k % 2 == 0 and k % int4_k_block(k) == 0
    return ok


def dense(p: Params, x: jax.Array, *, cfg: ModelConfig, tag: str = "",
          quantize: bool = True) -> jax.Array:
    """Quantization-aware dense layer — the RUBICON policy hook.

    When the config's :class:`QuantPolicy` is enabled, weights (and
    optionally activations) pass through symmetric fake-quant at the
    per-layer bit-width before the matmul (QAT semantics). Serving-time
    int8/int4 packed weights (``PackedTensor``) take the Pallas
    ``qmatmul`` kernel when the config carries QABAS bit-widths for the
    layer and the shapes satisfy the kernel's tiling contract; otherwise
    they dequantize on read (same int storage, XLA matmul).
    """
    dt = jnp.dtype(cfg.dtype)
    from repro.core.quant.policy import PackedTensor
    if isinstance(p["kernel"], PackedTensor):
        w_p = p["kernel"]
        wb, _ = cfg.quant.bits_for(tag)
        m = 1
        for s in x.shape[:-1]:
            m *= s
        if (wb in (4, 8) and w_p.data.ndim == 2
                and _qmatmul_tiles(m, x.shape[-1], w_p.data.shape[-1],
                                   w_p.bits)):
            from repro.kernels.ops import qmatmul
            y = qmatmul(x.astype(dt), w_p)
            if "bias" in p:
                y = y + p["bias"].astype(dt)
            return y
        w = kernel_of(p, dt)
    else:
        w = p["kernel"]
        if quantize and cfg.quant.enabled:
            from repro.core.quant.fake_quant import fake_quant
            wb, ab = cfg.quant.bits_for(tag)
            if wb:
                w = fake_quant(w, wb,
                               axis=0 if cfg.quant.per_channel else None)
            if ab:
                x = fake_quant(x, ab, axis=None)
        w = w.astype(dt)
    y = jnp.dot(x.astype(dt), w)
    if "bias" in p:
        y = y + p["bias"].astype(dt)
    return y


# ---------------------------------------------------------------------------
# Norms


def make_rmsnorm_params(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(dt)


def make_layernorm_params(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    return y.astype(dt)


# ---------------------------------------------------------------------------
# MLPs


def make_mlp_params(rng, d: int, ff: int, *, gated: bool = True,
                    bias: bool = False) -> Params:
    r = jax.random.split(rng, 3)
    p = {"wi": make_dense_params(r[0], d, ff, bias=bias),
         "wo": make_dense_params(r[1], ff, d, bias=bias)}
    if gated:
        p["wg"] = make_dense_params(r[2], d, ff, bias=bias)
    return p


def mlp(p: Params, x: jax.Array, *, cfg: ModelConfig, tag: str = "mlp",
        act: str = "silu", hidden_spec: Optional[P] = None) -> jax.Array:
    h = dense(p["wi"], x, cfg=cfg, tag=tag + "/wi")
    if "wg" in p:
        g = dense(p["wg"], x, cfg=cfg, tag=tag + "/wg")
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h) if act == "gelu" else jax.nn.silu(h)
    h = constrain(h, hidden_spec if hidden_spec is not None
                  else P(BATCH_AXES, None, "model"))
    return dense(p["wo"], h, cfg=cfg, tag=tag + "/wo")


# ---------------------------------------------------------------------------
# Sharding constraint helpers

# Logical data-parallel axes. The production mesh uses ("data","model") or
# ("pod","data","model"); batch shards over every non-"model" axis present.
BATCH_AXES: Tuple[str, ...] = ("pod", "data")


def _ambient_mesh() -> Optional[Any]:
    try:
        from jax._src import mesh as mesh_lib
        pm = mesh_lib.thread_resources.env.physical_mesh
        if not pm.empty:
            return pm
    except Exception:
        pass
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """``with_sharding_constraint`` that degrades to a no-op off-mesh.

    Axis names not present in the ambient mesh are dropped, as are axes
    that do not divide the dimension evenly (keeps every arch lowerable on
    the fixed production mesh; the padding waste this avoids is discussed
    in EXPERIMENTS.md)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.shape.values()
                     if hasattr(mesh.shape, "values") else mesh.shape))

    def fix(i, entry):
        if entry is None:
            return None
        kept = tuple(a for a in (entry if isinstance(entry, (tuple, list))
                                 else (entry,)) if a in names)
        while kept:
            total = 1
            for a in kept:
                total *= sizes[a]
            if i < x.ndim and x.shape[i] % total == 0:
                break
            kept = kept[:-1]
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    fixed = P(*(fix(i, e) for i, e in enumerate(spec)))
    return jax.lax.with_sharding_constraint(x, fixed)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ignore_id: int = -1) -> Tuple[jax.Array, jax.Array]:
    """Stable CE in fp32 over (possibly vocab-sharded) logits.

    Returns (sum_loss, sum_weight) so microbatch accumulation can average.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    picked = jnp.take_along_axis(lf, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    nll = lse - picked
    w = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * w), jnp.sum(w)
