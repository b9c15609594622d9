"""Unified model API: one bundle per architecture.

``get_bundle(cfg)`` returns init / loss / train_step / prefill_step /
decode_step plus ShapeDtypeStruct ``input_specs`` for AOT lowering (the
multi-pod dry-run lowers these without allocating anything).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, ShapeConfig
from repro.models.lm.common import cross_entropy, dense, rmsnorm
from repro.training.optimizer import AdamWConfig, OptState, adamw_update

MICRO_TOKENS = 65536       # grad-accum target: tokens per microbatch


class TrainCarry(NamedTuple):
    params: Any
    opt_state: OptState
    model_state: Any        # e.g. BatchNorm running stats (basecaller)


def _is_lm(cfg: ModelConfig) -> bool:
    return cfg.family != "basecaller"


# ---------------------------------------------------------------------------
# Init


def init_params(rng, cfg: ModelConfig):
    if cfg.family == "basecaller":
        from repro.models.basecaller import model as bc
        return bc.init_params(rng, cfg)
    return _init_lm_params(rng, cfg)


@functools.partial(jax.jit, static_argnums=1)
def _init_lm_params(rng, cfg: ModelConfig):
    """LM weights in ``cfg.dtype``. One program: each leaf is drawn in
    float32 and cast inside it, so only the target-dtype tree is ever
    held (a float32 copy of qwen1.5-4b alone would fill a v5e)."""
    from repro.models.lm import transformer as tfm
    params = tfm.init_decoder(rng, cfg)
    if cfg.family == "audio":
        from repro.models.lm import encdec
        params["encoder"] = encdec.init_encoder(jax.random.fold_in(rng, 7), cfg)
    if cfg.dtype != "float32":
        dt = jnp.dtype(cfg.dtype)
        params = jax.tree.map(lambda a: a.astype(dt), params)
    return params


def init_model_state(cfg: ModelConfig):
    if cfg.family == "basecaller":
        from repro.models.basecaller import model as bc
        return bc.init_state(cfg)
    return {}


def count_params_analytic(cfg: ModelConfig) -> int:
    import math
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    return sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))


def active_params(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: shared + top-k routed only)."""
    total = count_params_analytic(cfg)
    if cfg.family != "moe" or not cfg.n_experts:
        return total
    ff = cfg.moe_d_ff or cfg.d_ff
    n_moe_layers = cfg.n_layers - cfg.n_dense_layers
    routed = n_moe_layers * cfg.n_experts * 3 * cfg.d_model * ff
    active_routed = routed * cfg.experts_per_tok // cfg.n_experts
    return total - routed + active_routed


# ---------------------------------------------------------------------------
# Loss


def make_loss_fn(cfg: ModelConfig) -> Callable:
    if cfg.family == "basecaller":
        from repro.models.basecaller import model as bc

        def bc_loss(params, model_state, batch):
            return bc.loss_fn(params, model_state, batch, cfg)
        return bc_loss

    from repro.models.lm import transformer as tfm

    def lm_loss(params, model_state, batch):
        kw = {}
        if cfg.family == "vlm":
            kw["patch_embeds"] = batch["patch_embeds"]
        if cfg.family == "audio":
            from repro.models.lm import encdec
            kw["enc_out"] = encdec.encode(params["encoder"],
                                          batch["frames"], cfg)
        h, aux = tfm.forward(params, batch["tokens"], cfg, **kw)
        if cfg.family == "vlm":
            h = h[:, batch["patch_embeds"].shape[1]:]
        logits = tfm.unembed(params, h, cfg)
        lsum, wsum = cross_entropy(logits, batch["labels"])
        loss = lsum / jnp.maximum(wsum, 1.0)
        metrics = {"ce": loss}
        if aux is not None and cfg.n_experts:
            loss = loss + 0.01 * aux
            metrics["moe_aux"] = aux
        if cfg.mtp_depth:
            loss_mtp = _mtp_loss(params, h, batch, cfg)
            loss = loss + 0.3 * loss_mtp
            metrics["mtp"] = loss_mtp
        return loss, (metrics, model_state)

    return lm_loss


def _mtp_loss(params, h, batch, cfg: ModelConfig):
    """DeepSeek-V3 multi-token prediction head (depth 1): predict t+2."""
    from repro.models.lm import transformer as tfm
    mtp = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    emb_next = tfm.embed_tokens(params, tokens[:, 1:], cfg)
    hcat = jnp.concatenate(
        [rmsnorm(mtp["norm"], h[:, :-1], cfg.norm_eps), emb_next], axis=-1)
    x = dense(mtp["proj"], hcat, cfg=cfg, tag="mtp/proj")
    B, S1, _ = x.shape
    positions = jnp.arange(S1, dtype=jnp.int32)[None, :].repeat(B, 0)
    kind = "mla_dense" if cfg.mla else "dense"
    x, _, _ = tfm.block_forward(mtp["block"], x, positions, cfg, kind)
    logits = tfm.unembed(params, x, cfg)
    lsum, wsum = cross_entropy(logits, labels[:, 1:])
    return lsum / jnp.maximum(wsum, 1.0)


# ---------------------------------------------------------------------------
# Train step (microbatch grad accumulation)


def n_microbatches(cfg: ModelConfig, batch: int, seq: int,
                   dp: int = 1) -> int:
    """Grad-accumulation factor: ~MICRO_TOKENS tokens per microbatch, but
    never slicing the batch below one example per data-parallel shard."""
    n = max(1, (batch * seq) // MICRO_TOKENS)
    n = min(n, max(batch // max(dp, 1), 1))
    while batch % n:
        n -= 1
    return n


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    n_micro: int = 1) -> Callable:
    loss_fn = make_loss_fn(cfg)

    def train_step(carry: TrainCarry, batch: Dict) -> Tuple[TrainCarry, Dict]:
        params, opt_state, mstate = carry

        def split(x):
            return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])

        micro = jax.tree.map(split, batch)

        def gstep(acc, mb):
            gacc, lacc, st = acc
            (l, (_, new_st)), g = jax.value_and_grad(
                loss_fn, has_aux=True)(params, st, mb)
            g32 = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                               gacc, g)
            return (g32, lacc + l, new_st), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if n_micro == 1:
            (l, (_, mstate)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mstate,
                                       jax.tree.map(lambda x: x[0], micro))
            loss = l
        else:
            (grads, lsum, mstate), _ = jax.lax.scan(
                gstep, (zeros, jnp.zeros((), jnp.float32), mstate), micro)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = lsum / n_micro

        new_params, new_opt, om = adamw_update(params, grads, opt_state,
                                               opt_cfg)
        metrics = {"loss": loss, **om}
        return TrainCarry(new_params, new_opt, mstate), metrics

    return train_step


# ---------------------------------------------------------------------------
# Serving steps


def make_prefill_step(cfg: ModelConfig) -> Callable:
    from repro.models.lm import transformer as tfm

    def prefill_step(params, batch):
        kw = {}
        if cfg.family == "vlm":
            kw["patch_embeds"] = batch["patch_embeds"]
        if cfg.family == "audio":
            from repro.models.lm import encdec
            kw["enc_out"] = encdec.encode(params["encoder"],
                                          batch["frames"], cfg)
        return tfm.prefill(params, batch["tokens"], cfg, **kw)

    return prefill_step


def make_decode_step(cfg: ModelConfig) -> Callable:
    from repro.models.lm import transformer as tfm

    def decode_step(params, caches, tokens, t):
        return tfm.decode_step(params, caches, tokens, t, cfg)

    return decode_step


def make_serving_engine(params, cfg: ModelConfig, **kw):
    """Continuous-batching engine over this model (repro.serving).

    Dispatches through the serving runner registry: token-only LMs
    (TokenRunner over the paged KV pool, per-request SamplingParams),
    audio enc-dec (EncoderPrefixRunner — encoder K/V staged per slot at
    admission), and basecallers (BasecallerRunner — squiggle chunks in,
    bases out). vlm frontends have no runner yet and raise
    NotImplementedError. Extra ``**kw`` reach the runner (e.g.
    ``chunk_samples``/``beam``/``model_state`` for basecallers)."""
    from repro.serving.engine import ServingEngine
    return ServingEngine(params, cfg, **kw)


def make_runner(params, cfg: ModelConfig, **kw):
    """The registered serving backend alone (no scheduler) — see
    ``repro.serving.runner``."""
    from repro.serving.runner import make_runner as _make
    return _make(params, cfg, **kw)


# ---------------------------------------------------------------------------
# Shape/dtype specs for AOT lowering (dry-run) & smoke batches


def batch_struct(cfg: ModelConfig, shape: ShapeConfig) -> Dict:
    B, S = shape.global_batch, shape.seq_len
    i32, f32 = jnp.int32, jnp.float32
    sd = jax.ShapeDtypeStruct
    if cfg.family == "basecaller":
        T = S
        return {"signal": sd((B, T, 1), f32),
                "labels": sd((B, T // 8), i32),
                "label_lengths": sd((B,), i32)}
    if shape.kind == "decode":
        return {"tokens": sd((B, 1), i32), "t": sd((), i32)}
    tok = {"tokens": sd((B, S), i32)}
    if cfg.family == "vlm":
        Pt = cfg.frontend_tokens
        tok = {"tokens": sd((B, S - Pt), i32),
               "patch_embeds": sd((B, Pt, cfg.d_model), f32)}
    if cfg.family == "audio":
        tok["frames"] = sd((B, cfg.frontend_tokens, cfg.d_model), f32)
    if shape.kind == "train":
        lab_shape = (B, S - cfg.frontend_tokens) if cfg.family == "vlm" \
            else (B, S)
        tok["labels"] = sd(lab_shape, i32)
    return tok


def batch_specs(cfg: ModelConfig, shape: ShapeConfig,
                mesh_axes: Tuple[str, ...]) -> Dict:
    """PartitionSpecs matching batch_struct. Batch shards over every
    non-'model' axis when divisible, else replicates."""
    dp = tuple(a for a in mesh_axes if a != "model")
    struct = batch_struct(cfg, shape)

    def spec_of(leaf):
        if not leaf.shape:
            return P()
        b = leaf.shape[0]
        # divisibility check is done against axis sizes by the caller's mesh;
        # here we only emit names — dryrun validates divisibility.
        return P(dp if b > 1 else None,
                 *([None] * (len(leaf.shape) - 1)))

    return jax.tree.map(spec_of, struct)


def make_smoke_batch(rng, cfg: ModelConfig, batch: int = 2,
                     seq: int = 64) -> Dict:
    """Real (materialised) tiny batch for CPU tests."""
    r1, r2, r3 = jax.random.split(rng, 3)
    if cfg.family == "basecaller":
        sig = jax.random.normal(r1, (batch, seq, 1), jnp.float32)
        L = seq // 8
        labels = jax.random.randint(r2, (batch, L), 1, cfg.n_bases)
        lens = jnp.full((batch,), L, jnp.int32)
        return {"signal": sig, "labels": labels, "label_lengths": lens}
    out = {"tokens": jax.random.randint(r1, (batch, seq), 0, cfg.vocab_size),
           "labels": jax.random.randint(r2, (batch, seq), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        Pt = cfg.frontend_tokens
        out["tokens"] = out["tokens"][:, Pt:]
        out["labels"] = out["labels"][:, Pt:]
        out["patch_embeds"] = jax.random.normal(
            r3, (batch, Pt, cfg.d_model), jnp.float32)
    if cfg.family == "audio":
        out["frames"] = jax.random.normal(
            r3, (batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    return out
