#!/usr/bin/env python3
"""Smoke run of the serving main path on one TPU chip.

    python chip_smoke.py

Three phases in this one process (a child could not reach the chip this
process holds), at published widths with random weights from seed 0:

(a) RUBICALL (28 blocks x 344 channels, bf16) through
    ``api.make_serving_engine``: 8 simulated reads of 2,000 bases on 4
    slots, default 1,024-sample chunks, after warmup. The engine's
    greedy calls and window posteriors are compared with the whole-read
    forward + greedy CTC decode, in bf16 and again in float32, where the
    two geometries compute the same function.
(b) The same model serving packed int8 weights: the compiled basecall
    tick must hold one fused ``qconv1d`` kernel for every block whose
    weights and config qualify, and a float32 engine on the fused route
    is compared with one on the XLA dequant route over the same bytes.
(c) qwen1.5-4b (bf16) through ``repro.launch.serve.main``: 4 requests
    on 2 slots, ``--attn-backend auto`` with ``--warmup``; ``auto`` must
    resolve to the fused paged kernels, which must be in the compiled
    ticks. A second pass on ``xla`` gives the greedy tokens and the
    teacher-forced logits they are compared with. Then the same model in
    float32, cut to 4 layers at full width, must give identical greedy
    tokens on both backends.

Each phase prints its warmup (compile) and serving seconds, the work
done, its parity numbers beside their bounds and the peak device memory.
A passing run ends with one JSON line naming the device. Without a TPU,
or when any phase or bound fails, it exits non-zero without that line.
This is a bring-up check, not a benchmark: its times are one run's.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Parity bounds. CPU calibration at full depth and 64 channels (see
# ``weights_only``): a read compared with the NEXT read (the null, what
# a broken path scores) gives call identity 0.68 and mean posterior
# difference 0.016.
# (a) float32 engine vs whole read: the exactness check of the serving
# geometry (CPU: identity 1.0, posterior difference ~1e-7).
MIN_IDENTITY_A32, MAX_POSTERIOR_DIFF_A32 = 0.95, 0.001
# (a) bf16 engine vs whole read: only a rounding witness. The chip's
# first run read identity 0.818 (null 0.668) against the CPU's 0.947:
# at 344 channels the two geometries' bf16 roundings diverge with depth.
# Identity at least 0.75, and the mean posterior difference at most
# three quarters of the same run's null.
MIN_IDENTITY_A, MAX_POSTERIOR_NULL_SHARE_A = 0.75, 0.75
# (b) fused qconv1d vs XLA dequant route, float32: CPU 0.994 / 8.6e-5;
# the bound leaves room for the chip's matmul precision inside the
# kernel, which the CPU cannot show.
MIN_IDENTITY_B, MAX_POSTERIOR_DIFF_B = 0.80, 0.008
# (c) bf16 pallas vs xla logits, teacher-forced on the same tokens.
# Greedy tokens of two bf16 runs of a 40-layer random model fork at
# near-ties: rounding differences grow with depth (a 40 x 512 qwen
# proxy on the CPU: 1 of 4 requests forks at its first token, top-1
# agreement 0.963, mean |dlogit|/std 0.0099; unrelated logits give ~0
# and ~1.1). Greedy identity is held in float32, at 4 layers.
MIN_AGREEMENT_C, MAX_LOGIT_DIFF_C = 0.75, 0.05
WITNESS_LAYERS = 4
N_READS, READ_BASES, BC_SLOTS = 8, 2000, 4
LM_REQUESTS, LM_SLOTS, PROMPT_LEN, NEW_TOKENS = 4, 2, 128, 32


class PhaseFailed(RuntimeError):
    pass


def bound(name: str, value: float, limit: float, *, at_least: bool) -> None:
    ok = value >= limit if at_least else value <= limit
    print(f"[smoke]   {name} = {value!r} ({'>=' if at_least else '<='} "
          f"{limit}) {'ok' if ok else 'FAIL'}")
    if not ok:
        raise PhaseFailed(f"{name} {value!r} outside bound {limit}")


def equal(name: str, value, want) -> None:
    ok = value == want
    print(f"[smoke]   {name} = {value!r} (== {want!r}) "
          f"{'ok' if ok else 'FAIL'}")
    if not ok:
        raise PhaseFailed(f"{name} {value!r} != {want!r}")


class CacheEvents:
    """Counts persistent compile-cache hits through JAX's monitoring."""

    def __init__(self):
        import jax
        self.hits = 0
        self.requests = 0

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests += 1
        jax.monitoring.register_event_listener(on_event)


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def serve(argv):
    from repro.launch.serve import main as serve_main
    t0 = time.perf_counter()
    run = serve_main(argv)
    return run, time.perf_counter() - t0


def compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


# ----------------------------------------------------------- basecaller


def simulated_reads():
    """N_READS normalized squiggles of READ_BASES bases, seed 0."""
    import numpy as np
    from repro.data.squiggle import (SquiggleConfig, normalize, pore_table,
                                     simulate_read)
    rs = np.random.RandomState(0)
    sim, table = SquiggleConfig(noise=0.1, drift=0.0), pore_table()
    return [normalize(simulate_read(rs, sim, table, READ_BASES)[0])
            for _ in range(N_READS)]


def batch_stats_state(cfg, params, signals):
    """BatchNorm running statistics measured on the reads.

    At published depth a freshly initialised RUBICALL's eval-mode
    activations shrink layer by layer until every frame's posteriors
    tie and the calls are empty, which would make parity vacuous. One
    train-mode pass gives each layer's batch statistics (the running
    update is ``0.9 * old + 0.1 * batch``, inverted here), and serving
    with them keeps activations at unit scale, as a trained model's
    running statistics would."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.basecaller import model as bc
    n = min(s.shape[0] for s in signals) // 4096 * 4096
    x = jnp.asarray(np.stack([s[:n] for s in signals])[..., None])
    old = bc.init_state(cfg)
    new = jax.jit(lambda p, s, x: bc.forward(p, s, x, cfg,
                                             train=True)[1])(params, old, x)
    return jax.tree.map(lambda a, b: (b - 0.9 * a) / 0.1, old, new)


def serve_reads(cfg, params, state, signals):
    """Serve the reads through ``api.make_serving_engine`` after warmup.
    Returns (engine, per-read calls, per-read core posteriors of the
    engine's compiled tick, warmup s, serving s)."""
    from repro.models import api
    from repro.serving.engine import Request
    engine = api.make_serving_engine(params, cfg, n_slots=BC_SLOTS,
                                     chunk_samples=1024, model_state=state)
    t0 = time.perf_counter()
    engine.warmup()
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i, s in enumerate(signals):
        engine.submit(Request(rid=i, signal=s))
    done = engine.run()
    run_s = time.perf_counter() - t0
    post = [windowed(engine.runner, s) for s in signals]
    return engine, [done[i].out_tokens for i in range(len(signals))], \
        post, warm, run_s


def whole_read(cfg, params, state):
    """Whole-read forward as a function of one read: reads are padded to
    one length and masked past their end (the forward's ``bounds``), so
    every read shares one compiled program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.basecaller import model as bc
    fwd = jax.jit(lambda p, x, n: bc.forward(p, state, x, cfg, train=False,
                                             bounds=(0, n))[0])
    stride = bc.total_stride(cfg)

    def run(signals):
        S = max(s.shape[0] for s in signals)
        S = -(-S // 4096) * 4096
        out = []
        for s in signals:
            x = np.zeros((1, S, 1), np.float32)
            x[0, :s.shape[0], 0] = s
            lp = np.asarray(fwd(params, jnp.asarray(x),
                                jnp.int32(s.shape[0])))
            out.append(lp[0, :-(-s.shape[0] // stride)])
        return out
    return run


def windowed(runner, signal):
    """Posteriors of one read through the runner's compiled tick, its
    windows (the runner's own chunking) filling the tick rows, core
    frames concatenated. The tick returns each row's core frames."""
    import numpy as np
    B, W = runner.n_slots, runner.core + 2 * runner.halo
    wins = runner._bc.chunk_windows(signal, runner.core, runner.halo,
                                    runner.stride)
    frames = []
    for k0 in range(0, len(wins), B):
        rows = wins[k0:k0 + B]
        x = np.zeros((B, W, 1), np.float32)
        start = np.zeros((B,), np.int32)
        read_len = np.zeros((B,), np.int32)
        for i, (win, _, _) in enumerate(rows):
            x[i] = win
            start[i] = (k0 + i) * runner.core - runner.halo
            read_len[i] = signal.shape[0]
        lp = np.asarray(runner._fwd(runner.params, runner.state, x, start,
                                    read_len))
        frames += [lp[i, :nf] for i, (_, nf, _) in enumerate(rows)]
    return np.concatenate(frames)


def kernel_sites(runner) -> int:
    """Pallas kernel calls in the runner's compiled basecall tick."""
    import numpy as np
    B, W = runner.n_slots, runner.core + 2 * runner.halo
    z = np.zeros((B,), np.int32)
    return compiled_text(runner._fwd, runner.params, runner.state,
                         np.zeros((B, W, 1), np.float32), z, z).count(
        'custom_call_target="tpu_custom_call"')


def calls(logp):
    from repro.models.basecaller.ctc import greedy_decode
    return [int(v) for v in greedy_decode(logp[None])[0]]


def mean_identity(a_calls, b_calls) -> float:
    import numpy as np
    from repro.data.align import identity
    return float(np.mean([identity(np.asarray(a), np.asarray(b))
                          for a, b in zip(a_calls, b_calls)]))


def posterior_diff(a, b):
    import numpy as np
    d = np.concatenate([np.abs(np.exp(x) - np.exp(y)).ravel()
                        for x, y in zip(a, b)])
    return float(d.max()), float(d.mean())


def weights_only(cfg):
    """RUBICALL's per-layer weight bit-widths with activations left in
    the compute dtype.

    Its published policy also fake-quantizes activations per tensor (8
    bits, 4 in blocks 20-27), with the scale taken over whatever tensor
    the forward sees: a tick of windows and a whole read get different
    grids. With random weights that makes the output chaotic — on the
    CPU at 28 blocks x 64 channels, engine-vs-whole-read call identity
    (0.71) and mean posterior difference (0.012) equal those between
    two different reads — so no output comparison could tell a broken
    path from a working one. Without activation quantization both
    geometries compute the same function."""
    q = cfg.quant
    quant = dataclasses.replace(
        q, act_bits=0, overrides=tuple((p, (w, 0)) for p, (w, _)
                                       in q.overrides))
    return dataclasses.replace(cfg, quant=quant)


def phase_rubicall(arch: str = "rubicall"):
    """(a) engine greedy calls vs the whole-read basecall, bf16 and
    float32. Returns the config, weights, BN state and reads for b."""
    import jax
    from repro.config import get_config
    from repro.models import api
    print(f"[smoke] phase a: {arch} serving (weight bit-widths, bf16 "
          f"activations), {N_READS} reads of {READ_BASES} bases on "
          f"{BC_SLOTS} slots")
    cfg = weights_only(get_config(arch))
    params = api.init_params(jax.random.key(0), cfg)
    signals = simulated_reads()
    state = batch_stats_state(cfg, params, signals)
    _, served, post, warm, run_s = serve_reads(cfg, params, state, signals)
    ref = whole_read(cfg, params, state)(signals)
    ref_calls = [calls(x) for x in ref]
    exact = sum(a == b for a, b in zip(served, ref_calls))
    pmax, pmean = posterior_diff(post, ref)
    ident = mean_identity(served, ref_calls)
    # what a broken path would score: each read against the next one
    nxt = ref[1:] + ref[:1]
    n = [min(len(a), len(b)) for a, b in zip(post, nxt)]
    null_diff = posterior_diff([a[:k] for a, k in zip(post, n)],
                               [b[:k] for b, k in zip(nxt, n)])[1]
    null_ident = mean_identity(served, ref_calls[1:] + ref_calls[:1])
    print(f"[smoke]   warmup {warm:.2f}s | serving {run_s:.2f}s | "
          f"{len(served)} reads, {sum(map(len, served))} bases "
          f"(whole read: {sum(map(len, ref_calls))}) | {exact}/"
          f"{len(served)} reads bit-identical | peak {peak_bytes()} B")
    print(f"[smoke]   bf16 engine vs whole read: identity {ident!r}, "
          f"posterior diff mean {pmean!r} max {pmax!r} | null (read i "
          f"vs read i+1): identity {null_ident!r}, posterior diff mean "
          f"{null_diff!r}")
    # the same engine in float32 at full matmul precision, where the
    # windows and the whole read compute the same function
    f32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        _, served32, post32, warm32, run32 = serve_reads(f32, params, state,
                                                          signals)
        ref32 = whole_read(f32, params, state)(signals)
    ident32 = mean_identity(served32, [calls(x) for x in ref32])
    pmax32, pmean32 = posterior_diff(post32, ref32)
    print(f"[smoke]   float32 engine: warmup {warm32:.2f}s | serving "
          f"{run32:.2f}s | vs whole read: identity {ident32!r}, "
          f"posterior diff mean {pmean32!r} max {pmax32!r}")
    bound("bases called, engine", sum(map(len, served)), 1,
          at_least=True)
    bound("read identity, float32 engine vs whole read", ident32,
          MIN_IDENTITY_A32, at_least=True)
    bound("mean posterior diff, float32 engine vs whole read", pmean32,
          MAX_POSTERIOR_DIFF_A32, at_least=False)
    bound("read identity, bf16 engine vs whole read", ident,
          MIN_IDENTITY_A, at_least=True)
    bound("mean posterior diff, bf16 engine vs whole read", pmean,
          MAX_POSTERIOR_NULL_SHARE_A * null_diff, at_least=False)
    return cfg, params, state, signals


def xla_route(cfg):
    """The same config with the fused sep-conv route closed: ``sep_conv``
    takes ``qconv1d`` only where the pointwise layer carries 4/8-bit
    weights, so a leading 16-bit ``/pw`` override sends every block
    through the XLA dequant-on-read convs on the same packed bytes
    (activation precision keys on ``/act`` and is unchanged)."""
    quant = dataclasses.replace(
        cfg.quant, overrides=(("/pw", (16, 16)),) + cfg.quant.overrides)
    return dataclasses.replace(cfg, quant=quant)


def fused_sites(cfg, packed) -> int:
    """Sep-convs that take the fused ``qconv1d`` route in eval mode: the
    conditions of ``blocks.sep_conv`` read off the config and the packed
    weights (both int8-packed, stride 1, no dilation, square pointwise,
    4/8-bit pointwise in the config's policy)."""
    from repro.core.quant.policy import PackedTensor
    n = 0
    for i in range(cfg.n_blocks):
        for j in range(cfg.repeats[i]):
            p = packed[f"block{i:02d}"][f"rep{j}"]
            dw, pw = p["dw"], p["pw"]
            stride = cfg.strides[i] if j == 0 else 1
            n += bool(isinstance(dw, PackedTensor)
                      and isinstance(pw, PackedTensor) and stride == 1
                      and not cfg.name.startswith("causalcall")
                      and dw.bits == 8 and pw.bits == 8
                      and pw.orig_shape[-2] == pw.orig_shape[-1]
                      and cfg.quant.bits_for(
                          f"block{i:02d}/rep{j}/pw")[0] in (4, 8))
    return n


def phase_rubicall_int8(cfg, params, state, signals,
                        require_kernel: bool = True) -> None:
    """(b) packed int8 weights: one qconv1d per qualifying block in the
    compiled tick; float32 fused route vs XLA dequant route."""
    import jax
    from repro.launch.serve import quantize_for_serving
    print(f"[smoke] phase b: {cfg.name} serving packed int8 weights")
    packed = quantize_for_serving(params, 8)
    engine, served, _, warm, run_s = serve_reads(cfg, packed, state,
                                                 signals)
    want = fused_sites(cfg, packed)
    got = kernel_sites(engine.runner)
    print(f"[smoke]   warmup {warm:.2f}s | serving {run_s:.2f}s | "
          f"{len(served)} reads, {sum(map(len, served))} bases | compiled "
          f"tick: {got} tpu_custom_call sites, {want} blocks qualify | "
          f"peak {peak_bytes()} B")
    del engine
    # the kernel accumulates in float32 and rounds once per block, the
    # bf16 XLA route rounds weights and every conv output: compare the
    # two routes in float32 at full matmul precision, where they
    # compute the same function on the same packed bytes
    f32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision("highest"):
        e_fused, c_fused, p_fused, _, _ = serve_reads(f32, packed, state,
                                                      signals)
        e_xla, c_xla, p_xla, _, _ = serve_reads(xla_route(f32), packed,
                                                state, signals)
        sites = kernel_sites(e_fused.runner), kernel_sites(e_xla.runner)
    pmax, pmean = posterior_diff(p_fused, p_xla)
    ident = mean_identity(c_fused, c_xla)
    print(f"[smoke]   float32 engines, fused vs xla route: identity "
          f"{ident!r}, posterior diff mean {pmean!r} max {pmax!r} | "
          f"tpu_custom_call sites {sites[0]} vs {sites[1]}")
    bound("bases called, engine", sum(map(len, served)), 1,
          at_least=True)
    if require_kernel:
        bound("qualifying blocks", want, 1, at_least=True)
        equal("qconv1d sites in the compiled tick", got, want)
        equal("kernel sites in the xla route's tick", sites[1], 0)
    bound("read identity, fused vs xla (float32)", ident, MIN_IDENTITY_B,
          at_least=True)
    bound("mean posterior diff, fused vs xla (float32)", pmean,
          MAX_POSTERIOR_DIFF_B, at_least=False)


# ------------------------------------------------------------------ LM


def lm_argv(arch: str, backend: str, warmup: bool):
    return ["--arch", arch, "--requests", str(LM_REQUESTS),
            "--slots", str(LM_SLOTS), "--prompt-len", str(PROMPT_LEN),
            "--tokens", str(NEW_TOKENS), "--rate", "1e6",
            "--attn-backend", backend] + (["--warmup"] if warmup else [])


def forced_logits(cfg, params, backend, prompts, gens):
    """Float32 logits at every generated position, teacher-forced on
    ``gens`` (each request's prompt goes in 16-token chunks, then one
    token per decode step), one request at a time in a one-slot pool of
    the engine's cache layout. Both backends read the same tokens, so
    their logits differ by arithmetic alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.lm import transformer as tfm
    from repro.serving.cache import CachePool
    step = jax.jit(lambda p, c, tok, t, last, tb: tfm.decode_step_slots(
        p, c, tok, t, cfg, logits_at=last, tables=tb,
        attn_backend=backend))
    out = []
    for prompt, gen in zip(prompts, gens):
        pool = CachePool(cfg, 1, PROMPT_LEN + NEW_TOKENS,
                         jnp.dtype(cfg.dtype), block_len=16,
                         attn_backend=backend)
        seq = list(prompt) + list(gen[:-1])
        pool.alloc(0, len(seq))
        caches, tables, rows = pool.caches, pool.device_tables(), []
        spans = [(i, min(i + 16, len(prompt)))
                 for i in range(0, len(prompt), 16)]
        spans += [(i, i + 1) for i in range(len(prompt), len(seq))]
        for a, b in spans:
            w = 16 if b - a > 1 else 1
            tok = np.zeros((1, w), np.int32)
            t = np.full((1, w), -1, np.int32)
            tok[0, :b - a] = seq[a:b]
            t[0, :b - a] = np.arange(a, b)
            logits, caches = step(params, caches, tok, t,
                                  np.array([b - a - 1], np.int32), tables)
            if b >= len(prompt):
                rows.append(np.asarray(logits[0, 0], np.float32))
        out.append(np.stack(rows))
    return out


def phase_lm(arch: str = "qwen1.5-4b", backend: str = "auto",
             require_kernel: bool = True) -> None:
    """(c) fused paged attention on the chip, parity vs xla."""
    import numpy as np
    print(f"[smoke] phase c: {arch} serving, attn backend {backend}")
    run, wall = serve(lm_argv(arch, backend, warmup=True))
    runner = run.engine.runner
    if require_kernel and runner.attn_backend != "pallas":
        raise PhaseFailed(f"{backend} resolved to {runner.attn_backend!r},"
                          f" not pallas, on one chip")
    B = runner.n_slots
    z = np.zeros((B,), np.int32)
    tables = runner.pool.device_tables()
    sites = {}
    for key in (("decode", 1, "greedy"),
                ("mixed", runner.chunk_tokens, "greedy")):
        w = key[1]
        args = [runner.params, runner.pool.caches, np.zeros((B, w), np.int32),
                np.full((B, w), -1, np.int32), z, runner._prev_tokens]
        if key[0] == "mixed":
            args += [z, z]
        text = compiled_text(runner.plans.fn(key), *args, tables, None)
        sites[f"{key[0]}{w}"] = text.count(
            'custom_call_target="tpu_custom_call"')
    rids = sorted(run.done)
    prompts = [run.done[r].prompt for r in rids]
    max_new = [run.done[r].sampling.max_new_tokens for r in rids]
    fused = [run.done[r].out_tokens for r in rids]
    print(f"[smoke]   backend {runner.attn_backend} | compiled ticks: "
          f"{sites} tpu_custom_call sites | warmup {run.warmup_s:.2f}s | "
          f"wall {wall:.2f}s | {len(fused)} requests, "
          f"{sum(map(len, fused))} tokens | peak {peak_bytes()} B")
    if require_kernel and not all(sites.values()):
        raise PhaseFailed("a compiled tick lacks the fused paged kernels")
    del run, runner, tables
    gc.collect()
    ref_run, ref_wall = serve(lm_argv(arch, "xla", warmup=False))
    ref = [ref_run.done[r].out_tokens for r in rids]
    same = sum(a == b for a, b in zip(fused, ref))
    first = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b), None)
             for x, y in zip(fused, ref)]
    print(f"[smoke]   xla pass wall {ref_wall:.2f}s | {same}/{len(fused)} "
          f"requests with identical greedy tokens (bf16) | first "
          f"divergence per request {first}")
    cfg, params = ref_run.engine.runner.cfg, ref_run.engine.runner.params
    del ref_run
    gc.collect()
    lp = forced_logits(cfg, params, "pallas", prompts, fused)
    lx = forced_logits(cfg, params, "xla", prompts, fused)
    del params
    gc.collect()
    lp, lx = np.concatenate(lp), np.concatenate(lx)
    agree = float(np.mean(lp.argmax(-1) == lx.argmax(-1)))
    rel = np.abs(lp - lx) / lx.std(axis=-1, keepdims=True)
    print(f"[smoke]   teacher-forced over {lp.shape[0]} positions: top-1 "
          f"agreement {agree!r} | |dlogit|/std mean {float(rel.mean())!r} "
          f"max {float(rel.max())!r}")
    witness = float32_witness(arch, prompts, max_new)
    same32 = sum(a == b for a, b in zip(*witness))
    print(f"[smoke]   float32, {WITNESS_LAYERS} layers: {same32}/"
          f"{len(prompts)} requests with identical greedy tokens, "
          f"{sum(map(len, witness[0]))} tokens")
    bound("requests served", len(fused), LM_REQUESTS, at_least=True)
    bound("teacher-forced top-1 agreement, pallas vs xla", agree,
          MIN_AGREEMENT_C, at_least=True)
    bound("teacher-forced mean |dlogit|/std, pallas vs xla",
          float(rel.mean()), MAX_LOGIT_DIFF_C, at_least=False)
    equal("requests with identical greedy tokens, float32 pallas vs xla",
          same32, len(prompts))


def float32_witness(arch, prompts, max_new):
    """Greedy tokens of ``arch`` in float32 at full matmul precision,
    cut to WITNESS_LAYERS layers at full width, served through
    ``api.make_serving_engine`` on each attention backend. Returns the
    (pallas, xla) token lists."""
    import jax
    import jax.numpy as jnp
    from repro.config import get_config
    from repro.models import api
    from repro.serving.engine import Request
    from repro.serving.sampling import SamplingParams
    cfg = dataclasses.replace(get_config(arch), n_layers=WITNESS_LAYERS,
                              dtype="float32")
    out = []
    with jax.default_matmul_precision("highest"):
        params = api.init_params(jax.random.key(0), cfg)
        for backend in ("pallas", "xla"):
            engine = api.make_serving_engine(
                params, cfg, n_slots=LM_SLOTS,
                cache_len=PROMPT_LEN + NEW_TOKENS,
                cache_dtype=jnp.float32, attn_backend=backend)
            for i, (p, m) in enumerate(zip(prompts, max_new)):
                engine.submit(Request(rid=i, prompt=p, sampling=(
                    SamplingParams(max_new_tokens=m))))
            done = engine.run()
            out.append([done[i].out_tokens for i in range(len(prompts))])
            del engine
    return out


def main() -> int:
    try:
        import repro  # noqa: F401  — the package this script drives
    except ImportError:
        print("chip_smoke: the repro package (src/repro) is not beside "
              "this script", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this check "
              f"runs on the chip only", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    events = CacheEvents()
    print(f"[smoke] device {dev.platform} {dev.device_kind} "
          f"x{len(devices)} | compile cache {cache_dir}")
    t0 = time.perf_counter()
    try:
        phase_rubicall_int8(*phase_rubicall())
        phase_lm()
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s | "
          f"compile cache: {events.hits} hits of {events.requests} "
          f"requests")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
