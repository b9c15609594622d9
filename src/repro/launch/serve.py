"""Serving launcher: continuous-batching engine (default) or the legacy
static-batch loop, with optional weight-only quantization (the
RUBICALL-MP idea applied to LM serving).

Engine path (default)
---------------------
``python -m repro.launch.serve --arch qwen1.5-4b --smoke --requests 8``
replays a synthetic Poisson request stream (``--rate`` requests/s,
variable prompt/output lengths) into :class:`repro.serving.ServingEngine`:
requests queue on the host, a fixed pool of ``--slots`` decode slots
admits them as capacity frees up, and every tick runs ONE co-batched
jitted step in which prompts prefill in ``--prefill-chunk`` token
chunks ALONGSIDE the running slots' decode tokens (mixed ticks; JIT
shapes never change). ``--max-prefill-tokens`` bounds the prefill
payload a single tick may carry, so admission bursts cannot inflate
decode latency; ``--split-tick`` restores the legacy scheduler
(prefill steps stall decode) as the measured baseline. The run ends
with a metrics summary (tokens/s, TTFT p50/p95/p99, decode-interval
jitter, queue depth).

The engine dispatches through the serving RUNNER REGISTRY
(``repro.serving.runner``), so three workload families share one
scheduler:

- token-only LMs — attention (qwen, llama3, ...), MoE (granite), SSM
  (``--arch mamba2-130m``), hybrid (``--arch hymba-1.5b``), MLA/MoE
  (``--arch deepseek-v3-671b``);
- audio enc-dec (``--arch whisper-tiny``) — each request carries stub
  log-mel frames; the encoder runs once at admission and its K/V is
  staged per slot (EncoderPrefixRunner);
- the paper's own basecallers (``--arch bonito`` / ``rubicall`` /
  ``causalcall``) — requests are simulated squiggle READS that stream
  through halo-padded chunks with incremental CTC merge
  (BasecallerRunner; ``--chunk-samples``/``--beam``); the summary
  reports reads/s and bases/s.

Streaming + read-until (basecaller archs only)
----------------------------------------------
``--stream`` switches the basecaller traffic to LIVE reads: Poisson
read starts, then each read's samples arrive over wall-clock time at
the pore sample rate and are ``append()``-ed to a
:class:`repro.serving.stream.StreamingRequest`; bases emit
incrementally as their receptive field is covered (``--qos latency``)
or per fully-covered window (``--qos accuracy``, bit-identical to the
offline chunked path). ``--read-until`` trains the start-of-read
classifier at launch and ejects off-target reads (a ``1 -
--target-frac`` fraction of the stream is normalized white noise)
after ``--eject-after-chunks`` windows; ejected reads free their slot,
keep their bases-so-far, and the generator stops appending — the run
report prints ejections, samples saved, and emit-latency p50/p99.

Per-request sampling (``repro.serving.sampling.SamplingParams``):
``--temperature``/``--top-k``/``--top-p``/``--seed`` configure sampled
decode; ``--sampled-frac`` mixes greedy and sampled requests in one
stream (they share every decode batch — one jitted program), and the
run header reports the resulting sampler mix. Sampled tokens are
deterministic in (seed, rid, step), so reruns reproduce exactly.
``--eos-id`` marks a stop token on every LM request.

KV lives in a PAGED block pool (``repro.serving.cache``): ``--block-len``
sets the arena block size and ``--n-blocks`` the arena depth per layer
group — leave it 0 for full backing, or set it below
``slots * ceil(cache_len/block_len)`` to oversubscribe decode slots
against KV bytes (short requests only pay for blocks they touch; the
engine preempts the youngest request if the pool runs dry). The run
summary reports pool utilization and preemptions. ``--warmup`` pre-compiles
every bucketed tick plan at launch (the run report's ``retraces=``
line should then stay 0); ``--async-dispatch`` pipelines the tick
(dispatch tick N, harvest tick N-1 — token-identical, one-tick lag);
``--max-queue``/``--queue-timeout`` bound admission, shedding overflow
and expired waiters with explicit ``rejected`` statuses (see
``repro.serving`` "Dispatch pipeline, buckets & backpressure").
``--attn-backend``
picks the decode-attention read path over that pool: ``pallas`` fuses
decode ticks directly against the block arena (no per-layer logical-view
gather), ``xla`` is the reference, ``auto`` resolves per hardware; the
resolved backend is reported in the run summary. ``--history-limit``
bounds host-side per-request bookkeeping so the process can serve
indefinitely at flat memory.

``--wbits 8|4`` serves from packed int8/int4 weights (dequant-on-read —
halving/quartering weight HBM traffic; the Pallas ``qmatmul`` kernel is
the TPU twin of this XLA path).

Static path (``--static``)
--------------------------
The original single-shot loop: one fixed batch, prefill, then a Python
greedy-decode loop. Kept as the baseline the engine is benchmarked
against (benchmarks/bench_serving.py).
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import QuantPolicy, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.models.lm import transformer as tfm


class ServeRun(NamedTuple):
    """What an engine run leaves behind for an in-process caller."""
    engine: Any
    done: Dict[int, Any]          # rid -> completed request
    warmup_s: float               # 0.0 without --warmup


def quantize_for_serving(params, wbits: int):
    from repro.core.quant.policy import quantize_tree
    policy = QuantPolicy(weight_bits=wbits, act_bits=0)
    return quantize_tree(params, policy)


def dequantize_tree(params, dtype):
    """Up-front dequant (the static path's XLA fallback)."""
    from repro.core.quant.policy import PackedTensor, dequantize
    return jax.tree.map(
        lambda l: dequantize(l, dtype) if isinstance(l, PackedTensor) else l,
        params, is_leaf=lambda l: isinstance(l, PackedTensor))


def request_samples(args, i: int) -> bool:
    """Deterministic Bresenham mix: request ``i`` samples iff the
    running count of sampled requests crosses an integer at i — spreads
    ``--sampled-frac`` evenly through the stream (so greedy and sampled
    rows genuinely share decode batches)."""
    frac = min(max(args.sampled_frac, 0.0), 1.0)
    if args.temperature <= 0 or frac <= 0:
        return False
    return int((i + 1) * frac) > int(i * frac)


def build_request_stream(cfg, args, seed: int = 0):
    """Synthetic Poisson arrivals. LM archs get variable prompt/output
    lengths (+ audio frames for enc-dec); basecallers get simulated
    squiggle reads."""
    from repro.serving.engine import Request
    from repro.serving.sampling import SamplingParams
    rs = np.random.RandomState(seed)
    arrivals = np.cumsum(rs.exponential(1.0 / args.rate, size=args.requests))
    eos = args.eos_id if args.eos_id >= 0 else None
    reqs = []
    if cfg.family == "basecaller":
        from repro.data.squiggle import (SquiggleConfig, normalize,
                                         pore_table, simulate_read)
        sim = SquiggleConfig(noise=0.1, drift=0.0)
        table = pore_table()
        for i in range(args.requests):
            n_bases = int(rs.randint(max(args.read_bases // 2, 8),
                                     args.read_bases + 1))
            sig, _ = simulate_read(rs, sim, table, n_bases)
            reqs.append(Request(rid=i, signal=normalize(sig),
                                arrival_time=float(arrivals[i])))
        return reqs
    frames_needed = cfg.family == "audio"
    for i in range(args.requests):
        plen = int(rs.randint(max(args.prompt_len // 2, 1),
                              args.prompt_len + 1))
        mnew = int(rs.randint(max(args.tokens // 4, 1), args.tokens + 1))
        prompt = rs.randint(1, cfg.vocab_size, size=plen).tolist()
        if request_samples(args, i):
            sp = SamplingParams(max_new_tokens=mnew, eos_id=eos,
                                temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p,
                                seed=args.seed + i)
        else:
            sp = SamplingParams(max_new_tokens=mnew, eos_id=eos)
        frames = (rs.randn(cfg.frontend_tokens, cfg.d_model)
                  .astype(np.float32) if frames_needed else None)
        reqs.append(Request(rid=i, prompt=prompt, sampling=sp,
                            frames=frames, arrival_time=float(arrivals[i])))
    return reqs


def resolved_backend_label(engine) -> str:
    """Human-readable resolved decode-attention backend for the run
    summary, e.g. ``pallas (interpret)`` on a CPU forced-pallas run."""
    from repro.kernels.ops import interpret_default
    backend = getattr(engine.runner, "attn_backend", None)
    if backend is None:
        return "n/a (no KV decode path)"      # basecaller runner
    if backend == "pallas" and interpret_default():
        return "pallas (interpret)"
    return backend


def print_dispatch_report(s, args) -> None:
    """Dispatch-pipeline section of the end-of-run report: plan-cache
    health (the ``retraces=`` line is the mid-traffic-compile gate),
    tick-latency percentiles, idle fast-path skips, and backpressure."""
    print(f"[serve] plans: {s['plans']:.0f} registered, "
          f"{s['plans_warmed']:.0f} warmed | bucket hits "
          f"{s['bucket_hits']:.0f} misses {s['bucket_misses']:.0f} | "
          f"retraces={s['retraces']:.0f}")
    print(f"[serve] ticks ({'async pipelined' if args.async_dispatch else 'sync'}): "
          f"p50 {s['tick_latency_p50_s']*1e3:.2f}ms "
          f"p99 {s['tick_latency_p99_s']*1e3:.2f}ms | "
          f"idle skipped {s['idle_ticks']:.0f} | "
          f"queue hwm {s['queue_depth_hwm']:.0f}"
          + (f" (max {args.max_queue})" if args.max_queue else "")
          + f" | rejected {s['rejections']:.0f}")
    if args.warmup and s["retraces"] > 0:
        raise SystemExit(
            f"[serve] error: {s['retraces']:.0f} mid-traffic retrace(s) "
            f"after --warmup — traffic produced an argument signature "
            f"warmup never compiled (CI gates this at zero)")


PORE_HZ = 4000.0          # nanopore sample rate the streamed traffic mimics


def make_read_until(cfg, args):
    """Train the start-of-read classifier on synthetic windows matching
    the engine's window geometry and wrap it in a ReadUntil policy."""
    from repro.models.basecaller import classifier as rc
    from repro.models.basecaller import model as bc
    from repro.serving.stream import ReadUntil
    stride = bc.total_stride(cfg)
    halo = bc.chunk_halo(cfg)
    core = max(-(-args.chunk_samples // stride), 1) * stride
    window = core + 2 * halo
    rs = np.random.RandomState(args.seed + 77)
    x, y = rc.make_training_set(rs, window, n_per_class=32)
    cp = rc.init_params(jax.random.key(args.seed + 1))
    cp, loss = rc.fit(cp, x, y, steps=150, lr=0.1)
    print(f"[serve] read-until: classifier trained on {x.shape[0]} "
          f"windows of {window} samples (bce {loss:.3f}), ejecting after "
          f"{args.eject_after_chunks} chunks")
    return ReadUntil(params=cp, eject_after_chunks=args.eject_after_chunks)


def build_streamed_reads(cfg, args, seed: int = 0):
    """Streamed basecaller traffic: Poisson read starts; each entry is
    ``(start_time, on_target, full_signal)`` and the run loop appends
    the signal in wall-clock order at PORE_HZ. With --read-until, a
    ``1 - target_frac`` fraction are off-target white-noise reads."""
    from repro.data.squiggle import (SquiggleConfig, normalize, pore_table,
                                     simulate_read)
    rs = np.random.RandomState(seed)
    starts = np.cumsum(rs.exponential(1.0 / args.rate, size=args.requests))
    sim = SquiggleConfig(noise=0.1, drift=0.0)
    table = pore_table()
    target_frac = args.target_frac if args.read_until else 1.0
    reads = []
    for i in range(args.requests):
        n_bases = int(rs.randint(max(args.read_bases // 2, 8),
                                 args.read_bases + 1))
        on_target = bool(rs.rand() < target_frac)
        if on_target:
            sig, _ = simulate_read(rs, sim, table, n_bases)
            sig = normalize(sig)
        else:
            sig = normalize(rs.randn(n_bases * 9).astype(np.float32))
        reads.append((float(starts[i]), on_target, sig))
    return reads


def run_streamed(engine, cfg, args) -> Dict[int, Any]:
    """Drive the engine from live StreamingRequests: submit each read at
    its Poisson start, then append samples as wall-clock time covers
    them (PORE_HZ per pore). Ejected reads stop appending — the forgone
    tail is booked as samples saved."""
    from repro.serving.stream import StreamingRequest
    reads = build_streamed_reads(cfg, args, seed=args.seed)
    on_target = {i: tgt for i, (_, tgt, _) in enumerate(reads)}
    live = {}                       # rid -> [req, signal, appended_ptr]
    t0 = time.perf_counter()
    i = 0
    while i < len(reads) or live:
        now = time.perf_counter() - t0
        while i < len(reads) and reads[i][0] <= now:
            req = StreamingRequest(rid=i, arrival_time=reads[i][0])
            engine.submit(req)
            live[i] = [req, reads[i][2], 0]
            i += 1
        for rid in list(live):
            req, sig, ptr = live[rid]
            if req.done:
                if req.ejected and ptr < sig.shape[0]:
                    engine.metrics.record_samples_saved(sig.shape[0] - ptr)
                del live[rid]
                continue
            due = min(int((now - req.arrival_time) * PORE_HZ), sig.shape[0])
            if due > ptr:
                req.append(sig[ptr:due])
                live[rid][2] = due
            elif ptr >= sig.shape[0] and not req.stream_finished:
                req.finish()
        if engine.busy:
            engine.step()
        else:
            time.sleep(0.002)
    done = engine.drain_completed()
    ejected = [r for r in done.values() if r.ejected]
    n_off = sum(not on_target[rid] for rid in done)
    off_ejected = sum(not on_target[r.rid] for r in ejected)
    total_samples = sum(s.shape[0] for _, _, s in reads)
    s = engine.metrics.summary()
    print(f"[serve] streamed: {len(done)} reads "
          f"({n_off} off-target), qos={args.qos}, "
          f"emit latency p50 {s['emit_latency_p50_s']*1e3:.1f}ms "
          f"p99 {s['emit_latency_p99_s']*1e3:.1f}ms "
          f"({s['emit_events']} emissions)")
    if args.read_until:
        print(f"[serve] read-until: {s['ejections']:.0f} ejections "
              f"({off_ejected}/{n_off} off-target rejected, "
              f"{len(ejected) - off_ejected} on-target lost) | "
              f"samples saved {s['samples_saved']:.0f}"
              f"/{total_samples} "
              f"({s['samples_saved']/max(total_samples,1)*100:.0f}%) | "
              f"basecalled {s['ejected_consumed_samples']:.0f} samples "
              f"on ejected reads")
    print_dispatch_report(s, args)
    if done:
        first = done[min(done)]
        print(f"[serve] sample ({first.status}):", first.out_tokens[:16])
    return done


def resolve_quant_policy(cfg, args):
    """Admission-time validation of ``--cache-dtype``/``--quant-policy``:
    an invalid mode or an override naming a group this arch does not
    have is rejected HERE with a clear error, before any device memory
    is allocated (fp8 on an unsupported platform is NOT an error — the
    pool warns and falls back to bf16). Returns the policy spec to hand
    the runner, or None for the config-dtype default."""
    spec = args.quant_policy or args.cache_dtype or None
    if spec is None:
        return None
    if cfg.family == "basecaller":
        raise SystemExit(
            f"[serve] error: --cache-dtype/--quant-policy configure the "
            f"paged KV arena; basecaller arch {cfg.name!r} has no KV "
            f"cache (reads are not autoregressive)")
    from repro.models.lm import transformer as tfm
    from repro.serving.cache import CacheQuantPolicy
    try:
        policy = CacheQuantPolicy.parse(spec)
        policy.validate_groups([g for g, _, _ in tfm.group_names(cfg)])
    except ValueError as e:
        raise SystemExit(f"[serve] error: invalid cache quantization "
                         f"spec {spec!r}: {e}")
    return spec


def run_engine(params, cfg, args) -> ServeRun:
    if (args.stream or args.read_until) and cfg.family != "basecaller":
        raise SystemExit(
            f"[serve] error: --stream/--read-until serve live squiggle "
            f"reads; arch {cfg.name!r} is not a basecaller")
    quant_policy = resolve_quant_policy(cfg, args)
    runner_kw = {"attn_backend": args.attn_backend,
                 "quant_policy": quant_policy}
    if cfg.family == "basecaller":
        runner_kw = dict(chunk_samples=args.chunk_samples, beam=args.beam,
                         qos=args.qos)
        if args.read_until:
            runner_kw["read_until"] = make_read_until(cfg, args)
    engine = api.make_serving_engine(
        params, cfg, n_slots=args.slots, cache_len=args.cache_len,
        prefill_chunk=args.prefill_chunk,
        max_prefill_tokens=args.max_prefill_tokens,
        co_batch=not args.split_tick,
        cache_dtype=jnp.dtype(cfg.dtype),
        block_len=args.block_len, n_blocks=args.n_blocks,
        history_limit=args.history_limit or None,
        async_dispatch=args.async_dispatch, max_queue=args.max_queue,
        queue_timeout_s=args.queue_timeout, **runner_kw)
    basecall = cfg.family == "basecaller"
    warmup_s = 0.0
    if args.warmup:
        t0 = time.perf_counter()
        n = engine.warmup()
        warmup_s = time.perf_counter() - t0
        print(f"[serve] warmup: {n} tick plans pre-compiled in "
              f"{warmup_s:.2f}s")
    if args.stream:
        print(f"[serve] engine ({type(engine.runner).__name__}): "
              f"{args.requests} LIVE reads (rate {args.rate}/s, "
              f"{PORE_HZ:.0f} samples/s per pore), {args.slots} slots, "
              f"chunk {engine.runner.core} samples (halo "
              f"{engine.runner.halo}), qos={args.qos}")
        done = run_streamed(engine, cfg, args)
        return ServeRun(engine, done, warmup_s)
    pending = build_request_stream(cfg, args)
    print(f"[serve] engine ({type(engine.runner).__name__}): "
          f"{args.requests} requests over "
          f"{pending[-1].arrival_time:.2f}s (rate {args.rate}/s), "
          f"{args.slots} slots"
          + (f", chunk {engine.runner.core} samples (halo "
             f"{engine.runner.halo})" if basecall
             else f", chunk {args.prefill_chunk}"))
    if basecall:
        print(f"[serve] basecalling: "
              f"{'prefix-beam ' + str(args.beam) if args.beam else 'greedy'}"
              f" CTC merge, stride {engine.runner.stride}")
    else:
        n_sampled = sum(r.sampling.temperature > 0 for r in pending)
        mix = (f"{len(pending) - n_sampled} greedy, {n_sampled} sampled"
               + (f" (T={args.temperature}, top_k={args.top_k}, "
                  f"top_p={args.top_p}, seeds {args.seed}+rid)"
                  if n_sampled else ""))
        print(f"[serve] sampler mix: {mix}")
        pool = engine.pool
        by = pool.nbytes_by_class()
        print(f"[serve] paged pool: block_len {pool.block_len}, "
              f"{pool.block_stats()['blocks_total']} blocks "
              f"({pool.nbytes()/2**20:.2f} MiB cache = "
              f"{by['arena']/2**20:.2f} arena + "
              f"{by['scales']/2**20:.2f} scales + "
              f"{by['pos']/2**20:.2f} pos + "
              f"{by['state']/2**20:.2f} state)"
              + (f", history_limit {args.history_limit}"
                 if args.history_limit else ""))
        print(f"[serve] cache quantization: {pool.quant_policy.describe()}")
        print(f"[serve] attn backend: {resolved_backend_label(engine)} "
              f"(requested {args.attn_backend!r}; decode ticks "
              f"{'read the arena fused' if engine.runner.attn_backend == 'pallas' else 'gather the logical view'})")
    t0 = time.perf_counter()
    i = 0
    while i < len(pending) or engine.busy:
        now = time.perf_counter() - t0
        while i < len(pending) and pending[i].arrival_time <= now:
            engine.submit(pending[i])
            i += 1
        if engine.busy:
            engine.step()
        elif i < len(pending):
            time.sleep(min(pending[i].arrival_time - now, 0.01))
    s = engine.metrics.summary()
    if basecall:
        print(f"[serve] done: {s['requests_done']} reads, "
              f"{s['generated_tokens']} bases in {s['elapsed_s']:.2f}s "
              f"({s['requests_done']/max(s['elapsed_s'],1e-9):.2f} reads/s, "
              f"{s['tokens_per_s']:.0f} bases/s)")
        if args.read_until:
            print(f"[serve] read-until: {s['ejections']:.0f} ejections | "
                  f"samples saved {s['samples_saved']:.0f} | basecalled "
                  f"{s['ejected_consumed_samples']:.0f} samples on "
                  f"ejected reads")
    else:
        print(f"[serve] done: {s['requests_done']} requests, "
              f"{s['generated_tokens']} tokens in {s['elapsed_s']:.2f}s "
              f"({s['tokens_per_s']:.1f} tok/s end-to-end, "
              f"{s['decode_tokens_per_s']:.1f} tok/s decode)")
    print(f"[serve] ttft mean {s['ttft_mean_s']*1e3:.0f}ms "
          f"p50 {s['ttft_p50_s']*1e3:.0f}ms "
          f"p95 {s['ttft_p95_s']*1e3:.0f}ms "
          f"p99 {s['ttft_p99_s']*1e3:.0f}ms | queue depth "
          f"max {s['queue_depth_max']} mean {s['queue_depth_mean']:.1f} | "
          f"slot occupancy {s['slot_occupancy']:.2f}/{args.slots}")
    if not basecall:
        print(f"[serve] decode interval p50 "
              f"{s['decode_interval_p50_s']*1e3:.1f}ms p99 "
              f"{s['decode_interval_p99_s']*1e3:.1f}ms "
              f"({'split-tick' if args.split_tick else 'unified tick'}"
              + (f", prefill budget {args.max_prefill_tokens} tok"
                 if args.max_prefill_tokens else "") + ")")
    if not basecall:
        print(f"[serve] pool util mean {s['pool_util_mean']:.2f} "
              f"max {s['pool_util_max']:.2f} | "
              f"preemptions {s['preemptions']:.0f} | "
              f"attn backend {resolved_backend_label(engine)}")
    print_dispatch_report(s, args)
    done = engine.drain_completed()
    if done:
        sample = done[min(done)].out_tokens[:16]
        print("[serve] sample:", sample)
    return ServeRun(engine, done, warmup_s)


def run_static(params, cfg, args) -> None:
    """Legacy single-shot loop: one fixed batch, lockstep greedy decode."""
    batch = api.make_smoke_batch(jax.random.key(0), cfg, args.slots,
                                 args.prompt_len)
    cache_len = args.prompt_len + args.tokens + cfg.frontend_tokens

    kw = {}
    if cfg.family == "vlm":
        kw["patch_embeds"] = batch["patch_embeds"]
    if cfg.family == "audio":
        from repro.models.lm import encdec
        kw["enc_out"] = encdec.encode(params["encoder"], batch["frames"],
                                      cfg)
    t0 = time.time()
    logits, caches = jax.jit(
        lambda p, tk: tfm.prefill(p, tk, cfg, cache_len=cache_len, **kw)
    )(params, batch["tokens"])
    print(f"[serve] prefill {args.slots}x{args.prompt_len} in "
          f"{time.time()-t0:.2f}s")

    step = jax.jit(lambda p, c, tok, t: tfm.decode_step(p, c, tok, t, cfg))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out_tokens = [tok]
    t0 = time.time()
    pos0 = args.prompt_len + (cfg.frontend_tokens
                              if cfg.family == "vlm" else 0)
    for i in range(args.tokens - 1):
        logits, caches = step(params, caches, tok,
                              jnp.asarray(pos0 + i, jnp.int32))
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    dt = time.time() - t0
    total = args.slots * (args.tokens - 1)
    print(f"[serve] decoded {total} tokens in {dt:.2f}s "
          f"({total/max(dt,1e-9):.1f} tok/s)")
    print("[serve] sample:", jnp.concatenate(out_tokens, 1)[0][:16])


def run_knob_search(params, cfg, args) -> None:
    """QABAS-style serving-knob search: rank (cache policy, block_len,
    attn backend) by measured decode tok/s per cache byte."""
    if cfg.family == "basecaller":
        raise SystemExit(
            f"[serve] error: --knob-search tunes the paged KV arena; "
            f"basecaller arch {cfg.name!r} has no KV cache")
    from repro.core.qabas.serving import (format_knob_table,
                                          search_serving_knobs)
    backends = ([args.attn_backend] if args.attn_backend != "auto"
                else ["xla", "pallas"])
    block_lens = sorted({args.block_len, max(args.block_len // 2, 4)})
    results = search_serving_knobs(
        params, cfg, block_lens=block_lens, backends=backends,
        n_slots=args.slots, cache_len=args.cache_len,
        prompt_len=min(args.prompt_len, args.cache_len // 2),
        max_tokens=min(args.tokens, args.cache_len // 2),
        per_group=args.per_group,
        budget=args.knob_budget or None, emit=print)
    print(f"[serve] knob search over {cfg.name}: ranked by measured "
          f"decode tok/s per cache byte")
    print(format_knob_table(results))
    best = results[0]
    print(f"[serve] best: --quant-policy '{best.knobs.quant_policy}' "
          f"--block-len {best.knobs.block_len} "
          f"--attn-backend {best.knobs.attn_backend} "
          f"({best.decode_tok_s:.1f} tok/s at "
          f"{best.cache_bytes/2**20:.2f} MiB, "
          f"{best.bytes_vs_bf16:.2f}x smaller than bf16)")


def main(argv: Optional[Sequence[str]] = None) -> Optional[ServeRun]:
    """Parse ``argv`` (``sys.argv[1:]`` when None) and serve; engine
    runs return a :class:`ServeRun` so in-process callers can check
    the completed requests."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="legacy static-batch loop instead of the engine")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4,
                    help="decode slots (engine) / batch size (static)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=16.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--max-prefill-tokens", type=int, default=0,
                    help="per-tick prefill token budget for the unified "
                         "mixed tick: chunks schedule oldest-first until "
                         "the cumulative payload crosses it (soft cap; "
                         "0 = unlimited), so a burst of admissions "
                         "cannot inflate the running slots' decode "
                         "interval")
    ap.add_argument("--warmup", action="store_true",
                    help="pre-compile every bucketed tick plan at launch "
                         "(decode + all mixed chunk-width buckets x "
                         "greedy/sampled, encoder staging, basecaller "
                         "window) so traffic performs zero mid-run "
                         "compiles — the report's retraces= line gates it")
    ap.add_argument("--async-dispatch", action="store_true",
                    help="pipeline the engine tick: dispatch tick N's "
                         "device work, then harvest tick N-1's tokens — "
                         "host scheduling/CTC-merge overlaps device "
                         "compute behind a one-tick readback lag that is "
                         "token-identical to the sync engine")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission: reject new submits (status "
                         "'rejected', never silent) once this many fresh "
                         "requests are queued; preempted requests are "
                         "exempt (0 = unbounded)")
    ap.add_argument("--queue-timeout", type=float, default=0.0,
                    help="deadline-aware load-shed: reject queued "
                         "requests still unadmitted this many seconds "
                         "after arrival (0 = no deadline)")
    ap.add_argument("--split-tick", action="store_true",
                    help="legacy scheduler: one runner step per prefill "
                         "slot, then a decode-only step (admissions "
                         "stall decode) — the baseline the unified "
                         "co-batched tick is measured against")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop-token id for every request (engine path; "
                         "-1 = none). Requests end early when the decoded "
                         "token equals it — exercises early slot recycling")
    # ---- sampling (SamplingParams) ----
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampled requests (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus (top-p) truncation (1.0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed base; request i uses seed + i "
                         "(tokens are deterministic in (seed, rid, step))")
    ap.add_argument("--sampled-frac", type=float, default=1.0,
                    help="fraction of requests that sample when "
                         "--temperature > 0; the rest stay greedy and "
                         "share the same decode batches (sampler mix is "
                         "reported per run)")
    # ---- basecaller runner ----
    ap.add_argument("--read-bases", type=int, default=300,
                    help="basecaller archs: mean bases per simulated read")
    ap.add_argument("--chunk-samples", type=int, default=1024,
                    help="basecaller archs: core squiggle samples per "
                         "streamed chunk")
    ap.add_argument("--beam", type=int, default=0,
                    help="basecaller archs: prefix-beam width for the "
                         "incremental CTC merge (0 = greedy)")
    # ---- streaming + read-until (basecaller archs only) ----
    ap.add_argument("--stream", action="store_true",
                    help="basecaller archs: LIVE reads — samples arrive "
                         "over wall-clock time at the pore rate and are "
                         "appended to StreamingRequests; bases emit "
                         "incrementally (see --qos)")
    ap.add_argument("--qos", default="accuracy",
                    choices=["latency", "accuracy"],
                    help="streaming QoS knob: 'latency' re-forwards the "
                         "live window each tick and flushes every frame "
                         "the moment its receptive field is covered; "
                         "'accuracy' forwards each window exactly once "
                         "when fully covered (bit-identical to the "
                         "offline chunked basecall). Both emit prefixes "
                         "of the same final read")
    ap.add_argument("--read-until", action="store_true",
                    help="selective sequencing: train the start-of-read "
                         "classifier at launch, score the first chunks "
                         "of every read, and EJECT off-target reads "
                         "(slot freed, bases-so-far kept, status "
                         "'ejected'); with --stream the generator stops "
                         "appending and books the forgone samples as "
                         "saved")
    ap.add_argument("--target-frac", type=float, default=0.5,
                    help="streamed read-until traffic: fraction of reads "
                         "that are on-target pore-model squiggle; the "
                         "rest are off-target white noise")
    ap.add_argument("--eject-after-chunks", type=int, default=2,
                    help="read-until: decide after this many "
                         "window-complete classifier scores")
    ap.add_argument("--cache-len", type=int, default=0,
                    help="per-request KV capacity (0 = prompt+tokens)")
    ap.add_argument("--block-len", type=int, default=16,
                    help="KV positions per paged-pool arena block "
                         "(cache_len degenerates to contiguous rows)")
    ap.add_argument("--n-blocks", type=int, default=0,
                    help="arena blocks per layer group (0 = full "
                         "backing = n_slots*ceil(cache_len/block_len); "
                         "set lower to oversubscribe slots vs KV bytes)")
    ap.add_argument("--history-limit", type=int, default=0,
                    help="bound host-side per-request history to the "
                         "most recent N (0 = unbounded) so long serves "
                         "run at flat memory")
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="decode-attention read path: 'pallas' fuses "
                         "decode ticks over the paged KV arena (block "
                         "table scalar-prefetched, no logical-view "
                         "gather), 'xla' is the gather reference; "
                         "'auto' = pallas on a single-chip TPU, xla "
                         "everywhere else (the fused path is not "
                         "shard_map'd; forcing pallas on CPU runs the "
                         "kernel in interpret mode). The resolved "
                         "backend is reported in the run summary")
    ap.add_argument("--wbits", type=int, default=0, choices=[0, 4, 8])
    # ---- quantized KV arena (CacheQuantPolicy) ----
    ap.add_argument("--cache-dtype", default="",
                    help="uniform KV-arena storage mode: bf16 (default), "
                         "fp16, fp32, fp8, or int8 (per-block scale "
                         "leaves, in-kernel dequant). fp8 falls back to "
                         "bf16 with a warning where the platform lacks "
                         "float8; invalid modes are rejected at launch")
    ap.add_argument("--quant-policy", default="",
                    help="per-layer-group cache policy, e.g. "
                         "'default=bf16,g1_moe=int8' (group names from "
                         "the arch's layer groups; unknown groups are "
                         "rejected at launch). Overrides --cache-dtype")
    ap.add_argument("--knob-search", action="store_true",
                    help="QABAS-style serving-knob search: measure "
                         "per-layer cache dtype x block_len x attn "
                         "backend on a small greedy workload, print the "
                         "ranked tok/s-per-cache-byte table, and exit")
    ap.add_argument("--knob-budget", type=int, default=0,
                    help="cap measured knob-search candidates (taken in "
                         "roofline-prior order; 0 = measure all)")
    ap.add_argument("--per-group", action="store_true",
                    help="knob search: add the coordinate-descent "
                         "per-group precision refinement pass")
    args = ap.parse_args(argv)
    if not args.cache_len:
        args.cache_len = args.prompt_len + args.tokens

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[serve] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())} | compile cache {cache_dir}")
    cfg = get_config(args.arch + ("-smoke" if args.smoke else ""))
    params = api.init_params(jax.random.key(0), cfg)
    if args.wbits:
        params = quantize_for_serving(params, args.wbits)
        if args.static:
            # dequantize-on-load for the legacy path; the engine consumes
            # packed weights directly (dequant-on-read in `dense`)
            params = dequantize_tree(params, jnp.dtype(cfg.dtype))
        print(f"[serve] weights quantized to int{args.wbits} "
              f"(packed storage; dequant-on-read)")

    if args.knob_search:
        run_knob_search(params, cfg, args)
    elif args.static:
        run_static(params, cfg, args)
    else:
        return run_engine(params, cfg, args)
    return None


if __name__ == "__main__":
    main()
