"""Continuous-batching serving: one engine for LMs, audio, and the
basecaller itself.

Architecture (post Runner/SamplingParams redesign)
==================================================

The stack splits into three layers:

``engine``   :class:`ServingEngine` — PURE host-side scheduling: FIFO
             queue, fixed slot pool, admission, the unified mixed-tick
             schedule (below), preempt-youngest + resume-by-re-prefill,
             metrics. It imports no model code; everything model-shaped
             goes through a runner.

``runner``   the :class:`ModelRunner` protocol (``validate`` /
             ``make_chunks`` / ``admit`` / ``alloc_pool`` / ``step`` /
             ``reset_row``) plus a registry (:func:`make_runner`) with
             three backends:

             - ``TokenRunner`` — every token-only arch (attention
               ``dense``/``moe``, SSM, MLA, hybrid) over the paged
               block-granular KV pool, driving the fixed-shape jitted
               programs (lockstep ``(B, 1)`` decode-only ticks; one
               co-batched ``(B, C)`` program for mixed ticks).
             - ``EncoderPrefixRunner`` — whisper-style audio enc-dec:
               ``encdec.encode`` runs once per request at admission and
               each decoder layer's cross-attention K/V is scattered
               into a per-slot device buffer; decoder tokens then
               schedule exactly like a token-only arch.
             - ``BasecallerRunner`` — squiggle-in, bases-out: reads
               stream through the CTC basecaller as fixed-size
               halo-padded chunks (bit-identical to the whole-read
               forward) with incremental greedy/beam CTC merge. No
               decode phase, no KV pool — but the same slots, queue,
               admission and metrics.

``sampling`` :class:`SamplingParams` — per-request stopping criteria +
             temperature/top-k/top-p/seed. Sampling is vectorized
             on-device: per-slot parameter rows ride into the decode
             step, so a mixed greedy+sampled batch stays ONE jitted
             program, and sample noise is keyed by
             ``fold_in(PRNGKey(seed), rid, step)`` — deterministic
             across restarts, slot placement, and preemption/resume.
             ``temperature == 0`` rows take EXACT argmax; a pure-greedy
             tick runs a program with no sampling ops at all, pinned
             bit-identical to the pre-redesign engine by regression
             tests.

Unified mixed-tick scheduling (prefill + decode in one program)
---------------------------------------------------------------

Every scheduler tick emits ONE work list — one entry per slot: a
``PrefillWork`` (the slot's next prompt chunk, up to C tokens) or a
``DecodeWork`` (one lockstep token) — and the runner executes the whole
list in one jitted ``step``. Decode rows occupy column 0 of the
``(B, C)`` batch with their single token; prefill rows carry their
chunk with per-token positions; a per-row ``fresh`` vector folds slot
recycling into the step; and ``logits_at`` unembeds each row at its
own emitting position. Chunk-prefill attention reads run the same
backend as decode (for ``pallas``, the multi-token fused kernel — no
logical-view gather anywhere in the tick). The result: a long
admission no longer stalls decode for the running slots — prefill and
decode advance together, which is what flattens decode-interval jitter
and TTFT under bursty Poisson traffic.

The per-tick prefill payload is bounded by ``max_prefill_tokens``
(engine kwarg / ``serve.py --max-prefill-tokens``): chunks schedule
oldest-admission-first until the cumulative payload crosses the
budget — a soft cap, the crossing chunk still runs, so one chunk
always makes progress; 0 disables the budget. Decode-only ticks skip
the mixed program entirely and run the pinned ``(B, 1)`` decode
programs (the greedy-parity regression gate is unchanged).

``co_batch=False`` keeps the legacy split-tick scheduler — one runner
step per prefill slot, then a decode-only step — as the measured
baseline (``bench_serving --smoke`` asserts token parity between the
two modes and reports the TTFT/jitter delta). Token sequences are
IDENTICAL in both modes; only tick timing differs (co-batched slots
decode their first post-prefill token on the following tick).

Paged KV pool (block arena + block tables + free list)
------------------------------------------------------

KV bytes live in a shared BLOCK ARENA per layer group: ``(n_layers,
n_blocks, block_len, ...)`` leaves, instead of one contiguous
``cache_len`` row per slot. A host-side block table per group
(``(n_slots, T)``, ``T = ceil(ring_len/block_len)``) maps each slot's
logical block to an arena block; tables are tiny int32 arrays shipped
into the jitted programs every tick, so allocation (LIFO free list) is
pure host bookkeeping. Positions stay PER SLOT — which keeps validity
masking and the RESET-SPEC recycle machinery unchanged, and is what
makes block recycling safe: a freed block keeps its bytes, but the next
slot that maps it has an empty ``pos`` row until it writes, so stale KV
can never attend back in. SSM recurrent state is O(1) per row and stays
slot-indexed. ``block_len=cache_len, n_blocks=n_slots`` recovers the
contiguous layout exactly (the benchmark baseline).

Decode-attention backends (fused arena reads)
---------------------------------------------

How the jitted programs READ that pool is a backend choice, dispatched
by ``repro.kernels.ops.decode_gqa`` / ``decode_mla`` and threaded
``CachePool(attn_backend=…)`` -> ``TokenRunner`` ->
``transformer.decode_step_slots`` (the pool resolves ``auto``/``xla``/
``pallas`` once and is the single source of truth):

``xla``      the gather reference: each layer gathers its slots' blocks
             into a ``(B, T*block_len)`` logical view and runs
             masked-dense attention — the parity oracle and the
             multi-chip (GSPMD flash-decoding) default.
``pallas``   the fused kernel (``repro.kernels.paged_attention``):
             the block table is a scalar-prefetch operand, each grid
             step DMAs exactly one arena block, and online softmax
             fuses validity/ring-window/stale-KV masking — the logical
             view is never materialised. ``auto`` = pallas on a
             single-chip TPU, xla everywhere else (the fused path is
             not shard_map'd yet, so multi-chip meshes keep the GSPMD
             reference; forcing pallas on CPU runs interpret mode,
             which CI uses to exercise the kernel body).

WHICH PATHS FUSE: single-token decode ticks (``C == 1``) AND
multi-token chunk prefill (``C > 1``, the mixed-tick variant with a
per-query causal mask) for GQA self-attention (dense/moe/hybrid incl.
sliding-window rings) and absorbed-MLA latent reads; plus the audio
runner's single-token cross-attention (its multi-token rows keep the
dense fp32 einsum, which is not a paged gather and is
backend-identical by construction). Fused paths share the reference's
masking contract and compute dtypes; greedy token parity across the
paged configs (incl. recycle/preemption, bf16 caches, and C > 1
chunks) is enforced by tests/test_paged_attention.py and the
bench_serving ``--smoke`` backend section — the only residual
difference is online- vs plain-softmax rounding. A new arch opts in by
expressing its decode read through ``decode_gqa`` / ``decode_mla``
instead of gathering KV itself; anything else simply keeps the
reference path.

Cache quantization policy (fp8/int8 block arenas)
-------------------------------------------------

WHAT the pool stores is a per-layer-group policy, orthogonal to the
backend choice above: :class:`~repro.serving.cache.CacheQuantPolicy`
maps each KV-bearing layer group to a storage mode — ``bf16`` (the
default), ``fp8`` (``float8_e4m3fn`` bytes, no scales), or ``int8``
(symmetric per-token-per-head quantization with fp32 scale leaves
``k_scale``/``v_scale`` — per-token latent scales ``c_scale``/
``kr_scale`` for MLA — living in the arena alongside their blocks).
Construct it with ``CachePool(quant_policy=…)`` /
``ServingEngine(quant_policy=…)`` / ``serve.py --cache-dtype int8`` or
``--quant-policy "default=bf16,g1_moe=int8"``; a policy naming unknown
groups fails ADMISSION with the model's real group list, and fp8 on a
build without fp8 storage falls back to bf16 with a RuntimeWarning —
never a serve-time crash.

Scales are written IN LOCKSTEP with their K/V bytes — same scatter
indices, same tick — so a recycled block's stale scales are fenced by
exactly the same empty ``pos`` row that fences its stale bytes (there
is no separate scale-invalidation path to get wrong). Reads
dequantize per backend through one shared expression
(``paged_attention.dequantize_kv``): the XLA reference gathers scales
with the same clamped indices as the values; the fused Pallas kernels
take the scale leaves as extra VMEM operands and dequantize
in-register, keeping fp32 softmax statistics — so greedy token parity
across backends holds at every cache dtype
(tests/test_quantized_serving.py, ``bench_serving --smoke``).
``CachePool.nbytes()`` counts EVERY leaf — arena bytes, scale leaves,
pos rows, SSM state — and ``nbytes_by_class()`` splits them, so
equal-slot byte comparisons can't hide the int8 scale overhead
(``serve.py`` prints the breakdown; fp8 halves arena bytes with zero
overhead, int8 halves them plus one fp32 scale per token per head).

Admission policy: ``submit`` rejects only what can never run (runner
``validate``: ``prompt + max_new - 1 > cache_len`` — the final token is
never written — more blocks than the arena holds, or a malformed
payload). A queued request is admitted when a slot is free AND the
runner can back its payload; decode allocates one block at a time as
positions cross block boundaries. When the pool runs dry mid-decode,
the YOUNGEST running request is preempted (pool row freed, requeued at
the front) and later resumes by re-prefilling prompt + generated
tokens — greedy decode is deterministic and sampled decode replays its
``(seed, rid, step)`` keys, so tokens are unchanged either way.

Slot lifecycle
--------------

1. **Admit** — queue head -> free slot; the runner backs the payload
   (``alloc_pool``) and stages per-request device state (``admit`` —
   the audio runner encodes frames and scatters cross-attention K/V
   into the slot's buffer). Per-slot cache rows are reset in place per
   each cache's RESET SPEC on the first chunk.
2. **Prefill** — the payload streams through per-tick ``PrefillWork``
   chunks inside the unified ``step`` (prompt tokens for LMs;
   halo-padded squiggle windows for reads, which emit merged bases as
   they go — the basecaller batches every scheduled slot's window into
   one forward). The final chunk of an autoregressive prompt emits
   generated token #1 (TTFT).
3. **Decode** — autoregressive slots join the lockstep ``DecodeWork``
   batch until ``max_new_tokens`` or EOS, growing by one block at block
   crossings, co-batched with any in-flight prefill chunks. Basecaller
   reads skip this phase entirely: they finish with their last chunk.
4. **Evict** — ``reset_row`` returns pool blocks / clears per-slot
   runner state; the next queued request is admitted on the following
   tick. JIT shapes never change throughout.

Because the decode batch shape is pinned at ``n_slots``, oversubscribed
traffic queues on the host and drains into freed slots — steady-state
decode throughput stays at the full-batch rate instead of draining to
the stragglers' rate (bench_serving.py).

Streaming & read-until (PR 9, basecaller only)
----------------------------------------------

A :class:`~repro.serving.stream.StreamingRequest` is a basecaller read
whose signal does not exist up front: callers ``append(samples)`` as
the pore produces them and call ``finish()`` at the read end. Lifecycle:

1. **Submit** — any time, even before the first sample. The engine
   rejects streams at submit for every non-basecaller runner
   (``supports_streaming``); ``TokenRunner.validate`` refuses them too.
2. **Admit** — the slot gets a live :class:`~repro.serving.stream.
   StreamCursor` (built by the runner — the engine stays model-free)
   instead of a pre-chunked payload list.
3. **Emit** — each tick the cursor issues at most one window span whose
   frames' receptive fields are fully covered by arrived samples
   (frame ``g`` is STABLE once ``arrived >= (g+1)*stride + halo``), so
   every base that reaches ``out_tokens`` is FINAL: the emitted prefix
   is exactly a prefix of the whole-read offline basecall under ANY
   append schedule, and equals it bit-for-bit once the stream finishes
   (tests/test_streaming.py sweeps dribble/window/bursty/whole
   schedules). Preemption stashes the cursor + CTC merge and resumes
   exactly where the stream left off.

QoS semantics (``qos=`` runner kwarg / ``serve.py --qos``):

``latency``   (emit_latency) re-forwards the live window whenever new
              frames become stable — lowest sample-to-base latency
              (the ``emit_latency_p50_s``/``p99`` summary keys track
              sample-arrival -> base-emission), at the cost of
              re-running the window forward as its tail fills in.
``accuracy``  (halo_recompute, default) forwards each window exactly
              once, when core + halo is fully covered — windows are
              byte-identical to the offline chunked path for EVERY
              config, including act-quantized ones.

Read-until (selective sequencing): pass ``read_until=ReadUntil(params,
eject_after_chunks, threshold)`` and the runner co-executes the tiny
start-of-read classifier head (``models.basecaller.classifier``) inside
the same jitted tick, scoring each read's first ``eject_after_chunks``
window-complete forwards (content-complete windows only, so the verdict
is append-schedule invariant). A read whose mean on-target logit falls
below ``threshold`` is EJECTED: slot and pool freed, bases-so-far kept.

Ejection status contract: ``Request.status`` moves ``queued ->
running -> finished`` with two side states — ``preempted-pending``
while evicted awaiting resume, and ``ejected`` as a terminal state
distinct from ``finished`` (``req.done`` is true for both;
``req.finished``/``req.ejected`` disambiguate, and
``drain_completed(status=…)`` filters). An ejected read's
``out_tokens`` hold the partial basecall — a prefix of what the full
read would have produced — and the metrics book the ejection
(``ejections``, ``ejected_consumed_samples``) plus the samples never
basecalled (``samples_saved``; generators add the forgone tail via
``record_samples_saved``). ``serve.py --stream --read-until`` drives
all of this from a live Poisson pore simulation.

Dispatch pipeline, buckets & backpressure (PR 10)
-------------------------------------------------

How ticks reach the device is now a pipelined dispatch path built on a
bucketed plan cache (:mod:`repro.serving.plan`):

**Plan buckets + warmup.** Every schedulable tick shape rounds to a
small fixed bucket set and each bucket owns its OWN ``jax.jit``
wrapping (a *plan*): the pinned ``("decode", 1, flavor)`` lockstep
programs plus one ``("mixed", w, flavor)`` program per chunk-width
bucket — ``chunk_buckets(C)`` = powers of two below ``C`` plus ``C``
itself, and the scheduler pads a mixed tick only up to
``round_chunk(widest chunk)`` instead of always to the full
``prefill_chunk``. ``engine.warmup()`` (``serve.py --warmup``)
executes every registered plan once with representative padded
arguments at launch, so a full traffic run performs ZERO mid-traffic
compiles; ``PlanCache.stats()`` audits this by comparing each step
callable's compiled-signature count against its warmed-key count
(``retraces`` in the metrics summary and serve report — serve hard-
fails on a nonzero count after ``--warmup``, and tests set
``require_warm`` to turn any unwarmed plan lookup into a hard
:class:`~repro.serving.plan.PlanMissError`).

**Async pipelined dispatch** (``async_dispatch=True`` /
``serve.py --async-dispatch``): the runner's tick splits into a
dispatch half (enqueue the jitted step — NO host syncs, enforced by
the host-sync analyzer rule) and a harvest half (read back emitted
tokens). The engine dispatches tick N, then harvests tick N-1 — host
scheduling, CTC merging and queue work overlap device compute instead
of serializing behind ``device_get``. The one-tick readback lag is
semantically invisible: decode programs chain the previous tick's
on-device token into column 0 themselves (``chain``/``prev``
operands), so token sequences are IDENTICAL to sync mode across every
cache family, preemption/resume, and streamed reads
(tests/test_dispatch.py parity sweeps; ``bench_serving --smoke``
gates parity plus an async-over-sync throughput floor). Idle ticks
(every live slot a stream waiting on unarrived samples) skip dispatch
entirely.

**Full-carry donation.** Every plan is jitted with the whole tick
carry (cache pytree, sampler state, chained tokens) in
``donate_argnums``, so each bucket's program aliases the carry
in-place — steady-state decode allocates no second copy of any cache
leaf. ``cache.carry_leaves``/``cache.donated_fraction`` expose the
live-buffer accounting the donation test pins at 1.0. One measured
backend interaction (``runner.resolve_donate_carry``): the CPU PJRT
client executes a DONATING computation synchronously inside the jit
call, which would serialize the async dispatch half — so ``auto``
skips carry donation exactly when async dispatch runs on a multi-core
CPU host (where the overlap is real and worth the copy), and keeps it
everywhere else (TPU/GPU enqueue donating calls fine; a single-core
host has no second core to overlap onto).

**Admission backpressure.** ``max_queue`` bounds FRESH queued
arrivals (``submit`` returns False and the request completes
immediately with ``status='rejected'`` + ``reject_reason`` — never a
silent drop) and ``queue_timeout_s`` sheds queued waiters whose
deadline passed at the next tick. Preempted-pending requests hold
generated tokens and are EXEMPT from both: they never count against
the bound and are never shed. The metrics summary books
``rejections``, ``queue_depth_hwm``, tick-latency p50/p99,
``idle_ticks`` and the plan-cache counters; ``serve.py`` prints them
as the dispatch report.

Spans (``repro.serving.tracing``)
---------------------------------

While a profiler session collects (``jax.profiler.start_trace`` or a
capture through ``start_server``), the engine and the basecaller
runner record spans — ``serving.tick``, ``admit``, ``schedule``,
``dispatch``, ``device_wait``, ``readback``, ``ctc_merge``, ``book``,
and per request ``window_wait`` and ``verdict`` — as profiler
annotations beside the device ops and in a bounded in-memory ring
(``tracing.default()``). With no session a span costs one check.
Request counters stay in ``ServingMetrics``.

Migration note (PR 4)
---------------------

``Request(prompt, max_new_tokens=…, eos_id=…)`` is deprecated: stopping
criteria moved into ``SamplingParams`` alongside the sampler knobs —
``Request(rid, prompt, SamplingParams(max_new_tokens=…, eos_id=…,
temperature=…, top_k=…, top_p=…, seed=…))``. The legacy kwargs still
work (mapped to a default-greedy SamplingParams + DeprecationWarning),
and ``req.max_new_tokens`` / ``req.eos_id`` remain readable. New payload
kwargs: ``frames=`` (audio encoder input) and ``signal=`` (squiggle) —
exactly one of ``prompt``/``signal`` per request.

Migration note (PR 5, decode-attention backends)
------------------------------------------------

Direct callers of ``attn_decode_slots`` / ``mla_decode_slots`` are
unaffected by default (the new ``attn_backend=None`` keyword means the
XLA reference, bit-identical to before), but the paged READ plumbing
moved: ``paged_indices``/``EMPTY_POS``/``NEG_INF`` now live in
``repro.kernels.paged_attention`` (re-exported from
``models.lm.attention`` for compatibility), and code that previously
copied the gather-and-mask pattern should call
``repro.kernels.ops.decode_gqa`` / ``decode_mla`` so it picks up fused
backends for free. Pallas kernels no longer pin interpret mode at
import — ``repro.kernels.ops.interpret_default()`` resolves it per
call (``REPRO_PALLAS_INTERPRET=1|0`` overrides).

Migration note (PR 6, unified mixed ticks)
------------------------------------------

The ``ModelRunner`` protocol collapsed ``prefill_chunk(slot, payload,
pos, fresh, req, final)`` + ``decode_tick(views)`` into ONE method:
``step(works)``, taking a per-slot list of ``PrefillWork`` /
``DecodeWork`` / ``None`` and returning per-slot emitted tokens.
``DecodeView`` was renamed ``DecodeWork`` (same fields). Custom
runners must implement ``step``; the engine never calls anything else
per tick. Engine behavior note: under the default co-batched schedule
a slot that finishes prefill decodes its first token on the FOLLOWING
tick (the old scheduler decoded it in the same tick) — token
sequences, TTFT accounting, and preemption/resume semantics are
unchanged, but per-tick traces differ. ``co_batch=False`` restores
the old split-tick schedule exactly.

Migration note (PR 7, quantized serving)
----------------------------------------

``CachePool(cache_dtype=…)`` still works and now derives a uniform
:class:`~repro.serving.cache.CacheQuantPolicy` (``jnp.bfloat16`` ->
``"bf16"`` etc.); pass ``quant_policy=`` for per-group control — it
wins over ``cache_dtype`` when both are given. ``pool.nbytes()`` now
includes scale/pos/state leaves it previously ignored, so byte
numbers logged by older runs read LOW by the bookkeeping share; use
``nbytes_by_class()["arena"]`` for the old quantity. Serving-time
packed weights (``PackedTensor``) route decode matmuls through the
Pallas ``qmatmul``/``qconv1d`` kernels when the model config carries
8-bit QABAS widths for the layer and the kernel's tiling contract
holds; they dequantize on read otherwise — same ints, same numbers to
rounding, no action needed. The serving-knob search over these
policies lives in ``repro.core.qabas.search_serving_knobs``
(``serve.py --knob-search``).

Enforced invariants (repro.analysis)
------------------------------------

The contracts this stack is built on are MECHANIZED: ``python -m
repro.analysis`` (a blocking CI fast-gate step) traces the real jitted
serving programs (every cache family x both attention backends x both
tick shapes, int8 arenas included) and lints ``src/repro``, enforcing:

``no-materialization``
    The fused (Pallas) decode/chunk programs never gather or reshape a
    ``(B, T*block_len)``-or-larger logical KV view out of the block
    arena — the property the paged-attention kernels exist for. The
    XLA reference must KEEP that gather (it is the parity oracle).
``precision``
    Softmax statistics, scale math and matmul accumulation in the
    attention/qmatmul programs stay fp32: no bf16/f16 ``exp`` or
    reductions, no low-precision ``dot_general`` accumulators, and on
    quantized paths no fp32 downcast whose value reaches stats math.
    (bf16 QK/PV COMPUTE is the alignment contract and is exempt.)
``host-sync``
    ``np.asarray`` / ``.item()`` / ``device_get`` /
    ``block_until_ready`` inside engine/runner tick paths carry an
    explicit ``# sync: <reason>`` marker — the hot loop's device->host
    round trips are intentional, counted, and reviewable.
``trace-stability``
    Ticking the same shape bucket twice hits the jit cache (retrace-
    counter audit over the live ``TokenRunner`` step programs) — no
    mid-traffic recompiles from unstable static arguments.

Suppress a deliberate exception inline with ``# repro-allow:
<rule-id>`` (AST rules) or an ``"<rule-id>:<where-glob>"`` entry in
``repro.analysis.allowlist.DEFAULT_ALLOWLIST``; add a rule by
registering ``check(ctx)`` under ``repro/analysis/rules/``.
"""
from repro.serving.cache import CachePool
from repro.serving.engine import Request, ServingEngine
from repro.serving.metrics import ServingMetrics
from repro.serving.runner import (BasecallerRunner, EncoderPrefixRunner,
                                  ModelRunner, TokenRunner, make_runner,
                                  register_runner)
from repro.serving.sampling import GREEDY, SamplingParams
from repro.serving.stream import ReadUntil, StreamingRequest

__all__ = ["CachePool", "Request", "ServingEngine", "ServingMetrics",
           "SamplingParams", "GREEDY", "ModelRunner", "TokenRunner",
           "EncoderPrefixRunner", "BasecallerRunner", "make_runner",
           "register_runner", "StreamingRequest", "ReadUntil"]
