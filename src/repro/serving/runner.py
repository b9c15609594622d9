"""ModelRunner protocol + registry: the serving engine's model backend.

The engine (``repro.serving.engine``) is pure host-side scheduling —
queue, slots, admission, preemption, metrics. Everything model-shaped
lives behind a :class:`ModelRunner`:

``validate``       submit-time capacity/payload checks (raise ValueError)
``make_chunks``    split a request's payload into prefill chunks
``admit``          stage per-request device state into a slot (e.g. the
                   audio runner's encoder K/V)
``alloc_pool``     back payload positions ``[0, upto)`` with pool blocks
``step``           run ONE co-batched tick: a per-slot work list mixing
                   :class:`PrefillWork` (one prompt chunk, C tokens) and
                   :class:`DecodeWork` (one lockstep token) entries —
                   every scheduled slot advances in one jitted program.
                   Returns per-slot emitted tokens (empty for mid-prompt
                   chunks and idle slots; the final chunk of an
                   autoregressive prompt emits exactly the first
                   generated token).
``dispatch``/      the async split of ``step``: ``dispatch`` enqueues
``collect``        the tick's device work and returns an opaque handle
                   with the emitted tokens still ON DEVICE; ``collect``
                   performs the deferred readback (plus any host-side
                   merge work) one tick later. ``step`` ==
                   ``collect(dispatch(works))`` exactly, so the
                   synchronous engine path is unchanged. ``collect``
                   takes a ``discard`` slot set — post-completion
                   speculative rows whose tokens (and basecaller merge
                   feeds) must be dropped.
``warmup``         pre-compile every tick-plan bucket at launch (see
                   :mod:`repro.serving.plan`); ``plan_stats`` reports
                   the bucket hit/miss/retrace counters.
``reset_row``      release a slot's pool blocks / per-slot runner state

MIGRATION (unified tick): the former ``prefill_chunk(slot, payload,
pos, fresh, req, final)`` / ``decode_tick(views)`` split is GONE —
both shapes now arrive through ``step``'s work list (``DecodeView``
became :class:`DecodeWork`). Custom runners implement ``step`` instead
of the pair; the engine never calls anything else per tick.

Three registered implementations:

TokenRunner           every token-only arch (dense/moe/ssm/mla/hybrid)
                      over the paged KV pool, with per-request
                      ``SamplingParams``. Decode-only ticks run the
                      pure (B, 1) programs (greedy rows stay
                      bit-identical to the pre-runner engine — the
                      greedy decode program contains no sampling ops at
                      all); mixed ticks run one (B, C) program where
                      decode rows occupy column 0 and prefill rows
                      carry their chunk, each row unembedding at its
                      own emitting position.
EncoderPrefixRunner   whisper-style audio enc-dec: ``encdec.encode`` runs
                      once per request at admission and the per-layer
                      cross-attention K/V is scattered into a per-slot
                      buffer the step programs read; the decoder
                      tokens then serve exactly like a token-only arch.
BasecallerRunner      squiggle-in, bases-out: reads stream through the
                      CTC basecaller as fixed-size halo-padded chunks
                      (bit-identical to the whole-read forward — see
                      ``repro.models.basecaller.model``) with an
                      incremental greedy/beam CTC merge per slot. Every
                      scheduled slot's window batches into ONE forward
                      per tick (per-row read-edge bounds). Not
                      autoregressive: a read finishes with its last
                      chunk and never occupies a decode slot.

``make_runner(params, cfg, **kw)`` dispatches on the config; register
custom backends with :func:`register_runner`.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.serving import tracing
from repro.serving.cache import CachePool
from repro.serving.plan import PlanCache, chunk_buckets, round_chunk
from repro.serving.sampling import any_sampled, pack_rows, sample_tokens


class Chunk(NamedTuple):
    """One prefill unit: an opaque payload + how many logical positions
    it advances a slot (tokens for LMs, squiggle samples for reads)."""
    payload: Any
    n_units: int


class PrefillWork(NamedTuple):
    """One scheduled prompt chunk for one slot in a unified tick."""
    payload: Any                # one Chunk's payload
    n_units: int                # logical positions the chunk advances
    pos: int                    # positions already consumed before it
    fresh: bool                 # first chunk: invalidate the slot's row
    final: bool                 # last chunk of the payload
    req: Any                    # repro.serving.engine.Request


class DecodeWork(NamedTuple):
    """One scheduled lockstep decode token for one slot.

    ``step`` is the sampling step index at DISPATCH time (the count of
    tokens already emitted or in flight); -1 means "read it from
    ``len(req.out_tokens)``" — the synchronous path, where nothing is
    in flight. ``chained`` marks a token the host does not know yet:
    the previous dispatched tick emitted it and its readback is still
    deferred, so the step program substitutes the previous tick's
    on-device output for this row (``last_token`` is ignored).
    """
    last_token: int
    pos: int
    req: Any                    # repro.serving.engine.Request
    step: int = -1
    chained: bool = False


# ---------------------------------------------------------------------------
# Protocol


class ModelRunner:
    """Duck-typed base for serving backends (see the module docstring
    for the contract). The engine only ever touches these members.

    Streaming (``repro.serving.stream``) is opt-in: a runner that sets
    ``supports_streaming = True`` must implement ``open_stream`` (build
    the per-request window cursor) and ``export_row``/``restore_row``
    (stash/restore per-slot state across preemption); ``flush_row`` and
    ``pop_ejections`` back the read-until ejection path.
    """

    autoregressive: bool = True
    pool = None                         # CachePool or None
    supports_streaming: bool = False    # accepts StreamingRequest payloads
    supports_async: bool = False        # dispatch/collect pipeline the tick
                                        # (incl. chained decode tokens)

    def validate(self, req) -> None:
        raise NotImplementedError

    def make_chunks(self, req) -> List[Chunk]:
        raise NotImplementedError

    def admit(self, slot: int, req) -> None:
        pass

    def alloc_pool(self, slot: int, upto: int) -> bool:
        return True

    def reset_row(self, slot: int) -> None:
        pass

    def pool_util(self) -> float:
        return 0.0

    # ---- streaming / read-until hooks (basecaller-only today) ----
    def open_stream(self, req):
        """Build the window cursor for a freshly admitted stream."""
        raise NotImplementedError(
            f"{type(self).__name__} does not serve StreamingRequests")

    def export_row(self, slot: int):
        """Snapshot per-slot state for a preempted stream's resume."""
        return None

    def restore_row(self, slot: int, state) -> None:
        """Restore an :meth:`export_row` snapshot at re-admission."""

    def flush_row(self, slot: int) -> List[int]:
        """Best-so-far tokens held back by the slot's merge (ejection)."""
        return []

    def pop_ejections(self) -> List[int]:
        """Slots whose read-until verdict said eject (cleared on read)."""
        return []

    def step(self, works: List[Optional[Any]]) -> List[List[int]]:
        """Run one co-batched tick. ``works`` has one entry per slot:
        a :class:`PrefillWork`, a :class:`DecodeWork`, or None (idle).
        Returns the tokens each slot commits this tick (one per decode
        row; the emitted token for a final prefill chunk; ``[]`` for
        mid-prompt chunks and idle slots — basecaller chunks may emit
        several bases)."""
        raise NotImplementedError

    # ---- async dispatch pipeline (opt-in: supports_async) ----
    def dispatch(self, works: List[Optional[Any]]) -> Any:
        """Enqueue one tick's device work; the default defers the whole
        step to ``collect`` (no overlap — real pipelining needs the
        runner to enqueue the jitted program here and read back later).
        """
        return works

    def collect(self, handle: Any,
                discard: frozenset = frozenset()) -> List[List[int]]:
        """Deferred readback for a ``dispatch`` handle. ``discard``
        names slots whose emitted tokens (and any per-slot host merge
        side effects) must be dropped — post-completion speculative
        work under the engine's one-tick readback lag."""
        emitted = self.step(handle)
        return [[] if i in discard else toks
                for i, toks in enumerate(emitted)]

    def warmup(self) -> int:
        """Pre-compile every tick-plan bucket; returns plans warmed."""
        return 0

    def plan_stats(self) -> Dict[str, int]:
        """Bucket/retrace accounting (see ``PlanCache.stats``)."""
        return {}


# ---------------------------------------------------------------------------
# TokenRunner — token-only archs over the paged KV pool


def resolve_donate_carry(mode, async_dispatch: bool) -> bool:
    """Whether the tick plans donate the carry pytree (arena + scale +
    pos + state leaves alias in place through every program).

    ``auto`` donates everywhere EXCEPT async dispatch on a MULTI-CORE
    CPU host: the CPU PJRT client executes a donating computation
    synchronously inside the jit call (measured: a donated call returns
    after the full compute; the identical non-donated call returns in
    ~0.1ms), which would serialize the dispatch half of the pipeline
    and erase the overlap the async engine exists for. On a single-core
    CPU host there is no second core to overlap onto — host and
    "device" time-slice the same core — so donation stays on (aliasing
    beats the copy-per-tick a non-donated carry costs). On TPU/GPU
    donation and async dispatch compose — the call is enqueued either
    way — so both stay on. Pass True/False to force."""
    if mode != "auto":
        return bool(mode)
    import os
    return not (async_dispatch and jax.default_backend() == "cpu"
                and (os.cpu_count() or 1) > 1)


class TokenRunner(ModelRunner):
    """Drives ``decode_step_slots`` over a paged :class:`CachePool`,
    with vectorized per-request sampling, in two tick shapes:

    - DECODE-ONLY ticks run the lockstep ``(B, 1)`` programs. The
      pure-greedy one is byte-for-byte the pre-SamplingParams program
      (argmax only — the greedy-parity regression gate); the sampling
      one adds the per-row top-k/top-p/Gumbel work and is used only
      when a live row actually samples.
    - MIXED ticks (any prefill work scheduled) run ONE ``(B, C)``
      program: decode rows occupy column 0 with their single token,
      prefill rows carry up to C chunk tokens, a per-row ``fresh``
      vector folds slot recycling into the step, and ``logits_at``
      unembeds each row at its own emitting position. Sampling rows
      are packed only for rows that emit this tick (decode rows and
      final chunks); mid-prompt chunks pack as greedy — their token is
      discarded.

    ``attn_backend`` (``auto``/``xla``/``pallas``) picks the decode-
    attention read path (``repro.kernels.ops``): ``pallas`` computes
    both tick shapes directly from the paged block arena (the C == 1
    fused kernel for decode-only ticks, the multi-token chunk variant
    inside mixed ticks — no per-layer logical-view gather either way),
    ``xla`` keeps the gather reference; ``auto`` resolves to pallas on
    TPU. Both backends apply the identical masking contract, so
    emitted tokens do not depend on the backend.
    """

    autoregressive = True
    supports_async = True

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int,
                 cache_len: int, prefill_chunk: int, cache_dtype,
                 block_len: int = 0, n_blocks: int = 0,
                 attn_backend: str = "auto", quant_policy=None,
                 donate_carry="auto", async_dispatch: bool = False,
                 _check: bool = True, **_):
        from repro.models.lm import transformer as tfm
        if _check and not tfm.supports_slot_serving(cfg):
            kinds = sorted({k for _, k, _ in tfm.group_names(cfg)})
            raise NotImplementedError(
                f"TokenRunner needs a token-only arch (no vision/audio "
                f"frontend) with layer kinds in {tfm.SLOT_KINDS}; "
                f"{cfg.name} has family={cfg.family!r}, kinds={kinds}, "
                f"frontend_tokens={cfg.frontend_tokens}")
        self._tfm = tfm
        self.params = params
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.cache_len = int(cache_len)
        self.chunk_tokens = int(prefill_chunk)
        self.pool = CachePool(cfg, n_slots, cache_len, cache_dtype,
                              block_len=block_len, n_blocks=n_blocks,
                              attn_backend=attn_backend,
                              quant_policy=quant_policy)
        self.quant_policy = self.pool.quant_policy
        self.attn_backend = self.pool.attn_backend       # resolved
        self.donate_carry = resolve_donate_carry(donate_carry,
                                                 async_dispatch)
        self.enc_kv: Optional[Dict[str, Dict]] = None    # audio subclass
        self._build_programs()

    def _build_programs(self) -> None:
        cfg, tfm = self.cfg, self._tfm
        reset_spec = self.pool.reset_spec

        # Greedy argmax / sampling happen on-device inside the jitted
        # programs: the host sees token ids, not (B,1,vocab) logits —
        # one dispatch and a tiny transfer per tick. The chunk step
        # unembeds only the requested position (`logits_at`). The pool
        # is donated IN EVERY PLAN (when ``donate_carry`` resolves on —
        # see :func:`resolve_donate_carry` for the async-on-CPU
        # exception): scatter updates alias the input buffers, so the
        # full tick carry (arena + k/v/c scale leaves + pos rows + SSM
        # state — all leaves of ``pool.caches``) never
        # double-allocates within a tick. Block tables and sampling
        # rows arrive as tiny (non-donated) int32/f32 pytrees each
        # call; ``ekv`` is None for token-only archs and the per-slot
        # encoder K/V buffers for the audio runner.
        #
        # ``chain``/``prev`` back the async pipeline's one-tick
        # readback lag: a chained row's input token is the PREVIOUS
        # dispatched tick's on-device output for that row (the host
        # hasn't read it back yet). ``prev`` is never donated — the
        # engine still collects it after the next tick is enqueued.
        # With ``chain`` all-zero the substitution is the identity, so
        # synchronous ticks are token-identical to the pre-pipeline
        # programs.
        backend = self.attn_backend

        def chain_tok(tok, chain, prev):
            col0 = jnp.where(chain > 0, prev, tok[:, 0])
            return tok.at[:, 0].set(col0)

        def decode_greedy(p, pool, tok, t, chain, prev, tables, ekv):
            tok = chain_tok(tok, chain, prev)
            logits, npool = tfm.decode_step_slots(p, pool, tok, t, cfg,
                                                  tables=tables, enc_kv=ekv,
                                                  attn_backend=backend)
            return jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32), \
                npool

        def decode_sampled(p, pool, tok, t, chain, prev, tables, sp, ekv):
            tok = chain_tok(tok, chain, prev)
            logits, npool = tfm.decode_step_slots(p, pool, tok, t, cfg,
                                                  tables=tables, enc_kv=ekv,
                                                  attn_backend=backend)
            return sample_tokens(logits[:, 0, :], sp), npool

        def step_body(p, pool, tok, t, chain, prev, fresh, last, tables,
                      ekv):
            # recycle every freshly admitted row in-step, per the
            # cache's own reset spec (mask stale positions / zero SSM
            # recurrent state; arena bytes are shared and stay put —
            # the empty pos row is what keeps a recycled block's old KV
            # out of attention)
            tok = chain_tok(tok, chain, prev)
            pool = CachePool.mask_fresh_rows(pool, fresh, reset_spec)
            return tfm.decode_step_slots(p, pool, tok, t, cfg,
                                         logits_at=last, tables=tables,
                                         enc_kv=ekv, attn_backend=backend)

        def step_greedy(p, pool, tok, t, chain, prev, fresh, last,
                        tables, ekv):
            logits, npool = step_body(p, pool, tok, t, chain, prev,
                                      fresh, last, tables, ekv)
            return jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32), \
                npool

        def step_sampled(p, pool, tok, t, chain, prev, fresh, last,
                        tables, sp, ekv):
            logits, npool = step_body(p, pool, tok, t, chain, prev,
                                      fresh, last, tables, ekv)
            return sample_tokens(logits[:, 0, :], sp), npool

        # one jitted plan per (kind, width, flavor) bucket: decode-only
        # ticks stay pinned at (B, 1); mixed ticks round their widest
        # chunk to a power-of-two bucket instead of always padding to
        # the full prefill_chunk width
        self.buckets = chunk_buckets(self.chunk_tokens)
        self.plans = PlanCache()
        don = (1,) if self.donate_carry else ()
        self.plans.register(("decode", 1, "greedy"), decode_greedy,
                            donate=don)
        self.plans.register(("decode", 1, "sampled"), decode_sampled,
                            donate=don)
        for w in self.buckets:
            self.plans.register(("mixed", w, "greedy"), step_greedy,
                                donate=don)
            self.plans.register(("mixed", w, "sampled"), step_sampled,
                                donate=don)
        # previous tick's on-device token outputs, (B,) int32 — the
        # chained rows' input source under the one-tick readback lag.
        # Committed to the runtime device: ticks pass the previous jit
        # call's (committed) output here, and a committed-vs-host
        # placement difference is a fresh jit cache signature
        self._prev_tokens = jax.device_put(
            np.zeros((self.n_slots,), np.int32), jax.devices()[0])

    # plan aliases: the widest-bucket programs, kept under the pre-plan
    # attribute names for the analysis targets and retrace audits
    @property
    def _decode_greedy(self):
        return self.plans.fn(("decode", 1, "greedy"))

    @property
    def _decode_sampled(self):
        return self.plans.fn(("decode", 1, "sampled"))

    @property
    def _step_greedy(self):
        return self.plans.fn(("mixed", self.chunk_tokens, "greedy"))

    @property
    def _step_sampled(self):
        return self.plans.fn(("mixed", self.chunk_tokens, "sampled"))

    def plan_stats(self) -> Dict[str, int]:
        return self.plans.stats()

    def warmup(self) -> int:
        """Pre-compile every bucket plan by executing it once over an
        all-pad tick, threading the REAL donated carry through each
        program. Pad rows (``t = -1``) write nothing into the arena —
        their scatter indices clamp out of bounds and drop (see
        ``repro.serving.cache``) — and ``fresh`` is all-zero, so the
        carry round-trips bit-unchanged; any garbage a pad row leaves
        in per-slot recurrent state is wiped by the first real chunk's
        ``fresh`` reset, exactly as for the pad rows every live tick
        already carries. Runs at launch, before traffic."""
        B = self.n_slots
        chain = np.zeros((B,), np.int32)
        # match the runtime argument PLACEMENT exactly: mid-traffic the
        # carry and chained-prev are committed jit outputs, and a
        # committed-vs-host difference is a fresh jit cache signature —
        # warming with host buffers would leave the real ones cold
        dev = jax.devices()[0]
        self.pool.caches = jax.device_put(self.pool.caches, dev)
        prev = jax.device_put(np.zeros((B,), np.int32), dev)
        sp = pack_rows([None] * B)
        warmed = 0
        for key in self.plans.keys():
            kind, w, flavor = key
            if kind not in ("decode", "mixed"):
                continue
            tok = np.zeros((B, w), np.int32)
            t = np.full((B, w), -1, np.int32)
            args = [self.params, self.pool.caches, tok, t, chain, prev]
            if kind == "mixed":
                args += [np.zeros((B,), np.int32), np.zeros((B,), np.int32)]
            args.append(self.pool.device_tables())
            if flavor == "sampled":
                args.append(sp)
            args.append(self.enc_kv)
            toks, self.pool.caches = self.plans.fn(key)(*args)
            toks.block_until_ready()        # compile + execute NOW, not
            self.plans.mark_warmed(key)     # lazily at the first tick
            warmed += 1
        return warmed

    # ------------------------------------------------------------ intake
    def validate(self, req) -> None:
        if getattr(req, "streaming", False):
            raise ValueError(
                f"request {req.rid}: {type(self).__name__} cannot serve a "
                f"StreamingRequest — live signal append is basecaller-"
                f"only (token prompts arrive whole)")
        if req.signal is not None:
            raise ValueError(
                f"request {req.rid}: {type(self).__name__} serves token "
                f"prompts, not squiggle signals (use a basecaller arch)")
        if not req.prompt:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1 (got "
                f"{req.max_new_tokens}); zero-output requests have no "
                f"defined first token")
        # positions written are 0 .. P + max_new - 2: the final generated
        # token is returned but never written back into the cache, so a
        # request that EXACTLY fills the cache must be admitted
        need = len(req.prompt) + req.max_new_tokens - 1
        if need > self.cache_len:
            raise ValueError(
                f"request {req.rid}: prompt+max_new-1 = {need} positions "
                f"exceed cache_len {self.cache_len}")
        if not self.pool.fits(need):
            bl = self.pool.block_len
            raise ValueError(
                f"request {req.rid}: needs {-(-need // bl)} blocks of "
                f"{bl}, more than the arena holds "
                f"({min(self.pool.n_blocks.values())}); raise n_blocks")

    def make_chunks(self, req) -> List[Chunk]:
        # resume-after-preemption re-prefills prompt + already-generated
        # tokens (decode is deterministic — greedy by definition, sampled
        # because the (seed, rid, step) keys replay); fresh requests have
        # out_tokens == [] so this is the same code path
        seq = list(req.prompt) + list(req.out_tokens)
        C = self.chunk_tokens
        return [Chunk(seq[i:i + C], len(seq[i:i + C]))
                for i in range(0, len(seq), C)]

    def admit(self, slot: int, req) -> None:
        pass                                # nothing to stage for tokens

    # ------------------------------------------------------------- pool
    def alloc_pool(self, slot: int, upto: int) -> bool:
        return self.pool.alloc(slot, upto)

    def reset_row(self, slot: int) -> None:
        self.pool.release_slot(slot)

    def pool_util(self) -> float:
        return self.pool.block_stats()["util"]

    # ------------------------------------------------------------ device
    def step(self, works: List[Optional[Any]]) -> List[List[int]]:
        return self.collect(self.dispatch(works))

    def dispatch(self, works: List[Optional[Any]]) -> Any:
        """Enqueue one tick's device work (the jitted plan call returns
        with the tokens still on device); ``collect`` reads them back.
        """
        if any(isinstance(w, PrefillWork) for w in works):
            return self._dispatch_mixed(works)
        return self._dispatch_decode_only(works)

    def collect(self, handle: Any,
                discard: frozenset = frozenset()) -> List[List[int]]:
        works, toks = handle
        # the one intentional round trip per tick (a full tick behind
        # dispatch under the async engine):
        # sync: scheduler needs the tick's emitted tokens on the host
        toks = np.asarray(toks)
        out: List[List[int]] = []
        for i, w in enumerate(works):
            if w is None or i in discard:
                out.append([])
            elif isinstance(w, DecodeWork) or w.final:
                out.append([int(toks[i])])
            else:
                out.append([])
        return out

    def _row(self, w) -> Tuple:
        """Sampling row for a work: the step index is dispatch-time
        state (``w.step``) under the async engine, the booked token
        count otherwise."""
        step = w.step if isinstance(w, DecodeWork) and w.step >= 0 \
            else len(w.req.out_tokens)
        return (w.req.sampling, w.req.rid, step)

    def _dispatch_decode_only(self, works) -> Any:
        """Pure-decode tick: the lockstep (B, 1) plan, token-identical
        to the pre-unified-tick decode path (the greedy-parity gate)."""
        B = self.n_slots
        tok = np.zeros((B, 1), np.int32)
        t = np.full((B, 1), -1, np.int32)
        chain = np.zeros((B,), np.int32)
        rows: List[Optional[Tuple]] = [None] * B
        for i, w in enumerate(works):
            if w is None:
                continue
            tok[i, 0] = w.last_token
            t[i, 0] = w.pos
            chain[i] = int(w.chained)
            rows[i] = self._row(w)
        tables = self.pool.device_tables()
        args = (self.params, self.pool.caches, tok, t, chain,
                self._prev_tokens, tables)
        if any_sampled(rows):
            fn = self.plans.lookup(("decode", 1, "sampled"))
            toks, self.pool.caches = fn(*args, pack_rows(rows), self.enc_kv)
        else:
            fn = self.plans.lookup(("decode", 1, "greedy"))
            toks, self.pool.caches = fn(*args, self.enc_kv)
        self._prev_tokens = toks
        return (works, toks)

    def _dispatch_mixed(self, works) -> Any:
        """Mixed tick: decode rows (column 0) and prefill chunks share
        one (B, C) plan — chunked admissions no longer stall decode
        for the running slots. C is the tick's widest chunk rounded UP
        to its bucket (not always the full prefill_chunk width). Every
        row's logits are read at its own emitting position; only decode
        rows and final chunks commit their token (mid-prompt chunk
        tokens are speculative and discarded, so those rows pack as
        greedy — the sampled program's sort/top-k/Gumbel work would be
        thrown away)."""
        B = self.n_slots
        width = max(len(w.payload) for w in works
                    if isinstance(w, PrefillWork))
        C = round_chunk(width, self.buckets)
        tok = np.zeros((B, C), np.int32)
        t = np.full((B, C), -1, np.int32)
        chain = np.zeros((B,), np.int32)
        fresh = np.zeros((B,), np.int32)
        last = np.zeros((B,), np.int32)
        rows: List[Optional[Tuple]] = [None] * B
        for i, w in enumerate(works):
            if w is None:
                continue
            if isinstance(w, DecodeWork):
                tok[i, 0] = w.last_token
                t[i, 0] = w.pos
                chain[i] = int(w.chained)
                rows[i] = self._row(w)
                continue
            n = len(w.payload)
            tok[i, :n] = w.payload
            t[i, :n] = w.pos + np.arange(n)
            fresh[i] = int(w.fresh)
            last[i] = n - 1
            if w.final and w.req.sampling.temperature > 0:
                rows[i] = self._row(w)
        tables = self.pool.device_tables()
        args = (self.params, self.pool.caches, tok, t, chain,
                self._prev_tokens, fresh, last, tables)
        if any_sampled(rows):
            fn = self.plans.lookup(("mixed", C, "sampled"))
            toks, self.pool.caches = fn(*args, pack_rows(rows), self.enc_kv)
        else:
            fn = self.plans.lookup(("mixed", C, "greedy"))
            toks, self.pool.caches = fn(*args, self.enc_kv)
        self._prev_tokens = toks
        return (works, toks)


# ---------------------------------------------------------------------------
# EncoderPrefixRunner — audio enc-dec (whisper)


class EncoderPrefixRunner(TokenRunner):
    """Serve an encoder-decoder audio arch under the slot machinery.

    Each request carries ``frames`` (the stub log-mel embeddings,
    ``(frontend_tokens, d_model)``). At admission the encoder runs once
    and every decoder layer's cross-attention K/V is scattered into a
    per-slot device buffer (``(n_layers, n_slots, Se, Hkv, hd)`` per
    xdec group); the chunk/decode programs read the slot's rows, so the
    decoder tokens then schedule exactly like a token-only arch —
    chunked prefill, paged self-attention KV, sampling, preemption
    (resume restages the encoder output; ``encode`` is deterministic).
    """

    def __init__(self, params, cfg: ModelConfig, *, cache_dtype, **kw):
        if cfg.family != "audio":
            raise NotImplementedError(
                f"EncoderPrefixRunner serves audio enc-dec archs, not "
                f"{cfg.name} (family={cfg.family!r})")
        super().__init__(params, cfg, cache_dtype=cache_dtype, _check=False,
                         **kw)
        tfm = self._tfm
        Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        Se = cfg.frontend_tokens
        # committed placement from birth, like the pool carry: admit()
        # replaces this with a committed jit output, and the committed
        # flag is part of the jit cache signature
        self.enc_kv = jax.device_put({
            gname: {"k": jnp.zeros((n, self.n_slots, Se, Hkv, hd),
                                   cache_dtype),
                    "v": jnp.zeros((n, self.n_slots, Se, Hkv, hd),
                                   cache_dtype)}
            for gname, kind, n in tfm.group_names(cfg) if kind == "xdec"},
            jax.devices()[0])

        def stage(p, bufs, frames, slot):
            from repro.models.lm import encdec
            enc_out = encdec.encode(p["encoder"], frames[None], cfg)
            new = {}
            for gname in bufs:
                pstack = p["groups"][gname]
                kv = jax.vmap(lambda p1: tfm.enc_kv_for_layer(
                    p1["xattn"], enc_out, cfg))(pstack)
                new[gname] = jax.tree.map(
                    lambda dst, src: jax.lax.dynamic_update_slice_in_dim(
                        dst, src.astype(dst.dtype), slot, axis=1),
                    bufs[gname], kv)
            return new

        # admission-time staging is a tick-adjacent compile too: plan
        # it so warmup pre-pays it and a mid-traffic admit never traces
        self.plans.register(("stage", 0, "enc"), stage, donate=(1,))

    @property
    def _stage(self):
        return self.plans.fn(("stage", 0, "enc"))

    def warmup(self) -> int:
        warmed = super().warmup()
        frames = np.zeros((self.cfg.frontend_tokens, self.cfg.d_model),
                          np.float32)
        # stage zeros into slot 0 pre-traffic: admit() restages the
        # real frames at every admission, so nothing leaks forward
        self.enc_kv = self.plans.fn(("stage", 0, "enc"))(
            self.params, self.enc_kv, frames, np.int32(0))
        jax.block_until_ready(self.enc_kv)
        self.plans.mark_warmed(("stage", 0, "enc"))
        return warmed + 1

    def validate(self, req) -> None:
        super().validate(req)
        Se, d = self.cfg.frontend_tokens, self.cfg.d_model
        if req.frames is None:
            raise ValueError(
                f"request {req.rid}: audio serving needs a `frames` "
                f"payload of shape ({Se}, {d})")
        if tuple(np.shape(req.frames)) != (Se, d):
            raise ValueError(
                f"request {req.rid}: frames shape "
                f"{tuple(np.shape(req.frames))} != ({Se}, {d})")

    def admit(self, slot: int, req) -> None:
        frames = np.asarray(req.frames, np.float32)
        stage = self.plans.lookup(("stage", 0, "enc"))
        self.enc_kv = stage(self.params, self.enc_kv, frames,
                            np.int32(slot))


# ---------------------------------------------------------------------------
# BasecallerRunner — squiggle in, bases out


class BasecallerRunner(ModelRunner):
    """Serve nanopore reads through the CTC basecaller.

    A read's squiggle streams through fixed-size halo-padded windows
    (one jitted forward, one compile); each window's core frames feed an
    incremental CTC merge. With the read-edge masking in
    ``repro.models.basecaller.model``, the concatenated core frames are
    BIT-IDENTICAL to the whole-read offline forward, so greedy serving
    output == offline ``greedy_decode`` exactly (the parity gate; note
    act-quantized configs like rubicall compute activation scales over
    the visible extent, so their chunked frames can differ at ~1e-7 and
    parity is near-exact rather than bitwise). ``beam > 0`` switches to
    the incremental prefix-beam merge — tokens then arrive all at once
    when the read completes, equal to offline ``beam_decode``.

    Reads are NOT autoregressive: there is no decode phase, no KV pool
    (``alloc_pool`` always succeeds, so reads are never preempted), and
    a read finishes with its final chunk. Slot/admission/queue machinery
    — and the metrics — are shared with the LM runners unchanged.

    A tick batches EVERY scheduled slot's window into one fixed-shape
    ``(n_slots, W, 1)`` forward (idle rows are zero windows with
    ``read_len == 0`` — their frames mask to the read-edge value and
    are never read), with per-row ``(B,)`` start/read_len bounds; each
    row's core frames stay bit-identical to the whole-read forward, so
    batching changes throughput, not output.

    Payload contract: ``(window, f_lo, f_hi, start, read_len,
    classify)`` — the window's core frames ``[f_lo, f_hi)`` feed the
    merge (offline chunks always span the full window; streaming spans
    only the newly-STABLE frames under the latency QoS), ``start`` /
    ``read_len`` are the read-edge mask bounds (``read_len`` is the
    :data:`repro.serving.stream.UNBOUNDED` sentinel while a stream's
    end is unknown), and ``classify`` marks windows the read-until
    classifier scores.

    Streaming (``supports_streaming``): :class:`StreamingRequest`
    payloads skip ``make_chunks`` — the engine pulls works from the
    :class:`repro.serving.stream.StreamCursor` built by
    :meth:`open_stream`; ``qos`` picks eager per-frame flushing
    (``"latency"``) or once-per-window forwards (``"accuracy"``).

    Read-until (``read_until=ReadUntil(...)``): the start-of-read
    classifier head runs INSIDE the same jitted tick (the forward
    returns ``(log_probs, on-target logits)``; one readback either
    way). The host accumulates each read's logit over its first
    ``eject_after_chunks`` fully-covered windows and flags the slot for
    ejection when the mean falls below ``threshold``; the engine
    collects the flags via :meth:`pop_ejections` after booking the
    tick's bases.

    Spans (``tracer``, default ``tracing.default()``): ``dispatch``
    (``rows`` carrying a window; ``frames``, the frame-rows the forward
    computes for those rows, summed over its convs' spans —
    ``model.window_spans``), then in ``collect`` ``device_wait``,
    ``readback`` and ``ctc_merge`` (``rows`` merged).
    """

    autoregressive = False
    pool = None
    supports_streaming = True
    supports_async = True

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int,
                 chunk_samples: int = 1024, beam: int = 0,
                 model_state=None, qos: str = "accuracy",
                 read_until=None, tracer: Optional[tracing.Tracer] = None,
                 **_):
        from repro.models.basecaller import model as bc
        from repro.models.basecaller import ctc
        self._bc, self._ctc = bc, ctc
        self.params = params
        self.cfg = cfg
        self.n_slots = int(n_slots)
        self.stride = bc.total_stride(cfg)
        self.halo = bc.chunk_halo(cfg)
        self.core = max(-(-int(chunk_samples) // self.stride), 1) * self.stride
        self.beam = int(beam)
        self.qos = qos
        self.read_until = read_until
        self.tracer = tracer if tracer is not None else tracing.default()
        self.state = model_state if model_state is not None \
            else bc.init_state(cfg)
        self._merge: List[Optional[Any]] = [None] * self.n_slots
        # read-until bookkeeping: per-slot logit accumulator + verdicts
        self._cls_sum = np.zeros((self.n_slots,), np.float64)
        self._cls_n = np.zeros((self.n_slots,), np.int64)
        self._cls_decided = [False] * self.n_slots
        self._eject_pending: set = set()
        if read_until is not None:
            from repro.models.basecaller import classifier as rc
            cls_params = read_until.params

            def fwd(p, s, w, start, read_len):
                return (bc.forward_window(p, s, w, cfg, start, read_len),
                        rc.forward(cls_params, w))
        else:
            def fwd(p, s, w, start, read_len):
                return bc.forward_window(p, s, w, cfg, start, read_len)
        # one window geometry -> one plan; warmup pre-pays the compile
        # and the plan cache's retrace counter covers streaming ticks
        W = self.core + 2 * self.halo
        self._row_frames = bc.span_frames(bc.window_spans(cfg, W))
        self._plan_key = ("window", W, "fwd")
        self.plans = PlanCache()
        self.plans.register(self._plan_key, fwd)

    @property
    def _fwd(self):
        return self.plans.fn(self._plan_key)

    def plan_stats(self) -> Dict[str, int]:
        return self.plans.stats()

    def warmup(self) -> int:
        """Compile the window forward on an all-idle tick (zero windows,
        ``read_len == 0`` masks every frame to the read-edge value — no
        merge state exists yet, nothing is fed)."""
        B, W = self.n_slots, self.core + 2 * self.halo
        out = self.plans.fn(self._plan_key)(
            self.params, self.state, np.zeros((B, W, 1), np.float32),
            np.zeros((B,), np.int32), np.zeros((B,), np.int32))
        jax.block_until_ready(out)
        self.plans.mark_warmed(self._plan_key)
        return 1

    # ------------------------------------------------------------ intake
    def validate(self, req) -> None:
        if getattr(req, "streaming", False):
            return                      # samples arrive later via append()
        if req.signal is None:
            raise ValueError(
                f"request {req.rid}: basecaller serving needs a `signal` "
                f"payload (1-D float squiggle)")
        if np.asarray(req.signal).size < 1:
            raise ValueError(f"request {req.rid}: empty signal")

    def make_chunks(self, req) -> List[Chunk]:
        sig = np.asarray(req.signal, np.float32).reshape(-1)
        wins = self._bc.chunk_windows(sig, self.core, self.halo, self.stride)
        K = self.read_until.eject_after_chunks if self.read_until else 0
        return [Chunk((w, 0, nf, k * self.core - self.halo, sig.shape[0],
                       int(k < K)), ns)
                for k, (w, nf, ns) in enumerate(wins)]

    def admit(self, slot: int, req) -> None:
        self._merge[slot] = (self._ctc.BeamCTCMerge(self.beam) if self.beam
                             else self._ctc.GreedyCTCMerge())
        self._cls_sum[slot] = 0.0
        self._cls_n[slot] = 0
        self._cls_decided[slot] = False

    def open_stream(self, req):
        from repro.serving.stream import StreamCursor
        K = self.read_until.eject_after_chunks if self.read_until else 0
        return StreamCursor(self.core, self.halo, self.stride,
                            qos=self.qos, classify_chunks=K)

    # ------------------------------------------------------------- pool
    def alloc_pool(self, slot: int, upto: int) -> bool:
        return True                     # no KV pool — nothing to run dry

    def reset_row(self, slot: int) -> None:
        self._merge[slot] = None
        self._cls_sum[slot] = 0.0
        self._cls_n[slot] = 0
        self._cls_decided[slot] = False
        self._eject_pending.discard(slot)

    def export_row(self, slot: int):
        """Preemption stash: the merge (cloned — its state is mutated in
        place by feed) plus the read-until accumulator."""
        merge = self._merge[slot]
        return (merge.clone() if merge is not None else None,
                float(self._cls_sum[slot]), int(self._cls_n[slot]),
                self._cls_decided[slot])

    def restore_row(self, slot: int, state) -> None:
        merge, cls_sum, cls_n, decided = state
        self._merge[slot] = merge
        self._cls_sum[slot] = cls_sum
        self._cls_n[slot] = cls_n
        self._cls_decided[slot] = decided

    def flush_row(self, slot: int) -> List[int]:
        merge = self._merge[slot]
        return list(merge.finalize()) if merge is not None else []

    def pop_ejections(self) -> List[int]:
        out = sorted(self._eject_pending)
        self._eject_pending.clear()
        return out

    def pool_util(self) -> float:
        return 0.0

    # ------------------------------------------------------------ device
    def step(self, works: List[Optional[Any]]) -> List[List[int]]:
        return self.collect(self.dispatch(works))

    def dispatch(self, works: List[Optional[Any]]) -> Any:
        """Enqueue the tick's batched window forward; log-probs (and
        classifier logits) stay on device until ``collect``."""
        rows = sum(w is not None for w in works)
        with self.tracer.span("dispatch", rows=rows,
                              frames=rows * self._row_frames):
            B = self.n_slots
            W = self.core + 2 * self.halo
            wins = np.zeros((B, W, 1), np.float32)
            start = np.zeros((B,), np.int32)
            read_len = np.zeros((B,), np.int32)  # 0 = idle row: all masked
            for i, w in enumerate(works):
                if w is None:
                    continue
                window, _, _, st, rl, _ = w.payload
                wins[i] = window
                start[i] = st
                read_len[i] = rl
            fwd = self.plans.lookup(self._plan_key)
            return (works, fwd(self.params, self.state, wins, start,
                               read_len))

    def collect(self, handle: Any,
                discard: frozenset = frozenset()) -> List[List[int]]:
        """Deferred readback + host-side CTC merge / read-until verdict.
        ``discard`` rows (post-ejection speculative windows under the
        async engine) are dropped BEFORE the merge sees them, so an
        ejected read's bases match the synchronous engine exactly."""
        works, dev = handle
        with self.tracer.span("device_wait"):
            # sync: waits for the tick's result, which the readback
            # below waits for anyway — split out so the two are timed
            jax.block_until_ready(dev)
        with self.tracer.span("readback"):
            if self.read_until is not None:
                lp, cls = dev
                # sync: CTC merge (stitch/beam) and the read-until
                # verdict are host-side by design — one readback covers
                # both
                lp, cls = np.asarray(lp), np.asarray(cls)
            else:
                # sync: CTC merge (stitch/beam) is host-side by design —
                # every basecall tick reads the window's log-probs back
                lp = np.asarray(dev)
                cls = None
        rows = sum(w is not None and i not in discard
                   for i, w in enumerate(works))
        with self.tracer.span("ctc_merge", rows=rows):
            return self._merge_rows(works, lp, cls, discard)

    def _merge_rows(self, works, lp, cls, discard) -> List[List[int]]:
        out: List[List[int]] = []
        for i, w in enumerate(works):
            if w is None or i in discard:
                out.append([])
                continue
            _, f_lo, f_hi, _, _, classify = w.payload
            core = lp[i, f_lo:f_hi]
            merge = self._merge[i]
            toks = merge.feed(core if self.beam
                              else np.argmax(core, axis=-1))
            if w.final:
                toks = toks + merge.finalize()
            out.append(toks)
            if cls is not None and classify and not self._cls_decided[i]:
                self._cls_sum[i] += float(cls[i])
                self._cls_n[i] += 1
                ru = self.read_until
                if self._cls_n[i] >= ru.eject_after_chunks:
                    self._cls_decided[i] = True
                    mean = self._cls_sum[i] / self._cls_n[i]
                    if mean < ru.threshold:
                        self._eject_pending.add(i)
        return out


# ---------------------------------------------------------------------------
# Registry


_RUNNERS: List[Tuple[str, Callable[[ModelConfig], bool], Callable]] = []


def register_runner(name: str, predicate: Callable[[ModelConfig], bool],
                    factory: Callable) -> None:
    """Register a serving backend: first predicate match wins."""
    _RUNNERS.append((name, predicate, factory))


def runner_name_for(cfg: ModelConfig) -> Optional[str]:
    for name, pred, _ in _RUNNERS:
        if pred(cfg):
            return name
    return None


def make_runner(params, cfg: ModelConfig, **kw):
    """Build the registered runner for this config. Engine kwargs that a
    runner does not consume (e.g. ``block_len`` for the basecaller) are
    ignored by that runner."""
    for name, pred, factory in _RUNNERS:
        if pred(cfg):
            return factory(params, cfg, **kw)
    raise NotImplementedError(
        f"no serving runner registered for {cfg.name} (family="
        f"{cfg.family!r}, frontend_tokens={cfg.frontend_tokens}); "
        f"registered: {[n for n, _, _ in _RUNNERS]}")


def _token_supported(cfg: ModelConfig) -> bool:
    from repro.models.lm import transformer as tfm
    return tfm.supports_slot_serving(cfg)


register_runner("basecaller", lambda cfg: cfg.family == "basecaller",
                BasecallerRunner)
register_runner("encoder_prefix", lambda cfg: cfg.family == "audio",
                EncoderPrefixRunner)
register_runner("token", _token_supported, TokenRunner)
