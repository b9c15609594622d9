"""Paged block-granular cache pool for the continuous-batching engine.

Layout
------
Per layer group, KV bytes live in a shared BLOCK ARENA: leaves of shape
``(n_layers, n_blocks, ...)`` (GQA ``(n_blocks, Hkv, block_len, hd)``,
MLA ``(n_blocks, block_len, ...)``) instead of one contiguous
``cache_len`` row per slot. A host-side block table per group
(``(n_slots, T)`` int32, T = ceil(ring_len / block_len), -1 = free)
maps each slot's logical block j to an arena block; the tables are tiny
and are shipped into the jitted decode/chunk programs every tick, so
allocation is pure host bookkeeping — zero device dispatches.

What stays per slot (axis 1 of the stacked leaves, as before):

- position leaves (``pos: (n_layers, n_slots, T*block_len)``) — int32
  words, so validity masking and the RESET-SPEC recycle machinery are
  unchanged. This is also the stale-KV story for block recycling: a
  freed arena block keeps its bytes, but the next slot that maps it has
  an empty ``pos`` row until it writes, so the old owner's KV can never
  attend back in.
- SSM recurrent state (``h``/``conv``) — O(1) per row; nothing to page.

Allocation
----------
``alloc(slot, upto)`` assigns arena blocks (LIFO free list, per group)
covering logical positions ``[0, upto)`` — all-or-nothing, so a failed
call changes nothing and the engine can preempt and retry. Sliding-
window groups ring at ``min(window, cache_len)``: their logical blocks
wrap (``t % (T*block_len)``), so a slot never needs more than T blocks
per group no matter how long the request runs. ``release_slot`` returns
every block to the free lists.

Sizing: the contiguous layout reserved ``n_slots * cache_len`` KV
positions per group up front; the paged pool holds ``n_blocks *
block_len`` and hands them out on demand, so short requests stop taxing
the pool at worst-case length and ``n_slots`` can exceed what a
contiguous pool of equal bytes could back. ``block_len=cache_len,
n_blocks=n_slots`` degenerates to exactly the old contiguous semantics
(one block per slot) — the baseline benchmarks compare against.

Row operations (``gather_row`` / ``scatter_row`` / ``mask_fresh`` /
``reset_row``) are driven by two per-leaf spec pytrees from the cache
modules: SLOT AXES (does this leaf have a slot axis, or is it a shared
arena passed through whole?) and RESET SPECS (``keep`` / ``empty`` /
``zero`` — what slot recycling means for the leaf).

Quantized arenas (``CacheQuantPolicy``)
---------------------------------------
Cache precision is a per-layer-group serving policy: each group stores
its K/V (and MLA latent) leaves as ``bf16`` | ``fp8`` | ``int8``.
``fp8`` is a pure storage-dtype change (the kernels already compute in
bf16 for 1-byte caches). ``int8`` adds fp32 SCALE LEAVES to the arena —
``k_scale``/``v_scale`` of shape ``(n_blocks, Hkv, block_len)`` (MLA:
``c_scale``/``kr_scale`` at ``(n_blocks, block_len)``) — written at the
SAME ``(wblk, off)`` indices as the K/V scatter, in the same jitted
step, so a scale can never be newer or older than the bytes it scales.
Recycled blocks need no scale reset: a stale scale multiplies a stale
int8 value into a finite garbage float that the occupant's empty
``pos`` row masks out, exactly like stale KV bytes (scale leaves are
``keep``-reset shared-arena leaves). ``nbytes`` sums EVERY leaf —
arena, scales, positions, SSM state — so equal-bytes comparisons
between policies are honest.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.kernels.paged_attention import EMPTY_POS
from repro.models.lm import transformer as tfm

DEFAULT_BLOCK_LEN = 16

# storage modes a policy may name; fp8 availability is probed at resolve
CACHE_MODES = ("bf16", "fp8", "int8", "fp16", "fp32")


def _mode_dtype(mode: str):
    table = {"bf16": jnp.bfloat16, "fp16": jnp.float16,
             "fp32": jnp.float32, "int8": jnp.int8}
    if mode == "fp8":
        dt = getattr(jnp, "float8_e4m3fn", None)
        if dt is None:
            raise ValueError("fp8 cache mode requested but this JAX build "
                             "has no float8_e4m3fn dtype")
        return dt
    return table[mode]


def _dtype_mode(dtype) -> str:
    """Canonical mode name for a storage dtype (for reports/errors)."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.int8):
        return "int8"
    if dt.itemsize == 1:
        return "fp8"
    return {2: "bf16" if dt == jnp.dtype(jnp.bfloat16) else "fp16",
            4: "fp32"}.get(dt.itemsize, str(dt))


def fp8_supported() -> bool:
    """Can this JAX build materialize an fp8 arena? (Compute is bf16
    either way — storage is the only capability that matters.)"""
    try:
        jnp.zeros((1,), _mode_dtype("fp8")).astype(jnp.float32)
        return True
    except Exception:
        return False


@dataclasses.dataclass(frozen=True)
class CacheQuantPolicy:
    """Per-layer-group cache storage policy: ``default`` mode plus
    ``(group, mode)`` overrides, e.g. ``CacheQuantPolicy("int8")`` or
    ``CacheQuantPolicy("bf16", (("g0_dense", "int8"),))``.

    ``parse`` accepts the CLI grammar: a bare mode (``"int8"``) applies
    pool-wide; ``"g0_dense=int8,g1_moe=fp8"`` overrides named groups
    (an optional bare segment or ``default=...`` sets the default).
    """
    default: str = "bf16"
    overrides: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        for mode in (self.default,) + tuple(m for _, m in self.overrides):
            if mode not in CACHE_MODES:
                raise ValueError(
                    f"unknown cache mode {mode!r}; choose from {CACHE_MODES}")

    @classmethod
    def parse(cls, spec) -> "CacheQuantPolicy":
        if isinstance(spec, CacheQuantPolicy):
            return spec
        if spec is None:
            return cls()
        if not isinstance(spec, str):        # a raw dtype (legacy kwarg)
            return cls(_dtype_mode(spec))
        default, overrides = None, []
        for seg in filter(None, (s.strip() for s in spec.split(","))):
            if "=" in seg:
                g, _, m = seg.partition("=")
                g, m = g.strip(), m.strip()
                if g == "default":
                    default = m
                else:
                    overrides.append((g, m))
            elif default is None:
                default = seg
            else:
                raise ValueError(
                    f"quant policy {spec!r}: more than one default mode")
        return cls(default or "bf16", tuple(overrides))

    def mode_for(self, group: str) -> str:
        return dict(self.overrides).get(group, self.default)

    def dtype_for(self, group: str):
        return _mode_dtype(self.mode_for(group))

    def validate_groups(self, groups) -> None:
        """Reject overrides naming groups the model doesn't have — a
        typo'd policy must fail admission, not silently serve bf16."""
        unknown = [g for g, _ in self.overrides if g not in groups]
        if unknown:
            raise ValueError(
                f"quant policy names unknown layer groups {unknown}; "
                f"this model has {sorted(groups)}")

    def resolve(self) -> "CacheQuantPolicy":
        """Platform check: fp8 entries fall back to bf16 WITH A WARNING
        when the build can't store fp8 (never a crash at serve time)."""
        modes = {self.default, *(m for _, m in self.overrides)}
        if "fp8" not in modes or fp8_supported():
            return self
        warnings.warn("fp8 cache storage unsupported on this platform; "
                      "falling back to bf16", RuntimeWarning, stacklevel=2)
        swap = lambda m: "bf16" if m == "fp8" else m
        return CacheQuantPolicy(
            swap(self.default),
            tuple((g, swap(m)) for g, m in self.overrides))

    def describe(self) -> str:
        parts = [self.default] + [f"{g}={m}" for g, m in self.overrides]
        return ",".join(parts)


def _tree_gather_row(pool, slot, axes):
    """Slice row `slot` (length-1) off axis 1 of every per-slot leaf.

    Shared leaves — block arenas and the per-layer ``window`` scalars —
    pass through whole (the chunk program writes arenas via the block
    table, not by slot row).
    """
    def one(leaf, per_slot):
        if not per_slot or leaf.ndim < 2:
            return leaf
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)
    return jax.tree.map(one, pool, axes)


def _tree_scatter_row(pool, row, slot, axes):
    def one(dst, src, per_slot):
        if not per_slot or dst.ndim < 2:
            return src          # shared leaf: take the updated arena whole
        return jax.lax.dynamic_update_slice_in_dim(
            dst, src.astype(dst.dtype), slot, axis=1)
    return jax.tree.map(one, pool, row, axes)


def _reset_fill(val, how):
    """Constant a leaf is reset to under action ``how`` (None = keep)."""
    if how == "empty":
        return jnp.asarray(EMPTY_POS, val.dtype)
    if how == "zero":
        return jnp.asarray(0, val.dtype)
    if how == "keep":
        return None
    raise ValueError(f"unknown cache reset action {how!r}")


def _tree_mask_fresh(row, fresh, spec):
    """Conditionally invalidate a gathered row tree: where ``fresh`` is
    nonzero, every resettable leaf takes its spec'd reset value (a
    select, not a write — this folds slot recycling into the first
    prefill chunk so admission costs zero extra device dispatches).
    Arena leaves are always ``keep`` and pass through untouched."""
    def one(val, how):
        fill = _reset_fill(val, how)
        if fill is None:
            return val
        return jnp.where(fresh > 0, jnp.broadcast_to(fill, val.shape), val)
    return jax.tree.map(one, row, spec)


def _tree_mask_fresh_rows(row, fresh, spec):
    """Per-ROW variant of :func:`_tree_mask_fresh` over the whole pool:
    ``fresh`` is ``(n_slots,)`` int32 and every row with ``fresh > 0``
    takes its spec'd reset value on every resettable leaf (non-``keep``
    leaves are per slot by construction — positions and SSM state, slot
    axis 1). This is what lets the unified co-batched tick fold slot
    recycling for EVERY freshly admitted row into the one jitted step,
    exactly as the per-slot chunk program did with a scalar flag."""
    def one(val, how):
        fill = _reset_fill(val, how)
        if fill is None:
            return val
        sel = fresh.reshape((1, -1) + (1,) * (val.ndim - 2)) > 0
        return jnp.where(sel, jnp.broadcast_to(fill, val.shape), val)
    return jax.tree.map(one, row, spec)


def carry_leaves(caches) -> List[Any]:
    """Every device-buffer leaf of a tick-carry pytree — arena blocks,
    k/v/c scale leaves, pos rows, SSM state. The donation-accounting
    unit: a jitted step with the carry donated must consume (alias)
    every one of these in place rather than double-allocating the
    arena for the tick's output."""
    return [leaf for leaf in jax.tree.leaves(caches)
            if hasattr(leaf, "is_deleted")]


def donated_fraction(leaves) -> float:
    """Fraction of previously-captured carry leaves a jitted call
    actually consumed (``is_deleted()`` — XLA aliased the input buffer
    into the output). 1.0 means the whole carry was donated; anything
    less is a leaf the tick silently double-buffers."""
    if not leaves:
        return 0.0
    return sum(bool(leaf.is_deleted()) for leaf in leaves) / len(leaves)


def _tree_reset_row(pool, slot, spec):
    """Invalidate one slot in place per the reset spec (non-``keep``
    leaves are per slot by construction: positions and SSM state)."""
    def one(val, how):
        fill = _reset_fill(val, how)
        if fill is None:
            return val
        empty = jnp.broadcast_to(fill, val.shape[:1] + (1,) + val.shape[2:])
        return jax.lax.dynamic_update_slice_in_dim(val, empty, slot, axis=1)
    return jax.tree.map(one, pool, spec)


class CachePool:
    """Device-resident paged pool + host block allocator + jitted row ops.

    Parameters
    ----------
    n_slots : decode batch rows.
    cache_len : per-REQUEST logical capacity (positions a single request
        may write; the block tables address ceil(ring/block_len) blocks).
    block_len : KV positions per arena block. ``cache_len`` degenerates
        to the contiguous layout.
    n_blocks : arena blocks per full-length group. Groups that ring
        shorter (sliding-window) and any explicit oversize are capped at
        ``n_slots * T_g`` (every slot fully backed — more can never be
        used). 0/None = full backing, i.e. the contiguous pool's
        capacity at block granularity.
    attn_backend : decode-attention read path over this pool —
        ``auto``/``xla``/``pallas``, resolved once here
        (``repro.kernels.ops.resolve_attn_backend``) so the pool is the
        single source of truth the runner's jitted programs trace
        against. ``pallas`` computes decode ticks directly from the
        arena (the block table becomes a scalar-prefetch operand);
        ``xla`` is the gather reference.
    quant_policy : per-group cache storage policy — a
        :class:`CacheQuantPolicy`, a policy string (``"int8"``,
        ``"g0_dense=int8,g1_moe=fp8"``), or None to derive a uniform
        policy from the legacy ``cache_dtype`` kwarg. Resolved once
        here (fp8 falls back to bf16 with a warning on unsupported
        builds; overrides naming unknown groups raise).
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, cache_len: int,
                 cache_dtype=jnp.bfloat16, block_len: int = 0,
                 n_blocks: int = 0, attn_backend: str = "auto",
                 quant_policy=None):
        from repro.kernels.ops import resolve_attn_backend
        self.cfg = cfg
        self.attn_backend = resolve_attn_backend(attn_backend)
        self.n_slots = int(n_slots)
        self.cache_len = int(cache_len)
        self.block_len = int(block_len) or min(DEFAULT_BLOCK_LEN, cache_len)
        # {group: blocks per slot (T)} for KV-bearing groups
        self.layout: Dict[str, int] = tfm.paged_group_layout(
            cfg, cache_len, self.block_len)
        self.n_blocks: Dict[str, int] = {
            g: min(int(n_blocks) or self.n_slots * T, self.n_slots * T)
            for g, T in self.layout.items()}
        policy = CacheQuantPolicy.parse(
            quant_policy if quant_policy is not None else cache_dtype)
        all_groups = [g for g, _, _ in tfm.group_names(cfg)]
        policy.validate_groups(all_groups)
        self.quant_policy = policy.resolve()
        self.group_dtypes: Dict[str, Any] = {
            g: self.quant_policy.dtype_for(g) for g in all_groups}
        # committed to the device from birth: ticks replace the carry
        # with (committed) jit outputs, and the committed flag is part
        # of the jit cache signature — an uncommitted initial carry
        # would make every step program's first tick compile a second,
        # never-again-used signature
        self.caches: Dict[str, Any] = jax.device_put(
            tfm.init_caches_paged(
                cfg, self.n_slots, cache_len, self.n_blocks, self.block_len,
                cache_dtype=self.group_dtypes),
            jax.devices()[0])
        self.reset_spec: Dict[str, Any] = tfm.caches_reset_specs(
            cfg, cache_dtype=self.group_dtypes)
        self.slot_axes: Dict[str, Any] = tfm.caches_slot_axes(
            cfg, cache_dtype=self.group_dtypes)
        self._reset = jax.jit(
            functools.partial(_tree_reset_row, spec=self.reset_spec))
        # host allocator state: block tables + LIFO free lists
        self.tables: Dict[str, np.ndarray] = {
            g: np.full((self.n_slots, T), -1, np.int32)
            for g, T in self.layout.items()}
        self.free: Dict[str, List[int]] = {
            g: list(range(nb)) for g, nb in self.n_blocks.items()}
        self.alloc_count = 0            # lifetime block grants (stats)
        self._dev_tables = None         # rebuilt lazily after mutation

    # ------------------------------------------------------- allocator
    def blocks_for(self, n_positions: int) -> Dict[str, int]:
        """Blocks per group needed to back ``n_positions`` written
        positions (ring groups cap at their T — logical blocks wrap)."""
        bl = self.block_len
        return {g: min(-(-max(n_positions, 0) // bl), T)
                for g, T in self.layout.items()}

    def fits(self, n_positions: int) -> bool:
        """Could a request writing ``n_positions`` EVER be served (worst
        case vs total arena size)? Gate at submit — guarantees a lone
        slot can always run to completion, so preemption cannot livelock."""
        need = self.blocks_for(n_positions)
        return all(need[g] <= self.n_blocks[g] for g in need)

    def alloc(self, slot: int, upto: int) -> bool:
        """Ensure blocks covering logical positions ``[0, upto)`` are
        assigned to ``slot`` — all-or-nothing; False leaves the pool
        untouched (the engine preempts and retries)."""
        need = self.blocks_for(upto)
        missing: Dict[str, List[int]] = {}
        for g, j_max in need.items():
            tab = self.tables[g]
            miss = [j for j in range(j_max) if tab[slot, j] < 0]
            if len(miss) > len(self.free[g]):
                return False
            missing[g] = miss
        grew = False
        for g, miss in missing.items():
            for j in miss:
                self.tables[g][slot, j] = self.free[g].pop()
                self.alloc_count += 1
                grew = True
        if grew:
            self._dev_tables = None
        return True

    def release_slot(self, slot: int) -> None:
        """Return every block owned by ``slot`` to the free lists."""
        for g, tab in self.tables.items():
            owned = tab[slot][tab[slot] >= 0]
            if owned.size:
                self.free[g].extend(int(b) for b in owned)
                tab[slot] = -1
                self._dev_tables = None

    def device_tables(self) -> Dict[str, jax.Array]:
        """Block tables as device arrays (cached until the next mutation)."""
        if self._dev_tables is None:
            self._dev_tables = {g: jnp.asarray(t)
                                for g, t in self.tables.items()}
        return self._dev_tables

    def table_rows(self, slot: int) -> Dict[str, jax.Array]:
        """One slot's ``(1, T)`` table rows (the chunk program's view) —
        sliced from the cached device tables, so the prefill hot loop
        pays no host->device transfer while the tables are unchanged."""
        dev = self.device_tables()
        return {g: t[slot:slot + 1] for g, t in dev.items()}

    def block_stats(self) -> Dict[str, float]:
        total = sum(self.n_blocks.values())
        used = total - sum(len(f) for f in self.free.values())
        return {"blocks_used": used, "blocks_total": total,
                "util": used / total if total else 0.0}

    # ------------------------------------------------------ device ops
    def reset_slot(self, slot: int) -> None:
        self.caches = self._reset(self.caches, jnp.asarray(slot, jnp.int32))

    # Functional row ops (used inside the engine's jitted chunk step so
    # gather -> model -> scatter fuses into one program).
    gather_row = staticmethod(_tree_gather_row)
    scatter_row = staticmethod(_tree_scatter_row)
    mask_fresh = staticmethod(_tree_mask_fresh)
    mask_fresh_rows = staticmethod(_tree_mask_fresh_rows)

    def nbytes(self) -> int:
        """Total pool bytes over EVERY leaf — quantized K/V arenas, scale
        leaves, position rows, SSM state — so equal-bytes comparisons
        between cache policies can't hide bookkeeping overhead."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.caches))

    def nbytes_by_class(self) -> Dict[str, int]:
        """``nbytes`` split by leaf class: ``arena`` (K/V/latent bytes),
        ``scales`` (int8 dequant scales), ``pos`` (validity words),
        ``state`` (SSM/other per-slot leaves)."""
        out = {"arena": 0, "scales": 0, "pos": 0, "state": 0}
        for g, tree in self.caches.items():
            paged = g in self.layout
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
                name = str(path[-1].key) if path else ""
                nb = leaf.size * leaf.dtype.itemsize
                if name.endswith("_scale"):
                    out["scales"] += nb
                elif name == "pos":
                    out["pos"] += nb
                elif paged and name in ("k", "v", "c", "k_rope"):
                    out["arena"] += nb
                else:
                    out["state"] += nb
        return out
