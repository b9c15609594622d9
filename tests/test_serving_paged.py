"""Paged block-granular KV pool (repro.serving.cache) + the admission
bugfixes that rode along with it.

Load-bearing invariants on top of tests/test_serving.py's scheduling
parity: block-table indirection is invisible to the math (cross-block
decode == one-shot), recycled arena blocks never leak their previous
owner's KV, preemption-and-resume under block pressure is token-exact,
and admission admits exactly what fits (``prompt + max_new - 1``
positions — the final generated token is never written back).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.models import api
from repro.models.lm import transformer as tfm
from repro.serving import Request, ServingEngine

CACHE_LEN = 48


@pytest.fixture(scope="module")
def qwen():
    cfg = get_config("qwen1.5-4b-smoke")
    params = api.init_params(jax.random.key(0), cfg)
    return cfg, params


def oneshot_greedy(params, cfg, prompt, max_new, cache_len=CACHE_LEN):
    """Reference: single-request prefill + scalar-position decode loop."""
    toks = jnp.asarray([prompt], jnp.int32)
    P = len(prompt)
    logits, caches = tfm.prefill(params, toks, cfg, cache_len=cache_len,
                                 cache_dtype=jnp.float32)
    tok = int(jnp.argmax(logits[0, -1]))
    out = [tok]
    for i in range(max_new - 1):
        lg, caches = tfm.decode_step(params, caches,
                                     jnp.asarray([[tok]], jnp.int32),
                                     jnp.asarray(P + i, jnp.int32), cfg)
        tok = int(jnp.argmax(lg[0, 0]))
        out.append(tok)
    return out


def var_requests(cfg, spec, seed=0):
    rs = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rs.randint(1, cfg.vocab_size, size=pl).tolist(),
                    max_new_tokens=mn)
            for i, (pl, mn) in enumerate(spec)]


def paged_engine(params, cfg, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", CACHE_LEN)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("block_len", 4)
    return ServingEngine(params, cfg, cache_dtype=jnp.float32, **kw)


# ---------------------------------------------------------------- parity


def test_cross_block_decode_parity(qwen):
    """A request whose prefill AND decode cross several block boundaries
    (block_len 4, prompt 6, 10 new tokens -> positions 0..14 span 4
    blocks) matches the one-shot path token-for-token."""
    cfg, params = qwen
    eng = paged_engine(params, cfg)
    reqs = var_requests(cfg, [(6, 10), (10, 7)])
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    for r in reqs:
        want = oneshot_greedy(params, cfg, list(r.prompt), r.max_new_tokens)
        assert done[r.rid].out_tokens == want, r.rid
    # both slots really paged across blocks
    assert eng.pool.alloc_count >= 4 + 3


def test_block_recycling_no_stale_leak(qwen):
    """More block demand than the arena holds, served serially: every
    arena block hosts several requests over the run, and recycled blocks
    must not leak the previous owner's KV into attention (the paged
    analogue of the slot reset-spec tests — the new occupant's empty pos
    row is the guard)."""
    cfg, params = qwen
    eng = paged_engine(params, cfg, cache_len=16, n_blocks=4)
    reqs = var_requests(cfg, [(6, 4)] * 6, seed=1)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    # 6 requests x 3 blocks each through a 4-block arena => recycling
    assert eng.pool.alloc_count >= 18 > 4
    for r in reqs:
        want = oneshot_greedy(params, cfg, list(r.prompt), r.max_new_tokens)
        assert done[r.rid].out_tokens == want, r.rid
    # all blocks returned to the free lists, tables cleared
    for g, nb in eng.pool.n_blocks.items():
        assert len(eng.pool.free[g]) == nb
        assert (eng.pool.tables[g] == -1).all()


def test_paged_attn_matches_contiguous_layout():
    """Unit: the paged gather/scatter indirection is numerically
    invisible — same KV content laid out contiguous vs scattered across
    a poisoned arena via a block table produces identical attention (the
    poison in unwritten/unassigned blocks is masked by the per-slot pos
    row)."""
    from repro.models.lm import attention as A
    cfg = get_config("qwen1.5-4b-smoke")
    key = jax.random.key(2)
    p = A.make_attn_params(key, cfg)
    B, L, bl = 2, 8, 4
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

    cont = A.init_attn_cache_slots(cfg, B, L, dtype=jnp.float32)
    kv = jax.random.normal(key, (2, B, L, Hkv, hd), jnp.float32)
    pos = np.full((B, L), A.EMPTY_POS, np.int32)
    pos[0, :6] = np.arange(6)           # row 0 at position 6
    pos[1, :4] = np.arange(4)           # row 1 at position 4
    cont = {**cont, "k": kv[0], "v": kv[1], "pos": jnp.asarray(pos)}

    paged = A.init_attn_cache_paged(cfg, B, L, n_blocks=5, block_len=bl,
                                    dtype=jnp.float32)
    table = np.array([[2, 4], [1, 3]], np.int32)
    karena = jnp.full_like(paged["k"], 99.0)    # poison unwritten bytes
    varena = jnp.full_like(paged["v"], 99.0)
    hm = lambda a: jnp.swapaxes(a, 0, 1)        # (bl, Hkv, hd) -> arena
    karena = karena.at[2].set(hm(kv[0, 0, 0:4])).at[4, :, 0:2].set(
        hm(kv[0, 0, 4:6]))
    varena = varena.at[2].set(hm(kv[1, 0, 0:4])).at[4, :, 0:2].set(
        hm(kv[1, 0, 4:6]))
    karena = karena.at[1].set(hm(kv[0, 1, 0:4]))
    varena = varena.at[1].set(hm(kv[1, 1, 0:4]))
    paged = {**paged, "k": karena, "v": varena, "pos": jnp.asarray(pos)}

    x = jax.random.normal(key, (B, 1, cfg.d_model), jnp.float32)
    t = jnp.asarray([[6], [4]], jnp.int32)
    out_c, nc_c = A.attn_decode_slots(p, x, cont, t, cfg)
    out_p, nc_p = A.attn_decode_slots(p, x, paged, t, cfg,
                                      table=jnp.asarray(table))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_c),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nc_p["pos"]),
                                  np.asarray(nc_c["pos"]))
    # writes landed in the mapped arena blocks: row 0 pos 6 -> logical
    # block 1 -> arena block 4, offset 2; row 1 pos 4 -> arena block 3,
    # offset 0; untouched block 0 keeps its poison
    np.testing.assert_allclose(np.asarray(nc_p["k"][4, :, 2]),
                               np.asarray(nc_c["k"][0, 6]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(nc_p["k"][3, :, 0]),
                               np.asarray(nc_c["k"][1, 4]), rtol=1e-6)
    assert (np.asarray(nc_p["k"][0]) == 99.0).all()

    # an UNASSIGNED table entry must drop both the KV and the pos write
    # (pos/KV lockstep: a pos marked valid over a clamped gather would
    # admit another block's garbage into attention)
    hole = jnp.asarray(np.array([[2, 4], [1, -1]], np.int32))
    out_h, nc_h = A.attn_decode_slots(p, x, paged, t, cfg, table=hole)
    np.testing.assert_allclose(np.asarray(out_h[0]), np.asarray(out_c[0]),
                               rtol=1e-5, atol=1e-5)
    assert int(nc_h["pos"][1, 4]) == A.EMPTY_POS    # write dropped
    assert (np.asarray(nc_h["k"][3]) == 99.0).all()  # poison intact


def test_preemption_resumes_with_parity(qwen):
    """Two requests whose decode growth outruns a deliberately tight
    arena: the youngest is preempted (blocks freed, requeued) and later
    resumes by re-prefilling prompt + generated tokens — final tokens
    must still match the one-shot path exactly."""
    cfg, params = qwen
    eng = paged_engine(params, cfg, cache_len=24, n_blocks=6)
    reqs = var_requests(cfg, [(8, 8), (8, 8)], seed=3)
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert eng.metrics.preempts > 0     # the pool really ran dry
    for r in reqs:
        want = oneshot_greedy(params, cfg, list(r.prompt), r.max_new_tokens)
        assert done[r.rid].out_tokens == want, r.rid


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["mamba2-130m-smoke", "hymba-1.5b-smoke",
                                  "deepseek-v3-671b-smoke"])
def test_paged_cross_arch_parity(arch):
    """SSM/hybrid/MLA families through small blocks and a tight arena:
    cross-block decode, sliding-window ring wrap (hymba), block
    recycling and possible preemption — tokens identical to one-shot."""
    cfg = get_config(arch)
    params = api.init_params(jax.random.key(0), cfg)
    eng = paged_engine(params, cfg, n_blocks=8)
    reqs = var_requests(cfg, [(5, 6), (11, 3), (16, 8), (7, 1), (9, 5)])
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    for r in reqs:
        want = oneshot_greedy(params, cfg, list(r.prompt), r.max_new_tokens)
        assert done[r.rid].out_tokens == want, (arch, r.rid)


# ----------------------------------------------------- admission bugfixes


def test_boundary_admission_exact_fit(qwen):
    """Regression (off-by-one): a request with prompt + max_new - 1 ==
    cache_len writes positions 0..cache_len-1 — it exactly fits and must
    be ADMITTED (the final generated token is never written back). One
    more token must still be rejected."""
    cfg, params = qwen
    eng = paged_engine(params, cfg, cache_len=16, block_len=16)
    fit = var_requests(cfg, [(8, 9)], seed=5)[0]        # 8 + 9 - 1 == 16
    eng.submit(fit)
    done = eng.run()
    want = oneshot_greedy(params, cfg, list(fit.prompt), 9, cache_len=16)
    assert done[fit.rid].out_tokens == want
    with pytest.raises(ValueError):
        eng.submit(Request(rid=9, prompt=[1] * 8, max_new_tokens=10))


def test_zero_max_new_tokens_rejected(qwen):
    """Regression: max_new_tokens == 0 used to emit one token anyway
    (the prefill argmax was appended before consulting Request.done).
    The engine now rejects < 1 up front with a clear error."""
    cfg, params = qwen
    eng = paged_engine(params, cfg)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=0))
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(rid=1, prompt=[1, 2, 3], max_new_tokens=-2))
    assert not eng.queue


def test_oversized_block_demand_rejected(qwen):
    """A request needing more blocks than the whole arena holds can
    never run (even with preemption) and must be rejected at submit."""
    cfg, params = qwen
    eng = paged_engine(params, cfg, cache_len=32, n_blocks=4)  # 16 positions
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(Request(rid=0, prompt=[1] * 20, max_new_tokens=5))


# ------------------------------------------------- bounded host growth


def test_bounded_history_and_drain(qwen):
    """history_limit keeps every host-side structure flat (slot history,
    completed map, metrics reservoirs) while aggregate counters stay
    exact; drain_completed hands over and forgets."""
    cfg, params = qwen
    eng = paged_engine(params, cfg, history_limit=2)
    reqs = var_requests(cfg, [(4, 3)] * 6, seed=6)
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert len(eng.completed) <= 2
    assert all(len(h) <= 2 for h in eng.slot_history)
    assert len(eng.metrics.requests) <= 2 + eng.n_slots
    assert eng.metrics.queue_depth_samples.maxlen == 2
    s = eng.metrics.summary()
    assert s["requests_done"] == 6                      # counters exact
    assert s["generated_tokens"] == sum(r.max_new_tokens for r in reqs)
    drained = eng.drain_completed()
    assert drained and not eng.completed
    assert eng.drain_completed() == {}


def test_pool_utilization_reported(qwen):
    cfg, params = qwen
    eng = paged_engine(params, cfg)
    for r in var_requests(cfg, [(6, 5)] * 3, seed=7):
        eng.submit(r)
    eng.run()
    s = eng.metrics.summary()
    assert 0.0 < s["pool_util_max"] <= 1.0
    assert 0.0 <= s["pool_util_mean"] <= s["pool_util_max"]
    assert eng.pool.block_stats()["blocks_used"] == 0   # all returned
