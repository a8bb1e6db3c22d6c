"""Paged-KV serve path: property-based differential tests.

Three layers, mirroring the subsystem's own:

* **PagePool invariants** — randomized admit/write/fork/release
  schedules against a host-side contents model: refcounts conserve
  exactly (``check_conservation``), copy-on-write never mutates a page
  another holder can see, prefix matches always hand back pages holding
  the expected chain content, and releasing everything leaks nothing.
* **Engine differential** — randomized admission/decode/cancel
  schedules applied to a dense :class:`ServeEngine` and a
  :class:`PagedServeEngine` must produce token-identical streams (the
  paged ref decode path falls through to the same dense computation, so
  equality is exact, not approximate). Fork clones must continue
  exactly like their greedy parent, and CoW must leave the parent
  stream untouched.
* **MemTier pricing** — the paged traffic classes stay finite, ordered
  Grace <= SPR <= Zen 4 (the WA-priced store side), and recycled
  admission strictly undercuts the dense zero-fill on every registered
  machine.

Runs under real hypothesis or the deterministic stub
(tests/_hypothesis_stub.py) — conftest tags each test with the engine
that drove it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.core.machine import registered_names
from repro.models import model as M
from repro.serve import (PagedServeEngine, PagePool, Request, ServeEngine,
                         cow_fork_traffic, make_chunked_decode_step,
                         page_admission_traffic, page_gather_traffic,
                         plan_chunk_size)
from repro.serve import pages as PG

from _pool_hlo import pool_moves, pool_shapes

PAPER_CPUS = ["neoverse_v2", "golden_cove", "zen4"]
PS = 4                                   # page size used throughout
MAX_LEN = 24
CHUNK = 3
SLOTS = 2


# plain cached helpers instead of pytest fixtures: @given-wrapped tests
# (stub or real) cannot take fixture parameters through the wrapper
@functools.lru_cache(maxsize=None)
def _cfg():
    return get_smoke_config("yi-9b")     # dense FFN: streams bit-exact


@functools.lru_cache(maxsize=None)
def _params():
    return M.init_params(_cfg(), jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _engines():
    """One dense/paged pair reused across examples (compile once).

    Reuse is safe — and deliberate: after a drained schedule both
    engines have every slot free, and the paged pool's only residue is
    its retained prefix index, so later examples exercise cross-example
    prefix sharing on top of the differential check.
    """
    kw = dict(max_slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK, seed=0)
    return (ServeEngine(_cfg(), _params(), **kw),
            PagedServeEngine(_cfg(), _params(), page_size=PS, **kw))


# a small closed set of prompts: repeats trigger prefix sharing, jit
# retraces stay bounded by the distinct lengths. Ids must stay inside
# the smoke vocab (512): OOB ids NaN-fill the embedding gather, which
# used to make BOTH engines emit all-NaN logits (greedy argmax -> 0 on
# each, so the differential held vacuously); admission now rejects
# them and the serve guard quarantines any stream that slips through.
_PROMPT_RNG = np.random.default_rng(42)
PROMPTS = [tuple(int(t) for t in _PROMPT_RNG.integers(0, 512, n))
           for n in (3, 4, 6, 8, 8, 9)]


# ---------------------------------------------------------------------------
# PagePool invariants under random schedules (host-only, no device work)
# ---------------------------------------------------------------------------

def _chain_val(prompt, j, ps=PS):
    """Model content of full prompt page j: its chain prefix."""
    return ("chain", prompt[:(j + 1) * ps])


_POOL_OPS = st.lists(
    st.tuples(st.integers(0, 4), st.integers(0, 5), st.integers(0, 7)),
    min_size=1, max_size=50)


@given(_POOL_OPS)
def test_pool_schedule_invariants(ops):
    """Random admit/write/fork/release schedules conserve the pool and
    never let a write reach a page another holder still sees."""
    n_pages = 10
    pool = PagePool(n_pages, PS)
    contents: dict = {}                   # phys -> model payload
    holders: list = []                    # [{"pages", "prompt", "view"}]
    stamp = 0
    for kind, a, b in ops:
        kind %= 5
        if kind == 0:                                     # admit
            prompt = PROMPTS[a % len(PROMPTS)]
            npg = -(-len(prompt) // PS)
            if pool.available() < npg:
                continue
            shared = pool.match_prefix(prompt)
            for j, p in enumerate(shared):                # matched pages
                assert contents[p] == _chain_val(prompt, j), \
                    f"stale prefix match on page {p}"
            fresh = pool.allocate(npg - len(shared))
            held = list(shared) + list(fresh)
            full = len(prompt) // PS
            view = {}
            for j in range(npg):
                if j >= len(shared):
                    contents[held[j]] = (_chain_val(prompt, j)
                                         if j < full else ("partial", stamp))
                    stamp += 1
                view[j] = contents[held[j]]
            pool.register_prefix(prompt, held[:full])
            holders.append({"pages": held, "prompt": prompt, "view": view})
        elif kind == 1 and holders:                       # release
            h = holders.pop(a % len(holders))
            pool.release(h["pages"])
        elif kind == 2 and holders:                       # fork
            h = holders[a % len(holders)]
            pool.fork(h["pages"])
            holders.append({"pages": list(h["pages"]),
                            "prompt": h["prompt"],
                            "view": dict(h["view"])})
        elif kind == 3 and holders:                       # write (maybe CoW)
            h = holders[a % len(holders)]
            lg = b % len(h["pages"])
            if pool.available() < 1:
                continue
            page, copied = pool.prepare_write(h["pages"][lg])
            if copied:
                contents[page] = contents[h["pages"][lg]]
                h["pages"][lg] = page
            contents[page] = ("w", stamp)
            h["view"][lg] = contents[page]
            stamp += 1
        else:                                             # audit
            pool.check_conservation([h["pages"] for h in holders])
        # CoW soundness: every holder still sees exactly its own view
        for h in holders:
            for lg, p in enumerate(h["pages"]):
                assert contents[p] == h["view"][lg], \
                    f"holder view of logical page {lg} mutated"
    pool.check_conservation([h["pages"] for h in holders])
    for h in holders:                     # full teardown leaks nothing
        pool.release(h["pages"])
    pool.check_conservation([])


def test_pool_exhaustion_and_lru_eviction():
    pool = PagePool(2, PS)
    prompt = PROMPTS[1]                   # 4 tokens = 1 full page
    held = pool.match_prefix(prompt) or pool.allocate(1)
    pool.register_prefix(prompt, held[:1])
    pool.release(held)                    # page survives as retained index
    assert pool.available() == 2          # 1 free + 1 evictable
    got = pool.allocate(2)                # forces the LRU eviction
    assert len(got) == 2
    assert pool.stats["evictions"] == 1
    assert pool.match_prefix(prompt) == []   # evicted = no longer matchable
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.allocate(1)
    pool.release(got)
    with pytest.raises(RuntimeError, match="unheld"):
        pool.release(got[:1])


# ---------------------------------------------------------------------------
# Engine differential: paged == dense, token for token
# ---------------------------------------------------------------------------

def _apply_schedule(eng, sched):
    """Deterministically interpret one schedule; returns {rid: tokens}."""
    results, rid = {}, 0
    for kind, a, b in sched:
        kind %= 3
        if kind == 0 and eng.free_slots():                # admit
            prompt = PROMPTS[a % len(PROMPTS)]
            budget = 1 + b % 8
            eng.admit(Request(f"r{rid}", prompt, budget))
            rid += 1
        elif kind == 1:                                   # decode round
            for r, toks in eng.step():
                results[r] = toks
        elif kind == 2:                                   # cancel
            act = sorted(s.rid for s in eng.slots if s is not None)
            if act:
                r = act[a % len(act)]
                results[r] = eng.cancel(r)
    while any(s is not None for s in eng.slots):          # drain
        for r, toks in eng.step():
            results[r] = toks
    return results


_ENGINE_OPS = st.lists(
    st.tuples(st.integers(0, 2), st.integers(0, 5), st.integers(0, 7)),
    min_size=2, max_size=10)


@given(_ENGINE_OPS)
def test_paged_engine_differential(ops):
    """The same admission/decode/cancel schedule on dense and paged
    engines yields identical rids and bit-identical token streams."""
    dense, paged = _engines()
    rd = _apply_schedule(dense, ops)
    rp = _apply_schedule(paged, ops)
    paged.check_pool()
    assert set(rd) == set(rp)
    for r in rd:
        np.testing.assert_array_equal(
            rd[r], rp[r], err_msg=f"stream {r} diverged under paging")


def test_shared_prefix_admission_copies_nothing():
    """Identical prompts map the same physical pages: the second
    admission allocates only the partial page and copies zero pages."""
    eng = PagedServeEngine(_cfg(), _params(), max_slots=SLOTS, max_len=MAX_LEN,
                           chunk=CHUNK, page_size=PS)
    prompt = PROMPTS[4]                   # 8 tokens = 2 full pages
    eng.admit(Request("a", prompt, 2))
    before = dict(eng.pool.stats)
    eng.admit(Request("b", prompt, 2))
    after = eng.pool.stats
    assert after["shared_maps"] - before["shared_maps"] == 2
    assert after["cow_copies"] == before["cow_copies"] == 0
    allocs = (after["fresh_allocs"] + after["recycled_allocs"]
              - before["fresh_allocs"] - before["recycled_allocs"])
    assert allocs == 0                    # fully shared: no new pages
    assert list(eng.block_tables[0][:2]) == list(eng.block_tables[1][:2])
    res = eng.run([])
    eng.check_pool()
    assert np.array_equal(res["a"], res["b"])


def test_fork_cow_parent_stream_undisturbed():
    """A forked clone decodes exactly like its parent (greedy), CoW
    fires on the shared partial page, and the parent's stream matches a
    solo dense run bit for bit."""
    prompt = PROMPTS[5]                   # 9 tokens: partial last page
    eng = PagedServeEngine(_cfg(), _params(), max_slots=SLOTS, max_len=MAX_LEN,
                           chunk=CHUNK, page_size=PS)
    eng.admit(Request("x", prompt, 8))
    eng.fork("x", "y")
    res = _apply_schedule(eng, [])
    eng.check_pool()
    assert eng.pool.stats["cow_copies"] >= 1
    np.testing.assert_array_equal(res["x"], res["y"])
    dense = ServeEngine(_cfg(), _params(), max_slots=SLOTS, max_len=MAX_LEN,
                        chunk=CHUNK)
    ref = dense.run([Request("x", prompt, 8)])
    np.testing.assert_array_equal(res["x"], ref["x"])


def test_cancel_recycles_pages():
    eng = PagedServeEngine(_cfg(), _params(), max_slots=SLOTS, max_len=MAX_LEN,
                           chunk=CHUNK, page_size=PS, share_prefixes=False)
    eng.admit(Request("a", PROMPTS[3], 8))
    held = [int(p) for p in eng.block_tables[0] if p >= 0]
    assert held
    out = eng.cancel("a")
    assert out is not None and out.shape[0] >= 1
    assert eng.cancel("a") is None
    eng.check_pool()
    assert all(eng.pool.refcount[p] == 0 for p in held)
    eng.admit(Request("b", PROMPTS[3], 4))      # recycles, never zero-fills
    assert eng.pool.stats["recycled_allocs"] >= 1
    res = eng.run([])
    dense = ServeEngine(_cfg(), _params(), max_slots=SLOTS, max_len=MAX_LEN,
                        chunk=CHUNK)
    ref = dense.run([Request("b", PROMPTS[3], 4)])
    np.testing.assert_array_equal(res["b"], ref["b"])


# unit shapes the stacked pools have to serve: recurrent state beside a
# pool (and a tail pool outside the stack), and two pools per repeat
_STACKS = {
    "hybrid": dict(block_pattern=("attn", "mamba"), n_layers=5),
    "two_attn": dict(block_pattern=("attn_local", "attn"), n_layers=4),
}


@pytest.mark.parametrize("name", sorted(_STACKS))
def test_stacked_pool_step_matches_dense(name):
    """Every repeat of the layer scan writes and reads the stacked pools
    at its own index: the paged engine serves the dense engine's tokens
    when the unit holds recurrent state beside a pool, with a tail pool
    outside the stack, or two pools (one window-local) per repeat."""
    cfg = dataclasses.replace(_cfg(), **_STACKS[name])
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    kw = dict(max_slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK, seed=0)
    reqs = [Request(f"r{i}", PROMPTS[i], 9) for i in (5, 2, 3)]
    ref = ServeEngine(cfg, params, **kw).run(reqs)
    eng = PagedServeEngine(cfg, params, page_size=PS, **kw)
    got = eng.run(reqs)
    eng.check_pool()
    assert set(got) == set(ref)
    for r in ref:
        np.testing.assert_array_equal(got[r], ref[r], err_msg=r)


# ---------------------------------------------------------------------------
# Paged decode step: donation stays in place
# ---------------------------------------------------------------------------

def test_paged_decode_cache_update_stays_in_place():
    """The paged chunk step must not move a whole page pool, per token
    or per dispatch: with donation the pools alias in and out, and no
    copy, slice or update-slice of a pool's shape (stacked or one
    layer's) is left anywhere in the program, loop bodies included."""
    n_pages, pps = SLOTS * (MAX_LEN // PS) + 1, MAX_LEN // PS
    step = make_chunked_decode_step(_cfg(), CHUNK, paged=True)
    cshapes = PG.paged_cache_shapes(_cfg(), SLOTS, n_pages, PS)
    args = (M.param_shapes(_cfg()), cshapes,
            jax.ShapeDtypeStruct((SLOTS, pps), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    shapes = pool_shapes(_cfg(), cshapes)

    donated = jax.jit(step, donate_argnums=(1,)).lower(
        *args).compile().as_text()
    plain = jax.jit(step).lower(*args).compile().as_text()
    assert "input_output_alias" in donated
    # detector sanity: undonated, the step must copy K and V into its
    # outputs, and the detector must see those copies
    assert len(pool_moves(plain, shapes)) >= 2
    assert pool_moves(donated, shapes) == []    # in place with donation


# ---------------------------------------------------------------------------
# MemTier pricing of the paged traffic classes
# ---------------------------------------------------------------------------

def test_page_gather_pricing_ordered_and_bounded():
    rows = page_gather_traffic(_cfg(), 4, 256, 64, 8, machines=PAPER_CPUS)
    by = {r["machine"]: r for r in rows}
    assert set(by) == set(PAPER_CPUS)
    for r in rows:
        assert r["read_ratio"] > 1.0         # quarter-full cache: 4x fewer
        assert r["gather_seconds"] > 0.0
        assert r["table_read_bytes"] < r["gather_read_bytes"]
    # paper ordering rides on the WA-priced store side of the step
    assert (by["neoverse_v2"]["total_bytes"]
            <= by["golden_cove"]["total_bytes"]
            <= by["zen4"]["total_bytes"])
    # full cache: the gather equals the dense payload exactly; the only
    # overhead left is the block-table entries themselves, so the ratio
    # sits just below 1 (the dense path never issues that dependent load)
    full = page_gather_traffic(_cfg(), 4, 256, 256, 8, machines=PAPER_CPUS)
    for r in full:
        assert r["gather_read_bytes"] == r["dense_read_bytes"]
        assert 0.99 < r["read_ratio"] < 1.0


def test_cow_pricing_grace_cheapest():
    rows = cow_fork_traffic(_cfg(), 8, n_copies=3, machines=PAPER_CPUS)
    by = {r["machine"]: r for r in rows}
    for r in rows:
        assert r["total_bytes"] >= 2 * r["read_bytes"] - 1e-9  # r+w floor
        assert r["copy_seconds"] > 0.0
    assert (by["neoverse_v2"]["total_bytes"]
            <= by["golden_cove"]["total_bytes"]
            <= by["zen4"]["total_bytes"])


def test_recycled_admission_beats_zero_fill_everywhere():
    """On every registered machine, admitting into recycled pages is
    strictly cheaper than the dense horizon zero-fill whenever the
    prompt's pages cover less than the horizon."""
    rows = page_admission_traffic(_cfg(), 20, 256, 8,
                                  machines=registered_names())
    assert len(rows) >= 3
    for r in rows:
        assert r["recycled_bytes"] < r["zero_fill_bytes"], r["machine"]
        assert r["recycled_bytes"] <= r["fresh_bytes"]
        assert r["savings_ratio"] > 1.0
    # sharing shrinks it further; full sharing stores nothing
    shared = page_admission_traffic(_cfg(), 16, 256, 8, shared_pages=2,
                                    machines=PAPER_CPUS)
    for r in shared:
        assert r["shared_pages"] == 2
        assert r["recycled_bytes"] < rows[0]["zero_fill_bytes"]
    allshared = page_admission_traffic(_cfg(), 16, 256, 8, shared_pages=4,
                                       machines=PAPER_CPUS)
    assert all(r["recycled_bytes"] == 0.0 for r in allshared)


def test_planner_threads_page_size():
    dense = plan_chunk_size(_cfg(), 4, 256, occupancy=40)
    paged = plan_chunk_size(_cfg(), 4, 256, occupancy=40, page_size=8)
    assert dense.page_size is None and paged.page_size == 8
    assert paged.chunk >= 1
    # page-grid rounding can only tighten the bound vs the dense KV
    # block (pages are <= the autotuned block in every current tuning)
    for name, t in paged.per_machine.items():
        assert t <= dense.per_machine[name] + 1e-12


def test_paged_memory_scales_with_pool_not_horizon():
    """fig8's sizing gate at unit scale: dense KV bytes grow with the
    horizon, the page pool's with live pages only."""
    d1 = PG.dense_kv_bytes(_cfg(), 4, 256)
    d2 = PG.dense_kv_bytes(_cfg(), 4, 512)
    assert d2 == 2 * d1
    p1 = PG.paged_kv_bytes(_cfg(), 32, 8)
    assert PG.paged_kv_bytes(_cfg(), 32, 8) == p1   # horizon-free
    assert PG.paged_kv_bytes(_cfg(), 64, 8) == 2 * p1
    # pool sized for the live tokens of 4 quarter-full slots beats the
    # dense allocation by ~4x
    live_pages = 4 * (64 // 8)
    assert d1 / PG.paged_kv_bytes(_cfg(), live_pages, 8) > 3.9
