"""Overlapped serving runtime: pipelined decode dispatch is a pure
scheduling change (token streams byte-identical to serial on dense and
paged engines, through the router, at temperature 0 and >0), prompt
staging hits/misses/falls back safely, and opportunistic snapshots
never stall a decode round."""

import os

import jax
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_smoke_config
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.serve import (PagedServeEngine, PromptStager, ReplicaRouter,
                         Request, ServeEngine)

SLOTS, MAX_LEN, CHUNK = 3, 40, 2


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("yi-9b")


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


def _requests(cfg, n, seed=1, budgets=(9, 7, 11)):
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, cfg.vocab_size,
                                              6 + (i % 3))),
                    max_new_tokens=budgets[i % len(budgets)])
            for i in range(n)]


def _streams(results):
    return {rid: [int(t) for t in toks] for rid, toks in results.items()}


def _drain(eng):
    out = {}
    while any(s is not None for s in eng.slots):
        for rid, toks in eng.step():
            out[rid] = toks
    return out


def _engine(cfg, params, *, paged=False, pipeline=0, **kw):
    cls = PagedServeEngine if paged else ServeEngine
    if paged:
        kw.setdefault("page_size", 8)
    return cls(cfg, params, max_slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK,
               pipeline=pipeline, **kw)


# -- byte identity --------------------------------------------------------

@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_pipelined_streams_byte_identical(cfg, params, paged, temperature):
    """Serial vs pipeline=2, more requests than slots (mid-flight
    admission while rounds are in flight): identical token streams and
    identical dispatch counts — the overlap changes scheduling only."""
    reqs = _requests(cfg, 2 * SLOTS)
    out = {}
    for pipeline in (0, 2):
        eng = _engine(cfg, params, paged=paged, pipeline=pipeline,
                      temperature=temperature, seed=3)
        out[pipeline] = (_streams(eng.run([Request(r.rid, r.prompt,
                                                   r.max_new_tokens)
                                           for r in reqs])),
                         eng.decode_dispatches, eng.prefill_dispatches)
    assert out[0][0] == out[2][0]
    assert out[0][1:] == out[2][1:]


def test_router_pipelined_identical(cfg, params):
    """The router path: pipelined replicas retire the same streams as
    serial replicas, and stats() surfaces the per-replica overlap."""
    reqs = _requests(cfg, 8, seed=5)
    out = {}
    for pipeline in (0, 2):
        engines = [_engine(cfg, params, pipeline=pipeline, seed=2)
                   for _ in range(2)]
        router = ReplicaRouter(engines, policy="round_robin",
                               max_queue=8)
        out[pipeline] = _streams(router.run(
            [Request(r.rid, r.prompt, r.max_new_tokens) for r in reqs]))
        for row in router.stats():
            assert row["pipeline"] == pipeline
    assert out[0] == out[2]


def test_cancel_and_fork_sync_inflight(cfg, params):
    """cancel() (and paged fork()) first drain in-flight rounds, so the
    returned tokens-so-far match what a serial engine would report."""
    reqs = _requests(cfg, SLOTS, budgets=(12, 12, 12))
    got = {}
    for pipeline in (0, 2):
        eng = _engine(cfg, params, paged=True, pipeline=pipeline)
        for r in reqs:
            eng.admit(Request(r.rid, r.prompt, r.max_new_tokens))
        eng.step()
        eng.step()
        toks = eng.cancel("r1")
        eng.fork("r0", "r0b", max_new_tokens=3)
        rest = {}
        while any(s is not None for s in eng.slots):
            for rid, t in eng.step():
                rest[rid] = [int(x) for x in t]
        got[pipeline] = ([int(x) for x in toks], rest)
    assert got[0] == got[2]


def test_serial_keeps_donation_pipelined_does_not(cfg, params):
    """The double-buffer trade is mode-gated: serial donates the cache
    (in-place update), pipelined must not (a donated still-pending
    input blocks the next enqueue)."""
    assert _engine(cfg, params, pipeline=0)._donate() == (1,)
    assert _engine(cfg, params, pipeline=2)._donate() == ()


# -- prompt staging -------------------------------------------------------

def test_stager_hit_miss_and_fallback():
    st = PromptStager(depth=2)
    st.stage("a", (1, 2, 3))
    assert np.asarray(st.take("a", (1, 2, 3))).tolist() == [[1, 2, 3]]
    st.stage("b", (4, 5))
    # prompt mismatch: staged bytes must never win over the request
    assert np.asarray(st.take("b", (9, 9))).tolist() == [[9, 9]]
    # un-staged rid: inline fallback
    assert np.asarray(st.take("c", (7,))).tolist() == [[7]]
    s = st.stats()
    assert s["hits"] == 1 and s["misses"] == 2 and s["queued"] == 0


def test_stager_depth_eviction():
    st = PromptStager(depth=2)
    for i, rid in enumerate(("a", "b", "c")):
        st.stage(rid, (i,))
    assert st.stats()["queued"] == 2        # oldest ("a") evicted
    assert np.asarray(st.take("a", (0,))).tolist() == [[0]]
    assert st.stats()["misses"] == 1


def test_engine_staging_used_on_admit(cfg, params):
    """Staged admission is counted as a hit and decodes the same stream
    as an identical engine admitting the same request unstaged."""
    reqs = _requests(cfg, 2)
    eng = _engine(cfg, params)
    assert eng.stage(reqs[0]) is True
    eng.admit(reqs[0])
    eng.admit(reqs[1])                      # never staged -> miss
    s = eng.stats()["staging"]
    assert s["hits"] == 1 and s["misses"] == 1
    eng2 = _engine(cfg, params)
    eng2.admit(Request(reqs[0].rid, reqs[0].prompt,
                       reqs[0].max_new_tokens))
    drained = [{rid: [int(x) for x in t]
                for rid, t in _drain(e).items()} for e in (eng, eng2)]
    assert drained[0][reqs[0].rid] == drained[1][reqs[0].rid]


def test_sharded_engine_declines_staging(cfg, params):
    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = make_test_mesh((1, 1))
    eng = ServeEngine(cfg, params, max_slots=SLOTS, max_len=MAX_LEN,
                      chunk=CHUNK, mesh=mesh)
    assert eng.stage(_requests(cfg, 1)[0]) is False


def test_cancel_discards_staged_prompt(cfg, params):
    eng = _engine(cfg, params)
    req = _requests(cfg, 1)[0]
    eng.stage(req)
    assert eng.cancel(req.rid) is None      # never admitted
    assert eng.stager.stats()["queued"] == 0


# -- opportunistic snapshots ----------------------------------------------

def test_snapshot_skip_if_busy(cfg, params, tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ck"), keep=2)
    eng = _engine(cfg, params, pipeline=2)
    assert eng.snapshot(ckpt, step=0) is True
    # immediately queuing another snapshot must not block the serve
    # path: while the background write is live it is skipped, and after
    # wait() the next one lands
    skipped = eng.snapshot(ckpt, step=1)
    ckpt.wait()
    assert eng.snapshot(ckpt, step=2) is True
    ckpt.wait()
    steps = ckpt.all_steps()
    assert 2 in steps
    if skipped:
        assert 1 not in steps
    assert os.path.isdir(tmp_path / "ck")
