"""The serving program's host spans (``repro.serve.spans``): under a
profiler trace a router over a toy paged engine writes every
``serve.*`` span with its stats, nested as the code nests and tied
together by request id, and the stats add up to what the engine and
its page pool count themselves; with no trace running no span is made
and no stat is computed."""

import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve import (FaultTolerantRouter, PagedServeEngine,
                         ReplicaRouter, Request, ServeEngine)
from repro.serve import spans as S

SLOTS, MAX_LEN, CHUNK, PS = 2, 40, 3, 4
SHARED = (11, 12, 13, 14, 15, 16, 17, 18)   # two full pages

#: span -> the stats it carries
STATS = {
    "serve.submit": {"rid", "replica"},
    "serve.stage": {"rid", "tokens", "issued"},
    "serve.round": {"queued", "active"},
    "serve.admit": {"rid", "slot", "prompt_tokens", "prefix_hit_tokens",
                    "emitted"},
    "serve.prefill": {"tokens"},
    "serve.first_token": set(),
    "serve.insert": {"fresh_pages", "shared_pages"},
    "serve.decode": {"emitted", "retired"},
    "serve.pre_dispatch": {"pages_allocated", "cow_copies"},
    "serve.dispatch": {"slots", "chunk", "ctx_tokens"},
    "serve.readback": set(),
}


@pytest.fixture(scope="module")
def cfg():
    return get_smoke_config("yi-9b")


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


def _requests():
    """A shared-prefix pair, a one-token request and two more: more
    requests than slots, so some wait in the router's queue."""
    rng = np.random.default_rng(4)

    def ids(n):
        return tuple(int(t) for t in rng.integers(20, 200, n))
    return [Request("a", SHARED + (1, 2), 7),
            Request("b", SHARED + (3,), 5),
            Request("c", ids(6), 1),
            Request("d", ids(7), 5),
            Request("e", ids(5), 4)]


def _paged(cfg, params, pipeline=0):
    return PagedServeEngine(cfg, params, max_slots=SLOTS, max_len=MAX_LEN,
                            chunk=CHUNK, page_size=PS, pipeline=pipeline)


def _events(tdir) -> list:
    """Every ``serve.*`` host event of the trace in ``tdir`` as
    (name, start ns, end ns, stats), in start order."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(S.PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _traced(router, reqs, tdir):
    jax.profiler.start_trace(str(tdir))
    try:
        results = router.run(reqs)
    finally:
        jax.profiler.stop_trace()
    return _events(tdir), results


@pytest.fixture(scope="module", params=[0, 2], ids=["serial", "pipelined"])
def traced(request, cfg, params, tmp_path_factory):
    eng = _paged(cfg, params, pipeline=request.param)
    router = ReplicaRouter([eng])
    evs, results = _traced(router, _requests(),
                           tmp_path_factory.mktemp("trace"))
    return evs, results, eng


def named(evs, name):
    return [e for e in evs if e[0] == name]


def inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_every_span_is_recorded_with_its_stats(traced):
    evs, _, _ = traced
    assert {e[0] for e in evs} == set(STATS)
    for name, _, _, stats in evs:
        assert set(stats) == STATS[name], name


@pytest.mark.parametrize("child,parent", [
    ("serve.stage", "serve.submit"),
    ("serve.admit", "serve.round"),
    ("serve.decode", "serve.round"),
    ("serve.prefill", "serve.admit"),
    ("serve.first_token", "serve.admit"),
    ("serve.insert", "serve.admit"),
    ("serve.pre_dispatch", "serve.decode"),
    ("serve.dispatch", "serve.decode"),
    ("serve.readback", "serve.decode"),
])
def test_spans_nest(traced, child, parent):
    evs, _, _ = traced
    outer = named(evs, parent)
    kids = named(evs, child)
    assert kids
    assert all(any(inside(c, p) for p in outer) for c in kids)


def test_request_spans_share_the_rid(traced):
    evs, results, _ = traced
    submit = {e[3]["rid"]: e for e in named(evs, "serve.submit")}
    admit = {e[3]["rid"]: e for e in named(evs, "serve.admit")}
    stage = {e[3]["rid"] for e in named(evs, "serve.stage")}
    assert set(submit) == set(admit) == stage == set(results)
    for rid, sub in submit.items():
        assert sub[3]["replica"] == 0
        assert admit[rid][1] >= sub[2]        # queued, then admitted


def test_emitted_adds_up_to_the_tokens_returned(traced):
    evs, results, _ = traced
    emitted = sum(e[3].get("emitted", 0) for e in evs)
    assert emitted == sum(len(t) for t in results.values())
    decode = sum(e[3]["emitted"] for e in named(evs, "serve.decode"))
    assert decode == emitted - len(named(evs, "serve.admit"))
    assert sum(e[3]["retired"] for e in named(evs, "serve.decode")) \
        == len(results)


def test_prefix_hits_match_the_pool(traced):
    evs, _, eng = traced
    admits = named(evs, "serve.admit")
    hit = sum(e[3]["prefix_hit_tokens"] for e in admits)
    assert hit == eng.pool.stats["shared_maps"] * PS == len(SHARED)
    by_rid = {e[3]["rid"]: e[3] for e in admits}
    assert by_rid["b"]["prefix_hit_tokens"] == len(SHARED)
    assert by_rid["b"]["prompt_tokens"] == len(SHARED) + 1
    shared = sum(e[3]["shared_pages"] for e in named(evs, "serve.insert"))
    assert shared == eng.pool.stats["shared_maps"]


def test_page_and_dispatch_counts_match_the_engine(traced):
    evs, _, eng = traced
    st = eng.pool.stats
    pages = sum(e[3]["fresh_pages"] for e in named(evs, "serve.insert"))
    pages += sum(e[3]["pages_allocated"]
                 for e in named(evs, "serve.pre_dispatch"))
    assert pages == st["fresh_allocs"] + st["recycled_allocs"]
    cow = sum(e[3]["cow_copies"] for e in named(evs, "serve.pre_dispatch"))
    assert cow == st["cow_copies"]
    assert len(named(evs, "serve.dispatch")) == eng.decode_dispatches
    assert len(named(evs, "serve.prefill")) == eng.prefill_dispatches
    assert len(named(evs, "serve.first_token")) == eng.prefill_dispatches
    assert all(e[3]["chunk"] == CHUNK for e in named(evs, "serve.dispatch"))


def test_round_and_dispatch_stats(traced):
    """The first round finds every request queued and no slot busy; its
    dispatch decodes both slots from their prompt ends."""
    evs, _, _ = traced
    rounds = named(evs, "serve.round")
    assert rounds[0][3] == {"queued": len(_requests()), "active": 0}
    assert all(0 <= r[3]["active"] <= SLOTS for r in rounds)
    first = named(evs, "serve.dispatch")[0][3]
    assert first["slots"] == SLOTS
    assert first["ctx_tokens"] == len(SHARED) * 2 + 3
    prefill = [e[3]["tokens"] for e in named(evs, "serve.prefill")]
    assert prefill == [e[3]["prompt_tokens"]
                       for e in named(evs, "serve.admit")]


def test_dense_engine_maps_no_prefix(cfg, params, tmp_path):
    eng = ServeEngine(cfg, params, max_slots=SLOTS, max_len=MAX_LEN,
                      chunk=CHUNK)
    evs, results = _traced(ReplicaRouter([eng]), _requests()[:2], tmp_path)
    assert all(e[3]["prefix_hit_tokens"] == 0
               for e in named(evs, "serve.admit"))
    assert all(e[3] == {} for e in named(evs, "serve.insert"))
    assert not named(evs, "serve.pre_dispatch")
    assert sum(e[3].get("emitted", 0) for e in evs) \
        == sum(len(t) for t in results.values())


def test_fault_tolerant_round_is_a_span(cfg, params, tmp_path):
    router = FaultTolerantRouter([_paged(cfg, params)])
    evs, results = _traced(router, _requests()[:3], tmp_path)
    assert len(results) == 3
    assert all(any(inside(a, r) for r in named(evs, "serve.round"))
               for a in named(evs, "serve.admit"))


def test_off_is_the_shared_noop():
    def boom():
        raise AssertionError("stat computed with no trace running")
    sp = S.span("x", stat=boom)
    assert sp is S.OFF and S.span("y") is S.OFF
    with sp as entered:
        S.note(entered, stat=boom)
    assert entered is S.OFF


def test_off_computes_no_stat(cfg, params, monkeypatch, traced):
    """With no trace running, a whole serve (serial and pipelined)
    computes no stat, and serves the streams the traced serve did."""
    _, want, eng = traced

    def boom(stats):
        raise AssertionError(f"stats computed with no trace: {stats}")
    monkeypatch.setattr(S, "_values", boom)
    router = ReplicaRouter([_paged(cfg, params, pipeline=eng.pipeline)])
    got = router.run(_requests())
    assert {r: t.tolist() for r, t in got.items()} \
        == {r: t.tolist() for r, t in want.items()}
