"""What ``program_spans.py`` and the readers over it read from the
program's ``serve.*`` spans (``Trace.program_spans``): exact values on a
small hand-made trace, nothing on a trace that has no such spans, and,
in a traced CPU run of an added cell, span stats that reproduce the
harness's own counts round by round."""

import hashlib
import json
import os
import types

import pytest

import program_spans as P
import run
import trace as T
from test_run_smoke import run_once

US = 1_000_000       # picoseconds in a microsecond

#: host spans of the hand-made trace: name, start and end in us, stats
HOST = [
    ("bench.window", 0, 1000, {}),
    ("bench.submit", 10, 30, {}),
    ("serve.submit", 12, 28, {"rid": "r1", "replica": 0}),
    ("serve.stage", 14, 26, {"rid": "r1", "tokens": 64, "issued": 1}),
    ("bench.step", 100, 400, {}),
    ("serve.round", 105, 395, {"queued": 1, "active": 0}),
    ("serve.admit", 110, 200, {"rid": "r1", "slot": 0, "prompt_tokens": 64,
                               "prefix_hit_tokens": 0, "emitted": 1}),
    ("serve.prefill", 115, 150, {"tokens": 64}),
    ("serve.first_token", 150, 190, {}),
    ("serve.insert", 190, 198, {"fresh_pages": 1, "shared_pages": 0}),
    ("serve.decode", 205, 390, {"emitted": 8, "retired": 0}),
    ("serve.pre_dispatch", 206, 210, {"pages_allocated": 0,
                                      "cow_copies": 0}),
    ("serve.dispatch", 210, 220, {"slots": 1, "chunk": 8,
                                  "ctx_tokens": 64}),
    ("serve.readback", 220, 385, {}),
    ("bench.submit", 500, 520, {}),
    ("serve.submit", 505, 515, {"rid": "r2", "replica": 0}),
    ("bench.wait", 600, 990, {}),
]
#: device ops, us: prefill, decode, one more program
OPS = [(115, 180), (215, 380), (700, 750)]


def textproto(host=HOST, ops=OPS) -> str:
    """A two-plane XSpace: one TPU with its ops in one program each,
    and the host's spans with their stats."""
    names = sorted({h[0] for h in host})
    stats = sorted({k for h in host for k in h[3]})

    def ev(meta, a, b, st=()):
        s = " ".join(
            f'stats {{ metadata_id: {stats.index(k) + 1} '
            + (f'str_value: "{v}"' if isinstance(v, str)
               else f"int64_value: {v}") + " }" for k, v in st)
        return (f"    events {{ metadata_id: {meta} offset_ps: {a * US} "
                f"duration_ps: {(b - a) * US} {s} }}\n")
    dev = ("planes {\n  id: 1 name: \"/device:TPU:0\"\n"
           "  lines { id: 1 name: \"XLA Modules\" timestamp_ns: 0\n"
           + "".join(ev(1, a, b) for a, b in ops)
           + "  }\n  lines { id: 2 name: \"XLA Ops\" timestamp_ns: 0\n"
           + "".join(ev(2, a, b) for a, b in ops)
           + "  }\n"
           '  event_metadata { key: 1 value { id: 1 name: "jit_step(1)" } }\n'
           '  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = '
           'f32[8] fusion()" } }\n}\n')
    hst = ("planes {\n  id: 2 name: \"/host:CPU\"\n"
           "  lines { id: 1 name: \"python3\" timestamp_ns: 0\n"
           + "".join(ev(names.index(n) + 1, a, b, st.items())
                     for n, a, b, st in host)
           + "  }\n"
           + "".join(f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1}'
                     f' name: "{n}" }} }}\n' for i, n in enumerate(names))
           + "".join(f'  stat_metadata {{ key: {i + 1} value {{ id: {i + 1}'
                     f' name: "{n}" }} }}\n' for i, n in enumerate(stats))
           + "}\n")
    return dev + hst


#: sha256 of the excerpt's ``top_ops()`` as JSON, whole names included
TOP_SHA = "472709d04939d737308dd7f70e7b6fd63232d8da8637472fec08cc2eff4602da"
#: the readers of BENCHMARK.json over the program's spans
READERS = ("queue_wait_p90_s", "admit_p90_ms", "round_idle_ms")


def reduced(host=HOST):
    from jax.profiler import ProfileData
    return T.reduce(ProfileData.from_text_proto(textproto(host=host)))


@pytest.fixture(scope="module")
def made():
    tr = reduced()
    return tr, tr.program_spans


def test_spans_keep_their_stats(made):
    _, spans = made
    assert [s.name for s in spans] == [h[0] for h in HOST
                                       if h[0].startswith("serve.")]
    assert spans[0].stats == {"rid": "r1", "replica": 0}
    assert spans[0].start == 12_000 and spans[0].end == 28_000


def test_queue_wait_and_admission(made):
    tr, spans = made
    # r1 waits from its submit's end (28) to its admission (110); r2 is
    # never admitted and counts until the window closes (1000)
    assert P.queue_waits(spans, tr.window) == pytest.approx(
        [82e-6, 485e-6])
    assert P.lateness(spans, tr.window, {"r1": 2e-6, "r2": 500e-6}) \
        == pytest.approx([10e-6, 5e-6])
    rep = P.report(tr, spans)
    assert rep["queue_wait_p90_s"] == pytest.approx(485e-6)
    assert rep["queue_wait_p50_s"] == pytest.approx(82e-6)
    assert rep["admit_p90_ms"] == pytest.approx(0.09)
    assert rep["round_p50_ms"] == pytest.approx(0.29)
    assert rep["decode_p50_ms"] == pytest.approx(0.185)
    assert rep["requests_submitted"] == 2 and rep["admissions"] == 1


def test_round_idle(made):
    tr, spans = made
    # idle inside the round 105-395: 105-115, 180-215 and 380-395
    assert P.round_idle_s(tr, spans) == pytest.approx(60e-6)
    assert P.report(tr, spans)["round_idle_ms"] == pytest.approx(0.06)


def test_idle_by_span(made):
    tr, spans = made
    got = dict(P.idle_by_span(tr, spans, k=100))
    want = {"no span": 270, "bench.wait": 340, "serve.submit": 14,
            "bench.submit": 14, "serve.stage": 12, "bench.step": 10,
            "serve.round": 15, "serve.admit": 7, "serve.first_token": 10,
            "serve.insert": 8, "serve.decode": 6, "serve.pre_dispatch": 4,
            "serve.dispatch": 5, "serve.readback": 5}
    assert got == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    idle = tr.window_s - tr.mean_busy_s()
    assert sum(got.values()) == pytest.approx(idle)
    top = P.idle_by_span(tr, spans)
    assert len(top) == 10 and top[0][0] == "bench.wait"


def test_longest_gaps(made):
    tr, spans = made
    gaps = P.longest_gaps(tr, spans, k=2)
    # 380-700, then 750-1000
    assert [g[0] for g in gaps] == pytest.approx([320e-6, 250e-6])
    split = dict(gaps[0][1])
    assert split == pytest.approx({k: v * 1e-6 for k, v in {
        "serve.readback": 5, "serve.decode": 5, "serve.round": 5,
        "bench.step": 5, "no span": 180, "bench.submit": 10,
        "serve.submit": 10, "bench.wait": 100}.items()})
    assert gaps[0][1][0][0] == "no span"
    assert dict(gaps[1][1]) == pytest.approx({"bench.wait": 240e-6,
                                              "no span": 10e-6})


def test_self_time_and_rounds(made):
    tr, spans = made
    own = dict(P.self_seconds(spans, tr.window, k=100))
    assert own["serve.admit"] == pytest.approx(7e-6)   # 90 - 35 - 40 - 8
    assert own["serve.round"] == pytest.approx(15e-6)  # 290 - 90 - 185
    assert own["serve.decode"] == pytest.approx(6e-6)
    assert own["serve.readback"] == pytest.approx(165e-6)
    assert P.per_round(spans, tr.window) == [
        {"queued": 1, "slots": 1, "prompts": [64], "shared_pages": 0,
         "emitted": 9}]


def test_nothing_to_read_without_program_spans():
    tr = reduced([h for h in HOST if not h[0].startswith("serve.")])
    assert tr.program_spans == []
    rep = P.report(tr, tr.program_spans)
    assert rep["queue_wait_p90_s"] is None and rep["admit_p90_ms"] is None
    assert rep["round_idle_ms"] is None
    # the device's idle time then goes to the harness's spans
    assert {n for n, _ in rep["idle_by_span"]} <= {
        "no span", "bench.wait", "bench.submit", "bench.step"}


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_what_report_prints(made, name):
    tr, spans = made
    got = run.reader(name)(types.SimpleNamespace(trace=tr))
    assert got == P.report(tr, spans)[name]
    assert got == pytest.approx({"queue_wait_p90_s": 485e-6,
                                 "admit_p90_ms": 0.09,
                                 "round_idle_ms": 0.06}[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_without_program_spans(name):
    tr = reduced([h for h in HOST if not h[0].startswith("serve.")])
    assert tr.n_devices == 1
    assert run.reader(name)(types.SimpleNamespace(trace=tr)) is None


def test_the_harness_reduction_is_unchanged():
    """The recorded excerpt reduces to what the harness read before the
    reduction kept the program's spans: window, its own spans, the
    costliest ops and the longest idle gaps, pinned exactly."""
    from jax.profiler import ProfileData
    data = os.path.join(os.path.dirname(__file__), "data",
                        "paged_step_excerpt.textproto")
    with open(data) as f:
        tr = T.reduce(ProfileData.from_text_proto(f.read()))
    assert tr.program_spans == []
    assert tr.window == (417594404.0, 447594404.0)
    assert [(s.name, s.start, s.end, s.stats) for s in tr.spans] == [
        ("bench.window", 417594404.0, 447594404.0, {}),
        ("bench.window", 49934706.0, 8262038377.0, {}),
        ("bench.step", 334092720.0, 649961301.0, {})]
    top = tr.top_ops()
    assert [[k.split(" = ")[0], v] for k, v in top] == [
        ["jit_paged_step/%copy.90", 0.00339171],
        ["jit_paged_step/%bitcast_add_fusion.3", 0.0029138940000000006],
        ["jit_paged_step/%fusion.157", 0.0028951150000000006],
        ["jit_paged_step/%fusion.158", 0.002894521999999999],
        ["jit_paged_step/%checkpoint.10", 0.0027993709999999997],
        ["jit_paged_step/%copy.29", 0.002436296],
        ["jit_paged_step/%bitcast_dynamic-update-slice_fusion.4",
         0.0015832119999999996],
        ["jit_paged_step/%bitcast_dynamic-update-slice_fusion.5",
         0.0015828300000000001],
        ["jit_paged_step/%dynamic-slice_bitcast_fusion.5",
         0.0015322860000000003],
        ["jit_paged_step/%dynamic-slice_bitcast_fusion.4", 0.001529817]]
    assert hashlib.sha256(json.dumps(top).encode()).hexdigest() == TOP_SHA
    assert tr.idle_gaps() == [
        ["bench.step", 0.001612394], ["bench.step", 0.00038730700000000004]
    ] + [["bench.step", 2e-09]] + [["bench.step", 1e-09]] * 6


def test_traced_run_reproduces_the_recorder(bench_copy):
    """A traced CPU run of the added cell: the program's span stats
    give the harness's own per-round counts, round for round."""
    res, state, out, _ = run_once(bench_copy, "smoke.chat", 2**31 + 11,
                                  3.0, trace=1)
    assert json.loads(out.splitlines()[-1]) == res
    spans = state.trace.program_spans
    got = P.per_round(spans, state.trace.window)
    want = P.recorder_rows(state.rounds)
    assert len(got) == len(want) > 0
    assert got == want
    assert sum(r["emitted"] for r in got) == sum(r.emitted
                                                 for r in state.rounds)
    rep = P.report(state.trace, spans)
    assert rep["queue_wait_p90_s"] >= 0 and rep["admit_p90_ms"] > 0
    # the CPU has no device planes: what needs them stays silent
    assert rep["round_idle_ms"] is None and rep["idle_by_span"] == []
    assert res["breakdown"]["idle_by_span"] == []
    late = P.lateness(spans, state.trace.window,
                      {rid: life.due for rid, life in state.lives.items()})
    assert len(late) == rep["requests_submitted"] > 0
    assert min(late) > -1e-3          # none submitted before it was due
