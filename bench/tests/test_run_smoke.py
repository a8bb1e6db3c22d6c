"""A whole run of an added cell on the CPU, found by name in a copy of
the benchmark to which only new files were added."""

import io
import json
import os

from conftest import BENCH, load_run


def run_once(root, workload, seed, seconds, trace=0):
    run = load_run(root)
    args = run.parse(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)])
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out, err = io.StringIO(), io.StringIO()
    res, state = run.run_cell(args, bench, out=out, err=err)
    return res, state, out.getvalue(), err.getvalue()


def test_added_cell_runs_and_is_correct(bench_copy):
    res, _, out, err = run_once(bench_copy, "smoke.chat", 2**31 + 7, 3.0)
    last = json.loads(out.strip().splitlines()[-1])
    assert list(last)[-1] == "check"
    assert last["correct"] is True, err
    assert last["attempted"] > 0 and last["failed"] == 0
    # ttft_p90_s names its cells; the added one is not among them
    assert set(last["metrics"]) == {"tpot_p90_ms", "output_tok_s_per_chip",
                                    "setup_s"}
    assert err.strip().splitlines()[-1].startswith("check ")
    assert "slowest_round_s=" in err and "setup_full_gc_s=" in err
    # nothing that was there before was edited
    for sub in ("run.py", "arrivals.py", "configs/yi-9b-24l.json",
                "traffic/chat-poisson.json", "metrics/setup_s.py"):
        with open(os.path.join(BENCH, sub), "rb") as a, \
                open(os.path.join(bench_copy, "bench", sub), "rb") as b:
            assert a.read() == b.read()


def test_added_metric_is_read_in_a_traced_run(bench_copy):
    res, state, out, err = run_once(bench_copy, "smoke.chat", 5, 2.0,
                                    trace=1)
    assert res["correct"], err
    got = res["metrics"]
    assert got["rounds_in_window"]["value"] == len(state.rounds) > 0
    assert got["rounds_in_window"]["unit"] == "count"
    # the CPU has no device planes: readers of the trace stay silent
    assert "device_idle_share" not in got and "step_mfu" not in got
    assert {"slot_occupancy", "compiles_in_window"} <= set(got)

