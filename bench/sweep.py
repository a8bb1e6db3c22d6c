"""Knee sweep of an open-loop cell: the rate at which its queue stops
keeping up. Run by hand on the chip, once, to fix the cell's rate:

    python3 bench/sweep.py --workload <cell> --seconds 30 --rates 2 3 4 5

One process, one set-up; for each rate in turn it offers the cell's
traffic at that rate for ``--seconds``, drains, and prints one JSON line:
requests due, TTFT p50/p90, queue depth over the first and last quarter
of the window, output tokens per second, the slowest round and the
memory peak.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, conf, mix = R.find_cell(bench, args.workload)
    R.require_chips(cell["chips"])
    import jax
    import arrivals
    import serving
    from percentile import p90
    R.use_compile_cache()
    vocab = conf["model"]["vocab_size"]
    server = serving.Server(conf, args.seed)
    first = arrivals.open_loop(mix, args.seed, args.seconds, vocab)
    server.warm_up(first, np.random.default_rng(args.seed))
    R.settle_heap()
    for k, rate in enumerate(args.rates):
        m = dict(mix, rate_per_s=rate)
        reqs = arrivals.open_loop(m, args.seed + k, args.seconds, vocab)
        reqs = [dataclasses.replace(r, rid=f"s{k}-{r.rid}") for r in reqs]
        rec = R.Recorder(server, server.chunk)
        t0 = time.perf_counter()
        R.serve_window(server, reqs, args.seconds, t0, rec,
                       jax.profiler.TraceAnnotation)
        n_win = len(rec.rounds)
        for r in reqs[len(rec.lives):]:
            rec.add(r, r.due)
            server.submit(r)
        while server.busy():
            rec.step(t0)
        rounds = rec.rounds[:n_win]
        q = [r.queued for r in rounds]
        quarter = max(1, len(q) // 4)
        ttft = [l.t_first - l.due for l in rec.lives.values()]
        print(json.dumps({
            "rate": rate, "due": len(rec.lives),
            "ttft_p50_s": float(np.median(ttft)), "ttft_p90_s": p90(ttft),
            "queue_first_quarter": float(np.mean(q[:quarter])),
            "queue_last_quarter": float(np.mean(q[-quarter:])),
            "tok_s": sum(r.emitted for r in rounds
                         if r.t <= args.seconds) / args.seconds,
            "slowest_round_s": max(r.dur for r in rounds),
            "memory_peak_bytes": serving.memory_peak_bytes(),
            "drain_s": time.perf_counter() - t0 - args.seconds}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
