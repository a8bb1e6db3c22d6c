"""Readings that the check's limits are set from, on the chip:

    python3 bench/calibrate.py --workload <cell> --seconds 20 \
        --seeds 101 102 103 ...

One process, one set-up. For each seed it makes that seed's weights,
serves the cell's traffic at the cell's own rate for ``--seconds`` and
drains, exactly as a run does, then compares the same sample of served
tokens with the float32 reference (the program's reading) and runs the
control: the reference computed in float8 (e4m3), whose own greedy
token at each of those positions is scored against the float32
reference the same way. Both are held to the configuration's limits by
the run's own comparison (``run.compared``). One JSON line per seed:
the program's and the control's numbers, and whether each came out
correct (the control has to come out not correct).
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


def readings(m, seed, sample, check) -> dict:
    """Program and control readings on one sample of served requests,
    each held to ``check``'s limits."""
    import reference as ref
    seqs = [ref.sequence(l.ids, l.served) for _, l in sample]
    pos = [ref.served_positions(len(l.ids), len(l.served))
           for _, l in sample]
    f32 = ref.logits(m, seed, seqs, pos)
    prog = R.gap_readings(np.concatenate(
        [ref.token_gaps(x, l.served) for x, (_, l) in zip(f32, sample)]))
    low = ref.logits(m, seed, seqs, pos, control=True)
    ctrl = R.gap_readings(np.concatenate(
        [ref.token_gaps(x, np.asarray(y.argmax(-1)))
         for x, y in zip(f32, low)]))
    agree = np.concatenate([np.asarray(x.argmax(-1)) == l.served
                            for x, (_, l) in zip(f32, sample)])
    out = {"program": prog, "control": ctrl,
           "program_argmax_agree": float(agree.mean()),
           "requests": len(sample)}
    for side in ("program", "control"):
        out[side + "_correct"] = R.is_correct(R.compared(out[side], check, 0))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = R.load_json(os.path.join(R.ROOT, "BENCHMARK.json"))
    cell, conf, mix = R.find_cell(bench, args.workload)
    R.require_chips(cell["chips"])
    import jax
    import arrivals
    import serving
    R.use_compile_cache()
    vocab = conf["model"]["vocab_size"]
    server = serving.Server(conf, args.seeds[0])
    server.warm_up(arrivals.open_loop(mix, args.seeds[0], args.seconds,
                                      vocab),
                   np.random.default_rng(args.seeds[0]))
    R.settle_heap()
    for k, seed in enumerate(args.seeds):
        if k:
            server.reseed(seed)
        reqs = arrivals.open_loop(mix, seed, args.seconds, vocab)
        reqs = [dataclasses.replace(r, rid=f"c{k}-{r.rid}") for r in reqs]
        rec = R.Recorder(server, server.chunk)
        t0 = time.perf_counter()
        R.serve_window(server, reqs, args.seconds, t0, rec,
                       jax.profiler.TraceAnnotation)
        for r in reqs[len(rec.lives):]:
            rec.add(r, r.due)
            server.submit(r)
        while server.busy():
            rec.step(t0)
        server.drop_params()
        done = [(rid, l) for rid, l in rec.lives.items()
                if l.served is not None]
        sample = R.pick_sample(done, seed, conf["check"])
        t1 = time.perf_counter()
        out = readings(server.m, seed, sample, conf["check"])
        out.update(seed=seed, unfinished=len(rec.lives) - len(done),
                   reference_s=time.perf_counter() - t1)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
