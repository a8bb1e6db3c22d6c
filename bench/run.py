"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. It makes the weights on the device
from the seed, warms up the programs the cell's traffic needs, offers
that traffic to the program's own online entry (``ReplicaRouter.submit``
and ``.step`` over one ``PagedServeEngine``) for ``--seconds``, drains
what is left, and then checks the tokens served against a plain float32
reference. The last line of standard output is one JSON object: the
cell's end-to-end metrics (``--trace 0``) or its per-layer metrics read
from a profiler trace of the window (``--trace 1``), whether the output
was correct, and the device. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.

Everything is found by name: the cell in ``BENCHMARK.json``, its
configuration file, the architecture file ``bench/arch/<name>.py`` that
the configuration's ``model.architectures`` names, its traffic file
``bench/traffic/<mix>.json`` and one reader ``bench/metrics/<metric>.py``
per metric.
"""

from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: requests unfinished this long after the window closes (and, in a
#: traced run, after the trace is written) count as failed: a 1024-token
#: answer due at the close takes about 40 s to finish on yi-9b-24l
DRAIN_S = 90.0
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


# -- finding things by name ---------------------------------------------------

def load_json(path):
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> tuple:
    """(cell, configuration file, traffic mix) of one workload."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf = load_json(os.path.join(ROOT, entry["file"]))
    mix = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    return cell, conf, mix


def metrics_of(bench: dict, cell: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or its per-layer ones when traced."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def load_module(path: str, name: str):
    """The Python file at ``path`` as module ``name``, loaded afresh."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod           # where a dataclass looks itself up
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    path = os.path.join(BENCH, "metrics", name + ".py")
    return load_module(path, f"metric_{name}").read


def model(conf: dict):
    """The configuration's sizes as its architecture's ``Model``, from
    ``bench/arch/<name>.py``: ``<name>`` is ``model.architectures[0]``
    lower-cased, less ``ForCausalLM``."""
    arch = conf["model"]["architectures"][0]
    name = arch.removesuffix("ForCausalLM").lower()
    path = os.path.join(BENCH, "arch", name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"bench: no architecture file {path} for {arch!r}")
    return architecture(path).Model.from_config(conf)


@functools.cache
def architecture(path: str):
    """The architecture file at ``path``, loaded once per process: one
    ``Model`` class, so the reference's jitted calls, static in the
    model, compile once."""
    return load_module(path, "arch_" + os.path.basename(path)[:-3])


# -- what happened in the window ----------------------------------------------

@dataclasses.dataclass
class Life:
    """One request as the client saw it (seconds from window start)."""

    due: float
    ids: np.ndarray           # prompt token ids
    max_new: int
    t_first: float | None = None
    t_last: float | None = None
    tokens: int = 0
    served: np.ndarray | None = None


@dataclasses.dataclass
class Round:
    """One router round, as read after it returned."""

    t: float                 # end of the round, seconds from window start
    dur: float               # seconds the router's step took
    queued: int              # requests waiting before the round
    slots: int               # slots decoded in the round
    emitted: int             # tokens handed out by the round
    prefill_tokens: list     # prompt lengths admitted in the round
    decode_rows: list        # rows attended, every computed step
    kept_rows: list          # rows attended, steps whose token is kept
    shared_pages: int        # pages mapped from the prefix index


class Recorder:
    def __init__(self, server, chunk: int):
        self.server, self.chunk = server, chunk
        self.lives: dict = {}
        self.rounds: list = []
        self._seen: dict = {}

    def add(self, req, due: float) -> None:
        self.lives[req.rid] = Life(due=due, ids=req.prompt,
                                   max_new=req.max_new)

    def step(self, t0: float) -> None:
        srv = self.server
        queued = srv.queued()
        shared0 = srv.pool_stats()["shared_maps"]
        t1 = time.perf_counter()
        retired = srv.step()
        t2 = time.perf_counter()
        t = t2 - t0
        now = dict(srv.active())
        done = {rid: toks for rid, toks in retired}
        now.update({rid: len(toks) for rid, toks in done.items()})
        r = Round(t=t, dur=t2 - t1, queued=queued, slots=0, emitted=0,
                  prefill_tokens=[], decode_rows=[], kept_rows=[],
                  shared_pages=srv.pool_stats()["shared_maps"] - shared0)
        for rid, n in now.items():
            life = self.lives.get(rid)
            prev = self._seen.get(rid, 0)
            if life is None:          # warm-up requests are not recorded
                continue
            if prev == 0:
                life.t_first = t
                r.prefill_tokens.append(len(life.ids))
            r.slots += 1
            r.emitted += n - prev
            before = max(prev, 1)
            pos = len(life.ids) + before - 1
            take = min(self.chunk, life.max_new - before)
            r.decode_rows += range(pos + 1, pos + 1 + self.chunk)
            r.kept_rows += range(pos + 1, pos + 1 + max(take, 0))
            self._seen[rid] = n
            life.tokens = n
            if rid in done:
                life.t_last = t
                life.served = np.asarray(done[rid])
        self.rounds.append(r)


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: dict
    conf: dict
    mix: dict
    m: object                 # the architecture's Model
    chips: int
    seconds: float            # the measured window, as asked
    setup_s: float
    peak: object              # peaks.Peak
    lives: dict               # rid -> Life, requests due in the window
    rounds: list              # Rounds that ended inside the window
    drain_end: float
    compiles_in_window: int
    trace: object = None      # trace.Trace or None

    def ttfts(self) -> list:
        return [(l.t_first if l.t_first is not None else self.drain_end)
                - l.due for l in self.lives.values()]

    def tpots(self) -> list:
        out = []
        for l in self.lives.values():
            if l.t_last is None:
                out.append(float("inf"))
            elif l.tokens > 1:
                out.append((l.t_last - l.t_first) / (l.tokens - 1))
        return out


class CompileCounter:
    """Backend compiles and persistent-cache loads, with their times."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.times: list = []

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.times)


class GcWatch:
    """Python's garbage collections, each as (end time, seconds)."""

    def __init__(self):
        self.pauses: list = []
        self._t = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            now = time.perf_counter()
            self.pauses.append((now, now - self._t))

    def between(self, a: float, b: float) -> list:
        return [d for t, d in self.pauses if a <= t <= b]

    def close(self) -> None:
        gc.callbacks.remove(self)


def settle_heap() -> float:
    """Collect once and freeze what set-up made, as a server does after
    its warm-up: set-up leaves a large heap (traced and compiled
    programs), and a full collection that scanned it would stop a round
    of the window for as long as this one takes. Returns its seconds."""
    t = time.perf_counter()
    gc.collect()
    gc.freeze()
    return time.perf_counter() - t


# -- the run ------------------------------------------------------------------

def require_chips(n: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform is "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX has "
                         f"{len(devs)}; nothing was run")
    return devs


def use_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def serve_window(server, reqs, seconds, t0, rec, annotate):
    """Offer the traffic for ``seconds``; return when the window closes.

    Open loop: each request is submitted once it is due, whatever the
    server is doing; between rounds the loop sleeps only when the
    server has nothing to do.
    """
    i, n = 0, len(reqs)
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        with annotate("bench.submit"):
            while i < n and reqs[i].due <= now:
                rec.add(reqs[i], reqs[i].due)
                server.submit(reqs[i])
                i += 1
        if server.busy():
            with annotate("bench.step"):
                rec.step(t0)
        else:
            nxt = reqs[i].due if i < n else seconds
            with annotate("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - now))
    return i


def gap_readings(gaps) -> dict:
    """The numbers a check can compare, from per-token logit gaps."""
    gaps = np.asarray(gaps)
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean()),
            "tokens_compared": int(gaps.size)}


def compared(read: dict, check: dict, unfinished: int) -> dict:
    """Each number the configuration's ``check.limits`` names, with its
    limit, and the requests left unfinished (limit 0)."""
    out = {k: {"value": read[k], "limit": v}
           for k, v in check["limits"].items()}
    out["unfinished_requests"] = {"value": unfinished, "limit": 0}
    return out


def is_correct(numbers: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in numbers.values())


def check_served(run: Run, seed: int, lives: dict, check: dict) -> tuple:
    """Compare the served tokens of a sample of finished requests with
    the float32 reference. Returns the numbers compared, each with its
    limit, and every reading taken."""
    import reference as R
    done = [(rid, l) for rid, l in lives.items() if l.served is not None]
    read = {"max_logit_gap": float("inf"), "mean_logit_gap": float("inf"),
            "tokens_compared": 0}
    if done:
        sample = pick_sample(done, seed, check)
        seqs = [R.sequence(l.ids, l.served) for _, l in sample]
        pos = [R.served_positions(len(l.ids), len(l.served))
               for _, l in sample]
        lg = R.logits(run.m, seed, seqs, pos)
        read = gap_readings(np.concatenate(
            [R.token_gaps(x, l.served) for x, (_, l) in zip(lg, sample)]))
    return compared(read, check, len(lives) - len(done)), read


def pick_sample(done: list, seed: int, limits: dict) -> list:
    """The request with the most served tokens, then others drawn from
    the seed until the sample holds enough requests and tokens."""
    done = sorted(done, key=lambda kv: kv[0])
    longest = max(done, key=lambda kv: (len(kv[1].served), len(kv[1].ids)))
    rest = [kv for kv in done if kv[0] != longest[0]]
    order = np.random.default_rng([int(seed), 1]).permutation(len(rest))
    sample, tokens = [longest], len(longest[1].served)
    for j in order:
        if len(sample) >= limits["max_requests"] or (
                tokens >= limits["min_served_tokens"]
                and len(sample) >= limits["min_requests"]):
            break
        sample.append(rest[j])
        tokens += len(rest[j][1].served)
    return sample


def run_cell(args, bench: dict, out=sys.stdout, err=sys.stderr) -> dict:
    cell, conf, mix = find_cell(bench, args.workload)
    devs = require_chips(cell["chips"])
    import jax
    import arrivals
    import peaks
    import serving
    use_compile_cache()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    kind = devs[0].device_kind
    peak = peaks.peak(kind)
    m = model(conf)
    reqs = arrivals.open_loop(mix, args.seed, args.seconds, m.vocab)
    server = serving.Server(m, conf, args.seed)
    server.warm_up(reqs, np.random.default_rng([int(args.seed), 2]))
    rec = Recorder(server, server.chunk)
    annotate = jax.profiler.TraceAnnotation
    full_gc_s = settle_heap()
    gcw = GcWatch()
    tdir = None
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
    setup_s = time.time() - T0
    t0 = time.perf_counter()
    with annotate("bench.window"):
        serve_window(server, reqs, args.seconds, t0, rec, annotate)
    t_close = time.perf_counter()
    if args.trace:
        jax.profiler.stop_trace()
    t_drain = time.perf_counter()
    in_window = len(rec.rounds)
    compiles = counter.between(t0, t_close)
    gc_pauses = gcw.between(t0, t_close)
    gcw.close()
    for r in reqs[len(rec.lives):]:
        rec.add(r, r.due)
        server.submit(r)
    while server.busy() and time.perf_counter() - t_drain < DRAIN_S:
        rec.step(t0)
    drain_end = time.perf_counter() - t0
    gc.unfreeze()
    mem = serving.memory_peak_bytes()
    server.close()
    del server
    run = Run(cell=cell, conf=conf, mix=mix, m=m, chips=cell["chips"],
              seconds=args.seconds, setup_s=setup_s,
              peak=peak, lives=rec.lives, rounds=rec.rounds[:in_window],
              drain_end=drain_end, compiles_in_window=compiles)
    check, readings = check_served(run, args.seed, rec.lives, conf["check"])
    if args.trace:
        import trace as T
        run.trace = T.load(T.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
    metrics = {}
    for spec in metrics_of(bench, cell["name"], bool(args.trace)):
        v = reader(spec["name"])(run)
        if v is not None:
            metrics[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    result = {"correct": is_correct(check),
              "attempted": len(rec.lives),
              "failed": check["unfinished_requests"]["value"],
              "metrics": metrics, "device": device}
    if args.trace:
        device["busy_s"] = run.trace.mean_busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(),
                               "idle_gaps": run.trace.idle_gaps()}
    result["check"] = check
    slowest = max((r.dur for r in run.rounds), default=0.0)
    print(f"bench: {cell['name']} seed={args.seed} rounds={in_window} "
          f"compiles_in_window={compiles} setup_s={setup_s:.3f} "
          f"setup_full_gc_s={full_gc_s:.4f} gc_in_window={len(gc_pauses)} "
          f"gc_max_s={max(gc_pauses, default=0.0):.4f} "
          f"slowest_round_s={slowest:.4f} "
          + " ".join(f"{k}={v!r}" for k, v in readings.items()), file=err)
    for k, c in check.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return result, run


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    sys.path.insert(0, BENCH)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    run_cell(args, bench)
    return 0


if __name__ == "__main__":
    sys.exit(main())
