"""The serving program's own spans in a traced run of a cell.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell as ``bench/run.py --trace 1`` does, keeps the host events
named ``serve.*`` that the program writes into the trace
(``repro.serve.spans``, with their stats), and prints one more JSON
line after the run's own: what those spans say about the window.

- ``queue_wait_p90_s``: over the ``serve.submit`` spans that end in the
  window, the start of the ``serve.admit`` with the same ``rid`` less
  the submit's end; a request not admitted before the window closes
  counts until the close. Nearest-rank p90 (``percentile.py``).
- ``admit_p90_ms``: p90 of the durations of the ``serve.admit`` spans
  that end in the window.
- ``round_idle_ms``: device idle time inside the ``serve.round`` spans
  that end in the window, per round, mean over the cell's chips: the
  part of the device's idle share that the program's host work between
  its device programs causes. The rest is the harness's.
- ``idle_by_span``: every idle interval of a device in the window,
  split by the innermost span covering each instant (a ``serve.`` span
  before a ``bench.`` one; "no span" where none covers it), as the ten
  largest [span, seconds], mean over chips.
- ``longest_gaps``: the five longest idle intervals of the first
  device, each split the same way.
- ``self_s``: the host self time of each span name in the window (its
  duration less that of the spans directly inside it), the ten largest.
- the p50s of the queue wait, the admission, the round, the engine's
  decode round (``serve.decode``) and the time to first token; the p50
  of how late the harness submitted each request after it was due (the
  window opens at the start of ``bench.window``); and the cell's
  end-to-end metrics read from this traced run, so that tracing can be
  compared with an untraced run of the same seed.
- ``rounds``: how many rounds of the window the span stats reproduce
  exactly, against the harness's own counts after each round
  (``run.Recorder``): requests queued, slots decoded, prompt tokens
  admitted, pages mapped from the prefix index and tokens handed out.

Nothing here is read by ``bench/run.py`` or by a metric of
``BENCHMARK.json``; a trace without ``serve.*`` spans gives no number.
"""

from __future__ import annotations

import bisect
import dataclasses
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
PREFIX = "serve."
NO_SPAN = "no span"


@dataclasses.dataclass
class Span:
    name: str
    start: float          # ns on the trace's clock
    end: float
    stats: dict


def spans_of(pd) -> list:
    """The ``serve.*`` host events of a ``ProfileData``, by start."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    dict(ev.stats)))
    return sorted(out, key=lambda s: (s.start, -s.end))


def ending_in(spans, window, name=None) -> list:
    lo, hi = window
    return [s for s in spans if lo < s.end <= hi
            and (name is None or s.name == name)]


def queue_waits(spans, window) -> list:
    """Seconds from each submit's end to its admission (or the close)."""
    admits: dict = {}
    for s in spans:
        if s.name == PREFIX + "admit":
            admits.setdefault(s.stats.get("rid"), []).append(s.start)
    out = []
    for s in ending_in(spans, window, PREFIX + "submit"):
        later = [a for a in admits.get(s.stats.get("rid"), ()) if a >= s.end]
        out.append((min(later + [window[1]]) - s.end) * 1e-9)
    return out


def lateness(spans, window, dues: dict) -> list:
    """Seconds from when each request was due (seconds after the window
    opened) to the start of its ``serve.submit``: the harness's delay,
    while the program finishes the round it is in."""
    return [(s.start - window[0]) * 1e-9 - dues[s.stats["rid"]]
            for s in ending_in(spans, window, PREFIX + "submit")
            if s.stats.get("rid") in dues]


def durations(spans, window, name) -> list:
    return [(s.end - s.start) * 1e-9
            for s in ending_in(spans, window, PREFIX + name)]


def nearest_rank(values, q: float):
    v = sorted(values)
    return float(v[max(0, math.ceil(q * len(v)) - 1)]) if v else None


def idle_intervals(tr, dev: int) -> list:
    """(start, end) of each idle interval of one device in the window."""
    from trace import merged
    edges = [tr.window[0]] + [x for ab in merged(tr.ops[dev]) for x in ab] \
        + [tr.window[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    tot, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def round_idle_s(tr, spans):
    """Device idle seconds inside the window's rounds, per round, mean
    over devices; None without devices or rounds."""
    rounds = ending_in(spans, tr.window, PREFIX + "round")
    if not tr.n_devices or not rounds:
        return None
    lo, hi = tr.window
    iv = [(max(s.start, lo), s.end) for s in rounds]
    idle = [overlap(idle_intervals(tr, d), iv) for d in range(tr.n_devices)]
    return sum(idle) / len(idle) * 1e-9 / len(rounds)


def owners(tr, spans) -> list:
    """The window cut into (start, end, name) pieces, each named by the
    innermost span covering it: a ``serve.`` span before a ``bench.``
    one, the later-starting of two of a kind."""
    lo, hi = tr.window
    cover = [s for s in list(spans) + list(tr.spans)
             if s.name != "bench.window" and s.end > lo and s.start < hi]
    marks = sorted({lo, hi} | {min(max(x, lo), hi) for s in cover
                               for x in (s.start, s.end)})
    cover.sort(key=lambda s: s.start)
    out, live, k = [], [], 0
    for a, b in zip(marks, marks[1:]):
        while k < len(cover) and cover[k].start <= a:
            live.append(cover[k])
            k += 1
        live = [s for s in live if s.end > a]
        best = max(live, key=lambda s: (s.name.startswith(PREFIX), s.start),
                   default=None)
        out.append((a, b, best.name if best else NO_SPAN))
    return out


def _split(pieces, a, b, into: dict) -> None:
    """Add the seconds of (a, b) under each piece's name to ``into``."""
    i = bisect.bisect_right(pieces, (a, math.inf, "")) - 1
    while i < len(pieces) and pieces[i][0] < b:
        x, y, name = pieces[i]
        ov = min(b, y) - max(a, x)
        if ov > 0:
            into[name] = into.get(name, 0.0) + ov * 1e-9
        i += 1


def _largest(tot: dict, k: int, n: int = 1) -> list:
    return sorted([[name, v / n] for name, v in tot.items()],
                  key=lambda kv: -kv[1])[:k]


def idle_by_span(tr, spans, k: int = 10) -> list:
    """[name, seconds] of device idle time by the span covering it, the
    ``k`` largest, mean over devices."""
    if not tr.n_devices:
        return []
    pieces = owners(tr, spans)
    tot: dict = {}
    for dev in range(tr.n_devices):
        for a, b in idle_intervals(tr, dev):
            _split(pieces, a, b, tot)
    return _largest(tot, k, tr.n_devices)


def longest_gaps(tr, spans, k: int = 5, dev: int = 0) -> list:
    """The ``k`` longest idle intervals of one device, each as
    [seconds, [[name, seconds], ...]]: how the spans covering it split
    it, largest first."""
    if dev >= tr.n_devices:
        return []
    pieces = owners(tr, spans)
    out = []
    gaps = sorted(idle_intervals(tr, dev), key=lambda g: g[0] - g[1])
    for a, b in gaps[:k]:
        part: dict = {}
        _split(pieces, a, b, part)
        out.append([(b - a) * 1e-9, _largest(part, len(part))])
    return out


def self_seconds(spans, window, k: int = 10) -> list:
    """[name, seconds] of host self time of the spans ending in the
    window: each span's duration less its direct children's, the ``k``
    largest."""
    tot: dict = {}
    done, stack = [], []
    for s in spans:
        while stack and stack[-1][0].end <= s.start:
            done.append(stack.pop())
        if stack:
            stack[-1][1] += s.end - s.start
        stack.append([s, 0.0])
    for s, kids in done + stack:
        if window[0] < s.end <= window[1]:
            own = (s.end - s.start - kids) * 1e-9
            tot[s.name] = tot.get(s.name, 0.0) + own
    return _largest(tot, k)


def per_round(spans, window) -> list:
    """The stats of each round that lies in the window, summed over
    the spans inside it."""
    rounds = [s for s in spans if s.name == PREFIX + "round"
              and window[0] <= s.start and s.end <= window[1]]
    starts = [r.start for r in rounds]
    out = [{"queued": r.stats["queued"], "slots": 0, "prompts": [],
            "shared_pages": 0, "emitted": 0} for r in rounds]
    for s in spans:
        i = bisect.bisect_right(starts, s.start) - 1
        if i < 0 or s.end > rounds[i].end or s is rounds[i]:
            continue
        row, st = out[i], s.stats
        row["emitted"] += st.get("emitted", 0)
        if s.name == PREFIX + "dispatch":
            row["slots"] += st["slots"]
        elif s.name == PREFIX + "admit":
            bisect.insort(row["prompts"], st["prompt_tokens"])
        elif s.name == PREFIX + "insert":
            row["shared_pages"] += st.get("shared_pages", 0)
    return out


def recorder_rows(rounds) -> list:
    """The same counts from the harness's ``Round`` records."""
    return [{"queued": r.queued, "slots": r.slots,
             "prompts": sorted(r.prefill_tokens),
             "shared_pages": r.shared_pages, "emitted": r.emitted}
            for r in rounds]


def report(tr, spans) -> dict:
    """What the program's spans say about the traced window."""
    w = tr.window
    waits = queue_waits(spans, w)
    admits = durations(spans, w, "admit")
    idle = round_idle_s(tr, spans)

    def ms(x):
        return None if x is None else 1e3 * x
    return {"queue_wait_p90_s": nearest_rank(waits, 0.9),
            "admit_p90_ms": ms(nearest_rank(admits, 0.9)),
            "round_idle_ms": ms(idle),
            "queue_wait_p50_s": nearest_rank(waits, 0.5),
            "admit_p50_ms": ms(nearest_rank(admits, 0.5)),
            "round_p50_ms": ms(nearest_rank(durations(spans, w, "round"),
                                            0.5)),
            "decode_p50_ms": ms(nearest_rank(durations(spans, w, "decode"),
                                             0.5)),
            "requests_submitted": len(waits), "admissions": len(admits),
            "idle_by_span": idle_by_span(tr, spans),
            "longest_gaps": longest_gaps(tr, spans),
            "self_s": self_seconds(spans, w)}


def run_traced(run_mod, args, bench: dict):
    """``run_mod.run_cell`` with a trace, keeping the program's spans.
    Returns (result, the run's state, spans)."""
    import trace as T
    from jax.profiler import ProfileData
    kept = {}

    def load(path):
        pd = ProfileData.from_file(path)
        kept["spans"] = spans_of(pd)
        return T.reduce(pd)
    args.trace = 1
    orig, T.load = T.load, load
    try:
        result, state = run_mod.run_cell(args, bench)
    finally:
        T.load = orig
    return result, state, kept["spans"]


def main(argv=None) -> int:
    sys.path.insert(0, BENCH)
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import run as R
    args = R.parse(argv)
    bench = R.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    result, state, spans = run_traced(R, args, bench)
    rep = report(state.trace, spans)
    rep["late_p50_s"] = nearest_rank(lateness(
        spans, state.trace.window,
        {rid: life.due for rid, life in state.lives.items()}), 0.5)
    rep["ttft_p50_s"] = nearest_rank(state.ttfts(), 0.5)
    rep["end_to_end"] = {m["name"]: R.reader(m["name"])(state)
                         for m in R.metrics_of(bench, args.workload, False)}
    got = per_round(spans, state.trace.window)
    want = recorder_rows(state.rounds)
    rep["rounds"] = {"traced": len(got), "recorded": len(want),
                     "matched": sum(a == b for a, b in zip(got, want))}
    print(json.dumps({"program_spans": rep}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
