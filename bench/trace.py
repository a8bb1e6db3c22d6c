"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData``, nothing else. Device planes are
the ``/device:TPU:<n>`` planes; on each, the ``XLA Ops`` line holds one
event per operation run, named by its HLO text (``%fusion.12 = ...``),
and the ``XLA Modules`` line one per program run, named by the jitted
function (``jit_paged_step(<hash>)``). An op's program is the module
event that contains it. The host plane holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``, names starting ``bench.``); the span
``bench.window`` marks the measured window, and every device reading is
clipped to it. It also holds the serving program's own spans (names
starting ``serve.``, ``repro.serve.spans``), kept whole with their stats
as ``Trace.program_spans`` for the readers of ``program_spans.py``.
Control-flow ops (``%while`` and the like) span the ops of their bodies,
so they count towards busy time but are left out of the per-op list.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
PROGRAM_PREFIX = "serve."
WINDOW_SPAN = "bench.window"
#: ops whose interval holds other ops' intervals
CONTROL_OPS = ("%while", "%conditional", "%call")


@dataclasses.dataclass
class Event:
    name: str
    start: float          # ns on the trace's clock
    end: float
    module: str = ""      # the program (module event) it ran in
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def short(self) -> str:
        """``%fusion.12 = bf16[..]`` of an op's HLO text."""
        return self.name.split(" fusion(")[0].split(" custom-call(")[0][:160]


@dataclasses.dataclass
class Trace:
    window: tuple                    # (start, end) ns of bench.window
    ops: list                        # per device: [Event] in the window
    modules: list                    # per device: [Event] in the window
    spans: list                      # host [Event] named bench.*
    #: host [Event] named serve.*, with stats, by (start, -end), unclipped
    program_spans: list

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def n_devices(self) -> int:
        return len(self.ops)

    def busy_s(self, dev: int) -> float:
        return float(sum(b - a for a, b in merged(self.ops[dev]))) * 1e-9

    def mean_busy_s(self) -> float:
        return _mean([self.busy_s(i) for i in range(self.n_devices)])

    def op_seconds(self, pred) -> float:
        """Seconds in ops matching ``pred``, mean over devices."""
        return _mean([sum(e.end - e.start for e in evs if pred(e))
                      for evs in self.ops]) * 1e-9

    def module_seconds(self, pred) -> float:
        """Seconds in programs matching ``pred``, mean over devices."""
        return _mean([sum(e.end - e.start for e in evs if pred(e))
                      for evs in self.modules]) * 1e-9

    def top_ops(self, k: int = 10) -> list:
        """[name, seconds] of the ``k`` costliest ops (module/op name),
        mean over devices."""
        tot: dict = {}
        for evs in self.ops:
            for e in evs:
                if e.name.startswith(CONTROL_OPS):
                    continue
                key = f"{e.module.split('(')[0]}/{e.short}"
                tot[key] = tot.get(key, 0.0) + (e.end - e.start) * 1e-9
        n = max(1, self.n_devices)
        return sorted([[k_, v / n] for k_, v in tot.items()],
                      key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10, dev: int = 0) -> list:
        """[host span, seconds] of the ``k`` longest idle gaps of one
        device, each named by the host span that overlaps it most."""
        if dev >= self.n_devices:
            return []
        busy = merged(self.ops[dev])
        edges = [self.window[0]] + [x for ab in busy for x in ab] \
            + [self.window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            best, name = 0.0, "no span"
            for s in self.spans:
                if s.name == WINDOW_SPAN:
                    continue
                ov = min(b, s.end) - max(a, s.start)
                if ov > best:
                    best, name = ov, s.name
            out.append([name, (b - a) * 1e-9])
        return out


def _mean(xs) -> float:
    return float(np.mean(xs)) if len(xs) else 0.0


def merged(events) -> list:
    """Union of event intervals as sorted disjoint (start, end) pairs."""
    iv = sorted((e.start, e.end) for e in events)
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def find_xplane(root: str) -> str:
    paths = sorted(glob.glob(os.path.join(root, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return paths[-1]


def _attribute(ops, modules) -> None:
    """Name each op's program: the module event whose interval holds
    the op's start."""
    starts = [m.start for m in modules]
    for e in ops:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < modules[i].end:
            e.module = modules[i].name


def _clip(evs, lo, hi):
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in evs if e.end > lo and e.start < hi]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))


def reduce(pd) -> Trace:
    """The window's device events and host spans of a ``ProfileData``."""
    spans, program, ops, modules = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Event(ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        program.append(Event(ev.name, ev.start_ns,
                                             ev.start_ns + ev.duration_ns,
                                             stats=dict(ev.stats)))
        elif plane.name.startswith(DEVICE_PREFIX) \
                and plane.name[len(DEVICE_PREFIX):].isdigit():
            o, m = [], []
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                dst = o if line.name == OPS_LINE else m
                dst += [Event(ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                        for ev in line.events]
            m.sort(key=lambda e: e.start)
            _attribute(o, m)
            ops.append(o)
            modules.append(m)
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if not win:
        raise ValueError(f"trace has no {WINDOW_SPAN} span")
    lo, hi = win[0].start, win[0].end
    return Trace(window=(lo, hi), ops=[_clip(o, lo, hi) for o in ops],
                 modules=[_clip(m, lo, hi) for m in modules], spans=spans,
                 program_spans=sorted(program,
                                      key=lambda s: (s.start, -s.end)))
