"""Frontend of the in-core prediction engine.

The analysis stack is a pipeline (DESIGN.md §3):

    hloparse -> trace.lower (machine-independent µ-op trace IR, once
    per module) -> a scheduling backend per (machine, backend) pair
    (core/backends/: analytical ``tp_bound``, simulated ``mca_sched``)
    -> Report (core/report.py) -> resolve_tiers (memory ladder).

This module is the thin entry point everything downstream uses:
``analyze`` (one machine, one backend), ``compare`` (fan one module's
trace across machines x backends on a process pool), and
``resolve_tiers`` (fill a report's memory-ladder fields). The heavy
lifting lives in ``repro.core.trace`` and ``repro.core.backends``.
"""

from __future__ import annotations

import dataclasses
import functools
import multiprocessing
import os
import pickle
import warnings
from concurrent.futures import ProcessPoolExecutor

from repro.core import backends as backends_lib
from repro.core import trace as trace_lib
from repro.core.backends.mca_sched import McaSchedBackend
from repro.core.backends.tp_bound import TpBoundBackend
from repro.core.hloparse import parse_hlo, trip_counts_from_text
from repro.core.machine import get_machine, registered_names
from repro.core.report import Report  # noqa: F401  (public re-export)


@functools.lru_cache(maxsize=4)
def _parse_cached(hlo_text: str) -> tuple:
    """Memoized (module, trip-counts) for one HLO text.

    The parse products are read-only after construction, so one parse can
    be shared by every machine in a `compare()` fan-out (and by repeated
    `analyze()` calls on the same text). Deliberately small: each entry
    pins the raw HLO text plus its parse tree for the process lifetime."""
    return parse_hlo(hlo_text), trip_counts_from_text(hlo_text)


@functools.lru_cache(maxsize=4)
def _trace_cached(hlo_text: str, n_devices: int) -> trace_lib.Trace:
    """Memoized lowered trace for one HLO text.

    Decomposition (µ-ops, HBM byte math, loop structure) is machine-
    independent, so one lowering serves every (machine, backend) pair
    of a ``compare()`` fan-out — the old analyzer re-decomposed once
    per machine."""
    mod, trips = _parse_cached(hlo_text)
    return trace_lib.lower(mod, trips, n_devices)


class Analyzer:
    """Analyzes HLO against one machine model with one backend.

    Compatibility wrapper over the trace/backend pipeline: `machine`
    may be a MachineModel or the name of any registered machine, and
    `backend` any registered backend name or alias (``tp``/``mca``).
    """

    def __init__(self, machine, n_devices: int = 1,
                 backend="tp_bound"):
        self.machine = get_machine(machine)
        self.n_devices = n_devices
        self.backend = backends_lib.get_backend(backend)

    def analyze_text(self, hlo_text: str) -> Report:
        """Parse + lower (memoized) and analyze one compiled HLO text."""
        return self.backend.run(_trace_cached(hlo_text, self.n_devices),
                                self.machine)

    def analyze_module(self, mod, trips: dict) -> Report:
        """Analyze an already-parsed module with explicit trip counts."""
        tr = trace_lib.lower(mod, trips, self.n_devices)
        return self.backend.run(tr, self.machine)


def analyze(hlo_text: str, machine, n_devices: int = 1,
            backend="tp_bound") -> Report:
    """Analyze one HLO text on one machine (name or MachineModel) with
    one scheduling backend (name, alias, or Backend instance)."""
    return Analyzer(machine, n_devices, backend).analyze_text(hlo_text)


def resolve_tiers(report: Report, machine) -> Report:
    """Fill a report's memory-ladder fields against one machine.

    Resolves the report's trip-multiplied HBM/DRAM traffic through the
    machine's MemTier ladder (core/memtier.py) and writes `t_mem_tier`,
    `bottleneck_tier`, and `home_tier` in place (returning the report
    for chaining). The working set is approximated by the traffic
    itself — whole-module analyses land on the backing tier, which is
    the flat pre-ladder behaviour.
    """
    from repro.core import memtier  # local: memtier imports machine too

    model = get_machine(machine)
    res = memtier.memory_seconds(model, report.bytes_hbm,
                                 cores_active=model.cores or 1)
    report.t_mem_tier = res.seconds
    report.bottleneck_tier = res.bottleneck_tier
    report.home_tier = res.home
    return report


#: HLO text of the in-flight compare() fan-out, set once per worker by the
#: pool initializer so per-task IPC ships only the (small) machine model.
_WORKER_HLO: str | None = None


def _pool_init(hlo_text: str) -> None:
    global _WORKER_HLO
    _WORKER_HLO = hlo_text


def _compare_worker(model, backend, n_devices: int) -> Report:
    """One (machine, backend) analysis, run in a pool worker process.

    ``backend`` is the Backend *instance* (pickled per task), so ad-hoc
    instances with custom configuration run as-is — never swapped for
    the registry's default. With the (default on Linux) fork start
    method the parent's memoized trace (`_trace_cached`) is inherited
    copy-on-write, so workers skip re-lowering; under spawn they lower
    once per process — correct, just slower. Degradation warnings are
    suppressed here and re-raised once by the parent (``compare``) from
    the returned counts, so a missing µ-op class warns once per fan-out
    instead of once per worker.
    """
    tr = _trace_cached(_WORKER_HLO, n_devices)
    rep = backend.run(tr, model, warn=False)
    return resolve_tiers(rep, model)


def _warn_degraded_once(tasks, reports) -> None:
    """Single parent-side warning for µ-op-class degradation.

    Workers (and the serial loop) analyze with warnings suppressed and
    route occurrences through ``Report.fallback_uops`` /
    ``fallback_classes``; this aggregates them so one fan-out warns
    once, not once per (machine, backend, process)."""
    degraded: dict = {}
    total = 0
    for (model, _bname), rep in zip(tasks, reports):
        if rep.fallback_uops:
            total += rep.fallback_uops
            degraded.setdefault(model.name, set()).update(
                rep.fallback_classes)
    if not degraded:
        return
    detail = "; ".join(f"{m}: missing {sorted(cs)}"
                       for m, cs in degraded.items())
    warnings.warn(
        f"{total} µ-ops degraded to fallback classes during compare() "
        f"({detail}); counts are on Report.fallback_uops",
        RuntimeWarning, stacklevel=3)


def _accelerator_live() -> bool:
    """True once this process has brought up a non-CPU JAX backend.

    A forked child of a process that holds an accelerator inherits its
    runtime threads and device handles; the fan-out must not fork then.
    Asks without initializing any backend.
    """
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    import jax
    return jax.default_backend() != "cpu"


def compare(hlo_text: str, machines=None, n_devices: int = 1,
            max_workers: int | None = None, parallel: str = "auto",
            backends=None) -> dict:
    """Analyze one HLO module across machines (and backends).

    `machines`: iterable of names and/or MachineModels; defaults to every
    registered machine. The module is parsed and lowered to the µ-op
    trace IR exactly once (memoized); every (machine, backend) pair
    replays that trace, and every report comes back with its
    memory-ladder fields resolved (`resolve_tiers`), so callers can
    read the tier-resolved bound (`Report.tier_bound_seconds`) and
    bottleneck tier directly.

    `backends`: None or a single name keeps the legacy shape
    ``{machine name: Report}`` (default backend: the analytical
    ``tp_bound``). An iterable of names returns ``{machine name:
    {backend name: Report}}`` — e.g. ``backends=("tp", "mca")`` for
    the paper's OSACA-vs-MCA comparison. Order is preserved.

    The analyses are pure Python, so the fan-out runs on a **process**
    pool. `parallel`: "auto" (pool when the estimated analysis work
    amortizes the fork/IPC overhead, fork is available, and the models
    pickle), "serial" (in-process loop), or "process" (force the pool).
    A process that holds an accelerator never forks: there every mode
    runs the serial loop. Ad-hoc unpicklable models and pool failures
    degrade to the serial loop, so results never depend on the
    execution mode. Missing µ-op
    classes warn once here in the parent, not once per worker.
    """
    if machines is None:
        machines = registered_names()
    models = [get_machine(m) for m in machines]
    flat = backends is None or isinstance(backends, str) or \
        isinstance(backends, backends_lib.Backend)
    bspecs = ["tp_bound"] if backends is None else \
        ([backends] if flat else list(backends))
    # resolve to instances (names/aliases via the registry, instances
    # pass through untouched) and dedupe on the canonical name so
    # alias + canonical spellings don't double the fan-out
    bobjs, _seen = [], set()
    for b in bspecs:
        obj = backends_lib.get_backend(b)
        if obj.name not in _seen:
            _seen.add(obj.name)
            bobjs.append(obj)
    # the stock simulator runs the full analytical walk first and keeps
    # its fields intact, so an mca_sched report *contains* the tp_bound
    # one — when both stock engines are requested, run only the
    # simulator tasks and derive the tp reports (half the walks on the
    # documented OSACA-vs-MCA fan-out)
    by_name = {b.name: b for b in bobjs}
    derive_tp = (not flat and {"tp_bound", "mca_sched"} <= set(by_name)
                 and type(by_name["tp_bound"]) is TpBoundBackend
                 and isinstance(by_name["mca_sched"], McaSchedBackend))
    run_objs = [b for b in bobjs if b.name != "tp_bound"] \
        if derive_tp else bobjs
    tasks = [(model, obj) for model in models for obj in run_objs]
    tr = _trace_cached(hlo_text, n_devices)

    def run_serial():
        out = []
        for model, obj in tasks:
            rep = obj.run(tr, model, warn=False)
            out.append(resolve_tiers(rep, model))
        return out

    workers = min(max_workers or 8, len(tasks),
                  max(1, os.cpu_count() or 1))
    # ~17 µs/instr·machine analysis vs a few hundred ms of pool setup:
    # the pool only pays off when the serial fan-out is >~ 1 s of work
    big_enough = tr.n_ops() * len(tasks) > 50_000
    use_pool = not _accelerator_live() and (parallel == "process" or (
        parallel == "auto" and workers > 1 and big_enough
        and "fork" in multiprocessing.get_all_start_methods()))
    if use_pool:
        try:
            pickle.dumps((models, bobjs))
        except Exception:
            use_pool = False    # ad-hoc model/backend: serial fallback
    reports = None
    if use_pool:
        try:
            ctx = multiprocessing.get_context("fork")
            with ProcessPoolExecutor(max_workers=workers, mp_context=ctx,
                                     initializer=_pool_init,
                                     initargs=(hlo_text,)) as ex:
                chunk = max(1, len(tasks) // workers)
                reports = list(ex.map(
                    _compare_worker,
                    [m for m, _ in tasks], [b for _, b in tasks],
                    [n_devices] * len(tasks), chunksize=chunk))
        except Exception:
            reports = None          # broken pool: serial fallback
    if reports is None:
        reports = run_serial()
    _warn_degraded_once(tasks, reports)
    if flat:
        return {m.name: r for (m, _), r in zip(tasks, reports)}
    got = {(m.name, b.name): r for (m, b), r in zip(tasks, reports)}
    out: dict = {m.name: {} for m in models}
    for m in models:
        for b in bobjs:             # preserve the requested order
            if derive_tp and b.name == "tp_bound":
                out[m.name][b.name] = _derive_tp_report(
                    got[(m.name, "mca_sched")])
            else:
                out[m.name][b.name] = got[(m.name, b.name)]
    return out


def _derive_tp_report(mca_rep: Report) -> Report:
    """The tp_bound Report contained in a stock mca_sched Report.

    The simulator's analytic fields come from the same walk a tp_bound
    run would do (pinned equal by tests/test_trace_backends.py);
    clearing ``sim_cycles`` restores the analytical accessors. Dict
    fields are copied so the two reports never share mutable state.
    """
    return dataclasses.replace(
        mca_rep, backend="tp_bound", sim_cycles=None,
        port_occupation=dict(mca_rep.port_occupation),
        coll_bytes=dict(mca_rep.coll_bytes),
        trips_seen=dict(mca_rep.trips_seen),
        loop_bytes=dict(mca_rep.loop_bytes),
        fallback_classes=tuple(mca_rep.fallback_classes))
