"""Fig. 11: overlapped serving runtime — double-buffered decode dispatch
vs serial rounds, plus the offline plan database.

Three measured claims, one per section of the overlapped runtime
(repro.serve.engine pipeline mode, repro.serve.staging,
repro.serve.plandb):

1. **Dispatch overlap** — with ``pipeline=2`` the engine enqueues round
   N+1 while round N is still executing, so wall-clock tokens/s rises.
   Gated on the container host for the dense engine (tokens/s >=
   serial by the median of paired interleaved repeats — robust to
   shared-host load noise);
   the paged engine is gated leniently (its per-round host work —
   block-table assembly — is a larger fraction of a round). Token
   streams must be byte-identical between modes: the overlap is a
   scheduling change, never a numerics change.

2. **Priced per-machine prediction** — pipelined mode cannot donate the
   KV cache (a donated still-pending input blocks the enqueue, the
   exact stall the mode exists to remove), so it pays the
   copy-first cache update. That copy's WA-priced store traffic
   (repro.serve.kv_traffic.kv_update_traffic, ``delta_bytes``) is the
   per-machine *cost* of overlap, and must keep the paper's
   store-traffic ordering: Grace <= SPR <= Zen 4 — Grace's auto-claim
   writes spill least, Zen 4's explicit-only WA pays full allocate
   traffic.

3. **Plan database** — an offline sweep (both planner backends)
   persisted and reinstalled must make admission planning O(1): after a
   sweep covering the serving point, planning for every registered
   machine is a DB hit with *zero* online plans (pinned by the planner
   stats counters) and the returned plan is bit-identical to the online
   planner's. The tp_bound-vs-mca_sched disagreement count is reported.

Like fig6/fig9, the host wall-clock numbers are a smoke anchor — this
container is not a Grace/SPR/Genoa socket — while the priced rows carry
the cross-vendor prediction.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.models import model as M
from repro.serve import PagedServeEngine, Request, ServeEngine
from repro.serve.kv_traffic import kv_update_traffic

ARCH = "yi-9b"                    # GQA: distinct n_heads / n_kv_heads
SLOTS, CHUNK, GEN, PROMPT = 16, 8, 96, 12
ORDER = ("neoverse_v2", "golden_cove", "zen4")   # Grace, SPR, Genoa


def _requests(cfg, seed: int) -> list:
    """One full batch of seeded random-prompt requests."""
    rng = np.random.default_rng(seed)
    return [Request(rid=f"r{i}",
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, cfg.vocab_size, PROMPT)),
                    max_new_tokens=GEN)
            for i in range(SLOTS)]


def _run_once(eng, cfg, seed: int):
    """One timed serve of a full batch; returns (wall_s, results)."""
    reqs = _requests(cfg, seed)
    t0 = time.perf_counter()
    results = eng.run(reqs)
    return time.perf_counter() - t0, results


def _measure_pair(engs: dict, cfg, repeats: int, seed: int) -> dict:
    """Warmup both engines, then best-of-``repeats`` with the modes
    *interleaved* (serial, pipelined, serial, ...) so slow host-load
    drift hits both equally — back-to-back blocks let a load spike
    land entirely on one mode and flip the relative gate on noise.
    Returns {mode: (min wall, results)}."""
    for eng in engs.values():                       # compile + warm caches
        _run_once(eng, cfg, seed)
    walls = {m: [] for m in engs}
    results = {}
    for _ in range(repeats):
        for mode, eng in engs.items():
            w, results[mode] = _run_once(eng, cfg, seed)
            walls[mode].append(w)
    out = {m: (min(walls[m]), results[m]) for m in engs}
    out["pair_speedups"] = sorted(
        ws / wp for ws, wp in zip(walls["serial"], walls["pipelined"]))
    return out


def _stream_key(results: dict) -> tuple:
    return tuple((rid, tuple(int(t) for t in results[rid]))
                 for rid in sorted(results))


def _overlap_rows(cfg, params, repeats: int) -> list:
    """Serial vs pipelined on dense + paged engines; gates inside."""
    lines = []
    for kind, mk in (("dense", lambda **kw: ServeEngine(cfg, params, **kw)),
                     ("paged", lambda **kw: PagedServeEngine(
                         cfg, params, page_size=8, **kw))):
        engs = {mode: mk(max_slots=SLOTS, max_len=PROMPT + GEN,
                         chunk=CHUNK, pipeline=pipeline)
                for mode, pipeline in (("serial", 0), ("pipelined", 2))}
        runs = _measure_pair(engs, cfg, repeats, seed=7)
        if kind == "dense" and runs["pair_speedups"][repeats // 2] < 1.0:
            # a transient load storm can bury the (few-percent) win in
            # one measurement block; one independent re-measure with
            # doubled pairs must confirm before the gate fails
            runs = _measure_pair(engs, cfg, 2 * repeats, seed=7)
        (w_s, r_s), (w_p, r_p) = runs["serial"], runs["pipelined"]
        pairs = runs["pair_speedups"]
        assert _stream_key(r_s) == _stream_key(r_p), \
            f"{kind}: pipelined token streams diverged from serial"
        tok_s, tok_p = SLOTS * GEN / w_s, SLOTS * GEN / w_p
        # the tokens/s gate uses the MEDIAN of the paired per-repeat
        # ratios: adjacent-in-time pairs cancel common-mode host load,
        # and the median tolerates a minority of polluted pairs — the
        # best-of mins (reported below) still flip the comparison on a
        # single lucky serial repeat on a noisy shared host
        speedup = pairs[len(pairs) // 2]
        lines.append(
            f"fig11,overlap.{kind},{w_p*1e6:.0f},"
            f"slots={SLOTS};chunk={CHUNK};gen={GEN};repeats={repeats};"
            f"tok_s_serial={tok_s:.1f};tok_s_pipelined={tok_p:.1f};"
            f"speedup_median_paired={speedup:.3f};"
            f"streams=IDENTICAL")
        if kind == "dense":
            assert speedup >= 1.0, \
                f"dense: pipelined slower (median paired {speedup:.3f}x, " \
                f"pairs {[round(p, 3) for p in pairs]})"
        else:
            # paged per-round host work (block-table assembly) dilutes
            # the overlap win; gate leniently, report honestly
            assert speedup >= 0.9, \
                f"paged: pipelined regressed badly ({speedup:.3f}x)"
    return lines


def _priced_rows(cfg) -> list:
    """The per-machine priced copy cost of overlap, ordering-gated."""
    rows = {r["machine"]: r for r in kv_update_traffic(
        cfg, SLOTS, PROMPT + GEN, flavor="auto", machines=ORDER)}
    tri = [rows[m]["delta_bytes"] for m in ORDER]
    ok = tri[0] <= tri[1] <= tri[2]
    line = (
        "fig11,priced_copy_cost,0,"
        + ";".join(f"{m}={rows[m]['delta_bytes']:.0f}"
                   f"({rows[m]['wa_mode']})" for m in ORDER)
        + f";grace_le_spr_le_zen4={'OK' if ok else 'VIOLATED'}")
    if not ok:
        raise AssertionError(
            f"overlap copy-cost WA ordering violated: {tri}")
    return [line]


def _plandb_rows(cfg) -> list:
    """Sweep -> install -> every-machine plan is a DB hit, zero online."""
    from repro.core.machine import registered_names
    from repro.serve import plandb
    from repro.serve.planner import (plan_chunk_size, plan_stats,
                                     reset_plan_stats)
    t0 = time.perf_counter()
    db = plandb.sweep(cfg, batches=(SLOTS,), max_lens=(PROMPT + GEN,),
                      tps=(1,))
    sweep_s = time.perf_counter() - t0
    machines = registered_names()
    # online reference plans (DB not installed yet)
    ref = {m: plan_chunk_size(cfg, SLOTS, PROMPT + GEN, machine=m)
           for m in machines}
    prev = plandb.installed()
    try:
        plandb.install(db)
        reset_plan_stats()
        t0 = time.perf_counter()
        hits = {m: plan_chunk_size(cfg, SLOTS, PROMPT + GEN, machine=m)
                for m in machines}
        lookup_s = time.perf_counter() - t0
        stats = plan_stats()
    finally:
        plandb.install(prev)
    assert stats["online_plans"] == 0, \
        f"plan DB hit still planned online: {stats}"
    assert stats["db_hits"] == len(machines), f"missed DB hits: {stats}"
    for m in machines:
        assert hits[m] == ref[m], \
            f"{m}: DB plan differs from online plan"
    dis = plandb.backend_disagreements(db)
    return [
        f"fig11,plandb,{lookup_s*1e6:.0f},"
        f"entries={len(db)};machines={len(machines)};"
        f"sweep_ms={sweep_s*1e3:.0f};lookup_us={lookup_s*1e6:.0f};"
        f"online_plans={stats['online_plans']};db_hits={stats['db_hits']};"
        f"bit_identical=OK;backend_disagreements={len(dis)}"]


def main(quick: bool = False) -> list:
    """Emit the fig11 overlap table as benchmark CSV lines."""
    cfg = get_smoke_config(ARCH)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    repeats = 9 if quick else 15
    lines = _overlap_rows(cfg, params, repeats)
    lines.extend(_priced_rows(cfg))
    lines.extend(_plandb_rows(cfg))
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer timed repeats (CI overlap-smoke job)")
    args = ap.parse_args()
    print("\n".join(main(quick=args.smoke)))
