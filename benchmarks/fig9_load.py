"""Fig. 9: traffic-scale serving — replica router under seeded arrival
traces, measured tail latency and tokens/s/chip vs the planner.

A closed-loop generator replays a *seeded* arrival trace (Poisson or
bursty, mixed prompt/gen lengths) against a :class:`ReplicaRouter`
over N engine replicas, each sharded over the host-device-count mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=K`` fakes K chips
on CPU; with one device the mesh is (1, 1) and the engines take the
bit-exact single-device path). Arrivals are indexed in router rounds —
deterministic under a seed — while latencies are measured on the wall
clock: a request's latency spans from the round it became due (queue
wait included, backpressure deferrals included) to the round it
retired.

Reported per trace: p50/p95/p99 latency, measured tokens/s/chip, and
the planner's predicted tokens/s/chip on the plan machine — the same
predicted-vs-measured pairing as fig6, and like fig6 the host
measurement is a smoke anchor for the cross-vendor predictions, not a
validation (this container is not a Grace/SPR/Genoa socket). What *is*
gated here: percentile ordering, token conservation across the router,
and the sharded pricing invariants — the per-shard KV stream shrinks
with TP degree and the per-step collective's WA-priced bytes keep the
Grace <= SPR <= Zen 4 store-traffic ordering.
"""

from __future__ import annotations

import math
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.serve import QueueFull, ReplicaRouter, Request, ServeEngine
from repro.serve.kv_traffic import collective_traffic, kv_row_bytes
from repro.utils.sharding import mesh_axis_sizes, tp_degree

ARCH = "gemma3-4b"           # local+global attention: both cache kinds
SLOTS, MAX_LEN = 2, 48


def make_trace(kind: str, n: int, seed: int, *, mean_gap_rounds: float = 1.5,
               burst: int = 4) -> list:
    """Seeded arrival trace: (arrive_round, prompt_len, gen_len) tuples.

    ``poisson`` draws exponential inter-arrival gaps (in router rounds);
    ``bursty`` releases ``burst`` back-to-back arrivals per gap —
    identical offered load, maximally different short-term queue
    pressure.
    Prompt and gen lengths are mixed per request (short/long prompts,
    1..12 token budgets) from the same seeded stream.
    """
    rng = np.random.default_rng(seed)
    if kind == "poisson":
        gaps = rng.exponential(mean_gap_rounds, size=n)
        times = np.floor(np.cumsum(gaps)).astype(int)
    elif kind == "bursty":
        n_bursts = -(-n // burst)
        starts = np.floor(np.cumsum(
            rng.exponential(mean_gap_rounds * burst, size=n_bursts))
        ).astype(int)
        times = np.repeat(starts, burst)[:n]
    else:
        raise ValueError(f"unknown trace kind {kind!r}")
    out = []
    for t in times:
        plen = int(rng.choice([6, 10, 16]))
        glen = int(rng.integers(1, 13))
        out.append((int(t), plen, glen))
    return out


def _percentiles(xs: list) -> dict:
    v = np.asarray(sorted(xs), float)
    return {p: float(np.percentile(v, p)) for p in (50, 95, 99)}


def run_trace(router: ReplicaRouter, trace: list, vocab: int,
              seed: int) -> dict:
    """Drive one trace through the router on a round-indexed clock."""
    rng = np.random.default_rng(seed + 1)
    due = [(t, Request(rid=f"t{i}",
                       prompt=tuple(int(x) for x in
                                    rng.integers(0, vocab, plen)),
                       max_new_tokens=glen))
           for i, (t, plen, glen) in enumerate(trace)]
    budgets = {r.rid: r.max_new_tokens for _, r in due}
    due.sort(key=lambda p: p[0])
    arrive_wall: dict = {}
    latencies, served_tokens = [], 0
    rnd, i = 0, 0
    t0 = time.time()
    deferred: list = []
    while i < len(due) or deferred or router.busy():
        now = time.time() - t0
        todo, deferred = deferred, []
        while i < len(due) and due[i][0] <= rnd:
            todo.append(due[i][1])
            i += 1
        for req in todo:
            arrive_wall.setdefault(req.rid, now)
            try:
                router.submit(req)
            except QueueFull:
                deferred.append(req)     # closed loop: retry next round
        for rid, toks in router.step():
            done = time.time() - t0
            latencies.append(done - arrive_wall[rid])
            assert len(toks) == budgets[rid], \
                f"{rid}: served {len(toks)} of {budgets[rid]} tokens"
            served_tokens += len(toks)
        rnd += 1
    wall = time.time() - t0
    assert len(latencies) == len(trace), "router lost requests"
    return {"wall_s": wall, "served_tokens": served_tokens,
            "rounds": rnd, "latency_s": _percentiles(latencies)}


def build_router(cfg, params, *, replicas: int, chunk: int = 2):
    """Replicated engines over the host-device-count mesh."""
    n_dev = jax.device_count()
    tp = n_dev if (cfg.n_kv_heads % n_dev == 0
                   and cfg.n_heads % n_dev == 0) else 1
    mesh = make_test_mesh((1, tp)) if tp > 1 else None
    engines = [ServeEngine(cfg, params, max_slots=SLOTS, max_len=MAX_LEN,
                           chunk=chunk, mesh=mesh)
               for _ in range(replicas)]
    return ReplicaRouter(engines, policy="least_loaded",
                         max_queue=SLOTS * 2), mesh


def _sharding_gates(cfg) -> list:
    """Pricing invariants the sharded planner must keep (CSV lines)."""
    lines = []
    # per-shard KV stream: strictly 1/tp of the unsharded row bytes
    row = kv_row_bytes(cfg, SLOTS)
    for tp in (2, 4):
        assert row / tp < row, "per-shard KV stream must shrink with TP"
    # collective store traffic: WA residues keep the machine ordering
    rows = {r["machine"]: r for r in collective_traffic(cfg, SLOTS, 2)}
    triple = [rows[m]["coll_bytes"]
              for m in ("neoverse_v2", "golden_cove", "zen4")]
    ok = triple[0] <= triple[1] <= triple[2]
    lines.append(
        "fig9,collective_ordering,0,"
        f"grace={triple[0]:.0f};spr={triple[1]:.0f};zen4={triple[2]:.0f};"
        f"grace_le_spr_le_zen4={'OK' if ok else 'VIOLATED'}")
    if not ok:
        raise AssertionError(
            f"collective WA ordering violated: {triple}")
    return lines


def main(quick: bool = False, replicas: int = 2) -> list:
    """Emit the fig9 load table as benchmark CSV lines."""
    cfg = get_smoke_config(ARCH)
    k_params = jax.random.PRNGKey(0)
    params = M.init_params(cfg, k_params)
    n_req = 8 if quick else 24
    router, mesh = build_router(cfg, params, replicas=replicas)
    tp = tp_degree(mesh_axis_sizes(mesh)) if mesh is not None else 1
    chips = tp * replicas
    # planner prediction for the plan machine: slots tokens per step,
    # every replica decoding concurrently, divided per chip
    from repro.serve.planner import plan_chunk_size
    plan = plan_chunk_size(cfg, SLOTS, MAX_LEN, mesh=mesh)
    pred_tok_s_chip = SLOTS * replicas / max(plan.t_step_seconds,
                                            1e-12) / chips
    lines = []
    for kind in ("poisson", "bursty"):
        trace = make_trace(kind, n_req, seed=42)
        rec = run_trace(router, trace, cfg.vocab_size, seed=42)
        lat = rec["latency_s"]
        assert lat[50] <= lat[95] <= lat[99], "percentile ordering"
        tok_s_chip = rec["served_tokens"] / max(rec["wall_s"], 1e-9) / chips
        ratio = tok_s_chip / pred_tok_s_chip
        lines.append(
            f"fig9,load.{kind},{rec['wall_s']*1e6:.0f},"
            f"n={n_req};replicas={replicas};tp={tp};chips={chips};"
            f"p50_ms={lat[50]*1e3:.1f};p95_ms={lat[95]*1e3:.1f};"
            f"p99_ms={lat[99]*1e3:.1f};rounds={rec['rounds']};"
            f"tok_s_chip={tok_s_chip:.1f};"
            f"pred_tok_s_chip={pred_tok_s_chip:.0f};"
            f"pred_machine={plan.machine};ratio={ratio:.2e}")
        assert math.isfinite(ratio) and ratio > 0, "degenerate ratio"
    lines.extend(_sharding_gates(cfg))
    st = router.stats()
    lines.append(
        "fig9,router,0," + ";".join(
            f"r{s['replica']}={s['completed']}/{s['submitted']}"
            for s in st))
    return lines


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short traces (CI shard-smoke job)")
    ap.add_argument("--replicas", type=int, default=2)
    args = ap.parse_args()
    print("\n".join(main(quick=args.smoke, replicas=args.replicas)))
