"""Mesh-sharded serving: planner sharding keys, per-shard pricing,
mesh=None bit-identity, spec properties, and 2-device token identity.

The 2-device tests run the engines in a subprocess because jax pins
the host device count at first init — the suite process has already
initialized jax on one device by the time these tests run.
"""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest
from hypothesis import given, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.serve import (PagedServeEngine, Request, ServeEngine,
                         collective_traffic, kv_read_seconds,
                         plan_chunk_size)
from repro.serve import planner as planner_lib
from repro.utils.sharding import (SERVE_ENGINE_RULES, rules_fingerprint,
                                  spec_for, tp_degree)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_mesh(data=1, model=2):
    """Mesh stand-in for planner tests: only axis names/sizes are read
    (the planner never places arrays), so no real devices are needed."""
    return types.SimpleNamespace(
        axis_names=("data", "model"),
        devices=types.SimpleNamespace(shape=(data, model)))


@pytest.fixture()
def cfg():
    return get_smoke_config("yi-9b")     # 4 q heads / 2 kv heads: TP=2 ok


# -- planner ---------------------------------------------------------------
def test_plan_cache_keys_on_sharding(cfg):
    """Regression: the memo key must fold mesh sizes/rules/TP — a
    sharded plan must never serve an unsharded admission (and vice
    versa), which is exactly what happened when the key ignored
    sharding."""
    planner_lib.clear_plan_cache()
    p0 = plan_chunk_size(cfg, 2, 32)
    ps = plan_chunk_size(cfg, 2, 32, mesh=_fake_mesh())
    assert p0.tp == 1 and ps.tp == 2
    assert ps is not p0
    # both entries memo-hit their own key
    assert plan_chunk_size(cfg, 2, 32) is p0
    assert plan_chunk_size(cfg, 2, 32, mesh=_fake_mesh()) is ps
    # and a different TP degree is a third entry
    p4 = plan_chunk_size(cfg, 2, 32, mesh=_fake_mesh(model=4))
    assert p4.tp == 4 and p4 is not ps


def test_unsharded_plan_is_bit_identical_to_pre_mesh_planner(cfg):
    """mesh=None pins the single-device pricing exactly: no TP, no
    collective, no dense-adjustment pass."""
    planner_lib.clear_plan_cache()
    p = plan_chunk_size(cfg, 2, 32)
    assert p.tp == 1
    assert p.per_machine_collective is None
    assert p.per_machine_dense is None          # no occupancy, no adjust
    # explicit rules without a mesh are equally inert
    planner_lib.clear_plan_cache()
    q = plan_chunk_size(cfg, 2, 32)
    assert q.per_machine == p.per_machine
    assert q.chunk == p.chunk


def test_sharded_plan_prices_shard_stream_and_collective(cfg):
    planner_lib.clear_plan_cache()
    p0 = plan_chunk_size(cfg, 2, 32)
    ps = plan_chunk_size(cfg, 2, 32, mesh=_fake_mesh())
    assert ps.per_machine_collective
    assert set(ps.per_machine_collective) == set(ps.per_machine)
    for name in ps.per_machine:
        # per-shard KV stream can only shrink the step; the collective
        # adds back a (much smaller, at these shapes) reduce term
        assert ps.per_machine[name] <= p0.per_machine[name] + \
            ps.per_machine_collective[name] + 1e-18


def test_kv_read_seconds_scales_per_shard(cfg):
    for m in ("neoverse_v2", "golden_cove", "zen4"):
        t1 = kv_read_seconds(cfg, 2, 32, m, max_len=32)
        t1_explicit = kv_read_seconds(cfg, 2, 32, m, max_len=32, tp=1)
        t2 = kv_read_seconds(cfg, 2, 32, m, max_len=32, tp=2)
        assert t1 == t1_explicit
        assert t2 < t1


# -- collective pricing ----------------------------------------------------
def test_collective_traffic_machine_ordering(cfg):
    """WA residues on the ring's store legs keep the paper ordering
    Grace <= SPR <= Zen 4 per shard."""
    rows = {r["machine"]: r for r in collective_traffic(cfg, 4, 2)}
    grace = rows["neoverse_v2"]["coll_bytes"]
    spr = rows["golden_cove"]["coll_bytes"]
    zen4 = rows["zen4"]["coll_bytes"]
    assert grace <= spr <= zen4
    assert grace < zen4                  # WA evasion is a strict win


def test_collective_traffic_tp1_is_free(cfg):
    for r in collective_traffic(cfg, 4, 1):
        assert r["ring_bytes"] == 0.0
        assert r["coll_seconds"] == 0.0


def test_tp_degree_reads_rules():
    assert tp_degree({"data": 4, "model": 2}) == 2
    assert tp_degree({"data": 4}) == 1
    assert tp_degree({}) == 1
    assert tp_degree({"model": 8}, dict(SERVE_ENGINE_RULES,
                                        kvheads=())) == 1
    assert rules_fingerprint(None) == ()
    assert rules_fingerprint(SERVE_ENGINE_RULES) == \
        rules_fingerprint(dict(SERVE_ENGINE_RULES))


# -- engine mesh plumbing --------------------------------------------------
def test_engine_mesh_none_is_the_untouched_path(cfg):
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_slots=2, max_len=16, chunk=2)
    assert eng.mesh is None and eng.rules is None and eng.tp == 1
    assert eng.params is params          # no device_put detour


def test_engine_one_device_mesh_token_identity(cfg):
    """A (1, 1) mesh goes through every sharded hook (device_put,
    rule-scoped tracing, sc constraints) and must not move a token."""
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=f"r{i}",
                    prompt=tuple(int(t) for t in
                                 rng.integers(0, cfg.vocab_size, 5)),
                    max_new_tokens=3) for i in range(3)]
    mesh = make_test_mesh((1, 1))
    base = ServeEngine(cfg, params, max_slots=2, max_len=16,
                       chunk=2).run(list(reqs))
    for cls, kw in ((ServeEngine, {}),
                    (PagedServeEngine, {"page_size": 4})):
        eng = cls(cfg, params, max_slots=2, max_len=16, chunk=2,
                  mesh=mesh, **kw)
        out = eng.run(list(reqs))
        for r in reqs:
            np.testing.assert_array_equal(out[r.rid], base[r.rid])


def test_engine_rejects_indivisible_heads(cfg):
    # yi-9b smoke has 2 kv heads: TP=3 cannot split them
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="KV heads"):
        ServeEngine(cfg, params, max_slots=2, max_len=16, chunk=2,
                    mesh=_fake_mesh(model=3))


def _two_device_child(*argv) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests",
                                      "_sharded_serve_child.py"), *argv],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_two_device_sharded_token_identity(layout):
    """Acceptance pin: dense and paged engines sharded over a (1, 2)
    host mesh serve token-identical streams to the unsharded engine."""
    rec = _two_device_child(layout)
    assert rec["tp"] == 2
    assert rec["match"], f"sharded tokens diverged: {rec['tokens']}"


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_two_device_sharded_kernel_token_identity(layout):
    """The Pallas decode kernels under a (1, 2) mesh (shard_map over the
    KV heads) serve the unsharded kernel engine's streams."""
    rec = _two_device_child(layout, "pallas")
    assert rec["tp"] == 2
    assert rec["match"], f"sharded tokens diverged: {rec['tokens']}"


# -- spec properties -------------------------------------------------------
@given(st.sampled_from(sorted(ARCH_IDS)),
       st.sampled_from([1, 2, 4, 8, 16]),
       st.sampled_from([1, 2, 4, 16]))
def test_param_tree_specs_never_reuse_a_mesh_axis(arch, dp, tp):
    """Across a full param tree (and the serve cache tree), no leaf
    spec may assign the same mesh axis to two dims — jax would reject
    the sharding at placement; the greedy builder must never emit it."""
    cfg = get_config(arch)
    sizes = {"data": dp, "model": tp}
    trees = [M.param_pspecs(cfg, SERVE_ENGINE_RULES, sizes),
             M.cache_pspecs(cfg, SERVE_ENGINE_RULES, sizes, 4, 64)]
    leaves = [lf for t in trees
              for lf in jax.tree.leaves(t,
                                        is_leaf=lambda x:
                                        isinstance(x, P))]
    assert leaves
    for spec in leaves:
        used = [a for part in spec if part is not None
                for a in ((part,) if isinstance(part, str) else part)]
        assert len(used) == len(set(used)), (arch, sizes, spec)


def test_serve_engine_rules_pin_kvheads_to_model_axis():
    """The serve-engine layout: kv_seq never takes the model axis (the
    kernels tile the sequence), kvheads does."""
    sizes = {"data": 1, "model": 2}
    spec = spec_for((4, 64, 2, 32),
                    ("batch", "kv_seq", "kvheads", None),
                    SERVE_ENGINE_RULES, sizes)
    assert spec[1] is None
    assert spec[2] == "model"
