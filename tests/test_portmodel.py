"""Port-model engine invariants: flop exactness on dots, loop-trip
multiplication, unit routing, lower-bound structure, and hypothesis
property tests on the spec/shape machinery."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, strategies as st

from repro.core import baseline, hloparse, isa, portmodel
from repro.core.machine import MACHINES, TPU_V5E


def _compile_text(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_dot_flops_exact():
    txt = _compile_text(lambda a, b: a @ b,
                        ((256, 512), jnp.bfloat16),
                        ((512, 1024), jnp.bfloat16))
    rep = portmodel.analyze(txt, TPU_V5E)
    want = 2 * 256 * 512 * 1024
    assert abs(rep.flops - want) / want < 0.05
    assert rep.unknown_ops == 0


def test_scan_trip_multiplication():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c.T) @ c * 0.1, None
        y, _ = jax.lax.scan(body, x, None, length=37)
        return y
    txt = _compile_text(f, ((128, 128), jnp.float32))
    rep = portmodel.analyze(txt, TPU_V5E)
    want = 37 * 2 * (2 * 128 ** 3)
    assert abs(rep.flops - want) / want < 0.1
    assert 37 in rep.trips_seen.values()


def test_transcendental_routing():
    txt = _compile_text(lambda x: jnp.exp(x) + jnp.sin(x),
                        ((8192, 512), jnp.float32))
    rep = portmodel.analyze(txt, TPU_V5E)
    vpu = sum(c for p, c in rep.port_occupation.items()
              if p.startswith("VPU"))
    mxu = sum(c for p, c in rep.port_occupation.items()
              if p.startswith("MXU"))
    assert vpu > 0 and mxu == 0


def test_incore_excludes_memory_ports():
    txt = _compile_text(lambda a, b: a + b,
                        ((1 << 20,), jnp.float32), ((1 << 20,), jnp.float32))
    rep = portmodel.analyze(txt, TPU_V5E)
    assert rep.tp_incore_cycles <= rep.tp_cycles
    assert rep.bytes_hbm >= 3 * 4 * (1 << 20) * 0.9   # 2 reads + 1 write


def test_serial_floor_on_sequential_scan():
    def f(x):
        def body(c, _):
            return jnp.tanh(c) * 0.9 + 0.1, None
        y, _ = jax.lax.scan(body, x, None, length=512)
        return y
    txt = _compile_text(f, ((8, 128), jnp.float32))
    rep = portmodel.analyze(txt, TPU_V5E)
    assert rep.serial_cycles > 0
    # tiny per-step work: the LCD floor must dominate raw port occupation
    assert rep.serial_cycles >= rep.tp_incore_cycles * 0.5


def test_collective_accounting():
    # single-device: no collectives expected; exercise the parser path
    txt = _compile_text(lambda a: a.sum(), ((128, 128), jnp.float32))
    rep = portmodel.analyze(txt, TPU_V5E)
    assert rep.coll_bytes == {}


def test_baseline_predict_monotone():
    m = MACHINES["tpu_v5e"]
    r1 = baseline.predict({"flops": 1e12, "bytes accessed": 1e9}, m)
    r2 = baseline.predict({"flops": 2e12, "bytes accessed": 1e9}, m)
    assert r2.seconds >= r1.seconds
    assert r1.bottleneck() in ("compute", "memory")


# ---- hypothesis property tests --------------------------------------------

@given(st.lists(st.integers(1, 512), min_size=0, max_size=4),
       st.sampled_from(["f32", "bf16", "s32", "pred"]))
def test_parse_shapes_roundtrip(dims, dtype):
    s = f"{dtype}[{','.join(map(str, dims))}]"
    shapes = hloparse.parse_shapes(s)
    assert shapes[0].dtype == dtype
    assert shapes[0].dims == tuple(dims)
    import math
    assert shapes[0].elems == math.prod(dims) if dims else 1


@given(st.integers(1, 4096), st.integers(1, 4096), st.integers(1, 4096))
def test_mxu_pass_count_lower_bound(m, n, k):
    """ceil-div tiling: passes x 128^3 >= m*n*k (padding never loses work)."""
    import math
    passes = math.ceil(m / 128) * math.ceil(n / 128) * math.ceil(k / 128)
    assert passes * 128 ** 3 >= m * n * k


@given(st.integers(1, 10_000_000))
def test_vpu_blocks_cover_elements(e):
    blocks = isa._vpu_blocks(e)
    assert blocks * isa.VPU_BLOCK >= e
    assert (blocks - 1) * isa.VPU_BLOCK < e


def test_report_bound_is_max_of_terms():
    txt = _compile_text(lambda a, b: jax.nn.relu(a @ b),
                        ((512, 512), jnp.bfloat16),
                        ((512, 512), jnp.bfloat16))
    rep = portmodel.analyze(txt, TPU_V5E)
    assert rep.bound_cycles >= rep.tp_cycles
    assert rep.bound_cycles >= rep.serial_cycles
    assert rep.bound_incore_cycles <= rep.bound_cycles


# ---- compare() process-pool fan-out + degradation paths --------------------

def _chain_text():
    def f(x):
        def body(c, _):
            return jnp.tanh(c @ c.T) @ c * 0.1, None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y
    return _compile_text(f, ((64, 64), jnp.float32))


def test_compare_pool_matches_serial():
    txt = _chain_text()
    serial = portmodel.compare(txt, parallel="serial")
    pooled = portmodel.compare(txt, parallel="process")
    assert list(serial) == list(pooled)
    for name in serial:
        s, p = serial[name], pooled[name]
        assert s.tp_cycles == p.tp_cycles
        assert s.serial_cycles == p.serial_cycles
        assert s.bytes_hbm == p.bytes_hbm
        assert s.t_mem_tier == p.t_mem_tier
        assert s.bottleneck_tier == p.bottleneck_tier


def test_compare_unpicklable_model_falls_back_serial():
    import dataclasses
    txt = _chain_text()
    adhoc = dataclasses.replace(TPU_V5E, name="adhoc_unpicklable")
    object.__setattr__(adhoc, "chip", lambda: None)   # lambdas don't pickle
    import pickle
    with pytest.raises(Exception):
        pickle.dumps(adhoc)
    reports = portmodel.compare(txt, machines=[adhoc, TPU_V5E],
                                parallel="process")
    assert set(reports) == {"adhoc_unpicklable", "tpu_v5e"}
    ref = portmodel.compare(txt, machines=[TPU_V5E], parallel="serial")
    assert reports["tpu_v5e"].tp_cycles == ref["tpu_v5e"].tp_cycles


# ---- missing-µ-op-class degradation (Analyzer._occupy) ---------------------

def test_missing_vpu_class_degrades_with_counted_warning():
    """A machine injected straight into the MACHINES dict (bypassing
    validate_model) without a `vpu` entry used to KeyError; it now
    degrades to the cheapest available class, warns, and counts."""
    import dataclasses
    import warnings as _warnings
    table = {k: v for k, v in TPU_V5E.table.items() if k != "vpu"}
    novpu = dataclasses.replace(TPU_V5E, name="novpu_test", table=table)
    MACHINES["novpu_test"] = novpu
    try:
        txt = _compile_text(lambda x: jnp.exp(x) + x,
                            ((512, 512), jnp.float32))
        with _warnings.catch_warnings(record=True) as got:
            _warnings.simplefilter("always")
            rep = portmodel.analyze(txt, "novpu_test")
        assert rep.fallback_uops > 0
        assert any("novpu_test" in str(w.message) and
                   isinstance(w.message, RuntimeWarning) for w in got)
        # degradation is usable: a bound still comes out
        assert rep.tp_cycles > 0
    finally:
        del MACHINES["novpu_test"]


def test_full_machines_never_fall_back():
    txt = _chain_text()
    for name, rep in portmodel.compare(txt, parallel="serial").items():
        assert rep.fallback_uops == 0, name


def test_compare_never_forks_with_accelerator_live(monkeypatch):
    """A process that holds an accelerator runs the fan-out serially,
    even when the pool is forced."""
    txt = _chain_text()
    serial = portmodel.compare(txt, parallel="serial")

    def no_pool(*a, **kw):
        raise AssertionError("forked a pool with an accelerator live")

    monkeypatch.setattr(portmodel, "_accelerator_live", lambda: True)
    monkeypatch.setattr(portmodel, "ProcessPoolExecutor", no_pool)
    pooled = portmodel.compare(txt, parallel="process")
    assert {n: r.tp_cycles for n, r in pooled.items()} == \
        {n: r.tp_cycles for n, r in serial.items()}
