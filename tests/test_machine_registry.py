"""Cross-vendor machine registry: µ-op table completeness, registration
validation, calibration round-trips, compare() fan-out, and the paper's
qualitative write-allocate ordering (Fig. 4)."""

import jax
import jax.numpy as jnp
import pytest

from repro.core import isa, portmodel, wa
from repro.core.machine import (MACHINES, MachineModel, MachineValidationError,
                                OpEntry, get_machine, host_cpu_model,
                                register, registered_models,
                                registered_names, validate_model)

CPU_NAMES = ("zen4", "golden_cove", "neoverse_v2")


def _compile_text(fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


# ---- completeness of every registered machine -----------------------------

def test_all_machines_have_complete_uop_tables():
    assert registered_models(), "registry must not be empty"
    for m in registered_models():
        for cls in isa.UOP_CLASSES:
            e = m.table.get(cls)
            assert e is not None, f"{m.name} missing {cls}"
            assert e.cycles_per_unit > 0, f"{m.name}/{cls}"
            assert e.latency >= 0, f"{m.name}/{cls}"
            assert e.ports, f"{m.name}/{cls} has no ports"
            assert set(e.ports) <= set(m.ports)


def test_paper_cpus_registered_with_expected_topology():
    for name in CPU_NAMES:
        assert name in registered_names()
    zen4 = get_machine("zen4")
    glc = get_machine("golden_cove")
    v2 = get_machine("neoverse_v2")
    # Table II: FMA pipe pair on x86, all four pipes on V2
    assert len(zen4.entry("mxu").ports) == 2
    assert len(glc.entry("mxu").ports) == 2
    assert len(v2.entry("mxu").ports) == 4
    # divider pinned to a single pipe everywhere (asymmetric port set)
    for m in (zen4, glc, v2):
        assert len(m.entry("vdiv").ports) == 1
    # SIMD width: 2x256b double-pump < 512b; V2 has 4x128b
    assert zen4.simd_width_bytes == 32
    assert glc.simd_width_bytes == 64
    assert v2.simd_width_bytes == 16
    # WA-mode tags drive core/wa.py mode selection
    assert zen4.wa_mode == "explicit_only"
    assert glc.wa_mode == "saturation_gated"
    assert v2.wa_mode == "auto_claim"


# ---- registration validation ----------------------------------------------

def _tiny_model(name="tiny", **overrides) -> MachineModel:
    ports = ("P0", "MEM", "ICI")
    table = {cls: OpEntry(("MEM",) if cls in ("dma", "ici") else ("P0",),
                          1.0, 1.0)
             for cls in isa.UOP_CLASSES}
    table.update(overrides.pop("table_overrides", {}))
    kw = dict(name=name, clock_hz=1e9, ports=ports, table=table)
    kw.update(overrides)
    return MachineModel(**kw)


def test_register_rejects_incomplete_table():
    m = _tiny_model()
    t = dict(m.table)
    del t["vdiv"]
    bad = MachineModel(name="bad", clock_hz=1e9, ports=m.ports, table=t)
    with pytest.raises(MachineValidationError):
        register(bad)
    assert "bad" not in MACHINES


def test_register_rejects_bad_entries():
    with pytest.raises(MachineValidationError):
        validate_model(_tiny_model(
            table_overrides={"vpu": OpEntry(("P0",), 0.0, 1.0)}))
    with pytest.raises(MachineValidationError):
        validate_model(_tiny_model(
            table_overrides={"vpu": OpEntry(("P0",), 1.0, -1.0)}))
    with pytest.raises(MachineValidationError):
        validate_model(_tiny_model(
            table_overrides={"vpu": OpEntry(("NOPE",), 1.0, 1.0)}))
    with pytest.raises(MachineValidationError):
        validate_model(_tiny_model(wa_mode="sometimes"))
    with pytest.raises(MachineValidationError):
        validate_model(_tiny_model(
            table_overrides={"vpu": OpEntry(("P0",), 1.0, 1.0,
                                            port_weights=(1.0, 2.0))}))


def test_register_requires_replace_to_overwrite():
    m = _tiny_model(name="dup_test")
    try:
        register(m)
        with pytest.raises(ValueError):
            register(m)
        m2 = register(_tiny_model(name="dup_test", clock_hz=2e9),
                      replace=True)
        assert get_machine("dup_test") is m2
    finally:
        MACHINES.pop("dup_test", None)


def test_get_machine_resolves_names_and_models():
    m = get_machine("tpu_v5e")
    assert get_machine(m) is m
    with pytest.raises(KeyError):
        get_machine("not_a_machine")


# ---- host calibration round-trip ------------------------------------------

def test_host_cpu_model_calibration_roundtrip():
    calib = {"vpu": 2.5e9, "mxu": 4.0e7, "dma": 3.3e10}
    m = host_cpu_model(calib)
    validate_model(m)
    for cls, rate in calib.items():
        # cycles_per_unit at the nominal 1 GHz clock == 1e9 / rate
        assert m.entry(cls).cycles_per_unit == pytest.approx(1e9 / rate)
    # unlisted classes keep defaults but stay valid/positive
    assert m.entry("vdiv").cycles_per_unit > 0


def test_calibrated_model_registers_as_host_cpu():
    before = MACHINES.pop("host_cpu", None)
    try:
        register(host_cpu_model({"vpu": 1e9}), replace=True)
        assert "host_cpu" in registered_names()
        assert get_machine("host_cpu").entry("vpu").cycles_per_unit \
            == pytest.approx(1.0)
    finally:
        MACHINES.pop("host_cpu", None)
        if before is not None:
            MACHINES["host_cpu"] = before


# ---- analysis across the registry -----------------------------------------

def test_analyzer_accepts_machine_names():
    txt = _compile_text(lambda a, b: a @ b,
                        ((128, 128), jnp.float32), ((128, 128), jnp.float32))
    by_name = portmodel.analyze(txt, "zen4")
    by_model = portmodel.analyze(txt, get_machine("zen4"))
    assert by_name.tp_cycles == pytest.approx(by_model.tp_cycles)
    assert by_name.flops == pytest.approx(2 * 128 ** 3, rel=0.05)


def test_compare_returns_one_report_per_machine():
    txt = _compile_text(lambda a, b: jnp.tanh(a @ b),
                        ((128, 128), jnp.float32), ((128, 128), jnp.float32))
    names = ("zen4", "golden_cove", "neoverse_v2", "tpu_v5p")
    reps = portmodel.compare(txt, machines=names)
    assert tuple(reps) == names
    for name, rep in reps.items():
        assert isinstance(rep, portmodel.Report)
        assert rep.bound_cycles > 0
        assert rep.bottleneck() != "none"
    # same module, same flops on every machine — only cycles differ
    flops = {round(r.flops) for r in reps.values()}
    assert len(flops) == 1
    # fan-out matches sequential analysis exactly
    solo = portmodel.analyze(txt, "zen4")
    assert reps["zen4"].tp_cycles == pytest.approx(solo.tp_cycles)
    assert reps["zen4"].port_occupation == solo.port_occupation


def test_compare_defaults_to_whole_registry():
    txt = _compile_text(lambda a: a + 1.0, ((1024,), jnp.float32))
    reps = portmodel.compare(txt)
    assert set(reps) == set(registered_names())


def test_vdiv_routes_to_single_divider_port():
    txt = _compile_text(lambda a, b: a / b,
                        ((8192,), jnp.float32), ((8192,), jnp.float32))
    rep = portmodel.analyze(txt, "zen4")
    m = get_machine("zen4")
    div_port = m.entry("vdiv").ports[0]
    others = [p for p in m.entry("vpu").ports if p != div_port]
    assert rep.port_occupation.get(div_port, 0.0) > 0
    # divide work must not smear over the non-divider SIMD pipes
    assert rep.port_occupation.get(div_port, 0.0) > \
        max(rep.port_occupation.get(p, 0.0) for p in others)


def test_vlsu_port_weights_split_load_store():
    m = get_machine("neoverse_v2")
    e = m.entry("vlsu")
    assert e.port_weights is not None
    txt = _compile_text(lambda a: jnp.roll(a, 1), ((1 << 16,), jnp.float32))
    rep = portmodel.analyze(txt, m)
    ld = rep.port_occupation.get("LD0", 0.0)
    st = rep.port_occupation.get("ST0", 0.0)
    assert ld > 0 and st > 0
    # store pipes carry the smaller weighted share
    assert st < ld


# ---- the paper's WA ordering ----------------------------------------------

def test_wa_modes_follow_machine_tags():
    assert wa.wa_mode_of("zen4") == "explicit_only"
    assert wa.wa_mode_of(get_machine("tpu_v5e")) == "auto_claim"
    # Fig. 4, no NT stores: Grace <= SPR <= Zen 4
    grace = wa.traffic_ratio_for("neoverse_v2")
    spr = wa.traffic_ratio_for("golden_cove")
    zen = wa.traffic_ratio_for("zen4")
    assert grace <= spr <= zen
    assert grace == pytest.approx(1.0)
    assert zen == pytest.approx(2.0)
    # with NT stores Zen 4 evades fully, SPR keeps ~10% residue
    assert wa.traffic_ratio_for("zen4", nt_stores=True) == pytest.approx(1.0)
    assert wa.traffic_ratio_for("golden_cove", nt_stores=True) \
        == pytest.approx(1.1)


def test_apply_wa_mode_counts_rmw_consistently():
    # all-partial store scan: RMW reads equal the payload
    scan = {"stored_bytes": 100.0, "rmw_read_bytes": 100.0,
            "copy_bytes": 0.0, "wa_ratio": 2.0}
    grace = wa.apply_wa_mode(scan, "neoverse_v2")
    # auto_claim traffic must equal the scan's own stored+rmw bytes
    assert grace["traffic_bytes"] == pytest.approx(200.0)
    zen = wa.apply_wa_mode(scan, "zen4")
    # explicit_only: full write-allocate on top of the tiling reads
    assert zen["traffic_bytes"] == pytest.approx(300.0)


def test_machine_store_traffic_ordering_on_real_module():
    def f(x, cache):
        y = jnp.tanh(x) * 2.0
        return jax.lax.dynamic_update_slice(cache, y[None], (0, 0, 0))
    txt = _compile_text(f, ((64, 128), jnp.float32),
                        ((4, 64, 128), jnp.float32))
    t = {n: wa.machine_store_traffic(txt, n)["traffic_bytes"]
         for n in CPU_NAMES}
    assert t["neoverse_v2"] <= t["golden_cove"] <= t["zen4"]
    w = wa.machine_store_traffic(txt, "zen4")
    assert w["traffic_bytes"] >= w["stored_bytes"] > 0
    assert w["wa_mode"] == "explicit_only"


def test_tpu_machine_comes_from_device_kind():
    """One table maps a TPU's device_kind to its machine; an unknown
    kind raises instead of pricing the chip as some other one."""
    from repro.utils.hw import CHIPS, TPU_KINDS, chip_for_kind
    assert chip_for_kind("TPU v5 lite") == "tpu_v5e"
    assert set(TPU_KINDS.values()) <= set(CHIPS) <= set(MACHINES)
    with pytest.raises(ValueError, match="device_kind"):
        chip_for_kind("TPU v6 lite")
