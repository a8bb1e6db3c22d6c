"""Hardware constants for the target TPU fleet and roofline math.

These mirror the paper's Table I ("core features") for our three target
TPU generations, plus the assignment-mandated v5e numbers used for all
roofline terms:

    197 TFLOP/s bf16 per chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class MemTier:
    """One level of a machine's memory hierarchy (ECM-style tier).

    Capacities are the working-set capacity *visible from one core* (the
    classic cache-ladder x-axis): private L1/L2 capacity for the private
    tiers, the shared slice a single core can realistically occupy for
    L3/SLC, and ``inf`` for DRAM/HBM. Bandwidths are single-core
    sustained rates; ``shared_bw`` is the socket-level ceiling for
    shared tiers (0.0 marks a private tier whose aggregate bandwidth
    scales linearly with active cores).

    ``wa_residue`` parametrizes write-allocate evasion quality at this
    tier boundary, after the CloverLeaf WA-evasion study (arXiv:
    2311.04797): the fraction of allocate-read traffic that *remains*
    when the machine's evasion mechanism (cache-line claim, SpecI2M, NT
    stores) engages for stores homed here. 1.0 = no mechanism operates
    at this boundary; 0.0 = perfect evasion.
    """

    name: str                  # "L1" / "L2" / "L3" / "DRAM" / "VMEM"...
    capacity_bytes: float      # working-set capacity seen from one core
    load_bw: float             # bytes/s, single-core sustained load
    store_bw: float            # bytes/s, single-core sustained store
    shared_bw: float = 0.0     # socket ceiling; 0.0 = private tier
    wa_residue: float = 1.0    # allocate fraction left under evasion


def _cache_ladder(clock_hz: float, levels: tuple) -> tuple:
    """Build a MemTier ladder from per-level (name, capacity, load B/cy,
    store B/cy, shared GB/s or 0, wa_residue) rows at a fixed clock."""
    return tuple(
        MemTier(name=n, capacity_bytes=float(cap),
                load_bw=ld * clock_hz, store_bw=st * clock_hz,
                shared_bw=sh * 1e9, wa_residue=res)
        for (n, cap, ld, st, sh, res) in levels)


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    # peak compute
    bf16_flops: float          # FLOP/s per chip
    int8_ops: float            # OP/s per chip
    # memory system
    hbm_bytes: float           # capacity per chip
    hbm_bw: float              # bytes/s per chip
    vmem_bytes: float          # on-chip vector memory
    # interconnect
    ici_link_bw: float         # bytes/s per link (one direction)
    ici_links: int             # links per chip (3D torus: 6; 2D: 4)
    # core geometry (for the in-core port model)
    clock_hz: float
    n_mxu: int                 # 128x128 systolic arrays per core
    n_vpu: int                 # (8,128) vector ALU lanesets usable per cycle
    native_tile: tuple = (8, 128)  # tile granule (fp32 sublane x lane)
    mem_tiers: tuple = ()      # MemTier ladder (VMEM -> HBM), inner first


def _tpu_tiers(vmem_bytes: float, hbm_bw: float) -> tuple:
    """VMEM + HBM ladder for a TPU chip.

    VMEM feeds the compute units at roughly an order of magnitude above
    HBM (it backs every VPU operand fetch); HBM is the DMA-visible tier.
    Both claim full tiles on store (the Grace-like `auto_claim`
    behaviour, DESIGN.md §2), so the WA residue is 0 at both tiers.
    """
    return (
        MemTier("VMEM", float(vmem_bytes), 10.0 * hbm_bw, 10.0 * hbm_bw,
                shared_bw=10.0 * hbm_bw, wa_residue=0.0),
        MemTier("HBM", math.inf, hbm_bw, hbm_bw,
                shared_bw=hbm_bw, wa_residue=0.0),
    )


# TPU v5e — the assignment's target chip. 197 bf16 TFLOP/s at ~0.94 GHz
# with 4 MXUs: 4 * 128*128 * 2 flop * clock ≈ 197e12 → clock ≈ 1.5e9 / ...
# Public spec: 393 int8 TOPS / 197 bf16 TFLOPS, 16 GB HBM2E @ 819 GB/s,
# 1.6 Tbps ICI x4 links (=50 GB/s/link/dir).
TPU_V5E = ChipSpec(
    name="tpu_v5e",
    bf16_flops=197e12,
    int8_ops=394e12,
    hbm_bytes=16e9,
    hbm_bw=819e9,
    vmem_bytes=128 * 2**20,
    ici_link_bw=50e9,
    ici_links=4,
    clock_hz=1.5e9,   # modeled: 4 MXU * 128*128*2 * 1.5e9 = 196.6e12
    n_mxu=4,
    n_vpu=8,
    mem_tiers=_tpu_tiers(128 * 2**20, 819e9),
)

# TPU v5p — the "Sapphire Rapids" of the comparison: widest compute.
TPU_V5P = ChipSpec(
    name="tpu_v5p",
    bf16_flops=459e12,
    int8_ops=918e12,
    hbm_bytes=95e9,
    hbm_bw=2765e9,
    vmem_bytes=128 * 2**20,
    ici_link_bw=100e9,
    ici_links=6,
    clock_hz=1.75e9,  # modeled: 8 MXU * 128*128*2 * 1.75e9 ≈ 459e12
    n_mxu=8,
    n_vpu=16,
    mem_tiers=_tpu_tiers(128 * 2**20, 2765e9),
)

# TPU v4 — previous generation baseline.
TPU_V4 = ChipSpec(
    name="tpu_v4",
    bf16_flops=275e12,
    int8_ops=275e12,
    hbm_bytes=32e9,
    hbm_bw=1228e9,
    vmem_bytes=128 * 2**20,
    ici_link_bw=50e9,
    ici_links=6,
    clock_hz=1.05e9,  # modeled: 8 MXU * 128*128*2 * 1.05e9 ≈ 275e12
    n_mxu=8,
    n_vpu=16,
    mem_tiers=_tpu_tiers(128 * 2**20, 1228e9),
)

CHIPS = {c.name: c for c in (TPU_V5E, TPU_V5P, TPU_V4)}

#: ``jax.Device.device_kind`` -> the ChipSpec (and registered machine)
#: that models it. The kind strings are the ones libtpu reports for each
#: generation (``jax.experimental.topologies.get_topology_desc`` on
#: "v5e:2x2", "v5p:2x2x1" and "v4:2x2x1"); the peaks above are Google
#: Cloud's published per-chip figures ("TPU v5e", "TPU v5p", "TPU v4").
TPU_KINDS = {"TPU v5 lite": "tpu_v5e", "TPU v5": "tpu_v5p",
             "TPU v4": "tpu_v4"}


def chip_for_kind(device_kind: str) -> str:
    """Machine name for a TPU ``device_kind``; unknown kinds raise.

    A chip that is not in :data:`TPU_KINDS` has no peaks and no port
    model here, and pricing it as some other generation would mislabel
    every number derived from it.
    """
    try:
        return TPU_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"no machine model for TPU device_kind {device_kind!r}; "
            f"known kinds: {sorted(TPU_KINDS)}") from None


# --- the paper's actual CPUs (Table I / Table II core features) -------------

@dataclasses.dataclass(frozen=True)
class CpuSpec:
    """Core + node features of one paper CPU (Table I / Table II).

    Port counts describe the scheduler-visible functional-unit groups the
    in-core model needs: FMA-capable SIMD pipes (the `mxu` analogue), total
    SIMD/FP pipes (`vpu`), load/store pipes (`vlsu`), and the single
    divider pipe (`vdiv`).
    """
    name: str
    vendor: str
    uarch: str
    isa: str
    clock_hz: float            # fixed core clock used in the paper's runs
    issue_width: int           # rename/dispatch width, µops per cycle
    simd_width_bytes: int      # native datapath width per FP pipe
    n_fma: int                 # FMA-capable SIMD pipes
    n_simd: int                # all SIMD/FP ALU pipes
    n_load: int                # load pipes (SIMD-capable)
    n_store: int               # store-data pipes
    fma_latency: float         # cycles
    load_latency: float        # L1 load-to-use, cycles (vector)
    fdiv_recip_tput: float     # cycles per full-width vector divide
    fdiv_latency: float
    l1d_bytes: int
    mem_bw: float              # bytes/s sustained per socket (stream-like)
    xsocket_bw: float          # bytes/s cross-socket/C2C link
    cores: int                 # cores per socket
    wa_mode: str               # write-allocate behaviour (core/wa.py)
    mem_tiers: tuple = ()      # MemTier cache ladder, L1 first, DRAM last


# AMD Genoa / Zen 4 (EPYC 9654). 6-wide; 4 FP pipes of which FP0/FP1 are
# 256-bit FMA (AVX-512 is double-pumped on the 256-bit datapath); divider
# on one pipe, not pipelined. WA evasion only via explicit NT stores.
ZEN4 = CpuSpec(
    name="zen4", vendor="AMD", uarch="Zen 4", isa="x86-64 AVX-512(2x256b)",
    clock_hz=2.4e9, issue_width=6, simd_width_bytes=32,
    n_fma=2, n_simd=4, n_load=2, n_store=1,
    fma_latency=4.0, load_latency=7.0,
    fdiv_recip_tput=6.5, fdiv_latency=13.0,
    l1d_bytes=32 * 1024, mem_bw=460.8e9, xsocket_bw=50e9, cores=96,
    wa_mode="explicit_only",
    # Cache ladder (B/cy single core at 2.4 GHz; shared GB/s socket).
    # Standard stores write-allocate at every boundary (residue 1.0);
    # only explicit NT stores evade, fully, at the DRAM interface.
    mem_tiers=_cache_ladder(2.4e9, (
        ("L1", 32 * 1024, 64.0, 32.0, 0.0, 1.0),
        ("L2", 1 * 2**20, 32.0, 32.0, 0.0, 1.0),
        ("L3", 32 * 2**20, 24.0, 20.0, 1380.0, 1.0),   # one CCD slice
        ("DRAM", math.inf, 16.0, 10.0, 460.8, 0.0),    # NT: full evasion
    )),
)

# Intel Sapphire Rapids / Golden Cove (Xeon 8470). 6-wide; with AVX-512
# ports P0+P1 fuse into one 512-bit FMA pipe next to P5 -> two 512-bit
# FMA pipes; divider on P0; 2x512b loads + 1x512b store per cycle.
# SpecI2M evades write-allocates only near bandwidth saturation.
GOLDEN_COVE = CpuSpec(
    name="golden_cove", vendor="Intel", uarch="Golden Cove",
    isa="x86-64 AVX-512", clock_hz=2.0e9, issue_width=6,
    simd_width_bytes=64, n_fma=2, n_simd=2, n_load=2, n_store=1,
    fma_latency=4.0, load_latency=7.0,
    fdiv_recip_tput=8.0, fdiv_latency=16.0,
    l1d_bytes=48 * 1024, mem_bw=307.2e9, xsocket_bw=48e9, cores=52,
    wa_mode="saturation_gated",
    # SpecI2M operates only at the memory interface and leaves ~10% of
    # the allocate traffic behind even when fully engaged (Fig. 4).
    mem_tiers=_cache_ladder(2.0e9, (
        ("L1", 48 * 1024, 128.0, 64.0, 0.0, 1.0),
        ("L2", 2 * 2**20, 64.0, 48.0, 0.0, 1.0),
        ("L3", 105 * 2**20, 20.0, 12.0, 900.0, 1.0),   # mesh-limited
        ("DRAM", math.inf, 15.0, 10.0, 307.2, 0.1),    # SpecI2M residue
    )),
)

# NVIDIA Grace / Neoverse V2. 8-wide; 4x128-bit SIMD pipes V0..V3, all
# FMA-capable; divider on V0; 3 load + 2 store pipes. The cache claims
# lines on store misses -> next-to-optimal automatic WA evasion.
NEOVERSE_V2 = CpuSpec(
    name="neoverse_v2", vendor="NVIDIA", uarch="Neoverse V2",
    isa="AArch64 NEON/SVE2(4x128b)", clock_hz=3.4e9, issue_width=8,
    simd_width_bytes=16, n_fma=4, n_simd=4, n_load=3, n_store=2,
    fma_latency=4.0, load_latency=6.0,
    fdiv_recip_tput=7.0, fdiv_latency=15.0,
    l1d_bytes=64 * 1024, mem_bw=500e9, xsocket_bw=450e9, cores=72,
    wa_mode="auto_claim",
    # The cache claims lines on store misses at every level, so the WA
    # residue is 0 at every tier boundary — the paper's "next-to-
    # optimal automatic WA evasion".
    mem_tiers=_cache_ladder(3.4e9, (
        ("L1", 64 * 1024, 48.0, 32.0, 0.0, 0.0),
        ("L2", 1 * 2**20, 32.0, 24.0, 0.0, 0.0),
        ("L3", 114 * 2**20, 16.0, 12.0, 1100.0, 0.0),  # SLC
        ("DRAM", math.inf, 15.0, 11.0, 500.0, 0.0),    # LPDDR5X
    )),
)

CPU_CHIPS = {c.name: c for c in (ZEN4, GOLDEN_COVE, NEOVERSE_V2)}

# Assignment-mandated roofline constants (v5e).
PEAK_FLOPS = TPU_V5E.bf16_flops
HBM_BW = TPU_V5E.hbm_bw
ICI_BW = TPU_V5E.ici_link_bw


def dtype_bytes(dtype_str: str) -> int:
    return {
        "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
        "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
        "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
        "float64": 8, "float32": 4, "bfloat16": 2, "float16": 2,
        "int64": 8, "int32": 4, "int16": 2, "int8": 1, "uint8": 1,
        "bool": 1,
    }.get(dtype_str, 4)
