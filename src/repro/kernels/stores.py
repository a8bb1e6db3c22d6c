"""Per-machine store-path selection and WA-evading store kernels.

The paper's headline finding (§III, Fig. 4) is that the three vendors
need three different *store paths* to evade write-allocate traffic:
Grace claims cache lines automatically (standard stores are already
optimal), Zen 4 evades only via explicit non-temporal stores, and
SPR's SpecI2M sits in between — it engages only once the memory
interface saturates, so NT stores pay off *below* that gate and are
redundant above it. ``core/wa.py`` models this; this module turns the
model into an optimization: a **selector** that picks the fastest
store flavor per machine straight off the registry's WA mode and
``MemTier`` residues, plus the **kernel variants** the selection
routes between.

Store flavors:

* ``"standard"`` — plain stores: the XLA dynamic-update-slice path for
  KV writers, natural block tiling for the stream kernels. Pays the
  machine's full Fig. 4 allocate cost wherever no automatic mechanism
  evades it.
* ``"nt"`` — the non-temporal/streaming analogue. On TPU there is no
  NT opcode; the analogue (DESIGN.md §2) is a store that provably
  overwrites full native tiles in place: the stream kernels pad their
  block grid to the (8,128) tile granule, and the KV writers run a
  Pallas kernel whose output *aliases* the cache
  (``input_output_aliases``) and whose grid touches exactly the
  written rows — nothing else is read, copied, or allocated.
* ``"auto"`` — per-machine selection: the flavor whose modeled ladder
  ratio (`wa.ladder_traffic_ratio`) is lower wins, ties to
  ``standard``. Zen 4 → ``nt``; Grace/TPU → ``standard``; SPR →
  ``nt`` only while the modeled saturation gate is closed.

Execution routing mirrors ``repro.kernels`` impl routing: ``"nt"``
always runs the aligned/aliased kernel (interpret mode off-TPU — the
parity/CI path); ``"auto"`` runs it only on a real TPU and falls back
to the standard path elsewhere (the *modeled-only* fallback: plans and
traffic reports still price the selected flavor, execution uses the
XLA path that off-TPU backends compile well).

Consumers: ``models/model.py`` (prefill cache fill + decode row
updates), ``serve/engine.py`` / ``serve/planner.py`` (plans record
their flavor), ``serve/kv_traffic.py`` (flavor-priced traffic),
``kernels/tuning.py`` (tile plans carry the flavor), and
``benchmarks/fig4b_ntstore.py`` (the CI gate that the selected path's
traffic matches ``wa.priced_store_traffic``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import wa
from repro.kernels import interpret_mode, on_tpu

#: the public flavor vocabulary; "auto" resolves per machine
STORE_FLAVORS = ("standard", "nt", "auto")

#: selection tolerance: "nt" must beat "standard" by more than this
#: ratio margin (ties and noise go to the standard path, which needs
#: no special kernel)
_SELECT_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class StorePlan:
    """One store-path decision and the modeled ratios behind it."""

    machine: str              # registered machine name
    flavor: str               # chosen flavor: "standard" | "nt"
    wa_mode: str              # the machine's Fig. 4 behavioural mode
    ratio_standard: float     # modeled traffic ratio, standard stores
    ratio_nt: float           # modeled traffic ratio, NT stores
    saturation: float         # modeled interface saturation used, 0..1
    ws_bytes: float | None    # working set the ratios were gated on

    @property
    def ratio(self) -> float:
        """Modeled traffic ratio of the *chosen* flavor."""
        return self.ratio_nt if self.flavor == "nt" \
            else self.ratio_standard


def flavor_ratios(machine, *, ws_bytes: float | None = None,
                  cores_active: int | None = None,
                  bw_utilization: float | None = None,
                  tile_full_frac: float = 1.0) -> tuple:
    """(standard, nt) modeled traffic ratios on one machine.

    Both ratios come from the shared ladder-residue path
    (`wa.ladder_traffic_ratio`), so the selector, fig4, and fig4b can
    never disagree about what a store costs.
    """
    kw = dict(ws_bytes=ws_bytes, cores_active=cores_active,
              bw_utilization=bw_utilization,
              tile_full_frac=tile_full_frac)
    return (wa.ladder_traffic_ratio(machine, nt_stores=False, **kw),
            wa.ladder_traffic_ratio(machine, nt_stores=True, **kw))


def plan_stores(machine=None, *, flavor: str = "auto",
                ws_bytes: float | None = None,
                cores_active: int | None = None,
                bw_utilization: float | None = None) -> StorePlan:
    """Resolve the store path for one machine into a :class:`StorePlan`.

    ``flavor="auto"`` picks the cheaper modeled flavor (ties →
    ``standard``); an explicit ``"standard"``/``"nt"`` is honoured but
    the plan still records both ratios. ``ws_bytes`` gates the SpecI2M
    saturation model on the real working set (omitted → the stream is
    assumed DRAM-bound at full saturation, the Fig. 4 default);
    ``machine`` defaults to the autotuner's target
    (`repro.kernels.tuning.default_machine`).
    """
    from repro.core.machine import get_machine
    from repro.core.memtier import modeled_saturation
    if machine is None:
        from repro.kernels.tuning import default_machine
        machine = default_machine()
    m = get_machine(machine) if isinstance(machine, str) else machine
    if flavor not in STORE_FLAVORS:
        raise ValueError(f"unknown store flavor {flavor!r} "
                         f"(expected one of {STORE_FLAVORS})")
    r_std, r_nt = flavor_ratios(m, ws_bytes=ws_bytes,
                                cores_active=cores_active,
                                bw_utilization=bw_utilization)
    if flavor == "auto":
        flavor = "nt" if r_nt < r_std - _SELECT_EPS else "standard"
    sat = bw_utilization
    if sat is None:
        sat = (modeled_saturation(m, ws_bytes, cores_active)
               if ws_bytes is not None else 1.0)
    return StorePlan(machine=m.name, flavor=flavor,
                     wa_mode=wa.wa_mode_of(m),
                     ratio_standard=r_std, ratio_nt=r_nt,
                     saturation=sat, ws_bytes=ws_bytes)


def select_store_flavor(machine=None, *, ws_bytes: float | None = None,
                        cores_active: int | None = None,
                        bw_utilization: float | None = None) -> str:
    """The cheaper modeled store flavor for one machine.

    Zen 4 (``explicit_only``, DRAM residue 0) always selects ``"nt"``;
    Grace and the TPUs (``auto_claim``) always ``"standard"``; SPR
    (``saturation_gated``) selects ``"nt"`` only while the modeled
    saturation gate is closed — once SpecI2M engages, its residue
    matches the NT residue and the tie goes to ``standard``.
    """
    return plan_stores(machine, flavor="auto", ws_bytes=ws_bytes,
                       cores_active=cores_active,
                       bw_utilization=bw_utilization).flavor


def resolve_flavor(flavor: str, machine=None, *,
                   ws_bytes: float | None = None,
                   cores_active: int | None = None) -> str:
    """Validate a flavor string and resolve ``"auto"`` per machine."""
    if flavor not in STORE_FLAVORS:
        raise ValueError(f"unknown store flavor {flavor!r} "
                         f"(expected one of {STORE_FLAVORS})")
    if flavor != "auto":
        return flavor
    return select_store_flavor(machine, ws_bytes=ws_bytes,
                               cores_active=cores_active)


def executed_flavor(flavor: str, machine=None, *,
                    ws_bytes: float | None = None) -> str:
    """The flavor the *runtime* path should execute.

    An explicit ``"nt"`` always runs the NT kernel (interpret mode
    off-TPU — the parity path); ``"auto"`` runs it only when the
    selected flavor is ``nt`` AND the backend is a real TPU, degrading
    to the standard XLA path elsewhere (modeled-only fallback — the
    plans still record and price the selection).
    """
    if flavor not in STORE_FLAVORS:
        raise ValueError(f"unknown store flavor {flavor!r} "
                         f"(expected one of {STORE_FLAVORS})")
    if flavor != "auto":
        return flavor
    if not on_tpu():
        return "standard"
    return select_store_flavor(machine, ws_bytes=ws_bytes)


# --- NT KV-row writer (Pallas, cache-aliased) ------------------------------

def _kv_row_kernel(pos_ref, u_ref, c_ref, o_ref):
    """Copy one (1, 1, Hkv, Dh) update row into its aliased cache slot.

    The cache ref is untouched: with ``input_output_aliases`` the
    output *is* the cache buffer, so rows the grid never visits keep
    their bytes without a single read — the NT-store contract.
    """
    del pos_ref, c_ref
    o_ref[...] = u_ref[...]


def _kv_write_nt(cache, update, pos, *, interpret: bool):
    """Aliased Pallas row write: grid (B, Sq), rows at ``pos[b] + j``.

    The scalar-prefetched per-slot positions drive the output block
    index map, so each grid step lands exactly on the row it writes;
    ``input_output_aliases`` donates the cache into the output. Only
    ``B * Sq`` (Hkv, Dh) rows move — no whole-buffer copy and no
    read-modify-write of untouched rows.
    """
    b, _, hkv, dh = cache.shape
    sq = update.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    spec = pl.BlockSpec((1, 1, hkv, dh),
                        lambda i, j, pos_ref: (i, pos_ref[i] + j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, sq),
        in_specs=[
            pl.BlockSpec((1, 1, hkv, dh),
                         lambda i, j, pos_ref: (i, j, 0, 0)),
            spec,
        ],
        out_specs=spec,
    )
    return pl.pallas_call(
        _kv_row_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},   # cache (after pos, update) -> out
        interpret=interpret)(pos, update.astype(cache.dtype), cache)


def kv_row_update(cache, update, pos, *, flavor: str = "standard",
                  machine=None):
    """Write ``update`` rows into a KV ``cache`` at per-slot positions.

    ``cache`` is (B, S, Hkv, Dh); ``update`` is (B, Sq, Hkv, Dh) and
    ``pos`` a scalar or (B,) int32 — row ``b`` lands at
    ``cache[b, pos[b]:pos[b]+Sq]``. This is the single door every KV
    writer goes through (decode in-place row updates in
    ``models/model.py``); the flavor picks the store path:

    * ``"standard"`` — the vmapped ``dynamic_update_slice`` XLA path
      (in place under jit donation), byte-identical to the historical
      serve path.
    * ``"nt"`` — the cache-aliased Pallas row writer (interpret mode
      off-TPU).
    * ``"auto"`` — the machine-selected flavor, NT kernel only on a
      real TPU (see :func:`executed_flavor`).
    """
    run = executed_flavor(flavor, machine,
                          ws_bytes=float(cache.size * cache.dtype.itemsize))
    if run == "nt":
        return _kv_write_nt(cache, update, pos,
                            interpret=interpret_mode())
    upd = update.astype(cache.dtype)
    if jnp.ndim(pos) == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, upd, pos, axis=1)
    row_dus = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(
            c, u, i, axis=0))
    return row_dus(cache, upd, pos)


def pad_to_horizon(x, cache_len: int, *, flavor: str = "standard",
                   machine=None):
    """Grow a prefill KV leaf (B, S, Hkv, Dh) to the decode horizon.

    The prefill cache fill is itself a store subject: the whole
    ``cache_len`` buffer is written once. ``"standard"`` keeps the
    historical ``jnp.pad``; ``"nt"`` builds the horizon buffer as an
    explicit full-overwrite — a zero fill plus an offset-0 (tile-
    aligned by construction) dynamic-update-slice, the donation-
    friendly lowering whose stores the WA scan classifies as full-tile.
    Both produce identical bytes; off-TPU ``"auto"`` stays standard.
    """
    b, s, hkv, dh = x.shape
    if cache_len <= s:
        return x
    run = executed_flavor(flavor, machine,
                          ws_bytes=float(b * cache_len * hkv * dh
                                         * x.dtype.itemsize))
    if run == "nt":
        buf = jnp.zeros((b, cache_len, hkv, dh), x.dtype)
        return jax.lax.dynamic_update_slice_in_dim(buf, x, 0, axis=1)
    return jnp.pad(x, [(0, 0), (0, cache_len - s), (0, 0), (0, 0)])
