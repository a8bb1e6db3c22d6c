"""Logical-axis sharding rules (MaxText-style) and constraint helpers.

Parameters/caches/activations carry *logical* axis names; a ``Rules`` table
maps each logical name to an ordered list of mesh-axis candidates. The spec
builder greedily assigns candidates subject to (a) divisibility of the dim
by the mesh-axis size and (b) no mesh axis used twice in one spec — this is
what lets e.g. grok-1's 8 experts fall back from expert-parallel to
ffn-dim tensor-parallel automatically.
"""

from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Meta mesh-axis groups, expanded against the live mesh's axis names.
FSDP = ("pod", "data")
TP = ("model",)
DATA = ("pod", "data")

TRAIN_RULES = {
    "batch": DATA,
    "act_batch": DATA,      # activation batch dim at block boundaries
    "act_embed": (),        # activation d_model dim at block boundaries
    "embed": FSDP,          # FSDP: weight d_model rows sharded, gathered at use
    "mlp": TP,
    "qheads": TP,
    "kvheads": TP,
    "vocab": TP,
    "expert": TP,
    "emlp": TP,             # fallback when expert-count doesn't divide TP
    "ssm_inner": TP,
    "slstm_h": TP,
    "kv_seq": TP,           # decode KV-cache sequence dim
    "stack": (),            # scan-stacked leading dim: never sharded
    None: (),
}

# Serving: no FSDP on weights by default (pure TP); big archs override.
SERVE_RULES = dict(TRAIN_RULES, embed=())

# Serve-engine rules (repro.serve.ServeEngine): the KV cache shards over
# *heads* (kvheads -> TP) with the sequence dim resident — the split-KV
# and paged decode kernels tile the sequence themselves, so the TP split
# must land on the embarrassingly parallel head dim, not on kv_seq (which
# SERVE_RULES would grab first and which a block-table gather cannot
# shard). Batch stays on the data axis.
SERVE_ENGINE_RULES = dict(SERVE_RULES, kv_seq=())

# FSDP-flavored engine rules: same KV layout, activations 2D-sharded.
SERVE_ENGINE_FSDP_RULES = dict(SERVE_ENGINE_RULES, act_batch=(),
                               act_embed=FSDP)

# FSDP serving for > HBM models. `act_embed` -> FSDP turns every matmul
# into a partial-sum over resident 2D-sharded weights + an activation
# all-reduce (KBs) instead of a per-layer weight all-gather (GBs) — see
# EXPERIMENTS.md §Perf H2.
SERVE_FSDP_RULES = dict(TRAIN_RULES, act_batch=(), act_embed=FSDP)

# The pre-H2 baseline: weights FSDP-sharded, activations batch-sharded —
# GSPMD all-gathers every layer's weights per step (kept for the §Perf
# before/after comparison).
SERVE_FSDP_GATHER_RULES = dict(TRAIN_RULES)


class _MeshState(threading.local):
    def __init__(self):
        self.mesh = None
        self.rules = None


_STATE = _MeshState()


@contextlib.contextmanager
def use_mesh_rules(mesh: Mesh | None, rules: dict | None):
    prev = (_STATE.mesh, _STATE.rules)
    _STATE.mesh, _STATE.rules = mesh, rules
    try:
        yield
    finally:
        _STATE.mesh, _STATE.rules = prev


def current_mesh_rules() -> tuple:
    """The ambient ``(mesh, rules)`` of :func:`use_mesh_rules`, or Nones."""
    return _STATE.mesh, _STATE.rules


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def tp_degree(mesh_sizes: dict, rules: dict | None = None) -> int:
    """Tensor-parallel degree of a mesh under ``rules``.

    The product of the mesh-axis sizes that the ``kvheads`` logical axis
    may shard over — the number of ways attention heads (and with them
    the per-shard KV stream) are split. ``rules=None`` uses the standard
    TP group. Missing axes contribute 1, so a pure-data mesh (or no
    mesh at all, ``mesh_sizes={}``) has TP degree 1.
    """
    axes = (rules or {}).get("kvheads", TP)
    prod = 1
    for a in axes:
        prod *= int(mesh_sizes.get(a, 1))
    return prod


def rules_fingerprint(rules: dict | None) -> tuple:
    """Stable, hashable identity of a rules table (plan memo keys).

    ``id(rules)`` would alias a rebuilt-but-identical table to a
    different key (and a mutated one to the same key); this folds the
    table's *contents* instead. The ``None`` logical axis is folded via
    ``str`` so the tuple sorts cleanly.
    """
    if rules is None:
        return ()
    return tuple(sorted((str(k), tuple(v)) for k, v in rules.items()))


def spec_for(shape: tuple, axes: tuple, rules: dict, mesh_sizes: dict) -> P:
    """Build a PartitionSpec for `shape` with logical `axes` under `rules`."""
    assert len(shape) == len(axes), (shape, axes)
    used = set()
    parts = []
    for dim, ax in zip(shape, axes):
        cands = rules.get(ax, ())
        picked = []
        prod = 1
        for m in cands:
            if m in used or m not in mesh_sizes:
                continue
            if dim % (prod * mesh_sizes[m]) != 0:
                continue
            picked.append(m)
            prod *= mesh_sizes[m]
        used.update(picked)
        if not picked:
            parts.append(None)
        elif len(picked) == 1:
            parts.append(picked[0])
        else:
            parts.append(tuple(picked))
    return P(*parts)


def sc(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint against the ambient mesh/rules (no-op when
    no mesh is installed — smoke tests on one device)."""
    if _STATE.mesh is None or _STATE.rules is None:
        return x
    sizes = mesh_axis_sizes(_STATE.mesh)
    spec = spec_for(x.shape, tuple(axes), _STATE.rules, sizes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_STATE.mesh, spec))


def named_shardings(mesh: Mesh, pspecs):
    """PartitionSpec tree -> NamedSharding tree (P is a tuple: mark leaves)."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
