"""Production mesh builders.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state. The single-pod mesh is 16x16 = 256 chips
("data", "model"); the multi-pod mesh is 2x16x16 = 512 chips
("pod", "data", "model") — the "pod" axis is a pure extra data-parallel
axis whose gradient all-reduce crosses the inter-pod (DCN) boundary once
per step.

Every mesh here has ``Auto`` axes: the model code places arrays with
``with_sharding_constraint`` and lets GSPMD propagate, which is what
``jax.make_mesh``'s default ``Explicit`` axes refuse (an embedding
gather over a sharded table raises ``ShardingTypeError`` there).
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes over ``devices`` (default: all)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for the production mesh, have {len(devs)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{n} (see repro.launch.dryrun)")
    return make_mesh(shape, axes, devices=devs[:n])


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over however many real devices exist (tests/smoke)."""
    n = math.prod(shape)
    return make_mesh(shape, axes, devices=jax.devices()[:n])


def make_serve_mesh(spec: str | None):
    """Build a serve mesh from a CLI spec ``"axes=sizes"``, e.g.
    ``"data,model=1,2"`` -> a (1, 2) mesh on axes ("data", "model").

    ``None`` or ``""`` returns ``None`` — the engines' single-device
    path. Sizes must multiply to at most the visible device count (use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to fake N
    host devices for CPU smoke runs).
    """
    if not spec:
        return None
    try:
        axes_s, sizes_s = spec.split("=")
        axes = tuple(a.strip() for a in axes_s.split(","))
        shape = tuple(int(s) for s in sizes_s.split(","))
    except ValueError as e:
        raise ValueError(
            f"bad mesh spec {spec!r}; expected 'axis,axis=size,size' "
            "like 'data,model=1,2'") from e
    if len(axes) != len(shape) or not axes:
        raise ValueError(
            f"mesh spec {spec!r}: {len(axes)} axes vs {len(shape)} sizes")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {spec!r} needs {n} devices, have {len(devs)}; run "
            f"under XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return make_mesh(shape, axes, devices=devs[:n])
