"""Seeded weights of any architecture.

An architecture's ``Model`` (``arch/<name>.py``) lists the leaves of
each kind of layer and the top leaves, each as ``(name, shape, scale)``
or ``(name, shape, scale, dtype)``: the scale is the draw's standard
deviation, or "norm" for a norm weight (1 +- a tenth), and the type is
the model's unless the leaf names its own. ``Model.pattern`` is the
kinds, cycled over the layers (``kinds``).

The weights depend only on the seed, the layer and the leaf: the
benchmark makes the whole stacked set on the device in one jitted call
for the program, and the plain reference makes each layer again on its
own, so the reference takes nothing that the program has made. Draws
are uniform, built from integer random bits with one exactly rounded
multiply, so the same bits come out of either call.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words of threefry key data from any whole-number seed."""
    return np.random.SeedSequence(int(seed)).generate_state(2)


def _draw(key, shape, scale, dtype):
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    c = u * 2.0 - 1.0                            # exact, in [-1, 1)
    if scale == "norm":
        return (1.0 + c * jnp.float32(0.1)).astype(dtype)
    return (c * jnp.float32(scale * math.sqrt(3.0))).astype(dtype)


def _leaves(kd, index, leaves, dtype):
    key = jax.random.fold_in(jax.random.wrap_key_data(kd), index)
    return {name: _draw(jax.random.fold_in(key, i), shape, scale,
                        own[0] if own else dtype)
            for i, (name, shape, scale, *own) in enumerate(leaves)}


def kinds(m) -> list:
    """Each layer's kind: ``m.pattern`` cycled over the layers."""
    return [m.pattern[i % len(m.pattern)] for i in range(m.layers)]


def split(m) -> tuple:
    """(repeats, unit length, tail layers), as ``ModelConfig.scan_split``:
    the pattern's unit repeats while whole, the rest is the tail."""
    unit = len(m.pattern)
    return m.layers // unit, unit, m.layers % unit


def layer(m, kd, i, kind) -> dict:
    """Layer ``i``'s leaves (traceable in ``i``), of the given kind."""
    return _leaves(kd, i + 1, m.layer_leaves(kind), m.dtype)


def top(m, kd) -> dict:
    return _leaves(kd, 0, m.top_leaves(), m.dtype)


def stacked(m, kd) -> tuple:
    """(top leaves, {"scan": each unit position's leaves stacked over the
    repeats, "tail": each tail layer's leaves}), keyed by position."""
    n_rep, unit, n_tail = split(m)
    kind = kinds(m)

    def position(j):
        return jax.vmap(lambda r: layer(m, kd, r * unit + j, kind[j]))(
            jnp.arange(n_rep))
    scan = {str(j): position(j) for j in range(unit)} if n_rep else {}
    tail = {str(t): layer(m, kd, i, kind[i])
            for t, i in enumerate(range(n_rep * unit, m.layers))}
    return top(m, kd), {"scan": scan, "tail": tail}
