"""Model sizes from a configuration file, and seeded weights.

The weights depend only on the seed, the layer and the leaf: the
benchmark makes the whole stacked set on the device in one jitted call
for the program, and the plain reference makes each layer again on its
own, so the reference takes nothing that the program has made. Draws
are uniform, built from integer random bits with one exactly rounded
multiply, so the same bits come out of either call.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Model:
    """A Llama-style decoder's sizes, read from a config's ``model``."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    eps: float
    dtype: str

    @classmethod
    def from_config(cls, conf: dict) -> "Model":
        m = conf["model"]
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim", m["hidden_size"]
                                  // m["num_attention_heads"]),
                   ffn=m["intermediate_size"], vocab=m["vocab_size"],
                   qkv_bias=bool(m.get("attention_bias", False)),
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]), dtype=conf["dtype"])


#: (leaf, shape, scale) of one layer; the scale is the draw's standard
#: deviation, or "norm" for a norm weight (1 +- a tenth)
def layer_leaves(m: Model) -> list:
    d, h, hk, dh, f = m.d, m.heads, m.kv_heads, m.head_dim, m.ffn
    out = [("ln1", (d,), "norm"),
           ("wq", (d, h, dh), d ** -0.5),
           ("wk", (d, hk, dh), d ** -0.5),
           ("wv", (d, hk, dh), d ** -0.5),
           ("wo", (h, dh, d), (h * dh) ** -0.5),
           ("ln2", (d,), "norm"),
           ("w_gate", (d, f), d ** -0.5),
           ("w_up", (d, f), d ** -0.5),
           ("w_down", (f, d), f ** -0.5)]
    if m.qkv_bias:
        out += [("bq", (h, dh), 0.5), ("bk", (hk, dh), 0.5),
                ("bv", (hk, dh), 0.5)]
    return out


def top_leaves(m: Model) -> list:
    return [("embed", (m.vocab, m.d), 1.0),
            ("final_norm", (m.d,), "norm"),
            ("lm_head", (m.d, m.vocab), m.d ** -0.5)]


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
    return {name: _draw(jax.random.fold_in(key, i), shape, scale, dtype)
            for i, (name, shape, scale) in enumerate(leaves)}


def layer(m: Model, kd, i, dtype=None) -> dict:
    """Layer ``i``'s leaves (traceable in ``i``)."""
    return _leaves(kd, i + 1, layer_leaves(m), dtype or m.dtype)


def top(m: Model, kd, dtype=None) -> dict:
    return _leaves(kd, 0, top_leaves(m), dtype or m.dtype)


def stacked(m: Model, kd) -> tuple:
    """(top leaves, every layer's leaves stacked on a leading axis)."""
    layers = jax.vmap(lambda i: layer(m, kd, i))(jnp.arange(m.layers))
    return top(m, kd), layers
