"""A Llama-style decoder (``LlamaForCausalLM``): every layer attends
(grouped-query, rotary positions, optional query/key/value biases) and
then runs a SwiGLU feed forward.

The plain reference layer: RMSNorm, rotary position embedding
(half-split pairs, theta from the configuration), causal attention, and
the feed forward, every product through ``reference._linear``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reference as R


@dataclasses.dataclass(frozen=True)
class Model(R.Decoder):
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

    #: the kinds of layer, cycled over the layers
    pattern = ("attn",)

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

    # -- seeded leaves (``weights.py``) --------------------------------------
    def layer_leaves(self, kind: str) -> list:
        d, h, hk, dh, f = self.d, self.heads, self.kv_heads, self.head_dim, \
            self.ffn
        out = [("ln1", (d,), "norm"),
               ("wq", (d, h, dh), d ** -0.5),
               ("wk", (d, hk, dh), d ** -0.5),
               ("wv", (d, hk, dh), d ** -0.5),
               ("wo", (h, dh, d), (h * dh) ** -0.5),
               ("ln2", (d,), "norm"),
               ("w_gate", (d, f), d ** -0.5),
               ("w_up", (d, f), d ** -0.5),
               ("w_down", (f, d), f ** -0.5)]
        if self.qkv_bias:
            out += [("bq", (h, dh), 0.5), ("bk", (hk, dh), 0.5),
                    ("bv", (hk, dh), 0.5)]
        return out

    # -- the program ---------------------------------------------------------
    def program_config(self, name: str):
        from repro.configs.base import ModelConfig
        return ModelConfig(name=name, family="dense", n_layers=self.layers,
                           d_model=self.d, n_heads=self.heads,
                           n_kv_heads=self.kv_heads, head_dim=self.head_dim,
                           d_ff=self.ffn, vocab_size=self.vocab,
                           qkv_bias=self.qkv_bias, rope_theta=self.rope_theta,
                           norm_eps=self.eps, param_dtype=self.dtype)

    def program_tree(self, top: dict, layers: dict) -> dict:
        """The program's parameter tree (``repro.models.model``) from the
        benchmark's leaves: ``layers["scan"]`` each unit position's
        leaves stacked over the repeats, ``layers["tail"]`` the rest."""
        def block(lv):
            mixer = {k: lv[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk",
                                        "bv") if k in lv}
            return {"ln1": lv["ln1"], "mixer": mixer, "ln2": lv["ln2"],
                    "ffn": {k: lv[k] for k in ("w_gate", "w_up", "w_down")}}
        return {"tok_embed": top["embed"],
                **{part: {j: block(lv) for j, lv in layers[part].items()}
                   for part in ("scan", "tail")},
                "final_norm": top["final_norm"], "lm_head": top["lm_head"]}

    # -- the plain reference (``reference.py``), float32 weights -------------
    def ref_layer(self, kind: str, x, f: dict, control: bool):
        """One decoder layer over one padded sequence x (S, d) float32."""
        s = x.shape[0]
        pos = jnp.arange(s)
        h = R._rms(x, f["ln1"], self.eps)

        def proj(name, heads):
            y = R._linear(h, f[name].reshape(self.d, heads * self.head_dim),
                          control)
            y = y.reshape(s, heads, self.head_dim)
            bias = {"wq": "bq", "wk": "bk", "wv": "bv"}[name]
            return y + f[bias] if bias in f else y

        q = R._rope(proj("wq", self.heads), pos, self.rope_theta)
        k = R._rope(proj("wk", self.kv_heads), pos, self.rope_theta)
        v = proj("wv", self.kv_heads)
        o = R._attention(q, k, v).reshape(s, self.heads * self.head_dim)
        x = x + R._linear(o, f["wo"].reshape(self.heads * self.head_dim,
                                              self.d), control)
        h = R._rms(x, f["ln2"], self.eps)
        g = R._linear(h, f["w_gate"], control)
        u = R._linear(h, f["w_up"], control)
        return x + R._linear(jax.nn.silu(g) * u, f["w_down"], control)

    # -- what ``counts.py`` needs --------------------------------------------
    @property
    def token_params(self) -> int:
        """Weights one token multiplies by, summed over the layers."""
        qkv = self.d * (self.heads + 2 * self.kv_heads) * self.head_dim
        return self.layers * (qkv + self.heads * self.head_dim * self.d
                              + 3 * self.d * self.ffn)

    #: operations per token besides the products and attention
    mixer_flops = 0
