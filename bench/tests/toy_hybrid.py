"""A toy architecture of two kinds of layer (``ToyHybridForCausalLM``),
as a later configuration would add it: one new file, ``arch/<name>.py``.

Attention layers and Mamba-1 layers alternate, each followed by a SwiGLU
feed forward: the program's ``block_pattern`` ``("attn", "mamba")``, so
an odd number of layers leaves a tail. Only the attention layers read
the paged cache; the Mamba layers keep their recurrent state per slot.
Key names of the Mamba sizes follow Jamba's ``config.json``.

The plain reference Mamba layer follows ``repro.models.ssm.mamba_mixer``
(conv, selective scan, D skip, gate) one step at a time in float32.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

import reference as R
import weights as W


def causal_conv(x, w):
    """Depthwise causal convolution: x (S, C), w (K, C)."""
    k = w.shape[0]
    xx = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xx[i:i + x.shape[0]] * w[i] for i in range(k))


@dataclasses.dataclass(frozen=True)
class Model(R.Decoder):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    d_state: int
    d_conv: int
    expand: int
    dt_rank: int
    dtype: str

    pattern = ("attn", "mamba")

    @classmethod
    def from_config(cls, conf: dict) -> "Model":
        m = conf["model"]
        return cls(layers=m["num_hidden_layers"], d=m["hidden_size"],
                   heads=m["num_attention_heads"],
                   kv_heads=m["num_key_value_heads"],
                   head_dim=m["hidden_size"] // m["num_attention_heads"],
                   ffn=m["intermediate_size"], vocab=m["vocab_size"],
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]),
                   d_state=m["mamba_d_state"], d_conv=m["mamba_d_conv"],
                   expand=m["mamba_expand"], dt_rank=m["mamba_dt_rank"],
                   dtype=conf["dtype"])

    @property
    def inner(self) -> int:
        return self.expand * self.d

    # -- seeded leaves -------------------------------------------------------
    def layer_leaves(self, kind: str) -> list:
        d, h, hk, dh, f = self.d, self.heads, self.kv_heads, self.head_dim, \
            self.ffn
        di, n, r, k = self.inner, self.d_state, self.dt_rank, self.d_conv
        if kind == "attn":
            mixer = [("wq", (d, h, dh), d ** -0.5),
                     ("wk", (d, hk, dh), d ** -0.5),
                     ("wv", (d, hk, dh), d ** -0.5),
                     ("wo", (h, dh, d), (h * dh) ** -0.5)]
        else:
            mixer = [("in_x", (d, di), d ** -0.5),
                     ("in_z", (d, di), d ** -0.5),
                     ("conv_w", (k, di), k ** -0.5),
                     ("conv_b", (di,), 0.1),
                     ("x_dbc", (di, r + 2 * n), di ** -0.5),
                     ("dt_w", (r, di), r ** -0.5),
                     ("dt_b", (di,), 0.5, "float32"),
                     ("a_log", (di, n), 0.5, "float32"),
                     ("d_skip", (di,), "norm"),
                     ("out_proj", (di, d), di ** -0.5)]
        return [("ln1", (d,), "norm"), *mixer, ("ln2", (d,), "norm"),
                ("w_gate", (d, f), d ** -0.5), ("w_up", (d, f), d ** -0.5),
                ("w_down", (f, d), f ** -0.5)]

    # -- the program ---------------------------------------------------------
    def program_config(self, name: str):
        from repro.configs.base import ModelConfig
        return ModelConfig(
            name=name, family="hybrid", n_layers=self.layers, d_model=self.d,
            n_heads=self.heads, n_kv_heads=self.kv_heads,
            head_dim=self.head_dim, d_ff=self.ffn, vocab_size=self.vocab,
            block_pattern=self.pattern, rope_theta=self.rope_theta,
            norm_eps=self.eps, ssm_d_state=self.d_state,
            ssm_conv_dim=self.d_conv, ssm_expand=self.expand,
            ssm_dt_rank=self.dt_rank, param_dtype=self.dtype)

    def program_tree(self, top: dict, layers: dict) -> dict:
        def block(lv):
            ffn = ("w_gate", "w_up", "w_down")
            return {"ln1": lv["ln1"], "ln2": lv["ln2"],
                    "ffn": {k: lv[k] for k in ffn},
                    "mixer": {k: v for k, v in lv.items()
                              if k not in ffn + ("ln1", "ln2")}}
        return {"tok_embed": top["embed"],
                **{part: {j: block(lv) for j, lv in layers[part].items()}
                   for part in ("scan", "tail")},
                "final_norm": top["final_norm"], "lm_head": top["lm_head"]}

    # -- the plain reference, float32 weights --------------------------------
    def ref_layer(self, kind: str, x, f: dict, control: bool):
        h = R._rms(x, f["ln1"], self.eps)
        mix = self._attention if kind == "attn" else self._mamba
        x = x + mix(h, f, control)
        h = R._rms(x, f["ln2"], self.eps)
        g = R._linear(h, f["w_gate"], control)
        u = R._linear(h, f["w_up"], control)
        return x + R._linear(jax.nn.silu(g) * u, f["w_down"], control)

    def _attention(self, h, f, control):
        s, dh = h.shape[0], self.head_dim
        pos = jnp.arange(s)

        def proj(name, heads):
            w = f[name].reshape(self.d, heads * dh)
            return R._linear(h, w, control).reshape(s, heads, dh)
        q = R._rope(proj("wq", self.heads), pos, self.rope_theta)
        k = R._rope(proj("wk", self.kv_heads), pos, self.rope_theta)
        o = R._attention(q, k, proj("wv", self.kv_heads))
        return R._linear(o.reshape(s, self.heads * dh),
                         f["wo"].reshape(self.heads * dh, self.d), control)

    def _mamba(self, h, f, control):
        r, n = self.dt_rank, self.d_state
        z = R._linear(h, f["in_z"], control)
        xi = R._linear(h, f["in_x"], control)
        xi = jax.nn.silu(causal_conv(xi, f["conv_w"]) + f["conv_b"])
        dbc = R._linear(xi, f["x_dbc"], control)
        dt = jax.nn.softplus(R._linear(dbc[:, :r], f["dt_w"], control)
                             + f["dt_b"])
        b, c = dbc[:, r:r + n], dbc[:, r + n:]
        a = -jnp.exp(f["a_log"])                          # (di, N)

        def step(state, t):
            dt_t, b_t, c_t, x_t = t
            state = jnp.exp(dt_t[:, None] * a) * state \
                + (dt_t * x_t)[:, None] * b_t[None, :]
            return state, jnp.dot(state, c_t, precision=R.HI)
        _, y = jax.lax.scan(step, jnp.zeros((self.inner, n), jnp.float32),
                            (dt, b, c, xi))
        y = (y + xi * f["d_skip"]) * jax.nn.silu(z)
        return R._linear(y, f["out_proj"], control)

    # -- what ``counts.py`` needs --------------------------------------------
    @property
    def token_params(self) -> int:
        d, di, hd = self.d, self.inner, self.heads * self.head_dim
        per = {"attn": d * hd * 2 + d * 2 * self.kv_heads * self.head_dim,
               "mamba": 3 * d * di + 2 * di * (self.dt_rank + self.d_state)}
        return sum(per[k] + 3 * d * self.ffn for k in W.kinds(self))

    @property
    def mixer_flops(self) -> int:
        """A Mamba layer's convolution, and its scan's update and
        read-out over the (inner, state) recurrence."""
        per = 2 * self.d_conv * self.inner + 4 * self.inner * self.d_state
        return per * W.kinds(self).count("mamba")
