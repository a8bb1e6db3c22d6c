"""Plain float32 reference of a Llama-style decoder, layer by layer.

RMSNorm, rotary position embedding (half-split pairs, theta from the
configuration), grouped-query causal attention with the query, key and
value biases where the configuration has them, and a SwiGLU feed
forward; no cache, no kernels, no batching of requests. It imports
nothing of the program: the weights come from ``weights.py`` and the
seed. Every matrix product runs at ``Precision.HIGHEST``, because a
float32 product on a TPU otherwise runs in bfloat16 passes.

``control=True`` computes the same forward in float8 (e4m3): every
linear layer's weights and inputs are scaled to the format's range,
per output column and per row, and rounded to it. That is the next
precision below the bfloat16 the configurations serve in; the
benchmark's check has to tell it from the program.

Sequences are run one at a time, each padded to a multiple of
``PAD``, and attention goes in blocks of ``QBLOCK`` queries, so that
the reference fits next to nothing else on one chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

PAD = 512
QBLOCK = 512
HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round ``x`` to e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _linear(x, w, control):
    """x (S, K) @ w (K, N) in float32, or both rounded to e4m3 first."""
    if control:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.dot(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (S, H, Dh): rotate (first half, second half) pairs."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _attention(q, k, v):
    """Causal GQA: q (S, H, Dh), k/v (S, Hkv, Dh) -> (S, H, Dh)."""
    s, h, dh = q.shape
    hk = k.shape[1]
    qg = q.reshape(s // QBLOCK, QBLOCK, hk, h // hk, dh)
    kpos = jnp.arange(s)

    def blk(args):
        qb, i = args
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI) / np.sqrt(dh)
        qpos = i * QBLOCK + jnp.arange(QBLOCK)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)

    out = jax.lax.map(blk, (qg, jnp.arange(s // QBLOCK)))
    return out.reshape(s, h, dh)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _block(x, lw, m: W.Model, control: bool):
    """One decoder layer over one padded sequence x (S, d) float32."""
    f = {k: v.astype(jnp.float32) for k, v in lw.items()}
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, f["ln1"], m.eps)

    def proj(name, heads):
        y = _linear(h, f[name].reshape(m.d, heads * m.head_dim), control)
        y = y.reshape(s, heads, m.head_dim)
        bias = {"wq": "bq", "wk": "bk", "wv": "bv"}[name]
        return y + f[bias] if bias in f else y

    q = _rope(proj("wq", m.heads), pos, m.rope_theta)
    k = _rope(proj("wk", m.kv_heads), pos, m.rope_theta)
    v = proj("wv", m.kv_heads)
    o = _attention(q, k, v).reshape(s, m.heads * m.head_dim)
    x = x + _linear(o, f["wo"].reshape(m.heads * m.head_dim, m.d), control)
    h = _rms(x, f["ln2"], m.eps)
    g = _linear(h, f["w_gate"], control)
    u = _linear(h, f["w_up"], control)
    return x + _linear(jax.nn.silu(g) * u, f["w_down"], control)


@functools.partial(jax.jit, static_argnames=("m",))
def _layer_weights(kd, i, m: W.Model):
    return W.layer(m, kd, i)


@functools.partial(jax.jit, static_argnames=("m",))
def _top_weights(kd, m: W.Model):
    return W.top(m, kd)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _embed(emb, ids, m: W.Model, control: bool):
    del control
    return emb[ids].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "control"))
def _head(x, norm, head, m: W.Model, control: bool):
    h = _rms(x, norm.astype(jnp.float32), m.eps)
    return _linear(h, head.astype(jnp.float32), control)


def logits(m: W.Model, seed: int, seqs: list, wanted: list,
           control: bool = False) -> list:
    """Logits (n_i, V) float32 at positions ``wanted[i]`` of ``seqs[i]``.

    ``seqs`` are int token-id arrays; ``wanted`` int position arrays. The
    result stays on the device.
    """
    kd = jnp.asarray(W.key_data(seed))
    top = _top_weights(kd, m)
    xs = []
    for ids in seqs:
        n = len(ids)
        padded = np.zeros(-(-n // PAD) * PAD, np.int32)
        padded[:n] = ids
        xs.append(_embed(top["embed"], jnp.asarray(padded), m, control))
    for i in range(m.layers):
        lw = _layer_weights(kd, jnp.int32(i), m)
        xs = [_block(x, lw, m, control) for x in xs]
        del lw
    out = []
    for x, w in zip(xs, wanted):
        # positions padded to a multiple of PAD, so that the head
        # compiles once per padded count and not once per count
        w = np.asarray(w, np.int32)
        at = np.full(-(-len(w) // PAD) * PAD, w[-1], np.int32)
        at[:len(w)] = w
        out.append(_head(x[jnp.asarray(at)], top["final_norm"],
                         top["lm_head"], m, control)[:len(w)])
    return out


def served_positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits chose served tokens 0..n-1: the last
    prompt position, then each served token but the last."""
    return np.arange(prompt_len - 1, prompt_len - 1 + n_served)


def sequence(prompt, served) -> np.ndarray:
    """The tokens the reference reads: the prompt, then every served
    token but the last (nothing reads the last one)."""
    return np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(served[:-1], np.int32)])


def token_gaps(ref_logits, tokens) -> np.ndarray:
    """Per position: the reference's best logit less its logit of the
    token given there (0 where that token is the reference's argmax)."""
    tok = jnp.asarray(np.asarray(tokens, np.int32))
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tok[:, None], -1)[:, 0]
    return np.asarray(best - got)
