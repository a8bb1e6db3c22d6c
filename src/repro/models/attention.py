"""GQA attention: training/prefill (chunked-causal flash-style, pure JAX) and
single-token decode against a (possibly sequence-sharded) KV cache.

The chunked path is the reference ("ref") implementation that the Pallas
flash-attention kernel in ``repro.kernels.attention`` is validated against.
It never materializes the full (S, S) score matrix: queries are processed in
chunks (python-unrolled so each chunk only visits its causal KV prefix —
no wasted upper-triangle FLOPs) with an online-softmax accumulator.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B, Sq, Hkv, G, Dh)  k: (B, Skv, Hkv, Dh) -> (B, Hkv, G, Sq, Skv)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_values(p, v):
    """p: (B, Hkv, G, Sq, Skv)  v: (B, Skv, Hkv, Dh) -> (B, Sq, Hkv, G, Dh)."""
    return jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)


def dense_causal_attention(q, k, v, *, window: int | None = None,
                           q_offset: int = 0) -> jax.Array:
    """Exact, materializes (Sq, Skv) scores. Use for small S / tests.

    q: (B, Sq, H, Dh); k, v: (B, Skv, Hkv, Dh). Queries are at absolute
    positions q_offset..q_offset+Sq-1; keys at 0..Skv-1. Returns
    (B, Sq, H, Dh).
    """
    b, sq, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, dh) * (1.0 / math.sqrt(dh))
    s = _gqa_scores(qg, k)                               # (B,Hkv,G,Sq,Skv)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = kpos <= qpos
    if window is not None:
        mask &= kpos > (qpos - window)
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_values(p, v)
    return o.reshape(b, sq, h, dh)


def chunked_causal_attention(q, k, v, *, q_chunk: int = 512,
                             kv_chunk: int = 1024,
                             window: int | None = None) -> jax.Array:
    """Flash-style online-softmax attention, causal, optional sliding window.

    Self-attention only (Sq == Skv, positions aligned). Python-unrolls query
    chunks; each q-chunk scans only its causal KV prefix (and only the chunks
    inside the sliding window when set), so FLOPs match the true lower
    triangle at chunk granularity.

    q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh) -> (B, S, H, Dh)
    """
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q_chunk = min(q_chunk, s)
    kv_chunk = min(kv_chunk, s)
    if s % q_chunk or s % kv_chunk:
        # Pad to a chunk multiple; padded keys are causally in the future of
        # every real query, so they are masked; padded query rows are sliced.
        lcm = q_chunk * kv_chunk // math.gcd(q_chunk, kv_chunk)
        sp = ((s + lcm - 1) // lcm) * lcm
        pad = [(0, 0), (0, sp - s), (0, 0), (0, 0)]
        out = chunked_causal_attention(
            jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad),
            q_chunk=q_chunk, kv_chunk=kv_chunk, window=window)
        return out[:, :s]
    n_q = s // q_chunk
    scale = 1.0 / math.sqrt(dh)

    kc = k.reshape(b, s // kv_chunk, kv_chunk, hkv, dh)
    vc = v.reshape(b, s // kv_chunk, kv_chunk, hkv, dh)

    def scores(qi_g, kj, qpos, kpos):
        st = _gqa_scores(qi_g, kj)                            # (B,Hkv,G,qc,kc)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > (qpos[:, None] - window)
        return jnp.where(mask, st, NEG_INF)

    outs = []
    for i in range(n_q):
        qi = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        qi_g = qi.reshape(b, q_chunk, hkv, g, dh) * scale
        qpos = i * q_chunk + jnp.arange(q_chunk)

        # Causal prefix of KV chunks for this q chunk (static bounds).
        j_hi = (i * q_chunk + q_chunk + kv_chunk - 1) // kv_chunk   # exclusive
        j_lo = 0
        if window is not None:
            j_lo = max(0, (i * q_chunk - window) // kv_chunk)
        n_kv = j_hi - j_lo

        def body(carry, kv_j):
            m, l, acc = carry
            kj, vj, j = kv_j
            kpos = j * kv_chunk + jnp.arange(kv_chunk)
            st = scores(qi_g, kj, qpos, kpos)                 # (B,Hkv,G,qc,kc)
            m_new = jnp.maximum(m, st.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(st - m_new[..., None])
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd",
                p.astype(vj.dtype), vj).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dh), jnp.float32)
        ks = jax.lax.dynamic_slice_in_dim(kc, j_lo, n_kv, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(vc, j_lo, n_kv, axis=1)
        js = j_lo + jnp.arange(n_kv)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0), js))
        o = acc / jnp.maximum(l, 1e-30)[..., None]            # (B,Hkv,G,qc,Dh)
        o = jnp.moveaxis(o, 3, 1).reshape(b, q_chunk, h, dh)
        outs.append(o.astype(q.dtype))
    return jnp.concatenate(outs, axis=1)


def decode_attention(q, k_cache, v_cache, pos, *, window: int | None = None,
                     impl: str = "ref", kv_len: int | None = None,
                     block_tables=None, layer=None) -> jax.Array:
    """Single-token decode: q (B, 1, H, Dh) vs cache (B, Skv, Hkv, Dh).

    ``pos`` is the position of the new token — a scalar int32, or a (B,)
    vector when slots decode at independent positions (continuous batching,
    repro.serve). Cache entries at indices > pos are masked per row. With
    the cache sequence dim sharded over the "model" mesh axis, XLA SPMD
    turns the softmax/value reductions into cross-device psums
    (distributed flash-decoding).

    ``impl`` routes through the kernel suite
    (``repro.kernels.attention.ops.flash_decode``): ``"pallas"`` runs the
    split-KV flash-decode kernel (interpret mode off-TPU), ``"auto"``
    picks it on TPU, and ``kv_len`` — the static occupancy bound
    (``max(pos) + 1``, rounded up to the KV block grid by the router) —
    caps how much of the horizon is ever read on any routed path. The
    plain ``"ref"`` default below stays inline: the dense full-horizon
    read whose traffic the split-KV kernel exists to avoid, kept as the
    oracle it is validated against.

    ``block_tables`` switches to the paged cache layout: the caches are
    physical page pools, layer-stacked (R, P, page, Hkv, Dh) and read at
    layer ``layer``, or one layer's (P, page, Hkv, Dh) with
    ``layer=None``; ``block_tables`` (B, NB) int32 maps each row's
    logical pages to physical ones (repro.serve.pages). Routed impls run
    the scalar-prefetched paged kernel (``ops.flash_decode_paged``),
    which reads the layer's pages where they lie in the stack; the
    inline ``"ref"`` path gathers pages in logical order and falls
    through to the very same dense computation below, so paged-vs-dense
    is bit-identical (masked rows contribute exact zeros).
    """
    if block_tables is not None:
        from repro.kernels.attention import decode as kdec
        from repro.kernels.attention import ops as kops
        if impl != "ref" or kv_len is not None:
            return kops.flash_decode_paged(q, k_cache, v_cache,
                                           block_tables, pos, layer=layer,
                                           window=window, impl=impl,
                                           kv_len=kv_len)
        k_cache, v_cache, layer = kdec.as_stacked(k_cache, v_cache, layer)
        nb = q.shape[0]
        hkv_p, dh_p = k_cache.shape[3], k_cache.shape[4]
        bt = jnp.asarray(block_tables, jnp.int32)
        k_cache = k_cache[layer, bt].reshape(nb, -1, hkv_p, dh_p)
        v_cache = v_cache[layer, bt].reshape(nb, -1, hkv_p, dh_p)
    elif impl != "ref" or kv_len is not None:
        from repro.kernels.attention import ops as kops
        return kops.flash_decode(q, k_cache, v_cache, pos, window=window,
                                 impl=impl, kv_len=kv_len)
    b, _, h, dh = q.shape
    skv, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    qg = q.reshape(b, 1, hkv, g, dh) * (1.0 / math.sqrt(dh))
    s = _gqa_scores(qg, k_cache)                              # (B,Hkv,G,1,Skv)
    kpos = jnp.arange(skv)
    posb = jnp.reshape(jnp.asarray(pos), (-1, 1))             # (1|B, 1)
    mask = kpos[None, :] <= posb
    if window is not None:
        mask &= kpos[None, :] > (posb - window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_values(p, v_cache)                               # (B,1,Hkv,G,Dh)
    return o.reshape(b, 1, h, dh)
