"""Pallas TPU flash attention (causal, GQA, optional sliding window).

The in-core/roofline analysis of the scan-based reference attention shows
it DMA-bound: every online-softmax step round-trips (scores, m, l, acc)
through HBM at fusion boundaries (~6 GB per layer-pass for yi-9b train_4k
vs ~150 MB of Q/K/V/O payload — see EXPERIMENTS.md §Perf). This kernel is
the WA-evasion-spirited fix: the (bq, bk) score tile, the running max/sum
and the output accumulator never leave VMEM; the TPU grid's sequential
innermost dimension carries the accumulator across KV blocks (scratch
persists across grid steps that map to the same output block).

Grid: (batch, q_heads, n_q_blocks, n_kv_blocks), KV innermost.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  bq, bk, n_kv, scale, causal, window):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q_pos = iq * bq + jax.lax.iota(jnp.int32, bq)
    k_pos = ik * bk + jax.lax.iota(jnp.int32, bk)

    # causal/window block skip: any work in this block?
    lo_q, hi_k = iq * bq, ik * bk
    live = True
    if causal:
        live = hi_k <= lo_q + bq - 1
    if window is not None:
        live = jnp.logical_and(live, (ik + 1) * bk - 1 > lo_q - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)             # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask = jnp.logical_and(
                mask, k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention(q, k, v, *, bq: int | None = None,
                    bk: int | None = None, causal: bool = True,
                    window: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, Dh); k, v: (B, Hkv, S, Dh) -> (B, H, S, Dh).

    ``bq``/``bk`` default to the MemTier-autotuned tiling for the
    default target machine (``repro.kernels.tuning``) — the historical
    hardcoded 512s survive only as an explicit caller choice.
    """
    b, h, s, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    if bq is None or bk is None:
        from repro.kernels import tuning
        plan = tuning.flash_tiles(tuning.default_machine(), s=s, dh=dh,
                                  h=h, hkv=hkv, dtype=str(q.dtype))
        # snap to divisors of s — the grid below requires exact tiling
        bq = bq or tuning.fit_block(plan.bq, s)
        bk = bk or tuning.fit_block(plan.bk, s)
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=nk, scale=scale,
        causal=causal, window=window)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret)(q, k, v)
