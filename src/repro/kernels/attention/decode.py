"""Pallas TPU split-KV flash-decode kernel (GQA, per-slot positions).

The serve engine preallocates KV slots at the full decode horizon
(repro.serve), so the reference ``decode_attention`` reads and masks
**every** ``max_len`` cache row for every slot on every token — a slot
at ``pos=3`` pays the same DMA bill as one at ``pos=4095``, and the
dense ``(B, Hkv, G, 1, Skv)`` score tensor round-trips HBM at fusion
boundaries. This kernel is the WA-evasion-spirited fix at decode scale
(the CloverLeaf lesson: never move bytes you don't need):

* KV is tiled over the innermost grid dimension with **block-level
  early-out** — ``pl.when`` skips every KV block wholly beyond a
  slot's position (and, with a sliding window, wholly before it), so
  per-step work scales with cache *occupancy*, not horizon.
* The online-softmax accumulators (m, l, acc) live in VMEM scratch and
  never touch HBM. One grid step fetches a (bk, Hkv, Dh) block of every
  KV head at once; the queries arrive packed per KV head outside the
  kernel, ``(Hkv, Sq·G, Dh)``, so every in-kernel value is a 2-D tile
  and Mosaic sees no reshape or transpose (it refuses them).
* Long caches shard over ``n_splits`` KV splits (flash-decoding): each
  split accumulates its own partial (m, l, acc) and a cross-split
  combine merges them outside the kernel.

Grid: (batch, n_splits, kv_blocks_per_split), KV innermost. ``pos`` is
scalar-prefetched so both the kernel and its masks see every slot's
position before any block work is issued. Under a mesh the kernels run
per TP shard through ``shard_map`` (``ops._per_shard``).

Tile sizes come from the MemTier-driven autotuner
(``repro.kernels.tuning``), not constants; routing and CPU fallbacks
live in ``repro.kernels.attention.ops``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_scr, m_scr, l_scr, *, bk, bps, sq, g, hkv, scale,
                   window):
    """One (batch, split, kv-block) grid step of split-KV flash decode.

    Mosaic gets no in-kernel reshape or transpose: ``q_ref`` holds the
    query rows already packed per KV head outside the kernel, (Hkv,
    Sq*G, Dh) with rows ordered (Sq major, G minor), and each head's
    (bk, Dh) KV tile is a strided ref load ``k_ref[:, j, :]`` out of
    the (bk, Hkv, Dh) block. Scratch carries the online-softmax state
    per head across the innermost (kv-block) grid dimension.
    """
    b = pl.program_id(0)
    s = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    pos_b = pos_ref[b]
    start = (s * bps + ik) * bk
    # block-level early-out: skip blocks wholly beyond the slot's last
    # query position (and wholly before its window, when sliding)
    live = start <= pos_b + (sq - 1)
    if window is not None:
        live = jnp.logical_and(live, start + bk - 1 > pos_b - window)

    @pl.when(live)
    def _compute():
        shape = (sq * g, bk)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        # row r of a head's tile queries absolute position pos_b + r // g
        q_pos = pos_b
        if sq > 1:
            q_pos = q_pos + jax.lax.broadcasted_iota(jnp.int32, shape, 0) // g
        mask = k_pos <= q_pos
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        for j in range(hkv):
            q = q_ref[j].astype(jnp.float32) * scale    # (rows, dh)
            k = k_ref[:, j, :].astype(jnp.float32)      # (bk, dh)
            v = v_ref[:, j, :].astype(jnp.float32)
            st = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            st = jnp.where(mask, st, NEG_INF)
            m_prev = m_scr[j]                           # (rows, 1)
            m_new = jnp.maximum(m_prev, st.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(st - m_new)
            l_scr[j] = l_scr[j] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[j] = acc_scr[j] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[j] = m_new

    @pl.when(ik == bps - 1)
    def _finalize():
        o_ref[...] = acc_scr[...]
        m_ref[...] = m_scr[...]
        l_ref[...] = l_scr[...]


def _pack_q(q, hkv):
    """(B, Sq, H, Dh) -> (B, Hkv, Sq*G, Dh): one row tile per KV head."""
    b, sq, h, dh = q.shape
    g = h // hkv
    return q.reshape(b, sq, hkv, g, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, hkv, sq * g, dh)


def _unpack_o(o, sq):
    """(B, Hkv, Sq*G, Dh) -> (B, Sq, H, Dh)."""
    b, hkv, rows, dh = o.shape
    g = rows // sq
    return o.reshape(b, hkv, sq, g, dh).transpose(0, 2, 1, 3, 4).reshape(
        b, sq, hkv * g, dh)


def _partials_shapes(n_splits, b, hkv, rows, dh):
    return [
        jax.ShapeDtypeStruct((n_splits, b, hkv, rows, dh), jnp.float32),
        jax.ShapeDtypeStruct((n_splits, b, hkv, rows, 1), jnp.float32),
        jax.ShapeDtypeStruct((n_splits, b, hkv, rows, 1), jnp.float32),
    ]


def _partials_specs(hkv, rows, dh):
    """Output BlockSpecs shared by the dense and paged grids."""
    return [
        pl.BlockSpec((None, None, hkv, rows, dh),
                     lambda b_, s, ik, *_: (s, b_, 0, 0, 0)),
        pl.BlockSpec((None, None, hkv, rows, 1),
                     lambda b_, s, ik, *_: (s, b_, 0, 0, 0)),
        pl.BlockSpec((None, None, hkv, rows, 1),
                     lambda b_, s, ik, *_: (s, b_, 0, 0, 0)),
    ]


def _scratch(hkv, rows, dh):
    return [pltpu.VMEM((hkv, rows, dh), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32),
            pltpu.VMEM((hkv, rows, 1), jnp.float32)]


def flash_decode(q, k, v, pos, *, window: int | None = None,
                 bk: int = 128, n_splits: int = 1,
                 interpret: bool = False) -> jax.Array:
    """Split-KV flash decode against a fixed-horizon KV cache.

    q: (B, Sq, H, Dh) — the current decode token(s); k, v: (B, Skv,
    Hkv, Dh) slot caches. ``pos`` is the absolute position of the
    *first* query token — a scalar, or a (B,) vector when slots decode
    at independent positions (continuous batching); query token ``j``
    attends cache rows ``<= pos + j`` (all Sq new keys are already in
    the cache, as in the model's decode flow). Returns (B, Sq, H, Dh)
    in q's dtype.

    ``Skv`` need not divide ``bk``: the cache is padded up to the
    block grid and padded rows are causally masked (``pos < Skv``
    always). Splits partition the KV blocks; each split's partial
    (m, l, acc) is merged by :func:`combine_splits`.
    """
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    assert h == hkv * g and sq >= 1
    rows = sq * g
    bk = max(1, min(bk, max(skv, 1)))
    nb = math.ceil(skv / bk)
    n_splits = max(1, min(n_splits, nb))
    bps = math.ceil(nb / n_splits)
    skv_pad = n_splits * bps * bk
    if skv_pad > skv:
        padding = [(0, 0), (0, skv_pad - skv), (0, 0), (0, 0)]
        k = jnp.pad(k, padding)
        v = jnp.pad(v, padding)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    scale = 1.0 / math.sqrt(dh)

    def kv_map(b_, s, ik, p):
        return (b_, s * bps + ik, 0, 0)

    kernel = functools.partial(
        _decode_kernel, bk=bk, bps=bps, sq=sq, g=g, hkv=hkv, scale=scale,
        window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_splits, bps),
        in_specs=[
            pl.BlockSpec((None, hkv, rows, dh),
                         lambda b_, s, ik, p: (b_, 0, 0, 0)),
            pl.BlockSpec((None, bk, hkv, dh), kv_map),
            pl.BlockSpec((None, bk, hkv, dh), kv_map),
        ],
        out_specs=_partials_specs(hkv, rows, dh),
        scratch_shapes=_scratch(hkv, rows, dh))
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_shapes(n_splits, b, hkv, rows, dh),
        interpret=interpret)(pos_arr, _pack_q(q, hkv), k, v)
    o = combine_splits(o_part, m_part[..., 0], l_part[..., 0])
    return _unpack_o(o, sq).astype(q.dtype)


def _paged_decode_kernel(pos_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref,
                         o_ref, m_ref, l_ref, acc_scr, m_scr, l_scr, **kw):
    """Paged grid step: the block table and the layer index are consumed
    by the BlockSpec index maps (layer, physical page -> KV block), so
    the kernel body is the dense one verbatim — masking stays in
    *logical* coordinates."""
    del bt_ref, layer_ref
    _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_scr, m_scr, l_scr, **kw)


def as_stacked(k_pages, v_pages, layer):
    """Pools as ``(R, P, page, Hkv, Dh)`` plus the layer to read.

    ``layer=None`` takes one layer's ``(P, page, Hkv, Dh)`` pool and
    gives it a leading axis of 1 (a bitcast, no copy) at index 0.
    """
    if layer is None:
        return k_pages[None], v_pages[None], jnp.zeros((), jnp.int32)
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32)


def flash_decode_paged(q, k_pages, v_pages, block_tables, pos, *,
                       layer=None, window: int | None = None,
                       n_splits: int = 1,
                       interpret: bool = False) -> jax.Array:
    """Split-KV flash decode against a paged KV pool (vLLM-style).

    q: (B, Sq, H, Dh); ``k_pages``/``v_pages``: the layer-stacked
    physical page pools (R, P, page, Hkv, Dh) shared by every slot,
    read at layer ``layer`` (an int32 scalar, traced or not) — or, with
    ``layer=None``, one layer's (P, page, Hkv, Dh) pools;
    ``block_tables``: (B, NB) int32 mapping each slot's logical page
    ``i`` (cache rows ``i*page .. (i+1)*page-1``) to a physical page.
    The block table, ``pos`` and the layer are scalar-prefetched: the
    KV BlockSpec index maps read them, so each grid step DMAs exactly
    the physical page its logical block lives in, straight out of the
    stacked pool — the gather *is* the block indexing, and neither a
    materialized (B, NB*page, ...) cache nor a per-layer slice of the
    pool ever exists.

    The KV block equals the page size (one page per grid step) and the
    block-level early-out is unchanged: it tests the *logical* block
    start against ``pos``, so out-of-order physical tables cost
    nothing. Entries beyond a slot's live pages may be arbitrary valid
    page ids (they are fetched but fully masked). Returns
    (B, Sq, H, Dh) in q's dtype.
    """
    k_pages, v_pages, layer = as_stacked(k_pages, v_pages, layer)
    b, sq, h, dh = q.shape
    n_pages, ps, hkv = k_pages.shape[1:4]
    g = h // hkv
    assert h == hkv * g and sq >= 1
    rows = sq * g
    nb = block_tables.shape[1]
    n_splits = max(1, min(n_splits, nb))
    bps = math.ceil(nb / n_splits)
    bt = jnp.asarray(block_tables, jnp.int32)
    bt = jnp.clip(bt, 0, n_pages - 1)
    if n_splits * bps > nb:
        # pad the table to the split grid; padded blocks are logically
        # past every pos (start >= nb*ps) so the early-out skips them
        bt = jnp.pad(bt, [(0, 0), (0, n_splits * bps - nb)])
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    scale = 1.0 / math.sqrt(dh)

    def kv_map(b_, s, ik, p, t, lyr):
        return (lyr[0], t[b_, s * bps + ik], 0, 0, 0)

    kernel = functools.partial(
        _paged_decode_kernel, bk=ps, bps=bps, sq=sq, g=g, hkv=hkv,
        scale=scale, window=window)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_splits, bps),
        in_specs=[
            pl.BlockSpec((None, hkv, rows, dh),
                         lambda b_, s, ik, p, t, lyr: (b_, 0, 0, 0)),
            pl.BlockSpec((None, None, ps, hkv, dh), kv_map),
            pl.BlockSpec((None, None, ps, hkv, dh), kv_map),
        ],
        out_specs=_partials_specs(hkv, rows, dh),
        scratch_shapes=_scratch(hkv, rows, dh))
    o_part, m_part, l_part = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_partials_shapes(n_splits, b, hkv, rows, dh),
        interpret=interpret)(pos_arr, bt, layer.reshape(1),
                             _pack_q(q, hkv), k_pages, v_pages)
    o = combine_splits(o_part, m_part[..., 0], l_part[..., 0])
    return _unpack_o(o, sq).astype(q.dtype)


def ref_decode_paged(q, k_pages, v_pages, block_tables, pos, *,
                     layer=None, window: int | None = None) -> jax.Array:
    """Pure-JAX paged twin of :func:`flash_decode_paged` (off-TPU path).

    Gathers each slot's pages of layer ``layer`` in logical order and
    delegates to the dense reference decode. Because every logical row
    keeps its position, masked rows contribute exact zeros and the
    result is identical to decoding the equivalent contiguous cache.
    """
    k_pages, v_pages, layer = as_stacked(k_pages, v_pages, layer)
    b = q.shape[0]
    hkv, dh = k_pages.shape[3], k_pages.shape[4]
    bt = jnp.asarray(block_tables, jnp.int32)
    k = k_pages[layer, bt].reshape(b, -1, hkv, dh)
    v = v_pages[layer, bt].reshape(b, -1, hkv, dh)
    return ref_decode(q, k, v, pos, window=window)


def combine_splits(o_part, m_part, l_part) -> jax.Array:
    """Merge per-split partial softmax states (flash-decoding combine).

    o_part: (S, ..., Dh) unnormalized accumulators; m_part / l_part:
    (S, ...) running max / sum per split. Splits whose blocks were all
    skipped carry (m=NEG_INF, l=0) and contribute exactly zero weight.
    Returns (..., Dh) f32.
    """
    m_max = m_part.max(axis=0)
    w = jnp.exp(m_part - m_max[None])                    # dead split -> 0
    l_tot = (l_part * w).sum(axis=0)
    o = (o_part * w[..., None]).sum(axis=0)
    return o / jnp.maximum(l_tot, 1e-30)[..., None]


def ref_decode(q, k, v, pos, *, window: int | None = None,
               kv_len: int | None = None) -> jax.Array:
    """Occupancy-bounded pure-JAX oracle for :func:`flash_decode`.

    Numerically the dense masked-GQA decode, but — like the kernel's
    block early-out — it only ever touches the first ``kv_len`` cache
    rows (a static bound the caller derives from occupancy, rounded to
    the block grid). With ``kv_len=None`` it degrades to the dense
    full-horizon read. This is the off-TPU execution path the ops
    router uses, and the parity target the kernel is tested against.
    """
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    if kv_len is not None:
        kv_len = max(1, min(int(kv_len), skv))
        k = k[:, :kv_len]
        v = v[:, :kv_len]
        skv = kv_len
    qg = q.reshape(b, sq, hkv, g, dh) * (1.0 / math.sqrt(dh))
    st = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                    preferred_element_type=jnp.float32)
    k_pos = jnp.arange(skv)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1, 1),
                            (b, 1))
    q_pos = posb + jnp.arange(sq)[None, :]               # (B, Sq)
    mask = k_pos[None, None, :] <= q_pos[..., None]      # (B, Sq, Skv)
    if window is not None:
        mask &= k_pos[None, None, :] > (q_pos[..., None] - window)
    st = jnp.where(mask[:, None, None, :, :], st, NEG_INF)
    p = jax.nn.softmax(st, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o.reshape(b, sq, h, dh).astype(q.dtype)
