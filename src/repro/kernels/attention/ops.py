"""Public attention-kernel wrappers: impl routing, MemTier-autotuned
tile defaults, and a BHSD<->BSHD adapter for the model stack.

Tile sizes are no longer hardcoded: when a caller does not pin
``bq``/``bk``/``n_splits``, the MemTier-driven autotuner
(``repro.kernels.tuning``) prices the candidates against the target
machine's memory ladder and the cheapest tiling wins. ``impl`` follows
the suite-wide rules in ``repro.kernels``: ``ref`` / ``pallas``
(interpret mode off-TPU) / ``auto`` (Pallas on TPU, reference
elsewhere).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode, use_pallas
from repro.kernels import tuning
from repro.kernels.attention import decode as D
from repro.kernels.attention import flash as F
from repro.kernels.attention import ref as R
from repro.utils.sharding import current_mesh_rules, mesh_axis_sizes, spec_for


def _per_shard(kernel, q, k, v, pos, *tables, layer=None):
    """Run a Pallas decode kernel on each device's own heads.

    GSPMD cannot partition a Mosaic kernel, so under an ambient mesh
    (``repro.utils.sharding.use_mesh_rules``) the call goes through
    ``shard_map``: heads split over the ``kvheads`` axes — whole GQA
    groups per shard (``validate_tp_heads``) — and slots over the
    ``batch`` axes. A ``layer`` index marks the paged call: its pools
    are layer-stacked ``(R, P, page, Hkv, Dh)`` and keep every layer
    and page on every shard, and the index (replicated) is passed last.
    Without a mesh it is the plain call.
    """
    b = q.shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    extra = () if layer is None else (layer,)
    mesh, rules = current_mesh_rules()
    if mesh is None:
        return kernel(q, k, v, pos, *tables, *extra)
    from jax.sharding import PartitionSpec as P
    sizes = mesh_axis_sizes(mesh)
    bs = spec_for((b,), ("batch",), rules, sizes)[0]
    hs = spec_for((k.shape[-2],), ("kvheads",), rules, sizes)[0]
    qs = P(bs, None, hs, None)
    kvs = qs if layer is None else P(None, None, None, hs, None)
    in_specs = ((qs, kvs, kvs, P(bs)) + (P(bs, None),) * len(tables)
                + (P(),) * len(extra))
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=qs, check_vma=False)(q, k, v, pos,
                                                        *tables, *extra)


def validate_tp_heads(h: int, hkv: int, dh: int, tp: int, *,
                      page_size: int | None = None) -> int:
    """Check the decode dispatchers shard cleanly over ``tp`` TP shards.

    The flash-decode kernels pack all GQA heads of one shard into a
    single ``(Hkv_shard * G, Dh)`` query tile and tile the KV stream
    themselves, so a head-sharded (``kvheads`` -> TP) cache splits the
    kernel embarrassingly — *iff* the head counts divide: each shard
    must own a whole number of KV heads, the query heads must follow
    their KV groups, and the per-shard head tile must still be
    non-empty (head-dim tiles divide the per-shard head count). The
    paged kernel adds no head-side constraint (its KV block is the
    page), so ``page_size`` participates only in the error message.
    Returns the per-shard KV head count; raises ``ValueError`` on any
    violation.
    """
    tp = max(1, int(tp))
    what = "paged " if page_size is not None else ""
    if hkv % tp != 0:
        raise ValueError(
            f"{what}decode cannot shard {hkv} KV heads over TP={tp}: "
            "kvheads must divide the TP degree (pad heads or shrink "
            "the model mesh axis)")
    if h % tp != 0:
        raise ValueError(
            f"{what}decode cannot shard {h} query heads over TP={tp}: "
            "GQA groups must stay whole per shard")
    hkv_shard = hkv // tp
    g = h // hkv
    if hkv_shard * g < 1 or dh < 1:
        raise ValueError(
            f"{what}decode: empty per-shard head tile "
            f"(hkv/tp={hkv_shard}, G={g}, Dh={dh})")
    return hkv_shard


@partial(jax.jit, static_argnames=("causal", "window", "impl", "bq", "bk",
                                   "machine"))
def flash_attention(q, k, v, *, causal=True, window=None, impl="auto",
                    bq=None, bk=None, machine=None):
    """q: (B, H, S, Dh); k, v: (B, Hkv, S, Dh).

    ``bq``/``bk`` default to the autotuned tiling for ``machine``
    (``tuning.default_machine()`` when unset) instead of the old
    hardcoded 512s.
    """
    if not use_pallas(impl):
        return R.attention(q, k, v, causal=causal, window=window)
    _, h, s, dh = q.shape
    if bq is None or bk is None:
        plan = tuning.flash_tiles(machine or tuning.default_machine(),
                                  s=s, dh=dh, h=h, hkv=k.shape[1],
                                  dtype=str(q.dtype))
        bq = bq or tuning.fit_block(plan.bq, s)
        bk = bk or tuning.fit_block(plan.bk, s)
    return F.flash_attention(q, k, v, bq=bq, bk=bk, causal=causal,
                             window=window, interpret=interpret_mode())


def flash_attention_bshd(q, k, v, **kw):
    """(B, S, H, Dh) adapter."""
    o = flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), **kw)
    return jnp.swapaxes(o, 1, 2)


def flash_decode(q, k, v, pos, *, window=None, impl="auto", bk=None,
                 n_splits=None, kv_len=None, machine=None):
    """Split-KV decode against a fixed-horizon KV cache, impl-routed.

    q: (B, Sq, H, Dh) — the model stack's decode layout; k, v: (B,
    Skv, Hkv, Dh); ``pos`` scalar or (B,) (see
    ``kernels.attention.decode.flash_decode``). ``kv_len`` is the
    static occupancy bound — the highest cache row any slot can touch
    this step (``max(pos) + Sq``); rows past it are never read, which
    is the kernel's block early-out expressed as a shape. It is
    rounded up to the KV block grid and clamped to ``Skv``.

    ``bk``/``n_splits`` default to the autotuned decode tiling for
    ``machine``. Routing: ``pallas`` runs the kernel (interpret mode
    off-TPU); ``ref``/``auto``-off-TPU run the occupancy-bounded
    pure-JAX oracle — same traffic bound, XLA-fused. Designed to be
    called under an enclosing ``jax.jit`` (the decode step), so it is
    not jitted itself.
    """
    b, sq, h, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    bound = skv if kv_len is None else max(1, min(int(kv_len), skv))
    if bk is None or n_splits is None:
        plan = tuning.decode_tiles(machine or tuning.default_machine(),
                                   skv=bound, dh=dh, h=h, hkv=hkv,
                                   batch=b, dtype=str(q.dtype))
        bk = bk or plan.bk
        n_splits = n_splits or plan.n_splits
    bk = max(1, min(bk, skv))
    if kv_len is not None:
        bound = min(math.ceil(bound / bk) * bk, skv)
        k = k[:, :bound]
        v = v[:, :bound]
    if use_pallas(impl):
        return _per_shard(
            partial(D.flash_decode, window=window, bk=bk, n_splits=n_splits,
                    interpret=interpret_mode()), q, k, v, pos)
    return D.ref_decode(q, k, v, pos, window=window)


def flash_decode_paged(q, k_pages, v_pages, block_tables, pos, *,
                       layer=None, window=None, impl="auto", n_splits=None,
                       kv_len=None, machine=None):
    """Paged split-KV decode against a shared page pool, impl-routed.

    q: (B, Sq, H, Dh); ``k_pages``/``v_pages``: layer-stacked pools
    (R, P, page, Hkv, Dh) read at layer ``layer`` (int32 scalar), or
    one layer's (P, page, Hkv, Dh) with ``layer=None``;
    ``block_tables``: (B, NB) int32 (see
    ``kernels.attention.decode.flash_decode_paged``). ``kv_len`` bounds
    occupancy at *page* granularity: only the first
    ``ceil(kv_len / page)`` table columns are ever gathered — the
    paged analogue of the dense router's block rounding. The KV block
    is pinned to the page size (a page is the DMA unit), so only
    ``n_splits`` is autotuned; ``machine`` picks whose ladder tunes it.

    Routing matches :func:`flash_decode`: ``pallas`` runs the
    scalar-prefetched gather kernel (interpret mode off-TPU);
    ``ref``/``auto``-off-TPU gather pages in logical order and run the
    dense oracle. Call under an enclosing ``jax.jit``.
    """
    k_pages, v_pages, layer = D.as_stacked(k_pages, v_pages, layer)
    b, sq, h, dh = q.shape
    ps, hkv = k_pages.shape[2], k_pages.shape[3]
    nb = block_tables.shape[1]
    if kv_len is not None:
        nb_used = max(1, min(math.ceil(int(kv_len) / ps), nb))
        block_tables = block_tables[:, :nb_used]
        nb = nb_used
    if use_pallas(impl):
        if n_splits is None:
            plan = tuning.decode_tiles(machine or tuning.default_machine(),
                                       skv=nb * ps, dh=dh, h=h, hkv=hkv,
                                       batch=b, dtype=str(q.dtype))
            n_splits = plan.n_splits
        def kernel(q, k, v, pos, bt, layer):
            return D.flash_decode_paged(q, k, v, bt, pos, layer=layer,
                                        window=window, n_splits=n_splits,
                                        interpret=interpret_mode())
        return _per_shard(kernel, q, k_pages, v_pages, pos, block_tables,
                          layer=layer)
    return D.ref_decode_paged(q, k_pages, v_pages, block_tables, pos,
                              layer=layer, window=window)
