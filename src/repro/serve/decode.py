"""In-graph chunked decode: n tokens per dispatch, per-slot positions,
in-graph temperature sampling.

Generalizes ``train/serve.py:make_decode_loop_step`` (greedy, scalar
position) to the serve engine's needs: every slot decodes at its own
position (``pos`` is a (B,) vector), and sampling happens inside the
token scan so a temperature>0 engine still issues one dispatch per
chunk. The cache flows through the scan carry and is donated at the
jit level. With ``paged=True`` the page pools stay in one buffer for
the whole chunk: the model's layer scan carries the layer-stacked pools
and writes each new row in place (``models.model.forward``), so no
token copies a pool. The dense step's layer scan still takes the cache
as ``xs`` and returns it as ``ys``, which need not stay in place.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M


def make_chunked_decode_step(cfg: ModelConfig, n_tokens: int,
                             temperature: float = 0.0,
                             attn_impl: str | None = None,
                             kv_len: int | None = None,
                             store_flavor: str | None = None,
                             paged: bool = False,
                             guard: bool = False):
    """Build the n-token decode chunk: one dispatch, n in-graph steps.

    Returns ``step(params, cache, tokens, pos, key) -> (toks, cache, pos)``
    with ``tokens`` (B, 1) int32 (each slot's last emitted token), ``pos``
    a scalar or (B,) int32 (each slot's write position), and ``key`` a
    PRNG key consumed only when ``temperature > 0``. ``toks`` is
    (B, n_tokens): the next n tokens of every slot. Token-id models only.

    ``attn_impl`` routes decode attention through the split-KV kernel
    suite and ``kv_len`` is the *static* occupancy bound for the whole
    chunk — no slot may write past it, so it must cover
    ``max(pos) + n_tokens`` (the engine fixes one bound for its
    lifetime and rejects requests beyond it; each distinct ``kv_len``
    is its own compilation). Token ``i`` of the chunk reads at most
    ``kv_len`` cache rows instead of the full horizon — the split-KV
    traffic bound at dispatch granularity. ``store_flavor`` picks the
    KV-writer store path (repro.kernels.stores; None = standard).

    ``paged=True`` switches to the paged-cache step signature
    ``step(params, cache, block_tables, tokens, pos, key)``: attention
    KV leaves are physical page pools and ``block_tables`` (B, NB)
    int32 maps each slot's logical pages (repro.serve.pages). The
    cache stays positional argument 1 so the engine's donation hint is
    layout-independent.

    ``guard=True`` appends a per-slot non-finite guard to the return:
    the step yields ``(toks, cache, pos, ok)`` with ``ok`` a (B,) bool
    that is False for any slot whose logits went non-finite at *any*
    token of the chunk. The check is one ``jnp.isfinite`` reduce per
    in-graph step — jit-compatible, fused into the logits epilogue —
    and it is per-row, so one slot's NaN never condemns its batchmates
    (attention/recurrent mixers keep rows independent; see the MoE
    caveat in ``serve.engine``). Poisoned rows emit token 0 for the
    rest of the chunk so the self-fed garbage can't index out of the
    embedding; the serve engine quarantines the request and discards
    the chunk's tokens. ``guard=False`` (default) keeps the historical
    3-tuple and a bit-identical graph.
    """
    assert cfg.embed_inputs, "chunked decode needs a token embedding"
    assert n_tokens >= 1

    def step(params, cache, tokens, pos, key, block_tables=None):
        def body(carry, _):
            if guard:
                cache, tok, pos, key, ok = carry
            else:
                cache, tok, pos, key = carry
            logits, _, new_cache = M.forward(cfg, params, {"tokens": tok},
                                            mode="decode", cache=cache,
                                            pos=pos, attn_impl=attn_impl,
                                            kv_len=kv_len,
                                            store_flavor=store_flavor,
                                            block_tables=block_tables)
            # some mixers emit recurrent state in compute dtype (bf16);
            # the cache contract (model.cache_shapes) carries them f32 —
            # pin the scan carry to the contract's dtypes
            cache = jax.tree.map(lambda new, old: new.astype(old.dtype),
                                 new_cache, cache)
            lg = logits[:, 0]
            if temperature > 0.0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, lg / temperature, axis=-1)
                nxt = nxt.astype(jnp.int32)
            else:
                nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            if guard:
                ok = ok & jnp.all(jnp.isfinite(lg), axis=-1)
                # a poisoned row's self-fed token is garbage: pin it to 0
                # so the next embedding lookup stays in range (the chunk's
                # tokens are discarded at quarantine anyway)
                nxt = jnp.where(ok, nxt, 0)
                return (cache, nxt[:, None], pos + 1, key, ok), nxt
            return (cache, nxt[:, None], pos + 1, key), nxt

        if guard:
            ok0 = jnp.ones((tokens.shape[0],), bool)
            (cache, _, pos, _, ok), toks = jax.lax.scan(
                body, (cache, tokens, pos, key, ok0), None, length=n_tokens)
            return jnp.swapaxes(toks, 0, 1), cache, pos, ok
        (cache, _, pos, _), toks = jax.lax.scan(
            body, (cache, tokens, pos, key), None, length=n_tokens)
        return jnp.swapaxes(toks, 0, 1), cache, pos

    if not paged:
        return step

    def paged_step(params, cache, block_tables, tokens, pos, key):
        return step(params, cache, tokens, pos, key,
                    block_tables=block_tables)

    return paged_step
