"""Model assembly: parameter tables, block program (scan over repeated
pattern units), and forward passes for train / prefill / decode.

Single source of truth: every parameter is declared once as a
:class:`ParamDef` (shape, logical axes, init) — ``init_params``,
``param_shapes`` and ``param_pspecs`` all derive from the same table, so
sharding specs can never drift from the parameter tree structure.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import stores as stores_lib
from repro.models import attention as attn_lib
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.utils.sharding import sc, spec_for


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """One parameter leaf: shape, sharding axis names, and initializer."""

    shape: tuple
    axes: tuple
    init: str = "normal"     # normal|zeros|ones|embed|alog|dtbias


def _is_def(x):
    return isinstance(x, ParamDef)


def _map_defs(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=_is_def)


# ---------------------------------------------------------------------------
# Parameter tables
# ---------------------------------------------------------------------------

def _attn_defs(cfg: ModelConfig) -> dict:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_eff
    p = {
        "wq": ParamDef((d, h, dh), ("embed", "qheads", None)),
        "wk": ParamDef((d, hkv, dh), ("embed", "kvheads", None)),
        "wv": ParamDef((d, hkv, dh), ("embed", "kvheads", None)),
        "wo": ParamDef((h, dh, d), ("qheads", None, "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamDef((h, dh), ("qheads", None), "zeros")
        p["bk"] = ParamDef((hkv, dh), ("kvheads", None), "zeros")
        p["bv"] = ParamDef((hkv, dh), ("kvheads", None), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = ParamDef((dh,), (None,), "ones")
        p["k_norm"] = ParamDef((dh,), (None,), "ones")
    return p


def _ffn_defs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    p = {"w_up": ParamDef((d, f), ("embed", "mlp")),
         "w_down": ParamDef((f, d), ("mlp", "embed"))}
    if cfg.ffn_act == "swiglu":
        p["w_gate"] = ParamDef((d, f), ("embed", "mlp"))
    return p


def _moe_defs(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_ff_expert
    p = {
        "router": ParamDef((d, e), ("embed", None)),
        "w_up": ParamDef((e, d, f), ("expert", "embed", "emlp")),
        "w_down": ParamDef((e, f, d), ("expert", "emlp", "embed")),
    }
    if cfg.ffn_act == "swiglu":
        p["w_gate"] = ParamDef((e, d, f), ("expert", "embed", "emlp"))
    return p


def _mamba_defs(cfg: ModelConfig) -> dict:
    d, di, n, r, k = (cfg.d_model, cfg.d_inner, cfg.ssm_d_state,
                      cfg.dt_rank, cfg.ssm_conv_dim)
    return {
        "in_x": ParamDef((d, di), ("embed", "ssm_inner")),
        "in_z": ParamDef((d, di), ("embed", "ssm_inner")),
        "conv_w": ParamDef((k, di), (None, "ssm_inner")),
        "conv_b": ParamDef((di,), ("ssm_inner",), "zeros"),
        "x_dbc": ParamDef((di, r + 2 * n), ("ssm_inner", None)),
        "dt_w": ParamDef((r, di), (None, "ssm_inner")),
        "dt_b": ParamDef((di,), ("ssm_inner",), "dtbias"),
        "a_log": ParamDef((di, n), ("ssm_inner", None), "alog"),
        "d_skip": ParamDef((di,), ("ssm_inner",), "ones"),
        "out_proj": ParamDef((di, d), ("ssm_inner", "embed")),
    }


def _mlstm_defs(cfg: ModelConfig) -> dict:
    d, di, h = cfg.d_model, cfg.xlstm_d_inner, cfg.n_heads
    return {
        "up_x": ParamDef((d, di), ("embed", "ssm_inner")),
        "up_z": ParamDef((d, di), ("embed", "ssm_inner")),
        "wq": ParamDef((di, di), ("ssm_inner", None)),
        "wk": ParamDef((di, di), ("ssm_inner", None)),
        "wv": ParamDef((di, di), ("ssm_inner", None)),
        "w_if": ParamDef((di, 2, h), ("ssm_inner", None, None)),
        "b_if": ParamDef((2, h), (None, None), "zeros"),
        "out": ParamDef((di, di), ("ssm_inner", None)),
        "down": ParamDef((di, d), ("ssm_inner", "embed")),
    }


def _slstm_defs(cfg: ModelConfig) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    return {
        "w": ParamDef((d, 4, d), ("embed", None, "slstm_h")),
        "b": ParamDef((4, d), (None, "slstm_h"), "zeros"),
        "r": ParamDef((h, dh, 4, dh), (None, None, None, None)),
        "out": ParamDef((d, d), ("slstm_h", "embed")),
    }


_MIXER_DEFS = {
    "attn": _attn_defs, "attn_local": _attn_defs,
    "mamba": _mamba_defs, "mlstm": _mlstm_defs, "slstm": _slstm_defs,
}


def block_defs(cfg: ModelConfig, blk: str) -> dict:
    """ParamDef tree of one layer block (``mixer:ffn`` plan entry)."""
    mixer, ffn = blk.split(":")
    p = {"ln1": ParamDef((cfg.d_model,), (None,), "ones"),
         "mixer": _MIXER_DEFS[mixer](cfg)}
    if ffn != "none":
        p["ln2"] = ParamDef((cfg.d_model,), (None,), "ones")
        p["ffn"] = _ffn_defs(cfg) if ffn == "dense" else _moe_defs(cfg)
    return p


def model_defs(cfg: ModelConfig) -> dict:
    """Whole-model ParamDef tree (embeddings, scan stack, tail, head)."""
    plan = cfg.layer_plan()
    n_rep, unit, n_tail = cfg.scan_split()
    defs = {}
    if cfg.embed_inputs:
        defs["tok_embed"] = ParamDef((cfg.vocab_size, cfg.d_model),
                                     ("vocab", "embed"), "embed")
    if n_rep > 0:
        defs["scan"] = {str(j): block_defs(cfg, plan[j]) for j in range(unit)}
    defs["tail"] = {str(i): block_defs(cfg, plan[n_rep * unit + i])
                    for i in range(n_tail)}
    defs["final_norm"] = ParamDef((cfg.d_model,), (None,), "ones")
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab_size),
                                   ("embed", "vocab"))
    return defs


# ---------------------------------------------------------------------------
# Materialization from defs
# ---------------------------------------------------------------------------

def _init_one(key, d: ParamDef, dtype, stack: int | None):
    shape = ((stack,) + d.shape) if stack else d.shape
    if d.init == "zeros":
        return jnp.zeros(shape, dtype)
    if d.init == "ones":
        return jnp.ones(shape, dtype)
    if d.init == "alog":
        # S4D-real init: A_n = n+1 per state channel
        n = d.shape[-1]
        base = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        return jnp.broadcast_to(base, shape).astype(jnp.float32)
    if d.init == "dtbias":
        return jnp.full(shape, math.log(math.expm1(0.01)), jnp.float32)
    std = 0.02 if d.init == "embed" else (
        1.0 / math.sqrt(max(1, d.shape[0] if len(d.shape) < 2 else
                            math.prod(d.shape[:-1])
                            if d.axes[-1] in ("embed",) else d.shape[0])))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _tree_init(key, defs, dtype, stack: int | None):
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_one(k, d, dtype, stack) for k, d in zip(keys, leaves)]
    return jax.tree.unflatten(treedef, vals)


def init_params(cfg: ModelConfig, key) -> dict:
    """Materialize real parameters (smoke/tests/examples)."""
    dtype = jnp.dtype(cfg.param_dtype)
    defs = model_defs(cfg)
    n_rep, _, _ = cfg.scan_split()
    out = {}
    k_top, k_scan, k_tail = jax.random.split(key, 3)
    for name, sub in defs.items():
        if name == "scan":
            out[name] = _tree_init(k_scan, sub, dtype, n_rep)
        elif name == "tail":
            out[name] = _tree_init(k_tail, sub, dtype, None)
        else:
            out[name] = _tree_init(k_top, sub, dtype, None)
    return out


def param_shapes(cfg: ModelConfig) -> dict:
    """ShapeDtypeStructs for the full parameter tree (no allocation)."""
    dtype = jnp.dtype(cfg.param_dtype)
    defs = model_defs(cfg)
    n_rep, _, _ = cfg.scan_split()

    def mk(stack):
        def f(d):
            shape = ((stack,) + d.shape) if stack else d.shape
            dt = jnp.float32 if d.init in ("alog", "dtbias") else dtype
            return jax.ShapeDtypeStruct(shape, dt)
        return f

    out = {}
    for name, sub in defs.items():
        stack = n_rep if name == "scan" else None
        out[name] = _map_defs(mk(stack), sub)
    return out


def param_pspecs(cfg: ModelConfig, rules: dict, mesh_sizes: dict) -> dict:
    """PartitionSpec tree matching :func:`model_defs` under ``rules``."""
    defs = model_defs(cfg)
    n_rep, _, _ = cfg.scan_split()

    def mk(stacked):
        def f(d: ParamDef):
            shape = ((n_rep,) + d.shape) if stacked else d.shape
            axes = (("stack",) + d.axes) if stacked else d.axes
            return spec_for(shape, axes, rules, mesh_sizes)
        return f

    out = {}
    for name, sub in defs.items():
        out[name] = _map_defs(mk(name == "scan"), sub)
    return out


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    """Analytic parameter count. active_only: MoE experts counted as top-k."""
    total = 0
    for blk in cfg.layer_plan():
        defs = block_defs(cfg, blk)
        flat = jax.tree_util.tree_flatten_with_path(
            defs, is_leaf=_is_def)[0]   # jax.tree.flatten_with_path needs
        for path, d in flat:            # newer jax than the floor we support
            n = math.prod(d.shape)
            if active_only and d.shape and d.shape[0] == cfg.n_experts \
                    and len(d.shape) == 3 and cfg.n_experts > 0:
                n = n * cfg.experts_per_token // cfg.n_experts
            total += n
    total += cfg.d_model  # final norm
    if cfg.embed_inputs:
        total += cfg.vocab_size * cfg.d_model
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * cfg.d_model
    return total


# ---------------------------------------------------------------------------
# Cache (decode state) tables
# ---------------------------------------------------------------------------

def _cache_defs(cfg: ModelConfig, blk: str, batch: int, seq: int) -> dict:
    mixer = blk.split(":")[0]
    hkv, dh = cfg.n_kv_heads, cfg.head_dim_eff
    h = cfg.n_heads
    if mixer in ("attn", "attn_local"):
        # full-length cache also for local layers (window masked at use)
        return {
            "k": ParamDef((batch, seq, hkv, dh),
                          ("batch", "kv_seq", "kvheads", None), "zeros"),
            "v": ParamDef((batch, seq, hkv, dh),
                          ("batch", "kv_seq", "kvheads", None), "zeros"),
        }
    if mixer == "mamba":
        di, n, k = cfg.d_inner, cfg.ssm_d_state, cfg.ssm_conv_dim
        return {
            "h": ParamDef((batch, di, n),
                          ("batch", "ssm_inner", None), "zeros"),
            "conv": ParamDef((batch, k - 1, di),
                             ("batch", None, "ssm_inner"), "zeros"),
        }
    if mixer == "mlstm":
        di = cfg.xlstm_d_inner
        dh_i = di // h
        return {
            "c": ParamDef((batch, h, dh_i, dh_i),
                          ("batch", "qheads", None, None), "zeros"),
            "n": ParamDef((batch, h, dh_i),
                          ("batch", "qheads", None), "zeros"),
            "m": ParamDef((batch, h), ("batch", "qheads"), "zeros"),
        }
    if mixer == "slstm":
        d = cfg.d_model
        return {
            "c": ParamDef((batch, d), ("batch", "slstm_h"), "zeros"),
            "n": ParamDef((batch, d), ("batch", "slstm_h"), "zeros"),
            "h": ParamDef((batch, d), ("batch", "slstm_h"), "zeros"),
            "m": ParamDef((batch, h), ("batch", None), "zeros"),
        }
    raise ValueError(mixer)


def cache_defs(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """Decode-cache ParamDef tree (KV / SSM / xLSTM state per block)."""
    plan = cfg.layer_plan()
    n_rep, unit, n_tail = cfg.scan_split()
    out = {}
    if n_rep > 0:
        out["scan"] = {str(j): _cache_defs(cfg, plan[j], batch, seq)
                       for j in range(unit)}
    out["tail"] = {str(i): _cache_defs(cfg, plan[n_rep * unit + i], batch, seq)
                   for i in range(n_tail)}
    return out


def _is_kv_def(d: ParamDef) -> bool:
    """An attention KV leaf: its second logical axis is ``kv_seq``."""
    return d.axes[1] == "kv_seq"


def kv_leaf_flags(cfg: ModelConfig) -> dict:
    """Cache-structured tree of bools: True on attention KV leaves.

    These are the leaves the paged cache (repro.serve.pages) turns into
    page pools; recurrent state (mamba/xLSTM) stays slot-batched.
    """
    return jax.tree.map(_is_kv_def, cache_defs(cfg, 1, 1), is_leaf=_is_def)


def _cache_leaf_dtype(cfg, d: ParamDef):
    # recurrent states fp32; KV cache in param dtype
    if _is_kv_def(d):
        return jnp.dtype(cfg.param_dtype)
    return jnp.float32


def cache_shapes(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """ShapeDtypeStruct tree of the decode cache at serve shapes."""
    defs = cache_defs(cfg, batch, seq)
    n_rep, _, _ = cfg.scan_split()

    def mk(stacked):
        def f(d):
            shape = ((n_rep,) + d.shape) if stacked else d.shape
            return jax.ShapeDtypeStruct(shape, _cache_leaf_dtype(cfg, d))
        return f

    return {k: _map_defs(mk(k == "scan"), v) for k, v in defs.items()}


def init_cache(cfg: ModelConfig, batch: int, seq: int) -> dict:
    """Zero-filled decode cache matching :func:`cache_shapes`."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_shapes(cfg, batch, seq))


def cache_pspecs(cfg: ModelConfig, rules: dict, mesh_sizes: dict,
                 batch: int, seq: int) -> dict:
    """PartitionSpec tree matching :func:`cache_defs` under ``rules``."""
    defs = cache_defs(cfg, batch, seq)
    n_rep, _, _ = cfg.scan_split()

    def mk(stacked):
        def f(d):
            shape = ((n_rep,) + d.shape) if stacked else d.shape
            axes = (("stack",) + d.axes) if stacked else d.axes
            return spec_for(shape, axes, rules, mesh_sizes)
        return f

    return {k: _map_defs(mk(k == "scan"), v) for k, v in defs.items()}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _project(x, w, b=None):
    """x: (B,S,d) @ w: (d,H,Dh) -> (B,S,H,Dh)."""
    y = jnp.einsum("bsd,dhe->bshe", x, w)
    if b is not None:
        y = y + b
    return y


def _attn_mixer(cfg: ModelConfig, p: dict, x, *, local: bool, mode: str,
                positions, cache, pos, cache_len: int | None = None,
                attn_impl: str | None = None, kv_len: int | None = None,
                store_flavor: str | None = None, block_tables=None,
                layer=None):
    b, s, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_eff
    q = _project(x, p["wq"], p.get("bq"))
    k = _project(x, p["wk"], p.get("bk"))
    v = _project(x, p["wv"], p.get("bv"))
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_kind == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_kind == "mrope":
        q = L.apply_mrope(q, positions, cfg.rope_theta)
        k = L.apply_mrope(k, positions, cfg.rope_theta)
    window = cfg.sliding_window if local else None

    new_cache = None
    flav = store_flavor or "standard"
    if mode == "decode" and block_tables is not None:
        # paged cache: leaves are physical page pools shared across
        # slots — layer-stacked (R, P, page, Hkv, Dh) and addressed at
        # ``layer``, or one layer's (P, page, Hkv, Dh) with layer=None.
        # Scatter each slot's new row into the physical page its block
        # table names for the current logical page; the pool is never
        # sliced out of the stack. The engine guarantees every page in
        # a chunk's write range is allocated and exclusively held (CoW
        # already done), so the in-place scatter can never touch a
        # shared page.
        ps = cache["k"].shape[-3]
        nb = block_tables.shape[1]
        p1 = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
        lp = jnp.minimum(p1 // ps, nb - 1)    # overshoot-retiring clamp
        phys = block_tables[jnp.arange(b), lp]
        row = p1 % ps
        at = (phys, row) if layer is None else (layer, phys, row)
        kc = cache["k"].at[at].set(k[:, 0].astype(cache["k"].dtype))
        vc = cache["v"].at[at].set(v[:, 0].astype(cache["v"].dtype))
        # page pools stay pool-resident with heads on TP (no-op unmeshed)
        lead = (None,) * (kc.ndim - 2)
        kc = sc(kc, *lead, "kvheads", None)
        vc = sc(vc, *lead, "kvheads", None)
        y = attn_lib.decode_attention(q, kc, vc, pos, window=window,
                                      impl=attn_impl or "auto",
                                      kv_len=kv_len,
                                      block_tables=block_tables,
                                      layer=layer)
        new_cache = {"k": kc, "v": vc}
    elif mode == "decode":
        # the in-place KV row writes route through the store-flavor door
        # (repro.kernels.stores): standard = the historical dus paths,
        # nt = the cache-aliased full-tile Pallas writer
        kc = stores_lib.kv_row_update(cache["k"], k, pos, flavor=flav)
        vc = stores_lib.kv_row_update(cache["v"], v, pos, flavor=flav)
        # keep the updated cache on the slot-cache layout: the in-place
        # row write must not trigger a resharding gather (no-op unmeshed)
        kc = sc(kc, "batch", "kv_seq", "kvheads", None)
        vc = sc(vc, "batch", "kv_seq", "kvheads", None)
        y = attn_lib.decode_attention(q, kc, vc, pos, window=window,
                                      impl=attn_impl or "auto",
                                      kv_len=kv_len)
        new_cache = {"k": kc, "v": vc}
    else:
        y = attn_lib.chunked_causal_attention(
            q, k, v, q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, window=window)
        if mode == "prefill":
            kd = k.astype(jnp.dtype(cfg.param_dtype))
            vd = v.astype(jnp.dtype(cfg.param_dtype))
            if cache_len is not None and cache_len > s:
                # build the KV buffer at the full decode horizon in the
                # prefill graph itself — decode then updates it in place
                # (donation), with no post-hoc jnp.pad regrow/copy
                kd = stores_lib.pad_to_horizon(kd, cache_len, flavor=flav)
                vd = stores_lib.pad_to_horizon(vd, cache_len, flavor=flav)
            kd = sc(kd, "batch", "kv_seq", "kvheads", None)
            vd = sc(vd, "batch", "kv_seq", "kvheads", None)
            new_cache = {"k": kd, "v": vd}
    out = jnp.einsum("bshe,hed->bsd", y, p["wo"])
    return out, new_cache


def _mamba_mixer(cfg, p, x, *, mode, cache):
    want_state = mode in ("prefill", "decode")
    y, st = ssm_lib.mamba_mixer(
        p, x, d_state=cfg.ssm_d_state, conv_dim=cfg.ssm_conv_dim,
        chunk=cfg.ssm_chunk, state=cache if mode == "decode" else None,
        want_state=want_state, fuse=cfg.ssm_fuse)
    return y, st


def _mlstm_mixer(cfg, p, x, *, mode, cache):
    xm = x @ p["up_x"]
    z = x @ p["up_z"]
    want_state = mode in ("prefill", "decode")
    y, st = xlstm_lib.mlstm_mixer(
        p, xm, n_heads=cfg.n_heads, chunk=max(16, cfg.ssm_chunk // 2),
        state=cache if mode == "decode" else None, want_state=want_state)
    y = y * jax.nn.silu(z)
    return y @ p["down"], st


def _slstm_mixer(cfg, p, x, *, mode, cache):
    want_state = mode in ("prefill", "decode")
    y, st = xlstm_lib.slstm_mixer(
        p, x, n_heads=cfg.n_heads,
        state=cache if mode == "decode" else None, want_state=want_state)
    return y, st


def apply_block(cfg: ModelConfig, blk: str, p: dict, x, *, mode: str,
                positions, cache, pos, cache_len: int | None = None,
                attn_impl: str | None = None, kv_len: int | None = None,
                store_flavor: str | None = None, block_tables=None,
                layer=None):
    """Returns (x_out, aux_loss, new_cache).

    ``layer`` indexes a layer-stacked paged KV pool (see ``forward``).
    """
    mixer, ffn = blk.split(":")
    hx = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if mixer in ("attn", "attn_local"):
        y, new_cache = _attn_mixer(cfg, p["mixer"], hx,
                                   local=(mixer == "attn_local"),
                                   mode=mode, positions=positions,
                                   cache=cache, pos=pos, cache_len=cache_len,
                                   attn_impl=attn_impl, kv_len=kv_len,
                                   store_flavor=store_flavor,
                                   block_tables=block_tables, layer=layer)
    elif mixer == "mamba":
        y, new_cache = _mamba_mixer(cfg, p["mixer"], hx, mode=mode,
                                    cache=cache)
    elif mixer == "mlstm":
        y, new_cache = _mlstm_mixer(cfg, p["mixer"], hx, mode=mode,
                                    cache=cache)
    elif mixer == "slstm":
        y, new_cache = _slstm_mixer(cfg, p["mixer"], hx, mode=mode,
                                    cache=cache)
    else:
        raise ValueError(mixer)
    x = x + y
    x = sc(x, "act_batch", None, "act_embed")
    aux = jnp.zeros((), jnp.float32)
    if ffn != "none":
        hx = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        if ffn == "dense":
            y = L.dense_ffn(p["ffn"], hx, cfg.ffn_act)
        else:
            y, aux = moe_lib.moe_ffn(
                p["ffn"], hx, n_experts=cfg.n_experts,
                top_k=cfg.experts_per_token,
                capacity_factor=cfg.capacity_factor,
                group_size=cfg.moe_group_size, act=cfg.ffn_act)
        x = x + y
        x = sc(x, "act_batch", None, "act_embed")
    return x, aux, new_cache


def _remat_wrap(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # "full": save nothing


def forward(cfg: ModelConfig, params: dict, batch: dict, *,
            mode: str = "train",
            cache: dict | None = None, pos=None, cache_len: int | None = None,
            attn_impl: str | None = None, kv_len: int | None = None,
            store_flavor: str | None = None, block_tables=None):
    """Run the model.

    batch: {"tokens": (B,S) int32} or {"embeds": (B,S,d)}; optional
    "positions" ((B,S) int32, or (3,B,S) for mrope).
    mode: "train" -> logits
          "prefill" -> (logits, cache); `cache_len` (optional) preallocates
                       the attention KV buffers at the full decode horizon
                       inside the prefill graph (repro.serve slot caches)
          "decode" -> (logits, cache); S==1, `pos` required — scalar int32,
                      or (B,) int32 for per-slot positions (continuous
                      batching: each row attends/updates at its own pos).
                      `attn_impl` routes decode attention through the
                      split-KV kernel suite ("ref"/"pallas"/"auto", see
                      models.attention.decode_attention; None = "auto":
                      the Pallas kernel on TPU) and `kv_len`
                      statically bounds how much of the cache horizon a
                      step may read (occupancy bound, repro.serve).
    `store_flavor` ("standard"|"nt"|"auto", None = standard) picks the
    KV-writer store path (repro.kernels.stores): how decode rows are
    written into the cache and how prefill pads to the horizon.
    `block_tables` ((B, NB) int32, decode only) switches attention KV
    leaves to the paged layout: caches are physical page pools and each
    row's logical pages map through its table row (repro.serve.pages).
    The scanned layers' pools stay layer-stacked and are carried through
    the layer scan whole: each layer writes and reads them in place at
    its own index.
    Returns logits (B, S, V) plus aux-loss scalar as (logits, aux[, cache]).
    """
    if cfg.embed_inputs:
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = jnp.take(params["tok_embed"], tokens, axis=0)
    else:
        x = batch["embeds"]
        b, s, _ = x.shape
    x = x.astype(jnp.dtype(cfg.param_dtype))

    if "positions" in batch:
        positions = batch["positions"]
    elif mode == "decode":
        p1 = jnp.asarray(pos)
        base = jnp.broadcast_to(p1[:, None] if p1.ndim else p1,
                                (b, 1)).astype(jnp.int32)
        positions = jnp.broadcast_to(base, (3, b, 1)) \
            if cfg.rope_kind == "mrope" else base
    else:
        base = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        positions = jnp.broadcast_to(base, (3, b, s)) \
            if cfg.rope_kind == "mrope" else base

    if cfg.rope_kind == "sinusoidal":
        pe = L.sinusoidal_embedding(
            positions if positions.ndim == 2 else positions[0], cfg.d_model)
        x = x + pe.astype(x.dtype)

    x = sc(x, "act_batch", None, "act_embed")
    plan = cfg.layer_plan()
    n_rep, unit, n_tail = cfg.scan_split()
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {"tail": {}}

    if n_rep > 0 and mode == "decode" and cfg.decode_unroll:
        unit_blocks = [plan[j] for j in range(unit)]
        new_slices_all = []
        for r in range(n_rep):
            p_r = jax.tree.map(lambda x: x[r], params["scan"])
            c_r = jax.tree.map(lambda x: x[r], cache["scan"])
            new_slices = {}
            for j, blk in enumerate(unit_blocks):
                x, a, nc = apply_block(cfg, blk, p_r[str(j)], x,
                                       mode=mode, positions=positions,
                                       cache=c_r[str(j)], pos=pos,
                                       cache_len=cache_len,
                                       attn_impl=attn_impl, kv_len=kv_len,
                                       store_flavor=store_flavor,
                                       block_tables=block_tables)
                aux_total = aux_total + a
                new_slices[str(j)] = nc
            new_slices_all.append(new_slices)
        new_cache["scan"] = jax.tree.map(
            lambda *xs: jnp.stack(xs), *new_slices_all)
    elif n_rep > 0:
        unit_blocks = [plan[j] for j in range(unit)]
        # paged decode: the layer-stacked KV page pools ride in the scan
        # carry and each layer addresses them by its index, so they stay
        # in one buffer for the whole step — no per-layer slice of the
        # pool, no restacked ys, no copy back into the caller's loop
        # carry. Recurrent state stays in xs/ys (slot-batched, small).
        pools, layers = {}, None
        if mode == "decode" and block_tables is not None:
            kv = kv_leaf_flags(cfg)["scan"]
            pools = {j: c for j, c in cache["scan"].items()
                     if all(jax.tree.leaves(kv[j]))}
            layers = jnp.arange(n_rep, dtype=jnp.int32)
        rest = {j: c for j, c in cache["scan"].items() if j not in pools} \
            if mode == "decode" else {}

        def unit_body(carry, xs):
            x, aux, pools = carry
            p_slice, c_slice, layer = xs
            pools, new_slices = dict(pools), {}
            for j, blk in enumerate(unit_blocks):
                key = str(j)
                pooled = key in pools
                x, a, nc = apply_block(cfg, blk, p_slice[key], x,
                                       mode=mode, positions=positions,
                                       cache=pools[key] if pooled
                                       else c_slice.get(key), pos=pos,
                                       cache_len=cache_len,
                                       attn_impl=attn_impl, kv_len=kv_len,
                                       store_flavor=store_flavor,
                                       block_tables=block_tables,
                                       layer=layer if pooled else None)
                aux = aux + a
                if pooled:
                    pools[key] = nc
                elif nc is not None:
                    new_slices[key] = nc
            return (x, aux, pools), (new_slices if new_slices else None)

        body = _remat_wrap(cfg, unit_body)
        (x, aux_total, pools), scan_caches = jax.lax.scan(
            body, (x, aux_total, pools), (params["scan"], rest, layers))
        if mode in ("prefill", "decode") and (scan_caches or pools):
            new_cache["scan"] = {**(scan_caches or {}), **pools}

    for i in range(n_tail):
        blk = plan[n_rep * unit + i]
        ci = cache["tail"][str(i)] \
            if (cache is not None and mode == "decode") else None
        x, a, nc = apply_block(cfg, blk, params["tail"][str(i)], x,
                               mode=mode, positions=positions,
                               cache=ci, pos=pos, cache_len=cache_len,
                               attn_impl=attn_impl, kv_len=kv_len,
                               store_flavor=store_flavor,
                               block_tables=block_tables)
        aux_total = aux_total + a
        if nc is not None and mode in ("prefill", "decode"):
            new_cache["tail"][str(i)] = nc

    if mode == "prefill":
        # Serving: only the last position's logits are needed to start
        # decoding — skip the (B, S, V) vocab matmul entirely.
        x = x[:, -1:]
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["tok_embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head.astype(x.dtype)
    logits = sc(logits, "act_batch", None, "vocab")

    if mode == "train":
        return logits, aux_total
    return logits, aux_total, new_cache
