"""Pallas TPU kernels for the paper's streaming benchmark suite.

Every kernel uses explicit BlockSpec VMEM tiling sized to the native
(8,128) tile grid. The INIT kernel is the paper's §III write-allocate
subject: `init_store` writes full aligned tiles (the TPU/Grace
"cache-line claim" regime, traffic ratio 1.0); `init_partial` deliberately
writes tile-misaligned blocks so the WA analyzer (repro.core.wa) charges
the RMW reads (the Zen-4-without-NT-stores regime).

Validated against repro.kernels.stream.ref in interpret mode on CPU
(tests/test_kernels_stream.py); compiled lowering targets TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 256          # rows per block
DEFAULT_BN = 512          # cols per block (multiple of 128)

#: jnp dtype name -> the short name `repro.core.wa.native_tile` expects
_DTYPE_SHORT = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
                "int32": "s32", "int8": "s8", "uint8": "u8"}


def _grid2(shape, bm, bn):
    """(grid, bm, bn) for an exact block tiling of a 2-D shape."""
    m, n = shape
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0, (shape, bm, bn)
    return (m // bm, n // bn), bm, bn


def _nt_grid2(shape, dtype, bm=DEFAULT_BM, bn=DEFAULT_BN):
    """Tile-granule-snapped blocking for the NT store path.

    Returns ``(grid, bm, bn, mp, np)``: block sizes snapped to
    multiples of the native (sublane, lane) store granule of ``dtype``
    and the padded extents ``(mp, np)`` they tile exactly — every
    store an NT kernel issues overwrites whole tiles (traffic ratio
    1.0 by construction, the TPU NT-store analogue; DESIGN.md §2).
    """
    from repro.core.wa import native_tile
    st, sl = native_tile(_DTYPE_SHORT.get(jnp.dtype(dtype).name, "f32"))
    m, n = shape
    bm = max(st, min((bm // st) * st, -(-m // st) * st))
    bn = max(sl, min((bn // sl) * sl, -(-n // sl) * sl))
    mp, npad = -(-m // bm) * bm, -(-n // bn) * bn
    return (mp // bm, npad // bn), bm, bn, mp, npad


def _nt_call(kernel, args, shape, dtype, *, interpret):
    """Run a 2-D elementwise kernel on the tile-padded NT grid.

    Inputs are zero-padded up to the snapped grid, every output block
    is a full aligned tile multiple, and the result is sliced back to
    ``shape`` — numerics identical to the standard-blocked variant,
    store traffic provably allocate-free on the tile grid.
    """
    grid, bm, bn, mp, npad = _nt_grid2(shape, dtype)
    m, n = shape
    pad = [(0, mp - m), (0, npad - n)]
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    out = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[spec] * len(args),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((mp, npad), dtype),
        interpret=interpret)(*(jnp.pad(a, pad) for a in args))
    return out[:m, :n]


# --- elementwise family -----------------------------------------------------

def _init_kernel(o_ref, *, scalar):
    o_ref[...] = jnp.full(o_ref.shape, scalar, o_ref.dtype)


def init_store(shape, scalar=3.0, dtype=jnp.float32, *, bm=DEFAULT_BM,
               bn=DEFAULT_BN, interpret=False):
    """a[:] = s with full-tile aligned stores (perfect WA evasion)."""
    grid, bm, bn = _grid2(shape, bm, bn)
    return pl.pallas_call(
        functools.partial(_init_kernel, scalar=scalar),
        grid=grid,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        interpret=interpret)()


def init_partial(shape, scalar=3.0, dtype=jnp.float32, *, interpret=False):
    """Store-only with tile-MISALIGNED blocks (7 x 100): every block edge
    forces a read-modify-write on the (8,128) tile grid — full WA."""
    m, n = shape
    bm, bn = 7, 100
    gm, gn = -(-m // bm), -(-n // bn)

    def k(o_ref):
        o_ref[...] = jnp.full(o_ref.shape, scalar, o_ref.dtype)

    padded = pl.pallas_call(
        k, grid=(gm, gn),
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((gm * bm, gn * bn), dtype),
        interpret=interpret)()
    return padded[:m, :n]


def init_nt(shape, scalar=3.0, dtype=jnp.float32, *, interpret=False):
    """INIT through the NT store path: tile-granule-snapped blocks.

    Handles arbitrary (also misaligned) shapes by writing the padded
    full-tile grid and slicing — the WA-evading counterpart of
    :func:`init_partial`, which deliberately pays the full allocate
    cost on the same shapes.
    """
    return _nt_call(functools.partial(_init_kernel, scalar=scalar), (),
                    shape, dtype, interpret=interpret)


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def copy_nt(x, *, interpret=False):
    """COPY with NT (full-tile aligned, padded-grid) stores."""
    return _nt_call(_copy_kernel, (x,), x.shape, x.dtype,
                    interpret=interpret)


def copy(x, *, bm=DEFAULT_BM, bn=DEFAULT_BN, interpret=False):
    """COPY: o = x, standard block tiling."""
    grid, bm, bn = _grid2(x.shape, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _copy_kernel, grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret)(x)


def _add_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = a_ref[...] + b_ref[...]


def add(a, b, *, bm=DEFAULT_BM, bn=DEFAULT_BN, interpret=False):
    """ADD: o = a + b, standard block tiling."""
    grid, bm, bn = _grid2(a.shape, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _add_kernel, grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret)(a, b)


def _update_kernel(a_ref, o_ref, *, scalar):
    o_ref[...] = a_ref[...] * scalar


def update_nt(a, s=2.0, *, interpret=False):
    """UPDATE with NT (full-tile aligned, padded-grid) stores."""
    return _nt_call(functools.partial(_update_kernel, scalar=s), (a,),
                    a.shape, a.dtype, interpret=interpret)


def update(a, s=2.0, *, bm=DEFAULT_BM, bn=DEFAULT_BN, interpret=False):
    """UPDATE: o = s * a, standard block tiling."""
    grid, bm, bn = _grid2(a.shape, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_update_kernel, scalar=s),
        grid=grid, in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        interpret=interpret)(a)


def _triad_kernel(b_ref, c_ref, o_ref, *, scalar):
    o_ref[...] = b_ref[...] + scalar * c_ref[...]


def stream_triad_nt(b, c, s=2.0, *, interpret=False):
    """STREAM triad with NT (full-tile aligned, padded-grid) stores."""
    return _nt_call(functools.partial(_triad_kernel, scalar=s), (b, c),
                    b.shape, b.dtype, interpret=interpret)


def stream_triad(b, c, s=2.0, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                 interpret=False):
    """STREAM triad: o = b + s * c, standard block tiling."""
    grid, bm, bn = _grid2(b.shape, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_triad_kernel, scalar=s),
        grid=grid, in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        interpret=interpret)(b, c)


def _striad_kernel(b_ref, c_ref, d_ref, o_ref):
    o_ref[...] = b_ref[...] + c_ref[...] * d_ref[...]


def schoenauer_triad(b, c, d, *, bm=DEFAULT_BM, bn=DEFAULT_BN,
                     interpret=False):
    """Schoenauer triad: o = b + c * d (three loads, one store)."""
    grid, bm, bn = _grid2(b.shape, bm, bn)
    spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        _striad_kernel, grid=grid, in_specs=[spec, spec, spec],
        out_specs=spec, out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        interpret=interpret)(b, c, d)


# --- reductions -------------------------------------------------------------

def _partial_sum_kernel(x_ref, o_ref):
    o_ref[0, 0] = jnp.sum(x_ref[...])


def sum_reduction(x, *, bm=DEFAULT_BM, bn=DEFAULT_BN, interpret=False):
    """Two-stage: per-block partials in the kernel, final sum outside."""
    grid, bm, bn = _grid2(x.shape, bm, bn)
    parts = pl.pallas_call(
        _partial_sum_kernel, grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(grid, jnp.float32),
        interpret=interpret)(x)
    return jnp.sum(parts)


def _pi_kernel(o_ref, *, n, bn):
    j = pl.program_id(0)
    i = j * bn + jax.lax.iota(jnp.float32, bn)
    x = (i + 0.5) / n
    o_ref[0, 0] = jnp.sum(4.0 / (1.0 + x * x))


def pi_integration(n, *, bn=4096, interpret=False):
    """Midpoint-rule quadrature of 4/(1+x^2) on [0,1) with n points."""
    assert n % bn == 0
    parts = pl.pallas_call(
        functools.partial(_pi_kernel, n=n, bn=bn),
        grid=(n // bn,),
        out_specs=pl.BlockSpec((1, 1), lambda j: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((n // bn, 1), jnp.float32),
        interpret=interpret)()
    return jnp.sum(parts) / n


# --- stencils ---------------------------------------------------------------

def _jacobi2d_kernel(u_ref, o_ref):
    blk = u_ref[...]
    o_ref[...] = 0.25 * (blk[:-2, 1:-1] + blk[2:, 1:-1] +
                         blk[1:-1, :-2] + blk[1:-1, 2:])


def jacobi_2d5pt(u, *, bm=64, interpret=False):
    """Row-tiled with a +-1 halo via element-indexed block dims."""
    h, w = u.shape
    m = h - 2
    bm = min(bm, m)
    assert m % bm == 0, (h, bm)
    return pl.pallas_call(
        _jacobi2d_kernel, grid=(m // bm,),
        in_specs=[pl.BlockSpec((pl.Element(bm + 2), w),
                               lambda i: (i * bm, 0))],
        out_specs=pl.BlockSpec((bm, w - 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, w - 2), u.dtype),
        interpret=interpret)(u)


def _jacobi3d_kernel(u_ref, o_ref):
    b = u_ref[...]
    o_ref[...] = (1.0 / 6.0) * (
        b[:-2, 1:-1, 1:-1] + b[2:, 1:-1, 1:-1] +
        b[1:-1, :-2, 1:-1] + b[1:-1, 2:, 1:-1] +
        b[1:-1, 1:-1, :-2] + b[1:-1, 1:-1, 2:])


def jacobi_3d7pt(u, *, bz=8, interpret=False):
    """3-D 7-point Jacobi sweep, depth-tiled with a +-1 halo."""
    d, h, w = u.shape
    m = d - 2
    bz = min(bz, m)
    assert m % bz == 0, (d, bz)
    return pl.pallas_call(
        _jacobi3d_kernel, grid=(m // bz,),
        in_specs=[pl.BlockSpec((pl.Element(bz + 2), h, w),
                               lambda i: (i * bz, 0, 0))],
        out_specs=pl.BlockSpec((bz, h - 2, w - 2), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, h - 2, w - 2), u.dtype),
        interpret=interpret)(u)


def _gs_kernel(u_ref, o_ref, *, sweeps):
    """Gauss-Seidel row wavefront inside one kernel: LCD on the row loop.
    Row i reads the already-updated row i-1 straight from o_ref."""
    h = o_ref.shape[0]

    def one_sweep(_, carry):
        def row(i, c):
            prev = o_ref[pl.ds(i - 1, 1), :]             # updated row i-1
            cur = o_ref[pl.ds(i, 1), :]
            down = o_ref[pl.ds(i + 1, 1), :]             # old row i+1
            new_int = 0.25 * (prev[:, 1:-1] + down[:, 1:-1] +
                              cur[:, :-2] + cur[:, 2:])
            new = jnp.concatenate([cur[:, :1], new_int, cur[:, -1:]],
                                  axis=1)
            o_ref[pl.ds(i, 1), :] = new
            return c
        jax.lax.fori_loop(1, h - 1, row, 0)
        return carry

    o_ref[...] = u_ref[...]
    jax.lax.fori_loop(0, sweeps, one_sweep, 0)


def gauss_seidel_2d5pt(u, sweeps=1, *, interpret=False):
    """In-place 2-D 5-point Gauss-Seidel sweeps (row wavefront)."""
    return pl.pallas_call(
        functools.partial(_gs_kernel, sweeps=sweeps),
        grid=(1,),
        in_specs=[pl.BlockSpec(u.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(u.shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        interpret=interpret)(u)
