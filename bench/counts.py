"""Operations and bytes that the served model's work needs, from shapes.

All counts are of the algorithm, not of what a kernel happens to do: a
matrix product of (m, k) by (k, n) is 2mkn operations; attention over
``rows`` cached rows is 2 * rows * Dh operations per query head for the
scores and as many for the weighted values; the paged decode kernel has
to read every page that holds a live row, K and V, at the cache's
element size.
"""

from __future__ import annotations

import numpy as np

from weights import Model


def linear_params(m: Model) -> int:
    """Weights one token multiplies by in one layer (projections + FFN)."""
    qkv = m.d * (m.heads + 2 * m.kv_heads) * m.head_dim
    return qkv + m.heads * m.head_dim * m.d + 3 * m.d * m.ffn


def attn_flops(m: Model, rows) -> np.ndarray:
    """Scores and weighted values of one query over ``rows`` rows, one
    layer, every query head."""
    return 4 * m.heads * m.head_dim * np.asarray(rows, np.float64)


def prefill_flops(m: Model, n: int) -> float:
    """One prompt of ``n`` tokens: every layer over every token (causal
    attention), and the head at the last position only, which is all
    a prefill has to produce."""
    per_layer = 2.0 * linear_params(m) * n + float(
        attn_flops(m, np.arange(1, n + 1)).sum())
    return m.layers * per_layer + 2.0 * m.d * m.vocab


def decode_flops(m: Model, rows) -> float:
    """Decoded tokens, each attending over its ``rows`` (its position
    plus one): every layer, and the head."""
    rows = np.asarray(rows, np.float64)
    per_tok = 2.0 * (m.layers * linear_params(m) + m.d * m.vocab)
    return float(rows.size * per_tok + m.layers * attn_flops(m, rows).sum())


def kv_bytes_per_row(m: Model) -> int:
    """K and V of one cached row in one layer."""
    return 2 * m.kv_heads * m.head_dim * np.dtype(_np_dtype(m.dtype)).itemsize


def paged_attn_bytes(m: Model, rows, page: int) -> float:
    """What the paged decode kernel must read for queries over ``rows``
    rows each: every page that holds a live row, in every layer."""
    pages = np.ceil(np.asarray(rows, np.float64) / page)
    return float(pages.sum() * page * kv_bytes_per_row(m) * m.layers)


def paged_attn_flops(m: Model, rows) -> float:
    return float(m.layers * attn_flops(m, rows).sum())


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(name)


def least_seconds(flops: float, nbytes: float, peak) -> tuple:
    """(seconds, bound): the larger of compute time and memory time at
    the chip's peaks, and which of the two it is."""
    tc, tm = flops / peak.flops, nbytes / peak.bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
