"""Operations and bytes that the served model's work needs, from shapes.

All counts are of the algorithm, not of what a kernel happens to do: a
matrix product of (m, k) by (k, n) is 2mkn operations; attention over
``rows`` cached rows is 2 * rows * Dh operations per query head for the
scores and as many for the weighted values; the paged decode kernel has
to read every page that holds a live row, K and V, at the cache's
element size.

The sizes come from the architecture's ``Model`` (``arch/<name>.py``):
``token_params``, the weights one token multiplies by over all layers;
``cached_layers``, the layers that attend over the paged cache;
``attn_width``, query heads times head size in such a layer;
``kv_row_bytes``, one cached row of one such layer; and ``mixer_flops``,
a token's other operations (a recurrent mixer's scan).
"""

from __future__ import annotations

import numpy as np


def attn_flops(m, rows) -> np.ndarray:
    """Scores and weighted values of one query over ``rows`` rows, one
    layer, every query head."""
    return 4 * m.attn_width * np.asarray(rows, np.float64)


def prefill_flops(m, n: int) -> float:
    """One prompt of ``n`` tokens: every layer over every token (causal
    attention), and the head at the last position only, which is all
    a prefill has to produce."""
    return (2.0 * m.token_params * n + m.mixer_flops * n
            + m.cached_layers * float(attn_flops(m, np.arange(1, n + 1)).sum())
            + 2.0 * m.d * m.vocab)


def decode_flops(m, rows) -> float:
    """Decoded tokens, each attending over its ``rows`` (its position
    plus one): every layer, and the head."""
    rows = np.asarray(rows, np.float64)
    per_tok = 2.0 * (m.token_params + m.d * m.vocab) + m.mixer_flops
    return float(rows.size * per_tok
                 + m.cached_layers * attn_flops(m, rows).sum())


def paged_attn_bytes(m, rows, page: int) -> float:
    """What the paged decode kernel must read for queries over ``rows``
    rows each: every page that holds a live row, in every layer that
    attends."""
    pages = np.ceil(np.asarray(rows, np.float64) / page)
    return float(pages.sum() * page * m.kv_row_bytes * m.cached_layers)


def paged_attn_flops(m, rows) -> float:
    return float(m.cached_layers * attn_flops(m, rows).sum())


def least_seconds(flops: float, nbytes: float, peak) -> tuple:
    """(seconds, bound): the larger of compute time and memory time at
    the chip's peaks, and which of the two it is."""
    tc, tm = flops / peak.flops, nbytes / peak.bytes_per_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
