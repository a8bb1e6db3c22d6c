"""The percentile the end-to-end tails use."""

import math


def p90(values) -> float:
    """Nearest-rank 90th percentile (well defined with infinities)."""
    v = sorted(values)
    return float(v[max(0, math.ceil(0.9 * len(v)) - 1)])
