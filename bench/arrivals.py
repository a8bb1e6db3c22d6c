"""Request streams from a traffic file and a seed.

A traffic file (``bench/traffic/<mix>.json``) holds only parameters:

- ``arrival``: ``"poisson"``, open loop: requests are due on a schedule,
  whatever the server does, at ``rate_per_s`` per second;
- ``prompt`` and ``output``: lognormal lengths (``median``, ``sigma``)
  clipped to ``[min, max]``; ``prompt.grid`` snaps prompt lengths to a
  log grid of that many lengths (multiples of 8);
- ``source``: where the numbers come from (not read here).

Sizes and gaps are drawn at evenly spaced quantiles of their
distributions and put in one order, the same for every seed
(``SCHEDULE_SEED``); the seed draws the token ids. So every seed offers
the same work on the same schedule, and what differs between seeds is
the content (and, in the benchmark, the weights). A seed that also set
the order would move the tails with it: at 0.8 of the knee, runs of
one cell in six orders read a p90 TTFT from 0.61 to 0.94 s, while two
runs of one order agreed within 6 %.
"""

from __future__ import annotations

import dataclasses
import json

import numpy as np

#: the generator of the order of sizes and gaps, for every seed
SCHEDULE_SEED = 0


@dataclasses.dataclass(frozen=True)
class Req:
    rid: str
    due: float | None             # seconds after the window opens
    prompt: np.ndarray            # int32 token ids
    max_new: int


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _norm_ppf(p):
    """Inverse standard normal CDF (Acklam's rational approximation,
    relative error below 1.2e-9), so no SciPy is needed."""
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    p = np.asarray(p, np.float64)
    out = np.empty_like(p)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = np.sqrt(-2 * np.log(p[lo]))
    out[lo] = ((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4])
                * q + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3])
                               * q + 1))
    q = np.sqrt(-2 * np.log(1 - p[hi]))
    out[hi] = -((((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q + c[4])
                 * q + c[5]) / ((((d[0] * q + d[1]) * q + d[2]) * q + d[3])
                                * q + 1))
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4])
                 * r + a[5]) * q / (((((b[0] * r + b[1]) * r + b[2]) * r
                                      + b[3]) * r + b[4]) * r + 1))
    return out


def grid_of(spec: dict) -> list:
    """The prompt lengths a length spec can produce."""
    g, lo, hi = spec["grid"], spec["min"], spec["max"]
    pts = {int(round(lo * (hi / lo) ** (i / (g - 1)) / 8.0)) * 8
           for i in range(g)}
    return sorted(pts)


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the spec's lognormal,
    clipped, and snapped (in log space) to its grid if it has one."""
    x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(_quantiles(n)))
    x = np.clip(x, spec["min"], spec["max"])
    if "grid" not in spec:
        return np.round(x).astype(np.int64)
    grid = grid_of(spec)
    lg = np.log(np.asarray(grid, np.float64))
    idx = np.abs(np.log(x)[:, None] - lg[None, :]).argmin(axis=1)
    return np.asarray(grid, np.int64)[idx]


def gaps(mix: dict, n: int) -> np.ndarray:
    """Gaps between ``n`` arrivals: exponential quantiles at the rate."""
    return -np.log1p(-_quantiles(n)) / mix["rate_per_s"]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Requests due in ``[0, seconds)`` at the mix's rate, due-ordered:
    the fixed multisets of sizes and gaps, permuted in the schedule's
    order, with token ids from the seed."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(SCHEDULE_SEED)
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    g = order.permutation(gaps(mix, n))
    due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
    plen = order.permutation(lengths(mix["prompt"], n))
    olen = order.permutation(lengths(mix["output"], n))
    return [Req(rid=f"r{i}", due=float(due[i]),
                prompt=rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
                max_new=int(olen[i]))
            for i in range(n) if due[i] < seconds]
