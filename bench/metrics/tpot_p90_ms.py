"""90th percentile over requests due in the window of (time of last
token - time of first token) / (tokens - 1), in milliseconds; an
unfinished request counts as infinitely slow."""

from percentile import p90


def read(run):
    v = run.tpots()
    return 1e3 * p90(v) if v else None
