"""90th percentile over every request due in the window of the time
from when it was due to its first token; a request that never got one
counts with the time until the run gave up on it."""

from percentile import p90


def read(run):
    return p90(run.ttfts()) if run.lives else None
