"""Nearest-rank p90 over the requests submitted in the window of the
time each waited in the router's queue: from the end of its
``serve.submit`` span to the start of the ``serve.admit`` with the same
``rid``, or to the window's close; from the program's spans."""

import program_spans as P


def read(run):
    tr = run.trace
    return P.nearest_rank(P.queue_waits(tr.program_spans, tr.window), 0.9)
