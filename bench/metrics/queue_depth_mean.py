"""Requests waiting in the router's queue before each round of the
window, mean over rounds."""


def read(run):
    q = [r.queued for r in run.rounds]
    return sum(q) / len(q) if q else None
