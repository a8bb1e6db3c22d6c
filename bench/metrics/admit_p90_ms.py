"""Nearest-rank p90 of the durations of the ``serve.admit`` spans that
end in the window (prefill, first token and insertion into the pages),
in milliseconds; from the program's spans."""

import program_spans as P


def read(run):
    tr = run.trace
    p90 = P.nearest_rank(P.durations(tr.program_spans, tr.window, "admit"),
                         0.9)
    return None if p90 is None else 1e3 * p90
