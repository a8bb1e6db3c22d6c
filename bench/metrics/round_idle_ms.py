"""Device idle time inside the ``serve.round`` spans that end in the
window, per round, mean over the cell's chips, in milliseconds: the part
of the device's idle share that falls inside the program's rounds."""

import program_spans as P


def read(run):
    idle = P.round_idle_s(run.trace, run.trace.program_spans)
    return None if idle is None else 1e3 * idle
