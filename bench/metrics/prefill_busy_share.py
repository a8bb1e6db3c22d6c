"""Device seconds in the prefill program over device busy seconds,
from the trace's program (module) events."""


def read(run):
    tr = run.trace
    busy = tr.mean_busy_s()
    if busy <= 0:
        return None
    return tr.module_seconds(lambda e: "prefill" in e.name) / busy
