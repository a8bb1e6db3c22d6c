"""Output tokens handed out by rounds that ended inside the window,
over the window's length and the cell's chips."""


def read(run):
    n = sum(r.emitted for r in run.rounds if r.t <= run.seconds)
    return n / run.seconds / run.chips
