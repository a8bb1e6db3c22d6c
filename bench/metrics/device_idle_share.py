"""1 - (union of device operation intervals) / traced window, mean
over the cell's chips."""


def read(run):
    tr = run.trace
    if not tr.n_devices:
        return None
    return 1.0 - tr.mean_busy_s() / tr.window_s
