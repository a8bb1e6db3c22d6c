"""Seconds from process start to the opening of the window: imports,
weights, engine build, loading or compiling every program, warm-up."""


def read(run):
    return run.setup_s
