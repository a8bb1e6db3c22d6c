"""Backend compiles and compile-cache loads inside the window, from
``jax.monitoring``; every program should have been loaded in set-up."""


def read(run):
    return run.compiles_in_window
