"""XLA backend compiles inside the measured window (``jax.monitoring``)."""


def read(run):
    return run.compiles_in_window
