"""Engine: programs compiled, or loaded from the persistent compilation
cache, inside the measured window (``jax.monitoring`` events)."""


def read(run):
    return run.compiles_in_window
