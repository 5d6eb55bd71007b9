"""Executables built or loaded from the compile cache inside the window,
counted by a ``jax.monitoring`` listener the harness installs."""


def read(run):
    return run.compiles
