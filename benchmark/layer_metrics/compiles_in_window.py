"""Device: executables the backend built, or fetched from the persistent
cache, inside the measured window, counted by a ``jax.monitoring`` listener
on ``/jax/core/compile/backend_compile_duration``.  Must be 0: it is part
of ``correct`` in every run, traced or not."""


def read(run):
    return run.compiles_in_window
