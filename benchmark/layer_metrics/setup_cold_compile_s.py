"""Walk driver, set-up: ``sum(compiled_s)`` over set-up's ``program.build``
lines — what this set-up's programs cost to COMPILE, readable from a warm
run: on a hit jax's ``compile_time_saved_sec + retrieval_s`` (the compile
time the cache entry recorded), on a miss the backend's own seconds.
``first_setup_s`` less a warm ``setup_s`` should be near it; where it is not,
something else is cold.  ``None`` from a program without the log."""

from benchmark import setup_builds


def read(run):
    return setup_builds.attr_sum(run, "compiled_s")
