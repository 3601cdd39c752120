"""Walk driver, set-up: ``sum(retrieval_s)`` over set-up's ``program.build``
lines that hit the persistent cache — jax's
``cache_retrieval_time_sec``: the file's read, its decompression and
deserialisation, the executable's load onto the chip.  What smaller
executables or a local cache directory shorten; 0 on a cold run.  ``None``
from a program without the log."""

from benchmark import setup_builds


def read(run):
    return setup_builds.attr_sum(run, "retrieval_s")  # null but on a hit
