"""Process: wall seconds from the process's start (``run.py``'s first
statement) to the device mark, the moment ``jax.devices()`` has returned
and the device gate has passed — the interpreter, ``benchmark.*``, ``import
spark_timeseries_tpu`` with every subpackage, ``import jax`` (thousands of
files over the machine's 9p filesystem), libtpu and the runtime's start.
It is the stretch that PRECEDES ``setup_s`` (which counts from the device
mark) and is read beside it, which is all ``moves`` means here: until PR 33
it was inside ``setup_s``, 55-65% of a warm one.  It is the machine's more
than the program's and differs by 20-60% between runs that follow one
another, so nobody is held to it; ``process_start_cpu_s`` is the part of
it a program can move."""


def read(run):
    return run.process_start_s
