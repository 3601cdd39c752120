"""Sanitizer (``reliability/sanitize.py``): the share of the traced window
the device spent on events under the ``sanitize`` span — the probe of every
chunk, on a dense panel too (``benchmark/device_phases.py``; its idle
counterpart is ``sanitize_exposed_s_per_chunk``)."""

from benchmark import device_phases


def read(run):
    return device_phases.share(run, "sanitize")
