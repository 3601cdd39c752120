"""Device, walk cells: of the seconds the chip ran nothing inside the traced
window, the share in which the walk's driver thread was under no program
span but the ``walk`` root (or under the benchmark's own ``bench.walk``, or
under none) — by overlap, ``benchmark/span_idle.py``.  Says whether the
tracing covers the host path: what is left here no span can explain."""

from benchmark import span_idle


def read(run):
    parts = span_idle.split(run.trace)
    total = sum(parts.values()) if parts else 0.0
    if total <= 0:
        return None
    return sum(parts.get(n, 0.0) for n in span_idle.UNNAMED) / total
