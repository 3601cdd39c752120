"""What the test files that read an ``obs`` event file share: its span lines,
and the telemetry files' bitwise comparison of two fits (``conftest.py``'s
``plane_off`` keeps the plane off around those files' tests)."""

import json

import numpy as np


def _assert_bitwise(a, b):
    for f in ("params", "neg_log_likelihood", "converged", "iters", "status"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"field {f!r} differs")


def _span_lines(path, builds=False):
    """The file's span lines.  Without ``builds`` the ``program.build`` lines
    are left out: a stream starts with whatever the PROCESS had built before
    ``obs.enable`` (roots with no walk, other tests' programs among them)
    and holds one more wherever a test's shape is new to the process, and a
    test of the walk's own tree means neither."""
    with open(path, encoding="utf-8") as f:
        events = [json.loads(line) for line in f]
    return [e for e in events if e.get("kind") == "span"
            and (builds or e.get("name") != "program.build")]
