"""What a kernel must do, computed from its shapes, and its share of the
chip's roofline.  Kept with the benchmark so that no PR that changes a
kernel changes what it is measured against.

The objective kernels (``ops/pallas_kernels.py``) fold a ``[rows, time]``
chunk into ``[time, rows/128, 128]`` and walk it in ``time`` serial steps,
a grid step taking R vector registers of 1,024 series (8 sublanes x 128
lanes) each: R = 1, 2 or 4, the kernel file's own rule (``series_rows``;
the adjoint kernels take 1).  Time is padded to a multiple of 8 up to
1,024 steps and to a multiple of 1,024 beyond (``_time_layout``), rows to a
multiple of 1,024.  The bytes and steps counted here are the panel's and
do not depend on R.
"""

from __future__ import annotations

# one vector register of series: the unit rows are padded to, whatever
# number of registers a kernel takes a time step
SERIES_PER_BLOCK = 1024
CHUNK_T = 1024


def padded_time_steps(n: int) -> int:
    n8 = -(-n // 8) * 8
    return n8 if n8 <= CHUNK_T else -(-n // CHUNK_T) * CHUNK_T


def padded_rows(rows: int) -> int:
    return -(-rows // SERIES_PER_BLOCK) * SERIES_PER_BLOCK


def panel_pass_bytes(rows: int, time_steps: int, itemsize: int = 4) -> int:
    """Bytes one pass over a folded chunk must read: every observation
    once.  The least an objective evaluation can move; parameters, seeds
    and the ``[rows]`` result are thousandths of it."""
    return padded_rows(rows) * padded_time_steps(time_steps) * itemsize


def recurrence_flops(rows: int, time_steps: int, flops_per_step: int) -> int:
    return padded_rows(rows) * padded_time_steps(time_steps) * flops_per_step


def roofline_share(nbytes: float, flops: float, seconds: float, peaks: dict):
    """``(share in %, which bound)``: the least time the chip could take —
    the larger of bytes over HBM bandwidth and operations over peak
    FLOP/s — over the time the kernel took."""
    t_bytes = nbytes / (peaks["hbm_gbps"] * 1e9)
    t_flops = flops / (peaks["bf16_tflops"] * 1e12)
    bound = "hbm" if t_bytes >= t_flops else "flops"
    return 100.0 * max(t_bytes, t_flops) / seconds, bound


def kernel_roofline(run, scope: str):
    """Roofline share of the events under ``scope`` in the traced window.

    Bytes: what each event's HLO text in the trace says it reads and writes
    (operands and results, each once).  Where the trace carries no shapes,
    one read of the folded chunk per event (:func:`panel_pass_bytes`) —
    exact for the full-batch stage-1 events, an overcount for the compacted
    stage-2 ones, which is why the passes-and-rows counter is an open
    question in ``PERF.md``."""
    if run.trace is None or run.peaks is None:
        return None
    got = run.trace.scope(scope)
    if not got["events"] or got["seconds"] <= 0:
        return None
    cfg = run.cell.config
    obj = cfg["objective"]
    nbytes = got["bytes"] or got["events"] * panel_pass_bytes(
        int(cfg["chunk_rows"]), int(obj["time_steps"]))
    flops = got["events"] * recurrence_flops(
        int(cfg["chunk_rows"]), int(obj["time_steps"]),
        int(obj["flops_per_step"]))
    return roofline_share(nbytes, flops, got["seconds"], run.peaks)[0]
