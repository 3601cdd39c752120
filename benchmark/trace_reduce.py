"""From a profiler trace to numbers: the one reduction every PR is read by.

Two stages, so that the second can be checked against a small recorded
trace (``tests/data/``) without a chip:

1. :func:`load_xplane` reads the ``.xplane.pb`` the JAX profiler wrote
   (``jax.profiler.ProfileData``, nothing but JAX) into a plain dict: per
   device the executed operations ``[name, start_ns, dur_ns, bytes]`` and
   per host thread the annotated spans ``[name, start_ns, dur_ns]``.
2. :class:`Trace` reduces that dict: the traced window (the benchmark's own
   ``bench.window`` annotation), the union of device-op intervals per chip
   (busy) and its complement (idle gaps, each named by the ``obs`` spans the
   host was in), per-operation self time (an operation that contains others,
   such as a ``while``, counts only what its children do not cover), and
   sums over the operations under a kernel's named scope.

What a v5e trace looks like (jax 0.9.0, looked at by hand in PR 23): a plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` has one event per executed
HLO instruction, a ``while`` spanning the events of its body; the event's
NAME is the instruction's whole HLO text (``%name = shapes opcode(operands),
attributes``) and no stat carries a scope path.  A ``jax.named_scope`` around
a Pallas call survives in the instruction's name (``pallas.css_neg_loglik.8``,
``jvp_pallas.css_neg_loglik_.25``, ``transpose_jvp_pallas...``), which is what
:meth:`Trace.scope` matches.  Host threads are lines of ``/host:CPU``, several
of them named alike; device and host events share one clock.

    python3 benchmark/trace_reduce.py <file.xplane.pb> [--dump N]

prints the reduction (``--dump``: also the planes, lines and the first N
events of each with their stats, to look at a trace by hand).
"""

from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys

WINDOW_SPAN = "bench.window"
_OPS_LINE = "XLA Ops"  # the device line of executed HLO instructions
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_BRACES = re.compile(r"\{[^{}]*\}")
_SHAPE = re.compile(r"\b(pred|s8|u8|s16|u16|bf16|f16|s32|u32|f32|s64|u64|f64)"
                    r"\[([0-9,]*)\]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
             "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
             "f64": 8}


def _group(text: str, start: int) -> int:
    """Index just past the parenthesis group that opens at ``start``."""
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i + 1
    return len(text)


def parse_hlo(text: str):
    """``(instruction name, bytes)`` of one HLO instruction's text
    ``%name = result-shapes opcode(operands), attributes``: the bytes of its
    results and operands, each once — what the operation must at least
    move.  Layouts and attributes (which repeat shapes) are not counted."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%"), 0
    while True:  # layouts {2,1,0:T(8,128)}, then the attribute braces
        stripped = _BRACES.sub("", rest)
        if stripped == rest:
            break
        rest = stripped
    rest = rest.lstrip()
    end = _group(rest, 0) if rest.startswith("(") else rest.find(" ")
    opened = rest.find("(", end)
    span = rest[:end] + (rest[opened:_group(rest, opened)]
                         if opened >= 0 else "")
    total = 0
    for dtype, dims in _SHAPE.findall(span):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _ITEMSIZE[dtype]
    return name.lstrip("%"), total


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, host_names=None) -> dict:
    """Stage 1 (see module docstring).  ``host_names``: keep only host
    events with these names (the ``obs`` span vocabulary plus
    ``bench.window``); ``None`` keeps every host event that is not a
    profiler-internal one."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops, parsed = [], {}  # a walk repeats a few thousand texts
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for ev in line.events:
                    text = ev.name
                    if text not in parsed:
                        parsed[text] = parse_hlo(text)
                    name, nbytes = parsed[text]
                    ops.append([name, int(ev.start_ns), int(ev.duration_ns),
                                nbytes])
            devices.append({"plane": plane.name, "ordinal": int(m.group(1)),
                            "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    if host_names is not None:
                        if ev.name not in host_names:
                            continue
                    elif ev.name.startswith("$") or ev.duration_ns <= 0:
                        continue
                    spans.append([ev.name, int(ev.start_ns),
                                  int(ev.duration_ns)])
                if spans:
                    host.append({"thread": line.name, "spans": spans})
    devices.sort(key=lambda d: d["ordinal"])
    return {"devices": devices, "host": host}


def _union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(ops):
    """Per operation, its duration less what the operations nested inside
    it cover (a ``while`` spans its whole loop; its body's operations are
    events of the same line)."""
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [op[2] for op in ops]
    stack = []  # indices of the open enclosing operations
    for i in order:
        start, end = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            parent = stack[-1]
            p_end = ops[parent][1] + ops[parent][2]
            self_ns[parent] -= max(0, min(end, p_end) - start)
        stack.append(i)
    return [max(0, v) for v in self_ns]


def _label(name: str) -> str:
    """A device operation's name for the breakdown: its instruction name
    without the instance number (``jvp_pallas.css_neg_loglik_.25`` ->
    ``jvp_pallas.css_neg_loglik_``)."""
    return re.sub(r"\.\d+$", "", name) or name


class Trace:
    """Stage 2 (see module docstring) over :func:`load_xplane`'s dict."""

    def __init__(self, data: dict):
        self.data = data
        self.window = self._window()
        w0, w1 = self.window
        self.devices = []
        for dev in data["devices"]:
            ops = [[n, max(s, w0), min(s + d, w1) - max(s, w0), b]
                   for n, s, d, b in dev["ops"] if s < w1 and s + d > w0]
            busy = _union([(op[1], op[1] + op[2]) for op in ops])
            self.devices.append({"ordinal": dev["ordinal"], "ops": ops,
                                 "self_ns": _self_times(ops), "busy": busy})

    # -- window -------------------------------------------------------------

    def _window(self):
        spans = [(s, s + d) for th in self.data["host"]
                 for n, s, d in th["spans"] if n == WINDOW_SPAN]
        if spans:
            return min(s for s, _ in spans), max(e for _, e in spans)
        ends = [(op[1], op[1] + op[2]) for dev in self.data["devices"]
                for op in dev["ops"]]
        if not ends:
            return 0, 0
        return min(s for s, _ in ends), max(e for _, e in ends)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    # -- busy / idle ----------------------------------------------------------

    def busy_s_per_device(self) -> list:
        return [sum(e - s for s, e in d["busy"]) / 1e9 for d in self.devices]

    def busy_s(self) -> float:
        per = self.busy_s_per_device()
        return sum(per) / len(per) if per else 0.0

    def idle_share_worst(self):
        if not self.devices or self.window_s <= 0:
            return None
        return 1.0 - min(self.busy_s_per_device()) / self.window_s

    def idle_gaps(self, top: int = 10) -> list:
        """``[[what the host was doing, seconds], ...]``: the longest gaps
        between device operations on the chip that idled most."""
        if not self.devices:
            return []
        per = self.busy_s_per_device()
        dev = self.devices[per.index(min(per))]
        w0, w1 = self.window
        edges = [w0] + [t for iv in dev["busy"] for t in iv] + [w1]
        gaps = [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
        gaps.sort(reverse=True)
        return [[self._host_at((s + e) // 2), dur / 1e9]
                for dur, s, e in gaps[:top]]

    def _host_at(self, t_ns: int) -> str:
        """The innermost annotated span on each host thread at ``t_ns``."""
        names = set()
        for th in self.data["host"]:
            inner = None
            for n, s, d in th["spans"]:
                if n != WINDOW_SPAN and s <= t_ns < s + d:
                    if inner is None or s >= inner[1]:
                        inner = (n, s)
            if inner:
                names.add(inner[0])
        return "+".join(sorted(names)) or "no span"

    # -- operations ---------------------------------------------------------

    def device_ops(self, top: int = 10) -> list:
        """``[[name, seconds], ...]``: self time by operation label, summed
        over the chips."""
        acc = collections.Counter()
        for dev in self.devices:
            for op, self_ns in zip(dev["ops"], dev["self_ns"]):
                acc[_label(op[0])] += self_ns
        return [[n, ns / 1e9] for n, ns in acc.most_common(top)]

    def scope(self, scope: str) -> dict:
        """Events whose instruction name carries a named scope, over the
        chips: how many, their self time, and the bytes their HLO text says
        they must move."""
        count = ns = nbytes = 0
        for dev in self.devices:
            for op, self_ns in zip(dev["ops"], dev["self_ns"]):
                if scope in op[0] and self_ns > 0:
                    count += 1
                    ns += self_ns
                    nbytes += op[3]
        return {"events": count, "seconds": ns / 1e9, "bytes": nbytes}

    def busy_outside(self, scopes) -> float:
        """Seconds of self time outside every one of ``scopes``, summed
        over the chips."""
        ns = 0
        for dev in self.devices:
            for op, self_ns in zip(dev["ops"], dev["self_ns"]):
                if not any(s in op[0] for s in scopes):
                    ns += self_ns
        return ns / 1e9

    def host_spans(self, name: str) -> list:
        """``[(start_ns, dur_ns), ...]`` of one annotated span name inside
        the window, over all threads."""
        w0, w1 = self.window
        return sorted((s, d) for th in self.data["host"]
                      for n, s, d in th["spans"]
                      if n == name and w0 <= s < w1)

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops(10),
                "idle_gaps": self.idle_gaps(10)}


def cut(data: dict, start_ns: int, stop_ns: int) -> dict:
    """The part of a stage-1 dict inside ``[start_ns, stop_ns)``, times
    shifted to start at 0: how the small recorded trace under
    ``tests/data`` was taken from a whole one."""
    def keep(s, d):
        return s >= start_ns and s + d <= stop_ns

    return {
        "devices": [{**dev, "ops": [[n, s - start_ns, d, b]
                                    for n, s, d, b in dev["ops"]
                                    if keep(s, d)]}
                    for dev in data["devices"]],
        "host": [{"thread": th["thread"],
                  "spans": [[n, s - start_ns, d] for n, s, d in th["spans"]
                            if keep(s, d)]}
                 for th in data["host"]],
    }


def _dump(path: str, n: int) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name, dict(plane.stats))
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:n]:
                print("    ", ev.name, int(ev.start_ns), int(ev.duration_ns),
                      {k: str(v)[:300] for k, v in ev.stats})


def main(argv) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    path = argv[0]
    if os.path.isdir(path):
        path = find_xplane(path)
    if "--dump" in argv:
        _dump(path, int(argv[argv.index("--dump") + 1]))
    tr = Trace(load_xplane(path))
    print(json.dumps({"window_s": tr.window_s, "busy_s": tr.busy_s(),
                      "busy_s_per_device": tr.busy_s_per_device(),
                      "idle_share_worst": tr.idle_share_worst(),
                      **tr.breakdown()}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
