#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process, the only one to touch JAX.  It builds the cell's inputs from
``--seed`` on the device, warms up the cell's own shapes, measures for
``--seconds``, checks the outputs against the plain reference, and prints
as the LAST line of stdout one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (plus ``breakdown`` when traced, and
last ``checks``: every number compared beside its limit, which are also
the last lines of stderr).  ``--trace 0`` reports the cell's end-to-end
metrics with ``obs`` off, as users have it; ``--trace 1`` enables ``obs``
with ``profile=True``, profiles a few seconds inside the window and reports
the cell's per-layer metrics.  Earlier lines (also kept under ``--out``)
carry versions, per-walk walls, the journal's filesystem and the checks.

Two marks.  ``_T0`` is this file's first statement; every detail line's
``at_s`` counts from it.  The device mark is taken in :func:`prepare` the
moment ``jax.devices()`` has returned and the device gate has passed.
``setup_s`` is the wall from the DEVICE mark to the statement before the
window opens: the panel made on the device, every compile or
compile-cache load, the whole warm-up, ``obs.enable`` on a traced run —
the stretch a PR can move work into.  What precedes the mark (the
interpreter, the imports, the runtime's start: the machine's, 10-20 s
that differ by half from run to run) is ``process_start_s`` and
``process_start_cpu_s``, per-layer metrics and fields of the ``start`` and
``done`` lines; ``setup_end_at_s`` there is the end point's ``at_s``, which
is what ``setup_s`` measured until PR 33.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` is the exception: tiny sizes
on whatever backend there is, control flow only, every line stamped
``"rehearsal": true`` and every time, rate and share left null.
"""

_T0 = __import__("time").time()  # every line's at_s counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import operator  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import manifest as manifest_mod  # noqa: E402
from benchmark import trace_reduce  # noqa: E402

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# host events kept in the trace: 1 = annotations (the obs spans and the
# benchmark's own); 2 adds the runtime's transfers and executes, which the
# reduction does not read — raise it to look at a gap under "no span" by hand
HOST_TRACER_LEVEL = 1
MEASURED_SOURCES = ("device_trace", "host_clock", "program_span")
RULES = {"<=": operator.le, ">=": operator.ge, "==": operator.eq}


class Tracer:
    """The profiler around a few seconds of the window, with the window
    itself marked by an annotation the reduction reads."""

    def __init__(self, log_dir: str):
        self.log_dir, self.running, self._window = log_dir, False, None
        self.own_spans = {trace_reduce.WINDOW_SPAN}

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = HOST_TRACER_LEVEL
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._window.__enter__()
        self.running = True

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False

    def span(self, name: str):
        """A span of the benchmark's own, beside the program's ``obs``
        spans in the trace."""
        import jax

        self.own_spans.add(name)
        return jax.profiler.TraceAnnotation(name)


class Compiles:
    """Every executable the backend built or fetched from the persistent
    cache, by ``jax.monitoring``; those after :meth:`open_window` are the
    ``compiles_in_window`` that must be 0."""

    def __init__(self):
        self.events, self._window_from, self._window_to = [], None, None

    def listen(self) -> None:
        import jax

        def on_duration(event, duration_secs, **_):
            if event == BACKEND_COMPILE_EVENT:
                self.events.append((time.perf_counter(), duration_secs))

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def open_window(self) -> None:
        self._window_from, self._window_to = time.perf_counter(), None

    def close_window(self) -> None:
        self._window_to = time.perf_counter()

    def in_window(self) -> int:
        return sum(1 for t, _ in self.events
                   if self._window_from <= t <= self._window_to)


class Run:
    """What a traffic kind and a layer-metric reader are handed."""

    def __init__(self, cell, args, devices, out_dir, device_mark):
        self.cell, self.seed = cell, int(args.seed)
        # the device mark: time.time() and os.times() when the device was
        # held.  setup_s counts from it; what precedes it is the process's
        # start, the machine's more than the program's
        self.device_mark_t, cpu = device_mark
        self.process_start_s = self.device_mark_t - _T0
        # what time.process_time() reads: user + system, every thread
        self.process_start_cpu_s = cpu.user + cpu.system
        self.process_start_cpu_user_s = cpu.user
        self.setup_s = self.setup_end_at_s = None
        self.seconds, self.rehearse = float(args.seconds), args.rehearse
        self.devices = devices
        self.out_dir = out_dir
        self.work_dir = os.path.join(out_dir, "work")
        self.tracer = Tracer(os.path.join(out_dir, "trace")) \
            if args.trace else None
        self.compiles = Compiles()
        self.device = {}       # the result line's device block
        self.peaks = None      # this device_kind's row of peaks.json
        self.state = None      # the kind's set-up
        self.result = None     # the kind's measured window
        self.trace = None      # trace_reduce.Trace of the traced window
        self.spans = []        # obs span events of the traced run
        self.compared = {}     # every number `correct` compared, by name
        self._log_path = os.path.join(out_dir, "run.jsonl")

    def since_device_s(self) -> float:
        """Seconds of set-up so far (wall since the device mark)."""
        return time.time() - self.device_mark_t

    def close_setup(self) -> None:
        """The statement before the window opens: ``setup_s`` ends here."""
        end = time.time()
        self.setup_s = end - self.device_mark_t
        self.setup_end_at_s = end - _T0  # setup_s as it was until PR 33

    def compare(self, name: str, value, rule: str, limit) -> bool:
        """One number of ``correct`` beside its limit (``rule`` is ``<=``,
        ``>=`` or ``==``); kept for the result line's ``checks``."""
        ok = bool(RULES[rule](value, limit))
        self.compared[name] = {"value": value, "rule": rule, "limit": limit,
                               "ok": ok}
        return ok

    def log(self, what: str, **fields) -> None:
        line = {"what": what, "workload": self.cell.name, "seed": self.seed,
                "at_s": round(time.time() - _T0, 3), **fields}
        if self.rehearse:
            line["rehearsal"] = True
        text = json.dumps(line, default=repr)
        print(text, flush=True)
        with open(self._log_path, "a", encoding="utf-8") as f:
            f.write(text + "\n")

    def kernel_scopes(self) -> tuple:
        return tuple(self.cell.config.get("kernels", ()))


def _fs_type(path: str) -> str:
    """Filesystem of ``path`` (the journal's fsyncs land there)."""
    best, kind = "", "unknown"
    try:
        with open("/proc/mounts", encoding="utf-8") as f:
            for line in f:
                _, mount, fstype = line.split()[:3]
                if path.startswith(mount) and len(mount) > len(best):
                    best, kind = mount, fstype
    except OSError:
        pass
    return kind


def _load_peaks(kind: str):
    with open(os.path.join(HERE, "peaks.json"), encoding="utf-8") as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise SystemExit(f"benchmark: no peaks recorded for device_kind "
                         f"{kind!r} in benchmark/peaks.json; add it with "
                         "its source")
    return table[kind]


def _read_spans(path: str) -> list:
    spans = []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            for line in f:
                ev = json.loads(line)
                if ev.get("kind") == "span":
                    spans.append(ev)
    return spans


def _memory_peak(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use"):
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def _metric_lines(run: Run, traced: bool) -> dict:
    out = {}
    if not traced:
        values = dict(run.result["values"], setup_s=run.setup_s)
        for m in run.cell.end_to_end:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        for m in run.cell.per_layer:
            value = run.cell.plugin("layer_metrics", m["name"]).read(run)
            if value is not None:
                out[m["name"]] = {"value": value, "unit": m["unit"]}
    if run.rehearse:
        # a CPU's times, rates and shares are never written under the name
        # of a device metric; counts repeat anywhere and stay
        for m in run.cell.end_to_end + run.cell.per_layer:
            if m["name"] in out and m["source"] in MEASURED_SOURCES:
                out[m["name"]]["value"] = None
    return out


def parse_args(argv=None, extra=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any backend, control flow only")
    ap.add_argument("--out", default=os.path.join(HERE, "out"),
                    help="where detail lines, traces and work files go")
    ap.add_argument("--manifest", default=manifest_mod.MANIFEST_PATH,
                    help="another BENCHMARK.json, its directory the root its "
                         "paths are relative to (how a cell is tried before "
                         "it is added)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the raw profiler trace under --out (it is "
                         "large; by default only its reduction is kept)")
    if extra:
        extra(ap)
    return ap.parse_args(argv)


def prepare(args):
    """Everything before set-up: the cell's files, the compile cache, the
    device gate, the output directory.  Returns ``(run, kind)``; exits with
    code 1 and no result line when there is no device to measure on."""
    cell = manifest_mod.resolve_cell(
        manifest_mod.load_manifest(args.manifest), args.workload,
        root=os.path.dirname(os.path.abspath(args.manifest)),
        rehearse=args.rehearse)
    if args.rehearse and cell.chips > 1:
        flag = f"--xla_force_host_platform_device_count={cell.chips}"
        if "xla_force_host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    # the package first: alone in a directory this fails right here
    from spark_timeseries_tpu.utils import compile_cache

    cache_dir = compile_cache.configure()  # before the first backend use
    import jax
    import jaxlib

    # keep every program, the many sub-second ones too: a later run of this
    # cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and any(d.platform != "tpu" for d in devs):
        raise SystemExit(
            f"benchmark: jax.devices() reports {device}; a cell runs on a "
            "TPU and takes no CPU path (--rehearse is the control-flow "
            "check)")
    if len(devs) < cell.chips:
        raise SystemExit(f"benchmark: {cell.name} needs {cell.chips} chips, "
                         f"jax.devices() has {len(devs)}")
    # the device is held: the cell's set-up, and setup_s, start here
    device_mark = (time.time(), os.times())

    out_dir = os.path.join(os.path.abspath(args.out), cell.name)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(os.path.join(out_dir, "work"))
    run = Run(cell, args, devs[:cell.chips], out_dir, device_mark)
    run.device = device
    run.peaks = None if args.rehearse and device["platform"] != "tpu" \
        else _load_peaks(device["kind"])
    run.compiles.listen()
    run.log("start", seconds=args.seconds, trace=args.trace, device=device,
            jax=jax.__version__, jaxlib=jaxlib.__version__,
            cache_dir=cache_dir,
            cache_entries=len(os.listdir(cache_dir))
            if os.path.isdir(cache_dir) else 0,
            journal_fs=_fs_type(run.work_dir), config=cell.config_name,
            traffic=cell.traffic_name, process_start_s=run.process_start_s,
            process_start_cpu_s=run.process_start_cpu_s,
            process_start_cpu_user_s=run.process_start_cpu_user_s)
    return run, cell.plugin("kinds", cell.traffic["kind"])


def main(argv=None) -> int:
    args = parse_args(argv)
    run, kind = prepare(args)
    device = run.device

    from spark_timeseries_tpu.utils import compile_cache

    from spark_timeseries_tpu import obs

    obs_path = os.path.join(run.out_dir, "obs.jsonl")
    try:
        run.state = kind.setup(run)
        if args.trace:
            obs.enable(obs_path, profile=True)
        run.close_setup()
        run.compiles.open_window()
        run.result = kind.measure(run, run.state)
        run.compiles.close_window()
        if args.trace:
            obs.disable()
        run.compiles_in_window = run.compiles.in_window()
        flags = kind.check(run, run.state, run.result)
    finally:
        if run.tracer and run.tracer.running:
            run.tracer.stop()
        obs.disable()
        if run.state is not None and hasattr(kind, "teardown"):
            kind.teardown(run, run.state)

    flags["no_compile_in_window"] = run.compare(
        "compiles_in_window", run.compiles_in_window, "==", 0)
    device["memory_peak_bytes"] = _memory_peak(run.devices)
    flags["device"] = args.rehearse or (
        device["platform"] == "tpu" and device["memory_peak_bytes"] is not None)

    line = {"correct": all(flags.values()),
            "attempted": run.result["attempted"],
            "failed": run.result["failed"]}
    if args.trace:
        run.spans = _read_spans(obs_path)
        names = {s["name"] for s in run.spans} | run.tracer.own_spans
        data = trace_reduce.load_xplane(
            trace_reduce.find_xplane(run.tracer.log_dir), host_names=names)
        run.trace = trace_reduce.Trace(data)
        if not args.keep_trace:
            shutil.rmtree(run.tracer.log_dir, ignore_errors=True)
        if run.trace.devices:
            device["busy_s"] = run.trace.busy_s()
            device["window_s"] = run.trace.window_s
            if not args.rehearse:
                line["breakdown"] = run.trace.breakdown()
    line["metrics"] = _metric_lines(run, bool(args.trace))
    line["device"] = device
    run.log("done", flags=flags, setup_s=run.setup_s,
            setup_end_at_s=run.setup_end_at_s,
            process_start_s=run.process_start_s,
            process_start_cpu_s=run.process_start_cpu_s,
            compiles_in_window=run.compiles_in_window,
            compiles_total=len(run.compiles.events),
            program_cache=compile_cache.program_cache_stats(),
            window_wall_s=run.result["window_wall_s"],
            values=run.result["values"])
    shutil.rmtree(run.work_dir, ignore_errors=True)
    if args.rehearse:
        line["rehearsal"] = True
    # every number compared beside its limit: last on stderr, last in the line
    line["checks"] = run.compared
    for name, c in run.compared.items():
        print(f"check {name}: {c['value']!r} {c['rule']} {c['limit']!r}"
              f"{'' if c['ok'] else '  <-- NOT MET'}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
