"""``chip_smoke.py`` and the bench's device honesty (ISSUE 22).

Tier-1 pins that nothing on the chip path succeeds without a chip: the
smoke and a full-size ``bench.py`` refuse the CPU, a parity-gate trip
fails the bench, an unknown ``device_kind`` has no HBM peak.  Marked
``slow``: the smoke's ``--rehearse`` control flow end to end on forced CPU
devices, and an AOT compile of every Pallas kernel family for a
compile-only v5e topology — run that one before editing
``ops/pallas_kernels.py``, so a Mosaic refusal shows up in the sandbox.
"""

import json
import os
import re
import subprocess
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)  # bench.py and chip_smoke.py live at the root


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=1500)


def test_smoke_refuses_cpu(tmp_path):
    r = _run(["chip_smoke.py", "--out", str(tmp_path)])
    assert r.returncode != 0
    assert "device gate FAILED" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout == ""  # no leg ran, no result line, nothing compiled


def test_bench_refuses_cpu_without_quick():
    r = _run(["bench.py"])
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "'cpu'" in r.stderr
    assert r.stdout == ""  # nothing measured


def test_bench_parity_failure_is_fatal(monkeypatch):
    import bench

    def trip(jnp_, on_tpu):
        raise RuntimeError("forced parity trip")

    monkeypatch.setattr(bench, "check_backend_parity", trip)
    monkeypatch.setattr(sys, "argv", ["bench.py", "--quick"])
    with pytest.raises(RuntimeError, match="forced parity trip"):
        bench.main()


def test_bench_unknown_device_kind_is_an_error():
    import bench

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert bench._hbm_peak_gbps(v5e) == 819.0
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(RuntimeError, match="TPU v99"):
        bench._hbm_peak_gbps(unknown)


@pytest.mark.slow
def test_smoke_rehearsal_end_to_end(tmp_path):
    r = _run(["chip_smoke.py", "--rehearse", "--chips", "4",
              "--out", str(tmp_path)],
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert r.returncode == 0, r.stderr[-4000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()]
    assert all(ln["rehearsal"] is True for ln in lines)
    assert lines[-1]["ok"] is True
    legs = [ln["leg"] for ln in lines[:-1]]
    assert legs == ["device_gate", "backend_gate", "parity", "panel", "walk",
                    "resume", "forecast", "server", "sharded_walk",
                    "time_sharded", "done"]
    assert all(ln["claim"] is None for ln in lines[:-1])


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a compile-only ``v5e:2x2`` topology as a sharding — no
    chip needed, none taken; the tests that ask for it are skipped where
    libtpu cannot describe one.  Described HERE and in no other test file:
    a file is what an xdist worker takes, and the library is one process's
    at a time."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # building the topology loads libtpu, which by default takes the
    # machine's libtpu lockfile for the life of the process: on a host WITH
    # a chip no other process could then open it.  With this variable the
    # load takes no lock (checked on a v5e host, PR 22: a second process
    # ran on the chip while this one held a compiled kernel).
    with pytest.MonkeyPatch.context() as patch:
        patch.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no libtpu: nothing to compile for
            reason = f"compile-only v5e topology unavailable: {e!r}"
            print(reason)
            pytest.skip(reason)
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


_SHAPE = re.compile(r"\b[a-z]+\d+\[([\d,]*)\]\{([\d,]*)[^}]*\}")


def _shapes(hlo_line):
    """Every array shape a line of compiled text names, as ``(text, dims,
    minor axis)``: ``f32[33,131072]{1,0:T(8,128)}`` -> ``(33, 131072), 1``."""
    return [(found.group(0),
             tuple(int(n) for n in found.group(1).split(",") if n),
             int(found.group(2).split(",")[0]) if found.group(2) else None)
            for found in _SHAPE.finditer(hlo_line)]


def _in_loop_lines(hlo_text):
    """The instructions of every computation a ``while`` of the compiled
    text runs, body and condition and what they call."""
    comps, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                        line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    called = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
    todo = [c for lines in comps.values() for ln in lines if " while(" in ln
            for c in re.findall(r"(?:body|condition)=%?([\w.\-]+)", ln)]
    seen = set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        todo += [c for ln in comps[comp] for c in called.findall(ln)]
    assert seen, "the program has a loop"
    return [ln for comp in seen for ln in comps[comp]]


@pytest.mark.parametrize("d", [33, 3])
def test_lockstep_state_keeps_its_rows_on_the_lanes(v5e_chip, d):
    """PERF.md §6, PR 50, as a test: ``optim._lockstep`` with the line
    search's tail over a quadratic at ``[131072, d]``, compiled for the v5e.
    Inside its loops no array with 131,072 rows has ``d`` as its minor axis
    (at d = 33 that is 33 padded to 128 lanes and every dot over ``d`` a
    reduction across them: 0.36 of the harmonic cell's window before PR
    50), and no history-sized ``copy`` / ``transpose`` runs there.  The
    shape the state is WRITTEN in does not decide it — with ``[m, d, B]``
    written and unpinned the compiler chose the parent's layout again — so
    the day somebody routes the state back through ``[B, m, d]``, or takes
    ``optim._pin`` away, this fails on a CPU."""
    from spark_timeseries_tpu.utils import optim

    rows, m, cap = 131072, 8, 16384

    def run(x0):
        # one ill-scaled bowl for every row: the objective brings no
        # ``[B, d]`` data of its own into the loop, so every wide array
        # there is the optimizer's
        scales = jnp.logspace(-1.0, 1.0, d, dtype=jnp.float32)
        fb = lambda x: jnp.sum(scales * x * x - x, axis=-1)  # noqa: E731
        return optim._lockstep(
            fb, x0, cap, False, max_iters=60, history=m, tol=1e-4, ftol=None,
            max_linesearch=20, c1=1e-4, tail_fun=lambda idxc: fb)

    arg = jax.ShapeDtypeStruct((rows, d), jnp.float32, sharding=v5e_chip)
    with jax.enable_x64(False):  # as on the chip
        text = jax.jit(run).lower(arg).compile().as_text()
    lines = _in_loop_lines(text)
    wide = {text: (dims, minor) for ln in lines
            for text, dims, minor in _shapes(ln)
            if rows in dims and len(dims) > 1}
    assert any(dims == (m, d, rows) for dims, _ in wide.values()), (
        "the history is in the loop")
    on_lanes = sorted(text for text, (dims, minor) in wide.items()
                      if dims[minor] == d)
    assert not on_lanes, f"d = {d} is the minor axis of {on_lanes}"
    moved = [ln.strip()[:200] for ln in lines
             if re.search(r"= \S+ (?:copy|transpose)\(", ln)
             and any(np.prod(dims) >= m * d * rows
                     for _, dims, _ in _shapes(ln.split("(")[0]))]
    assert not moved, f"history-sized relayouts in the loop: {moved}"


@pytest.mark.slow
def test_pallas_kernels_compile_for_v5e(v5e_chip):
    """Every kernel family lowers through Mosaic and compiles for a
    compile-only ``v5e:2x2`` topology — no chip needed, none taken."""
    from jax._src import xla_bridge

    from spark_timeseries_tpu.models import arima, ewma, garch
    from spark_timeseries_tpu.models import holtwinters as hw
    from spark_timeseries_tpu.ops import pallas_kernels as pk

    sharding = v5e_chip

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    B, T, TOL = 1024, 200, 1e-4
    o111, o212 = (1, 1, 1), (2, 1, 2)
    programs = {}
    for mode in ("dense", "general"):
        programs[f"arima111 stage1 {mode}"] = (
            arima._fit_stage1_program(o111, True, "pallas", 60, TOL, False,
                                      mode), [arg(4096, T)])
        programs[f"arima111 inline {mode}"] = (
            arima._fit_program(o111, True, "css-lbfgs", "pallas", 60, TOL,
                               False, mode), [arg(B, T)])
        programs[f"garch {mode}"] = (
            garch._fit_program(60, TOL, "pallas", mode), [arg(B, T)])
    programs["arima212 inline"] = (
        arima._fit_program(o212, True, "css-lbfgs", "pallas", 60, TOL,
                           False, "dense"), [arg(B, T)])
    programs["arima111 T=2500 (multi-chunk)"] = (
        arima._fit_program(o111, True, "css-lbfgs", "pallas", 60, TOL,
                           False, "dense"), [arg(B, 2500)])
    # the seasonal family: the CSS kernels over the lag sets {1, 24, 25},
    # one chunk at the benchmark's length and two past it
    airline = ((0, 1, 1), True, "pallas", 60, TOL, False, "dense", False,
               (0, 1, 1, 24))
    programs["sarima airline24 stage1 T=960"] = (
        arima._fit_stage1_program(*airline), [arg(4096, 960)])
    programs["sarima airline24 stage1 T=2500 (multi-chunk)"] = (
        arima._fit_stage1_program(*airline), [arg(4096, 2500)])
    programs["sarima (1,0,1)(1,0,1)_4 inline general"] = (
        arima._fit_program((1, 0, 1), True, "css-lbfgs", "pallas", 60, TOL,
                           False, "general", False, True, (1, 0, 1, 4)),
        [arg(B, T)])
    programs["arima111 forecast (tail kernel)"] = (
        arima._forecast_program(o111, 24, True, "pallas", "dense"),
        [arg(B, 3), arg(B, T)])
    programs["argarch"] = (
        garch._fit_argarch_program(60, TOL, "pallas", True, "dense"),
        [arg(B, T)])
    programs["ewma"] = (ewma._fit_program(60, TOL, "pallas", "dense"),
                        [arg(B, T)])
    programs["hw additive m=24"] = (
        hw._fit_program(24, False, 60, TOL, "pallas", "dense"),
        [arg(B, 192)])
    programs["hw multiplicative m=24"] = (
        hw._fit_program(24, True, 60, TOL, "pallas", "dense", False, True, 3),
        [arg(B, 192)])
    programs["hw additive m=168 T=2016"] = (
        hw._fit_program(168, False, 60, TOL, "pallas", "dense"),
        [arg(B, 2016)])
    programs["fill_linear"] = (jax.jit(pk.fill_linear), [arg(B, T)])
    programs["fill_linear_chain"] = (jax.jit(pk.fill_linear_chain),
                                     [arg(B, T)])
    programs["batch_autocorr(10)"] = (
        jax.jit(lambda x: pk.batch_autocorr(x, 10)), [arg(B, T)])

    # x64 off, as on the chip (chip_smoke.py, bench.py): under the suite's
    # jax_enable_x64 the Mosaic lowering of the kernels' int32 loop-index
    # convert recurses without end in jax 0.9.0
    with jax.enable_x64(False):
        # the lazy pair the walk runs: stage 2 takes what stage 1 hands it
        # (the folded straggler columns), so its shapes come from stage 1
        hw_s1 = hw._fit_stage1_program(24, False, 60, TOL, "pallas", "dense",
                                       1)
        programs["hw additive stage1"] = (hw_s1, [arg(4096, 192)])
        programs["hw additive stage2"] = (
            hw._fit_stage2_program(24, False, 60, TOL, "pallas"),
            [jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sharding),
                jax.eval_shape(hw_s1, arg(4096, 192))[1]["starts"][0])])
        garch_s1 = garch._fit_stage1_program(60, TOL, "pallas", "dense")
        programs["garch stage1"] = (garch_s1, [arg(4096, T)])
        garch_aux = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            jax.eval_shape(garch_s1, arg(4096, T))[1])
        programs["garch stage2"] = (
            garch._fit_stage2_program(60, TOL, "pallas"),
            [garch_aux["starts"][0], garch_aux["fin"]])
        # the fused order search: nine orders over one folded panel, the
        # order-grouped blocks of stage 1 (T = 1000: the cell's VMEM) and
        # stage 2's one order over the gathered cells
        grid9 = (tuple(((p, 1, q), None) for p in range(3)
                       for q in range(3)), True, "pallas", 60, TOL)
        grid_s1 = arima._grid_stage1_program(*grid9, "dense")
        programs["arima grid9 stage1"] = (grid_s1, [arg(4096, 1000)])
        grid_aux = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            jax.eval_shape(grid_s1, arg(4096, 1000))[1])
        programs["arima grid9 stage2"] = (
            arima._grid_stage2_program(*grid9),
            [grid_aux["starts"][0], grid_aux["fin"]])
        # the shared-design regression: both design products INSIDE the CSS
        # kernel calls since ISSUE 51 (MXU dots at HIGHEST over
        # sublane-strided slices of the blocks); past one chunk the
        # design's block moves with the time chunk
        from spark_timeseries_tpu.models import regression_arima as ra

        design = [arg(960, 31), arg(31, 960),
                  arg(31, 1 + ra._UNIT_LAGS)]
        shared = ((1, 0, 1), "pallas", 60, TOL)
        shared_s1 = ra._shared_stage1_program(*shared, "dense")
        programs["harmonic arma stage1 T=960 k=31"] = (
            shared_s1, [arg(4096, 960), *design])
        shared_aux = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding),
            jax.eval_shape(shared_s1, arg(4096, 960), *design)[1])
        programs["harmonic arma stage2"] = (
            ra._shared_stage2_program(*shared),
            [shared_aux["starts"][0], shared_aux["fin"]])
        programs["harmonic arma inline general"] = (
            ra._shared_fit_program(*shared, "general", True),
            [arg(B, 960), *design])
        programs["harmonic arma inline T=2500 (multi-chunk)"] = (
            ra._shared_fit_program(*shared, "dense", True),
            [arg(B, 2500), arg(2500, 31), arg(31, 2500), design[2]])
        programs["arima grid9 inline general T=2500 (multi-chunk)"] = (
            arima._grid_fit_program(*grid9, "general"), [arg(256, 2500)])
        for name, (program, args) in programs.items():
            try:
                program.lower(*args).compile()
            except Exception as e:  # noqa: BLE001 - name the family, then fail
                pytest.fail(f"{name} does not compile for v5e: "
                            f"{type(e).__name__}: {str(e)[:2000]}")
    # a compile-only topology is not a client: the chip stays free
    assert "tpu" not in xla_bridge._backends
