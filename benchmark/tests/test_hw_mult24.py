"""``hw-mult24`` and its cell ``hw-mult24.walk-dense``: the manifest
resolves them, its per-layer entries are pinned BY NAME, the plain reference
computes the model's objective, the generating process draws what the
configuration says, the three readers read what the program writes and
nothing where it writes nothing, and the cell runs end to end at tiny sizes
on the CPU.  (The reference and process cases stand here and not in
``test_reference.py`` / ``test_generators.py``: a ``model_config`` PR adds
files under ``benchmark/`` and edits none.  The fit's spans are held by
``tests/test_hw_mult_config.py`` in tier 1.)"""

import jax
import numpy as np
import pytest

from benchmark import generators as g
from benchmark import manifest as mf
from benchmark.processes import seasonal_multiplicative
from benchmark.reference import check, holtwinters_multiplicative
from benchmark.tests.test_rehearse import check_line, rehearse

CELL = "hw-mult24.walk-dense"
OWN = {"merge_switched_row_share": "share",
       "stage2_dispatches_per_chunk": "dispatches",
       "merge_exposed_s_per_chunk": "s"}


@pytest.fixture(scope="module")
def cell():
    return mf.resolve_cell(mf.load_manifest(), CELL)


def test_manifest_resolves_the_cell(cell):
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, "hw-mult24", "walk-dense")
    assert cell.traffic["kind"] == "walk" and not cell.traffic["sharded"]
    # the model type and the kernels by name; n_starts (3), max_iters (60)
    # and tol (1e-4) stay the library's
    assert cfg["model"] == {
        "fit": "spark_timeseries_tpu.models.holtwinters:fit",
        "server_name": "holtwinters", "kwargs": {
            "period": 24, "model_type": "multiplicative",
            "backend": "pallas"}}
    assert (cfg["n_time"], cfg["chunk_rows"], cfg["dtype"]) \
        == (960, 131072, "float32")
    # nothing but rows may be cut, and rows only to a power of two
    assert cfg["rows"] in (262144, 524288, 1048576)
    assert cfg["reduced"] == ([] if cfg["rows"] == 1048576 else ["rows"])
    entry = {c["name"]: c for c in cell.manifest["configs"]}["hw-mult24"]
    assert entry["reduced"] == cfg["reduced"] and len(entry["source"]) <= 200
    assert cfg["reference"]["module"] == "holtwinters_multiplicative"
    assert cfg["reference"]["loglik_gap_max"] <= 3.9
    assert cfg["reference"]["min_share"] >= 0.9
    assert [m["name"] for m in cell.end_to_end] \
        == ["series_per_s_chip", "setup_s"]
    # every shared reader of the walk cells, the Holt-Winters kernel's two
    # (the replay's roofline, its five panels) and this cell's own three
    layers = {m["name"]: m for m in cell.per_layer}
    other = {m["name"] for m in mf.resolve_cell(
        mf.load_manifest(), "hw-add24.walk-dense").per_layer}
    assert {"hw_sse_roofline", "hw_adjoint_panels"} <= other
    assert set(layers) == other | set(OWN)
    for name, unit in OWN.items():
        m = layers[name]
        assert m["workloads"] == [CELL] and m["layer"] == "optimizer"
        assert (m["unit"], m["moves"], m["source"]) \
            == (unit, "series_per_s_chip", "program_span")
    tiny = mf.resolve_cell(mf.load_manifest(), CELL, rehearse=True).config
    assert tiny["rows"] % tiny["chunk_rows"] == 0
    assert tiny["n_time"] >= 2 * 24
    assert tiny["model"]["kwargs"] == {  # no chip here: "auto" -> scan
        "period": 24, "model_type": "multiplicative"}


def panel(cell, seed, rows=512, n_time=128):
    return np.asarray(g.build_panel(
        seasonal_multiplicative.rows, cell.config["process"], {}, seed,
        jax.devices()[:1], rows, n_time, 128))


@pytest.mark.parametrize("params", [[0.3, 0.1, 0.2], [0.05, 0.0, 0.9],
                                    [0.9, 0.5, 0.02], [0.12, 0.05, 0.6]])
def test_reference_is_the_models_objective(cell, params):
    """``holtwinters.sse(..., multiplicative=True)`` at arbitrary
    parameters, on positive rows of the configuration's process."""
    from spark_timeseries_tpu.models import holtwinters

    kw = cell.config["model"]["kwargs"]
    for y in panel(cell, 11, 128, 240)[:4]:
        ss, n_eff = holtwinters_multiplicative.objective(params, y, kw)
        sse = float(holtwinters.sse(jax.numpy.asarray(params, "float32"),
                                    jax.numpy.asarray(y), 24, True))
        assert n_eff == len(y) - 24
        assert sse == pytest.approx(ss, rel=1e-4)


def test_optimum_is_the_best_of_the_three_starts(cell):
    from spark_timeseries_tpu.models import holtwinters

    assert holtwinters_multiplicative.STARTS == holtwinters._MULTISTART_NATS
    kw = cell.config["model"]["kwargs"]
    rows = panel(cell, 11, 128, 240)[:8]
    best = np.array([holtwinters_multiplicative.optimum(y, kw)
                     for y in rows])
    assert np.all(np.abs(check.loglik_gaps(
        holtwinters_multiplicative, kw, rows, best)) < 1e-9)
    for start in holtwinters_multiplicative.STARTS:
        assert np.all(check.loglik_gaps(
            holtwinters_multiplicative, kw, rows,
            np.tile(start, (len(rows), 1))) >= 0)
    # the timed path broken underneath is refused: parameters moved by 0.2
    limit = cell.config["reference"]["loglik_gap_max"]
    moved = check.loglik_gaps(holtwinters_multiplicative, kw, rows,
                              np.clip(best + 0.2, 0, 1))
    assert np.mean(moved <= limit) < 0.5


def test_panel_is_a_function_of_the_seed(cell):
    a, b = panel(cell, 7), panel(cell, 7)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert np.isfinite(a).all() and a.min() > 0
    assert not np.array_equal(a[:128], a[128:256])
    c = panel(cell, 8)  # the same chunks of the same rows, laid otherwise
    order = lambda y: y[np.lexsort(y.T[::-1])]  # noqa: E731
    chunks = lambda y: sorted(order(y[i:i + 128]).tobytes()  # noqa: E731
                              for i in range(0, 512, 128))
    assert not np.array_equal(a, c) and chunks(a) == chunks(c)


def test_process_is_positive_and_one_draw_a_row(cell):
    p = cell.config["process"]
    y = panel(cell, 3, rows=512, n_time=960)
    assert y.shape == (512, 960) and np.isfinite(y).all() and y.min() > 0
    par = np.asarray(seasonal_multiplicative.draw_params(
        jax.random.key(1), 4096, p), np.float64)
    assert par.shape == (4096, len(seasonal_multiplicative.PARAMS))
    for name in ("level", "drift", "level_noise", "amplitude", "amplitude2",
                 "noise"):
        v, (lo, hi) = par[:, seasonal_multiplicative.PARAMS.index(name)], \
            p[name]
        assert lo <= v.min() and v.max() <= hi * (1 + 1e-6)
        assert np.unique(v).size > 4000  # one draw a row, not one point
    # the day's profile averages 1, so the level is the day's mean: rows
    # start anywhere in the level's decade and swing with it
    day0 = y[:, :24].mean(axis=1)
    assert day0.min() < 15 and day0.max() > 70
    swing = y[:, :24].max(axis=1) - y[:, :24].min(axis=1)
    assert np.corrcoef(np.log(swing), np.log(day0))[0, 1] > 0.7


class _Run:
    """What a reader is handed, with the traced window wide open."""

    def __init__(self, spans, trace=None):
        self.spans, self.trace = spans, trace


def _span(name, **attrs):
    return {"kind": "span", "name": name, "attrs": attrs}


@pytest.fixture()
def open_window(monkeypatch):
    from benchmark import span_idle

    monkeypatch.setattr(span_idle, "window_spans", lambda run, name: [
        s for s in run.spans if s["name"] == name])


def test_merge_switched_reader(cell, open_window):
    reader = cell.plugin("layer_metrics", "merge_switched_row_share")
    run = _Run([_span("fit.readback", rows=131072, merge_switched=86630),
                _span("fit.readback", rows=131072, merge_switched=0)])
    assert reader.read(run) == 86630 / 262144
    # one start a row, or the parent: no such attribute; an untraced run
    assert reader.read(_Run([_span("fit.readback", rows=131072)])) is None
    assert reader.read(_Run([])) is None


def test_stage2_dispatches_reader(cell, open_window):
    reader = cell.plugin("layer_metrics", "stage2_dispatches_per_chunk")
    chunk = [_span("fit.stage1", rows=131072, undone=9,
                   undone_by_start=[4, 5, 0], iters_by_start=[13, 16, 14]),
             _span("fit.stage2", rows=16384, start=0),
             _span("fit.stage2", rows=16384, start=1)]
    quiet = [_span("fit.stage1", rows=131072, undone=0,
                   undone_by_start=[0, 0, 0], iters_by_start=[9, 9, 9])]
    assert reader.read(_Run(chunk + quiet)) == 1.0
    assert reader.read(_Run(quiet)) == 0.0  # ran, and dispatched nothing
    # the parent's stage spans do not report per start
    parent = [_span("fit.stage1", rows=131072, undone=9),
              _span("fit.stage2", rows=16384)]
    assert reader.read(_Run(parent)) is None
    assert reader.read(_Run([])) is None


def test_merge_exposed_reader(cell, monkeypatch):
    from benchmark import span_idle

    reader = cell.plugin("layer_metrics", "merge_exposed_s_per_chunk")
    asked = []
    monkeypatch.setattr(span_idle, "per_chunk", lambda trace, names: (
        asked.append((trace, names)), 0.0015)[1])
    assert reader.read(_Run([], trace="the trace")) == 0.0015
    assert asked == [("the trace", ("fit.merge",))]

    class NoMerge:  # a trace of a program without the span: nothing
        data = {"host": [{"spans": [("chunk", 0, 10)]}]}
        devices = [{"busy": [(0, 5)]}]
        window = (0, 10)

        def host_spans(self, name):
            return [1] if name == "chunk" else []

    monkeypatch.undo()
    assert reader.read(_Run([], trace=NoMerge())) is None
    assert reader.read(_Run([], trace=None)) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace, tmp_path):
    line = rehearse(CELL, trace, tmp_path)
    check_line(line, cell, trace)
    assert line["correct"] and line["failed"] == 0
    if trace:
        # on the CPU the fit takes the scan: no stage span, no merge, no
        # kernel event — the cell's own readers find nothing to read
        assert not set(OWN) & set(line["metrics"])
        assert "hw_sse_roofline" not in line["metrics"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0
