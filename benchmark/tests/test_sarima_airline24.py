"""``sarima-airline24`` and its cell ``sarima-airline24.walk-dense``: the
manifest resolves them, the plain reference computes the model's objective,
the generating process draws what the configuration says, the two readers
read what the program writes and nothing where it writes nothing, and the
cell runs end to end at tiny sizes on the CPU."""

import jax
import numpy as np
import pytest

from benchmark import generators as g
from benchmark import manifest as mf
from benchmark.processes import seasonal_airline
from benchmark.reference import check, sarima_css
from benchmark.tests.test_rehearse import check_line, rehearse

CELL = "sarima-airline24.walk-dense"
KW = {"order": [0, 1, 1], "seasonal": [0, 1, 1, 24]}


@pytest.fixture(scope="module")
def cell():
    return mf.resolve_cell(mf.load_manifest(), CELL)


@pytest.fixture(scope="module")
def rows(cell):
    return np.asarray(g.build_panel(
        seasonal_airline.rows, cell.config["process"], {}, 11,
        jax.devices()[:1], 64, 400, 64))


def test_manifest_resolves_the_cell(cell):
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, "sarima-airline24", "walk-dense")
    assert cell.traffic["kind"] == "walk" and not cell.traffic["sharded"]
    # the orders and the kernels by name ("auto"'s choice on a TPU: a
    # program without seasonal kernels then refuses at once and does not
    # walk the panel on its scan); everything else at library defaults
    assert cfg["model"] == {
        "fit": "spark_timeseries_tpu.models.arima:fit", "server_name":
        "arima", "kwargs": {**KW, "backend": "pallas"}}
    assert (cfg["rows"], cfg["n_time"], cfg["chunk_rows"], cfg["dtype"]) \
        == (1048576, 960, 131072, "float32")
    assert cfg["rows"] * cfg["n_time"] * 4 >= 4e9  # a quarter of the HBM
    assert cfg["reduced"] == [] and len(cfg["source"]) <= 200
    assert cfg["objective"]["time_steps"] == 960 - 25
    assert [m["name"] for m in cell.end_to_end] \
        == ["series_per_s_chip", "setup_s"]
    layers = {m["name"] for m in cell.per_layer}
    other = {m["name"] for m in mf.resolve_cell(
        mf.load_manifest(), "arima111.walk-dense").per_layer}
    # every shared reader of the walk cells, and this kernel's own two
    assert layers == other - {"css_neg_loglik_roofline"} | {
        "css_seasonal_neg_loglik_roofline", "css_lag_terms_per_step"}
    for m in mf.load_manifest()["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["layer"] == "objective_kernel"
    tiny = mf.resolve_cell(mf.load_manifest(), CELL, rehearse=True).config
    assert (tiny["rows"], tiny["n_time"], tiny["chunk_rows"]) \
        == (1024, 128, 128)
    assert tiny["model"]["kwargs"] == KW  # no chip here: "auto" -> scan


def test_reference_is_the_models_objective(rows):
    """Same sum of squares as the system's float64 scan over the expanded
    polynomial, on arbitrary parameters, dense rows and ragged ones; the
    moving-average polynomial has 26 coefficients and 3 free ones."""
    from spark_timeseries_tpu.models import arima

    assert np.flatnonzero(sarima_css._poly([-0.4], [-0.6], 24, 1.0)).tolist() \
        == [0, 1, 24, 25]
    with jax.enable_x64(True):
        for y, params in zip(rows[:4], ([0.05, -0.4, -0.6], [0.0, 0.3, 0.2],
                                        [-0.1, -0.7, 0.5], [0.2, 0.0, -0.8])):
            y = y.copy()
            y[:int(10 * abs(params[1]))] = np.nan  # some rows start late
            ss, n_eff = sarima_css.objective(params, y, KW)
            x = y[np.isfinite(y)].astype(np.float64)
            yd = np.diff(x)
            yd = yd[24:] - yd[:-24]
            nll = float(arima.sarima_neg_loglik(
                jax.numpy.asarray(params, jax.numpy.float64),
                jax.numpy.asarray(yd), (0, 1, 1), (0, 1, 1, 24), True))
            assert n_eff == len(yd)
            ref = 0.5 * n_eff * (np.log(2 * np.pi * ss / n_eff) + 1.0)
            assert nll == pytest.approx(ref, rel=1e-10)
    # an AR side too: (1,0,1)(1,0,1)_4 conditions out p + P s = 5 errors
    kw = {"order": [1, 0, 1], "seasonal": [1, 0, 1, 4]}
    par = np.array([0.1, 0.5, 0.3, 0.4, -0.2])
    ss, n_eff = sarima_css.objective(par, rows[5], kw)
    assert n_eff == 400 - 5
    with jax.enable_x64(True):
        e = np.asarray(arima._sarima_css_errors(
            jax.numpy.asarray(par), jax.numpy.asarray(rows[5], "float64"),
            (1, 0, 1), (1, 0, 1, 4), True))
    assert float(e @ e) == pytest.approx(ss, rel=1e-10)


def test_optimum_beats_the_truth_and_system_fits_pass(rows):
    from spark_timeseries_tpu.models import arima

    best = np.array([sarima_css.optimum(y, KW) for y in rows[:16]])
    assert np.all(np.abs(check.loglik_gaps(sarima_css, KW, rows[:16], best))
                  < 1e-9)
    centre = np.tile([0.0, -0.45, -0.6], (16, 1))
    assert np.all(check.loglik_gaps(sarima_css, KW, rows[:16], centre) > 0)
    res = arima.fit(rows, (0, 1, 1), seasonal=(0, 1, 1, 24))
    gaps = check.loglik_gaps(sarima_css, KW, rows[:16],
                             np.asarray(res.params)[:16])
    assert gaps.max() < 0.1
    # the timed path broken underneath is refused (coefficients moved by
    # 0.2, as benchmark/tests/broken_fits.py moves arima111's)
    moved = check.loglik_gaps(sarima_css, KW, rows[:16],
                              np.asarray(res.params)[:16] + 0.2)
    assert np.mean(moved <= 0.1) < 0.1


def test_process_draws_what_the_configuration_says(cell):
    p = cell.config["process"]
    par = np.asarray(seasonal_airline.draw_params(jax.random.key(1), 8192,
                                                  p), np.float64)
    for values, (lo, hi) in ((par[:, 0], p["theta"]),
                             (par[:, 1], p["seasonal_theta"])):
        assert lo <= values.min() and values.max() <= hi
        assert values.min() < lo + 0.01 * (hi - lo)  # the whole range
        assert values.max() > hi - 0.01 * (hi - lo)
    rec = {r["name"]: r["value"] for r in cell.config["recovery"]}
    assert abs(np.median(par[:, 0]) - rec["theta"]) < 0.01
    assert abs(np.median(par[:, 1]) - rec["seasonal_theta"]) < 0.01
    assert np.abs(par).max() < 1.0  # every row invertible


def test_rows_are_the_process_the_model_describes(cell):
    """Finite f32 rows whose double difference is the drawn moving
    average: its variance ``(1 + th^2)(1 + TH^2)`` and its autocorrelations
    at lags 1, 24 and 25 (``th / (1 + th^2)``, ``TH / (1 + TH^2)`` and their
    product; nothing at lag 2), in the mean over rows."""
    p, n_rows, n_time = cell.config["process"], 2048, 960
    key = jax.random.key(7)
    y = np.asarray(jax.jit(
        lambda k: seasonal_airline.rows(k, n_rows, n_time, p))(key))
    assert y.shape == (n_rows, n_time) and y.dtype == np.float32
    assert np.isfinite(y).all()
    k_par, _ = jax.random.split(key)
    th, TH = np.asarray(seasonal_airline.draw_params(k_par, n_rows, p),
                        np.float64).T
    w = np.diff(y.astype(np.float64), axis=1)
    w = w[:, 24:] - w[:, :-24]
    assert abs(w.mean()) < 0.01
    assert abs(np.mean(w.var(axis=1) / ((1 + th**2) * (1 + TH**2))) - 1) \
        < 0.02
    wc = w - w.mean(axis=1, keepdims=True)

    def rho(k):  # n - k products over n squares: rescaled to unbiased
        return (np.sum(wc[:, k:] * wc[:, :-k], axis=1)
                / np.sum(wc * wc, axis=1) * w.shape[1] / (w.shape[1] - k))

    r1, r24 = th / (1 + th**2), TH / (1 + TH**2)
    assert abs(np.mean(rho(1) - r1)) < 0.01
    assert abs(np.mean(rho(24) - r24)) < 0.01
    assert abs(np.mean(rho(25) - r1 * r24)) < 0.01
    assert abs(np.mean(rho(2))) < 0.01
    # a row has left zero behind by its first observation (the burn-in)
    assert np.median(np.abs(y[:, 0])) > 1.0
    # through the general generator: a function of the seed, blocks differ
    a = np.asarray(g.build_panel(seasonal_airline.rows, p, {}, 7,
                                 jax.devices()[:1], 512, 128, 128))
    b = np.asarray(g.build_panel(seasonal_airline.rows, p, {}, 7,
                                 jax.devices()[:1], 512, 128, 128))
    assert np.array_equal(a, b) and not np.array_equal(a[:128], a[128:256])


class _Run:
    """What a reader is handed, with the traced window wide open."""

    def __init__(self, spans, trace=None, peaks=None, config=None):
        self.spans, self.trace, self.peaks = spans, trace, peaks
        self.cell = type("Cell", (), {"config": config or {}})


def _stage1(**attrs):
    return {"kind": "span", "name": "fit.stage1", "attrs": attrs}


def test_lag_terms_reader(cell, monkeypatch):
    from benchmark import span_idle

    reader = cell.plugin("layer_metrics", "css_lag_terms_per_step")
    monkeypatch.setattr(span_idle, "window_spans", lambda run, name: [
        s for s in run.spans if s["name"] == name])
    with_attr = _Run([_stage1(rows=131072, iters=7, lag_terms=3, lag_span=25)
                      for _ in range(8)])
    assert reader.read(with_attr) == 3
    # the parent's spans carry no such attribute; an untraced run has none
    assert reader.read(_Run([_stage1(rows=131072, iters=7)])) is None
    assert reader.read(_Run([])) is None


def test_seasonal_roofline_reader(cell):
    reader = cell.plugin("layer_metrics", "css_seasonal_neg_loglik_roofline")
    peaks = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}

    class Trace:
        def __init__(self, got):
            self.got = got

        def scope(self, name):
            assert name == "pallas.css_seasonal_neg_loglik"
            return self.got

    cfg = cell.config
    # 10 events that each read the folded chunk once, at the HBM's pace
    nbytes = 10 * 131072 * 936 * 4
    got = {"events": 10, "seconds": nbytes / 819e9, "bytes": nbytes}
    assert reader.read(_Run([], Trace(got), peaks, cfg)) \
        == pytest.approx(100.0)
    got = {"events": 10, "seconds": 2 * nbytes / 819e9, "bytes": 0}
    assert reader.read(_Run([], Trace(got), peaks, cfg)) \
        == pytest.approx(50.0)  # no shapes in the trace: one pass an event
    # a trace with no seasonal event (the parent's), no trace, no peaks
    none = {"events": 0, "seconds": 0.0, "bytes": 0}
    assert reader.read(_Run([], Trace(none), peaks, cfg)) is None
    assert reader.read(_Run([], None, peaks, cfg)) is None
    assert reader.read(_Run([], Trace(got), None, cfg)) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace, tmp_path):
    line = rehearse(CELL, trace, tmp_path)
    check_line(line, cell, trace)
    assert line["correct"] and line["failed"] == 0
    if trace:
        # on the CPU the fit takes the scan: no stage span, no kernel
        # event, no chip's peaks — both readers find nothing to read
        assert "css_seasonal_neg_loglik_roofline" not in line["metrics"]
        assert "css_lag_terms_per_step" not in line["metrics"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0
