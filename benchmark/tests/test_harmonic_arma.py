"""``harmonic-arma24x168`` and its cell ``harmonic-arma24x168.walk-dense``:
the manifest resolves them, its per-layer entries are pinned BY NAME, the
plain reference computes the model's objective and profiles its optimum, the
generating process draws what the configuration says, the two readers read
what the program writes and nothing where it writes nothing, the cell runs
end to end at tiny sizes on the CPU, and the nearest precision below — the
design and the coefficients held in bfloat16 — fails the cell's limit.
(The reference and process cases stand here and not in ``test_reference.py``
/ ``test_generators.py``: a ``model_config`` PR adds files under
``benchmark/`` and edits none.  The fit's spans and programs are held by
``tests/test_regression_arma.py`` in tier 1.)"""

import jax
import numpy as np
import pytest
from scipy.optimize import minimize

from benchmark import generators as g
from benchmark import manifest as mf
from benchmark.processes import harmonic_arma
from benchmark.reference import check
from benchmark.reference import regression_arma_css as ref
from benchmark.tests.test_rehearse import check_line, rehearse

CELL = "harmonic-arma24x168.walk-dense"
OWN = {"xreg_panel_moves": "panels", "design_exposed_s_per_chunk": "s"}


@pytest.fixture(scope="module")
def cell():
    return mf.resolve_cell(mf.load_manifest(), CELL)


def test_manifest_resolves_the_cell(cell):
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, "harmonic-arma24x168", "walk-dense")
    assert cell.traffic["kind"] == "walk" and not cell.traffic["sharded"]
    # both periods, K = 10 and 5, ARMA(1,1) and the kernels by name;
    # max_iters (60) and tol (1e-4) stay the library's
    assert cfg["model"] == {
        "fit": "spark_timeseries_tpu.models.regression_arima:fit_harmonic",
        "kwargs": {"periods": [24, 168], "harmonics": [10, 5],
                   "order": [1, 0, 1], "backend": "pallas"}}
    assert (cfg["n_time"], cfg["chunk_rows"], cfg["dtype"]) \
        == (960, 131072, "float32")
    # nothing but rows may be cut, and rows only to a power of two
    assert cfg["rows"] in (262144, 524288, 1048576)
    assert cfg["reduced"] == ([] if cfg["rows"] == 1048576 else ["rows"])
    entry = {c["name"]: c for c in cell.manifest["configs"]}[
        "harmonic-arma24x168"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    for name in ("periods", "harmonics"):
        assert cfg["process"][name] == cfg["model"]["kwargs"][name]
    assert cfg["reference"]["module"] == "regression_arma_css"
    assert cfg["reference"]["loglik_gap_max"] <= check.LOGLIK_GAP_MAX
    assert cfg["reference"].get("min_share", 1.0) >= 0.9
    assert [(r["name"], r["index"]) for r in cfg["recovery"]] \
        == [("phi", 31), ("theta", 32)]
    assert [m["name"] for m in cell.end_to_end] \
        == ["series_per_s_chip", "setup_s"]
    # every shared reader of the walk cells, the CSS kernel's roofline
    # (arima111's scope: no new kernel) and this cell's own two
    layers = {m["name"]: m for m in cell.per_layer}
    other = {m["name"] for m in mf.resolve_cell(
        mf.load_manifest(), "arima111.walk-dense").per_layer}
    assert "css_neg_loglik_roofline" in other
    assert set(layers) == other | set(OWN)
    for name, unit in OWN.items():
        m = layers[name]
        assert m["workloads"] == [CELL] and m["layer"] == "optimizer"
        assert (m["unit"], m["better"], m["moves"], m["source"]) \
            == (unit, "lower", "series_per_s_chip", "program_span")
    tiny = mf.resolve_cell(mf.load_manifest(), CELL, rehearse=True).config
    assert tiny["rows"] % tiny["chunk_rows"] == 0
    assert tiny["n_time"] >= 2 * 168
    assert tiny["model"]["kwargs"] == {  # no chip here: "auto" -> scan
        "periods": [24, 168], "harmonics": [10, 5], "order": [1, 0, 1]}


def panel(cell, seed, rows=128, n_time=960):
    return np.asarray(g.build_panel(
        harmonic_arma.rows, cell.config["process"], {}, seed,
        jax.devices()[:1], rows, n_time, min(rows, 128)))


def test_reference_is_the_models_objective(cell):
    """``fit_harmonic``'s portable objective (the scan, float64) at arbitrary
    parameters, on rows of the configuration's process."""
    from spark_timeseries_tpu.models import arima
    from spark_timeseries_tpu.models import regression_arima as ra

    kw = cell.config["model"]["kwargs"]
    x = ra.harmonic_design(960, kw["periods"], kw["harmonics"])
    np.testing.assert_allclose(x, ref.design(960, kw), rtol=0, atol=1e-11)
    rng = np.random.default_rng(5)
    with jax.enable_x64():
        for y in panel(cell, 11)[:4].astype(np.float64):
            par = np.concatenate([rng.normal(scale=5.0, size=31),
                                  [rng.uniform(-0.9, 0.9)],
                                  [rng.uniform(-0.9, 0.9)]])
            ss, n_eff = ref.objective(par, y, kw)
            nll = float(arima.css_neg_loglik(
                jax.numpy.asarray(par[31:]),
                jax.numpy.asarray(y - x @ par[:31]), (1, 0, 1), False))
            assert n_eff == 959
            assert nll == pytest.approx(
                0.5 * n_eff * (np.log(2 * np.pi * ss / n_eff) + 1), rel=1e-9)


def test_optimum_is_a_profile_and_beats_its_neighbours(cell):
    kw = cell.config["model"]["kwargs"]
    rows = panel(cell, 11)[:6]
    best = np.array([ref.optimum(y, kw) for y in rows])
    assert best.shape == (6, 33)
    assert np.all(np.abs(check.loglik_gaps(ref, kw, rows, best)) < 1e-9)
    # at the optimum's (phi, theta) no other coefficients do better, and
    # any move of (phi, theta) with the coefficients re-profiled does worse
    rng = np.random.default_rng(2)
    for d in (0.02, -0.02):
        nudged = best + d * rng.normal(size=best.shape) * np.abs(best)
        assert np.all(check.loglik_gaps(ref, kw, rows, nudged) > 0)
    x = ref.design(960, kw)
    for y, opt in zip(rows.astype(np.float64), best):
        css = ref.profile(opt[31:], y, x, 1)[1]
        for step in ((0.01, 0), (-0.01, 0), (0, 0.01), (0, -0.01)):
            assert ref.profile(opt[31:] + step, y, x, 1)[1] > css
    # the timed path broken underneath is refused: the least-squares start
    # with no ARMA term loses whole units
    limit = cell.config["reference"]["loglik_gap_max"]
    ols = np.array([np.concatenate([np.linalg.lstsq(x, y, rcond=None)[0],
                                    [0.0, 0.0]]) for y in rows])
    assert np.all(check.loglik_gaps(ref, kw, rows, ols) > 100 * limit)


def test_panel_is_a_function_of_the_seed(cell):
    a, b = panel(cell, 7, 512, 336), panel(cell, 7, 512, 336)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert np.isfinite(a).all()
    assert not np.array_equal(a[:128], a[128:256])
    c = panel(cell, 8, 512, 336)  # the same chunks of the same rows
    order = lambda y: y[np.lexsort(y.T[::-1])]  # noqa: E731
    chunks = lambda y: sorted(order(y[i:i + 128]).tobytes()  # noqa: E731
                              for i in range(0, 512, 128))
    assert not np.array_equal(a, c) and chunks(a) == chunks(c)


def test_process_draws_what_the_configuration_says(cell):
    p, kw = cell.config["process"], cell.config["model"]["kwargs"]
    y = panel(cell, 3, rows=512).astype(np.float64)
    x = ref.design(960, kw)
    beta = np.linalg.lstsq(x, y.T, rcond=None)[0].T  # [512, 31]
    mu = beta[:, 0]
    assert 10 * 0.97 < mu.min() < 13 and 75 < mu.max() < 100 * 1.03
    # harmonic h of period i: amplitude uniform on amplitude[i] x mu / h, a
    # draw a row (least squares recovers it to the noise, which at the
    # week's frequencies is a good part of the range)
    amp = np.hypot(beta[:, 1::2], beta[:, 2::2]) / mu[:, None]
    at = 0
    for (lo, hi), k in zip(p["amplitude"], p["harmonics"]):
        for h in range(1, k + 1):
            a = amp[:, at] * h
            assert abs(np.median(a) - (lo + hi) / 2) < 0.15 * (hi - lo)
            assert 0.2 * (hi - lo) < a.std() < 0.45 * (hi - lo)
            at += 1
    # the noise is an ARMA(1,1) around the generating ranges' middle
    u = y - beta @ x.T
    r1 = np.sum(u[:, 1:] * u[:, :-1], axis=1) / np.sum(u * u, axis=1)
    assert 0.55 < np.median(r1) < 0.8 and r1.min() > 0.2
    sd = u.std(axis=1) / mu
    assert 0.01 < sd.min() and sd.max() < 0.05 * 3.5  # 1 / sqrt(1 - 0.9^2)


class _Run:
    """What a reader is handed, with the traced window wide open."""

    def __init__(self, spans, trace=None):
        self.spans, self.trace = spans, trace


def _span(name, **attrs):
    return {"kind": "span", "name": name, "attrs": attrs}


def test_xreg_panel_moves_reader(cell, monkeypatch):
    from benchmark import span_idle

    monkeypatch.setattr(span_idle, "window_spans", lambda run, name: [
        s for s in run.spans if s["name"] == name])
    reader = cell.plugin("layer_metrics", "xreg_panel_moves")
    run = _Run([_span("fit.stage1", rows=131072, xreg_panel_moves=4),
                _span("fit.stage1", rows=131072, xreg_panel_moves=4),
                _span("fit.stage2", rows=16384, xreg_panel_moves=4)])
    assert reader.read(run) == 4.0
    # another family's stage spans, or the parent: no such attribute
    assert reader.read(_Run([_span("fit.stage1", rows=131072,
                                   adjoint_panels=2)])) is None
    assert reader.read(_Run([])) is None


def test_design_exposed_reader(cell, monkeypatch):
    from benchmark import span_idle

    reader = cell.plugin("layer_metrics", "design_exposed_s_per_chunk")
    asked = []
    monkeypatch.setattr(span_idle, "per_chunk", lambda trace, names: (
        asked.append((trace, names)), 0.0012)[1])
    assert reader.read(_Run([], trace="the trace")) == 0.0012
    assert asked == [("the trace", ("fit.design",))]

    class NoDesign:  # a trace of a program without the span: nothing
        data = {"host": [{"spans": [("chunk", 0, 10)]}]}
        devices = [{"busy": [(0, 5)]}]
        window = (0, 10)

        def host_spans(self, name):
            return [1] if name == "chunk" else []

    monkeypatch.undo()
    assert reader.read(_Run([], trace=NoDesign())) is None
    assert reader.read(_Run([], trace=None)) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace, tmp_path):
    line = rehearse(CELL, trace, tmp_path)
    check_line(line, cell, trace)
    assert line["correct"] and line["failed"] == 0
    if trace:
        # on the CPU the fit takes the scan: no stage span, no kernel event
        # and no device plane — the cell's own readers find nothing to read
        assert not set(OWN) & set(line["metrics"])
        assert "css_neg_loglik_roofline" not in line["metrics"]
        assert line["metrics"]["compiles_in_window"]["value"] == 0


# -- the nearest precision below ----------------------------------------------


def _bf16_neighbours(v):
    """The two bfloat16 values around each float: toward zero, and away."""
    toward = np.asarray(v, np.float32).view(np.uint32) & np.uint32(0xFFFF0000)
    away = toward + np.uint32(0x10000)
    return (toward.view(np.float32).astype(np.float64),
            away.view(np.float32).astype(np.float64))


def _bf16(v):
    toward, away = _bf16_neighbours(v)
    v = np.asarray(v, np.float64)
    return np.where(np.abs(v - toward) <= np.abs(away - v), toward, away)


def bf16_optimum(y, kw):
    """The reference's model with the design and the coefficients held in
    bfloat16 — a default-precision f32 product on the TPU, one bfloat16
    pass: exact products of rounded operands — and its optimum found IN
    FULL: at the profile's ``(phi, theta)`` a coordinate descent over the
    coefficients' bfloat16 grid to a point no single step improves, then
    ``(phi, theta)`` re-fitted to it, twice over."""
    y = np.asarray(y, np.float64)
    xb = _bf16(ref.design(len(y), kw))
    arma = ref.optimum(y, kw)[-2:]
    for _ in range(2):
        f = ref.errors(np.column_stack([y, xb]), arma[:1], arma[1:])[1:]
        fy, fx = f[:, 0], f[:, 1:]
        beta = _bf16(np.linalg.solve(fx.T @ fx, fx.T @ fy))
        norms = np.sum(fx * fx, axis=0)
        for _sweep in range(50):
            before = beta.copy()
            for j in range(len(beta)):
                target = beta[j] + fx[:, j] @ (fy - fx @ beta) / norms[j]
                near = np.array(_bf16_neighbours(target))
                beta[j] = near[np.argmin(np.abs(near - target))]
            if np.array_equal(beta, before):
                break
        u = y - xb @ beta
        arma = minimize(
            lambda a: np.log(np.sum(ref.errors(u, a[:1], a[1:]) ** 2)), arma,
            method="L-BFGS-B", bounds=[(-0.999, 0.999)] * 2).x
    return np.concatenate([beta, arma])


def test_bfloat16_product_fails_the_limit(cell):
    """PR 44's way: 32 rows of the population, the rounded model's optimum
    judged under the float64 objective.  The reading the configuration's
    ``assumed`` and ``PERF.md`` section 6 (PR 49) give — a median gap of
    0.157, 14 of 32 rows within 0.1, the largest 2.03 — is read anew."""
    kw, limit = cell.config["model"]["kwargs"], cell.config["reference"]
    rows = panel(cell, 11)[:32]
    best = np.array([bf16_optimum(y, kw) for y in rows])
    gaps = check.loglik_gaps(ref, kw, rows, best)
    share = float(np.mean(gaps <= limit["loglik_gap_max"]))
    assert share < limit.get("min_share", 1.0) - 0.3
    assert np.median(gaps) > limit["loglik_gap_max"]
    assert 0.1 < np.median(gaps) < 0.25 and 0.3 < share < 0.6
    assert gaps.min() > 0
