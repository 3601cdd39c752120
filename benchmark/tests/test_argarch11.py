"""``argarch11`` and its cell ``argarch11.walk-dense``: the manifest resolves
them, its per-layer entries are pinned BY NAME, the plain reference computes
the model's objective and bounds the system's fit on seeded rows, the
generating process draws what the configuration says, the two readers read
what the program writes and nothing where it writes nothing, and the cell
runs end to end at tiny sizes on the CPU.  (The reference and process cases
stand here and not in ``test_reference.py`` / ``test_generators.py``: a
``model_config`` PR adds files under ``benchmark/`` and edits none.  The
fit's spans and kernels are held by ``tests/test_garch_config.py`` and
``tests/test_pallas_argarch.py`` in tier 1.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import generators as g
from benchmark import manifest as mf
from benchmark.processes import argarch11_returns
from benchmark.reference import argarch11, check
from benchmark.tests.test_rehearse import check_line, rehearse

CELL = "argarch11.walk-dense"
OWN = {"argarch_neg_loglik_roofline": ("%", "device_trace",
                                       "objective_kernel", "higher"),
       "mean_panel_moves": ("panels", "program_span", "optimizer", "lower")}


@pytest.fixture(scope="module")
def cell():
    return mf.resolve_cell(mf.load_manifest(), CELL)


def test_manifest_resolves_the_cell(cell):
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, "argarch11", "walk-dense")
    assert cell.traffic["kind"] == "walk" and not cell.traffic["sharded"]
    # library defaults: no keyword argument (max_iters 100, tol 1e-4,
    # backend auto, compact on)
    assert cfg["model"] == {
        "fit": "spark_timeseries_tpu.models.garch:fit_argarch",
        "server_name": "argarch"}
    assert (cfg["n_time"], cfg["chunk_rows"], cfg["dtype"]) \
        == (1000, 131072, "float32")
    # nothing but rows may be cut, and rows only to a power of two
    assert cfg["rows"] in (262144, 524288, 1048576)
    assert cfg["reduced"] == ([] if cfg["rows"] == 1048576 else ["rows"])
    entry = {c["name"]: c for c in cell.manifest["configs"]}["argarch11"]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert cfg["kernels"] == [cfg["objective"]["kernel"]] \
        == ["pallas.argarch_neg_loglik"]
    assert cfg["reference"]["module"] == "argarch11"
    # half garch11's gap, on a share midway between this cell's own two
    # readings (the configuration's ``assumed``; the controls below)
    assert cfg["reference"]["loglik_gap_max"] <= 0.5
    assert cfg["reference"]["min_share"] >= 0.75
    assert [r["name"] for r in cfg["recovery"]] == ["phi", "alpha", "beta"]
    assert [m["name"] for m in cell.end_to_end] \
        == ["series_per_s_chip", "setup_s"]
    # every shared reader of the walk cells and this cell's own two; the
    # plain GARCH kernel's roofline stays garch11's
    layers = {m["name"]: m for m in cell.per_layer}
    other = {m["name"] for m in mf.resolve_cell(
        mf.load_manifest(), "garch11.walk-dense").per_layer}
    assert "garch_neg_loglik_roofline" in other
    assert set(layers) == (other - {"garch_neg_loglik_roofline"}) | set(OWN)
    for name, (unit, source, layer, better) in OWN.items():
        m = layers[name]
        assert m["workloads"] == [CELL]
        assert (m["unit"], m["source"], m["layer"], m["better"],
                m["moves"]) == (unit, source, layer, better,
                                "series_per_s_chip")
    tiny = mf.resolve_cell(mf.load_manifest(), CELL, rehearse=True).config
    assert tiny["rows"] % tiny["chunk_rows"] == 0 and tiny["n_time"] >= 64


def panel(cell, seed, rows=512, n_time=128):
    return np.asarray(g.build_panel(
        argarch11_returns.rows, cell.config["process"], {}, seed,
        jax.devices()[:1], rows, n_time, 128))


@pytest.mark.parametrize("params", [
    [4e-4, 0.1, 1e-5, 0.09, 0.85], [-1e-3, -0.3, 4e-6, 0.2, 0.6],
    [0.0, 0.6, 3e-5, 0.03, 0.96], [2e-3, 0.0, 1e-4, 0.0, 0.0]])
def test_reference_is_the_models_objective(cell, params):
    """``models.garch.argarch_neg_log_likelihood`` in float64 at arbitrary
    parameters, on dense rows of the configuration's process and on a row
    with a NaN head (the fit conditions on the first VALID observation)."""
    from spark_timeseries_tpu.models import garch

    jax.config.update("jax_enable_x64", True)
    try:
        rows = panel(cell, 11, 128, 240)[:4].astype(np.float64)
        rows[3, :17] = np.nan
        for y in rows:
            n = int(np.isfinite(y).sum())
            ya = np.concatenate([np.zeros(len(y) - n), y[np.isfinite(y)]])
            want = float(garch.argarch_neg_log_likelihood(
                jax.numpy.asarray(params, "float64"), jax.numpy.asarray(ya),
                jax.numpy.asarray(n)))
            assert argarch11.nll(params, y) == pytest.approx(want, rel=1e-10)
            ss, n_eff = argarch11.objective(params, y, {})
            assert n_eff == n - 1
            assert 0.5 * n_eff * np.log(ss) == pytest.approx(want, rel=1e-10)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_optimum_bounds_the_systems_fit(cell):
    """On seeded rows of the process at a small size, on the CPU: scipy's
    best of two starts is an optimum of the reference objective (nothing the
    system finds is better by more than a hair), the system's fit at library
    defaults lies within the configured gap of it on the configured share,
    and parameters that are off by a start's worth are refused."""
    from spark_timeseries_tpu.models import garch

    ref = cell.config["reference"]
    rows = panel(cell, 11, 128, 500)[:24]
    best = np.array([argarch11.optimum(y, {}) for y in rows])
    assert np.all(np.abs(check.loglik_gaps(argarch11, {}, rows, best)) < 1e-9)
    fit = garch.fit_argarch(jax.numpy.asarray(rows))
    gaps = check.loglik_gaps(argarch11, {}, rows, np.asarray(fit.params))
    assert gaps.min() > -0.05
    assert np.mean(gaps <= ref["loglik_gap_max"]) >= ref["min_share"]
    # the library's own start (the moments' mean, 0.1 var, 0.1, 0.8): where
    # the parent's fit stops on the same returns in decimals (PERF.md §6,
    # PR 52)
    start = best.copy()
    start[:, 2:] = np.column_stack([0.1 * rows.var(axis=1),
                                    np.full(len(rows), 0.1),
                                    np.full(len(rows), 0.8)])
    stuck = check.loglik_gaps(argarch11, {}, rows, start)
    assert np.mean(stuck <= ref["loglik_gap_max"]) < 0.5


class _KeptOptima:
    """``reference.argarch11`` with each row's optimum kept: the controls
    below compare several sets of parameters on the same rows, and
    ``check.loglik_gaps`` asks for the optimum each time."""

    objective = staticmethod(argarch11.objective)

    def __init__(self):
        self.kept = {}

    def optimum(self, y, model_kwargs):
        key = np.asarray(y).tobytes()
        if key not in self.kept:
            self.kept[key] = argarch11.optimum(y, model_kwargs)
        return self.kept[key]


def control_rows(cell, block, rows=64, n_time=1000):
    """``rows`` fresh rows of the cell's process at the cell's length: what
    one run's reference comparison samples."""
    return np.asarray(argarch11_returns.rows(
        jax.random.key(1000 + block), rows, n_time, cell.config["process"]))


def rounded_nll(dtype):
    """The reference's objective with the recursion held in ``dtype``: the
    series, the returns, their squares, the seed and the variance path are
    rounded to it at every step; the likelihood's terms and their sum are
    float32 (a precision below the configuration's float32 is what a panel
    kept in bfloat16 would be).  ``[N, 5]`` natural parameters on ``[N, T]``
    dense rows -> ``[N]``; ``inf`` outside the GARCH box."""

    @jax.jit
    def nll(params, y):
        f32 = jnp.float32
        c, phi, omega, alpha, beta = (params[:, i].astype(dtype)
                                      for i in range(5))
        y = y.astype(dtype)
        r = y[:, 1:] - c[:, None] - phi[:, None] * y[:, :-1]
        r2 = r * r
        var = jnp.var(r.astype(f32), axis=1).astype(dtype)

        def step(carry, r2_t):
            h = omega + alpha * carry[1] + beta * carry[0]
            return (h, r2_t), h

        _, h = jax.lax.scan(step, (var, var), r2.T)
        h = jnp.maximum(h.T.astype(f32), argarch11.H_FLOOR)
        out = 0.5 * jnp.sum(jnp.log(2.0 * jnp.pi * h) + r2.astype(f32) / h,
                            axis=1)
        inside = ((omega > 0) & (alpha >= 0) & (beta >= 0)
                  & (alpha + beta < 1) & (jnp.abs(phi) < 1))
        return jnp.where(inside & jnp.isfinite(out), out, jnp.inf)

    return nll


def simplex_optimum(nll, rows, restarts=3, iters=250):
    """Each row's optimum of ``nll`` (:func:`rounded_nll`) by Nelder-Mead,
    every row's simplex moved at once, restarted ``restarts`` times around
    its best point; from the library's start (the moments' mean, 0.1 var,
    0.1, 0.8), in ``reference.argarch11.optimum``'s scaled coordinates.  No
    gradient: a rounded objective is a staircase."""
    y = np.asarray(rows, np.float64)
    n = len(y)
    d = y - y.mean(axis=1, keepdims=True)
    phi0 = np.clip(np.sum(d[:, 1:] * d[:, :-1], axis=1)
                   / np.sum(d * d, axis=1), -0.95, 0.95)
    c0 = y.mean(axis=1) * (1.0 - phi0)
    var = np.var(y[:, 1:] - c0[:, None] - phi0[:, None] * y[:, :-1], axis=1)
    scale = np.stack([np.sqrt(var), np.ones(n), var, np.ones(n), np.ones(n)],
                     axis=1)
    y32 = np.asarray(rows, np.float32)

    def f(z):  # [n, k, 5] scaled points -> [n, k]
        k = z.shape[1]
        par = (z * scale[:, None]).reshape(n * k, 5).astype(np.float32)
        return np.asarray(nll(par, np.repeat(y32, k, axis=0)),
                          np.float64).reshape(n, k)

    best = np.stack([c0 / np.sqrt(var), phi0, np.full(n, 0.1),
                     np.full(n, 0.1), np.full(n, 0.8)], axis=1)
    step = np.array([0.05, 0.05, 0.05, 0.03, -0.05])
    take = lambda a, i: np.take_along_axis(a, i, axis=1)  # noqa: E731
    for _ in range(restarts):
        x = np.concatenate([best[:, None], best[:, None] + np.diag(step)],
                           axis=1)
        fx = f(x)
        for _ in range(iters):
            order = np.argsort(fx, axis=1)
            x, fx = take(x, order[:, :, None]), take(fx, order)
            mid = x[:, :-1].mean(axis=1)
            away = mid - x[:, -1]
            # reflected, expanded, contracted outside and inside
            tries = mid[:, None] + away[:, None] * np.array(
                [1.0, 2.0, 0.5, -0.5])[None, :, None]
            fr, fe, fo, fi = f(tries).T
            lo, second, hi = fx[:, 0], fx[:, -2], fx[:, -1]
            pick = np.where(
                fr < lo, np.where(fe < fr, 1, 0),
                np.where(fr < second, 0,
                         np.where(fr < hi, np.where(fo <= fr, 2, -1),
                                  np.where(fi < hi, 3, -1))))
            moved = pick >= 0
            at = np.maximum(pick, 0)[:, None]
            x[moved, -1] = take(tries, at[:, :, None])[moved, 0]
            fx[moved, -1] = take(np.stack([fr, fe, fo, fi], 1), at)[moved, 0]
            if not moved.all():  # shrink toward the best point
                x[~moved, 1:] = 0.5 * (x[~moved, :1] + x[~moved, 1:])
                fx[~moved] = f(x)[~moved]
        best = take(x, np.argmin(fx, axis=1)[:, None, None])[:, 0]
        step = 0.2 * step
    return best * scale


def share_within(cell, reference, rows, params):
    ref = cell.config["reference"]
    gaps = check.loglik_gaps(reference, {}, rows, np.asarray(params))
    return float(np.mean(gaps <= ref["loglik_gap_max"])), gaps


@pytest.mark.parametrize("block", [0, 1])
def test_tolerance_tells_a_lower_precision_and_a_looser_fit(cell, block):
    """The cell's limit — ``loglik_gap_max`` on ``min_share`` of 64 rows —
    through the harness's own comparison, on 64 fresh rows of the process
    at the cell's length and in its units, re-read whenever the process or
    the limit moves (the readings over ten such blocks: the
    configuration's ``assumed``, PERF.md §6, PR 52).  The system at library
    defaults passes; the same recursion in bfloat16 at its own optimum is
    NOT correct, and the search that finds that optimum passes on the
    recursion in float32, so the precision and not the search fails; the
    library's fit at ``tol`` 1e-3 is NOT correct."""
    from spark_timeseries_tpu.models import garch

    rows = control_rows(cell, block)
    reference, floor = _KeptOptima(), cell.config["reference"]["min_share"]
    fit = garch.fit_argarch(jax.numpy.asarray(rows))
    assert share_within(cell, reference, rows, fit.params)[0] >= floor
    share, gaps = share_within(
        cell, reference, rows, simplex_optimum(rounded_nll(jnp.float32),
                                               rows))
    assert share >= floor and np.median(gaps) < 0.01
    assert share_within(cell, reference, rows, simplex_optimum(
        rounded_nll(jnp.bfloat16), rows))[0] < floor
    loose = garch.fit_argarch(jax.numpy.asarray(rows), tol=1e-3)
    assert share_within(cell, reference, rows, loose.params)[0] < floor


def test_panel_is_a_function_of_the_seed(cell):
    a, b = panel(cell, 7), panel(cell, 7)
    assert a.dtype == np.float32 and np.array_equal(a, b)
    assert np.isfinite(a).all()
    assert not np.array_equal(a[:128], a[128:256])
    c = panel(cell, 8)  # the same chunks of the same rows, laid otherwise
    order = lambda y: y[np.lexsort(y.T[::-1])]  # noqa: E731
    chunks = lambda y: sorted(order(y[i:i + 128]).tobytes()  # noqa: E731
                              for i in range(0, 512, 128))
    assert not np.array_equal(a, c) and chunks(a) == chunks(c)


def test_process_draws_its_ranges_one_draw_a_row(cell):
    p = cell.config["process"]
    par = np.asarray(argarch11_returns.draw_params(
        jax.random.key(1), 4096, p), np.float64)
    c, phi, omega, alpha, beta = par.T
    assert par.shape == (4096, 5)
    for v, (lo, hi) in ((phi, p["phi"]), (c / (1.0 - phi), p["mean_return"]),
                        (alpha, p["alpha"]), (alpha + beta, p["persistence"]),
                        (np.sqrt(omega / (1.0 - alpha - beta)),
                         p["daily_vol"])):
        assert lo - 1e-6 <= v.min() and v.max() <= hi * (1 + 1e-5) + 1e-9
        assert np.unique(v).size > 4000  # one draw a row, not one point
    # the generating medians the configuration's recovery names
    rec = {r["name"]: r["value"] for r in cell.config["recovery"]}
    assert np.median(phi) == pytest.approx(rec["phi"], abs=0.01)
    assert np.median(alpha) == pytest.approx(rec["alpha"], abs=0.005)
    assert np.median(beta) == pytest.approx(rec["beta"], abs=0.005)


def test_process_rows_carry_their_phi(cell):
    """The lag-1 autocorrelation of a row estimates its ``phi`` (standard
    error ~ 1 / sqrt(n) under GARCH's fat tails a little more): over many
    rows the two are strongly correlated and unbiased in the mean."""
    p = cell.config["process"]
    n_time = 1000
    y = np.asarray(g.build_panel(argarch11_returns.rows, p, {}, 3,
                                 jax.devices()[:1], 512, n_time, 512),
                   np.float64)
    assert y.shape == (512, n_time) and np.isfinite(y).all()
    assert 0.003 < np.median(y.std(axis=1)) < 0.05  # a 1% day is 0.01
    d = y - y.mean(axis=1, keepdims=True)
    acf1 = np.sum(d[:, 1:] * d[:, :-1], axis=1) / np.sum(d * d, axis=1)
    lo, hi = p["phi"]
    assert lo - 0.15 < acf1.min() and acf1.max() < hi + 0.15
    assert np.mean(acf1) == pytest.approx((lo + hi) / 2, abs=0.015)
    assert np.std(acf1) > 0.1  # rows are not one generating point
    # squared returns cluster: the variance equation is there
    r2 = (d * d) - np.mean(d * d, axis=1, keepdims=True)
    assert np.median(np.sum(r2[:, 1:] * r2[:, :-1], axis=1)
                     / np.sum(r2 * r2, axis=1)) > 0.03


class _Run:
    """What a reader is handed, with the traced window wide open."""

    def __init__(self, spans, trace=None, peaks=None, cell=None):
        self.spans, self.trace, self.peaks, self.cell = (spans, trace, peaks,
                                                          cell)


def _span(name, **attrs):
    return {"kind": "span", "name": name, "attrs": attrs}


def test_mean_panel_moves_reader(cell, monkeypatch):
    from benchmark import span_idle

    monkeypatch.setattr(span_idle, "window_spans", lambda run, name: [
        s for s in run.spans if s["name"] == name])
    reader = cell.plugin("layer_metrics", "mean_panel_moves")
    run = _Run([_span("fit.stage1", rows=131072, mean_terms=2,
                      mean_panel_moves=0)] * 3)
    assert reader.read(run) == 0
    # the parent's stage spans carry no such attribute; an untraced run
    assert reader.read(_Run([_span("fit.stage1", rows=131072)])) is None
    assert reader.read(_Run([])) is None
    # the constant the program reports is the one its tier-1 test holds
    from spark_timeseries_tpu.models import garch

    assert garch.ARGARCH_MEAN_PANEL_MOVES == 0


def test_roofline_reader_reads_its_scope_alone(cell):
    reader = cell.plugin("layer_metrics", "argarch_neg_loglik_roofline")

    class Trace:
        def __init__(self, got):
            self.got, self.asked = got, []

        def scope(self, name):
            self.asked.append(name)
            return self.got

    peaks = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}
    # one value-only event over the folded chunk in 1.0 ms: its bytes over
    # the HBM's rate, over the time
    trace = Trace({"events": 1, "seconds": 1e-3, "bytes": 131072 * 1000 * 4})
    share = reader.read(_Run([], trace, peaks, cell))
    assert trace.asked == ["pallas.argarch_neg_loglik"]
    assert share == pytest.approx(100 * 131072 * 4000 / 819e9 / 1e-3)
    # the parent has no such scope: nothing to read, and no raise
    none = Trace({"events": 0, "seconds": 0.0, "bytes": 0})
    assert reader.read(_Run([], none, peaks, cell)) is None
    assert reader.read(_Run([], None, peaks, cell)) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace, tmp_path):
    line = rehearse(CELL, trace, tmp_path)
    check_line(line, cell, trace)
    assert line["correct"] and line["failed"] == 0
    if trace:
        # on the CPU the fit takes the scan: no stage span, no kernel event
        # — the cell's own readers find nothing to read
        assert not set(OWN) & set(line["metrics"])
        assert line["metrics"]["compiles_in_window"]["value"] == 0
