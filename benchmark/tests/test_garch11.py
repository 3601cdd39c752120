"""``garch11`` and its cell ``garch11.walk-dense``: the manifest resolves
them, the generating process draws what the configuration says, and the
cell runs end to end at tiny sizes on the CPU."""

import jax
import numpy as np
import pytest

from benchmark import generators as g
from benchmark import manifest as mf
from benchmark.processes import garch11_returns
from benchmark.tests.test_rehearse import check_line, rehearse

CELL = "garch11.walk-dense"


@pytest.fixture(scope="module")
def cell():
    return mf.resolve_cell(mf.load_manifest(), CELL)


def test_manifest_resolves_the_cell(cell):
    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, "garch11", "walk-dense")
    assert cell.traffic["kind"] == "walk" and not cell.traffic["sharded"]
    assert cfg["model"] == {"fit": "spark_timeseries_tpu.models.garch:fit",
                            "server_name": "garch"}  # library defaults
    assert (cfg["rows"], cfg["n_time"], cfg["chunk_rows"], cfg["dtype"]) \
        == (1048576, 1000, 131072, "float32")
    assert cfg["rows"] * cfg["n_time"] * 4 >= 4e9  # fills a quarter of HBM
    assert cfg["reduced"] == [] and cfg["assumed"]
    assert [m["name"] for m in cell.end_to_end] \
        == ["series_per_s_chip", "setup_s"]
    layers = {m["name"] for m in cell.per_layer}
    # every per-layer metric of the other walk cells but their kernels'
    other = {m["name"] for m in mf.resolve_cell(
        mf.load_manifest(), "arima111.walk-dense").per_layer}
    assert layers == other - {"css_neg_loglik_roofline"} \
        | {"garch_neg_loglik_roofline"}
    for group, name in (("processes", cfg["process"]["name"]),
                        ("reference", cfg["reference"]["module"]),
                        ("layer_metrics", "garch_neg_loglik_roofline")):
        assert cell.plugin(group, name)
    tiny = mf.resolve_cell(mf.load_manifest(), CELL, rehearse=True).config
    assert (tiny["rows"], tiny["n_time"], tiny["chunk_rows"]) \
        == (1024, 128, 128)


def test_process_draws_what_the_configuration_says(cell):
    p = cell.config["process"]
    par = np.asarray(garch11_returns.draw_params(jax.random.key(1), 4096, p),
                     np.float64)
    omega, alpha, beta = par.T
    vol = np.sqrt(omega / (1.0 - alpha - beta))
    for values, (lo, hi) in ((alpha, p["alpha"]),
                             (alpha + beta, p["persistence"]),
                             (vol, p["daily_vol"])):
        assert lo - 1e-6 <= values.min() and values.max() <= hi + 1e-6
        # the whole range is used, not its middle
        assert values.min() < lo + 0.02 * (hi - lo)
        assert values.max() > hi - 0.02 * (hi - lo)
    assert abs(np.median(alpha) - 0.09) < 0.005
    assert abs(np.median(beta) - 0.855) < 0.005  # the recovery targets
    # log-uniform: the median volatility is the geometric mean of the range
    assert abs(np.median(vol) / np.sqrt(np.prod(p["daily_vol"])) - 1) < 0.05


def test_rows_are_the_process_the_model_describes(cell):
    """Finite f32 rows whose sample variance is the drawn unconditional
    variance ``omega / (1 - alpha - beta)``: in the mean over rows to 3%,
    row by row within the wide band a persistent GARCH row of 2,000 days
    keeps to (its variance's own standard error is some 10-40%)."""
    p, n_rows, n_time = cell.config["process"], 2048, 2000
    key = jax.random.key(7)
    y = np.asarray(jax.jit(
        lambda k: garch11_returns.rows(k, n_rows, n_time, p))(key))
    assert y.shape == (n_rows, n_time) and y.dtype == np.float32
    assert np.isfinite(y).all()
    k_par, _ = jax.random.split(key)
    par = np.asarray(garch11_returns.draw_params(k_par, n_rows, p),
                     np.float64)
    uncond = par[:, 0] / (1.0 - par[:, 1] - par[:, 2])
    ratio = y.astype(np.float64).var(axis=1) / uncond
    assert abs(ratio.mean() - 1.0) < 0.03
    assert np.quantile(ratio, 0.01) > 0.4 and np.quantile(ratio, 0.99) < 3.0
    assert abs(y.mean()) < 1e-4  # returns, not prices: no drift
    # volatility clusters: squared returns are autocorrelated, returns not
    sq = y.astype(np.float64) ** 2
    sq -= sq.mean(axis=1, keepdims=True)
    rho_sq = np.sum(sq[:, 1:] * sq[:, :-1], axis=1) / np.sum(sq * sq, axis=1)
    yc = y - y.mean(axis=1, keepdims=True)
    rho = np.sum(yc[:, 1:] * yc[:, :-1], axis=1) / np.sum(yc * yc, axis=1)
    assert np.median(rho_sq) > 0.05 and abs(np.median(rho)) < 0.01
    # through the general generator: a function of the seed, blocks differ
    a = np.asarray(g.build_panel(garch11_returns.rows, p, {}, 7,
                                 jax.devices()[:1], 512, 128, 128))
    b = np.asarray(g.build_panel(garch11_returns.rows, p, {}, 7,
                                 jax.devices()[:1], 512, 128, 128))
    assert np.array_equal(a, b) and not np.array_equal(a[:128], a[128:256])


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace, tmp_path):
    line = rehearse(CELL, trace, tmp_path)
    check_line(line, cell, trace)
    assert line["correct"] and line["failed"] == 0
    if trace:
        # counts repeat on the CPU; the roofline reader finds no chip's
        # peaks, returns None and is left out of the line
        assert "garch_neg_loglik_roofline" not in line["metrics"]
        assert line["metrics"]["rescued_row_share"]["value"] == 0
        assert line["metrics"]["compiles_in_window"]["value"] == 0
