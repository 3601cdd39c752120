"""``arima-grid9`` and its cell ``arima-grid9.walk-dense``: the manifest
resolves them, the process draws the orders and polynomials the
configuration says, the plain reference packs what ``fit_grid`` packs and
its one number IS half the AICc gap, the three readers read what the
program writes and nothing where it writes nothing, and the cell runs end
to end at tiny sizes on the CPU."""

import jax
import numpy as np
import pytest

from benchmark import manifest as mf
from benchmark.processes import arma_order_mix
from benchmark.reference import arima_grid_css as ref
from benchmark.reference import check
from benchmark.tests.test_rehearse import check_line, rehearse

CELL = "arima-grid9.walk-dense"
ORDERS = [(p, 1, q) for p in range(3) for q in range(3)]
KW = {"specs": [[list(o), None] for o in ORDERS]}
NEW = ("css_grid_neg_loglik_roofline", "css_grid_orders_per_call",
       "grid_stage1_undone_share")


@pytest.fixture(scope="module")
def cell():
    return mf.resolve_cell(mf.load_manifest(), CELL)


def test_manifest_resolves_the_cell(cell):
    from spark_timeseries_tpu.models import arima

    cfg = cell.config
    assert (cell.chips, cell.config_name, cell.traffic_name) \
        == (1, "arima-grid9", "walk-dense")
    assert cell.traffic["kind"] == "walk" and not cell.traffic["sharded"]
    # the nine orders row-major and the kernels by name; all else default
    assert cfg["model"] == {
        "fit": "spark_timeseries_tpu.models.arima:fit_grid",
        "server_name": "arima", "kwargs": {**KW, "backend": "pallas"}}
    assert (cfg["n_time"], cfg["dtype"]) == (1000, "float32")
    assert cfg["reduced"] == ["rows"] and len(cfg["source"]) <= 200
    rows, chunk = cfg["rows"], cfg["chunk_rows"]
    assert rows & (rows - 1) == 0 and rows <= 1048576 and rows % chunk == 0
    # a chunk's work is per CELL: the nine error panels of a gradient alone
    # are a quarter of the HBM or more
    assert 9 * chunk * cfg["n_time"] * 4 >= 2e9
    assert cfg["objective"]["time_steps"] == 999
    assert [m["name"] for m in cell.end_to_end] \
        == ["series_per_s_chip", "setup_s"]
    layers = {m["name"] for m in cell.per_layer}
    other = {m["name"] for m in mf.resolve_cell(
        mf.load_manifest(), "arima111.walk-dense").per_layer}
    assert layers == other - {"css_neg_loglik_roofline"} | set(NEW)
    for m in mf.load_manifest()["per_layer"][-3:]:
        assert m["name"] in NEW and m["workloads"] == [CELL]
    pack = arima.grid_pack_width([(o, None) for o in ORDERS])
    assert pack == 90 and cfg["recovery"][0]["index"] < pack
    tiny = mf.resolve_cell(mf.load_manifest(), CELL, rehearse=True).config
    assert tiny["model"]["kwargs"] == KW  # no chip here: "auto" -> scan


def test_process_draws_what_the_configuration_says(cell):
    p = cell.config["process"]
    which, coef = arma_order_mix.draw_params(jax.random.key(1), 65536, p)
    which, coef = np.asarray(which), np.asarray(coef, np.float64)
    shares = np.array([o[2] for o in p["orders"]])
    assert shares.sum() == pytest.approx(1.0)
    got = np.bincount(which, minlength=len(shares)) / which.size
    assert np.abs(got - shares).max() < 0.01
    for i, (pp, qq, _) in enumerate(p["orders"]):
        c = coef[which == i]
        # an order's own terms are there and no other
        assert (c[:, 1] != 0).all() == (pp == 2) and (c[:, 0] != 0).all() \
            == (pp >= 1)
        assert (c[:, 3] != 0).all() == (qq == 2) and (c[:, 2] != 0).any() \
            == (qq >= 1)
    # stationary and invertible: the roots of both polynomials inside the
    # drawn ranges, so under 1 in magnitude
    phi1, phi2, th1, th2 = coef.T
    for a, b, hi in ((phi1, -phi2, p["ar_root"][1]),
                     (-th1, th2, p["ma_root_abs"][1])):
        disc = a * a - 4 * b  # roots of z^2 - a z + b (real by construction)
        assert disc.min() > -1e-6
        big = (np.abs(a) + np.sqrt(np.maximum(disc, 0))) / 2
        assert big.max() <= hi + 1e-6
    # arima111's generating point lies inside both ranges
    assert p["ar_root"][0] <= 0.6 <= p["ar_root"][1]
    assert p["ma_root_abs"][0] <= 0.3 <= p["ma_root_abs"][1]


def test_rows_are_integrated_arma_with_drift(cell):
    p = cell.config["process"]
    key = jax.random.key(7)
    y = np.asarray(jax.jit(
        lambda k: arma_order_mix.rows(k, 4096, 1000, p))(key))
    assert y.shape == (4096, 1000) and y.dtype == np.float32
    assert np.isfinite(y).all()
    which, coef = arma_order_mix.draw_params(jax.random.split(key)[0], 4096,
                                             p)
    x = np.diff(y.astype(np.float64), axis=1)
    # the differenced rows are the drift plus a zero-mean ARMA
    assert abs(np.mean(x.mean(axis=1)) - p["drift"]) < 0.01
    # white noise where the order is (0,1,0)
    wn = x[np.asarray(which) == 0]
    wc = wn - wn.mean(axis=1, keepdims=True)
    assert abs(np.mean(np.sum(wc[:, 1:] * wc[:, :-1], axis=1)
                       / np.sum(wc * wc, axis=1))) < 0.01
    assert abs(wn.var(axis=1).mean() - 1.0) < 0.02


@pytest.fixture(scope="module")
def rows(cell):
    return np.asarray(jax.jit(lambda k: arma_order_mix.rows(
        k, 12, 600, cell.config["process"]))(jax.random.key(11)))


def test_reference_packs_what_fit_grid_packs(rows):
    from spark_timeseries_tpu.models import arima

    specs = [(o, None) for o in ORDERS]
    pack = ref.optimum(rows[0], KW)
    assert pack.shape == (arima.grid_pack_width(specs),)
    width = 5 + arima.GRID_PACK_COLS
    blocks = pack.reshape(9, width)
    for (p, _, q), blk in zip(ORDERS, blocks):
        assert not blk[1 + p + q:5].any()  # zero beyond the order's own k
        assert tuple(blk[6:]) == (1.0, 1.0, 0.0, 0.0)
        # the nll column is the system's concentrated likelihood
        x = np.diff(rows[0].astype(np.float64))
        with jax.enable_x64(True):
            nll = float(arima.css_neg_loglik(
                jax.numpy.asarray(blk[:1 + p + q]), jax.numpy.asarray(x),
                (p, 0, q), True))
        assert blk[5] == pytest.approx(nll, rel=1e-9)
    # nested orders never fit worse than the order they contain
    nll = blocks[:, 5].reshape(3, 3)
    n_eff = 599 - np.arange(3)[:, None]
    css = np.exp(2 * nll / n_eff - 1) * n_eff / (2 * np.pi)
    assert (np.diff(css, axis=1) <= 1e-6 * css[:, :-1]).all()
    assert (np.diff(css, axis=0) <= 1e-6 * css[:-1]).all()


def test_the_gap_is_half_the_aicc_gap(rows):
    """``check.loglik_gaps`` on packs IS ``h_sys - h_ref``, ``h`` the half
    AICc of the order each pack's own columns select — also when the two
    sides choose orders of different ``p`` — and the selection is the
    documented argmin (a strict ``<``: ties to the earlier entry)."""
    y = rows[1]
    x = np.diff(y.astype(np.float64))
    n = x.shape[0]
    best = ref.optimum(y, KW)
    assert check.loglik_gaps(ref, KW, [y], [best])[0] == pytest.approx(0.0)
    blocks = best.reshape(9, 10)
    crit = [2 * blk[5] + 2 * (k + k * (k + 1) / (n - p - k - 1))
            for (p, _, q), blk in zip(ORDERS, blocks)
            for k in [1 + p + q]]
    g_ref = int(np.argmin(crit))
    assert ref.select(best, ORDERS, n) == g_ref
    # a hand-made pack: only (1,1,0) eligible, its AR term moved by 0.1
    hand = best.reshape(9, 10).copy()
    hand[:, 6] = 0.0
    hand[3, 6], hand[3, 1] = 1.0, hand[3, 1] + 0.1
    hand = hand.reshape(-1)
    assert ref.select(hand, ORDERS, n) == 3
    css = ref.arima_css._css(hand[30:32].copy(), x.copy(), 1, 0)
    want = ref.half_aicc(css, n, 1, 0) - crit[g_ref] / 2
    assert check.loglik_gaps(ref, KW, [y], [hand])[0] \
        == pytest.approx(want, rel=1e-9)
    # the pack's OWN nll decides the choice: an understated likelihood of
    # the largest order is chosen, and its real fit is what is charged
    lied = best.reshape(9, 10).copy()
    lied[8, 5] -= 100.0
    assert ref.select(lied.reshape(-1), ORDERS, n) == 8
    assert check.loglik_gaps(ref, KW, [y], [lied.reshape(-1)])[0] \
        == pytest.approx(crit[8] / 2 - crit[g_ref] / 2, abs=1e-9)
    # equal likelihoods: the smallest penalty wins, on the fewest lost
    # observations; no eligible order is an infinite gap
    flat = best.reshape(9, 10).copy()
    flat[:, 5] = 10.0
    assert ref.select(flat.reshape(-1), ORDERS, n) == 0
    none = best.reshape(9, 10).copy()
    none[:, 6] = 0.0
    assert np.isinf(check.loglik_gaps(ref, KW, [y], [none.reshape(-1)])[0])


def test_system_fits_pass_and_a_broken_pack_is_refused(rows):
    from spark_timeseries_tpu.models import arima

    res = arima.fit_grid(jax.numpy.asarray(rows, "float32"),
                         tuple((o, None) for o in ORDERS))
    gaps = check.loglik_gaps(ref, KW, rows, np.asarray(res.params))
    assert np.median(gaps) < 0.01 and np.mean(gaps <= 0.1) >= 0.75
    moved = np.asarray(res.params).reshape(-1, 9, 10).copy()
    moved[:, :, 1:5] += 0.2 * (moved[:, :, 1:5] != 0)
    assert np.mean(check.loglik_gaps(
        ref, KW, rows, moved.reshape(len(rows), -1)) <= 0.1) < 0.1


class _Run:
    """What a reader is handed, with the traced window wide open."""

    def __init__(self, spans, trace=None, peaks=None, config=None):
        self.spans, self.trace, self.peaks = spans, trace, peaks
        self.cell = type("Cell", (), {"config": config or {}})


def _stage1(**attrs):
    return {"kind": "span", "name": "fit.stage1", "attrs": attrs}


def test_span_readers(cell, monkeypatch):
    from benchmark import span_idle

    monkeypatch.setattr(span_idle, "window_spans", lambda run, name: [
        s for s in run.spans if s["name"] == name])
    orders = cell.plugin("layer_metrics", "css_grid_orders_per_call")
    undone = cell.plugin("layer_metrics", "grid_stage1_undone_share")
    grid = _Run([_stage1(rows=9 * 65536, iters=9, undone=u, orders=9,
                         cells=9 * 65536) for u in (147456, 100000)])
    assert orders.read(grid) == 9
    assert undone.read(grid) == pytest.approx(247456 / (2 * 9 * 65536))
    # a single order's spans carry no `orders`; an untraced run has none
    for run in (_Run([_stage1(rows=131072, iters=4, undone=9000)]),
                _Run([])):
        assert orders.read(run) is None and undone.read(run) is None


def test_grid_roofline_reader(cell):
    reader = cell.plugin("layer_metrics", "css_grid_neg_loglik_roofline")
    peaks = {"hbm_gbps": 819.0, "bf16_tflops": 197.0}

    class Trace:
        def __init__(self, got):
            self.got = got

        def scope(self, name):
            assert name == "pallas.css_grid_neg_loglik"
            return self.got

    cfg = cell.config
    nbytes = 10 * cfg["chunk_rows"] * 1000 * 4
    got = {"events": 10, "seconds": 4 * nbytes / 819e9, "bytes": nbytes}
    assert reader.read(_Run([], Trace(got), peaks, cfg)) \
        == pytest.approx(25.0)
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
        # event, no chip's peaks — the three readers find nothing to read
        assert not set(NEW) & set(line["metrics"])
        assert line["metrics"]["compiles_in_window"]["value"] == 0
