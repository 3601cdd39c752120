"""The fused order search on the CSS grid kernels (ISSUE 36): ``arima.fit_grid``
as a ``lockstep.Family`` over the ``K x B`` cells.  The interpreted kernels
against the scan objective per order, the straggler compaction over cells,
the time chunks and the block rule; ``test_grid_fit.py`` holds the fused FIT
to nine separate fits and to the benchmark's plain reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _grid_cases import ORDERS, SPECS, K, mix_panel
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.ops import pallas_kernels as pk


def _prepared(backend, y):
    family, _ = arima._grid_family(SPECS, True, backend, "general")
    prepared = family.prep(jnp.asarray(y))
    return family, prepared


@pytest.fixture(scope="module")
def objectives():
    """Value and gradient of the two batched objectives at one point, on a
    ragged panel: rows that start late (NaN heads) and one that ends early."""
    b = 24
    y = mix_panel(b, 90, 5).astype(np.float32)
    y[1, :7] = np.nan
    y[4, :19] = np.nan
    y[7, -5:] = np.nan
    rng = np.random.default_rng(0)
    out = {}
    x = None
    for backend in ("scan", "pallas-interpret"):
        family, p = _prepared(backend, y)
        if x is None:  # the scan's Hannan-Rissanen start, nudged
            live = np.asarray(p.x0s[0]) != 0.0
            x = p.x0s[0] + jnp.asarray(
                live * rng.uniform(-0.05, 0.05, live.shape), jnp.float32)
        fb = family.objective(p.folded, p.rows)
        out[backend] = (np.asarray(fb(x)), np.asarray(jax.grad(
            lambda v: jnp.sum(fb(v)))(x)), np.asarray(p.scale))
    return b, out


@pytest.mark.parametrize("g", range(K), ids=[str(o) for o in ORDERS])
def test_grid_kernels_match_the_scan_objective(objectives, g):
    # (a) per order, ragged rows included: the kernels' value and gradient
    # are the scan's; an order's padded slots — the zero planes of the
    # union lag set — have a gradient of exactly 0
    b, out = objectives
    (f_s, g_s, ne_s), (f_k, g_k, ne_k) = out["scan"], out["pallas-interpret"]
    rows = slice(g * b, (g + 1) * b)
    k = 1 + ORDERS[g][0] + ORDERS[g][2]
    assert np.array_equal(ne_s[rows], ne_k[rows])
    np.testing.assert_allclose(f_k[rows], f_s[rows], rtol=2e-5)
    np.testing.assert_allclose(g_k[rows, :k], g_s[rows, :k], rtol=2e-3,
                               atol=2e-3)
    assert not g_k[rows, k:].any() and not g_s[rows, k:].any()


@pytest.mark.parametrize("backend", ["scan", "pallas-interpret"])
def test_straggler_cells_are_the_full_objectives_entries(backend):
    # (c) compaction over cells: a straggler subset of mixed orders and
    # repeated rows evaluates to the full objective's entries
    b = 40
    y = mix_panel(b, 70, 9).astype(np.float32)
    y[3, :11] = np.nan
    family, p = _prepared(backend, y)
    x = p.x0s[0] + 0.01
    full = np.asarray(family.objective(p.folded, p.rows)(x))
    idx = jnp.asarray(np.random.default_rng(1).integers(0, K * b, 1024),
                      jnp.int32)
    sub = family.objective(family.take(p.folded, idx),
                           tuple(a[idx] for a in p.rows))
    got = np.asarray(sub(x[idx]))
    if backend == "scan":  # another program: the per-cell coefficient maps
        np.testing.assert_allclose(got, full[np.asarray(idx)], rtol=1e-5)
    else:  # the same kernel body over gathered columns
        assert np.array_equal(got, full[np.asarray(idx)])


def test_grid_kernels_cross_time_chunks():
    # past one time chunk (T > 1,024) the order groups re-read the panel's
    # blocks chunk by chunk and the carries cross chunks per order: value
    # and gradient are the per-order kernels', to the bit
    rng = np.random.default_rng(0)
    b, t, orders = 16, 1100, [(0, 0), (1, 1), (2, 2)]
    yd = jnp.asarray(rng.normal(size=(b, t)), jnp.float32)
    folded = pk.css_grid_prefold(yd, [p for p, _ in orders], None)
    planes = np.zeros((len(orders), b, 5), np.float32)
    planes[:, :, 0] = 0.1 * rng.normal(size=(len(orders), b))
    for g, (p, q) in enumerate(orders):
        planes[g, :, 1:1 + p] = rng.uniform(-0.4, 0.4, (b, p))
        planes[g, :, 3:3 + q] = rng.uniform(-0.4, 0.4, (b, q))
    ne = jnp.concatenate([jnp.full((b,), t - p, jnp.float32)
                          for p, _ in orders])

    def grid(x):
        return pk.css_grid_neg_loglik_folded(x, folded, 2, 2, ne,
                                             interpret=True)

    x = jnp.asarray(planes.reshape(-1, 5))
    val, grad = np.asarray(grid(x)), np.asarray(
        jax.grad(lambda v: jnp.sum(grid(v)))(x))
    for g, (p, q) in enumerate(orders):
        cols = [0, *range(1, 1 + p), *range(3, 3 + q)]

        def one(par, p=p, q=q):
            return pk.css_neg_loglik(par, yd, (p, 0, q), True, None,
                                     interpret=True)

        par = jnp.asarray(planes[g][:, cols])
        rows = slice(g * b, (g + 1) * b)
        assert np.array_equal(val[rows], np.asarray(one(par)))
        assert np.array_equal(grad[rows][:, cols], np.asarray(
            jax.grad(lambda v: jnp.sum(one(v)))(par)))


def test_grid_block_rule():
    # G orders and R registers of series a grid step: what VMEM allows of
    # what the chip showed best (static facts only)
    t, nsub = 999, 131072 // 128
    for mode, layout in (
            ("sum", pk._css_fwd_layout(2, 2, "sum", t)),
            ("both", pk._css_fwd_layout(2, 2, "both", t)),
            ("adjoint", pk._css_bwd_layout((1, 2), (1, 2), t))):
        g, r = pk.css_grid_block(9, nsub, layout, mode)
        assert 9 % g == 0 and g <= pk._CSS_GRID_G[mode]
        # (the adjoint: three orders of two registers, 86.5 of 88 MiB)
        assert r in (1, 2, 4) and (mode != "adjoint" or (g, r) == (3, 2))
        # one order over a straggler subset: the plain kernels' widths
        assert pk.css_grid_block(1, nsub, layout, mode) == (1, {
            "sum": pk._CSS_R["sum"], "both": pk._CSS_R["both"],
            "adjoint": pk._ADJOINT_R["css"]}[mode])
    # a block that is not a multiple of 8 R sublane rows: R = 1
    assert pk.css_grid_block(9, 8, pk._css_fwd_layout(2, 2, "sum", t),
                             "sum")[1] == 1
