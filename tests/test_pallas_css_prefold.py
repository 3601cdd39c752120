"""``pk.css_prefold`` (ISSUE 46: fold first, difference in the folded layout)
against its row-major reference, bit for bit: the fold, the fit on it, the
stage-1 program's two transposes and the straggler gathers' series-major
copy.  Interpret mode, as ``test_pallas_css.py`` says.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import _arma_panel, _panel_ops, _stage_programs
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def _row_major_prefold(y, order, n_valid=None, *, lags=()):
    """``pk.css_prefold`` as it stood before ISSUE 46: the differences at
    ``lags`` taken row by row (lane shifts of the ``[B, T]`` panel), the
    mask and the pad in that layout, the fold last."""
    yd = y
    for lag in lags:
        yd = yd[:, lag:] - yd[:, :-lag]
    p = pk._span(pk._lags(order[0]))
    b, n = yd.shape
    nv = (jnp.full((b,), n, yd.dtype) if n_valid is None
          else n_valid.astype(yd.dtype))
    start = n - nv
    t_idx = jnp.arange(n, dtype=yd.dtype)
    ydz = jnp.where(t_idx[None, :] >= start[:, None], yd, 0.0)
    tp, _, _ = pk._time_layout(n)
    y3 = pk._fold(jnp.pad(ydz, ((0, 0), (0, tp - n))))
    zb3 = pk._fold((start + p).astype(yd.dtype)[:, None])
    return y3, zb3


def _ragged_heads(y, lag_sum, seed):
    """-> (``y`` with ragged unobserved heads, the aligned rows' valid
    lengths): a full row, an empty one, one as long as the lags reach and
    one shorter; the heads NaN in the even rows and 0 in the odd ones."""
    b, t = y.shape
    nv0 = np.random.default_rng(seed).integers(0, t + 1, size=b)
    nv0[:4] = [t, 0, lag_sum, max(lag_sum - 1, 0)]
    head = np.arange(t)[None, :] < (t - nv0)[:, None]
    fill = np.where(np.arange(b) % 2 == 0, np.nan, 0.0)[:, None]
    return (jnp.where(head, jnp.asarray(fill, y.dtype), y),
            jnp.asarray(nv0, jnp.int32))


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.signbit(got), np.signbit(want))


@pytest.mark.parametrize("t", [37, 960, 1000, 2100])
@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "ragged"])
@pytest.mark.parametrize("seasonal", [(0, 0), (1, 24)])
@pytest.mark.parametrize("d", [0, 1, 2])
def test_css_prefold_differences_in_the_folded_layout(d, seasonal, ragged, t):
    # ISSUE 46: fold first, then the differences as shifts of the major
    # axis, the mask and the padded tail in one pass — bit for bit what the
    # row-by-row differences and the fold-last ``css_prefold`` gave, on one
    # time chunk and on three, over rows that are no multiple of 1,024
    D, s = seasonal
    lags = (1,) * d + (s,) * D
    b = 1100
    y = _arma_panel(b, t, d_int=True, seed=t + d)
    nvd = None
    if ragged:
        y, nv0 = _ragged_heads(y, sum(lags), seed=7)
        nvd = nv0 - sum(lags)
    yd = jax.vmap(lambda v: arima._difference_seasonal(
        arima._difference(v, d), D, s))(y)
    want = _row_major_prefold(yd, (2, 0, 1), nvd)
    got = jax.jit(lambda y, nv: pk.css_prefold(y, (2, 0, 1), nv, lags=lags))(
        y, nvd)
    tp = pk._time_layout(t - sum(lags))[0]
    assert got[0].shape == (tp, 2048 // 128, 128)
    for g, w in zip(got, want):
        _same_bits(g, w)


@pytest.mark.parametrize("case", ["arima111-dense", "arima111-ragged",
                                  "airline4-dense", "airline4-ragged"])
def test_arima_fit_is_the_row_major_preps_fit(monkeypatch, case):
    # ISSUE 46: the order of the prep's operations is all that changed, so
    # ``arima.fit`` on a 2,048-row panel returns what the row-major prep's
    # program returns, bit for bit
    model, shape = case.split("-")
    seasonal = (0, 1, 1, 4) if model == "airline4" else None
    order = (0, 1, 1) if seasonal else (1, 1, 1)
    b, t = 2048, 64
    y = _arma_panel(b, t, d_int=True, seed=46)
    if shape == "ragged":  # unobserved heads, and one unobserved tail
        nv0 = _ragged_heads(y, 0, seed=8)[1]
        y = jnp.where(jnp.arange(t)[None, :] < (t - nv0)[:, None], jnp.nan, y)
        y = y.at[5, -3:].set(jnp.nan)
    got = arima.fit(y, order, seasonal=seasonal, backend="pallas-interpret",
                    max_iters=6)
    from spark_timeseries_tpu.models.base import resolve_align_mode

    mode = resolve_align_mode(y, None)
    assert mode == ("dense" if shape == "dense" else "general")
    monkeypatch.setattr(pk, "css_prefold", _row_major_prefold)
    want = jax.jit(arima._fit_program.__wrapped__(
        order, True, "css-lbfgs", "pallas-interpret", 6, 1e-4, False, mode,
        False, True, seasonal))(y)
    assert int(jnp.sum(want.converged)) > 0
    for name in ("params", "neg_log_likelihood", "iters", "converged",
                 "status"):
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=name)


@pytest.mark.parametrize("family", ["arima111", "sarima-airline4"])
def test_arima_prep_transposes_the_panel_twice(monkeypatch, family):
    # ISSUE 46, beside the cotangent pin above: the stage-1 program of a
    # dense fit folds the panel FIRST and differences it there — no
    # panel-sized ``sub`` has a ``[B, n]`` row-major result — and transposes
    # a panel exactly TWICE, both times ahead of the lockstep loop: the fold,
    # and the differenced panel back to rows for the straggler gathers
    # (``pk.series_major``: a column gather of ``y3`` inside the loop is a
    # transpose of the whole panel every call on the chip)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48
    n_panel = b * (t - 6)

    def relayouts():
        _, programs = _stage_programs(family, b, t)
        (stage1, args, _), *rest = programs
        jaxpr = jax.make_jaxpr(stage1)(*args).jaxpr
        loop = [e.primitive.name for e in jaxpr.eqns].index("while")
        return (_panel_ops(jaxpr.eqns[:loop], n_panel, ("transpose",)),
                _panel_ops(jaxpr.eqns, n_panel, ("transpose",)),
                [shape for _, shape in _panel_ops(jaxpr.eqns, n_panel,
                                                  ("sub",))
                 if len(shape) == 2],
                [_panel_ops(jax.make_jaxpr(fn)(*a).jaxpr.eqns, n_panel,
                            ("transpose",)) for fn, a, _ in rest])

    fold_and_back = [("transpose", (t, b)), ("transpose", (b, t))]
    ahead, anywhere, row_major_subs, others = relayouts()
    assert ahead == anywhere == fold_and_back
    assert row_major_subs == []
    # the inline program is the same prep; stage 2 takes gathered columns
    assert others == [fold_and_back, []]
    # the detector sees what it is for: the row-major prep's lane-shifted
    # differences, one a lag
    monkeypatch.setattr(pk, "css_prefold", _row_major_prefold)
    lags = 2 if family == "sarima-airline4" else 1
    assert len(relayouts()[2]) == lags


def test_straggler_gathers_read_the_series_major_panel():
    # the gather of a straggler subset is ``take_series``' columns, bit for
    # bit, read as rows of ``series_major``'s copy — for a fit's panel and
    # for the order grid's cells
    rng = np.random.default_rng(46)
    y = jnp.asarray(rng.normal(size=(3000, 37)).astype(np.float32))
    nv = jnp.asarray(rng.integers(0, 37, size=3000), jnp.int32)
    idxc = jnp.asarray(rng.permutation(3000)[:1024])
    f = arima._CssFolded.of(y, (2, 0, 1), nv - 1, (1,))
    assert f.y_rows.shape == (3072, 40)
    sub = f.take(idxc)
    assert sub.y_rows is None and sub.t == f.t
    want = pk.take_series((f.y3, f.zb3), idxc)
    _same_bits(sub.y3, want[0])
    _same_bits(sub.zb3, want[1])
    g = pk.css_grid_prefold(y, [0, 2, 1], nv - 1, lags=(1,))
    cells = jnp.asarray(rng.permutation(3 * 3000)[:2048])
    sub = pk.take_cells(g, cells)
    assert sub.y_rows is None and (sub.b, sub.k) == (2048, 1)
    _same_bits(sub.y3, pk.take_series(g.y3, cells % 3000))
