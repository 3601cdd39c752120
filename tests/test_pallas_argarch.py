"""The GARCH kernel pair with a MEAN EQUATION in its calls (ARGARCH's AR(1):
``r_t = y_t - c - phi y_{t-1}`` formed in VMEM, ``dL/dc`` and ``dL/dphi``
reduced in the adjoint; ISSUE 52) against the portable ``lax.scan``
likelihood in float64, the fit on it against the scan backend, its
compaction, and the fit programs' loop bodies.  Interpret mode, as
``test_pallas_css.py`` says.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import (_dist_parity, _panel_moves_in_loops,
                             _stage_programs, _traced_fit_parity)
from spark_timeseries_tpu.models import garch
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def _argarch_panel(b, t, seed=0, scale=1.0):
    """AR(1)+GARCH(1,1) rows, one parameter draw a row."""
    rng = np.random.default_rng(seed)
    nat = np.column_stack([
        rng.uniform(-0.1, 0.1, b), rng.uniform(-0.3, 0.6, b),
        rng.uniform(0.01, 0.05, b), rng.uniform(0.05, 0.2, b),
        rng.uniform(0.5, 0.75, b)]).astype(np.float32)
    y = jax.vmap(lambda pr, k: garch.argarch_sample(pr, k, t))(
        jnp.asarray(nat), jax.random.split(jax.random.PRNGKey(seed), b))
    return scale * np.asarray(y, np.float64)


@pytest.mark.parametrize("ragged", [False, True], ids=["dense", "padded"])
def test_in_kernel_objective_matches_float64_scan(ragged):
    """Value and gradient in all five coordinates (through the transform,
    as the optimizer takes them) against ``jax.grad`` of the vmapped
    ``argarch_neg_log_likelihood`` in float64: the returns formed in the
    kernel, the seed variance from the three moments, ``dL/dh0`` chained
    into ``phi``.  Right-aligned padded rows condition on THEIR first valid
    observation; a row too short to fit (``nv`` < 12) stays finite and is
    left out of the comparison."""
    assert jax.config.jax_enable_x64  # tests/conftest.py: the f64 oracle
    b, t = 40, 75
    y = _argarch_panel(b, t, seed=3)
    nv = np.full(b, t)
    if ragged:
        nv[[1, 3, 5, 7]] = [t - 3, t - 7, 13, 5]
    nv = jnp.asarray(nv, jnp.int32)
    ya = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None],
                   jnp.asarray(y), 0.0)
    rng = np.random.default_rng(15)
    u = jnp.asarray(rng.normal(scale=0.3, size=(b, 5)))
    w = jnp.asarray(rng.uniform(0.5, 1.5, b))  # a cotangent a row
    fit_rows = np.asarray(nv) >= 12

    def scan(U):
        return jax.vmap(garch.argarch_neg_log_likelihood)(
            jax.vmap(garch._argarch_to_natural)(U), ya, nv)

    f32 = jnp.float32
    folded, mom = pk.argarch_prefold(ya.astype(f32), nv)
    assert folded.y3.shape == (pk._time_layout(t)[0], 8, 128)
    assert mom.shape == (b, 3) and mom.dtype == f32

    def kernel(U):
        return pk.argarch_neg_loglik_folded(
            jax.vmap(garch._argarch_to_natural)(U), folded, mom,
            interpret=True)

    want, got = np.asarray(scan(u)), np.asarray(kernel(u.astype(f32)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[fit_rows], want[fit_rows], rtol=2e-5)
    g_want = np.asarray(jax.grad(lambda U: jnp.sum(w * scan(U)))(u))
    g_got = np.asarray(jax.grad(lambda U: jnp.sum(
        w.astype(f32) * kernel(U)))(u.astype(f32)))
    assert g_got.dtype == np.float32 and np.isfinite(g_got).all()
    scale = np.abs(g_want[fit_rows]).max(axis=0)
    assert np.all(np.abs(g_got - g_want)[fit_rows] <= 2e-5 * scale)
    # value-only and value-and-gradient agree to the bit, row by row
    both, _ = jax.vjp(kernel, u.astype(f32))
    assert np.asarray(both).tobytes() == got.tobytes()
    # the series is a constant of this objective
    with pytest.raises(NotImplementedError, match="parameters and seed"):
        jax.grad(lambda v: jnp.sum(pk._argarch_ll_f(
            True, jnp.ones((b, 5), f32), jnp.ones((b,), f32),
            pk.ArgarchFolded(v, folded.zb3, t))))(folded.y3)


def test_mean_calls_are_bit_equal_across_block_widths():
    """R = 2 registers of series a time step against R = 1: the same chains,
    two a loop iteration (``series_rows``)."""
    b, t = 2048, 24
    y = jnp.asarray(_argarch_panel(b, t, seed=5), jnp.float32)
    folded, mom = pk.argarch_prefold(y)
    rng = np.random.default_rng(2)
    par = jax.vmap(garch._argarch_to_natural)(jnp.asarray(
        rng.normal(scale=0.3, size=(b, 5)), jnp.float32))
    gbar = jnp.asarray(rng.uniform(0.5, 1.5, b), jnp.float32)
    outs = []
    for r in (1, 2):
        (h3, ll3), planes = pk._argarch_fwd_call_f(True, "both", par,
                                                   mom[:, 0], folded, _r=r)
        outs.append((h3, ll3, *pk._argarch_ll_f_bwd(
            True, (folded, *planes, h3), gbar, _r=r)[:2]))
    for a, c in zip(*outs):
        assert np.asarray(a).tobytes() == np.asarray(c).tobytes()


def test_fit_argarch_pallas_matches_scan():
    """Parameters and likelihood, dense and padded rows, and in the DATA's
    units: daily returns in decimals (``c`` ~ 1e-4) fit as the same rows in
    percent do — the optimizer's ``c`` is in the row's own units (PR 52)."""
    y = _argarch_panel(8, 300, seed=3)
    y[1, :7] = np.nan
    y[2, -4:] = np.nan
    rescale = np.array([0.01, 1.0, 1e-4, 1.0, 1.0])
    for scale in (1.0, 0.01):
        ys = jnp.asarray(scale * y, jnp.float32)
        scan = garch.fit_argarch(ys, backend="scan")
        pal = garch.fit_argarch(ys, backend="pallas-interpret")
        assert bool(jnp.all(scan.converged)) and bool(jnp.all(pal.converged))
        np.testing.assert_allclose(np.asarray(pal.neg_log_likelihood),
                                   np.asarray(scan.neg_log_likelihood),
                                   rtol=1e-5, atol=2e-3)
        # a flat row's alpha / beta differ in the second place at the same
        # likelihood: per-coordinate room, in the data's units
        room = np.array([5e-3 * scale, 0.02, 5e-3 * scale ** 2, 0.02, 0.02])
        assert np.all(np.abs(np.asarray(pal.params) - np.asarray(scan.params))
                      <= room)
        if scale == 1.0:
            unit = np.asarray(pal.params)
    # the same rows in other units: the same fit, up to where a flat row's
    # search stops
    assert np.all(np.median(np.abs(np.asarray(pal.params) - unit * rescale),
                            axis=0) <= room / 4)


def test_argarch_compaction_gathers_folded_columns(monkeypatch):
    """Stage 1 hands stage 2 its stragglers as COLUMNS of the one fold and
    rows of the moments (nothing re-folded, no shifted copy kept), and the
    lazy pair is the uncompacted fit (the parity contract of
    ``test_argarch_lazy_stage2_split_parity``, at tier-1's size)."""
    rng = np.random.default_rng(33)
    y = jnp.asarray((rng.normal(size=(2048, 64)) * 0.1).astype(np.float32))
    ref = garch.fit_argarch(y, backend="pallas-interpret", max_iters=20,
                            compact=False)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    _, aux = garch._fit_argarch_stage1_program(
        20, 1e-4, "pallas-interpret", "dense")(y)
    (start,) = aux["starts"]
    folded, (mom, c0, units), _ = start["sub"]
    idxc = np.asarray(start["carry"].idxc)
    whole, mom_all = pk.argarch_prefold(y)
    assert isinstance(folded, pk.ArgarchFolded) and folded.t == 64
    np.testing.assert_array_equal(
        np.asarray(folded.y3).reshape(64, -1),
        np.asarray(whole.y3).reshape(64, -1)[:, idxc])
    np.testing.assert_allclose(mom, np.asarray(mom_all)[idxc], rtol=1e-4)
    assert 0 < int(start["carry"].undone) <= 1024  # stage 2 has work
    assert int(start["carry"].k) < 20
    got = garch.fit_argarch(y, backend="pallas-interpret", max_iters=20)
    _dist_parity(ref, got)
    _traced_fit_parity(got, lambda v: garch.fit_argarch(
        v, backend="pallas-interpret", max_iters=20, align_mode="dense"), y)


def test_mean_panel_moves_is_the_traced_programs(monkeypatch):
    """``ARGARCH_MEAN_PANEL_MOVES`` (0) against the traced stage-1, inline
    and stage-2 programs: inside their loops no equation outside the kernel
    calls takes or gives anything of the panel's size — and the detector
    sees the composition that builds the returns in XLA (the path of a
    series past one time chunk)."""
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48

    def moves():
        _, programs = _stage_programs("argarch", b, t)
        return [len(_panel_moves_in_loops(jax.make_jaxpr(fn)(*args).jaxpr,
                                          rows * (t - 6)))
                for fn, args, rows in programs]

    assert moves() == [garch.ARGARCH_MEAN_PANEL_MOVES] * 3 == [0, 0, 0]
    attrs = garch._garch_kernel_attrs(t, True)
    assert attrs["stage_attrs"] == {"adjoint_panels": 2, "mean_terms": 2,
                                    "mean_panel_moves": 0}
    assert attrs["series_block"](b, "sum") \
        == pk.argarch_series_block(b, t, "sum")
    monkeypatch.setattr(pk, "garch_mean_structural_ok", lambda n: False)
    assert min(moves()) > garch._XLA_RETURNS_PANEL_MOVES
    assert garch._garch_kernel_attrs(t, False)["stage_attrs"][
        "mean_panel_moves"] == garch._XLA_RETURNS_PANEL_MOVES
    assert "mean_terms" not in garch._garch_kernel_attrs(t)["stage_attrs"]


def test_mean_equation_takes_one_time_chunk():
    assert pk.garch_mean_structural_ok(1000)
    assert pk.garch_mean_structural_ok(pk._CHUNK_T)
    assert not pk.garch_mean_structural_ok(pk._CHUNK_T + 1)
    with pytest.raises(ValueError, match="at most 1024 steps"):
        pk.argarch_prefold(jnp.zeros((4, pk._CHUNK_T + 8), jnp.float32))
    assert not garch._mean_in_kernel("scan", 1000)
    assert garch._mean_in_kernel("pallas", 1000)
    assert not garch._mean_in_kernel("pallas", 2000)
