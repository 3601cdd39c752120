"""The transform kernels (fill chain, autocorrelation) against the portable
path, the resident folded layout (``ops.layout``) and the kernel file's
structural guards.  Interpret mode, as ``test_pallas_css.py`` says.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_timeseries_tpu.ops import pallas_kernels as pk


def test_structural_guards():
    # the chunked layouts have static bounds (ADVICE round 2): large orders /
    # periods must raise a clear ValueError at the kernel entry, and the
    # auto backend must resolve to scan instead of tripping them
    from spark_timeseries_tpu.models.base import resolve_backend

    assert pk.css_structural_ok(1, 1)
    assert not pk.css_structural_ok(2048, 1)
    assert pk.hw_structural_ok(24)
    assert not pk.hw_structural_ok(5000)
    with pytest.raises(ValueError, match="fused CSS"):
        pk.css_errors(2048, 1, True, jnp.zeros((1, 2050)), jnp.zeros((1, 8)),
                      jnp.zeros((1,)))
    with pytest.raises(ValueError, match="fused Holt-Winters"):
        pk.hw_additive_sse(jnp.zeros((1, 3)), jnp.zeros((1, 16)), 5000,
                           interpret=True)
    # auto never picks pallas for a structurally unsupported config
    assert resolve_backend("auto", jnp.float32, 100, structural_ok=False) == "scan"


def _gappy(b, t, seed=0, edge_nans=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(b, t)).cumsum(axis=1).astype(np.float32)
    gaps = rng.random(size=(b, t)) < 0.25
    x[gaps] = np.nan
    if edge_nans:
        x[0, :3] = np.nan   # leading edge
        x[1, -4:] = np.nan  # trailing edge
        x[2, :] = np.nan    # all-NaN series
    return jnp.asarray(x)


@pytest.mark.parametrize("t", [
    37, pytest.param(200, marks=pytest.mark.slow)])  # the long chain
# runs in ci.sh's unfiltered pass
def test_fill_linear_chain_matches_portable(t):
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(6, t, seed=11)
    f_ref = jax.vmap(uv.fill_linear)(y)
    d_ref = jax.vmap(lambda v: uv.differences_at_lag(v, 1))(f_ref)
    l_ref = jax.vmap(lambda v: uv.lag(v, 1))(f_ref)
    f, d, lg = pk.fill_linear_chain(y, interpret=True)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(l_ref), rtol=1e-6, atol=1e-6)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_fill_linear_chain_chunked_long_series():
    from spark_timeseries_tpu.ops import univariate as uv

    # time axis spanning multiple VMEM chunks: carries must cross boundaries
    y = _gappy(3, 2 * pk._CHUNK_T + 57, seed=12)
    f_ref = jax.vmap(uv.fill_linear)(y)
    f, d, lg = pk.fill_linear_chain(y, interpret=True)
    np.testing.assert_allclose(np.asarray(f), np.asarray(f_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(d[:, 1:]), np.asarray((f_ref[:, 1:] - f_ref[:, :-1])),
        rtol=1e-6, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(lg[:, 1:]), np.asarray(f_ref[:, :-1]),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("t", [64, 333])
def test_batch_autocorr_matches_portable(t):
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(5, t, seed=13, edge_nans=False)
    ref = uv.batch_autocorr(7, backend="scan")(y)
    got = pk.batch_autocorr(y, 7, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_batch_autocorr_chunked_long_series():
    y = _gappy(3, pk._CHUNK_T + 100, seed=14, edge_nans=False)
    from spark_timeseries_tpu.ops import univariate as uv

    ref = uv.batch_autocorr(5, backend="scan")(y)
    got = pk.batch_autocorr(y, 5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_fill_linear_fill_only_matches_portable():
    # the singleton-output variant (no difference/lag stores) — regression
    # for the pallas_call sequence-return handling
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(5, 90, seed=15)
    f = pk.fill_linear(y, interpret=True)
    ref = jax.vmap(uv.fill_linear)(y)
    np.testing.assert_allclose(np.asarray(f), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_fold_unfold_roundtrip():
    from spark_timeseries_tpu.ops.layout import fold_panel, unfold_panel

    y = _gappy(5, 333, seed=21)
    fp = fold_panel(y)
    assert fp.shape == (5, 333)
    back = unfold_panel(fp)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(y))


def test_folded_panel_is_a_pytree():
    from spark_timeseries_tpu.ops.layout import FoldedPanel, fold_panel

    y = _gappy(4, 64, seed=22)
    fp = fold_panel(y)

    @jax.jit
    def through(p):
        return FoldedPanel(p.data * 2.0, p.b, p.t)

    out = through(fp)
    assert isinstance(out, FoldedPanel)
    assert (out.b, out.t) == (fp.b, fp.t)
    np.testing.assert_allclose(np.asarray(out.data), np.asarray(fp.data) * 2.0)


@pytest.mark.parametrize("t", [90, 2 * pk._CHUNK_T + 57])
def test_fill_chain_folded_matches_natural(t):
    from spark_timeseries_tpu.ops.layout import fold_panel, unfold_panel

    y = _gappy(5, t, seed=23)
    f_ref, d_ref, l_ref = pk.fill_linear_chain(y, interpret=True)
    fps = pk.fill_linear_chain_folded(fold_panel(y), interpret=True)
    for fp, ref in zip(fps, (f_ref, d_ref, l_ref)):
        np.testing.assert_allclose(
            np.asarray(unfold_panel(fp)), np.asarray(ref), rtol=1e-6, atol=1e-6
        )


@pytest.mark.parametrize("outputs", [("diff", "lag"), ("lag",), ("lag", "filled")])
def test_fill_chain_output_selection(outputs):
    from spark_timeseries_tpu.ops.layout import fold_panel, unfold_panel

    y = _gappy(5, 200, seed=24)
    full = dict(zip(("filled", "diff", "lag"), pk.fill_linear_chain(y, interpret=True)))
    fps = pk.fill_linear_chain_folded(fold_panel(y), outputs, interpret=True)
    assert len(fps) == len(outputs)
    for name, fp in zip(outputs, fps):
        np.testing.assert_allclose(
            np.asarray(unfold_panel(fp)), np.asarray(full[name]),
            rtol=1e-6, atol=1e-6,
        )


def test_fill_chain_output_selection_rejects_unknown():
    from spark_timeseries_tpu.ops.layout import fold_panel

    y = _gappy(3, 50, seed=25)
    with pytest.raises(ValueError, match="subset"):
        pk.fill_linear_chain_folded(fold_panel(y), ("diff", "bogus"))
    with pytest.raises(ValueError, match="subset"):
        pk.fill_linear_chain_folded(fold_panel(y), ())


@pytest.mark.parametrize("t", [200, pk._CHUNK_T + 100])
def test_batch_autocorr_folded_matches_natural(t):
    from spark_timeseries_tpu.ops.layout import fold_panel

    y = _gappy(5, t, seed=26, edge_nans=False)
    ref = pk.batch_autocorr(y, 7, interpret=True)
    got = pk.batch_autocorr_folded(fold_panel(y), 7, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_univariate_dispatch_accepts_folded_off_tpu():
    # off-TPU (this suite is CPU-pinned) the folded input falls back to the
    # portable path via unfold, preserving results and — for the chain —
    # returning folded outputs
    from spark_timeseries_tpu.ops import univariate as uv
    from spark_timeseries_tpu.ops.layout import FoldedPanel, fold_panel, unfold_panel

    y = _gappy(4, 96, seed=27, edge_nans=False)
    fp = fold_panel(y)
    ref = uv.batch_autocorr(5, backend="scan")(y)
    got = uv.batch_autocorr(5)(fp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5, atol=1e-5)

    f_ref, d_ref, l_ref = uv.batch_fill_linear_chain(y, backend="scan")
    outs = uv.batch_fill_linear_chain(fp, outputs=("diff", "filled"))
    assert all(isinstance(o, FoldedPanel) for o in outs)
    np.testing.assert_allclose(np.asarray(unfold_panel(outs[0])), np.asarray(d_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(unfold_panel(outs[1])), np.asarray(f_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_batch_fill_chain_outputs_natural_subset():
    from spark_timeseries_tpu.ops import univariate as uv

    y = _gappy(4, 80, seed=28)
    f_ref, d_ref, l_ref = uv.batch_fill_linear_chain(y, backend="scan")
    d, = uv.batch_fill_linear_chain(y, backend="scan", outputs=("diff",))
    np.testing.assert_allclose(np.asarray(d), np.asarray(d_ref), rtol=1e-6, atol=1e-6)
