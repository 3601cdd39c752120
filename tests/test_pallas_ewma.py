"""The EWMA kernels against the portable ``lax.scan`` implementations.
Interpret mode, as ``test_pallas_css.py`` says.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_timeseries_tpu.ops import pallas_kernels as pk


def test_ewma_sse_and_grad_matches_scan():
    from spark_timeseries_tpu.models import ewma

    b, t = 5, 61
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    nv = jnp.asarray([t, t - 6, t, t - 11, t - 1], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    xz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], x, 0.0)
    alpha = jnp.asarray(rng.uniform(0.1, 0.9, b).astype(np.float32))

    ref = jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(alpha, xz, nv)
    got = pk.ewma_sse(alpha, xz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def loss_scan(A):
        return jnp.sum(jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(A, xz, nv))

    def loss_pal(A):
        return jnp.sum(pk.ewma_sse(A, xz, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(alpha)
    g_got = jax.grad(loss_pal)(alpha)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t", [
    61, pytest.param(2100, marks=pytest.mark.slow)])  # single-chunk and
# chunked grids; the chunked grid runs in ci.sh's unfiltered pass
def test_ewma_data_gradient_matches_scan(t):
    # ADVICE r3: jax.grad of the fused EWMA objectives w.r.t. the DATA used
    # to silently return zeros; the adjoint kernel now emits the true x
    # cotangent when (and only when) x is perturbed
    from spark_timeseries_tpu.models import ewma

    b = 4
    rng = np.random.default_rng(23)
    x = jnp.asarray(np.cumsum(rng.normal(size=(b, t)), axis=1).astype(np.float32))
    nv = jnp.asarray([t, t - 7, t - 1, max(t - t // 3, 3)], jnp.int32)
    alpha = jnp.asarray(rng.uniform(0.2, 0.8, b).astype(np.float32))
    start = (t - nv).astype(jnp.float32)
    xz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], x, 0.0)

    def sse_scan(x_):
        return jnp.sum(jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(alpha, x_, nv))

    def sse_pal(x_):
        return jnp.sum(pk.ewma_sse(alpha, x_, nv, interpret=True))

    gx_ref = jax.grad(sse_scan)(xz)
    gx_got = jax.grad(sse_pal)(xz)
    np.testing.assert_allclose(np.asarray(gx_got), np.asarray(gx_ref),
                               rtol=1e-4, atol=1e-4)

    # the smoothing op's x cotangent (weighted-sum pullback)
    w = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))

    def sm_scan(x_):
        s = jax.vmap(lambda a, v, n: ewma.smooth(a, v, n))(alpha, x_, nv)
        return jnp.sum(w * s)

    def sm_pal(x_):
        return jnp.sum(w * pk.ewma_smooth(alpha, x_, start, interpret=True))

    np.testing.assert_allclose(
        np.asarray(jax.grad(sm_pal)(xz)), np.asarray(jax.grad(sm_scan)(xz)),
        rtol=1e-4, atol=1e-4,
    )


def test_ewma_fit_backend_pallas_matches_scan():
    from spark_timeseries_tpu.models import ewma

    rng = np.random.default_rng(22)
    b, t = 6, 90
    x = np.cumsum(rng.normal(size=(b, t)), axis=1).astype(np.float32)
    x[1, :13] = np.nan  # ragged head
    r_scan = ewma.fit(jnp.asarray(x), backend="scan")
    r_pal = ewma.fit(jnp.asarray(x), backend="pallas-interpret")
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=1e-3, atol=1e-3
    )


def test_chunked_ewma_matches_scan_long_series():
    from spark_timeseries_tpu.models import ewma

    b, t = 3, 2100
    rng = np.random.default_rng(44)
    x = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    nv = jnp.asarray([t, t - 1100, t - 13], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    xz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], x, 0.0)
    alpha = jnp.asarray(rng.uniform(0.1, 0.9, b).astype(np.float32))

    ref = jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(alpha, xz, nv)
    got = pk.ewma_sse(alpha, xz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5)

    g_ref = jax.grad(lambda A: jnp.sum(
        jax.vmap(lambda a, v, n: ewma.sse(a, v, n))(A, xz, nv)))(alpha)
    g_got = jax.grad(lambda A: jnp.sum(pk.ewma_sse(A, xz, nv, interpret=True)))(alpha)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-4, atol=2e-4)
