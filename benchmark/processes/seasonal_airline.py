"""Hourly series of the airline model: ARIMA(0,1,1)(0,1,1)_s, one draw of
``(theta, seasonal theta)`` per row.

``(1 - L)(1 - L^s) y_t = (1 + theta L)(1 + THETA L^s) e_t`` with unit normal
innovations, in the package's sign convention (Box-Jenkins-Reinsel write the
same model with minus signs: their Series G estimates 0.40 and 0.61 are
``theta = -0.40``, ``THETA = -0.61`` here).  Per row ``theta`` is uniform on
``theta`` and ``THETA`` on ``seasonal_theta``; every row is invertible.  The
moving average ``w_t = e_t + theta e_{t-1} + THETA e_{t-s} + theta THETA
e_{t-s-1}`` is integrated once at lag 1 and once at lag ``s``, both from
zero, and the first ``burn_in`` steps of the integrated series are thrown
away, so that a row starts at the level and the seasonal profile it has
reached by then.
"""

import jax
import jax.numpy as jnp


def draw_params(key, n_rows: int, p: dict):
    """``[n_rows, 2]`` f32 rows ``[theta, THETA]``: columns 1 and 2 of what
    ``models.arima.fit(..., seasonal=(0, 1, 1, s))`` returns."""
    k_theta, k_seasonal = jax.random.split(key)
    return jnp.stack(
        [jax.random.uniform(k_theta, (n_rows,), jnp.float32, *p["theta"]),
         jax.random.uniform(k_seasonal, (n_rows,), jnp.float32,
                            *p["seasonal_theta"])], axis=1)


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    s, burn_in = int(p["period"]), int(p["burn_in"])
    k_par, k_noise = jax.random.split(key)
    par = draw_params(k_par, n_rows, p)
    theta, seasonal = par[:, :1], par[:, 1:]
    n = burn_in + n_time
    e = jax.random.normal(k_noise, (n_rows, n + s + 1), jnp.float32)
    w = (e[:, s + 1:] + theta * e[:, s:-1] + seasonal * e[:, 1:-s]
         + theta * seasonal * e[:, :-s - 1])
    x = jnp.cumsum(w, axis=1)  # integrated at lag 1
    # integrated at lag s: a running sum down each of the s phases
    pad = (-n) % s
    phases = jnp.pad(x, ((0, 0), (0, pad))).reshape(n_rows, -1, s)
    y = jnp.cumsum(phases, axis=1).reshape(n_rows, -1)[:, :n]
    return y[:, burn_in:]
