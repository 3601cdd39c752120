"""Integrated ARMA(1,1): the process ARIMA(1,1,1) describes exactly.

``y = cumsum(x)``, ``x_t = phi x_{t-1} + e_t + theta e_{t-1}``, unit normal
innovations (the shape of ``chip_smoke.make_panel`` and
``bench._arima_panel_on_device``, copied here so that the yardstick does not
move with them).
"""

import jax
import jax.numpy as jnp


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    phi, theta = float(p["phi"]), float(p["theta"])
    e = jax.random.normal(key, (n_rows, n_time), jnp.float32)

    def step(carry, e_t):
        x_prev, e_prev = carry
        x_t = phi * x_prev + e_t + theta * e_prev
        return (x_t, e_t), x_t

    _, x = jax.lax.scan(step, (e[:, 0], e[:, 0]), e[:, 1:].T)
    x = jnp.concatenate([e[:, :1], x.T], axis=1)
    return jnp.cumsum(x, axis=1)
