"""Daily returns of a ticker universe: Gaussian GARCH(1,1), one draw of
``(omega, alpha, beta)`` per row.

``h_t = omega + alpha r_{t-1}^2 + beta h_{t-1}``, ``r_t = sqrt(h_t) e_t``
with standard normal ``e`` (matched to the Gaussian likelihood the model
maximises, so the fitted parameters estimate the drawn ones), in decimal
units (a 1% day is 0.01).  Tickers are not one generating point: per row,
``alpha`` is uniform on ``alpha``, the persistence ``alpha + beta`` uniform
on ``persistence``, the unconditional daily volatility log-uniform on
``daily_vol`` and ``omega = vol^2 (1 - alpha - beta)``.  The recursion
starts at the unconditional variance and ``burn_in`` steps are thrown away.
"""

import jax
import jax.numpy as jnp


def draw_params(key, n_rows: int, p: dict):
    """``[n_rows, 3]`` f32 rows ``[omega, alpha, beta]``, the layout of
    ``models.garch``."""
    k_alpha, k_pers, k_vol = jax.random.split(key, 3)

    def uniform(k, lo, hi):
        return jax.random.uniform(k, (n_rows,), jnp.float32, lo, hi)

    alpha = uniform(k_alpha, *p["alpha"])
    persistence = uniform(k_pers, *p["persistence"])
    lo, hi = p["daily_vol"]
    vol = jnp.exp(uniform(k_vol, jnp.log(lo), jnp.log(hi)))
    return jnp.stack([vol * vol * (1.0 - persistence), alpha,
                      persistence - alpha], axis=1)


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    k_par, k_noise = jax.random.split(key)
    par = draw_params(k_par, n_rows, p)
    omega, alpha, beta = par[:, 0], par[:, 1], par[:, 2]
    burn_in = int(p["burn_in"])
    e = jax.random.normal(k_noise, (burn_in + n_time, n_rows), jnp.float32)

    def step(carry, e_t):
        h_prev, r2_prev = carry
        h_t = omega + alpha * r2_prev + beta * h_prev
        r_t = jnp.sqrt(h_t) * e_t
        return (h_t, r_t * r_t), r_t

    h0 = omega / (1.0 - alpha - beta)
    _, r = jax.lax.scan(step, (h0, h0), e)
    return r[burn_in:].T
