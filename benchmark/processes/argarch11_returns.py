"""Daily returns of a ticker universe with a conditional mean: AR(1) over
Gaussian GARCH(1,1) innovations, one draw of ``(c, phi, omega, alpha,
beta)`` per row.

``y_t = c + phi y_{t-1} + r_t``, ``r_t = sqrt(h_t) e_t``, ``h_t = omega +
alpha r_{t-1}^2 + beta h_{t-1}`` with standard normal ``e`` — the recursion
of ``garch11_returns`` under a mean equation, matched to the Gaussian
likelihood the model maximises, in ``garch11_returns``' units (decimals: a
1% day is 0.01; the configuration's ``mean_return`` and ``daily_vol`` ranges
state them).  Per row: ``phi`` uniform on ``phi`` (daily returns: individual
stocks slightly negative, indices and portfolios 0.1-0.35; Campbell, Lo &
MacKinlay 1997, ch. 2), the mean daily return ``mu`` uniform on
``mean_return`` and ``c = mu (1 - phi)``; ``alpha`` uniform on ``alpha``,
the persistence ``alpha + beta`` uniform on ``persistence``, the
unconditional daily volatility log-uniform on ``daily_vol`` and ``omega =
vol^2 (1 - alpha - beta)`` (``garch11_returns``' three ranges).  The pair
starts at its unconditional mean and variance and ``burn_in`` steps are
thrown away.
"""

import jax
import jax.numpy as jnp


def draw_params(key, n_rows: int, p: dict):
    """``[n_rows, 5]`` f32 rows ``[c, phi, omega, alpha, beta]``, the layout
    of ``models.garch.fit_argarch``."""
    k_phi, k_mu, k_alpha, k_pers, k_vol = jax.random.split(key, 5)

    def uniform(k, lo, hi):
        return jax.random.uniform(k, (n_rows,), jnp.float32, lo, hi)

    phi = uniform(k_phi, *p["phi"])
    mu = uniform(k_mu, *p["mean_return"])
    alpha = uniform(k_alpha, *p["alpha"])
    persistence = uniform(k_pers, *p["persistence"])
    lo, hi = p["daily_vol"]
    vol = jnp.exp(uniform(k_vol, jnp.log(lo), jnp.log(hi)))
    return jnp.stack([mu * (1.0 - phi), phi,
                      vol * vol * (1.0 - persistence), alpha,
                      persistence - alpha], axis=1)


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    k_par, k_noise = jax.random.split(key)
    c, phi, omega, alpha, beta = draw_params(k_par, n_rows, p).T
    burn_in = int(p["burn_in"])
    e = jax.random.normal(k_noise, (burn_in + n_time, n_rows), jnp.float32)

    def step(carry, e_t):
        h_prev, r2_prev, y_prev = carry
        h_t = omega + alpha * r2_prev + beta * h_prev
        r_t = jnp.sqrt(h_t) * e_t
        y_t = c + phi * y_prev + r_t
        return (h_t, r_t * r_t, y_t), y_t

    h0 = omega / (1.0 - alpha - beta)
    _, y = jax.lax.scan(step, (h0, h0, c / (1.0 - phi)), e)
    return y[burn_in:].T
