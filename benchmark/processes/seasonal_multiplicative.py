"""Hourly POSITIVE series whose seasonal swing scales with a level that
wanders (load, traffic, call volume): one draw of every parameter per row.

``y_t = lev_t s_{t mod m} (1 + sigma eps_t)`` with the level a geometric
random walk with drift, ``lev_t = L0 exp(sum_{u <= t} (g + tau eta_u))``,
and a daily profile of two harmonics, ``s_h = exp(A sin(2 pi h / m + phi1) +
B sin(4 pi h / m + phi2))`` over its mean (so the profile averages 1, what
the model's seed assumes).  Per row: ``L0`` log-uniform on ``level``, ``g``
uniform on ``drift``, ``tau`` uniform on ``level_noise``, ``A`` uniform on
``amplitude``, ``B`` uniform on ``amplitude2``, both phases uniform on the
circle, ``sigma`` uniform on ``noise``; ``eps`` and ``eta`` standard normal.
The level's noise against the observation's (``tau / sigma``) is what
identifies the model's ``alpha``: rows are not one generating point, and the
optimum is not at ``alpha`` = 0 as a deterministic level's is.
"""

import jax
import jax.numpy as jnp

PARAMS = ("level", "drift", "level_noise", "amplitude", "amplitude2",
          "phase1", "phase2", "noise")


def draw_params(key, n_rows: int, p: dict):
    """``[n_rows, 8]`` f32 rows ``[L0, g, tau, A, B, phi1, phi2, sigma]``
    (:data:`PARAMS`)."""
    keys = jax.random.split(key, len(PARAMS))

    def uniform(k, lo, hi):
        return jax.random.uniform(k, (n_rows,), jnp.float32, lo, hi)

    lo, hi = p["level"]
    turn = (0.0, 2.0 * jnp.pi)
    return jnp.stack([
        jnp.exp(uniform(keys[0], jnp.log(lo), jnp.log(hi))),
        uniform(keys[1], *p["drift"]), uniform(keys[2], *p["level_noise"]),
        uniform(keys[3], *p["amplitude"]), uniform(keys[4], *p["amplitude2"]),
        uniform(keys[5], *turn), uniform(keys[6], *turn),
        uniform(keys[7], *p["noise"])], axis=1)


def profile(par, m: int):
    """``[n_rows, m]``: the rows' seasonal factors, mean 1."""
    h = 2.0 * jnp.pi * jnp.arange(m, dtype=jnp.float32)[None, :] / m
    a, b, phi1, phi2 = (par[:, i:i + 1] for i in range(3, 7))
    s = jnp.exp(a * jnp.sin(h + phi1) + b * jnp.sin(2.0 * h + phi2))
    return s / jnp.mean(s, axis=1, keepdims=True)


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    m = int(p["period"])
    k_par, k_level, k_noise = jax.random.split(key, 3)
    par = draw_params(k_par, n_rows, p)
    level0, drift, tau, sigma = (par[:, i:i + 1] for i in (0, 1, 2, 7))
    eta = jax.random.normal(k_level, (n_rows, n_time), jnp.float32)
    level = level0 * jnp.exp(jnp.cumsum(drift + tau * eta, axis=1))
    season = jnp.tile(profile(par, m), (1, -(-n_time // m)))[:, :n_time]
    eps = jax.random.normal(k_noise, (n_rows, n_time), jnp.float32)
    return level * season * (1.0 + sigma * eps)
