"""Hourly series with a daily AND a weekly cycle around a level, with
serially correlated noise (a meter, a cell site, a SKU): one draw of every
parameter per row.

``y_t = mu + sum_i sum_{h <= K_i} a_ih sin(2 pi h t / P_i + ph_ih) + u_t``
over the ``periods`` ``P_i`` with ``harmonics`` ``K_i``, and ``u`` an
ARMA(1,1) ``u_t = phi u_{t-1} + e_t + theta e_{t-1}`` with normal
innovations of s.d. ``sigma`` after ``burn_in`` steps.  Per row: ``mu``
log-uniform on ``level``; harmonic ``h`` of period ``i`` has amplitude
uniform on ``amplitude[i]`` times ``mu / h`` and a phase uniform on the
circle; ``phi`` uniform on ``phi``, ``theta`` uniform on ``theta``, ``sigma``
uniform on ``noise`` times ``mu``.  The harmonics are the model's own
columns, so the model describes a row exactly; rows are not one generating
point.
"""

import jax
import jax.numpy as jnp
import numpy as np


def basis(n_time: int, p: dict) -> np.ndarray:
    """``[2 sum(K), n_time]`` f32: ``sin`` and ``cos`` of every harmonic,
    the angles reduced in integers (exact at any ``t``), and ``1 / h``."""
    t = np.arange(n_time)
    rows, scale = [], []
    for period, k in zip(p["periods"], p["harmonics"]):
        for h in range(1, int(k) + 1):
            angle = 2.0 * np.pi * ((h * t) % int(period)) / int(period)
            rows += [np.sin(angle), np.cos(angle)]
            scale.append(1.0 / h)
    return np.asarray(rows, np.float32), np.asarray(scale, np.float32)


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    waves, inv_h = basis(n_time, p)
    burn = int(p.get("burn_in", 200))
    k_mu, k_amp, k_ph, k_phi, k_theta, k_sig, k_e = jax.random.split(key, 7)

    def uniform(k, lo, hi, shape=(n_rows, 1)):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    lo, hi = p["level"]
    mu = jnp.exp(uniform(k_mu, jnp.log(lo), jnp.log(hi)))
    # one amplitude range a period, every harmonic of it a draw of its own
    bounds = np.repeat(np.asarray(p["amplitude"], np.float32),
                       [int(k) for k in p["harmonics"]], axis=0)
    amp = (bounds[:, 0] + uniform(k_amp, 0.0, 1.0, (n_rows, len(inv_h)))
           * (bounds[:, 1] - bounds[:, 0])) * inv_h * mu
    phase = uniform(k_ph, 0.0, 2.0 * jnp.pi, (n_rows, len(inv_h)))
    # a sin(w t + ph) = a cos(ph) sin(w t) + a sin(ph) cos(w t)
    coef = jnp.stack([amp * jnp.cos(phase), amp * jnp.sin(phase)],
                     axis=2).reshape(n_rows, -1)
    season = jnp.dot(coef, waves, precision=jax.lax.Precision.HIGHEST)

    phi = uniform(k_phi, *p["phi"])[:, 0]
    theta = uniform(k_theta, *p["theta"])[:, 0]
    sigma = uniform(k_sig, *p["noise"]) * mu
    e = jax.random.normal(k_e, (n_rows, burn + n_time), jnp.float32)

    def step(carry, e_t):
        u_prev, e_prev = carry
        u_t = phi * u_prev + e_t + theta * e_prev
        return (u_t, e_t), u_t

    zero = jnp.zeros((n_rows,), jnp.float32)
    _, u = jax.lax.scan(step, (zero, zero), e.T)
    return mu + season + sigma * u[burn:].T
