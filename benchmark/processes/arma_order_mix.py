"""Integrated ARMA series whose ORDER is drawn per row: the panel an order
search is for.

``y = cumsum(drift + x)`` with ``phi(L) x_t = theta(L) e_t``, unit normal
innovations.  Per row ONE draw of the generating order ``(p, 1, q)`` by the
shares of ``orders`` (each entry ``[p, q, share]``) and of its polynomials:
``phi(L) = (1 - r_1 L)(1 - r_2 L)`` from real roots ``r`` uniform on
``ar_root`` (the first ``p`` of them, the others 0), ``theta(L) = (1 + s_1
L)(1 + s_2 L)`` from ``s`` of magnitude uniform on ``ma_root_abs`` = ``[lo,
hi]``
with a fair sign (so ``|s| >= lo``: a drawn term is never a near-zero one),
the first ``q`` of them.  Every row is stationary and invertible.  The first
``burn_in`` steps of ``x`` are thrown away, then ``drift + x`` is integrated
once from zero.  In the package's convention ``x_t = phi_1 x_{t-1} + phi_2
x_{t-2} + e_t + theta_1 e_{t-1} + theta_2 e_{t-2}``: ``phi = (r_1 + r_2,
-r_1 r_2)``, ``theta = (s_1 + s_2, s_1 s_2)``.
"""

import jax
import jax.numpy as jnp


def draw_params(key, n_rows: int, p: dict):
    """``(order index [n_rows] int32, coefficients [n_rows, 4] f32)``: the
    row's entry of ``orders`` and ``[phi_1, phi_2, theta_1, theta_2]`` with
    zeros where its order has no such term."""
    k_order, k_ar, k_ma, k_sign = jax.random.split(key, 4)
    orders = jnp.asarray(p["orders"], jnp.float32)  # [n, 3]: p, q, share
    which = jax.random.choice(k_order, orders.shape[0], (n_rows,),
                              p=orders[:, 2] / jnp.sum(orders[:, 2]))
    pq = orders[which, :2]
    live = jnp.arange(2)[None, :]
    r = jax.random.uniform(k_ar, (n_rows, 2), jnp.float32, *p["ar_root"])
    r = jnp.where(live < pq[:, :1], r, 0.0)
    s = jax.random.uniform(k_ma, (n_rows, 2), jnp.float32, *p["ma_root_abs"])
    s = s * jnp.where(jax.random.bernoulli(k_sign, 0.5, (n_rows, 2)),
                      1.0, -1.0)
    s = jnp.where(live < pq[:, 1:], s, 0.0)
    coef = jnp.stack([r[:, 0] + r[:, 1], -r[:, 0] * r[:, 1],
                      s[:, 0] + s[:, 1], s[:, 0] * s[:, 1]], axis=1)
    return which.astype(jnp.int32), coef


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    burn_in, drift = int(p["burn_in"]), float(p["drift"])
    k_par, k_noise = jax.random.split(key)
    _, coef = draw_params(k_par, n_rows, p)
    phi1, phi2, th1, th2 = (coef[:, i] for i in range(4))
    e = jax.random.normal(k_noise, (n_rows, burn_in + n_time + 2),
                          jnp.float32)
    w = e[:, 2:] + th1[:, None] * e[:, 1:-1] + th2[:, None] * e[:, :-2]

    def step(carry, w_t):
        x1, x2 = carry
        x_t = phi1 * x1 + phi2 * x2 + w_t
        return (x_t, x1), x_t

    zero = jnp.zeros((n_rows,), jnp.float32)
    _, x = jax.lax.scan(step, (zero, zero), w.T)
    return jnp.cumsum(drift + x.T[:, burn_in:], axis=1)
