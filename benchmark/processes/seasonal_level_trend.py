"""Hourly "M4-style" rows: level + linear trend + one sinusoidal season
with a random phase + white noise (``bench.gen_seasonal_panel``'s shape,
made on the device)."""

import jax
import jax.numpy as jnp


def rows(key, n_rows: int, n_time: int, p: dict):
    """``[n_rows, n_time]`` f32, traced inside the generator's jit."""
    k_phase, k_noise = jax.random.split(key)
    t = jnp.arange(n_time, dtype=jnp.float32)[None, :]
    phase = jax.random.uniform(k_phase, (n_rows, 1), jnp.float32,
                               0.0, 2.0 * jnp.pi)
    season = float(p["amplitude"]) * jnp.sin(
        2.0 * jnp.pi * t / float(p["period"]) + phase)
    noise = float(p["noise"]) * jax.random.normal(
        k_noise, (n_rows, n_time), jnp.float32)
    return float(p["level"]) + float(p["trend"]) * t + season + noise
