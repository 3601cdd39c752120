"""Long-series (sequence-parallel) tour: one series too big for one chip.

The reference never shards a single series — a series is one JVM vector, so
its length is bounded by executor memory (SURVEY.md Section 5.7).  Here the
time axis of a ``[keys, time]`` panel is split across the ``time`` axis of a
2-D device mesh and within-series work runs as local kernels + ICI
collectives under ``shard_map``: moments/autocorrelation (halo exchange for
lagged cross terms), linear-interpolation fill (carry hand-off of the
nearest-valid summaries), differencing, and EWMA smoothing (log-depth
affine-carry scan).

Runs anywhere: with no accelerator attached, force an 8-device CPU mesh —
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_series.py
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from spark_timeseries_tpu.ops import seqparallel as sp  # noqa: E402
from spark_timeseries_tpu.ops import univariate as uv  # noqa: E402
from spark_timeseries_tpu.parallel import mesh as meshlib  # noqa: E402


def main():
    n_dev = len(jax.devices())
    if n_dev < 2:
        print(f"only {n_dev} device visible — sequence parallelism needs a "
              "time-sharded mesh; rerun with\n  JAX_PLATFORMS=cpu "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8 "
              "python examples/long_series.py")
        return
    mesh = meshlib.default_mesh(time_shards=2)
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"on {n_dev} {jax.devices()[0].platform} device(s)")

    # a gappy panel: series axis AND time axis both sharded
    rng = np.random.default_rng(0)
    keys, t = 8, 4096
    vals = rng.normal(size=(keys, t)).cumsum(axis=1).astype(np.float32)
    vals[rng.random((keys, t)) < 0.1] = np.nan
    panel = jax.device_put(jnp.asarray(vals), meshlib.series_sharding(mesh))

    # distributed fill -> difference -> lag feature chain (each shard fills
    # from GLOBAL bracketing observations; the lag crosses shard boundaries
    # through a one-column halo)
    filled, diff, lagged = sp.sp_fill_linear_chain_sharded(mesh, panel)
    print(f"filled NaNs: {int(jnp.isnan(panel).sum())} -> "
          f"{int(jnp.isnan(filled).sum())} (edges only)")

    # distributed moments + autocorrelation (psum + halo over ICI)
    stats = sp.sp_moments_sharded(mesh, filled)
    ac = sp.sp_autocorr_sharded(mesh, jnp.nan_to_num(filled), 5)
    print(f"mean[0]={float(stats['mean'][0]):+.3f}  "
          f"autocorr[0,:3]={np.asarray(ac[0][:3]).round(4)}")

    # cross-check against the single-device kernels
    ref = uv.batch_autocorr(5, backend="scan")(jnp.nan_to_num(
        jax.vmap(uv.fill_linear)(jnp.asarray(vals))))
    np.testing.assert_allclose(np.asarray(ac), np.asarray(ref), atol=1e-4)
    print("sequence-parallel results match the unsharded kernels")

    # time-sharded model FIT: the whole CSS objective (differencing,
    # Yule-Walker init, the error recursion as a log-depth affine scan, the
    # batched L-BFGS) runs with the series split across the time axis — the
    # reference cannot fit a series longer than one executor's memory.
    # (A fresh dense panel: the filled one keeps its EDGE NaNs by design,
    # and zero-stuffing those would corrupt the fit.)
    dense = jax.device_put(
        jnp.asarray(rng.normal(size=(keys, t)).cumsum(axis=1)
                    .astype(np.float32)),
        meshlib.series_sharding(mesh))
    fit = sp.sp_arima_fit(mesh, dense, (1, 1, 1))
    print(f"time-sharded ARIMA(1,1,1): params[0]="
          f"{np.asarray(fit.params[0]).round(4)}  "
          f"converged={float(jnp.mean(fit.converged.astype(jnp.float32))):.2f}")


if __name__ == "__main__":
    main()
