"""The nine-order grid and the benchmark's order-mix panel that the fused
order search's test files share (``test_grid_lockstep.py``, the kernels;
``test_grid_fit.py``, the fits)."""

import jax
import numpy as np

from benchmark.processes import arma_order_mix
from spark_timeseries_tpu.models import arima

ORDERS = [(p, 1, q) for p in range(3) for q in range(3)]
SPECS = tuple((o, None) for o in ORDERS)
K, K_MAX = len(ORDERS), 5
WIDTH = K_MAX + arima.GRID_PACK_COLS
MIX = {"orders": [[0, 0, 0.10], [1, 0, 0.20], [0, 1, 0.25], [1, 1, 0.25],
                  [2, 0, 0.05], [0, 2, 0.05], [2, 2, 0.10]],
       "ar_root": [0.2, 0.8], "ma_root_abs": [0.15, 0.6], "drift": 0.1,
       "burn_in": 200}


def mix_panel(rows, n_time, seed):
    return np.asarray(jax.jit(
        lambda key: arma_order_mix.rows(key, rows, n_time, MIX))(
            jax.random.key(seed)))
