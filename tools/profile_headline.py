"""Profile the headline ARIMA fit: count objective evaluations per L-BFGS
iteration and measure per-pass costs on the real chip.

Diagnostic only (VERDICT round 2, next-round item 1a): quantify where the
headline latency goes so the optimizer levers (linesearch evals, fused
fwd+bwd, converged-row compaction) are applied where they pay.  Uses the
PRODUCTION optimizer's ``count_evals`` instrumentation — there is no forked
copy of the algorithm to drift out of date.

Usage: python tools/profile_headline.py [--b 25088] [--t 1000] [--iters 60]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen_arima_panel
from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.models.base import maybe_align
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--b", type=int, default=25088)
    ap.add_argument("--t", type=int, default=1000)
    ap.add_argument("--iters", type=int, default=60)
    args = ap.parse_args()

    b, t = args.b, args.t
    order = (1, 1, 1)
    print(f"devices: {jax.devices()}", file=sys.stderr)
    y = jnp.asarray(gen_arima_panel(b, t, seed=0))
    jax.block_until_ready(y)

    # objective exactly as models.arima._fit_program builds it (pallas path,
    # dense panel)
    @jax.jit
    def prep(yb):
        ya, nv0 = maybe_align(yb, "dense")
        yd = jax.vmap(lambda v: arima._difference(v, 1))(ya)
        nvd = nv0 - 1
        init = pk.hr_init(yd, order, True, nvd)
        return yd, nvd, init

    yd, nvd, init = prep(y)
    jax.block_until_ready(init)
    t0 = time.perf_counter()
    out = prep(y)
    jax.block_until_ready(out[2])
    print(f"prep (diff + fused HR init): {(time.perf_counter() - t0) * 1e3:.1f} ms"
          " (includes one dispatch round trip)")
    n_eff = jnp.maximum(nvd - 1, 1).astype(yd.dtype)

    # data rides as jit ARGUMENTS throughout: a closure would embed the
    # panel as an HLO constant
    def objective(P, yd, nvd, n_eff):
        return pk.css_neg_loglik(P, yd, order, True, nvd) / n_eff

    # -- per-pass costs (dispatch round trip included) ---------------------
    fwd = jax.jit(lambda P, yd, nvd, ne: jnp.sum(objective(P, yd, nvd, ne)))
    vgj = jax.jit(lambda P, yd, nvd, ne: jax.vjp(
        lambda P_: objective(P_, yd, nvd, ne), P)[1](jnp.ones((b,), yd.dtype))[0])

    jax.block_until_ready(fwd(init, yd, nvd, n_eff))
    jax.block_until_ready(vgj(init, yd, nvd, n_eff))
    N = 10
    t0 = time.perf_counter()
    for _ in range(N):
        jax.block_until_ready(fwd(init, yd, nvd, n_eff))
    t_fwd = (time.perf_counter() - t0) / N
    t0 = time.perf_counter()
    for _ in range(N):
        jax.block_until_ready(vgj(init, yd, nvd, n_eff))
    t_vg = (time.perf_counter() - t0) / N
    print(f"fwd pass: {t_fwd*1e3:.1f} ms   value+grad: {t_vg*1e3:.1f} ms "
          "(each includes one dispatch round trip)")

    # -- instrumented full fit (the PRODUCTION optimizer) ------------------
    run = jax.jit(lambda x0, yd, nvd, ne: optim.minimize_lbfgs_batched(
        lambda P: objective(P, yd, nvd, ne), x0,
        max_iters=args.iters, tol=1e-4, count_evals=True))
    out = run(init, yd, nvd, n_eff)
    jax.block_until_ready(out[0].x)
    t0 = time.perf_counter()
    res, info = run(init, yd, nvd, n_eff)
    jax.block_until_ready(res.x)
    dt = time.perf_counter() - t0
    iters_np = np.asarray(res.iters)
    conv = np.asarray(res.converged)
    outer = int(iters_np.max())
    ls = np.asarray(info["ls_evals"])[:outer]
    n_ls = int(ls.sum())
    print(f"fit wall: {dt:.3f}s  ({b/dt:.0f} series/s raw, "
          f"{b*conv.mean()/dt:.0f} converged-only)")
    print(f"outer iterations run: {outer}  (batch moves in lockstep)")
    print(f"converged frac: {conv.mean():.4f}")
    print(f"ls evals per outer iter: {ls.tolist()}")
    print(f"linesearch evals total: {n_ls}  (avg {n_ls/max(outer,1):.2f}/iter)")
    print(f"objective passes: {n_ls} fwd (linesearch) + {outer+1} vg")
    if int(info["cap"]):
        print(f"compaction: engaged at iter {int(info['compact_at'])} "
              f"(cap {int(info['cap'])})")
    else:
        print("compaction: not enabled in this tool (no straggler_fun)")
    qs = [50, 75, 90, 95, 99, 100]
    print("per-row iters quantiles:",
          {q: int(np.percentile(iters_np, q)) for q in qs})


if __name__ == "__main__":
    main()
