#!/usr/bin/env python3
"""Time the fit-objective kernels ALONE at forced series-block widths: the
forward ones, and the adjoints as the objectives call them.

    chiprun -- python tools/kernel_block_sweep.py            # on the chip
    python tools/kernel_block_sweep.py --compile-only        # here: Mosaic + VMEM

For each kernel (CSS ARIMA(1,1,1), Holt-Winters additive m=24 — and the
multiplicative model's as ``save_resid.mult`` / ``adjoint.mult`` — GARCH(1,1),
and its calls with an AR(1) mean equation as ``argarch_neg_loglik``),
mode and panel shape of the benchmark's cells (the stage-1 chunk and the
stage-2 compaction), R = 1, 2, 4 (``pallas_kernels.series_rows`` is bypassed
through the call functions' private ``_r``): milliseconds a call, ns a time
step and 1,024-series block, and whether every output is BIT-equal to R = 1's.
The adjoints (mode ``adjoint``; the seasonal lag set ``{1, 24, 25}`` as
``css_seasonal_neg_loglik`` beside the three) are the call the objective's
``custom_vjp`` makes — the cotangent formed in the kernel from the plane —
at the same forced widths.
The CSS calls with a SHARED DESIGN of 31 columns as an operand (PR 51: the
residual ``u = y - x @ beta'`` and ``-x' dS/du`` formed in VMEM) ride as
``sum.x`` / ``both.x`` / ``u.x`` (the residual panel alone) / ``adjoint.x``
over ``[rows, 960]``.
The order search's grid kernels (``css_grid_neg_loglik``: 9 orders over one
``[131072, 1000]`` panel, union lags {1, 2} on both sides) run at G = 1, 3, 9
orders a grid step, each at every R (modes ``sum.g1`` .. ``adjoint.g9``;
``ns_step_block`` is per ORDER there) and, as stage 2 sees them, as one
order over a gathered subset of the cells (``.cells``: a quarter of them,
flat).
One line a case to ``chiprun_out/kernel_block_sweep.jsonl`` and to stdout.
``--compile-only`` compiles every case for a described v5e and runs nothing
(no time is reported from it).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spark_timeseries_tpu.ops import pallas_kernels as pk  # noqa: E402

ROWS = (131072, 16384)
GRID_ORDERS = 9  # p, q in 0..2


def _planes(key, n, nsub, scale=1.0, loc=0.0):
    return loc + scale * jax.random.normal(key, (n, nsub, pk._LANES),
                                           jnp.float32)


def cases():
    """-> (name, mode, rows, t, make_args(key), call(r, *args))."""
    for rows in ROWS:
        nsub = rows // pk._LANES

        def css_args(key, nsub=nsub, rows=rows):
            k1, k2 = jax.random.split(key)
            par = (jnp.asarray([0.01, 0.5, 0.3], jnp.float32)
                   + 0.05 * jax.random.normal(k2, (rows, 3), jnp.float32))
            return (par, _planes(k1, 1000, nsub),
                    jnp.ones((1, nsub, pk._LANES), jnp.float32))

        for mode in ("sum", "both", "e"):
            yield ("css_neg_loglik", mode, rows, 999, css_args,
                   lambda r, par, y3, zb3, mode=mode: pk._css_fwd_call_f(
                       1, 1, False, mode, par, y3, zb3, 999, _r=r)[0])

        def css_adj_args(key, lags, t, rows=rows, nsub=nsub):
            # what _css_ss_f_bwd holds: the residuals of a "both" forward
            # (any error panel times the recursion alike) and gbar
            k1, k2, k3, k4 = jax.random.split(key, 4)
            k = 1 + len(pk._lags(lags[0])) + len(pk._lags(lags[1]))
            par3 = 0.1 * _planes(k2, k, nsub)
            tp, _, _ = pk._time_layout(t)
            return ((_planes(k1, tp, nsub), par3,
                     jnp.ones((1, nsub, pk._LANES), jnp.float32),
                     _planes(k3, tp, nsub), None),
                    jax.random.normal(k4, (rows,), jnp.float32))

        for name, lags, t in (("css_neg_loglik", (1, 1), 999),
                              ("css_seasonal_neg_loglik",
                               ((), (1, 24, 25)), 935)):
            yield (name, "adjoint", rows, t,
                   functools.partial(css_adj_args, lags=lags, t=t),
                   lambda r, resid, gbar, lags=lags, t=t, rows=rows:
                   [pk._fold(pk._css_ss_f_bwd(*lags, False, t, rows, resid,
                                              gbar, _r=r)[0])])

        # a shared design of 31 columns (PR 51; [rows, 960], the columns
        # padded to 32): the forward calls form u = y - x @ beta' in VMEM,
        # "both" writes it beside the errors, "u" alone; the adjoint leaves
        # its final adjoints in scratch and accumulates -x' dS/du from them
        # (modes ``sum.x`` / ``both.x`` / ``u.x`` / ``adjoint.x``)
        def css_x_args(key, nsub=nsub, rows=rows):
            k1, k2, k3 = jax.random.split(key, 3)
            par = jnp.concatenate([
                jnp.asarray([0.0, 0.5, 0.3], jnp.float32)
                + 0.05 * jax.random.normal(k2, (rows, 3), jnp.float32),
                jax.random.normal(k3, (rows, 32), jnp.float32)], axis=1)
            k4, k5 = jax.random.split(k1)
            return (par, _planes(k4, 960, nsub),
                    jnp.ones((1, nsub, pk._LANES), jnp.float32),
                    jax.random.normal(k5, (960, 32), jnp.float32))

        for mode in ("sum", "both", "u"):
            yield ("css_neg_loglik", f"{mode}.x", rows, 960, css_x_args,
                   lambda r, par, y3, zb3, x, mode=mode: pk._css_fwd_call_f(
                       1, 1, False, mode, par, y3, zb3, 960, _r=r, x=x)[0])

        def css_x_adj_args(key, nsub=nsub):
            # _css_ss_x_bwd's residuals: a plain adjoint's, 32 more planes
            # of parameters and the design in the marker's place
            (u3, par3, zb3, e3, _), gbar = css_adj_args(key, (1, 1), 960)
            k5 = jax.random.fold_in(key, 5)
            return ((u3, jnp.concatenate([par3, _planes(k5, 32, nsub)]), zb3,
                     e3, jax.random.normal(k5, (960, 32), jnp.float32)), gbar)

        yield ("css_neg_loglik", "adjoint.x", rows, 960, css_x_adj_args,
               lambda r, resid, gbar, rows=rows: [pk._fold(pk._css_ss_x_bwd(
                   1, 1, False, 960, rows, resid, gbar, _r=r)[0])])

        # the order search's grid (PR 36): 9 orders over ONE panel at G
        # orders a grid step (G = 1: the order as a grid axis, G = 9: nine
        # chains a time step), and stage 2's compaction as a grid of one
        # order over a quarter of the cells
        def grid_fold(key, k, rows=rows):
            b = rows if k > 1 else GRID_ORDERS * rows // 4
            return pk.CssGridFolded(
                _planes(key, 1000, b // pk._LANES),
                jnp.ones((1, k, b // pk._LANES, pk._LANES), jnp.float32),
                999, b, k)

        def grid_args(key, k, rows=rows):
            k1, k2 = jax.random.split(key)
            f = grid_fold(k1, k)
            par = (jnp.asarray([0.01, 0.4, 0.1, 0.3, 0.1], jnp.float32)
                   + 0.05 * jax.random.normal(k2, (k * f.b, 5), jnp.float32))
            return par, f

        def grid_adj_args(key, k, rows=rows):
            k1, k2, k3, k4 = jax.random.split(key, 4)
            f = grid_fold(k1, k)
            nsub = f.b // pk._LANES
            return (f, 0.1 * jax.random.normal(
                        k2, (5, k, nsub, pk._LANES), jnp.float32),
                    jax.random.normal(k3, (1000, k, nsub, pk._LANES),
                                      jnp.float32),
                    jax.random.normal(k4, (1, k, nsub, pk._LANES),
                                      jnp.float32))

        if rows == ROWS[0]:
            for k, gs in ((GRID_ORDERS, (1, 3, 9)), (1, (1,))):
                for g in gs:
                    tag = f"g{g}" if k > 1 else "cells"
                    for mode in ("sum", "both"):
                        yield ("css_grid_neg_loglik", f"{mode}.{tag}", rows,
                               999, functools.partial(grid_args, k=k),
                               lambda r, par, f, mode=mode, g=g:
                               pk._css_grid_fwd_call(
                                   (1, 2), (1, 2), False, mode, par, f,
                                   _g=g, _r=r)[0])
                    yield ("css_grid_neg_loglik", f"adjoint.{tag}", rows,
                           999, functools.partial(grid_adj_args, k=k),
                           lambda r, f, par4, e4, gb4, g=g: [pk._fold(
                               pk._css_grid_bwd_call(
                                   (1, 2), (1, 2), False, f, par4, e4, gb4,
                                   _g=g, _r=r))])

        def hw_args(key, nsub=nsub, rows=rows):
            k1, k2, k3 = jax.random.split(key, 3)
            par = (jnp.asarray([0.3, 0.1, 0.2], jnp.float32)
                   + 0.02 * jax.random.normal(k2, (rows, 3), jnp.float32))
            one = jnp.ones((1, nsub, pk._LANES), jnp.float32)
            return par, pk.HWFolded(
                _planes(k1, 960, nsub, loc=10.0), 10.0 * one, 0.0 * one,
                _planes(k3, 24, nsub, scale=0.5), 0.0 * one, 960)

        # additive: the save_resid forward writes ONE panel (the raw errors)
        # and the adjoint reads it alone; ``.mult`` is the multiplicative
        # model's pair (the old season and L + T written, the panel and the
        # two read)
        for mode, mult, save in (("sum", False, False),
                                 ("save_resid", False, True),
                                 ("save_resid.mult", True, True)):
            yield ("hw_sse", mode, rows, 960, hw_args,
                   lambda r, par, f, save=save, mult=mult:
                   pk._hw_fwd_call_f(False, 24, mult, save, par, f, _r=r)[0])

        def hw_adj_args(key, mult, rows=rows, nsub=nsub):
            # what _hw_ss_f_bwd holds: (f, par3, r3), or the multiplicative
            # (f, par3, so3, p3), and gbar
            k0, k1, k2, k3 = jax.random.split(key, 4)
            par, f = hw_args(k0)
            saved = ((_planes(k1, 960, nsub, scale=0.1, loc=1.0),
                      _planes(k2, 960, nsub, loc=10.0)) if mult
                     else (_planes(k1, 960, nsub),))
            return ((f, pk._fold(par), *saved),
                    jax.random.normal(k3, (rows,), jnp.float32))

        for mode, mult in (("adjoint", False), ("adjoint.mult", True)):
            yield ("hw_sse", mode, rows, 960,
                   functools.partial(hw_adj_args, mult=mult),
                   lambda r, resid, gbar, mult=mult: [pk._fold(
                       pk._hw_ss_f_bwd(False, 24, mult, resid, gbar,
                                       _r=r)[0])])

        def garch_args(key, nsub=nsub, rows=rows):
            k1, k2 = jax.random.split(key)
            par = jnp.asarray([1e-6, 0.08, 0.9], jnp.float32) * (
                1.0 + 0.05 * jax.random.normal(k2, (rows, 3), jnp.float32))
            one = jnp.ones((1, nsub, pk._LANES), jnp.float32)
            r23 = (0.01 * _planes(k1, 1000, nsub)) ** 2
            return par, pk.GarchFolded(r23, 1e-4 * one, 0.0 * one, 1000)

        for mode in ("sum", "both", "e"):
            yield ("garch_neg_loglik", mode, rows, 1000, garch_args,
                   lambda r, par, f, mode=mode: pk._garch_fwd_call_f(
                       False, mode, par, f, _r=r)[0])

        def garch_adj_args(key, rows=rows, nsub=nsub):
            k0, k1, k2 = jax.random.split(key, 3)
            par, f = garch_args(k0)
            h3 = 1e-4 * (1.0 + 0.1 * _planes(k1, 1000, nsub))
            return ((f, pk._fold(par), h3, None),
                    jax.random.normal(k2, (rows,), jnp.float32))

        yield ("garch_neg_loglik", "adjoint", rows, 1000, garch_adj_args,
               lambda r, resid, gbar: [pk._fold(pk._garch_ll_f_bwd(
                   False, resid, gbar, _r=r)[0])])

        # the recursion with a mean equation in the step (PR 52: the returns
        # y_t - c - phi y_{t-1} formed in VMEM; five planes a side, the
        # seed's cotangent out)
        def argarch_args(key, nsub=nsub, rows=rows):
            k0, k1 = jax.random.split(key)
            par, f = garch_args(k0)
            mean = jnp.asarray([5e-4, 0.1], jnp.float32) * (
                1.0 + 0.05 * jax.random.normal(k1, (rows, 2), jnp.float32))
            return (jnp.concatenate([mean, par], axis=1),
                    pk._unfold(f.h03, rows)[:, 0], pk.ArgarchFolded(
                        0.01 * _planes(k1, 1000, nsub), 1.0 + f.zb3, 1000))

        for mode in ("sum", "both"):
            yield ("argarch_neg_loglik", mode, rows, 1000, argarch_args,
                   lambda r, par, h0, f, mode=mode: pk._argarch_fwd_call_f(
                       False, mode, par, h0, f, _r=r)[0])

        def argarch_adj_args(key, rows=rows, nsub=nsub):
            k0, k1, k2 = jax.random.split(key, 3)
            par, h0, f = argarch_args(k0)
            h3 = 1e-4 * (1.0 + 0.1 * _planes(k1, 1000, nsub))
            return ((f, pk._fold(par), pk._fold(h0[:, None]), h3),
                    jax.random.normal(k2, (rows,), jnp.float32))

        yield ("argarch_neg_loglik", "adjoint", rows, 1000, argarch_adj_args,
               lambda r, resid, gbar: [
                   pk._fold(g.reshape(g.shape[0], -1))
                   for g in pk._argarch_ll_f_bwd(False, resid, gbar,
                                                 _r=r)[:2]])


def _time(fn, args, calls, reps=3):
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--only", default="")
    ap.add_argument("--r", type=int, nargs="*", default=[1, 2, 4])
    ap.add_argument("--rows", type=int, nargs="*", default=list(ROWS))
    a = ap.parse_args()
    sharding = None
    if a.compile_only:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.devices()[0].platform != "tpu":
        sys.exit("no TPU: a time comes from the chip only "
                 "(--compile-only compiles for a described v5e)")
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, "kernel_block_sweep.jsonl"), "a")
    for name, mode, rows, t, make, call in cases():
        if (a.only and a.only not in f"{name}.{mode}") or rows not in a.rows:
            continue
        tp, _, _ = pk._time_layout(t)
        blocks = rows // pk._SBLK
        if mode.endswith(".cells"):  # a quarter of the grid's cells, flat
            blocks = blocks * GRID_ORDERS // 4
        orders = GRID_ORDERS if ".g" in mode else 1
        ref = None
        args = None if a.compile_only else make(jax.random.key(rows + t))
        for r in a.r:
            rec = {"kernel": name, "mode": mode, "rows": rows, "t": t, "r": r,
                   "orders": orders,
                   "device": ("described v5e (compile only)" if a.compile_only
                              else jax.devices()[0].device_kind)}
            fn = jax.jit(functools.partial(call, r))
            try:
                if a.compile_only:
                    shapes = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                       sharding=sharding),
                        jax.eval_shape(make, jax.random.key(0)))
                    fn.lower(*shapes).compile()
                    rec["compiled"] = True
                else:
                    host = [np.asarray(x) for x in fn(*args)]
                    if ref is None:  # the first width asked for: R = 1
                        ref = host
                    rec["bit_equal_r1"] = all(
                        x.tobytes() == y.tobytes() for x, y in zip(host, ref))
                    del host
                    s = _time(fn, args, a.calls)
                    rec["ms_call"] = s * 1e3
                    # per order where one call carries several
                    rec["ns_step_block"] = s * 1e9 / (tp * blocks * orders)
            except Exception as e:  # noqa: BLE001 - a refused width is a reading
                rec["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            line = json.dumps(rec)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()


if __name__ == "__main__":
    main()
