"""Holt-Winters triple exponential smoothing (L4).

Rebuild of the reference's ``sparkts/models/HoltWinters.scala`` (SURVEY.md
Section 2.2, upstream path unverified): additive and multiplicative
seasonality with period ``m``; level/trend/seasonal start values taken from
the first two seasons; ``(alpha, beta, gamma)`` fitted by minimizing the
one-step-ahead SSE.  The reference uses BOBYQA per series; here the
smoothing recursion is a ``lax.scan``, the (0,1) bounds are a sigmoid
reparameterization, and the fit is the shared vmapped L-BFGS
(SURVEY.md Section 7's BOBYQA-replacement strategy).

Parameter layout (natural space): ``[alpha, beta, gamma]``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import optim
from . import lockstep
from .base import (FitResult, align_right, debatch_fit, ensure_batched,
                   jit_program, maybe_align, require_pallas_for_count_evals,
                   resolve_align_mode, resolve_backend)


def _init_state(y, period: int, multiplicative: bool, start=None):
    """Start values from the first two seasons (upstream's scheme).

    ``start`` (traced scalar) points at the first valid observation of a
    right-aligned series; the seasons are sliced dynamically from there.
    """
    if start is None:
        s1 = y[:period]
        s2 = y[period : 2 * period]
    else:
        s1 = lax.dynamic_slice(y, (start,), (period,))
        s2 = lax.dynamic_slice(y, (start + period,), (period,))
    level0 = jnp.mean(s1)
    trend0 = (jnp.mean(s2) - jnp.mean(s1)) / period
    if multiplicative:
        seasonal0 = s1 / jnp.maximum(level0, 1e-12)
    else:
        seasonal0 = s1 - level0
    return level0, trend0, seasonal0


def _run(params, y, period: int, multiplicative: bool, n_valid=None):
    """Run the smoothing recursion; returns (one-step forecasts, final state).

    forecasts[t] is the prediction of y[t] made at t-1 (for t >= period... the
    first ``period`` entries predict using the seed state).  ``n_valid`` marks
    a right-aligned valid span: the state holds through the zero prefix so the
    recursion effectively starts at the first valid observation.
    """
    alpha, beta, gamma = params[0], params[1], params[2]
    start = None if n_valid is None else y.shape[0] - n_valid
    level0, trend0, seasonal0 = _init_state(y, period, multiplicative, start)

    def step(carry, inp):
        yt, t = inp
        level, trend, seasonal = carry  # seasonal: [period], rotating
        s = seasonal[0]
        if multiplicative:
            pred = (level + trend) * s
            new_level = alpha * yt / jnp.maximum(s, 1e-12) + (1 - alpha) * (level + trend)
            new_seasonal_last = gamma * yt / jnp.maximum(new_level, 1e-12) + (1 - gamma) * s
        else:
            pred = level + trend + s
            new_level = alpha * (yt - s) + (1 - alpha) * (level + trend)
            new_seasonal_last = gamma * (yt - new_level) + (1 - gamma) * s
        new_trend = beta * (new_level - level) + (1 - beta) * trend
        new_seasonal = jnp.concatenate([seasonal[1:], new_seasonal_last[None]])
        if start is not None:
            skip = t < start
            new_level = jnp.where(skip, level, new_level)
            new_trend = jnp.where(skip, trend, new_trend)
            new_seasonal = jnp.where(skip, seasonal, new_seasonal)
        return (new_level, new_trend, new_seasonal), pred

    (level, trend, seasonal), preds = lax.scan(
        step, (level0, trend0, seasonal0), (y, jnp.arange(y.shape[0]))
    )
    return preds, (level, trend, seasonal)


def sse(params, y, period: int, multiplicative: bool, n_valid=None):
    """One-step-ahead SSE, skipping the seeded first season."""
    preds, _ = _run(params, y, period, multiplicative, n_valid)
    err = y - preds
    start = 0 if n_valid is None else y.shape[0] - n_valid
    err = jnp.where(jnp.arange(y.shape[0]) >= start + period, err, 0.0)
    return jnp.sum(err * err)


# seeded multi-start inits (natural (alpha, beta, gamma) space), probed in
# order: the long-standing default first, then two deterministic probes at
# opposite corners of the smoothing cube.  The multiplicative SSE surface is
# non-convex with a fat local-optimum tail (PRECISION.md round 5: p99 drift
# 0.74, f64 oracle non-converged on 9.8% of rows); re-running the optimizer
# from 2-3 spread inits and keeping each row's best final objective
# collapses that tail for ~(n_starts - 1) extra fit passes.
_MULTISTART_NATS = (
    (0.3, 0.1, 0.1),
    (0.7, 0.25, 0.4),
    (0.12, 0.05, 0.6),
)


def fit(
    y,
    period: int,
    model_type: str = "additive",
    *,
    max_iters: int = 60,
    tol: Optional[float] = None,
    backend: str = "auto",
    count_evals: bool = False,
    compact: bool = True,
    n_starts: Optional[int] = None,
    align_mode: Optional[str] = None,
) -> FitResult:
    """Fit (alpha, beta, gamma) per series -> params ``[batch?, 3]``.

    ``backend``: ``"scan"`` (portable), ``"pallas"`` (fused TPU kernel —
    additive and multiplicative, ragged panels via the right-aligned span),
    or ``"auto"`` (pallas whenever the platform/dtype/period allow).

    ``count_evals=True`` (pallas backend only) returns ``(FitResult, info)``
    with the optimizer's pass-accounting dict (``utils.optim``; multi-start
    fits report the FIRST start's passes plus an ``n_starts`` multiplier);
    the fit that is counted is the fit that runs without the flag.

    ``compact=False`` disables straggler compaction for run-to-run
    reproducibility (it engages on the pallas backend at batches >=
    ``utils.optim.COMPACT_MIN_BATCH`` = 4096 and is a different compiled
    program — bitwise outputs can differ from the uncompacted run).

    ``n_starts`` (default: 3 for multiplicative, 1 for additive; at most
    ``len(_MULTISTART_NATS)`` = 3 — extend that table for more) runs the
    optimizer from that many deterministic seeded inits
    (``_MULTISTART_NATS``) and keeps each row's best final objective —
    preferring converged starts — so rows stranded in a bad local optimum
    of the non-convex (especially multiplicative) SSE surface are rescued
    by a better basin instead of shipping a 0.7-drift parameter tail.

    ``align_mode`` is the static alignment hint (``base.resolve_align_mode``)
    the chunk driver threads through sliced walks to skip the per-chunk NaN
    probe; a hint too strong for the data flags the violating rows
    (DIVERGED / EXCLUDED) instead of silently misfitting them.
    ``FitResult.status`` carries per-row ``reliability.FitStatus`` codes."""
    if model_type not in ("additive", "multiplicative"):
        raise ValueError(f"model_type must be additive|multiplicative, got {model_type!r}")
    multiplicative = model_type == "multiplicative"
    if n_starts is None:
        n_starts = 3 if multiplicative else 1
    if not 1 <= int(n_starts) <= len(_MULTISTART_NATS):
        raise ValueError(
            f"n_starts must be in [1, {len(_MULTISTART_NATS)}] (one per "
            "seeded init in holtwinters._MULTISTART_NATS — extend that "
            f"table to probe more basins), got {n_starts}")
    n_starts = int(n_starts)
    yb, single = ensure_batched(y)
    if yb.shape[1] < 2 * period:
        raise ValueError(
            f"need at least two seasons ({2 * period} points), got {yb.shape[1]}"
        )
    if tol is None:
        tol = 1e-7 if yb.dtype == jnp.float64 else 1e-4
    from ..ops import pallas_kernels as pk

    backend = resolve_backend(backend, yb.dtype, yb.shape[1],
                              structural_ok=pk.hw_structural_ok(period))
    require_pallas_for_count_evals(count_evals, backend)
    align_mode = resolve_align_mode(yb, align_mode)
    static = (period, multiplicative, max_iters, float(tol), backend)
    out = lockstep.fit(
        (yb,), backend=backend, compact=compact, max_iters=max_iters,
        inline=lambda: _fit_program(*static, align_mode, count_evals,
                                    compact, n_starts),
        stage1=lambda: _fit_stage1_program(*static, align_mode, n_starts,
                                           count_evals),
        stage2=lambda: _fit_stage2_program(*static),
        merge=lambda: _merge_starts_program(*static),
        series_block=lambda rows, mode: pk.hw_series_block(
            rows, yb.shape[1], period, mode, multiplicative),
        # a gradient's forward writes 1 panel and its adjoint reads 1
        # (additive: the raw errors), or 2 written and 3 read
        # (multiplicative: the old season and L + T, read beside y)
        stage_attrs={"adjoint_panels": pk.HW_ADJOINT_PANELS[multiplicative]})
    if count_evals:
        out = (out[0], {**out[1], "n_starts": n_starts})
    return debatch_fit(out, single, count_evals)


def _hw_family(period, multiplicative, backend, align_mode=None,
               n_starts=1) -> lockstep.Family:
    from ..ops import pallas_kernels as pk

    def to_natural(u):
        return optim.sigmoid_to_interval(u, 0.0, 1.0)

    def prep(yb):
        ya, nv = maybe_align(yb, align_mode)
        n_err = jnp.maximum(nv - period, 1).astype(yb.dtype)
        folded = ()
        if backend in lockstep.PALLAS:
            # seeds are data-only: computed and folded ONCE, before the
            # first start, and shared by every start (vmapped seed slices
            # are batched gathers — recomputed per objective call they
            # dominate an evaluation at panel scale; the dense mode takes
            # the gather-free static-slice path)
            folded = pk.hw_prefold(ya, pk.hw_seeds(
                ya, period, multiplicative,
                None if align_mode == "dense" else nv))
        x0s = tuple(
            jnp.broadcast_to(
                optim.interval_to_sigmoid(
                    jnp.asarray(nat0, yb.dtype), 0.0, 1.0),
                (yb.shape[0], 3))
            for nat0 in _MULTISTART_NATS[:n_starts])
        # the seed needs two full seasons of real data
        return lockstep.Prepared(x0s, nv >= 2 * period, n_err, (ya, nv),
                                 folded)

    def objective(folded, _):
        return lambda u: pk.hw_sse_folded(
            to_natural(u), folded, period, multiplicative,
            interpret=backend == "pallas-interpret")

    def scan_objective(u, data):
        yv, n = data
        return sse(to_natural(u), yv, period, multiplicative, n)

    return lockstep.Family(backend, prep, objective, scan_objective,
                           to_natural, _select_best_start)


@jit_program
def _fit_program(period, multiplicative, max_iters, tol, backend,
                 align_mode="general", count_evals=False, compact=True,
                 n_starts=1):
    return lockstep.fit_program(
        _hw_family(period, multiplicative, backend, align_mode, n_starts),
        max_iters, tol, count_evals, compact)


@jit_program
def _fit_stage1_program(period, multiplicative, max_iters, tol, backend,
                        align_mode="general", n_starts=1, count_evals=False):
    return lockstep.stage1_program(
        _hw_family(period, multiplicative, backend, align_mode, n_starts),
        max_iters, tol, count_evals)


@jit_program
def _fit_stage2_program(period, multiplicative, max_iters, tol, backend):
    return lockstep.stage2_program(
        _hw_family(period, multiplicative, backend), max_iters, tol)


@jit_program
def _merge_starts_program(period, multiplicative, max_iters, tol, backend):
    return lockstep.merge_program(
        _hw_family(period, multiplicative, backend))


def _select_best_start(starts):
    """Per-row basin selection across seeded multi-start results.

    Selection is two-stage and designed to be DETERMINISTIC ACROSS
    PRECISIONS (PRECISION.md: the multiplicative surface has near-tied
    local optima, and picking by raw SSE order lets f32 and f64 flip
    coins on which basin float noise ranks first, shipping a fat
    cross-precision parameter-drift tail):

    1. candidates = converged starts (all starts when none converged)
       within 0.1% relative of the row's best final objective —
       statistically indistinguishable fits;
    2. among candidates, prefer the SMOOTHEST model (smallest
       alpha+beta+gamma; basins sit far apart in parameter space, so this
       comparison is float-noise-robust), ties to the earliest start.

    Returns ``lockstep.Family.merge``'s pair: the merged result and the
    rows whose choice is NOT the first start's, one ``int32`` (what the
    later starts bought; ``None`` from one start, which chooses nothing).
    """
    if len(starts) == 1:
        return starts[0], None
    res = starts[0]
    xs = jnp.stack([r.x for r in starts])  # [S, B, 3]
    fs = jnp.stack([jnp.nan_to_num(r.f, nan=jnp.inf, posinf=jnp.inf)
                    for r in starts])
    convs = jnp.stack([r.converged for r in starts])
    any_conv = convs.any(axis=0)
    eligible = jnp.where(any_conv[None, :], convs, True)
    f_elig = jnp.where(eligible, fs, jnp.inf)
    best_f = jnp.min(f_elig, axis=0)
    near = eligible & (f_elig <= best_f[None, :] * (1 + 1e-3) + 1e-12)
    smooth = jnp.sum(
        optim.sigmoid_to_interval(xs, 0.0, 1.0), axis=-1)
    sel = jnp.argmin(jnp.where(near, smooth, jnp.inf), axis=0)
    take = lambda field: jnp.take_along_axis(  # noqa: E731
        jnp.stack([getattr(r, field) for r in starts]),
        sel[None, :], axis=0)[0]
    merged = {
        "x": jnp.take_along_axis(
            xs, sel[None, :, None], axis=0)[0],
        "f": take("f"),
        "converged": take("converged"),
        "iters": take("iters"),
    }
    if hasattr(res, "grad_norm"):
        merged["grad_norm"] = take("grad_norm")
    return res._replace(**merged), jnp.sum(sel != 0, dtype=jnp.int32)


def forecast(params, y, period: int, n_future: int, model_type: str = "additive"):
    """h-step-ahead forecasts from the end state:
    additive: (level + h*trend) + seasonal; multiplicative: * seasonal."""
    multiplicative = model_type == "multiplicative"
    yb, single = ensure_batched(y)
    pb = jnp.atleast_2d(params)
    out = _forecast_program(period, multiplicative, n_future)(pb, yb)
    return out[0] if single else out


@jit_program
def _forecast_program(period, multiplicative, n_future):
    def run(pb, yb):
        def one(pr, yv):
            ya, nv = align_right(yv)
            _, (level, trend, seasonal) = _run(pr, ya, period, multiplicative, nv)
            h = jnp.arange(1, n_future + 1, dtype=yv.dtype)
            seas = seasonal[(jnp.arange(n_future)) % period]
            base = level + h * trend
            out = base * seas if multiplicative else base + seas
            # seeding needs two full seasons (same gate as fit): shorter
            # spans would return finite garbage from clamped seed windows
            return jnp.where(nv >= 2 * period, out, jnp.nan)

        return jax.vmap(one)(pb, yb)

    return run


def fitted(params, y, period: int, model_type: str = "additive"):
    """In-sample one-step-ahead predictions (``addTimeDependentEffects``
    analog for diagnostics)."""
    multiplicative = model_type == "multiplicative"
    yb, single = ensure_batched(y)
    pb = jnp.atleast_2d(params)
    out = _fitted_program(period, multiplicative)(pb, yb)
    return out[0] if single else out


@jit_program
def _fitted_program(period, multiplicative):
    return jax.vmap(lambda pr, yv: _run(pr, yv, period, multiplicative)[0])
