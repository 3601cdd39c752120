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

from .. import obs
from ..utils import optim
from .base import (FitResult, align_right, debatch,
                   debatch_fit, derive_status,
                   require_pallas_for_count_evals,
                   ensure_batched, maybe_align,
                   jit_program, resolve_align_mode, resolve_backend)


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


# module-level so tests can monkeypatch the gate per model (sizing lives
# with the compaction feature: utils.optim)
_COMPACT_MIN_BATCH = optim.COMPACT_MIN_BATCH

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
    fits report the FIRST start's passes plus an ``n_starts`` multiplier).

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
    bsz = yb.shape[0]
    # lazy straggler compile (utils.optim stage-1/stage-2 split): the
    # compacted stage-2 program is traced/compiled only when a start's
    # stage 1 actually leaves unconverged rows — same gate and host check
    # as models.arima.fit, extended with a PER-START carry: the seeded
    # multi-start runs several optimizer passes per fit, and each start
    # gates its own stage-2 dispatch; the ONE stage-2 program (stable
    # shapes across starts) is shared by every start that needs it, and
    # the basin selection re-merges only when some start re-ran.
    lazy = (compact and not count_evals
            and backend in ("pallas", "pallas-interpret")
            and not isinstance(yb, jax.core.Tracer)
            and bsz >= _COMPACT_MIN_BATCH
            and optim.compaction_cap(bsz) < bsz)
    if lazy:
        # fit.stage1: the dispatch of every start's stage 1 and the host's
        # wait for it at the first gate below (the later starts' gates find
        # their scalars ready); fit.stage2 only around a dispatch
        with obs.span("fit.stage1", rows=bsz) as stage1:
            out, aux = _fit_stage1_program(
                period, multiplicative, max_iters, float(tol), backend,
                align_mode, n_starts)(yb)
            undone = [int(a["carry"].undone) for a in aux["starts"]]
            if obs.enabled():
                stage1.set(iters=max(int(a["carry"].k)
                                     for a in aux["starts"]),
                           undone=sum(undone))
        finished, redo = [], False
        for a, n_undone in zip(aux["starts"], undone):
            if n_undone > 0 and int(a["carry"].k) < max_iters:
                with obs.span("fit.stage2",
                              rows=optim.compaction_cap(bsz)):
                    finished.append(_fit_stage2_program(
                        period, multiplicative, max_iters, float(tol),
                        backend)(a))
                redo = True
            else:
                finished.append(a["res"])
        if redo:
            out = _merge_starts_program(n_starts)(
                tuple(finished), aux["ok"], aux["n_err"])
        return debatch_fit(out, single, False)
    out = _fit_program(period, multiplicative, max_iters, float(tol), backend,
                       align_mode, count_evals, compact,
                       n_starts)(yb)
    return debatch_fit(out, single, count_evals)


@jit_program
def _fit_program(period, multiplicative, max_iters, tol, backend,
                 align_mode="general", count_evals=False, compact=True,
                 n_starts=1):
    def run(yb):
        ya, nv = maybe_align(yb, align_mode)

        # optimize the MEAN one-step squared error: same argmin as the SSE,
        # but the gradient scale is O(1), so the relative grad-norm stopping
        # rule fires when the fit is actually done instead of never
        n_err = jnp.maximum(nv - period, 1).astype(yb.dtype)
        if backend in ("pallas", "pallas-interpret"):
            from ..ops import pallas_kernels as pk

            interp = backend == "pallas-interpret"

            # seeds are data-only: compute ONCE, not per objective call or
            # per start (vmapped seed slices are batched gathers — recomputed
            # inside the loop they dominate an objective evaluation at panel
            # scale; the dense mode takes the gather-free static-slice path)
            seeds = pk.hw_seeds(
                ya, period, multiplicative,
                None if align_mode == "dense" else nv)
            # ... and so is the kernel layout: fold the panel and its seeds
            # ONCE, outside every while_loop (XLA does not hoist the [B, T]
            # relayout out of the line search); all starts share it
            folded = pk.hw_prefold(ya, seeds)

            def fb(u):
                nat = optim.sigmoid_to_interval(u, 0.0, 1.0)
                return pk.hw_sse_folded(
                    nat, folded, period, multiplicative, interpret=interp
                ) / n_err

            # straggler compaction (utils.optim): the subset gather repacks
            # folded COLUMNS (series ride the lanes), grid-aligned by the cap
            bsz = ya.shape[0]
            cap = optim.compaction_cap(bsz)
            straggler_fun = None
            if compact and bsz >= _COMPACT_MIN_BATCH:

                def straggler_fun(idxc):
                    folded_s = folded.take(idxc)
                    nes = n_err[idxc]

                    def fb_s(u):
                        nat = optim.sigmoid_to_interval(u, 0.0, 1.0)
                        return pk.hw_sse_folded(
                            nat, folded_s, period, multiplicative,
                            interpret=interp) / nes

                    return fb_s

            def one_start(nat0, want_info):
                u0 = jnp.broadcast_to(
                    optim.interval_to_sigmoid(
                        jnp.asarray(nat0, yb.dtype), 0.0, 1.0),
                    (yb.shape[0], 3))
                r = optim.minimize_lbfgs_batched(
                    fb, u0, max_iters=max_iters, tol=tol,
                    count_evals=want_info,
                    straggler_fun=straggler_fun, straggler_cap=cap)
                return r if want_info else (r, None)
        else:
            def objective(u, data):
                yv, n, ne = data
                nat = optim.sigmoid_to_interval(u, 0.0, 1.0)
                return sse(nat, yv, period, multiplicative, n) / ne

            def one_start(nat0, want_info):
                u0 = jnp.broadcast_to(
                    optim.interval_to_sigmoid(
                        jnp.asarray(nat0, yb.dtype), 0.0, 1.0),
                    (yb.shape[0], 3))
                r = optim.batched_minimize(
                    objective, u0, (ya, nv, n_err), max_iters=max_iters,
                    tol=tol)
                return r, None

        # seeded multi-start: run the optimizer from each init and keep,
        # per row, the best basin (_select_best_start).  Pass accounting
        # (count_evals) reports the first start's passes; n_starts rides
        # in the info dict as a multiplier.
        res, info = one_start(_MULTISTART_NATS[0], count_evals)
        if info is not None:
            info = {**info, "n_starts": n_starts}
        if n_starts > 1:
            starts = [res] + [one_start(_MULTISTART_NATS[s], False)[0]
                              for s in range(1, n_starts)]
            res = _select_best_start(starts)
        ok = nv >= 2 * period  # seed needs two full seasons of real data
        out = _finalize_hw_fit(res, ok, n_err)
        return (out, info) if count_evals else out

    return run


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

    ONE implementation serves the inline multi-start program and the lazy
    stage-1/stage-2 split's re-merge — the basin choice must never diverge
    between them.
    """
    if len(starts) == 1:
        return starts[0]
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
    return res._replace(**merged)


def _finalize_hw_fit(res, ok, n_err):
    """Optimizer result -> FitResult (same ops as the inline program);
    the reported objective is the unscaled SSE."""
    params = jnp.where(
        ok[:, None], optim.sigmoid_to_interval(res.x, 0.0, 1.0), jnp.nan)
    return FitResult(
        params,
        jnp.where(ok, res.f * n_err, jnp.nan),
        res.converged & ok,
        res.iters,
        derive_status(ok, res.converged, params),
    )


@jit_program
def _fit_stage1_program(period, multiplicative, max_iters, tol, backend,
                        align_mode="general", n_starts=1):
    """Stage 1 of the lazily compiled compact Holt-Winters fit: the full
    prep (alignment + one-time seed state) and, PER SEEDED START, the
    lockstep L-BFGS with the straggler early-exit — returning the
    finalized as-if-done merged result PLUS one compacted carry per start,
    so the stage-2 program is traced/compiled only when some start's
    ``carry.undone`` says rows actually remain (and dispatched only for
    those starts).  Pallas backends only (the gate lives in ``fit``)."""

    def run(yb):
        ya, nv = maybe_align(yb, align_mode)
        n_err = jnp.maximum(nv - period, 1).astype(yb.dtype)
        from ..ops import pallas_kernels as pk

        interp = backend == "pallas-interpret"
        # seeds are data-only: compute and fold ONCE, before the first
        # start, and share across every start (same contract as the inline
        # program)
        folded = pk.hw_prefold(ya, pk.hw_seeds(
            ya, period, multiplicative,
            None if align_mode == "dense" else nv))

        def fb(u):
            nat = optim.sigmoid_to_interval(u, 0.0, 1.0)
            return pk.hw_sse_folded(
                nat, folded, period, multiplicative, interpret=interp
            ) / n_err

        bsz = ya.shape[0]
        cap = optim.compaction_cap(bsz)
        results, starts_aux = [], []
        for s in range(n_starts):
            u0 = jnp.broadcast_to(
                optim.interval_to_sigmoid(
                    jnp.asarray(_MULTISTART_NATS[s], yb.dtype), 0.0, 1.0),
                (bsz, 3))
            res1, carry = optim.lbfgs_batched_stage1(
                fb, u0, straggler_cap=cap, max_iters=max_iters, tol=tol)
            # gather the compacted objective data HERE (the same folded-
            # COLUMN gather the inline straggler_fun performs) so the
            # stage-2 program is a pure function of its inputs, folds
            # nothing and keeps stable shapes across starts — ONE compiled
            # stage-2 program serves every start that needs it
            starts_aux.append({
                "carry": carry, "res": res1,
                "folded_s": folded.take(carry.idxc),
                "nes": n_err[carry.idxc]})
            results.append(res1)
        ok = nv >= 2 * period
        out = _finalize_hw_fit(_select_best_start(results), ok, n_err)
        return out, {"starts": tuple(starts_aux), "ok": ok, "n_err": n_err}

    return run


@jit_program
def _fit_stage2_program(period, multiplicative, max_iters, tol, backend):
    """Stage 2 of the lazy compact Holt-Winters fit: finish ONE start's
    gathered stragglers on the compacted objective and scatter back into
    that start's full-batch result — compiled on the first call where any
    start left unconverged rows, then reused by every such start."""
    interp = backend == "pallas-interpret"

    def run(aux_s):
        from ..ops import pallas_kernels as pk

        def fb_s(u):
            nat = optim.sigmoid_to_interval(u, 0.0, 1.0)
            return pk.hw_sse_folded(
                nat, aux_s["folded_s"], period, multiplicative,
                interpret=interp) / aux_s["nes"]

        return optim.lbfgs_batched_stage2(
            fb_s, aux_s["res"], aux_s["carry"], max_iters=max_iters, tol=tol)

    return run


@jit_program
def _merge_starts_program(n_starts):
    """Re-merge the per-start results after lazy stage-2 dispatches: the
    same basin selection + finalize the inline program applies."""

    def run(results, ok, n_err):
        return _finalize_hw_fit(_select_best_start(list(results)), ok, n_err)

    return run


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
