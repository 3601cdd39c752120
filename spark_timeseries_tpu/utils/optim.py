"""Batched unconstrained optimization for model fitting.

The reference fits every model with Apache Commons Math optimizers —
``NonLinearConjugateGradientOptimizer`` (css-cgd) and ``BOBYQAOptimizer``
(css-bobyqa / Holt-Winters) — one series at a time on one JVM core
(SURVEY.md Section 2.2).  The TPU rebuild needs ONE optimizer that fits a
million independent small problems simultaneously, which means it must be:

- jit-compatible: fixed iteration budget, ``lax.while_loop`` control flow;
- vmap-compatible: every series carries its own state (history, step size,
  converged flag) with identical static shapes;
- autodiff-driven: gradients come from ``jax.grad`` of the CSS/likelihood
  scan (the reference hand-derives them).

This module implements L-BFGS (two-loop recursion, fixed-size history,
Armijo backtracking line search).  BOBYQA has no JAX analog; bounded
problems (GARCH/Holt-Winters positivity) use parameter transforms (sigmoid /
softplus) and come through the same unconstrained path — SURVEY.md Section 7
"hard parts".
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs


# Straggler-compaction sizing shared by every fit driver: below this batch
# size the compaction stage is not worth its gather, and the cap must cover
# whole [8, 128] kernel series blocks (ops.pallas_kernels._SBLK) so folded-
# column gathers stay grid-aligned.
COMPACT_MIN_BATCH = 4096


def compaction_cap(bsz: int) -> int:
    """Straggler cap for a batch of ``bsz`` rows: ~bsz/8, 1024-aligned."""
    return -(-max(1024, bsz // 8) // 1024) * 1024


def retry_cap(n: int, align: int = 8) -> int:
    """Bucket size for a host-side failed-subset gather: the next power of
    two at or above ``n`` (minimum ``align``).

    The resilient runner (``reliability.runner``) pads retry sub-batches to
    this cap for the same reason :func:`compaction_cap` aligns the
    straggler gather: the padded shape, not the exact failure count,
    determines the compiled program, so bucketing bounds the number of
    distinct shapes (and recompiles) the ladder can create.
    """
    n = max(int(n), 1)
    cap = max(align, 1)
    while cap < n:
        cap *= 2
    return cap


def gather_pad_indices(rows, cap: int):
    """Pad a host-side row-index gather to ``cap`` slots by repeating the
    first index.

    The convention every bounded-shape subset dispatch shares — the
    resilient retry ladder's failed-row buckets and the auto-fit winners
    stage-2 basin refits (``models.auto``): the padded tail recomputes a
    real row (its results are dropped on scatter), so the compiled
    program's shape is the :func:`retry_cap` bucket, never one shape per
    subset size.
    """
    import numpy as _np

    rows = _np.asarray(rows)
    if rows.size == 0:
        raise ValueError("gather_pad_indices needs at least one row")
    if int(cap) < rows.size:
        raise ValueError(f"cap {cap} smaller than the {rows.size}-row gather")
    return _np.concatenate(
        [rows, _np.full(int(cap) - rows.size, rows[0], rows.dtype)])


class LBFGSResult(NamedTuple):
    x: jax.Array  # [d] solution
    f: jax.Array  # [] final objective
    converged: jax.Array  # [] bool: grad-norm tolerance reached
    iters: jax.Array  # [] iterations actually taken
    grad_norm: jax.Array  # [] gradient norm at the returned x (best-seen iterate)


class _State(NamedTuple):
    k: jax.Array
    x: jax.Array
    f: jax.Array
    g: jax.Array
    s_hist: jax.Array  # [m, d]
    y_hist: jax.Array  # [m, d]
    rho_hist: jax.Array  # [m]
    converged: jax.Array
    failed: jax.Array  # line search broke down
    tprev: jax.Array  # last accepted linesearch step (warm-start)
    # best-seen iterate: the noise-floor-relaxed accept can adopt a step
    # that RAISES f by up to ftol*max(1,|f|) (and ftol-convergence then
    # freezes there), so the returned (x, f) is the best visited point,
    # guaranteeing f(returned) <= f(x0) (ADVICE r3).  bg is the gradient AT
    # bx, so the reported grad_norm is a valid stationarity diagnostic for
    # the returned point (ADVICE r4)
    bx: jax.Array
    bf: jax.Array
    bg: jax.Array


def _two_loop(g, s_hist, y_hist, rho_hist, k, m):
    """L-BFGS two-loop recursion with masked (not-yet-filled) history slots.

    History is a ring buffer; slot ``i`` is valid when ``rho_hist[i] > 0``.
    The loops are unrolled (``m`` is a small static history size): unrolling
    lets XLA fuse the whole recursion into a couple of kernels instead of
    ``2m`` sequential scan steps — this machinery runs once per optimizer
    iteration on every series, so launch overhead matters.
    """
    idx = (k - 1 - jnp.arange(m)) % m  # newest -> oldest

    q = g
    alphas = []
    for j in range(m):
        i = idx[j]
        valid = rho_hist[i] > 0.0
        alpha = jnp.where(valid, rho_hist[i] * jnp.dot(s_hist[i], q), 0.0)
        q = q - alpha * y_hist[i] * valid
        alphas.append(alpha)

    # initial Hessian scaling gamma = s·y / y·y of the newest valid pair
    newest = idx[0]
    sy = jnp.dot(s_hist[newest], y_hist[newest])
    yy = jnp.dot(y_hist[newest], y_hist[newest])
    gamma = jnp.where((rho_hist[newest] > 0.0) & (yy > 0.0), sy / yy, 1.0)
    r = gamma * q

    for j in reversed(range(m)):
        i = idx[j]
        valid = rho_hist[i] > 0.0
        beta = jnp.where(valid, rho_hist[i] * jnp.dot(y_hist[i], r), 0.0)
        r = r + (alphas[j] - beta) * s_hist[i] * valid
    return r  # approximates H g


def minimize_lbfgs(
    fun: Callable[[jax.Array], jax.Array],
    x0: jax.Array,
    *,
    max_iters: int = 50,
    history: int = 8,
    tol: float = 1e-6,
    ftol: float | None = None,
    max_linesearch: int = 20,
    c1: float = 1e-4,
) -> LBFGSResult:
    """Minimize ``fun`` from ``x0`` with a fixed compute budget.

    Designed for ``vmap``: all shapes static, all control flow ``lax``.
    Non-finite objective values are treated as +inf by the line search, so
    transformed-parameter models can guard invalid regions with ``jnp.where``.

    Convergence is EITHER the relative gradient-norm test (``tol``) OR an
    accepted step whose relative objective decrease falls below ``ftol``
    (scipy/Commons-Math style): at f32 the gradient of a long-series
    objective bottoms out on its accumulation noise floor while the
    objective itself has visibly stopped moving.  ``ftol=None`` picks
    1e-6 (f32) / 1e-9 (f64).
    """
    d = x0.shape[0]
    m = history
    dtype = x0.dtype
    if ftol is None:
        ftol = 1e-9 if dtype == jnp.float64 else 1e-6

    value_and_grad = jax.value_and_grad(fun)

    def safe_vg(x):
        f, g = value_and_grad(x)
        bad = ~jnp.isfinite(f) | ~jnp.all(jnp.isfinite(g))
        return jnp.where(bad, jnp.inf, f), jnp.where(bad, 0.0, g)

    f0, g0 = safe_vg(x0)
    init = _State(
        k=jnp.zeros((), jnp.int32),
        x=x0,
        f=f0,
        g=g0,
        s_hist=jnp.zeros((m, d), dtype),
        y_hist=jnp.zeros((m, d), dtype),
        rho_hist=jnp.zeros((m,), dtype),
        converged=(jnp.linalg.norm(g0) < tol) & jnp.isfinite(f0),
        failed=jnp.isinf(f0),
        tprev=jnp.ones((), dtype),
        bx=x0,
        bf=f0,
        bg=g0,
    )

    def linesearch(x, f, g, direction, t0):
        """Backtracking with quadratic interpolation: each failed trial fits
        the 1-D quadratic through (0, f), slope g·dir, and (t, f(t)) and jumps
        to its minimizer (clamped to [0.1t, 0.5t] — plain halving needs ~12
        full objective evaluations per iteration on badly scaled first steps,
        the dominant cost of a batched fit).  The Armijo test carries a noise
        floor of ftol*max(1, |f|): near convergence the predicted decrease
        falls below the objective's own evaluation noise and the strict test
        would reject EVERY step size; the relaxed accept is then resolved by
        the ftol stopping rule.  Returns (t, ok)."""
        gd = jnp.dot(g, direction)
        eps = ftol * jnp.maximum(1.0, jnp.abs(f))

        def body(carry):
            t, _, j = carry
            fnew = fun(x + t * direction)
            fnew = jnp.where(jnp.isfinite(fnew), fnew, jnp.inf)
            ok = fnew <= f + c1 * t * gd + eps
            tq = -gd * t * t / (2.0 * (fnew - f - gd * t))
            # non-finite fnew gives tq = 0 -> clamp to the aggressive edge
            tq = jnp.where(jnp.isfinite(tq), tq, 0.0)
            # the objective may evaluate in a wider dtype; the carry must not
            tq = jnp.clip(tq, 0.1 * t, 0.5 * t).astype(t.dtype)
            return jnp.where(ok, t, tq), ok, j + 1

        def cond(carry):
            t, ok, j = carry
            return (~ok) & (j < max_linesearch)

        t, ok, _ = lax.while_loop(
            cond, body, (t0, jnp.zeros((), bool), 0)
        )
        return t, ok

    def step(state: _State) -> _State:
        direction = -_two_loop(state.g, state.s_hist, state.y_hist, state.rho_hist, state.k, m)
        # fall back to steepest descent if direction is not a descent direction
        descent = jnp.dot(state.g, direction) < 0.0
        direction = jnp.where(descent, direction, -state.g)

        # with no curvature history the direction is raw steepest descent,
        # whose scale is arbitrary: bound the first trial step length by 1.
        # With history, warm-start from the last accepted step — a problem
        # that keeps needing tiny steps should not re-pay the whole
        # backtrack from t=1 every iteration
        has_hist = jnp.any(state.rho_hist > 0.0)
        t0 = jnp.where(
            has_hist & descent,
            jnp.minimum(1.0, 4.0 * state.tprev),
            1.0 / jnp.maximum(1.0, jnp.linalg.norm(direction)),
        ).astype(dtype)
        t, ok = linesearch(state.x, state.f, state.g, direction, t0)
        x_new = state.x + t * direction
        f_new2, g_new = safe_vg(x_new)

        s = x_new - state.x
        y = g_new - state.g
        sy = jnp.dot(s, y)
        slot = state.k % m
        good_pair = (sy > 1e-10) & ok
        s_hist = state.s_hist.at[slot].set(jnp.where(good_pair, s, state.s_hist[slot]))
        y_hist = state.y_hist.at[slot].set(jnp.where(good_pair, y, state.y_hist[slot]))
        rho_hist = state.rho_hist.at[slot].set(
            jnp.where(good_pair, 1.0 / jnp.maximum(sy, 1e-30), state.rho_hist[slot])
        )

        # same noise floor as the Armijo test: a step that moved f by less
        # than the evaluation noise is "accepted" and then resolved by ftol
        accept = ok & (f_new2 <= state.f + ftol * jnp.maximum(1.0, jnp.abs(state.f)))
        x_out = jnp.where(accept, x_new, state.x)
        f_out = jnp.where(accept, f_new2, state.f)
        g_out = jnp.where(accept, g_new, state.g)
        conv = jnp.linalg.norm(g_out) < tol * jnp.maximum(1.0, jnp.linalg.norm(x_out))
        conv = conv | (
            accept & (state.f - f_new2 <= ftol * jnp.maximum(1.0, jnp.abs(f_new2)))
        )
        better = f_out < state.bf
        return _State(
            k=state.k + 1,
            x=x_out,
            f=f_out,
            g=g_out,
            s_hist=jnp.where(accept, s_hist, state.s_hist),
            y_hist=jnp.where(accept, y_hist, state.y_hist),
            rho_hist=jnp.where(accept, rho_hist, state.rho_hist),
            converged=conv,
            failed=state.failed | (~ok & ~conv),
            tprev=jnp.where(accept, t, state.tprev),
            bx=jnp.where(better, x_out, state.bx),
            bf=jnp.where(better, f_out, state.bf),
            bg=jnp.where(better, g_out, state.bg),
        )

    def cond(state: _State):
        return (state.k < max_iters) & ~state.converged & ~state.failed

    final = lax.while_loop(cond, step, init)
    # (x, f, grad_norm) all refer to the best-seen iterate
    return LBFGSResult(
        x=final.bx,
        f=final.bf,
        converged=final.converged & jnp.isfinite(final.bf),
        iters=final.k,
        grad_norm=jnp.linalg.norm(final.bg),
    )


_rownorm = lambda v: jnp.linalg.norm(v, axis=-1)
_rowdot = lambda a, b: jnp.sum(a * b, axis=-1)
_two_loop_b = jax.vmap(_two_loop, in_axes=(0, 0, 0, 0, None, None))


def _make_vg_b(fb):
    """Batched value-and-grad with the non-finite guard rows carry."""

    def vg(x):
        f, pullback = jax.vjp(fb, x)
        (g,) = pullback(jnp.ones_like(f))
        bad = ~jnp.isfinite(f) | ~jnp.all(jnp.isfinite(g), axis=-1)
        return jnp.where(bad, jnp.inf, f), jnp.where(bad[:, None], 0.0, g)

    return vg


def _init_state_b(vg, x0, m, tol):
    bsz, d = x0.shape
    dtype = x0.dtype
    f0, g0 = vg(x0)
    return _State(
        k=jnp.zeros((), jnp.int32),
        x=x0,
        f=f0,
        g=g0,
        s_hist=jnp.zeros((bsz, m, d), dtype),
        y_hist=jnp.zeros((bsz, m, d), dtype),
        rho_hist=jnp.zeros((bsz, m), dtype),
        converged=(_rownorm(g0) < tol) & jnp.isfinite(f0),
        failed=jnp.isinf(f0),
        tprev=jnp.ones((bsz,), dtype),
        bx=x0,
        bf=f0,
        bg=g0,
    )


def _make_linesearch_b(fb, *, ftol, max_linesearch, c1):
    def linesearch(x, f, g, direction, done, t0):
        # done rows are pre-satisfied: their (frozen) state can never
        # pass the strict Armijo test, and one such row would otherwise
        # drag the whole batch through max_linesearch extra objective
        # evaluations.  Failed trials jump to the minimizer of the
        # quadratic through (0, f), slope g·dir, and (t, f(t)) (clamped
        # to [0.1t, 0.5t]): every trial is a FULL-batch objective pass
        # gated by the worst row, and plain halving needs ~12 of them
        # per iteration on badly scaled steps
        gd = _rowdot(g, direction)
        # noise floor: near convergence the predicted decrease falls
        # below the objective's f32 evaluation noise and the strict
        # Armijo test rejects EVERY step size, dragging the whole batch
        # through deep backtracks; the relaxed accept is resolved by the
        # ftol rule
        eps = ftol * jnp.maximum(1.0, jnp.abs(f))

        def body(carry):
            t, ok, j = carry
            fnew = fb(x + t[:, None] * direction)
            fnew = jnp.where(jnp.isfinite(fnew), fnew, jnp.inf)
            ok_new = ok | (fnew <= f + c1 * t * gd + eps)
            tq = -gd * t * t / (2.0 * (fnew - f - gd * t))
            tq = jnp.where(jnp.isfinite(tq), tq, 0.0)
            # the objective may evaluate in a wider dtype; the carry
            # must not
            tq = jnp.clip(tq, 0.1 * t, 0.5 * t).astype(t.dtype)
            return jnp.where(ok_new, t, tq), ok_new, j + 1

        def cond(carry):
            _, ok, j = carry
            return jnp.any(~ok) & (j < max_linesearch)

        t, ok, n_ls = lax.while_loop(cond, body, (t0, done, 0))
        return t, ok, n_ls

    return linesearch


def _make_step_b(fb, *, m, dtype, tol, ftol, max_linesearch, c1):
    """One lockstep L-BFGS iteration over a batched objective ``fb``."""
    vg_fb = _make_vg_b(fb)
    linesearch = _make_linesearch_b(fb, ftol=ftol,
                                    max_linesearch=max_linesearch, c1=c1)

    def step(carry):
        state, iters, ls_hist, trials = carry
        done = state.converged | state.failed
        with jax.named_scope("optim.lbfgs_batched.two_loop"):
            direction = -_two_loop_b(
                state.g, state.s_hist, state.y_hist, state.rho_hist,
                state.k, m
            )
        descent = _rowdot(state.g, direction) < 0.0
        direction = jnp.where(descent[:, None], direction, -state.g)

        # rows with no curvature history step along raw steepest
        # descent, whose scale is arbitrary: bound their first trial
        # step length by 1.  With history, warm-start from the row's
        # last accepted step — every extra trial is a FULL-batch
        # objective pass, so a straggler row that keeps needing tiny
        # steps must not re-pay the whole backtrack from t=1 every
        # iteration
        has_hist = jnp.any(state.rho_hist > 0.0, axis=-1)
        t0 = jnp.where(
            has_hist & descent,
            jnp.minimum(1.0, 4.0 * state.tprev),
            1.0 / jnp.maximum(1.0, _rownorm(direction)),
        ).astype(dtype)
        with jax.named_scope("optim.lbfgs_batched.linesearch"):
            t, ok, n_ls = linesearch(
                state.x, state.f, state.g, direction, done, t0)
        x_new = state.x + t[:, None] * direction
        with jax.named_scope("optim.lbfgs_batched.value_and_grad"):
            f_new, g_new = vg_fb(x_new)

        s = x_new - state.x
        y = g_new - state.g
        sy = _rowdot(s, y)
        slot = state.k % m
        accept = (
            ok
            & (f_new <= state.f + ftol * jnp.maximum(1.0, jnp.abs(state.f)))
            & ~done
        )
        # gate history on accept (not just the linesearch ok), matching
        # the per-series minimize_lbfgs: a step rejected at the
        # re-evaluation must not poison the curvature history
        good_pair = (sy > 1e-10) & accept
        upd = lambda hist, v: hist.at[:, slot].set(
            jnp.where(good_pair[:, None], v, hist[:, slot])
        )
        s_hist = upd(state.s_hist, s)
        y_hist = upd(state.y_hist, y)
        rho_hist = state.rho_hist.at[:, slot].set(
            jnp.where(good_pair, 1.0 / jnp.maximum(sy, 1e-30),
                      state.rho_hist[:, slot])
        )
        x_out = jnp.where(accept[:, None], x_new, state.x)
        f_out = jnp.where(accept, f_new, state.f)
        g_out = jnp.where(accept[:, None], g_new, state.g)
        conv = state.converged | (
            _rownorm(g_out) < tol * jnp.maximum(1.0, _rownorm(x_out))
        )
        conv = conv | (
            accept
            & (state.f - f_new <= ftol * jnp.maximum(1.0, jnp.abs(f_new)))
        )
        better = f_out < state.bf
        new_state = _State(
            k=state.k + 1,
            x=x_out,
            f=f_out,
            g=g_out,
            s_hist=s_hist,
            y_hist=y_hist,
            rho_hist=rho_hist,
            converged=conv,
            failed=state.failed | (~ok & ~conv & ~done),
            tprev=jnp.where(accept, t, state.tprev),
            bx=jnp.where(better[:, None], x_out, state.bx),
            bf=jnp.where(better, f_out, state.bf),
            bg=jnp.where(better[:, None], g_out, state.bg),
        )
        iters = jnp.where(done, iters, state.k + 1)
        if ls_hist is not None:
            ls_hist = ls_hist.at[state.k].set(n_ls)
        return new_state, iters, ls_hist, trials + n_ls

    return step


def _lockstep(fun_batched, x0, cap, count_evals, *, max_iters, history, tol,
              ftol, max_linesearch, c1):
    """Run the lockstep loop from ``x0`` until the budget is spent or at
    most ``cap`` rows remain unconverged (``cap=None``: until none does)
    -> ``(state, iters, ls_hist, trials)``; ``ls_hist`` is ``None`` unless
    ``count_evals``, ``trials`` is always there: the line search's trials
    summed over the iterations run, one ``int32`` scalar of the carry (the
    stage gate reports it, ``models/lockstep.py``)."""
    bsz, _ = x0.shape
    dtype = x0.dtype
    if ftol is None:
        ftol = 1e-9 if dtype == jnp.float64 else 1e-6
    knobs = dict(m=history, dtype=dtype, tol=tol, ftol=ftol,
                 max_linesearch=max_linesearch, c1=c1)
    vg = _make_vg_b(fun_batched)
    init = _init_state_b(vg, x0, history, tol)
    iters0 = jnp.zeros((bsz,), jnp.int32)
    ls0 = jnp.zeros((max_iters,), jnp.int32) if count_evals else None
    step_full = _make_step_b(fun_batched, **knobs)

    def cond_full(carry):
        state = carry[0]
        undone = ~(state.converged | state.failed)
        if cap is None:
            live = jnp.any(undone)
            return (state.k < max_iters) & live
        n_undone = jnp.sum(undone)
        # keep lockstepping only while the stragglers outnumber the cap
        return (state.k < max_iters) & (n_undone > cap)

    return lax.while_loop(cond_full, step_full,
                          (init, iters0, ls0, jnp.zeros((), jnp.int32)))


def _result_b(state, iters):
    """(x, f, grad_norm) all refer to the best-seen iterate per row."""
    return LBFGSResult(
        x=state.bx,
        f=state.bf,
        converged=state.converged & jnp.isfinite(state.bf),
        iters=iters,
        grad_norm=_rownorm(state.bg),
    )


def minimize_lbfgs_batched(
    fun_batched: Callable[[jax.Array], jax.Array],
    x0: jax.Array,
    *,
    max_iters: int = 50,
    history: int = 8,
    tol: float = 1e-6,
    ftol: float | None = None,
    max_linesearch: int = 20,
    c1: float = 1e-4,
    count_evals: bool = False,
    straggler_fun: "Callable[[jax.Array], Callable] | None" = None,
    straggler_cap: int | None = None,
) -> "LBFGSResult | tuple[LBFGSResult, dict]":
    """Jointly minimize ``B`` independent problems with ONE batched objective.

    ``fun_batched(x[B, d]) -> f[B]`` evaluates every problem at once — the
    entry point for fused whole-batch objectives (e.g. the Pallas CSS kernel,
    ``ops.pallas_kernels``) that cannot be traced per-series under ``vmap``.
    Semantics match ``vmap(minimize_lbfgs)``: each row carries its own
    history, step size, and convergence flag; rows are block-diagonal so the
    gradient of ``sum(f)`` is exactly the per-row gradient.  All rows step in
    lockstep (as they do under ``vmap`` of a ``while_loop``); finished rows
    freeze their state.

    **Straggler compaction**: every lockstep pass costs a full-batch
    objective evaluation even when most rows have converged — the tail of
    the fit pays O(B) per iteration for O(B/8) live rows.  When
    ``straggler_fun`` is given and ``straggler_cap`` (default
    ``max(128, B // 8)``) is below ``B``, the fit is
    :func:`lbfgs_batched_stage1` followed, in the same trace, by
    :func:`lbfgs_batched_stage2` on ``straggler_fun(row_indices)``: the
    lockstep loop exits as soon as at most ``straggler_cap`` rows remain
    unconverged, those rows (and their whole optimizer state) continue as a
    ``[cap, d]`` problem for the remaining iteration budget, and the results
    scatter back.  In exact arithmetic per-row trajectories are identical to
    the uncompacted run (the step-size carry, accept tests, and convergence
    tests are all per-row, and batched objectives compute rows
    independently) — but the compacted program IS a different compiled
    program, so f32 fusion differences exist, and rows sitting on flat or
    non-convex stretches can amplify them into different (equally valid)
    optima.  Callers should hold compaction to the same distribution-level
    parity bar as any backend change (see the bench parity gates), not to
    bitwise equality.

    ``count_evals=True`` additionally returns ``(result, info)`` with
    ``info["ls_evals"]`` (``[max_iters] int32`` — linesearch objective
    evaluations per outer iteration), ``info["compact_at"]`` (iteration at
    which compaction engaged, == iterations run when it never did), and
    ``info["cap"]`` (0 when uncompacted).  The counts ride the loops' carry:
    the optimizer that runs is the same with the flag on or off.
    """
    bsz = x0.shape[0]
    knobs = dict(max_iters=max_iters, history=history, tol=tol, ftol=ftol,
                 max_linesearch=max_linesearch, c1=c1)
    cap = straggler_cap if straggler_cap is not None else max(128, bsz // 8)
    if straggler_fun is not None and cap < bsz:
        res1, carry = lbfgs_batched_stage1(
            fun_batched, x0, straggler_cap=cap, count_evals=count_evals,
            **knobs)
        return lbfgs_batched_stage2(
            straggler_fun(carry.idxc), res1, carry, **knobs)
    final, iters, ls_hist, _ = _lockstep(
        fun_batched, x0, None, count_evals, **knobs)
    result = _result_b(final, iters)
    if not count_evals:
        return result
    return result, {"ls_evals": ls_hist, "compact_at": final.k, "cap": 0}


# -- straggler compaction: the stage-1 / stage-2 split ------------------------
#
# Stage 1 is the lockstep loop with the early exit plus the gather of the
# straggler state; stage 2 finishes the gathered rows and scatters them back.
# Traced together (minimize_lbfgs_batched) they are one program.  A model fit
# that can check a scalar on the host runs stage 1 as its own compiled
# program and dispatches — and therefore only ever traces and compiles —
# stage 2 when stragglers actually remain, which roughly halves compile time
# for batches that never need it.  The decision is a pure function of the
# fit's inputs (same data -> same undone count -> same programs), so
# journaled resumes stay bitwise-reproducible per config.


class StragglerCarry(NamedTuple):
    """Stage-1 exit state that stage 2 resumes from.

    ``state`` is the full optimizer state of the (at most ``cap``)
    unconverged rows; ``idx`` are the scatter indices (fill value ``bsz`` ->
    dropped on scatter), ``idxc`` the clamped gather indices model code uses
    to repack the objective's data for the compacted problem.  ``undone``
    and ``k`` are the host-checkable dispatch gate: stage 2 is worth
    dispatching iff ``undone > 0`` and ``k < max_iters`` (the shared budget
    — see the truncation contract in :func:`lbfgs_batched_stage2`);
    ``trials`` rides beside them: the line search's trials summed over
    stage 1's ``k`` iterations, what the gate reports when tracing is on.
    ``ls_hist`` is the pass accounting of ``count_evals`` (``None`` when
    off: no leaf, so the compiled programs are those of an uncounted fit)."""

    state: _State  # compacted [cap, ...] optimizer state
    idx: jax.Array  # [cap] scatter indices (fill = bsz: dropped)
    idxc: jax.Array  # [cap] clamped gather indices
    iters: jax.Array  # [bsz] per-row iteration counts at stage-1 exit
    undone: jax.Array  # [] int32 unconverged-row count at stage-1 exit
    k: jax.Array  # [] int32 stage-1 exit iteration
    trials: jax.Array  # [] int32 linesearch evals summed over stage 1
    ls_hist: "jax.Array | None" = None  # [max_iters] int32 linesearch evals


def pass_info(carry: StragglerCarry, ls_hist=None) -> dict:
    """The ``count_evals`` dict of a compacted fit, from its carry (and from
    stage 2's ``ls_hist`` once that has run)."""
    return {"ls_evals": carry.ls_hist if ls_hist is None else ls_hist,
            "compact_at": carry.k, "cap": carry.idx.shape[0]}


def lbfgs_batched_stage1(
    fun_batched: Callable[[jax.Array], jax.Array],
    x0: jax.Array,
    *,
    straggler_cap: int,
    max_iters: int = 50,
    history: int = 8,
    tol: float = 1e-6,
    ftol: float | None = None,
    max_linesearch: int = 20,
    c1: float = 1e-4,
    count_evals: bool = False,
) -> "tuple[LBFGSResult, StragglerCarry]":
    """Stage 1 of the compacted batched L-BFGS, as a standalone traceable.

    Runs the lockstep loop until at most ``straggler_cap`` rows remain
    unconverged, then gathers the straggler state into the ``[cap, ...]``
    layout and returns ``(result_as_if_done, carry)``.  When no rows remain
    unconverged the result IS the final answer (stage 2 would run zero
    iterations and scatter the state back unchanged); otherwise the caller
    runs :func:`lbfgs_batched_stage2` with a compacted objective built from
    ``carry.idxc``.

    ``straggler_cap`` must be < the batch size (callers gate on
    :func:`compaction_cap`).  ``count_evals`` threads the linesearch history
    through the loop and hands it on as ``carry.ls_hist``
    (:func:`pass_info`).
    """
    bsz, _ = x0.shape
    cap = int(straggler_cap)
    if cap >= bsz:
        raise ValueError(
            f"straggler_cap {cap} must be < batch {bsz} (an uncompacted fit "
            "has no stage 2 to defer — use minimize_lbfgs_batched)")
    stage1, iters, ls_hist, trials = _lockstep(
        fun_batched, x0, cap, count_evals, max_iters=max_iters,
        history=history, tol=tol, ftol=ftol, max_linesearch=max_linesearch,
        c1=c1)
    undone1 = ~(stage1.converged | stage1.failed)
    # out-of-range fill indices read row bsz-1 and are dropped on the
    # scatter, so duplicates never corrupt live rows.  At k == max_iters with
    # more than cap rows undone this size=cap gather drops the excess: see
    # the truncation contract in lbfgs_batched_stage2.
    idx = jnp.nonzero(undone1, size=cap, fill_value=bsz)[0]
    idxc = jnp.minimum(idx, bsz - 1)
    take = lambda a: a[idxc]
    sub = _State(
        k=stage1.k,
        x=take(stage1.x), f=take(stage1.f), g=take(stage1.g),
        s_hist=take(stage1.s_hist), y_hist=take(stage1.y_hist),
        rho_hist=take(stage1.rho_hist),
        converged=take(stage1.converged), failed=take(stage1.failed),
        tprev=take(stage1.tprev),
        bx=take(stage1.bx), bf=take(stage1.bf), bg=take(stage1.bg),
    )
    result = _result_b(stage1, iters)
    carry = StragglerCarry(state=sub, idx=idx, idxc=idxc, iters=iters,
                           undone=jnp.sum(undone1).astype(jnp.int32),
                           k=stage1.k, trials=trials, ls_hist=ls_hist)
    return result, carry


def lbfgs_batched_stage2(
    fun_sub_batched: Callable[[jax.Array], jax.Array],
    full: LBFGSResult,
    carry: StragglerCarry,
    *,
    max_iters: int = 50,
    history: int = 8,
    tol: float = 1e-6,
    ftol: float | None = None,
    max_linesearch: int = 20,
    c1: float = 1e-4,
) -> "LBFGSResult | tuple[LBFGSResult, dict]":
    """Stage 2: finish the compacted stragglers and scatter them back.

    ``fun_sub_batched`` is the compacted objective over the ``[cap, d]``
    problem (the model builds it from ``carry.idxc`` — a row gather of the
    panel, or a column gather of a folded one); ``full`` is stage 1's
    as-if-done result, into which the finished straggler rows are
    scattered: per scattered row, ``converged & isfinite(f)`` and the grad
    norm come from the sub state, untouched rows keep stage 1's values
    verbatim.  Returns ``(result, info)`` when stage 1 counted passes
    (``carry.ls_hist``), else the result.

    TRUNCATION CONTRACT: the budget is SHARED with stage 1 (``carry.k``
    continues counting toward the same ``max_iters``).  The stage-1
    ``size=cap`` gather silently drops the excess when stage 1 exits at
    ``max_iters`` with more than ``cap`` rows undone — benign only because
    the sub-loop then runs zero steps and the dropped rows' state is
    unchanged by the scatter.  Any change that gives stage 2 its OWN budget
    must first make the gather lossless.
    """
    return lbfgs_batched_stage2_counted(
        fun_sub_batched, full, carry, max_iters=max_iters, history=history,
        tol=tol, ftol=ftol, max_linesearch=max_linesearch, c1=c1)[0]


def lbfgs_batched_stage2_counted(fun_sub_batched, full, carry, *, max_iters,
                                 history=8, tol=1e-6, ftol=None,
                                 max_linesearch=20, c1=1e-4):
    """:func:`lbfgs_batched_stage2` -> ``(what it returns, (iters, trials))``:
    beside the result the two ``int32`` scalars the stage-2 loop counted,
    the iterations it ran (``k_final - carry.k``) and its line search's
    trials from 0 — what ``models/lockstep.py`` defers to the read-back's
    span when tracing is on, and drops otherwise."""
    dtype = carry.state.x.dtype
    if ftol is None:
        ftol = 1e-9 if dtype == jnp.float64 else 1e-6
    stage2_max_iters = max_iters
    assert stage2_max_iters == max_iters, (
        "stage-2 straggler budget must equal max_iters while the size=cap "
        "gather can truncate at max_iters (make the gather lossless before "
        "giving stage 2 its own budget)")
    # runs once per TRACE of the enclosing program, so the counter counts
    # stage-2 compile trips, not steady-state dispatches
    obs.counter("optim.stage2_compact_traces").inc()
    step_sub = _make_step_b(
        fun_sub_batched, m=history, dtype=dtype, tol=tol, ftol=ftol,
        max_linesearch=max_linesearch, c1=c1)

    def cond_sub(c):
        state = c[0]
        return (state.k < stage2_max_iters) & jnp.any(
            ~(state.converged | state.failed))

    sub_f, sub_iters, ls_hist, trials = lax.while_loop(
        cond_sub, step_sub,
        (carry.state, carry.iters[carry.idxc], carry.ls_hist,
         jnp.zeros((), jnp.int32)))
    counts = (sub_f.k - carry.k, trials)
    put = lambda a, s: a.at[carry.idx].set(s, mode="drop")
    result = LBFGSResult(
        x=put(full.x, sub_f.bx),
        f=put(full.f, sub_f.bf),
        converged=put(full.converged,
                      sub_f.converged & jnp.isfinite(sub_f.bf)),
        iters=put(full.iters, sub_iters),
        grad_norm=put(full.grad_norm, _rownorm(sub_f.bg)),
    )
    if ls_hist is None:
        return result, counts
    return (result, pass_info(carry, ls_hist)), counts


def batched_minimize(
    fun: Callable[[jax.Array, jax.Array], jax.Array],
    x0: jax.Array,
    data: jax.Array,
    **kwargs,
) -> LBFGSResult:
    """vmap ``minimize_lbfgs`` over problems: ``fun(params[d], data_row)``.

    ``x0``: ``[batch, d]`` initial points; ``data``: ``[batch, ...]`` per-
    problem data (e.g. each series' observations).  This is the rebuild's
    replacement for the reference's per-series optimizer loop: one XLA
    computation fits every series at once.
    """
    solver = partial(minimize_lbfgs, **kwargs)
    return jax.vmap(lambda x, row: solver(lambda p: fun(p, row), x))(x0, data)


# -- bounded-parameter transforms (BOBYQA replacement) ----------------------


def sigmoid_to_interval(u, lo, hi):
    """Map R -> (lo, hi)."""
    return lo + (hi - lo) * jax.nn.sigmoid(u)


def interval_to_sigmoid(x, lo, hi):
    """Inverse of :func:`sigmoid_to_interval` (x strictly inside)."""
    p = (x - lo) / (hi - lo)
    p = jnp.clip(p, 1e-7, 1 - 1e-7)
    return jnp.log(p) - jnp.log1p(-p)


def softplus_inverse(y):
    return jnp.log(jnp.expm1(jnp.maximum(y, 1e-10)))
