"""Resilient fit execution: sanitize -> fit -> retry ladder -> fallback.

The batch analog of Spark task retry (PAPER.md: per-series numerics ran
inside executor tasks, and a failed task was simply re-run elsewhere).
Here a "task" is a ROW of a monolithic vmapped fit, so recovery is a
gather/re-fit/scatter ladder:

1. **Sanitize** the input panel (``reliability.sanitize``): repair or
   exclude rows no fit can survive (inf, interior NaN, constant, all-NaN).
2. **Primary fit** via the model's public ``fit`` — one compiled program
   over the whole batch, exactly as before.
3. **Retry rung**: rows that came back non-converged or non-finite are
   gathered into a small padded batch (the host-side analog of the
   straggler compaction in ``utils.optim`` — ``optim.retry_cap`` bounds
   the distinct compiled shapes) and re-fit with a larger iteration budget
   (at least twice the primary's: its ``max_iters`` keyword, else the fit's
   own default, read from its signature).
4. **Fallback rung**: rows still failing are re-fit on the conservative
   path — portable ``scan`` backend (no Pallas), no straggler compaction,
   largest budget.  ``utils.linalg.ridge_solve`` independently falls back
   from the unpivoted Cholesky to ``jnp.linalg.solve`` for non-SPD rows.
5. Rows that survive nothing are marked ``DIVERGED`` (NaN params, flagged)
   instead of silently propagating NaNs into downstream aggregates.

What a rung STARTS a row from, where the model takes ``init_params`` (a
model that does not starts every rung at its own start): a row that ran
the budget of the attempt before the rung OUT and left a finite point —
``iters`` at that attempt's ``max_iters``, parameters and objective finite
— is **continued**: it enters at that point (the primary's end point for
the first rung, the first rung's for the second), unperturbed, so a rung
finishes a fit instead of repeating it.  Any other failed row (stopped
before its budget at a stalled line search, non-finite) starts from the
primary's best-seen point, deterministically perturbed: the point it
stopped at is where it got stuck.  The choice reads the row's own
read-back and nothing else.

Per-row outcomes are reported as :class:`~.status.FitStatus` codes;
``meta`` records what every rung attempted and recovered.
"""

from __future__ import annotations

import inspect
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..utils import optim
from .sanitize import sanitize as _sanitize
from .status import STATUS_DTYPE, FitStatus, status_counts

__all__ = ["BegunFit", "RetryRung", "ResilientFitResult", "begin_fit",
           "default_ladder", "finish_fit", "resilient_fit"]


class RetryRung(NamedTuple):
    """One rung of the retry ladder."""

    name: str  # label recorded in meta
    status: int  # FitStatus granted to rows this rung rescues
    kwargs: dict  # fit-kwarg overrides (filtered to the fit's signature)
    perturb: float = 0.0  # init perturbation scale (models with init_params)


class ResilientFitResult(NamedTuple):
    """Batched fit output with per-row status and run metadata.

    Field layout extends ``models.base.FitResult``; arrays are host-side
    (the ladder assembles rows across several device programs).  ``params``
    of a call in which no row was rewritten is the device read-back's own
    buffer and read-only: copy it before writing into it.
    """

    params: np.ndarray  # [batch, k]
    neg_log_likelihood: np.ndarray  # [batch]
    converged: np.ndarray  # [batch] bool
    iters: np.ndarray  # [batch]
    status: np.ndarray  # [batch] int8 FitStatus codes
    meta: dict


class BegunFit(NamedTuple):
    """A resilient fit between its two halves: probed and DISPATCHED
    (:func:`begin_fit`), not yet read back (:func:`finish_fit`).  Holds the
    device arrays of one chunk; whoever drops it frees them."""

    single: bool  # the caller's panel was one series
    y_clean: jax.Array  # the sanitized panel: what a rung gathers from
    status: np.ndarray  # the sanitizer's per-row codes
    san_meta: dict
    fit_kwargs: dict  # as the primary fit got them (align hint resolved)
    res: object  # the primary fit's result, device arrays
    deferred: object  # ``obs.take_deferred()`` of the dispatching thread


def default_ladder(fit_fn: Callable, base_iters: Optional[int] = None) -> tuple:
    """The standard two-rung ladder, filtered to what ``fit_fn`` accepts.

    Rung 1 (``RETRIED``) re-fits with a LARGER iteration budget, at least
    double the primary fit's; rung 2 (``FALLBACK``) escalates to the
    portable scan backend with compaction disabled and a budget of at least
    four times it.  The primary's budget is ``base_iters`` (the caller's
    ``max_iters`` keyword) and otherwise the fit's own default, read from
    its signature (:func:`_default_max_iters`: GARCH's 80 gives 160 / 320);
    60 is assumed only where neither says.  What a rung STARTS from is
    :func:`resilient_fit`'s to decide, row by row: the end point of the
    attempt before it for a row that ran out of budget, else a start
    perturbed by the rung's ``perturb``.  Models without a ``backend`` /
    ``max_iters`` knob simply get whichever overrides their signature
    supports.
    """
    base = int(base_iters or _default_max_iters(fit_fn) or 60)
    return (
        RetryRung("retry", int(FitStatus.RETRIED),
                  {"max_iters": max(120, 2 * base)}, perturb=0.05),
        RetryRung("fallback", int(FitStatus.FALLBACK),
                  {"max_iters": max(240, 4 * base), "backend": "scan",
                   "compact": False},
                  perturb=0.2),
    )


def _signature_parameters(fit_fn: Callable):
    """``fit_fn``'s parameters by name; ``None`` where it has no signature
    to read (builtins / C callables)."""
    try:
        return inspect.signature(fit_fn).parameters
    except (TypeError, ValueError):
        return None


def _default_max_iters(fit_fn: Callable) -> Optional[int]:
    """The iteration budget ``fit_fn`` runs with when no ``max_iters``
    keyword is passed: its signature's default (a ``functools.partial``'s
    bound value included), ``None`` where it has none."""
    param = (_signature_parameters(fit_fn) or {}).get("max_iters")
    if param is None or isinstance(param.default, bool) \
            or not isinstance(param.default, int):
        return None
    return int(param.default)


def _accepted_kwargs(fit_fn: Callable, kwargs: dict) -> dict:
    """Drop overrides the fit's signature does not accept."""
    params = _signature_parameters(fit_fn)
    if params is None:  # builtins / C callables: pass through
        return dict(kwargs)
    if any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()):
        return dict(kwargs)
    return {k: v for k, v in kwargs.items() if k in params}


def _failed_mask(res) -> np.ndarray:
    """Rows whose fit cannot be trusted: non-converged or non-finite."""
    params = np.asarray(res.params)
    nll = np.asarray(res.neg_log_likelihood)
    conv = np.asarray(res.converged)
    finite = np.isfinite(params)
    # a reduction along a short last axis walks the rows one by one (four
    # times the flat one's time on a [131072, 33] chunk): by rows only if
    # the flat one finds something
    rows_finite = np.isfinite(nll) if finite.all() \
        else finite.all(axis=-1) & np.isfinite(nll)
    return ~(conv & rows_finite)


def _structurally_excluded(res) -> np.ndarray:
    """Rows the model itself refused (too short / empty): retry cannot help."""
    if res.status is None:
        return np.zeros(np.asarray(res.converged).shape, bool)
    return np.asarray(res.status) == FitStatus.EXCLUDED


def _ran_out_of_budget(params, nll, iters, budget) -> np.ndarray:
    """Of an attempt's failed rows, those the BUDGET stopped at a usable
    point: ``iters`` reached the attempt's ``max_iters`` (``None``: the fit
    has no such knob, so no row can tell) with parameters and objective
    finite.  A row the optimizer gave up on before that (a stalled line
    search) or a non-finite one is not among them."""
    if budget is None:
        return np.zeros(np.shape(iters), bool)
    return ((np.asarray(iters) >= budget) & np.isfinite(nll)
            & np.isfinite(params).all(axis=-1))


@jax.jit
def _scatter_rows(params, slots, values):
    """``params`` with ``values[i]`` at row ``slots[i]``; a slot past the
    last row is dropped (a rung's pad rows and the rows it did not rescue)."""
    return params.at[slots].set(values.astype(params.dtype), mode="drop")


def _recoverable_oom(e: BaseException) -> bool:
    """RESOURCE_EXHAUSTED is recoverable one layer up (``fit_chunked``
    backoff) — no crash dump for it here; the lazy import avoids the
    runner<->chunked cycle (chunked imports this module)."""
    from .chunked import is_resource_exhausted

    return is_resource_exhausted(e)


@obs.dump_on_failure("resilient_fit", unless=_recoverable_oom)
def resilient_fit(
    fit_fn: Callable,
    y,
    *,
    policy: str = "impute",
    ladder: Optional[Sequence[RetryRung]] = None,
    sanitize: bool = True,
    max_retry_rows: Optional[int] = None,
    seed: int = 0,
    **fit_kwargs,
) -> ResilientFitResult:
    """Run ``fit_fn(y, **fit_kwargs)`` with sanitization and the retry ladder.

    ``fit_fn`` is any public model fit (``models.arima.fit`` partials
    included) returning a ``FitResult``.  ``policy`` is the sanitizer's
    non-finite policy (``"impute"`` / ``"exclude"`` / ``"raise"``);
    ``sanitize=False`` skips the pass entirely (rows the models reject
    still come back ``EXCLUDED`` via their own status output).  ``ladder``
    overrides :func:`default_ladder`; an empty ladder means failed rows go
    straight to ``DIVERGED``.  ``seed`` drives the deterministic init
    perturbation of retry rungs (a rung whose ``perturb`` is 0 hands its
    rows no start at all, so it neither perturbs nor continues).

    COST NOTE: every non-converged row enters the ladder, and the default
    fallback rung re-fits on the portable ``scan`` backend — much slower
    per row than the fused path (one row of a million through it from the
    fit's own start was a fifth of a GARCH walk's device time, PERF.md §6,
    PR 53).  Where the fit takes ``init_params`` a row that merely ran out
    of budget is CONTINUED from the point it reached (module docstring), so
    a rung pays for the remainder of its fit; a fit without the keyword
    pays each rung from its start, and the rungs' budgets are multiples of
    the primary's (``default_ladder``).  A panel where a sizable fraction
    of rows legitimately fails to converge within budget can therefore
    spend far longer in the ladder than in the primary fit.  For
    latency-critical serving, bound the ladder with ``max_retry_rows``
    (rows beyond the cap skip the ladder and are flagged ``DIVERGED``
    directly, ladder rungs recorded in ``meta`` either way), pass a custom
    ``ladder`` without the scan rung, or ``ladder=()`` to disable retries
    entirely.

    An ``align_mode=`` entry in ``fit_kwargs`` (the chunk driver's static
    alignment plan) is forwarded to ``fit_fn`` only when its signature
    accepts it, and is downgraded to ``"general"`` whenever the sanitizer
    actually repaired or excluded rows — the repairs change the panel's
    NaN pattern, so a stronger panel-level claim may no longer hold on
    the cleaned values.

    Healthy rows are fitted bit-identically to a direct ``fit_fn`` call on
    the SANITIZED panel: the ladder only ever re-fits the failed subset,
    scattering recovered rows back without touching their neighbors.  (A
    direct call on the raw panel can differ at f32 fusion level when
    sanitization changes the panel's NaN pattern — the alignment mode, and
    with it the compiled program, is chosen per panel.)
    """
    return finish_fit(
        fit_fn,
        begin_fit(fit_fn, y, policy=policy, sanitize=sanitize, **fit_kwargs),
        ladder=ladder, max_retry_rows=max_retry_rows, seed=seed)


@obs.dump_on_failure("resilient_fit", unless=_recoverable_oom)
def begin_fit(fit_fn: Callable, y, *, policy: str = "impute",
              sanitize: bool = True,
              not_before: Optional[Callable[[], None]] = None,
              **fit_kwargs) -> BegunFit:
    """The first half of :func:`resilient_fit`: the sanitizer's probe and
    the primary fit's dispatch, to the last program it queues (a lazy
    optimizer's stage gate is waited for in here; the result is not).
    ``not_before()`` is called between the two, the probe read and nothing
    of the fit dispatched yet: a lane that fits one chunk ahead
    (``plan.LaneRunner``) waits there for the chunk before this one to have
    queued ITS last program, so the device's first-in first-out queue never
    holds this chunk's stage 1 before that chunk's stage 2.  Whatever it
    raises leaves this function with nothing dispatched."""
    yb = jnp.asarray(y)
    single = yb.ndim == 1
    if single:
        yb = yb[None, :]
    b = yb.shape[0]

    # static align-mode hint (the chunk driver's per-walk plan): held back
    # from the fit until the sanitizer has run — repairs and exclusions
    # CHANGE the panel's NaN pattern (imputed gaps, inf->NaN edges, rows
    # NaN-ed out), so a panel-level "dense"/"no-trailing" claim may no
    # longer hold on the cleaned values.  Untouched chunks keep the fast
    # plan; touched chunks downgrade to the always-correct "general" path
    # (deterministic per chunk content, so journaled resumes reproduce it)
    align_hint = fit_kwargs.pop("align_mode", None)

    if sanitize:
        rep = _sanitize(yb, policy=policy)
        y_clean, status, san_meta = rep.values, rep.status.copy(), rep.meta
    else:
        y_clean = yb
        status = np.zeros(b, STATUS_DTYPE)
        san_meta = {"policy": "off"}
    if align_hint is not None:
        if san_meta.get("rows_sanitized") or san_meta.get("rows_excluded"):
            align_hint = "general"
        if "align_mode" in _accepted_kwargs(fit_fn, {"align_mode": None}):
            fit_kwargs = {**fit_kwargs, "align_mode": align_hint}

    if not_before is not None:
        not_before()
    obs.settle()  # nothing pending from a fit that raised before its read-back
    with obs.span("fit.primary", rows=b):
        res = fit_fn(y_clean, **fit_kwargs)
    # what the fit deferred to its read-back's span goes with the result:
    # the read-back may run on another thread (a chunk fitted ahead)
    return BegunFit(single, y_clean, status, san_meta, fit_kwargs, res,
                    obs.take_deferred())


@obs.dump_on_failure("resilient_fit", unless=_recoverable_oom)
def finish_fit(fit_fn: Callable, begun: BegunFit, *,
               ladder: Optional[Sequence[RetryRung]] = None,
               max_retry_rows: Optional[int] = None,
               seed: int = 0) -> ResilientFitResult:
    """The second half of :func:`resilient_fit`: the read-back of what
    :func:`begin_fit` dispatched, the retry ladder over its failed rows and
    the ``DIVERGED`` mark, on the calling thread."""
    single, y_clean, status, san_meta, fit_kwargs, res, deferred = begun
    b = y_clean.shape[0]
    # fit.readback: the first host read of the result waits for the device.
    # On a lane that fits one chunk ahead (plan.LaneRunner) the next chunk's
    # programs are queued behind it already; on any other the next chunk is
    # not dispatched before these passes are done
    with obs.span("fit.readback", rows=b) as readback:
        # the read-back's own buffer, read-only: a second copy of a wide
        # result is 14-16 ms on the driver's thread with the device idle
        # (17 MB; PERF.md §6, PR 49).  Rows a rung rescues are written into
        # the DEVICE's array and the whole read back once more (6 ms, and
        # the same kind of buffer as an untouched chunk's); a host copy is
        # made only for the DIVERGED mark and for a fit that returns host
        # arrays
        params = np.asarray(res.params)
        params_dev = res.params if isinstance(res.params, jax.Array) else None
        if params is res.params:  # a host array is the fit's own: as before
            params = params.copy()
        nll = np.array(res.neg_log_likelihood)
        conv = np.array(res.converged)
        iters = np.array(res.iters)
        excluded = (status == FitStatus.EXCLUDED) | _structurally_excluded(res)
        status = np.maximum(
            status, np.where(excluded, FitStatus.EXCLUDED, 0)
        ).astype(STATUS_DTYPE)

        failed = _failed_mask(res) & ~excluded
        if obs.enabled():
            # what the lockstep optimizer spent: every row of the chunk
            # rides along for iters_max iterations, iters_sum of them useful
            # (and what stage 2's loops counted: lockstep.fit deferred it)
            readback.set(iters_max=int(iters.max(initial=0)),
                         iters_sum=int(iters.sum()),
                         failed=int(failed.sum()), **obs.settle(deferred))
    # ladder size cap: rows past the cap skip the ladder entirely (they
    # stay in ``failed`` and are flagged DIVERGED below), bounding the
    # worst-case ladder cost on mass-non-convergence panels
    retryable = failed.copy()
    over_cap = 0
    if max_retry_rows is not None and int(retryable.sum()) > max_retry_rows:
        skipped = np.nonzero(retryable)[0][max_retry_rows:]
        retryable[skipped] = False
        over_cap = skipped.size
    rungs = (default_ladder(fit_fn, fit_kwargs.get("max_iters"))
             if ladder is None else tuple(ladder))
    # register every rung's counters up front (zero-valued when no row ever
    # enters the ladder) so the run summary always reports the full
    # ladder-rung vocabulary, not just the rungs that happened to fire
    for rung in rungs:
        obs.counter(f"ladder.{rung.name}.attempted")
        obs.counter(f"ladder.{rung.name}.rescued")
    rung_meta = []
    rng = np.random.default_rng(seed)
    supports_init = "init_params" in _accepted_kwargs(
        fit_fn, {"init_params": None}
    )
    # the attempt BEFORE the rung at hand: its iteration budget, and what it
    # read back for the rows still failing (rows, params, nll, iters) —
    # ``None`` while that attempt is the primary fit, whose read-back is the
    # batch's own arrays
    budget = fit_kwargs.get("max_iters") or _default_max_iters(fit_fn)
    left = None

    for depth, rung in enumerate(rungs):
        idx = np.nonzero(retryable)[0]
        if idx.size == 0:
            break
        # gather the failed subset into an aligned bucket (same contract as
        # the optimizer's straggler compaction: out-of-range pad rows are
        # copies of a real row whose results are dropped on the scatter)
        cap = optim.retry_cap(idx.size)
        pad_idx = optim.gather_pad_indices(idx, cap)
        y_sub = y_clean[jnp.asarray(pad_idx)]
        kw = {**fit_kwargs, **rung.kwargs}
        continued = np.zeros(idx.size, bool)
        if supports_init and rung.perturb:
            # deterministic perturbed init: best-seen params of the failed
            # rows, jittered relative to their own magnitude
            base = np.nan_to_num(params[pad_idx], nan=0.0,
                                 posinf=0.0, neginf=0.0)
            jitter = rung.perturb * (1.0 + np.abs(base)) * rng.standard_normal(
                base.shape
            )
            start = base + jitter
            # ... but a row that ran its budget out and left a usable point
            # is CONTINUED: it enters at the end point of the attempt before
            # this rung, unperturbed, and so does every pad slot that
            # repeats it (a lockstep bucket runs as long as its slowest row)
            at = (params[idx], nll[idx], iters[idx]) if left is None else \
                tuple(a[np.searchsorted(left[0], idx)] for a in left[1:])
            continued = _ran_out_of_budget(*at, budget)
            if continued.any():
                slot = np.searchsorted(idx, pad_idx)
                start = np.where(continued[slot][:, None], at[0][slot], start)
            kw["init_params"] = jnp.asarray(
                start.astype(y_clean.dtype)  # no host round-trip for dtype
            )
        kw = _accepted_kwargs(fit_fn, kw)
        with obs.span(f"fit.rung.{rung.name}", rows=int(idx.size), cap=cap,
                      continued=int(continued.sum())) as span:
            sub = fit_fn(y_sub, **kw)
            sub_failed = _failed_mask(sub)[: idx.size]
            sub_iters = np.asarray(sub.iters)[: idx.size]
            rung_iters = int(sub_iters.max(initial=0))
            span.set(iters=rung_iters, rescued=int((~sub_failed).sum()))
        rescued = idx[~sub_failed]
        if rescued.size:
            keep = np.nonzero(~sub_failed)[0]
            if params_dev is not None:
                slots = np.full(cap, b, np.int32)
                slots[keep] = rescued
                params_dev = _scatter_rows(params_dev, jnp.asarray(slots),
                                           jnp.asarray(sub.params))
            else:
                params[rescued] = np.asarray(sub.params)[keep]
            nll[rescued] = np.asarray(sub.neg_log_likelihood)[keep]
            conv[rescued] = np.asarray(sub.converged)[keep]
            iters[rescued] = sub_iters[keep]
            status[rescued] = np.maximum(status[rescued], rung.status)
            failed[rescued] = False
            retryable[rescued] = False
        # what this rung leaves for the next to continue from
        budget = kw.get("max_iters")
        still = np.nonzero(sub_failed)[0]
        left = (idx[still], np.asarray(sub.params)[still],
                np.asarray(sub.neg_log_likelihood)[still], sub_iters[still])
        rung_meta.append({
            "rung": rung.name, "depth": depth,
            "attempted": int(idx.size), "rescued": int(rescued.size),
            "continued": int(continued.sum()), "iters": rung_iters,
            "kwargs": {k: v for k, v in rung.kwargs.items()},
        })
        obs.counter(f"ladder.{rung.name}.attempted").add(int(idx.size))
        obs.counter(f"ladder.{rung.name}.rescued").add(int(rescued.size))

    if params_dev is not None and params_dev is not res.params:
        params = np.asarray(params_dev)  # the rescued rows in their places

    # survivors of every rung: flag DIVERGED and refuse to hand back
    # non-finite params as if they were estimates
    if failed.any():
        if not params.flags.writeable:
            params = params.copy()
        params[failed] = np.nan
        nll[failed] = np.nan
        conv[failed] = False
        status[failed] = np.maximum(status[failed], FitStatus.DIVERGED)

    meta = {
        "sanitize": san_meta,
        "ladder": rung_meta,
        "retry_rows_over_cap": over_cap,
        "status_counts": status_counts(status),
    }
    if single:
        return ResilientFitResult(
            params[0], nll[0], conv[0], iters[0], status[0], meta
        )
    return ResilientFitResult(params, nll, conv, iters, status, meta)
