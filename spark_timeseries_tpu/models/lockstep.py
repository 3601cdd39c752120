"""The lockstep fit driver (L4): one assembly of a family's fit programs.

ARIMA-CSS, Holt-Winters, GARCH and ARGARCH fit a panel the same way: prepare
the panel once, minimize a batched objective with the lockstep L-BFGS of
``utils.optim`` from one or several starts, and turn the optimizer's result
into a :class:`~.base.FitResult`.  A family declares what is its own
(:class:`Family`); this module builds the compiled programs from it and owns
the straggler compaction's host side: the predicate that chooses the lazily
compiled stage-1 / stage-2 pair, the gate between the two stages, and the
``fit.stage1`` / ``fit.stage2`` spans around them.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .. import obs
from ..utils import optim
from .base import FitResult, derive_status

PALLAS = ("pallas", "pallas-interpret")


class Prepared(NamedTuple):
    """A panel made ready for the optimizer (``Family.prep``)."""

    x0s: tuple  # one [B, d] initial point per start, in optimizer space
    ok: jax.Array  # [B] structural gate: enough observations to fit the row
    # [B] effective observations: the optimizer minimizes the MEAN objective
    # (the family's objective / scale) — same argmin as the sum, but O(1)
    # gradients keep the relative stopping rule reachable at f32 instead of
    # stalling on the accumulation noise floor of a ~1k-term sum (the
    # reported objective is the sum, f * scale)
    scale: jax.Array
    series: tuple  # per-row natural-layout data of the scan objective
    # kernel-layout pytree of the pallas objective (series ride the lanes):
    # folded ONCE here, outside every while_loop — XLA does not hoist the
    # [B, T] relayout out of the line search; a straggler subset is a gather
    # of its COLUMNS (grid-aligned by the cap), so nothing is re-folded
    folded: Any = ()
    rows: tuple = ()  # [B, ...] arrays that objective reads row by row
    # ``scale`` and every entry of ``rows`` hold ONE value in all rows (a
    # dense panel: every length is the panel's), which XLA folds into the
    # batch's objective as constants (:func:`_straggler_fun`)
    uniform: bool = False
    # [B, ...] arrays ``Family.to_natural`` takes beside the optimizer's
    # point: a row's own change of variables (a regression's coefficients
    # as offsets from its least-squares start in units of its residual's
    # scale, so that the optimizer's relative tests see O(1) parameters)
    natural: tuple = ()


def straggler_cap(cells: int) -> Optional[int]:
    """The straggler cap of a batch of ``cells`` optimizer rows, ``None``
    where the compaction does not pay: ``optim``'s rule, and every family's
    unless it declares its own (``Family.cap``)."""
    cap = optim.compaction_cap(cells)
    return cap if cells >= optim.COMPACT_MIN_BATCH and cap < cells else None


class Family(NamedTuple):
    """What a model family declares about its fit, each thing once."""

    backend: str  # resolved: "scan" | "pallas" | "pallas-interpret"
    prep: Callable[..., Prepared]  # (panel, *extra) -> Prepared
    # (folded, rows) -> fb(x[B, d]) -> f[B]: the fused objective (the sum,
    # unscaled), over the whole prepared panel or a straggler subset of it
    objective: Callable[[Any, tuple], Callable]
    # (x[d], one row of Prepared.series) -> f: the portable per-series
    # objective (the retry ladder's fallback rung, the tests' reference);
    # None: ``objective`` is the family's on the scan backend too
    scan_objective: Optional[Callable]
    # optimizer space [B, d] (and ``Prepared.natural``) -> reported params
    # [B, k]
    to_natural: Callable
    # results -> (per-row choice among the starts' results, the rows whose
    # choice is not the first start's: one int32, None from one start);
    # None declares ONE start, whose stage 2 finalizes in its own program
    merge: Optional[Callable] = None
    # optimizer rows -> their straggler cap (None: no compaction).  A grid
    # of K orders optimizes K cells a panel row under a cap of its own
    cap: Callable[[int], Optional[int]] = straggler_cap
    # (folded, idxc) -> the stragglers' folded pytree; None: the optimizer's
    # rows are the panel's columns (``pallas_kernels.take_series``)
    take: Optional[Callable] = None


def finalize(res, ok, scale, natural=(), to_natural=lambda x: x) -> FitResult:
    """Optimizer result -> FitResult: natural-space params (NaN where the
    row could not be fit) and the unscaled objective."""
    params = jnp.where(ok[:, None], to_natural(res.x, *natural), jnp.nan)
    return FitResult(
        params,
        jnp.where(ok, res.f * scale, jnp.nan),
        res.converged & ok,
        res.iters,
        derive_status(ok, res.converged, params),
    )


def _merged(family: Family, results):
    """``(merged result, rows a later start won)``: ``Family.merge``'s
    pair, or the one start's result and ``None``."""
    return family.merge(results) if family.merge else (results[0], None)


def _mean_objective(family: Family, folded, rows, scale):
    fb = family.objective(folded, rows)
    return lambda x: fb(x) / scale


def _take_folded(family: Family, p: Prepared, idxc):
    """The columns ``idxc`` of the prep's folded data."""
    from ..ops import pallas_kernels as pk

    return (family.take or pk.take_series)(p.folded, idxc)


def _stragglers(family: Family, p: Prepared, idxc):
    """The rows ``idxc`` of the fused objective's data and scale."""
    *rows, scale = optim.take_rows((*p.rows, p.scale), idxc)
    return _take_folded(family, p, idxc), tuple(rows), scale


def _straggler_fun(family: Family, p: Prepared):
    """``idxc -> fb_sub``: the mean objective over the gathered rows
    ``idxc`` — the tail of stage 1's line search
    (``optim.lbfgs_batched_stage1``) and the in-trace stage 2 of
    :func:`fit_program`.

    On a ``uniform`` panel it ROUNDS like the batch's objective.  There
    XLA folds the lengths into the batch's epilogue as constants (``css *
    (2 pi / 998)``, ``* 499``, ``* (1 / 998)``) and not through a gather
    (``css / n[idxc]``, ``/ scale[idxc]``): one unit in the last place on
    an eighth of ``arima111``'s rows (PERF.md §6, PR 39), which the
    interpolated step of a row at its noise floor amplifies into another
    path.  So the sub-objective takes the FIRST rows' ``rows`` and
    ``scale`` — any rows will do, and a static slice of a constant is a
    constant — and a barrier keeps its division by ``scale`` from merging
    with the family's epilogue, as it does not in the batch's program.
    (The lazily compiled stage 2 keeps its gathered operands, and with
    them the digits it had before the tail.)"""
    if not p.uniform:
        return lambda idxc: _mean_objective(
            family, *_stragglers(family, p, idxc))

    def fun(idxc):
        *rows, scale = (a[:idxc.shape[0]] for a in (*p.rows, p.scale))
        fb = family.objective(_take_folded(family, p, idxc), tuple(rows))
        return lambda x: jax.lax.optimization_barrier(fb(x)) / scale

    return fun


def fit_program(family: Family, max_iters: int, tol: float,
                count_evals: bool = False, compact: bool = True):
    """The whole fit as ONE traceable program: small batches,
    ``compact=False``, the scan backend, and a fit traced under a caller's
    ``jit`` (which cannot check the straggler count on the host).  Batches
    at or above ``optim.COMPACT_MIN_BATCH`` compact in-trace."""

    def run(xb, *extra):
        p = family.prep(xb, *extra)
        info = None
        if family.backend in PALLAS or family.scan_objective is None:
            fb = _mean_objective(family, p.folded, p.rows, p.scale)
            cap = family.cap(p.x0s[0].shape[0]) if compact else None
            straggler_fun = (None if cap is None
                             else _straggler_fun(family, p))
            results = []
            for s, x0 in enumerate(p.x0s):
                # pass accounting reports the first start's passes
                counted = count_evals and s == 0
                res = optim.minimize_lbfgs_batched(
                    fb, x0, max_iters=max_iters, tol=tol,
                    count_evals=counted, straggler_fun=straggler_fun,
                    straggler_cap=cap)
                if counted:
                    res, info = res
                results.append(res)
        else:
            def mean_scan(x, row):
                return family.scan_objective(x, row[:-1]) / row[-1]

            results = [
                optim.batched_minimize(mean_scan, x0, (*p.series, p.scale),
                                       max_iters=max_iters, tol=tol)
                for x0 in p.x0s]
        out = finalize(_merged(family, results)[0], p.ok, p.scale,
                       p.natural, family.to_natural)
        return (out, info) if count_evals else out

    return run


def stage1_program(family: Family, max_iters: int, tol: float,
                   count_evals: bool = False):
    """Stage 1 of the lazily compiled compact fit: the prep and, per start,
    the lockstep loop with the straggler early exit, its line search's tail
    on the stragglers' objective -> the finalized as-if-done result and
    ``{"starts": (per start: carry, res, sub), "fin": (ok, scale,
    natural)}``; where
    several starts were merged also ``"merge_switched"``, the rows whose
    merged result is not the first start's (one ``int32`` that :func:`fit`
    defers to the read-back's span when tracing is on and never reads).
    Pallas backends only (:func:`fit` holds the gate)."""

    def run(xb, *extra):
        p = family.prep(xb, *extra)
        fb = _mean_objective(family, p.folded, p.rows, p.scale)
        cap = family.cap(p.x0s[0].shape[0])
        tail_fun = _straggler_fun(family, p)
        results, starts = [], []
        for s, x0 in enumerate(p.x0s):
            res1, carry = optim.lbfgs_batched_stage1(
                fb, x0, straggler_cap=cap, max_iters=max_iters, tol=tol,
                count_evals=count_evals and s == 0, tail_fun=tail_fun)
            # the compacted problem's data is gathered HERE, so stage 2 is a
            # pure function of its inputs, folds nothing, and keeps stable
            # shapes: ONE compiled stage 2 serves every start that needs it
            starts.append({"carry": carry, "res": res1,
                           "sub": _stragglers(family, p, carry.idxc)})
            results.append(res1)
        merged, switched = _merged(family, results)
        fin = (p.ok, p.scale, p.natural)
        aux = {"starts": tuple(starts), "fin": fin}
        if switched is not None:
            aux["merge_switched"] = switched
        return finalize(merged, *fin, family.to_natural), aux

    return run


def stage2_program(family: Family, max_iters: int, tol: float):
    """Stage 2 of the lazy compact fit: finish ONE start's gathered
    stragglers on the compacted objective and scatter back — compiled on
    the first call where a stage 1 left unconverged rows.  A one-start
    family finalizes here; with several starts the result goes to
    :func:`merge_program`.  Beside its result it returns ``(iterations run,
    line-search trials)``, two scalars of the loop's carry that :func:`fit`
    defers to the read-back's span when tracing is on."""

    def run(start, fin=None):
        res, counts = optim.lbfgs_batched_stage2_counted(
            _mean_objective(family, *start["sub"]), start["res"],
            start["carry"],
            max_iters=max_iters, tol=tol)
        if family.merge is not None:
            return res, counts
        if start["carry"].ls_hist is None:
            return finalize(res, *fin, family.to_natural), counts
        return (finalize(res[0], *fin, family.to_natural), res[1]), counts

    return run


def merge_program(family: Family):
    """Re-merge the per-start results after a stage 2 ran -> the finalized
    result and ``Family.merge``'s count of the rows a later start won."""

    def run(results, fin):
        merged, switched = family.merge(list(results))
        return finalize(merged, *fin, family.to_natural), switched

    return run


def _kernel_attrs(series_block, stage_attrs, rows: int) -> dict:
    """What a stage span says of the objective kernel: ``series_block`` and
    ``adjoint_series_block``, the series its value-only kernel and its
    adjoint take per grid step over ``rows`` series (1,024 x the
    ``pallas_kernels.series_rows`` of their shapes), and the family's own
    static ``stage_attrs``; computed on the host and only when the tracing
    plane is on."""
    if not obs.enabled():
        return {}
    block = {} if series_block is None else {
        "series_block": series_block(rows, "sum"),
        "adjoint_series_block": series_block(rows, "adjoint")}
    return {**block, **(stage_attrs or {})}


def _span_scalars(carry):
    """What ``fit.stage1`` reports of a start's loop beside ``undone``."""
    return carry.k, carry.trials, carry.tail_trials


def fit(args: tuple, *, backend: str, compact: bool, max_iters: int,
        inline: Callable, stage1: Callable, stage2: Callable,
        merge: Optional[Callable] = None,
        series_block: Optional[Callable[[int, str], int]] = None,
        stage_attrs: Optional[dict] = None,
        cells: Optional[int] = None,
        cap: Callable[[int], Optional[int]] = straggler_cap):
    """Fit the panel ``args[0]`` with a family's compiled programs, each
    given as a thunk that looks it up (only the programs that run are looked
    up, and stage 2 is traced and compiled only when a stage 1 leaves
    unconverged rows).  Returns what the programs return: a ``FitResult``,
    or ``(FitResult, info)`` from programs built with ``count_evals``.
    ``series_block`` is the family's ``(rows, mode) -> series per grid
    step`` of its objective kernel (``mode`` ``"sum"``, the value-only call,
    or ``"adjoint"``) and ``stage_attrs`` what else it has to say of a
    kernel step (every family ``adjoint_panels``, the panel-sized operands
    of its objective's adjoint call; ARIMA also ``lag_terms``,
    ``lag_span``; a grid of orders also ``orders``, ``cells``); both are
    reported on the stage spans and choose nothing.  ``cells`` is the
    optimizer's row count where it is not the panel's (a grid of K orders:
    K cells a row; the spans' ``rows`` / ``undone`` then count cells) and
    ``cap`` the family's straggler rule (``Family.cap``).

    The lazy pair runs on the pallas backends when the batch is concrete
    and the family's cap says the compaction pays (:func:`straggler_cap`:
    ``optim.COMPACT_MIN_BATCH``, and a cap below the batch).  ``fit.stage1`` spans the dispatch of every
    start's stage 1 and the host's wait for it at the first gate (the later
    starts' gates find their scalars ready); ``fit.stage2`` opens only
    around a dispatch.

    What the loops counted (tracing on only; off, the gate makes the two
    transfers it needs, ``undone`` and ``k``, and no other): ``fit.stage1``
    reports ``trials``, ``tail_trials`` and ``iter_passes``, the starts' sums
    of the carry's line-search trials (of either width), of those that ran
    on the tail's cap rows, and of ``k`` (``iters`` is their max), and
    ``starts``;
    stage 2 is never waited for here, so its two scalars are handed to
    ``obs.defer`` as device handles and land on the ``fit.readback`` span
    that reads the result anyway (``stage2_iters``, ``stage2_trials``; 0
    and 0 where no stage 2 was dispatched).

    A family of several starts (``len(aux["starts"]) > 1``; a one-start
    family's span lines are as they were) also reports, tracing on only:
    on ``fit.stage1`` ``undone_by_start`` and ``iters_by_start`` (the gate's
    own reads, per start; ``undone`` stays their sum, ``iters`` their max);
    on each ``fit.stage2`` ``start``, the 0-based start it finishes; a
    ``fit.merge`` span (``starts``) around the dispatch of the re-merge that
    follows a stage 2; and on ``fit.readback`` ``merge_switched``, the rows
    whose merged result is not the first start's — the one ``int32`` of the
    program whose result is RETURNED (stage 1's merge, or the re-merge that
    replaced it), deferred once a fit and never read here.
    """
    xb = args[0]
    bsz = xb.shape[0] if cells is None else cells
    cap = cap(bsz)
    if not (compact and backend in PALLAS
            and not isinstance(xb, jax.core.Tracer) and cap is not None):
        return inline()(*args)
    run1 = stage1()
    with obs.span("fit.stage1", rows=bsz) as span:
        out, aux = run1(*args)
        starts = aux["starts"]
        several = len(starts) > 1
        if obs.enabled():
            # what the span reports comes over beside ``undone``, not in
            # round trips of its own after it (a scalar read is some 1 ms)
            for s in starts:
                for scalar in _span_scalars(s["carry"]):
                    scalar.copy_to_host_async()
        # the gate: a tiny scalar sync per start
        undone = [int(s["carry"].undone) for s in starts]
        if obs.enabled():
            ks, trials, tail = zip(*(map(int, _span_scalars(s["carry"]))
                                     for s in starts))
            span.set(iters=max(ks), undone=sum(undone), starts=len(starts),
                     iter_passes=sum(ks), trials=sum(trials),
                     tail_trials=sum(tail),
                     **({"undone_by_start": tuple(undone),
                         "iters_by_start": ks} if several else {}),
                     **_kernel_attrs(series_block, stage_attrs, bsz))
    obs.defer(stage2_iters=0, stage2_trials=0)
    results, reran, info = [], False, None
    for i, (start, n_undone) in enumerate(zip(starts, undone)):
        carry, res = start["carry"], start["res"]
        counted = carry.ls_hist is not None
        if counted:
            info = optim.pass_info(carry)
        # stage 2 shares stage 1's iteration budget, so an exhausted budget
        # skips the dispatch (the scatter of unchanged state is an identity)
        if n_undone > 0 and int(carry.k) < max_iters:
            with obs.span("fit.stage2", rows=cap,
                          **({"start": i} if several else {}),
                          **_kernel_attrs(series_block, stage_attrs, cap)):
                res, (iters2, trials2) = (
                    stage2()(start) if merge
                    else stage2()(start, aux["fin"]))
            obs.defer(stage2_iters=iters2, stage2_trials=trials2)
            if counted:
                res, info = res
            reran = True
        results.append(res)
    switched = aux.get("merge_switched")
    if reran and merge:
        # a one-start family's "merge" is its finalize: no span of its own
        with obs.span("fit.merge", starts=len(starts)) if several \
                else contextlib.nullcontext():
            out, switched = merge()(tuple(results), aux["fin"])
    elif reran:
        out = results[0]
    if switched is not None:
        obs.defer(merge_switched=switched)
    return out if info is None else (out, info)
