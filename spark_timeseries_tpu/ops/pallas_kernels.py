"""Pallas TPU kernels for the sequential-recursion hot paths.

The reference runs its model recursions (ARMA one-step-ahead CSS errors,
GARCH conditional variance, EWMA smoothing, Holt-Winters state) as
per-series JVM loops (``sparkts/models/ARIMA.scala`` ``logLikelihoodCSS`` /
``gradientLogLikelihoodCSSARMA``, ``GARCH.scala``, ``EWMA.scala``,
``HoltWinters.scala`` — SURVEY.md §2.2, upstream paths unverified).  The
portable rebuild expresses them as ``jax.vmap(lax.scan)`` (``models/*``),
which is correct everywhere but pays one XLA loop iteration — several HBM
round trips — per time step.

These kernels fuse the recursion into grid steps whose series block lives in
VMEM: series are folded to ``[time, 8, 128]`` tiles (sublane x lane = 1024
series per block), the natural f32 vector-register shape, so every time step
is a handful of full-width VPU ops instead of an XLA loop iteration.  The
fit-objective kernels (CSS, GARCH, Holt-Winters; forward and adjoint) take
``R`` such tiles per grid step (:func:`series_rows`): ``R`` independent
recurrence chains share one loop iteration.

SERIES LENGTH IS UNBOUNDED: the grid is ``(series_block, time_chunk)`` with
the chunk axis innermost (TPU iterates it sequentially), each chunk holding
``_CHUNK_T`` steps in VMEM.  Lag reads that cross a chunk boundary come from
a NEIGHBOR INPUT BLOCK (the previous time chunk mapped as a second input);
recursion state that flows forward/backward across chunks (trailing errors,
the variance/smoothing carry, adjoint carries, gradient accumulators) lives
in VMEM scratch, which persists across the sequential chunk dimension.
Parameter-gradient outputs use the revisited-output-block reduction pattern
(initialize at the first chunk, accumulate, final value flushed once).

Like the reference — which hand-derives ``gradientLogLikelihoodCSSARMA``
rather than relying on automatic differentiation — every kernel pair ships a
hand-derived adjoint recursion, exposed through ``jax.custom_vjp`` so the
batched L-BFGS driver (``utils/optim``) can differentiate the objectives
without XLA's scan transpose.  Cotangents flow to the parameters (for GARCH
on request also to the squared returns and the variance seed, for callers
that build the returns themselves; ARGARCH's AR(1) mean runs INSIDE its
own kernel pair, whose adjoint reduces the mean's two gradients beside the
three); everything else is a constant of the fit objective, so these entry
points are used inside fit objectives and not exposed as general autodiff
building blocks.

Everything here is optional: callers gate on :func:`supported` and fall back
to the ``lax.scan`` implementations (same semantics, cross-checked by
``tests/test_pallas.py`` in interpret mode and by the on-device parity gate
in ``bench.py``).

PROFILED HEADROOM (next round): the per-step recursion loops are bounded by
loop machinery, not arithmetic — the vectorized (full-tile, static-slice)
rewrite of the non-recursive kernels here (autocorr, HR moments) measured
~6x over their per-step forms.  The CSS/GARCH/EWMA recursions with q <= 1
are LINEAR with per-series constant coefficients, i.e. affine maps of the
carry, so they admit an in-VMEM log-depth doubling scan over composed
(m, b) pairs exactly like ``ops.seqparallel.sp_ewma_smooth`` does across
shards — ~10 full-tile steps instead of ~1000 serial ones, for both the
forward and the (also affine) adjoint recursion.  One invariant to keep:
the value-only and residual-saving variants of an objective must emit
BITWISE-identical values (same accumulation association), so the scan
rewrite must cover every mode of a kernel at once, not just the hot one.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_derivatives import (SymbolicZero,
                                    custom_vjp_primal_tree_values)
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Order = Tuple[int, int, int]

_SUBL = 8  # f32 sublanes per vector register
_LANES = 128  # TPU lane width
_SBLK = _SUBL * _LANES  # series per grid step (1024)
_CHUNK_T = 1024  # time steps resident in VMEM per grid step
# Scoped-VMEM override shared by every kernel here: a handful of
# [_CHUNK_T, 8, 128] blocks plus double buffering exceeds the default budget.
_VMEM_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=100 * 1024 * 1024)

_ZERO = lambda: jnp.zeros((_SUBL, _LANES), jnp.float32)  # noqa: E731

# -- the series-block width of the fit-objective kernels ---------------------
#
# A recurrence kernel's time step is a chain of dependent vector ops on ONE
# register of 1,024 series: the VPU waits out each op's latency with nothing
# else to issue.  The kernels of the three fit objectives, forward and
# adjoint, therefore take R registers of series per step — blocks of ``(cs,
# 8 * R, 128)`` — so R independent chains share one loop iteration and one
# basic block.  No series' arithmetic or accumulation order changes, series
# never mix, and the HBM arrays keep their layout: only the grid and the
# block shape differ.
_R_CHOICES = (4, 2)  # tried widest first; 1 is today's block
_TILE_BYTES = _SBLK * 4  # one (8, 128) f32 tile
# what one call's pipelined blocks and scratch may take of _VMEM_PARAMS'
# 100 MB; the rest is Mosaic's own (its internal scratch, spilled vregs).
# 88 MiB until PR 45: three panel blocks of 960 steps at R = 4 count 90.7 /
# 91.4 MiB (the multiplicative Holt-Winters adjoint and save_resid forward),
# compile for the v5e and run bit-equal to R = 1 (PERF.md §6, PR 45); of the
# cells' other calls none changes its width between the two budgets
_VMEM_BLOCK_BUDGET = 92 * 1024 * 1024


def _vmem_bytes(layout, r: int = 1) -> int:
    """What a call of ``layout`` = ``(ins, outs, scratch)`` holds in VMEM at
    width ``r``: every input and output block twice (the pipeline's double
    buffer, the ``_prev`` neighbour blocks included), scratch once.  ``ins``
    / ``outs``: ``(leading size, index map)`` pairs of ``(n, 8 r, 128)``
    blocks (an entry with a third element states its own block shape, and
    counts as ``n`` tiles a register all the same: :func:`_design_block`);
    ``scratch``: leading sizes."""
    ins, outs, scratch = layout
    return ((2 * sum(e[0] for e in ins + outs) + sum(scratch))
            * r * _TILE_BYTES)


def series_rows(nsub: int, layout, r_best: int) -> int:
    """R, the vector registers of series an objective kernel (forward or
    adjoint) takes per time step — from static facts only: ``nsub`` (=
    ``Bp / 128``, the folded panel's sublane rows) divisible by ``8 * R``;
    the call's VMEM (:func:`_vmem_bytes` of its ``layout``) inside
    ``_VMEM_BLOCK_BUDGET``; and at most ``r_best``, the width the chip
    showed best for this kernel and mode (``_CSS_R`` / ``_GARCH_R`` /
    ``_HW_R``, the adjoints' ``_ADJOINT_R``).  A 256-row serving
    batch, a padded retry bucket, a compaction cap that is 1,024- but not
    2,048-aligned: R = 1, today's program."""
    for r in _R_CHOICES:
        if (r <= r_best and nsub % (_SUBL * r) == 0
                and _vmem_bytes(layout, r) <= _VMEM_BLOCK_BUDGET):
            return r
    return 1


# the width the chip showed best for each objective's ADJOINT kernel, the
# cotangent formed from the plane (PERF.md §6, PR 37: ns a time step and
# 1,024-series block over [131072, 1000] at R = 1 / 2 / 4, the inner loop's
# bundles a step in brackets — CSS (1, 1) 20.29 / 11.61 / 11.22 (23 / 25 /
# 31); the seasonal lag set {1, 24, 25} 38.34 / 20.69 / 12.70 (55 / 55 / 69); one
# order of the grid over gathered cells 29.56 / 15.59 / 11.15 (41 / 44 / 59);
# GARCH 16.30 / 12.46 / 11.43 (21 / 31 / 65: four chains fill every vector
# slot and spill, and over the 16,384-row compaction read 17.66 / 13.54 /
# 14.43).  Holt-Winters by ``mult`` (PR 43, over [131072, 960]): the
# additive adjoint reads one panel, 20.93 / 11.60 / 9.01 (30 / 33 / 51), at
# R = 4 on the vector slots and no longer on the HBM (13.2 against 12.9 at
# R = 2 over the 16,384-row compaction); the multiplicative adjoint (PR 45)
# reads three panels, 44.38 / 23.46 / 18.20 (55 / 68 / 104): 12 KB a step and
# block at 675 GB/s, and 46.25 / 26.19 / 22.58 over the compaction.  The
# adjoints write no panel: 11.2 ns is 8 KB at 730 GB/s)
_ADJOINT_R = {"css": 4, "garch": 4, "hw": {False: 4, True: 4}}


def _nsub(rows: int) -> int:
    return (rows + _pad_to(rows, _SBLK)) // _LANES


def _plane_zero(ref):
    """A zero ``(8 * R, 128)`` plane: one time step of ``ref``'s block."""
    return jnp.zeros(ref.shape[1:], jnp.float32)


def _fori(n, body, init, unroll: int = 1):
    """Sequential time loop with the index coerced to int32: under
    ``jax_enable_x64`` the loop variable would otherwise trace as int64,
    which pallas ref indexing cannot lower.  (Unrolling buys nothing for the
    RECURSION kernels: a step waits for the step before it, so the next
    iteration's ops cannot fill the idle slots.  What fills them is a second
    INDEPENDENT chain in the same iteration — :func:`series_rows`; on a v5e
    a step of the CSS / Holt-Winters / GARCH value-only kernels costs 17.6 /
    21.0 / 29.6 ns on one register of series and 22.9 / 28.2 / 35.4 ns on
    four, PERF.md §6, PR 31; the CSS adjoint's 20.3 ns on one and 44.9 on
    four, PR 37.  The fill sweeps' dependency chains are one
    select deep, and there loop machinery dominates: pass ``unroll`` > 1
    for those.)"""

    def body32(i, carry):
        return body(jnp.asarray(i, jnp.int32), carry)

    if unroll == 1:
        return lax.fori_loop(0, n, body32, init)
    if n % unroll:  # chunk lengths are 8-aligned, so 2/4/8 always divide
        raise ValueError(f"unroll={unroll} must divide n={n}")

    def outer(j, carry):
        i0 = j * jnp.int32(unroll)
        for k in range(unroll):  # Mosaic only full-unrolls, so do it by hand
            carry = body32(i0 + k, carry)
        return carry

    return lax.fori_loop(0, n // unroll, outer, init)


def supported(dtype, n_time: int) -> bool:
    """True when the fused kernels can run natively on this platform/dtype.

    ``n_time`` is unrestricted (time-chunked grids); it remains a parameter
    so callers keep passing their shape and future constraints stay cheap.
    """
    del n_time
    # a broken backend raises here rather than reading as "unsupported":
    # falling back to scan would hide the device from the caller
    platform = jax.devices()[0].platform
    return platform == "tpu" and jnp.dtype(dtype) == jnp.dtype(jnp.float32)


def _lags(n) -> Tuple[int, ...]:
    """A static lag set, ascending: an int ``n`` is the dense set ``1..n``
    (the plain ARMA orders), anything else the set itself (the live lags of
    a seasonal product polynomial: ``(1, 24, 25)`` for the airline model)."""
    if isinstance(n, int):
        return tuple(range(1, n + 1))
    return tuple(sorted(int(lag) for lag in n))


def _span(lags) -> int:
    """The largest lag of a set: what the carries and the mask reach back."""
    return max(lags, default=0)


def css_structural_ok(p, q) -> bool:
    """The CSS kernels' chunked layout: lag reads reach back at most one
    chunk (the neighbor input block), and the cross-chunk adjoint/error
    stashes interleave their reads (positions ``>= cs - lag``) with their
    writes (positions ``< lag``) inside one chunk, which is race-free only
    while ``lag <= chunk/2`` — so the LARGEST lag of either side (``p`` /
    ``q``: an order or a lag set, :func:`_lags`) must stay under
    ``_CHUNK_T // 2``."""
    return all((not isinstance(n, int) or n >= 0)
               and _span(_lags(n)) <= _CHUNK_T // 2 for n in (p, q))


def hw_structural_ok(period: int) -> bool:
    """The Holt-Winters kernels keep two whole ``[period, 8, 128]`` seasonal
    rings in VMEM scratch beside the chunk blocks; periods past one chunk
    blow the scoped-VMEM budget with an opaque Mosaic error, so they are
    rejected up front (use the scan backend)."""
    return 0 < period <= _CHUNK_T


def _pad_to(n: int, m: int) -> int:
    return (-n) % m


def _scoped(name):
    """Profiler annotation (SURVEY.md §5.1 rebuild analog): each fused
    objective shows up as one named block in jax.profiler / Perfetto traces."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapped(*a, **k):
            with jax.named_scope(name):
                return fn(*a, **k)
        return wrapped
    return deco


def _time_layout(t: int) -> Tuple[int, int, int]:
    """-> (padded_t, chunk_len, n_chunks) for a series of length ``t``."""
    tp8 = t + _pad_to(t, _SUBL)
    if tp8 <= _CHUNK_T:
        return tp8, tp8, 1
    tp = t + _pad_to(t, _CHUNK_T)
    return tp, _CHUNK_T, tp // _CHUNK_T


def _fold(x2d):
    """``[B, n] -> [n, Bp/128, 128]`` series folding: consecutive series map
    to consecutive lanes; the kernel grid walks 8-sublane blocks of axis 1."""
    b, n = x2d.shape
    x2d = jnp.pad(x2d, ((0, _pad_to(b, _SBLK)), (0, 0)))
    bp = x2d.shape[0]
    return x2d.T.reshape(n, bp // _LANES, _LANES)


def _unfold(x3d, b: int):
    """Inverse of :func:`_fold`: ``[n, Bp/128, 128] -> [B, n]``."""
    n = x3d.shape[0]
    return x3d.reshape(n, -1).T[:b]


def take_series(folded, idxc):
    """The series ``idxc`` (a multiple of 1024 of them) of every leaf of a
    folded pytree: series ride the lanes, so a row gather of the natural
    layout is a column gather here and nothing is re-folded (the straggler
    compaction of ``models.lockstep``)."""
    nb = idxc.shape[0] // _LANES
    return jax.tree_util.tree_map(
        lambda x3: x3.reshape(x3.shape[0], -1)[:, idxc].reshape(
            x3.shape[0], nb, _LANES), folded)


def series_major(x3d):
    """A folded panel's series as ROWS ``[Bp, n]``, materialised once, ahead
    of the loops that gather stragglers out of it (:func:`take_rows`).  A
    column gather of the folded panel costs a transpose of the WHOLE panel
    per call — XLA's TPU gather wants its slices minor — and inside a
    lockstep loop nothing hoists it (PERF.md §6, PR 46: until then the
    row-major differencing's by-product served, unasked)."""
    return jax.lax.optimization_barrier(
        _unfold(x3d, x3d.shape[1] * _LANES))


def take_rows(y_rows, idxc):
    """The series ``idxc`` (a multiple of 1024 of them) of a panel kept
    :func:`series_major`, folded: :func:`take_series`' columns of its
    ``y3``, read as rows."""
    return _fold(y_rows[idxc])


def _bs(n0: int, imap, r: int = 1):
    return pl.BlockSpec((n0, _SUBL * r, _LANES), imap)


def _cur(blk, c):  # current time chunk
    return (c, blk, 0)


def _prev(blk, c):  # previous time chunk (clamped; guarded by global-t checks)
    return (jnp.maximum(c - 1, 0), blk, 0)


def _fixed(blk, c):  # chunk-invariant block (params, seeds, reductions)
    return (0, blk, 0)


def _rev(nchunk):  # walk time chunks last-to-first
    return lambda blk, c: (nchunk - 1 - c, blk, 0)


def _rev_prev(nchunk):  # previous TIME chunk while walking backward
    return lambda blk, c: (jnp.maximum(nchunk - 2 - c, 0), blk, 0)


def _rev_panel(t):
    """A panel operand of an adjoint call as layout entries
    (:func:`_vmem_bytes`): its block of the time chunk, walked last to
    first, and past one chunk the neighbour block the lag reads reach."""
    _, cs, nchunk = _time_layout(t)
    return ([(cs, _rev(nchunk))]
            + ([(cs, _rev_prev(nchunk))] if nchunk > 1 else []))


# ---------------------------------------------------------------------------
# ARMA CSS one-step-ahead prediction errors (forward + hand-derived adjoint)
# ---------------------------------------------------------------------------
#
# Per series (reference ARIMAModel.logLikelihoodCSSARMA), over STATIC lag
# sets A (AR side) and M (MA side) with one coefficient plane per live lag
# — the plain ARMA(p, q) is the dense sets 1..p and 1..q, a multiplicative
# seasonal model the few non-zero lags of its product polynomials (the
# airline model (0,1,1)(0,1,1)_24: M = {1, 24, 25}, three lag terms a step
# where the dense range pays 25):
#   u_t = y_t - c - sum_{i in A} a_i * y_{t-i} - sum_{j in M} b_j * e_{t-j}
#   e_t = m_t * u_t        with m_t = [zb <= t < t_limit], y_{<0} = e_{<0} = 0
#
# Adjoint (reference gradientLogLikelihoodCSSARMA, generalized to an
# arbitrary upstream cotangent gbar of e):
#   al_t      = m_t * (gbar_t - sum_{j in M} b_j * al_{t+j})    (t descending)
#   dL/dc     = -sum_t al_t
#   dL/da_i   = -sum_t y_{t-i} * al_t
#   dL/db_j   = -sum_t e_{t-j} * al_t
#
# Parameter planes: [c, a_i (i in A, ascending), b_j (j in M, ascending)].
# Cross-chunk state: the forward carries the last max(M) errors (scratch);
# the backward carries the adjoints of the first max(M) positions of the
# next-later chunk (scratch; max(A) more for the data cotangent) and
# accumulates the k parameter gradients in the revisited output block.
#
# A SHARED DESIGN (``nx`` > 0 columns; regression with ARMA errors): the
# series the recurrence runs on is ``y_t - x_t' beta``, ``x [tp, nx]`` the
# same for every series and ``beta`` per series — ``nx`` more parameter
# planes after the ``k`` above, and the design's rows of the time chunk one
# more operand.  The forward forms that residual in VMEM BEFORE its serial
# loop, a product on the MXU a sublane row of the block: ``x_chunk [cs, nx] @
# beta[:, n, :] [nx, 128]`` is ``[cs, 128]`` with time on the sublanes, the
# layout a sublane-strided slice ``ref[:, n, :]`` of a ``(cs, 8 R, 128)``
# block reads and writes as it stands, so ``u[:, n, :] = y[:, n, :] -
# product`` and nothing is turned; the loop then reads ``u`` where it read
# ``y``.  "sum" keeps ``u`` in scratch; "both" writes it out beside the
# errors, and the adjoint reads ``(u3, e3)`` as it reads ``(y3, e3)`` of a
# plain fit; mode "u" stops after the residual and writes it alone (the
# start's Hannan-Rissanen sweep reads that panel).  The adjoint forms no
# data cotangent ``g_t = dS/du_t = al_t - sum_{i in A} a_i al_{t+i}`` at
# all: its loop leaves the final ``al_t`` in its scratch, and since
#   sum_t x_t g_t = sum_s (x_s - sum_{i in A} a_i x_{s-i}) al_s,
# AFTER the loop ``[-x_chunk, x_chunk shifted by each i in A]' @ al`` — ONE
# transposed product a sublane row, the same slices, the shifts taken of the
# WHOLE design outside the call (so nothing crosses a chunk) — lands in the
# ``nx`` planes' rows of the revisited gradient block, the lag blocks scaled
# by the series' own ``a_i``.  Both kernels take the chunk's products in
# slabs of at most 64 steps, a loop (:func:`_design_slab`).  Every product
# is f32 at ``HIGHEST`` (six bfloat16 passes of the MXU: PRECISION.md).
# ``nx`` = 0 is the plain kernel, equation for equation.


def _css_fwd_kernel(ar, ma, t_limit, cs, hp, mode, *refs, nx=0):
    # mode "e":    errors out (the css_errors vjp building block)
    # mode "sum":  ONLY the per-series sum of squares leaves the kernel
    #              (linesearch evaluations: the [B, T] error write + re-read
    #              is the pass's HBM bill); errors live in a VMEM scratch
    # mode "both": errors out AND the sum, accumulated in the SAME order as
    #              "sum" (the optimizer compares f across both paths; mixed
    #              accumulation orders stall rows at the noise floor)
    # mode "tail": ONLY the last q errors leave the kernel (the forecast
    #              carry rebuild: a read-only pass over y instead of a full
    #              [B, T] error write the caller immediately discards)
    # mode "u":    (a shared design only) the residual panel alone: no
    #              recurrence runs
    p, q = len(ar), _span(ma)  # AR planes; the MA side's reach
    refs = list(refs)
    y_ref = refs.pop(0)
    # with a design the chunk before is u's, not y's: a carry (cu_ref)
    yp_ref = refs.pop(0) if hp and not nx else None
    par_ref = refs.pop(0)
    zb_ref = refs.pop(0)
    x_ref = refs.pop(0) if nx else None
    e_ref = refs.pop(0) if mode in ("e", "both") else None
    u_ref = refs.pop(0) if nx and mode in ("both", "u") else None  # a panel
    css_ref = refs.pop(0) if mode in ("sum", "both") else None
    tail_ref = refs.pop(0) if mode == "tail" else None
    if mode in ("sum", "tail") and q > 0:
        e_ref = refs.pop(0)  # scratch: lag reads still need recent errors
    if nx and u_ref is None:
        u_ref = refs.pop(0)  # scratch: u never leaves VMEM
    ce_ref = refs.pop(0)
    cu_ref = refs.pop(0) if nx and hp and ar else None
    c = pl.program_id(1)
    base = c * cs
    zb = zb_ref[0]

    zero = _plane_zero(zb_ref)

    @pl.when(c == 0)
    def _():
        for j in range(max(q, 1)):
            ce_ref[j] = zero
        if css_ref is not None:
            css_ref[0] = zero

    if nx:
        _design_residual(y_ref, x_ref, par_ref, 1 + p + len(ma), nx, u_ref)
        if mode == "u":
            return
        y_ref = u_ref  # the recurrence reads u where it read y

    def y_far(tl, i):  # y_{t-i} of the chunk before
        if cu_ref is not None:  # slot s: u at global base - max(A) + s
            return cu_ref[jnp.clip(_span(ar) + tl - i, 0, _span(ar) - 1)]
        return yp_ref[jnp.clip(cs + tl - i, 0, cs - 1)] if hp else 0.0

    def body(tl, acc):
        t = base + tl
        pred = par_ref[0]
        for n, i in enumerate(ar, 1):
            far = y_far(tl, i)
            yv = jnp.where(tl - i >= 0, y_ref[jnp.maximum(tl - i, 0)], far)
            pred += par_ref[n] * jnp.where(t - i >= 0, yv, 0.0)
        for n, j in enumerate(ma, 1):
            ev = jnp.where(
                tl - j >= 0,
                e_ref[jnp.maximum(tl - j, 0)],
                ce_ref[jnp.clip(q + tl - j, 0, max(q - 1, 0))],
            )
            pred += par_ref[p + n] * jnp.where(t - j >= 0, ev, 0.0)
        live = (t.astype(jnp.float32) >= zb) & (t < t_limit)
        e = jnp.where(live, y_ref[tl] - pred, 0.0)
        if e_ref is not None:  # sum mode with q == 0 never reads errors back
            e_ref[tl] = e
        return (acc + e * e) if css_ref is not None else acc

    # (a guarded-prologue / unguarded-steady-state split was measured to buy
    # nothing: a step is 20 bundles that wait on one another with most
    # slots empty, 21 for two registers of series and 26 for four — the
    # block width, not the boundary selects, is what a step's cost divides by)
    acc = _fori(cs, body, zero if css_ref is not None else 0)
    if css_ref is not None:
        css_ref[0] = css_ref[0] + acc
    if tail_ref is not None:
        # the last q TRUE errors sit at static global positions
        # t_limit - q + j; each lands in a statically known chunk/slot
        for j in range(q):
            g = t_limit - q + j
            ci, loc = g // cs, g % cs

            @pl.when(c == ci)
            def _(j=j, loc=loc):
                tail_ref[j] = e_ref[loc]
    # slot s holds e at global (base + cs) - q + s for the next chunk
    for j in range(q):
        ce_ref[j] = e_ref[cs - q + j]
    if cu_ref is not None:
        for j in range(_span(ar)):
            cu_ref[j] = u_ref[cs - _span(ar) + j]


def _design_residual(y_ref, x_ref, par_ref, k, nx, u_ref):
    """``u = y - x_chunk @ beta`` over a block, ``beta`` the ``nx`` planes
    after the first ``k`` of ``par_ref``: a product a sublane row, time on
    the sublanes on both sides (the CSS section's header).  At two
    registers of series the pass is the MXU's own time (1.0 ms of 1.07 over
    ``[131072, 960]``: six passes of eight-row pushes against 32 of the
    array's 128 columns), the rows' single-sublane stores hidden under it;
    other relayouts compile to no fewer bundles (PERF.md §6, PR 51)."""
    ts = _design_slab(y_ref.shape[0])

    def slab(i, carry):
        t = pl.ds(pl.multiple_of(i * ts, _SUBL), ts)
        x = x_ref[t, :]
        for n in range(y_ref.shape[1]):
            u_ref[t, n, :] = y_ref[t, n, :] - jnp.dot(
                x, par_ref[pl.ds(k, nx), n, :],
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        return carry

    _fori(y_ref.shape[0] // ts, slab, 0)


def _design_slab(cs: int) -> int:
    """The time steps one product of the design takes: the chunk in slabs of
    at most 64 steps, whole sublane tiles, a LOOP over them — Mosaic unrolls
    a product's pushes, pops and row stores, and a kernel that held every
    row's product over the whole chunk was 23.5k bundles a block before its
    recurrence: the family's three programs 7 MB more in the compile cache
    and 5.7 s more of a warm ``setup_s`` reading them (PERF.md §6, PR 51)."""
    return _SUBL * max(d for d in range(1, 9) if (cs // _SUBL) % d == 0)


def _css_bwd_kernel(ar, ma, t_limit, cs, nchunk, hp, want_gy, g_plane,
                    *refs, nx=0):
    # p / q: the reach of each side (the carries' depth); the parameter
    # planes number one per LIVE lag.  ``g_plane``: ``g_ref`` is not the
    # cotangent of e as a panel but the plane gbar of the sum of squares'
    # cotangent, and the kernel forms ``2 e gbar`` from the errors it reads.
    # ``nx``: the panel is a shared design's residual: the loop leaves the
    # final adjoints in ``adj_ref`` and ``xs_ref' @`` them lands in ``nx``
    # more rows of ``gpar_ref`` (``xs_ref``: ``-x_chunk`` beside its shifted
    # blocks, the CSS section's header)
    p, q, npar = _span(ar), _span(ma), len(ar)
    refs = list(refs)
    y_ref = refs.pop(0)
    yp_ref = refs.pop(0) if hp else None
    e_ref = refs.pop(0)
    ep_ref = refs.pop(0) if hp else None
    par_ref = refs.pop(0)
    zb_ref = refs.pop(0)
    g_ref = refs.pop(0)
    xs_ref = refs.pop(0) if nx else None
    gpar_ref = refs.pop(0)
    gy_ref = refs.pop(0) if want_gy else None
    adj_ref = refs.pop(0)
    ca_ref = refs.pop(0)
    cap_ref = refs.pop(0) if want_gy else None
    c = pl.program_id(1)
    base = (nchunk - 1 - c) * cs
    zb = zb_ref[0]
    k = 1 + npar + len(ma)
    zero = _plane_zero(zb_ref)

    @pl.when(c == 0)
    def _():
        for j in range(max(q, 1)):
            ca_ref[j] = zero
        for r in range(k):
            gpar_ref[r] = zero
        if nx:
            gpar_ref[pl.ds(k, nx)] = jnp.zeros((nx, *zero.shape), jnp.float32)
        if want_gy:
            for i_ in range(max(p, 1)):
                cap_ref[i_] = zero

    adj_ref[:] = 2.0 * e_ref[:] * g_ref[0] if g_plane else g_ref[:]

    def body(i, accs):
        tl = cs - 1 - i
        t = base + tl
        live = (t.astype(jnp.float32) >= zb) & (t < t_limit)
        aval = adj_ref[tl]
        # contributions from a_{t+j} that live in the next-later chunk
        for n, j in enumerate(ma, 1):
            aval = aval - jnp.where(
                tl + j >= cs,
                par_ref[npar + n] * ca_ref[jnp.clip(tl + j - cs, 0, max(q - 1, 0))],
                0.0,
            )
        a = jnp.where(live, aval, 0.0)
        if nx:
            adj_ref[tl] = a  # the slot is dead (below): keep the FINAL a_t
        if want_gy:
            # adj_ref[s] for s > tl has already been read (descending walk)
            # and every theta adjustment targeting it landed before its own
            # iteration, so the slot is dead — overwrite it with the FINAL
            # adjoint a_s and read it back for the data cotangent
            #   dL/dy_t = a_t - sum_{i in A} a_i al_{t+i}
            # (al_{t+i} in the next-later chunk comes from the cap carry)
            adj_ref[tl] = a
            gy = a
            for n, i_ in enumerate(ar, 1):
                far = (cap_ref[jnp.clip(tl + i_ - cs, 0, max(p - 1, 0))]
                       if hp else 0.0)
                av = jnp.where(
                    tl + i_ < cs, adj_ref[jnp.clip(tl + i_, 0, cs - 1)], far
                )
                gy = gy - par_ref[n] * av
            gy_ref[tl] = gy
            if hp and p > 0:
                # stash a for the chunk below: writes hit tl < p, reads need
                # tl >= cs - p; disjoint because cs >= 2p (css_structural_ok)
                curc = cap_ref[jnp.clip(tl, 0, max(p - 1, 0))]
                cap_ref[jnp.clip(tl, 0, max(p - 1, 0))] = jnp.where(
                    tl < p, a, curc
                )
        for n, j in enumerate(ma, 1):
            idx = jnp.maximum(tl - j, 0)
            contrib = jnp.where(tl - j >= 0, par_ref[npar + n] * a, 0.0)
            adj_ref[idx] = adj_ref[idx] - contrib
        new = [accs[0] - a]
        for n, i_ in enumerate(ar, 1):
            far = yp_ref[jnp.clip(cs + tl - i_, 0, cs - 1)] if hp else 0.0
            yv = jnp.where(tl - i_ >= 0, y_ref[jnp.maximum(tl - i_, 0)], far)
            yv = jnp.where(t - i_ >= 0, yv, 0.0)
            new.append(accs[n] - yv * a)
        for n, j in enumerate(ma, 1):
            far = ep_ref[jnp.clip(cs + tl - j, 0, cs - 1)] if hp else 0.0
            ev = jnp.where(tl - j >= 0, e_ref[jnp.maximum(tl - j, 0)], far)
            ev = jnp.where(t - j >= 0, ev, 0.0)
            new.append(accs[npar + n] - ev * a)
        # stash a for the chunk below: writes hit tl < q, reads need
        # tl >= cs - q; disjoint because cs >= 2q
        cur = ca_ref[jnp.clip(tl, 0, max(q - 1, 0))]
        ca_ref[jnp.clip(tl, 0, max(q - 1, 0))] = jnp.where(tl < q, a, cur)
        return tuple(new)

    accs = _fori(cs, body, (zero,) * k)
    for r in range(k):
        gpar_ref[r] = gpar_ref[r] + accs[r]
    if nx:
        ts = _design_slab(cs)

        def slab(i, carry):
            t = pl.ds(pl.multiple_of(i * ts, _SUBL), ts)
            xs = xs_ref[t, :]
            for n in range(zero.shape[0]):
                d = lax.dot_general(
                    xs, adj_ref[t, n, :], (((0,), (0,)), ((), ())),
                    precision=lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
                g = d[:nx]
                for m in range(1, npar + 1):
                    g = g + par_ref[pl.ds(m, 1), n, :] * d[m * nx:(m + 1) * nx]
                gpar_ref[pl.ds(k, nx), n, :] = (
                    gpar_ref[pl.ds(k, nx), n, :] + g)
            return carry

        _fori(cs // ts, slab, 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def css_errors(p, q, interpret: bool, params, yd, zb):
    """Batched ARMA(p, q) CSS errors ``[B, T]`` on a fused TPU kernel.

    ``p`` / ``q``: an order (the dense lags ``1..p``) or a static lag set
    (:func:`_lags`).  ``params``: ``[B, 1 + p + q]`` rows ``[c, phi_1..p,
    theta_1..q]``, one column per live lag (models without an intercept
    pass ``c = 0``); ``yd``: ``[B, T]`` differenced
    series with any invalid prefix already zeroed; ``zb``: ``[B]`` float —
    errors before this position are forced to zero (``start + p`` for the
    conditional likelihood).  Differentiable in ``params`` AND ``yd`` (the
    data cotangent ``dL/dy_t = a_t - sum_i phi_i a_{t+i}`` is an extra
    backward-kernel output computed only when ``yd`` is perturbed, so the
    params-only fit path pays nothing for it — ADVICE r4).
    """
    _require_css_structure(p, q)
    e, _ = _css_errors_primal(p, q, interpret, params, yd, zb)
    return e


def _require_css_structure(p, q):
    if not css_structural_ok(p, q):
        raise ValueError(
            f"fused CSS kernel supports lags <= {_CHUNK_T // 2} (got p={p}, "
            f"q={q}); use backend='scan'"
        )


def _css_fwd_call(p, q, interpret, mode, params, yd, zb):
    b, t = yd.shape
    k = 1 + len(_lags(p)) + len(_lags(q))
    assert params.shape == (b, k), (params.shape, (b, k))
    tp, cs, nchunk = _time_layout(t)
    y3 = _fold(jnp.pad(yd, ((0, 0), (0, tp - t))))
    zb3 = _fold(zb.astype(yd.dtype)[:, None])
    return _css_fwd_call_f(p, q, interpret, mode, params, y3, zb3, t)


# the width the chip showed best, per mode (PERF.md §6, PR 31: ms a call
# over [131072, 1000] at R = 1 / 2 / 4 — sum 2.25 / 1.15 / 0.73, both 2.27 /
# 1.58 / 1.56, e 2.00 / 1.56 / 1.56, the last two at the HBM's pace; "tail"
# writes no panel either and rides with "sum")
_CSS_R = {"sum": 4, "both": 4, "e": 4, "tail": 4}
# with a shared design's products in the call (PERF.md §6, PR 51: ms a call
# over [131072, 960] and 32 columns at R = 1 / 2 / 4, the products in slabs
# of 64 steps — sum 3.45 / 2.28 / 2.03, both 3.49 / 2.32 / 2.29 (VMEM
# refuses 4: three panels double-buffered), u 1.58 / 1.56 / 1.58, adjoint
# 4.06 / 2.72 / 2.21; the forward entries were filled for the products of a
# whole chunk unrolled, 2.24 / 2.41 for sum, and "sum" at 4 has not run in
# the cell: PERF.md §7)
_CSS_DESIGN_R = {"sum": 2, "both": 2, "u": 2, "adjoint": 4}


def _css_r_best(mode: str, nx: int) -> int:
    if nx:
        return _CSS_DESIGN_R[mode]
    return _ADJOINT_R["css"] if mode == "adjoint" else _CSS_R[mode]


def _design_block(cs, shape, imap):
    """The shared design's rows of a time chunk as a layout entry: a 2-D
    block ``shape`` of ``x [tp, nx]`` (or of its transpose), whatever the
    series block; at most ``cs / 8`` tiles."""
    return (cs // _SUBL, imap, shape)


def _css_fwd_layout(p, q, mode, t, nx=0):
    """The forward CSS call's blocks -> ``(ins, outs, scratch)``
    (:func:`_vmem_bytes`): a parameter plane per live lag, the error carry
    and the tail as deep as the largest MA lag; with a shared design of
    ``nx`` columns its planes, its rows of the chunk, the residual ``u`` (a
    scratch, or in modes "both" and "u" a panel out) and, past one chunk,
    ``u``'s carry in the place of the panel's neighbour block."""
    ar, ma = _lags(p), _lags(q)
    q = _span(ma)
    _, cs, nchunk = _time_layout(t)
    ins = ([(cs, _cur)] + ([(cs, _prev)] if nchunk > 1 and not nx else [])
           + [(1 + len(ar) + len(ma) + nx, _fixed), (1, _fixed)]
           + ([_design_block(cs, (cs, nx), lambda blk, c: (c, 0))]
              if nx else []))
    outs = []
    if mode in ("e", "both"):
        outs.append((cs, _cur))
    if nx and mode in ("both", "u"):
        outs.append((cs, _cur))
    if mode in ("sum", "both"):
        outs.append((1, _fixed))
    if mode == "tail":
        outs.append((max(q, 1), _fixed))
    # errors live in VMEM only; the cross-chunk error carry
    scratch = (([cs] if mode in ("sum", "tail") and q > 0 else [])
               + ([cs] if nx and mode not in ("both", "u") else [])
               + [max(q, 1)]
               + ([_span(ar)] if nx and nchunk > 1 and ar else []))
    return ins, outs, scratch


def css_series_block(rows: int, t: int, order: Order, mode: str = "sum",
                     want_gy: bool = False, design: int = 0) -> int:
    """Series per grid step of the CSS kernel over ``rows`` series of
    (differenced) length ``t``: ``1024 * R`` (:func:`series_rows`) — of a
    forward ``mode``, or of the fit objective's ``"adjoint"`` (``want_gy``:
    the adjoint that also writes the data cotangent's panel, a caller that
    perturbs the data).  ``design``: the columns of a shared design the call
    takes (:func:`css_neg_loglik_folded`), 0 for none.  ``order``'s ``p`` /
    ``q`` may be lag sets (:func:`_lags`)."""
    p, _, q = order
    nx = design + _pad_to(design, _SUBL)
    layout = (_css_bwd_layout(p, q, t, want_gy, nx=nx) if mode == "adjoint"
              else _css_fwd_layout(p, q, mode, t, nx))
    return _SBLK * series_rows(_nsub(rows), layout, _css_r_best(mode, nx))


def _block_call(kernel, layout, r, interpret, args):
    """One ``pallas_call`` of a forward or an adjoint kernel over ``(cs,
    8 * r, 128)`` blocks of the folded operands ``args``, the panel ``[tp,
    nsub, 128]`` first; an output that moves with the time chunk (any index
    map but ``_fixed``) is a panel too, any other a few planes."""
    ins, outs, scratch = layout
    tp, nsub, _ = args[0].shape

    def spec(n, imap, *shape):  # :func:`_design_block` states its own
        return pl.BlockSpec(*shape, imap) if shape else _bs(n, imap, r)

    return pl.pallas_call(
        kernel,
        grid=(nsub // (_SUBL * r), tp // ins[0][0]),
        in_specs=[spec(*entry) for entry in ins],
        out_specs=[_bs(n, im, r) for n, im in outs],
        out_shape=[jax.ShapeDtypeStruct(
            (n if im is _fixed else tp, nsub, _LANES), args[0].dtype)
            for n, im in outs],
        scratch_shapes=[pltpu.VMEM((n, _SUBL * r, _LANES), jnp.float32)
                        for n in scratch],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(*args)


def _css_fwd_call_f(p, q, interpret, mode, params, y3, zb3, t, _r=None,
                    x=None):
    # pre-FOLDED entry: y3/zb3 already in kernel layout.  The fit objective
    # is evaluated hundreds of times inside one lax.while_loop, and XLA does
    # not reliably hoist the [B, T] zero-mask + fold transpose out of the
    # loop body — callers that fold once (css_prefold) skip that cost on
    # every evaluation.  ``_r`` forces the block width (tests only).  ``x
    # [tp, nx]``: a shared design, its coefficients the last ``nx`` columns
    # of ``params`` (the CSS section's header); "both" then returns ``(e3,
    # u3, css3)``.
    par3 = _fold(params)  # [B, k]: trivially small
    _, cs, nchunk = _time_layout(t)
    hp = nchunk > 1
    nx = 0 if x is None else x.shape[1]
    layout = _css_fwd_layout(p, q, mode, t, nx)
    r = _r or series_rows(y3.shape[1], layout, _css_r_best(mode, nx))
    outs = _block_call(
        functools.partial(_css_fwd_kernel, _lags(p), _lags(q), t, cs, hp,
                          mode, nx=nx),
        layout, r, interpret,
        (*((y3, y3) if hp and not nx else (y3,)), par3, zb3,
         *((x,) if nx else ())))
    return outs, (y3, par3, zb3)


def _css_errors_primal(p, q, interpret, params, yd, zb):
    b, t = yd.shape
    (e3,), (y3, par3, zb3) = _css_fwd_call(p, q, interpret, "e", params, yd, zb)
    return _unfold(e3, b)[:, :t], (y3, par3, zb3, e3)


def _css_errors_fwd(p, q, interpret, params, yd, zb):
    # symbolic_zeros: args are CustomVJPPrimal; .perturbed says whether the
    # caller differentiates w.r.t. each input (see _ewma_s_fwd).  The data
    # cotangent is an extra backward-kernel output computed only when yd is
    # perturbed; the marker is structural (None vs ()) so the bwd branch is
    # resolved at trace time.
    b, t = yd.value.shape
    e, res = _css_errors_primal(p, q, interpret, params.value, yd.value,
                                zb.value)
    marker = () if yd.perturbed else None
    return e, res + (b, t, marker)


@_scoped("pallas.css_last_errors")
def css_last_errors(p, q, interpret: bool, params, yd, zb):
    """The last ``q`` one-step CSS errors ``[B, q]`` (oldest first; for a
    lag set ``q`` as many as its largest lag).

    The forecast carry rebuild (``models.arima.forecast``) needs only the
    trailing ``q`` errors; this runs the same recursion as
    :func:`css_errors` but keeps the error panel in VMEM scratch, so the
    pass reads ``y`` once and writes O(B * q) — not a ``[B, T]`` panel.
    Not differentiable (forecasting is a post-fit read-only path; use the
    scan backend for gradients through forecasts).
    """
    _require_css_structure(p, q)
    reach = _span(_lags(q))
    if reach == 0:
        return jnp.zeros((yd.shape[0], 0), yd.dtype)
    if yd.shape[1] < reach:
        raise ValueError(f"series length {yd.shape[1]} < q={q}")
    b, t = yd.shape
    (tail3,), _ = _css_fwd_call(p, q, interpret, "tail", params, yd, zb)
    return _unfold(tail3, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _css_ss_f(p, q, interpret: bool, t: int, b: int, params, y3, zb3):
    """Per-series CSS sum of squared errors ``[B]`` from the FOLDED layout
    (differentiable in ``params`` and ``y3`` — the data cotangent is computed
    only when the data is perturbed; ``t``/``b`` are the true unpadded
    lengths).

    Primal path uses the sum-only kernel (errors never leave VMEM — a
    linesearch objective evaluation pays one panel READ, not a read plus a
    full error write and re-read); the vjp path saves the errors and reuses
    the hand-derived adjoint, with the VALUE accumulated in the identical
    in-kernel order (mixed accumulation orders stall noise-floor rows).
    The unfolded API (:func:`css_neg_loglik`) is a thin fold-then-delegate
    wrapper, so there is exactly ONE adjoint implementation."""
    (css3,), _ = _css_fwd_call_f(p, q, interpret, "sum", params, y3, zb3, t)
    return _unfold(css3, b)[:, 0]


def _css_ss_f_fwd(p, q, interpret, t, b, params, y3, zb3):
    (e3, css3), (y3_, par3, zb3_) = _css_fwd_call_f(
        p, q, interpret, "both", params.value, y3.value, zb3.value, t
    )
    marker = () if y3.perturbed else None  # see _css_errors_fwd
    return _unfold(css3, b)[:, 0], (y3_, par3, zb3_, e3, marker)


def _css_ss_f_bwd(p, q, interpret, t, b, resid, gbar, _r=None):
    y3, par3, zb3, e3, marker = resid
    k = par3.shape[0]
    if isinstance(gbar, SymbolicZero):  # output provably unused
        return (jnp.zeros((b, k), e3.dtype), jnp.zeros(y3.shape, y3.dtype),
                jnp.zeros(zb3.shape, zb3.dtype))
    # gbar [B] folds to a [1, Bp/128, 128] plane, and the plane is all the
    # adjoint kernel is handed: it forms the error cotangent 2 e gbar from
    # the errors it reads anyway, so a gradient makes no panel-sized XLA
    # pass between its forward and its adjoint (this runs once per
    # optimizer iteration on the fit hot path)
    gb3 = _fold(gbar[:, None].astype(e3.dtype))
    if marker is not None:
        # data perturbed: the backward kernel additionally emits the folded
        # data cotangent (an output the params-only fit path never pays for)
        gparams, gy3 = _css_errors_bwd_f(p, q, interpret, (y3, par3, zb3, e3),
                                         gb3, b, t, want_gy=True,
                                         g_plane=True, _r=_r)
    else:
        gparams = _css_errors_bwd_f(p, q, interpret, (y3, par3, zb3, e3),
                                    gb3, b, t, g_plane=True, _r=_r)
        gy3 = jnp.zeros(y3.shape, y3.dtype)
    return gparams, gy3, jnp.zeros(zb3.shape, zb3.dtype)


_css_ss_f.defvjp(_css_ss_f_fwd, _css_ss_f_bwd, symbolic_zeros=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _css_ss_x(p, q, interpret: bool, t: int, b: int, params, y3, zb3, x):
    """:func:`_css_ss_f` of the residual of a SHARED DESIGN ``x [tp, nx]``
    (the CSS section's header): the per-series sum of squared errors of ``y
    - x @ beta'``, ``beta`` the last ``nx`` columns of ``params``, formed in
    the calls.  Differentiable in ``params`` alone; its rule is
    :func:`_css_ss_f`'s with the residual ``u3`` the both-mode forward wrote
    standing where ``y3`` stands, and the same bitwise agreement between the
    value-only and the saving forward."""
    (css3,), _ = _css_fwd_call_f(p, q, interpret, "sum", params, y3, zb3, t,
                                 x=x)
    return _unfold(css3, b)[:, 0]


def _css_ss_x_fwd(p, q, interpret, t, b, params, y3, zb3, x):
    if y3.perturbed or x.perturbed:
        raise NotImplementedError(
            "the CSS kernels differentiate a shared design's objective in "
            "its parameters alone")
    (e3, u3, css3), (_, par3, zb3_) = _css_fwd_call_f(
        p, q, interpret, "both", params.value, y3.value, zb3.value, t,
        x=x.value)
    return _unfold(css3, b)[:, 0], (u3, par3, zb3_, e3, x.value)


def _css_ss_x_bwd(p, q, interpret, t, b, resid, gbar, _r=None):
    u3, par3, zb3, e3, x = resid
    if isinstance(gbar, SymbolicZero):  # output provably unused
        gparams = jnp.zeros((b, par3.shape[0]), e3.dtype)
    else:
        gparams = _css_errors_bwd_f(
            p, q, interpret, (u3, par3, zb3, e3),
            _fold(gbar[:, None].astype(e3.dtype)), b, t, g_plane=True, _r=_r,
            x=x)
    # the panel, the mask and the design are constants: no cotangent formed
    return gparams, None, None, None


_css_ss_x.defvjp(_css_ss_x_fwd, _css_ss_x_bwd, symbolic_zeros=True)


def css_prefold(y, order: Order, n_valid=None, *, lags=()):
    """Fold a panel into the CSS kernel layout ONCE -> ``(y3, zb3)`` for
    :func:`css_neg_loglik_folded`, differencing it on the way at ``lags``.

    The fit objective runs hundreds of evaluations inside one
    ``lax.while_loop``; folding outside the loop keeps the [B, T]
    zero-mask + layout transpose off every evaluation (XLA does not
    reliably hoist them out of the loop body).  ``order``'s ``p`` may be a
    lag set: the conditioning depth is its largest lag.

    The panel is folded FIRST.  With time the major axis, the differences
    still to take — ``lags`` in order, ``(1,) * d + (s,) * D`` of an
    undifferenced panel — are shifts of whole ``(8, 128)`` tiles, and they,
    the ``t >= start`` mask and the zeros of the kernel's padded tail are
    ONE pass over the folded panel: the f32 subtractions that ``v[k:] -
    v[:-k]`` forms lag after lag on a row, on the same pairs, so an already
    differenced panel is the ``lags=()`` case and not a second path.
    ``n_valid`` counts the DIFFERENCED series' live tail.
    """
    p = _span(_lags(order[0]))
    b, n = y.shape[0], y.shape[1] - sum(lags)
    nv = jnp.full((b,), n, y.dtype) if n_valid is None else n_valid.astype(y.dtype)
    start = n - nv
    tp, _, _ = _time_layout(n)
    # what the v5e compiler makes of it (PERF.md §6, PR 46): without the
    # barrier it moves every lag's slice above the transpose and transposes
    # the panel once a slice; and it fuses no producer into a ``pad``, so a
    # padded result is a pass of its own — the tail's rows read the first
    # row instead (any row that exists: the mask zeroes them)
    y3 = jax.lax.optimization_barrier(_fold(y))
    if tp > n:
        y3 = jnp.concatenate(
            [y3, jnp.broadcast_to(y3[:1], (tp - n, *y3.shape[1:]))])

    def diff(lags, off):
        # rows off .. off + tp of the panel differenced at ``lags``, as one
        # expression over slices of the folded panel
        if not lags:
            return y3[off:off + tp]
        return diff(lags[:-1], off + lags[-1]) - diff(lags[:-1], off)

    t_idx = jnp.arange(tp, dtype=y.dtype)[:, None, None]
    live = (t_idx >= _fold(start[:, None])) & (t_idx < n)
    y3 = jnp.where(live, diff(tuple(lags), 0), 0.0)
    zb3 = _fold((start + p).astype(y.dtype)[:, None])
    return y3, zb3


def design_plane(x, coef):
    """``x @ coef'`` as a folded panel ``[tp, Bp/128, 128]``: a design ``x
    [tp, k]`` SHARED by every series times per-series coefficients ``coef
    [B, k]``.  The product's ``[tp, Bp]`` result is the kernel layout as it
    stands (time major, series on the lanes), so a regression's fitted
    values meet a folded panel with no relayout and no ``[B, T, k]`` array;
    differentiated, its transpose is ``x' @ g`` over a folded cotangent.
    ``HIGHEST``: a default-precision f32 product on the TPU is one bfloat16
    pass.  The einsum and not a 2-D ``dot`` with a reshape: on the chip the
    residual ``y3 - design_plane`` over ``[131072, 960]`` takes 3.07 ms
    this way (the panel's read and write alone 1.55, and the default
    precision no less: the layout, not the MXU, is what it pays) and 4.77 ms
    through ``[tp, Bp]``, whose tiles are not the folded panel's (PERF.md
    §6, PR 49)."""
    return jnp.einsum("tk,kns->tns", x, _fold(coef),
                      precision=lax.Precision.HIGHEST)


@_scoped("pallas.css_design_residual")
def css_design_residual(y3, x, beta, n: int, *, interpret: bool = False):
    """``u3 = y3 - x @ beta'`` as a folded PANEL, by the CSS forward call's
    own prologue and nothing after it (mode "u": the products on the MXU at
    ``HIGHEST``, one panel read and one written, where the XLA residual
    ``y3 + design_plane(x, -beta)`` took twice the time of those two moves):
    what a start that needs the residual itself reads (Hannan-Rissanen's
    sweeps).  ``x [tp, k]``, ``beta [B, k]``, ``n`` the true length."""
    params, x = _beside_design(
        jnp.zeros((beta.shape[0], 1), beta.dtype), x, beta)
    (u3,), _ = _css_fwd_call_f(0, 0, interpret, "u", params, y3,
                               jnp.zeros((1, *y3.shape[1:]), y3.dtype), n,
                               x=x)
    return u3


def _beside_design(params_k, x, beta):
    """-> the kernel's planes ``[params_k, beta]`` and the design, both with
    the design's columns padded by zeros to whole sublane tiles."""
    pad = _pad_to(x.shape[1], _SUBL)
    return (jnp.concatenate(
        [params_k, beta, jnp.zeros((beta.shape[0], pad), beta.dtype)],
        axis=1), jnp.pad(x, ((0, 0), (0, pad))))


def design_project(w, y3, b: int):
    """``(w @ y)' [B, k]`` for ``w [k, tp]`` shared by every series and a
    folded panel ``y3``: with ``w = (x'x)^-1 x'`` the least-squares
    coefficients of all ``b`` series in one product."""
    return _unfold(jnp.einsum("kt,tns->kns", w, y3,
                              precision=lax.Precision.HIGHEST), b)


@_scoped("pallas.css_neg_loglik")
def css_neg_loglik_folded(params, y3, zb3, n: int, order: Order,
                          include_intercept: bool, n_valid=None, *,
                          design=None, interpret: bool = False):
    """Batched CSS negative log-likelihood from a pre-folded panel
    (:func:`css_prefold`).  Matches :func:`css_neg_loglik` exactly.

    ``design = (x [tp, k], beta [B, k])``: the likelihood of the ARMA errors
    of ``y - x @ beta'``, a design shared by every series (zero rows past
    ``n``) and per-series coefficients — the kernels form the residual and,
    differentiated, ``-x' dS/du`` themselves (the CSS section's header), so
    ``beta``'s gradient comes back through ``params``' path and no
    panel-sized product runs beside the calls."""
    p, _, q = order
    b = params.shape[0]
    if include_intercept:
        params_k = params
    else:  # kernel layout always carries an intercept slot
        params_k = jnp.concatenate(
            [jnp.zeros((b, 1), params.dtype), params], axis=1
        )
    if design is None:
        return _css_nll_f(p, q, interpret, n, params_k, y3, zb3, n_valid)
    params_k, x = _beside_design(params_k, *design)
    return _css_nll_f(p, q, interpret, n, params_k, y3, zb3, n_valid, x)


def _css_nll_f(p, q, interpret, n, params_k, y3, zb3, n_valid, x=None):
    """The concentrated Gaussian likelihood of the kernel's sum of squares,
    ``n_eff`` the valid length less the AR side's reach."""
    b = params_k.shape[0]
    nv = (jnp.full((b,), n, params_k.dtype) if n_valid is None
          else n_valid.astype(params_k.dtype))
    css = (_css_ss_f(p, q, interpret, n, b, params_k, y3, zb3) if x is None
           else _css_ss_x(p, q, interpret, n, b, params_k, y3, zb3, x))
    n_eff = nv - _span(_lags(p))
    sigma2 = css / n_eff
    return 0.5 * n_eff * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)


@_scoped("pallas.css_seasonal_neg_loglik")
def css_seasonal_neg_loglik_folded(params_k, y3, zb3, n: int, ar, ma,
                                   n_valid=None, *, interpret: bool = False):
    """The same likelihood over the lag sets ``ar`` / ``ma`` of a seasonal
    product polynomial, from a panel folded with the sets' reach
    (``css_prefold(yd, (max(ar), 0, max(ma)), n_valid)``); ``params_k``:
    ``[B, 1 + len(ar) + len(ma)]`` kernel planes ``[c, a.., b..]`` over the
    LIVE lags (``models.arima`` owns the map from the model's parameters
    and its chain rule).  A scope of its own, so that a trace tells a
    seasonal fit's kernel events from a plain ARMA's."""
    return _css_nll_f(tuple(ar), tuple(ma), interpret, n, params_k, y3, zb3,
                      n_valid)


def _css_errors_bwd(p, q, interpret, res, g):
    y3, par3, zb3, e3, b, t, marker = res
    k = par3.shape[0]
    if isinstance(g, SymbolicZero):  # output provably unused: all-zero grads
        return (jnp.zeros((b, k), e3.dtype), jnp.zeros((b, t), e3.dtype),
                jnp.zeros((b,), e3.dtype))
    tp = y3.shape[0]
    g3 = _fold(jnp.pad(g, ((0, 0), (0, tp - t))))
    core_res = (y3, par3, zb3, e3)
    if marker is not None:
        gparams, gy3 = _css_errors_bwd_f(p, q, interpret, core_res, g3, b, t,
                                         want_gy=True)
        gy = _unfold(gy3, b)[:, :t]
    else:
        gparams = _css_errors_bwd_f(p, q, interpret, core_res, g3, b, t)
        gy = jnp.zeros((b, t), g.dtype)
    # the mask boundary zb is discrete: its cotangent stays zero
    return gparams, gy, jnp.zeros((b,), g.dtype)


# the panel-sized operands of the fit objective's adjoint call: y3, e3 (a
# stage span's ``adjoint_panels``; ``css_errors``' own adjoint takes g3 too)
CSS_ADJOINT_PANELS = 2


def _css_bwd_layout(p, q, t, want_gy=False, g_plane=True, nx=0):
    """The CSS adjoint call's blocks, as :func:`_css_fwd_layout` states the
    forward's: the panel and the error panel (each with its neighbour past
    one chunk), parameters, mask, the cotangent — a plane, or ``css_errors``'
    panel — and with ``want_gy`` the data cotangent's panel out; with a
    shared design of ``nx`` columns (the panel is its residual ``u3``) the
    design's rows of the chunk come in, a column block for the product
    itself and one for each AR lag's shift, and ``nx`` more planes of
    gradient go out."""
    ar, ma = _lags(p), _lags(q)
    k = 1 + len(ar) + len(ma) + nx
    panel = _rev_panel(t)
    _, cs, nchunk = _time_layout(t)
    ins = panel + panel + [(k, _fixed), (1, _fixed),
                           (1, _fixed) if g_plane else panel[0]]
    if nx:
        ins.append(_design_block(cs, (cs, (1 + len(ar)) * nx),
                                 lambda blk, c: (nchunk - 1 - c, 0)))
    outs = [(k, _fixed)] + ([panel[0]] if want_gy else [])
    # the adjoint path; its carry across chunks (and the data cotangent's)
    scratch = ([cs, max(_span(ma), 1)]
               + ([max(_span(ar), 1)] if want_gy else []))
    return ins, outs, scratch


def _css_errors_bwd_f(p, q, interpret, res, g3, b, t, want_gy=False,
                      g_plane=False, _r=None, x=None):
    """Adjoint core on FOLDED cotangents -> ``gparams [B, k]`` or, with
    ``want_gy``, ``(gparams, gy3)`` where ``gy3`` is the data cotangent in
    the folded layout (an extra kernel output only callers that perturb the
    data pay for — see ``_css_ss_f_fwd``).  ``g3`` is the cotangent of the
    errors as a panel (``css_errors``' rule: any cotangent) or, with
    ``g_plane``, the plane gbar of the sum of squares' (``_css_ss_f``'s
    rule: the kernel forms ``2 e gbar`` itself); which rule is calling is
    all that chooses.  ``_r`` forces the block width (tests and the sweep).
    ``x [tp, nx]``: ``y3`` is a shared design's residual ``u3`` and
    ``gparams`` carries the ``nx`` coefficients' gradient ``-x' dS/du`` in
    its last columns, no data cotangent formed (the CSS section's header)."""
    y3, par3, zb3, e3 = res
    _, cs, nchunk = _time_layout(t)
    hp = nchunk > 1
    nx = 0 if x is None else x.shape[1]
    layout = _css_bwd_layout(p, q, t, want_gy, g_plane, nx)
    r = _r or series_rows(y3.shape[1], layout, _css_r_best("adjoint", nx))
    outs = _block_call(
        functools.partial(_css_bwd_kernel, _lags(p), _lags(q), t, cs, nchunk,
                          hp, want_gy, g_plane, nx=nx),
        layout, r, interpret,
        (*((y3, y3, e3, e3) if hp else (y3, e3)), par3, zb3, g3,
         *((_design_shifts(x, _lags(p)),) if nx else ())))
    gparams = _unfold(outs[0], b)
    if want_gy:
        return gparams, outs[1]
    return gparams


def _design_shifts(x, ar):
    """``[-x, x shifted down by each lag of ar] [tp, (1 + len(ar)) nx]``:
    column block ``m`` holds ``x_{t-i}`` at row ``t`` (zero before the
    series' start), what the adjoint's transposed products take."""
    tp = x.shape[0]
    return jnp.concatenate(
        [-x] + [jnp.pad(x, ((i, 0), (0, 0)))[:tp] for i in ar], axis=1)


css_errors.defvjp(_css_errors_fwd, _css_errors_bwd, symbolic_zeros=True)


@_scoped("pallas.css_neg_loglik")
def css_neg_loglik(params, yd, order: Order, include_intercept: bool,
                   n_valid=None, *, interpret: bool = False):
    """Batched CSS negative log-likelihood ``[B]`` on the fused kernel.

    Matches ``models.arima.css_neg_loglik`` (vmapped) to float tolerance;
    differentiable in ``params`` via the hand-derived adjoint.
    """
    y3, zb3 = css_prefold(yd, order, n_valid)
    return css_neg_loglik_folded(params, y3, zb3, yd.shape[1], order,
                                 include_intercept, n_valid,
                                 interpret=interpret)


# -- a GRID of K orders over one folded panel --------------------------------
#
# An order search fits K candidate orders to every series.  Their parameter
# planes, masks, errors and sums are CELL arrays ``[n, K, Bp/128, 128]`` (cell
# ``g * B + r`` is order ``g`` of series ``r``); the panel ``y3`` stays the one
# ``[tp, Bp/128, 128]`` array and is never tiled.  The kernels are the two
# above over the UNION lag sets of the group, an order's missing terms zero
# planes (``models.arima`` owns the map from each order's parameters to the
# planes, so a zero plane's gradient never reaches a parameter).  A grid step
# takes G orders of R registers of series: its panel block is ``(cs, 8 R,
# 128)`` whatever the order (the index map ignores it, so the G-order groups
# of one series block re-use the resident block), its cell blocks ``(n, G,
# 8 R, 128)``, and in the body a plane of the panel broadcasts against G
# planes of parameters — G x R independent recurrence chains in one loop
# iteration that load ``y`` once.  G = 1 is "the order as a grid axis", G = K
# "K chains a time step"; :func:`css_grid_block` chooses between them by
# VMEM and by what the chip showed best.  A straggler subset of cells is a
# grid of ONE order over as many gathered series (:func:`take_cells`).


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["y3", "zb4", "y_rows"],
                   meta_fields=["t", "b", "k"])
@dataclasses.dataclass(frozen=True)
class CssGridFolded:
    """A differenced panel folded once for ``k`` orders a series
    (:func:`css_grid_prefold`): ``y3 [tp, Bp/128, 128]``, the orders'
    first live positions ``zb4 [1, k, Bp/128, 128]``; ``t`` the panel's true
    length and ``b`` its rows (static: they ride the treedef); ``y_rows``
    the panel :func:`series_major`, what :func:`take_cells` gathers from
    (``None`` on a gathered subset: nothing is gathered from it again)."""

    y3: jax.Array
    zb4: jax.Array
    t: int
    b: int
    k: int
    y_rows: Optional[jax.Array] = None


def _fold_cells(x2d, k: int):
    """``[k * B, n] -> [n, k, Bp/128, 128]``: :func:`_fold` per order."""
    kb, n = x2d.shape
    x3 = jnp.pad(x2d.reshape(k, kb // k, n),
                 ((0, 0), (0, _pad_to(kb // k, _SBLK)), (0, 0)))
    return x3.transpose(2, 0, 1).reshape(n, k, -1, _LANES)


def _unfold_cells(x4d, b: int):
    """Inverse of :func:`_fold_cells`: ``[n, k, Bp/128, 128] -> [k * B, n]``."""
    n, k = x4d.shape[:2]
    return x4d.reshape(n, k, -1)[:, :, :b].transpose(1, 2, 0).reshape(k * b, n)


def css_grid_prefold(y, depths, n_valid=None, *, lags=()) -> CssGridFolded:
    """Fold a panel ONCE for a grid of ``len(depths)`` orders, order ``g``
    conditioning on its own ``depths[g]`` steps (:func:`css_prefold`'s
    panel, differenced at its ``lags``, and a mask start per cell)."""
    b, n = y.shape[0], y.shape[1] - sum(lags)
    y3, start3 = css_prefold(y, (0, 0, 0), n_valid, lags=lags)
    zb4 = jnp.stack([start3 + float(d) for d in depths], axis=1)
    return CssGridFolded(y3, zb4, n, b, len(depths), series_major(y3))


def take_cells(folded: CssGridFolded, idxc) -> CssGridFolded:
    """The cells ``idxc`` (a multiple of 1024 of them) as a grid of ONE
    order: cell ``i`` gathers the panel's column ``i % b`` and the mask start
    of order ``i // b`` (:func:`take_series` over cells)."""
    rows, orders = idxc % folded.b, idxc // folded.b
    nb = idxc.shape[0] // _LANES
    zb = folded.zb4.reshape(folded.k, -1)[orders, rows]
    return CssGridFolded(take_rows(folded.y_rows, rows),
                         zb.reshape(1, 1, nb, _LANES), folded.t,
                         idxc.shape[0], 1)


# orders a grid step, the most the chip showed worth taking, per mode
# (PERF.md §6, PR 36: ms a call of 9 orders over [131072, 1000] at (G, R) —
# sum (1, 4) 7.91, (3, 4) 4.74, (9, 2) 4.94, (9, 1) 5.25; both (1, 4) 8.76,
# (3, 2) 7.71, (9, 1) 7.58, the error panels' write at the HBM's pace;
# adjoint (1, 1) 34.09, (3, 1) 14.31, VMEM refuses 9; PR 37, R beside G as
# the budget allows: (1, 2) 17.84, (1, 4) 12.32, (3, 1) 14.23, (3, 2) 10.52 —
# 73 bundles a step for six chains at 86.5 of the budget's 88 MiB)
_CSS_GRID_G = {"sum": 3, "both": 9, "adjoint": 3}


def css_grid_block(k: int, nsub: int, layout, mode: str):
    """``(G, R)``: the orders and the registers of series a grid step of a
    CSS grid kernel takes — static facts only, as :func:`series_rows`.  G is
    the largest divisor of ``k`` within ``_CSS_GRID_G[mode]`` whose blocks fit
    VMEM at one register of series (the panel's ``len(panel)`` blocks once,
    every other block and the scratch G times); R then the widest
    :func:`series_rows`' three facts allow beside it."""
    ins, outs, scratch = layout
    npanel = sum(1 for _, im in ins if im is not _fixed)
    if mode == "adjoint":
        npanel //= 2  # the error panels are cell blocks

    def fits(g, r):
        shared = 2 * sum(n for n, _ in ins[:npanel])
        cells = 2 * sum(n for n, _ in ins[npanel:] + outs) + sum(scratch)
        return (shared + g * cells) * r * _TILE_BYTES <= _VMEM_BLOCK_BUDGET

    g = max((d for d in range(1, k + 1)
             if k % d == 0 and d <= _CSS_GRID_G[mode] and fits(d, 1)),
            default=1)
    r_best = _ADJOINT_R["css"] if mode == "adjoint" else _CSS_R[mode]
    r = next((r for r in _R_CHOICES if r <= r_best
              and nsub % (_SUBL * r) == 0 and fits(g, r)), 1)
    return g, r


def css_grid_series_block(k: int, rows: int, t: int, p, q,
                          mode: str = "sum") -> int:
    """Series per grid step of the CSS grid kernel over ``k`` orders of
    ``rows`` series: ``1024 * R`` (:func:`css_grid_block`) — of a forward
    ``mode``, or of the ``"adjoint"``."""
    layout = (_css_bwd_layout(p, q, t) if mode == "adjoint"
              else _css_fwd_layout(p, q, mode, t))
    return _SBLK * css_grid_block(k, _nsub(rows), layout, mode)[1]


def _grid_specs(entries, npanel, kg, g, r):
    """BlockSpecs on the merged grid axis ``i = series block * kg + order
    group``: the first ``npanel`` entries are the panel's (the order group
    does not move them), the rest cell blocks of ``g`` orders."""
    def panel(imap):
        return lambda i, c: imap(i // kg, c)

    def cell(imap):
        def cell_map(i, c):
            tc, blk, z = imap(i // kg, c)
            return (tc, i % kg, blk, z)
        return cell_map

    return ([_bs(n, panel(im), r) for n, im in entries[:npanel]]
            + [pl.BlockSpec((n, g, _SUBL * r, _LANES), cell(im))
               for n, im in entries[npanel:]])


def _css_grid_fwd_call(ar, ma, interpret, mode, params, f: CssGridFolded,
                       _g=None, _r=None):
    """The forward CSS kernel over every cell of ``f`` -> ``(outs,
    par4)``; ``mode`` ``sum`` or ``both``.  ``_g`` / ``_r`` force the
    block (tests and the sweep)."""
    par4 = _fold_cells(params, f.k)
    _, cs, nchunk = _time_layout(f.t)
    hp = nchunk > 1
    layout = _css_fwd_layout(ar, ma, mode, f.t)
    tp, nsub, _ = f.y3.shape
    g, r = css_grid_block(f.k, nsub, layout, mode)
    g, r = _g or g, _r or r
    ins, outs, scratch = layout
    npanel = 2 if hp else 1
    kg = f.k // g
    outs4 = pl.pallas_call(
        functools.partial(_css_fwd_kernel, ar, ma, f.t, cs, hp, mode),
        grid=(nsub // (_SUBL * r) * kg, nchunk),
        in_specs=_grid_specs(ins, npanel, kg, g, r),
        out_specs=_grid_specs(outs, 0, kg, g, r),
        out_shape=[jax.ShapeDtypeStruct(
            (tp if im is _cur else n, f.k, nsub, _LANES), f.y3.dtype)
            for n, im in outs],
        scratch_shapes=[pltpu.VMEM((n, g, _SUBL * r, _LANES), jnp.float32)
                        for n in scratch],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(*((f.y3, f.y3) if hp else (f.y3,)), par4, f.zb4)
    return outs4, par4


def _css_grid_bwd_call(ar, ma, interpret, f: CssGridFolded, par4, e4, gb4,
                       _g=None, _r=None):
    """The CSS adjoint over every cell -> ``gparams [k * b, planes]``: the
    cotangent ``2 e gbar`` formed in the kernel from the plane ``gb4``.
    ``_g`` / ``_r`` force the block (tests and the sweep)."""
    _, cs, nchunk = _time_layout(f.t)
    hp = nchunk > 1
    layout = _css_bwd_layout(ar, ma, f.t)
    nsub = f.y3.shape[1]
    g, r = css_grid_block(f.k, nsub, layout, "adjoint")
    g, r = _g or g, _r or r
    ins, outs, scratch = layout
    kg = f.k // g
    gpar4 = pl.pallas_call(
        functools.partial(_css_bwd_kernel, ar, ma, f.t, cs, nchunk, hp,
                          False, True),
        grid=(nsub // (_SUBL * r) * kg, nchunk),
        in_specs=_grid_specs(ins, 2 if hp else 1, kg, g, r),
        out_specs=_grid_specs(outs, 0, kg, g, r)[0],
        out_shape=jax.ShapeDtypeStruct(par4.shape, e4.dtype),
        scratch_shapes=[pltpu.VMEM((n, g, _SUBL * r, _LANES), jnp.float32)
                        for n in scratch],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(*((f.y3, f.y3, e4, e4) if hp else (f.y3, e4)), par4, f.zb4, gb4)
    return _unfold_cells(gpar4, f.b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _css_grid_ss(ar, ma, interpret: bool, params, f: CssGridFolded):
    """Per-cell CSS sum of squared errors ``[k * b]`` (differentiable in
    ``params`` only: the panel is the fit's constant).  As
    :func:`_css_ss_f`: the value-only kernel on the primal path, errors
    saved on the vjp path with the value accumulated in the same order."""
    (css4,), _ = _css_grid_fwd_call(ar, ma, interpret, "sum", params, f)
    return _unfold_cells(css4, f.b)[:, 0]


def _css_grid_ss_fwd(ar, ma, interpret, params, f):
    (e4, css4), par4 = _css_grid_fwd_call(ar, ma, interpret, "both", params,
                                          f)
    return _unfold_cells(css4, f.b)[:, 0], (f, par4, e4)


def _css_grid_ss_bwd(ar, ma, interpret, resid, gbar):
    f, par4, e4 = resid
    gb4 = _fold_cells(gbar[:, None].astype(e4.dtype), f.k)
    gparams = _css_grid_bwd_call(ar, ma, interpret, f, par4, e4, gb4)
    return gparams, jax.tree_util.tree_map(jnp.zeros_like, f)


_css_grid_ss.defvjp(_css_grid_ss_fwd, _css_grid_ss_bwd)


@_scoped("pallas.css_grid_neg_loglik")
def css_grid_neg_loglik_folded(params_k, folded: CssGridFolded, ar, ma,
                               n_eff, *, interpret: bool = False):
    """The concentrated CSS likelihood of every cell of a grid of orders
    ``[k * b]``, from a panel folded once (:func:`css_grid_prefold`) or a
    straggler subset of its cells (:func:`take_cells`).  ``params_k``:
    ``[k * b, 1 + len(ar) + len(ma)]`` kernel planes ``[c, a.., b..]`` over
    the group's UNION lag sets, zero where a cell's order has no such term;
    ``n_eff [k * b]``: each cell's effective observations (its valid length
    less its own order's AR reach).  A scope of its own, so that a trace
    tells an order search's kernel events from a single order's."""
    ar, ma = _lags(ar), _lags(ma)
    _require_css_structure(ar, ma)
    css = _css_grid_ss(ar, ma, interpret, params_k, folded)
    sigma2 = css / n_eff
    return 0.5 * n_eff * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)


# ---------------------------------------------------------------------------
# GARCH(1, 1) conditional-variance recursion (forward + hand-derived adjoint)
# ---------------------------------------------------------------------------
#
# h_t = omega + alpha * r_{t-1}^2 + beta * h_{t-1}, h_start = h0
# (reference GARCH.scala log-likelihood loop).  The prefix [0, zb) holds
# h_t = h0 so padded series contribute nothing.
#
# Adjoint, for an upstream cotangent gbar of h (t descending over live steps):
#   lam_t      = gbar_t + beta * lam_{t+1}
#   dL/domega  = sum_t lam_t
#   dL/dalpha  = sum_t lam_t * r2p_t          (r2p_zb = h0 at the seed)
#   dL/dbeta   = sum_t lam_t * h_{t-1}        (h_{zb-1} = h0 at the seed)
#   dL/dr2_t   = alpha * lam_{t+1}            (t+1 live and not the seed)
#   dL/dh0     = lam_zb * (alpha + beta) + sum_{dead t} gbar_t
# Cotangents flow to r^2 and h0 as well as the parameters so callers that
# build the returns from model parameters on their own (``garch_variances``,
# the time-sharded fits, ``fit_argarch`` past one time chunk) get exact
# gradients; ``zb`` is a constant of the objective.  The two data cotangents
# cost a [B, T] write, so the adjoint emits them only when the data is
# perturbed (symbolic_zeros on the likelihood's custom_vjp, as EWMA's ``x``
# below): ``garch.fit`` differentiates in the parameters alone and never
# pays them.
#
# ONE forward call and ONE adjoint call, both on FOLDED operands
# (:class:`GarchFolded`).  A fit folds its panel once, before the optimizer
# (:func:`garch_prefold`), and evaluates :func:`garch_neg_loglik_folded`;
# the natural-layout entries (:func:`garch_neg_loglik`,
# :func:`garch_variances`) fold per call and delegate, so JAX differentiates
# through the fold for callers whose returns depend on the iterate.


def _garch_fwd_kernel(t_limit, cs, hp, mode, *refs):
    # mode "e": conditional variances out; "sum": only the per-series
    # Gaussian log-likelihood sum leaves the kernel (linesearch evals);
    # "both": variances AND the sum, accumulated in the identical order
    refs = list(refs)
    r2_ref = refs.pop(0)
    r2p_ref = refs.pop(0) if hp else None
    par_ref = refs.pop(0)
    h0_ref = refs.pop(0)
    zb_ref = refs.pop(0)
    h_ref = refs.pop(0) if mode != "sum" else None
    ll_ref = refs.pop(0) if mode != "e" else None
    ch_ref = refs.pop(0)
    c = pl.program_id(1)
    base = c * cs
    zb = zb_ref[0]
    h0 = h0_ref[0]

    zero = _plane_zero(zb_ref)

    @pl.when(c == 0)
    def _():
        ch_ref[0] = h0
        if mode != "e":
            ll_ref[0] = zero

    def body(tl, carry):
        hprev_c, acc = carry
        t = base + tl
        tf = t.astype(jnp.float32)
        hprev = jnp.where(tl - 1 >= 0, hprev_c, ch_ref[0])
        far = r2p_ref[cs - 1] if hp else 0.0
        r2p = jnp.where(tl - 1 >= 0, r2_ref[jnp.maximum(tl - 1, 0)], far)
        r2p = jnp.where(t - 1 >= 0, r2p, 0.0)
        # the first live step seeds with h0 standing in for r_{start-1}^2
        # (matching models.garch.variances)
        r2p = jnp.where(tf == zb, h0, r2p)
        h = par_ref[0] + par_ref[1] * r2p + par_ref[2] * hprev
        live = (tf >= zb) & (t < t_limit)
        hval = jnp.where(live, h, h0)
        if mode != "sum":
            h_ref[tl] = hval
        if mode != "e":
            hc = jnp.maximum(hval, 1e-12)
            acc = acc + jnp.where(
                live, jnp.log(2.0 * jnp.pi * hc) + r2_ref[tl] / hc, 0.0
            )
        return hval, acc

    hlast, acc = _fori(cs, body, (ch_ref[0], zero))
    ch_ref[0] = hlast
    if mode != "e":
        ll_ref[0] = ll_ref[0] + acc


def _garch_bwd_kernel(t_limit, cs, nchunk, hpv, want_gdata, g_plane, *refs):
    # ``want_gdata``: also emit the cotangents of r^2 (panel-sized) and h0.
    # ``g_plane``: ``g_ref`` is not the cotangent of h as a panel but the
    # plane gbar of the likelihood sum's cotangent, and the kernel forms
    # gbar d ll_t / d h_t from the r^2 and h blocks it reads
    refs = list(refs)
    r2_ref = refs.pop(0)
    r2p_ref = refs.pop(0) if hpv else None
    par_ref, h0_ref, zb_ref, h_ref = (refs.pop(0) for _ in range(4))
    hp_ref = refs.pop(0) if hpv else None
    g_ref, gpar_ref = refs.pop(0), refs.pop(0)
    gr2_ref, gh0_ref = (refs.pop(0), refs.pop(0)) if want_gdata else (None, None)
    cl_ref = refs.pop(0)
    c = pl.program_id(1)
    base = (nchunk - 1 - c) * cs
    zb = zb_ref[0]
    h0 = h0_ref[0]
    alpha = par_ref[1]
    beta = par_ref[2]
    zero = _plane_zero(zb_ref)

    @pl.when(c == 0)
    def _():
        cl_ref[0] = zero
        for r in range(3):
            gpar_ref[r] = zero
        if want_gdata:
            gh0_ref[0] = zero

    # d ll_t / d h_t = 1/h - r^2/h^2 in two stages a step apart (see body)
    def operands(tl):
        ht = h_ref[tl]
        hc = jnp.maximum(ht, 1e-12)
        return ht, hc, hc * hc, r2_ref[tl]

    def quotients(ht, hc, hh, r2):
        return ht, 1.0 / hc, r2 / hh

    def body(i, carry):
        lam_next, dw, da, db = carry[:4]
        tl = cs - 1 - i
        t = base + tl
        tf = t.astype(jnp.float32)
        live = (tf >= zb) & (t < t_limit)
        if want_gdata:
            # r2_t feeds h_{t+1} unless t+1 is the seed (which uses h0)
            next_live = (tf + 1.0 > zb) & (t + 1 < t_limit)
            gr2_ref[tl] = jnp.where(next_live, alpha * lam_next, 0.0)
        if g_plane:
            # gbar d ll_t / d h_t (zero through the eps clamp), formed over
            # THREE steps: this one finishes its cotangent from the
            # quotients the step before divided, divides for the next and
            # loads for the one after.  The divides are off the recurrence's
            # chain (lam waits for lam_next alone) but 17 bundles deep
            # behind a load, and a loop iteration is as long as its longest
            # path: in one piece they doubled the step (36 bundles for the
            # panel cotangent's 20), in stages they fill the slots it leaves
            # empty.  (The last two steps of a chunk stage a clamped step
            # that nothing finishes.)
            ht, inv, quo = carry[-7:-4]
            g = jnp.where(live & (ht >= 1e-12), g_ref[0] * (inv - quo), 0.0)
            ahead = (quotients(*carry[-4:])
                     + operands(jnp.maximum(tl - 2, 0)))
        else:
            g, ahead = g_ref[tl], ()
        lam = g + beta * lam_next
        lam = jnp.where(live, lam, 0.0)
        seed = tf == zb
        hfar = hp_ref[cs - 1] if hpv else 0.0
        hprev = jnp.where(tl - 1 >= 0, h_ref[jnp.maximum(tl - 1, 0)], hfar)
        hprev = jnp.where(t - 1 >= 0, hprev, h0)
        rfar = r2p_ref[cs - 1] if hpv else 0.0
        r2p = jnp.where(tl - 1 >= 0, r2_ref[jnp.maximum(tl - 1, 0)], rfar)
        r2p = jnp.where(t - 1 >= 0, r2p, 0.0)
        r2p_eff = jnp.where(seed, h0, r2p)
        dw = dw + lam
        da = da + lam * r2p_eff
        db = db + lam * hprev
        if not want_gdata:
            return (lam, dw, da, db) + ahead
        # dead positions emit h0 directly (the likelihood has none: its
        # cotangent is zero there)
        dh0 = carry[4] if g_plane else carry[4] + jnp.where(live, 0.0, g)
        # h0 enters the seed step through BOTH recursion inputs
        hp_is_h0 = tf - 1.0 < zb
        dh0 = dh0 + jnp.where(live & seed, alpha * lam, 0.0)
        dh0 = dh0 + jnp.where(live & hp_is_h0, beta * lam, 0.0)
        return (lam, dw, da, db, dh0) + ahead

    out = lax.fori_loop(
        0, cs, body, (cl_ref[0],) + (zero,) * (4 if want_gdata else 3)
        + (quotients(*operands(cs - 1)) + operands(max(cs - 2, 0))
           if g_plane else ()))
    cl_ref[0] = out[0]
    for r in range(3):
        gpar_ref[r] = gpar_ref[r] + out[1 + r]
    if want_gdata:
        gh0_ref[0] = gh0_ref[0] + out[4]


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["r23", "h03", "zb3"], meta_fields=["t"])
@dataclasses.dataclass(frozen=True)
class GarchFolded:
    """A returns panel in the GARCH kernel layout (:func:`garch_prefold`):
    the squared, masked returns ``r23 [Tp, Bp/128, 128]`` zero-padded by
    :func:`_time_layout`, the start variance ``h03`` and the first live
    position ``zb3`` as ``[1, Bp/128, 128]`` planes; ``t`` is the true
    series length (static: it rides the treedef through a ``jit``
    boundary)."""

    r23: jax.Array
    h03: jax.Array
    zb3: jax.Array
    t: int

    def take(self, idxc):
        """The series ``idxc`` (a multiple of 1024 of them) as folded
        COLUMNS: the straggler compaction re-folds nothing."""
        return take_series(self, idxc)


# see _CSS_R: sum 3.79 / 2.00 / 1.13 ms, both 3.81 / 2.06 / 1.59, e 1.75 /
# 1.56 / 1.55
_GARCH_R = {"sum": 4, "both": 4, "e": 4}


def _garch_fwd_layout(mode, t):
    """The forward GARCH call's blocks (see :func:`_css_fwd_layout`)."""
    _, cs, nchunk = _time_layout(t)
    ins = ([(cs, _cur)] + ([(cs, _prev)] if nchunk > 1 else [])
           + [(3, _fixed), (1, _fixed), (1, _fixed)])
    outs = (([(cs, _cur)] if mode != "sum" else [])
            + ([(1, _fixed)] if mode != "e" else []))
    return ins, outs, [1]  # scratch: the cross-chunk variance carry


def garch_series_block(rows: int, t: int, mode: str = "sum") -> int:
    """Series per grid step of the GARCH kernel (see
    :func:`css_series_block`)."""
    layout, best = ((_garch_bwd_layout(t), _ADJOINT_R["garch"])
                    if mode == "adjoint"
                    else (_garch_fwd_layout(mode, t), _GARCH_R[mode]))
    return _SBLK * series_rows(_nsub(rows), layout, best)


def _garch_fwd_call_f(interpret, mode, params, f: GarchFolded, _r=None):
    # pre-FOLDED entry (see _css_fwd_call_f): only the [B, 3] parameters are
    # folded per call; the panel and its seeds arrive in kernel layout
    _, cs, nchunk = _time_layout(f.t)
    r23 = f.r23
    par3 = _fold(params)
    hp = nchunk > 1
    layout = _garch_fwd_layout(mode, f.t)
    r = _r or series_rows(r23.shape[1], layout, _GARCH_R[mode])
    outs = _block_call(
        functools.partial(_garch_fwd_kernel, f.t, cs, hp, mode),
        layout, r, interpret,
        (*((r23, r23) if hp else (r23,)), par3, f.h03, f.zb3))
    return outs, par3


# the panel-sized operands of the fit objective's adjoint call: r23, h3 (a
# stage span's ``adjoint_panels``; ``garch_variances``' adjoint takes g3 too)
GARCH_ADJOINT_PANELS = 2


def _garch_bwd_layout(t, want_gdata=False, g_plane=True):
    """The GARCH adjoint call's blocks (see :func:`_css_bwd_layout`): the
    squared returns and the variance path (each with its neighbour past one
    chunk), parameters, seed, mask, the cotangent — a plane, or
    ``garch_variances``' panel — and with ``want_gdata`` the cotangents of
    ``r^2`` (a panel) and ``h0`` out."""
    panel = _rev_panel(t)
    ins = (panel + [(3, _fixed), (1, _fixed), (1, _fixed)] + panel
           + [(1, _fixed) if g_plane else panel[0]])
    outs = [(3, _fixed)] + ([panel[0], (1, _fixed)] if want_gdata else [])
    return ins, outs, [1]  # scratch: lambda's carry across chunks


def _garch_bwd_call_f(interpret, f: GarchFolded, par3, h3, g3, want_gdata,
                      g_plane=False, _r=None):
    """The adjoint on FOLDED operands: ``g3`` is the cotangent of the
    variance path ``h3`` as a panel (``_garch_h``'s rule: any cotangent)
    or, with ``g_plane``, the plane gbar of the likelihood sum's
    (``_garch_ll_f``'s rule: the kernel forms ``gbar d ll / d h`` itself;
    which rule is calling is all that chooses) -> ``(gpar3, gr23, gh03)``,
    the two data cotangents ``None`` unless ``want_gdata`` (two more kernel
    outputs, one of them panel-sized).  ``_r`` forces the block width
    (tests and the sweep)."""
    _, cs, nchunk = _time_layout(f.t)
    hp = nchunk > 1
    layout = _garch_bwd_layout(f.t, want_gdata, g_plane)
    r = _r or series_rows(f.r23.shape[1], layout, _ADJOINT_R["garch"])
    outs = _block_call(
        functools.partial(_garch_bwd_kernel, f.t, cs, nchunk, hp, want_gdata,
                          g_plane),
        layout, r, interpret,
        (*((f.r23, f.r23) if hp else (f.r23,)), par3, f.h03, f.zb3,
         *((h3, h3) if hp else (h3,)), g3))
    return tuple(outs) if want_gdata else (outs[0], None, None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _garch_h(interpret: bool, params, r2, h0, zb):
    h, _ = _garch_h_fwd(interpret, params, r2, h0, zb)
    return h


def _garch_h_fwd(interpret, params, r2, h0, zb):
    b, t = r2.shape
    tp, _, _ = _time_layout(t)
    f = GarchFolded(
        _fold(jnp.pad(r2, ((0, 0), (0, tp - t)))),
        _fold(h0[:, None].astype(r2.dtype)),
        _fold(zb.astype(r2.dtype)[:, None]),
        t,
    )
    (h3,), par3 = _garch_fwd_call_f(interpret, "e", params, f)
    return _unfold(h3, b)[:, :t], (f, par3, h3)


def _garch_h_bwd(interpret, res, g):
    f, par3, h3 = res
    b, t = g.shape
    g3 = _fold(jnp.pad(g, ((0, 0), (0, h3.shape[0] - t))))
    gpar3, gr23, gh03 = _garch_bwd_call_f(interpret, f, par3, h3, g3, True)
    return (
        _unfold(gpar3, b),
        _unfold(gr23, b)[:, :t],
        _unfold(gh03, b)[:, 0],
        jnp.zeros((b,), g.dtype),
    )


_garch_h.defvjp(_garch_h_fwd, _garch_h_bwd)


def garch_variances(params, r, h0, zb, *, interpret: bool = False):
    """Batched GARCH(1,1) conditional variances ``[B, T]`` on a fused kernel.

    ``params``: ``[B, 3]`` rows ``[omega, alpha, beta]``; ``r``: ``[B, T]``
    returns with the invalid prefix zeroed; ``h0``: ``[B]`` start variance;
    ``zb``: ``[B]`` first live position.  Differentiable in ``params``, ``r``,
    and ``h0`` via the hand-derived adjoint (``zb`` is constant).
    """
    return _garch_h(interpret, params, r * r, h0, zb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _garch_ll_f(interpret: bool, params, f: GarchFolded):
    """Unscaled Gaussian log-likelihood sum ``[B]`` of the GARCH recursion
    from the FOLDED layout: ``sum_t mask (log 2 pi h_t + r_t^2 / h_t)``
    (the true unpadded sizes are ``params.shape[0]`` and ``f.t``).

    Primal path: sum-only kernel (the variance path never reaches HBM);
    vjp path saves the variances, folded, and chains the likelihood
    partials into the hand-derived recursion adjoint, with the VALUE
    accumulated in the identical in-kernel order (see ``_css_ss_f``).
    Differentiable in ``params`` and in ``f.r23`` / ``f.h03``; the data
    cotangents are computed only when the data is perturbed.
    """
    (ll3,), _ = _garch_fwd_call_f(interpret, "sum", params, f)
    return _unfold(ll3, params.shape[0])[:, 0]


def _garch_ll_f_fwd(interpret, params, f):
    # symbolic_zeros: the leaves are CustomVJPPrimal (see _ewma_s_fwd); the
    # marker is structural (None vs ()) so bwd branches at trace time
    marker = () if f.r23.perturbed or f.h03.perturbed else None
    params, f = custom_vjp_primal_tree_values((params, f))
    (h3, ll3), par3 = _garch_fwd_call_f(interpret, "both", params, f)
    return _unfold(ll3, params.shape[0])[:, 0], (f, par3, h3, marker)


def _garch_ll_f_bwd(interpret, resid, gbar, _r=None):
    f, par3, h3, marker = resid
    b = gbar.shape[0]
    zeros = jax.tree_util.tree_map(jnp.zeros_like, f)
    if isinstance(gbar, SymbolicZero):  # output provably unused
        return jnp.zeros((b, 3), h3.dtype), zeros
    # gbar [B] folds to a plane, and the plane is all the adjoint kernel is
    # handed (see _css_ss_f_bwd): it forms the likelihood's cotangent
    # gbar (1/h - r^2/h^2) from the r^2 and h blocks it reads anyway, so a
    # gradient makes no panel-sized XLA pass; padded series carry a zero
    # gbar, and padded time is dead
    gb3 = _fold(gbar[:, None].astype(h3.dtype))
    gpar3, gr23, gh03 = _garch_bwd_call_f(
        interpret, f, par3, h3, gb3, marker is not None, g_plane=True, _r=_r)
    if marker is None:  # params-only: the fit hot path
        return _unfold(gpar3, b), zeros
    # r^2 feeds the likelihood through the recursion AND directly
    t_idx = jnp.arange(h3.shape[0], dtype=h3.dtype)[:, None, None]
    live = (t_idx >= f.zb3) & (t_idx < f.t)
    gr23 = gr23 + jnp.where(live, gb3 / jnp.maximum(h3, 1e-12), 0.0)
    return _unfold(gpar3, b), dataclasses.replace(zeros, r23=gr23, h03=gh03)


_garch_ll_f.defvjp(_garch_ll_f_fwd, _garch_ll_f_bwd, symbolic_zeros=True)


def garch_prefold(r, n_valid=None) -> GarchFolded:
    """Mask a returns panel, seed its variance and fold it into the GARCH
    kernel layout ONCE -> the operand of :func:`garch_neg_loglik_folded`.

    ``h0`` is the masked sample variance of the valid span and the prefix
    is dead, as ``models.garch.neg_log_likelihood`` has them.  The fit
    objective runs hundreds of evaluations inside ``lax.while_loop`` bodies
    and XLA does not hoist the re-tiling of the folded panel out of them
    (see :func:`hw_prefold`): a fit folds once, before the optimizer, and
    closes over the result.  Differentiable in ``r``."""
    b, n = r.shape
    nv = (
        jnp.full((b,), n, jnp.int32)
        if n_valid is None
        else n_valid.astype(jnp.int32)
    )
    start = (n - nv).astype(r.dtype)
    t_idx = jnp.arange(n, dtype=r.dtype)
    mask = t_idx[None, :] >= start[:, None]
    rz = jnp.where(mask, r, 0.0)
    nvf = jnp.maximum(nv, 1).astype(r.dtype)
    mean = jnp.sum(rz, axis=1) / nvf
    h0 = jnp.sum(jnp.where(mask, (rz - mean[:, None]) ** 2, 0.0), axis=1) / nvf
    tp, _, _ = _time_layout(n)
    return GarchFolded(
        _fold(jnp.pad(rz * rz, ((0, 0), (0, tp - n)))),
        _fold(h0[:, None]),
        _fold(start[:, None]),
        n,
    )


@_scoped("pallas.garch_neg_loglik")
def garch_neg_loglik_folded(params, folded: GarchFolded, *,
                            interpret: bool = False):
    """Batched GARCH(1,1) Gaussian negative log-likelihood ``[B]`` from a
    pre-folded panel (:func:`garch_prefold`) — the fit-loop entry point.
    Matches :func:`garch_neg_loglik` exactly."""
    return 0.5 * _garch_ll_f(interpret, params, folded)


@_scoped("pallas.garch_neg_loglik")
def garch_neg_loglik(params, r, n_valid=None, *, interpret: bool = False):
    """Batched GARCH(1,1) Gaussian negative log-likelihood ``[B]``.

    Matches ``models.garch.neg_log_likelihood`` (vmapped) to float tolerance:
    h0 is the masked sample variance of the valid span, the prefix is dead,
    and the likelihood sums over valid steps.  Differentiable in ``params``
    and (through the returns/variance seed) in ``r``.  Folds per call:
    inside an optimizer loop whose returns are constant use
    :func:`garch_prefold` + :func:`garch_neg_loglik_folded`.
    """
    return garch_neg_loglik_folded(params, garch_prefold(r, n_valid),
                                   interpret=interpret)


# ---------------------------------------------------------------------------
# AR(1) + GARCH(1, 1): the GARCH recursion with its mean equation in the step
# ---------------------------------------------------------------------------
#
# ARGARCH's returns depend on the iterate, its series does not: the panel
# operand is the masked series ``y`` itself, five parameter planes ``(c, phi,
# omega, alpha, beta)``, and each step forms its return in VMEM,
#   r_t = y_t - c - phi * y_{t-1},
# before the GARCH section's recursion and likelihood run on ``r_t^2``.  A
# kernel pair of its own and not a mode of the plain one — another carry, one
# panel where that reads two, and ``garch.fit``'s programs stay the text they
# were — for series of ONE time chunk (``y_{t-1}`` and the carried
# ``r_{t-1}^2`` never cross a block: no neighbour operand, no scratch).
#
# The forward carries the last step's ``r^2`` in registers, reads ONE panel
# and in its value-only mode writes none.  The adjoint reads ``y`` and the
# saved variance path, recomputes ``r_t``, runs the GARCH section's ``lam``
# recursion under the likelihood's own cotangent ``gbar (1/h_t - r_t^2 /
# h_t^2)`` and, with
#   G_t = alpha * lam_{t+1} + gbar / h_t         (t live: the cotangent of
#                                                 r_t^2, the recursion's part
#                                                 and the likelihood's)
# accumulates beside the three
#   dL/dc   = -sum_t 2 r_t G_t
#   dL/dphi = -sum_t 2 r_t G_t y_{t-1}
# and hands back ``dL/dh0 = lam_zb (alpha + beta)`` as a plane: five
# parameter planes and one seed plane out, two panels in, no panel out.  The
# seed variance is the caller's (``h0`` depends on ``phi``:
# :func:`argarch_neg_loglik_folded` forms it from three row moments in plain
# ``jnp`` and JAX chains ``dL/dh0`` through it).  No live step is the
# panel's first (``zb >= 1``: the fit conditions on the first valid
# observation), so a clamped read of ``y_{t-1}`` is exact wherever it counts.


def _argarch_fwd_kernel(t_limit, cs, mode, y_ref, par_ref, h0_ref, zb_ref,
                        *outs):
    # mode "sum": only the per-series Gaussian log-likelihood sum leaves the
    # kernel (linesearch evals); "both": the variance path too, the sum
    # accumulated in the identical order
    h_ref = outs[0] if mode == "both" else None
    ll_ref = outs[-1]
    zb = zb_ref[0]
    h0 = h0_ref[0]
    zero = _plane_zero(zb_ref)

    def body(t, carry):
        hprev, r2p, acc = carry
        tf = t.astype(jnp.float32)
        r = (y_ref[t] - par_ref[0]
             - par_ref[1] * y_ref[jnp.maximum(t - 1, 0)])
        # the first live step seeds with h0 standing in for r_{start-1}^2
        # (matching models.garch.variances)
        r2p = jnp.where(tf == zb, h0, r2p)
        h = par_ref[2] + par_ref[3] * r2p + par_ref[4] * hprev
        live = (tf >= zb) & (t < t_limit)
        hval = jnp.where(live, h, h0)
        if mode == "both":
            h_ref[t] = hval
        hc = jnp.maximum(hval, 1e-12)
        r2 = r * r
        acc = acc + jnp.where(
            live, jnp.log(2.0 * jnp.pi * hc) + r2 / hc, 0.0)
        return hval, r2, acc

    ll_ref[0] = _fori(cs, body, (h0, zero, zero))[2]


def _argarch_bwd_kernel(t_limit, cs, y_ref, par_ref, h0_ref, zb_ref, h_ref,
                        g_ref, gpar_ref, gh0_ref):
    # ``g_ref``: the plane gbar of the likelihood sum's cotangent
    zb = zb_ref[0]
    h0 = h0_ref[0]
    alpha = par_ref[3]
    beta = par_ref[4]
    zero = _plane_zero(zb_ref)

    # a step's loads, and its divides: d ll_t / d h_t = 1/h - r^2/h^2 in two
    # stages a step apart, as ``_garch_bwd_kernel`` forms it (see there why),
    # the step's return riding along behind its square
    def operands(t):
        ht = h_ref[t]
        hc = jnp.maximum(ht, 1e-12)
        r = (y_ref[t] - par_ref[0]
             - par_ref[1] * y_ref[jnp.maximum(t - 1, 0)])
        return ht, hc, hc * hc, r * r, r

    def quotients(ht, hc, hh, r2, r):
        return ht, 1.0 / hc, r2 / hh, r

    def body(i, carry):
        lam_next, dw, da, db, dc, dphi, lam_seed = carry[:7]
        (ht, inv, quo, r), staged = carry[7:11], carry[11:]
        t = cs - 1 - i
        tf = t.astype(jnp.float32)
        live = (tf >= zb) & (t < t_limit)
        g = jnp.where(live & (ht >= 1e-12), g_ref[0] * (inv - quo), 0.0)
        lam = jnp.where(live, g + beta * lam_next, 0.0)
        seed = tf == zb
        # h_{t-1} and r_{t-1}^2 are the staged step's (at t = 0 a clamped
        # step's, under a lam of 0: that step is never live)
        r2p_eff = jnp.where(seed, h0, staged[3])
        # r_t G_t: r_t^2 feeds h_{t+1} (a live t's successor is never the
        # seed; past the end lam_next is 0) and the likelihood
        rg = r * jnp.where(live, alpha * lam_next + g_ref[0] * inv, 0.0)
        return ((lam, dw + lam, da + lam * r2p_eff, db + lam * staged[0],
                 dc + rg, dphi + rg * y_ref[jnp.maximum(t - 1, 0)],
                 jnp.where(seed, lam, lam_seed))
                + quotients(*staged) + operands(jnp.maximum(t - 2, 0)))

    out = lax.fori_loop(
        0, cs, body,
        (zero,) * 7 + quotients(*operands(cs - 1)) + operands(max(cs - 2, 0)))
    gpar_ref[0] = -2.0 * out[4]
    gpar_ref[1] = -2.0 * out[5]
    for k in range(3):
        gpar_ref[2 + k] = out[1 + k]
    # h0 enters the seed step through BOTH recursion inputs
    gh0_ref[0] = (alpha + beta) * out[6]


# the widths the chip showed best for the pair (PERF.md §6, PR 52, run 5:
# over [131072, 1000] at R = 1 / 2 / 4, sum 3.38 / 1.76 / 1.03 ms, both 3.41 /
# 1.79 / 1.61 — a step forms r_t but carries r_{t-1}^2 where the plain one
# loads and selects it, and is the faster of the two; the adjoint 2.03 /
# 1.82 / 2.21, 15.89 / 14.25 / 17.24 ns a step and block, and 24.48 / 24.43 /
# 24.82 over the 16,384-row compaction: sixteen carried planes a chain fill
# the vector slots on ONE register, a second chain buys 10% and four spill)
_ARGARCH_R = {"sum": 4, "both": 4, "adjoint": 2}


def _argarch_layout(mode, t):
    """The blocks of the pair's calls (see :func:`_css_fwd_layout`): the
    series, the five parameter planes, seed and mask; the forward writes the
    likelihood plane (``"both"``: the variance path too), the adjoint reads
    the variance path and the cotangent's plane and writes five gradient
    planes and the seed's."""
    cs = _time_layout(t)[1]
    ins = [(cs, _cur), (5, _fixed), (1, _fixed), (1, _fixed)]
    if mode == "adjoint":
        return (ins + [(cs, _cur), (1, _fixed)],
                [(5, _fixed), (1, _fixed)], [])
    return ins, ([(cs, _cur)] if mode == "both" else []) + [(1, _fixed)], []


def argarch_series_block(rows: int, t: int, mode: str = "sum") -> int:
    """Series per grid step of the ARGARCH kernels (see
    :func:`css_series_block`)."""
    return _SBLK * series_rows(_nsub(rows), _argarch_layout(mode, t),
                               _ARGARCH_R[mode])


def garch_mean_structural_ok(n_time: int) -> bool:
    """The kernels' mean equation reads ``y_{t-1}`` and carries ``r_{t-1}^2``
    inside ONE time chunk: series of at most ``_CHUNK_T`` steps (longer ones
    build their returns in XLA and take :func:`garch_neg_loglik`)."""
    return _time_layout(n_time)[2] == 1


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["y3", "zb3"], meta_fields=["t"])
@dataclasses.dataclass(frozen=True)
class ArgarchFolded:
    """A panel in the layout of the ARGARCH kernels (:func:`argarch_prefold`):
    the masked series ``y3 [Tp, Bp/128, 128]`` and the first live RETURN's
    position ``zb3`` (one past the first valid observation, which the fit
    conditions on); ``t`` the true length."""

    y3: jax.Array
    zb3: jax.Array
    t: int


def argarch_prefold(y, n_valid=None):
    """Mask and fold a panel ONCE for :func:`argarch_neg_loglik_folded` ->
    ``(ArgarchFolded, mom [B, 3])``.

    ``mom`` holds the second moments of the live pairs ``(y_t, y_{t-1})``,
    ``t`` past the first valid observation, each about its own mean and over
    their count ``n_valid - 1``: ``var y``, ``cov(y, y_prev)``, ``var
    y_prev``.  The returns' sample variance, the recursion's seed, is a
    quadratic in ``phi`` of these three (``c`` shifts the returns and their
    mean alike), so no pass forms a returns panel to seed itself."""
    if not garch_mean_structural_ok(y.shape[1]):
        raise ValueError(
            f"the GARCH kernels' mean equation takes series of at most "
            f"{_CHUNK_T} steps, got {y.shape[1]}")
    b, n = y.shape
    nv = (jnp.full((b,), n, jnp.int32) if n_valid is None
          else n_valid.astype(jnp.int32))
    start = (n - nv)[:, None]
    t_idx = jnp.arange(n)[None, :]
    ya = jnp.where(t_idx >= start, y, 0.0)
    pair = (t_idx[:, 1:] > start).astype(y.dtype)
    m = jnp.maximum(nv - 1, 1).astype(y.dtype)[:, None]
    dev = [(v - jnp.sum(v * pair, axis=1, keepdims=True) / m) * pair
           for v in (ya[:, 1:], ya[:, :-1])]
    mom = jnp.stack([jnp.sum(dev[i] * dev[j], axis=1)
                     for i, j in ((0, 0), (0, 1), (1, 1))], axis=1) / m
    tp, _, _ = _time_layout(n)
    return ArgarchFolded(
        _fold(jnp.pad(ya, ((0, 0), (0, tp - n)))),
        _fold((start + 1).astype(y.dtype)), n), mom


def _argarch_fwd_call_f(interpret, mode, params, h0, f: ArgarchFolded,
                        _r=None):
    # only the [B, 5] parameters and the [B] seed are folded per call
    par3 = _fold(params)
    h03 = _fold(h0[:, None].astype(f.y3.dtype))
    layout = _argarch_layout(mode, f.t)
    r = _r or series_rows(f.y3.shape[1], layout, _ARGARCH_R[mode])
    outs = _block_call(
        functools.partial(_argarch_fwd_kernel, f.t, _time_layout(f.t)[1],
                          mode),
        layout, r, interpret, (f.y3, par3, h03, f.zb3))
    return outs, (par3, h03)


def _argarch_bwd_call_f(interpret, f: ArgarchFolded, par3, h03, h3, g3,
                        _r=None):
    """The adjoint on FOLDED operands: ``g3`` the plane gbar of the
    likelihood sum's cotangent -> ``(gpar3, gh03)``.  ``_r`` forces the
    block width (tests and the sweep)."""
    layout = _argarch_layout("adjoint", f.t)
    r = _r or series_rows(f.y3.shape[1], layout, _ARGARCH_R["adjoint"])
    return _block_call(
        functools.partial(_argarch_bwd_kernel, f.t, _time_layout(f.t)[1]),
        layout, r, interpret, (f.y3, par3, h03, f.zb3, h3, g3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _argarch_ll_f(interpret: bool, params, h0, f: ArgarchFolded):
    """:func:`_garch_ll_f` of the returns ``y_t - c - phi y_{t-1}``, formed
    in the calls: ``params [B, 5]`` rows ``[c, phi, omega, alpha, beta]``,
    ``h0 [B]`` the seed variance.  Differentiable in ``params`` and ``h0``
    (the panel is a constant of the objective), with the same bitwise
    agreement between the value-only and the saving forward."""
    (ll3,), _ = _argarch_fwd_call_f(interpret, "sum", params, h0, f)
    return _unfold(ll3, params.shape[0])[:, 0]


def _argarch_ll_f_fwd(interpret, params, h0, f):
    if f.y3.perturbed:
        raise NotImplementedError(
            "the ARGARCH kernels differentiate the objective in its "
            "parameters and seed alone")
    params, h0, f = custom_vjp_primal_tree_values((params, h0, f))
    (h3, ll3), (par3, h03) = _argarch_fwd_call_f(interpret, "both", params,
                                                 h0, f)
    return _unfold(ll3, params.shape[0])[:, 0], (f, par3, h03, h3)


def _argarch_ll_f_bwd(interpret, resid, gbar, _r=None):
    f, par3, h03, h3 = resid
    b = gbar.shape[0]
    if isinstance(gbar, SymbolicZero):  # output provably unused
        return jnp.zeros((b, 5), h3.dtype), jnp.zeros((b,), h3.dtype), None
    gpar3, gh03 = _argarch_bwd_call_f(
        interpret, f, par3, h03, h3, _fold(gbar[:, None].astype(h3.dtype)),
        _r=_r)
    # the panel and the mask are constants: no cotangent formed
    return _unfold(gpar3, b), _unfold(gh03, b)[:, 0], None


_argarch_ll_f.defvjp(_argarch_ll_f_fwd, _argarch_ll_f_bwd,
                     symbolic_zeros=True)


def argarch_neg_loglik_folded(params, folded: ArgarchFolded, mom, *,
                              interpret: bool = False):
    """Batched AR(1)+GARCH(1,1) Gaussian negative log-likelihood ``[B]``
    from a pre-folded panel and its moments (:func:`argarch_prefold`) — the
    fit-loop entry point.  Matches ``models.garch.argarch_neg_log_likelihood``
    (vmapped) to float tolerance; differentiable in ``params [B, 5]``.

    The seed variance, ``[B]``-sized and plain ``jnp``, is formed HERE each
    pass from the moments — the returns' sample variance ``var y - 2 phi
    cov + phi^2 var y_prev`` — and JAX chains the adjoint's ``dL/dh0``
    through it into ``phi``."""
    phi = params[:, 1]
    h0 = jnp.maximum(
        mom[:, 0] - 2.0 * phi * mom[:, 1] + phi * phi * mom[:, 2], 0.0)
    with jax.named_scope("pallas.argarch_neg_loglik"):
        return 0.5 * _argarch_ll_f(interpret, params, h0, folded)


# ---------------------------------------------------------------------------
# EWMA smoothing recursion (forward + hand-derived adjoint)
# ---------------------------------------------------------------------------
#
# s_t = alpha * x_t + (1 - alpha) * s_{t-1}, seeded s_zb = x_zb, prefix 0
# (reference EWMA.scala; matches models.ewma.smooth with a right-aligned
# span).  Adjoint for an upstream cotangent gbar of s:
#   lam_t     = gbar_t + (1 - alpha) * lam_{t+1}   (no flow into the seed's
#                                                   predecessor)
#   dL/dalpha = sum_{t > zb} lam_t * (x_t - s_{t-1})
#   dL/dx_t   = alpha * lam_t  (t > zb);  lam_zb at the seed (s_zb = x_zb)
# The data cotangent costs an extra [B, T] write, so it is emitted only
# when the caller actually differentiates w.r.t. x (symbolic_zeros on the
# custom_vjp) — the fit hot path (alpha-only) never pays it (ADVICE r3).


def _ewma_fwd_kernel(t_limit, cs, mode, *refs):
    # mode "e": smoothed series out; "sum": only the one-step-ahead SSE
    # leaves the kernel (linesearch evals); "both": series AND the SSE,
    # accumulated in the identical order
    refs = list(refs)
    x_ref = refs.pop(0)
    a_ref = refs.pop(0)
    zb_ref = refs.pop(0)
    s_ref = refs.pop(0) if mode != "sum" else None
    ss_ref = refs.pop(0) if mode != "e" else None
    cs_ref = refs.pop(0)
    c = pl.program_id(1)
    base = c * cs
    zb = zb_ref[0]
    a = a_ref[0]

    @pl.when(c == 0)
    def _():
        cs_ref[0] = _ZERO()
        if mode != "e":
            ss_ref[0] = _ZERO()

    def body(tl, carry):
        sprev_c, acc = carry
        t = base + tl
        tf = t.astype(jnp.float32)
        xt = x_ref[tl]
        sp = jnp.where(tl - 1 >= 0, sprev_c, cs_ref[0])
        s = a * xt + (1.0 - a) * sp
        s = jnp.where(tf == zb, xt, s)
        live = (tf >= zb) & (t < t_limit)
        sval = jnp.where(live, s, 0.0)
        if mode != "sum":
            s_ref[tl] = sval
        if mode != "e":
            # one-step-ahead error x_t - s_{t-1}, live strictly after seed
            e = jnp.where((tf > zb) & (t < t_limit), xt - sp, 0.0)
            acc = acc + e * e
        return sval, acc

    sval, acc = _fori(cs, body, (cs_ref[0], _ZERO()))
    cs_ref[0] = sval
    if mode != "e":
        ss_ref[0] = ss_ref[0] + acc


def _ewma_bwd_kernel(t_limit, cs, nchunk, hp, want_gx, *refs):
    refs = list(refs)
    x_ref = refs.pop(0)
    a_ref = refs.pop(0)
    zb_ref = refs.pop(0)
    s_ref = refs.pop(0)
    sp_ref = refs.pop(0) if hp else None
    g_ref = refs.pop(0)
    ga_ref = refs.pop(0)
    gx_ref = refs.pop(0) if want_gx else None
    cl_ref = refs.pop(0)
    c = pl.program_id(1)
    base = (nchunk - 1 - c) * cs
    zb = zb_ref[0]
    a = a_ref[0]

    @pl.when(c == 0)
    def _():
        cl_ref[0] = _ZERO()
        ga_ref[0] = _ZERO()

    def body(i, carry):
        lam_next, da = carry
        tl = cs - 1 - i
        t = base + tl
        tf = t.astype(jnp.float32)
        live = (tf >= zb) & (t < t_limit)
        lam = g_ref[tl] + (1.0 - a) * lam_next
        lam = jnp.where(live, lam, 0.0)
        far = sp_ref[cs - 1] if hp else 0.0
        sp = jnp.where(tl - 1 >= 0, s_ref[jnp.maximum(tl - 1, 0)], far)
        sp = jnp.where(t - 1 >= 0, sp, 0.0)
        da = da + jnp.where(live & (tf > zb), lam * (x_ref[tl] - sp), 0.0)
        if gx_ref is not None:
            # d s_t / d x_t = alpha past the seed, 1 at it (s_zb = x_zb)
            gx_ref[tl] = jnp.where(live, jnp.where(tf > zb, a * lam, lam), 0.0)
        # the seed step s_zb = x_zb does not read s_{zb-1}
        lam_out = jnp.where(tf > zb, lam, 0.0)
        return lam_out, da

    lam, da = _fori(cs, body, (cl_ref[0], _ZERO()))
    cl_ref[0] = lam
    ga_ref[0] = ga_ref[0] + da


def _ewma_fwd_call(interpret, mode, alpha, x, zb):
    b, t = x.shape
    tp, cs, nchunk = _time_layout(t)
    x3 = _fold(jnp.pad(x, ((0, 0), (0, tp - t))))
    a3 = _fold(alpha[:, None].astype(x.dtype))
    zb3 = _fold(zb.astype(x.dtype)[:, None])
    nblk = x3.shape[1] // _SUBL
    out_specs, out_shape = [], []
    if mode != "sum":
        out_specs.append(_bs(cs, _cur))
        out_shape.append(jax.ShapeDtypeStruct(x3.shape, x.dtype))
    if mode != "e":
        out_specs.append(_bs(1, _fixed))
        out_shape.append(jax.ShapeDtypeStruct((1, x3.shape[1], _LANES), x.dtype))
    outs = pl.pallas_call(
        functools.partial(_ewma_fwd_kernel, t, cs, mode),
        grid=(nblk, nchunk),
        in_specs=[_bs(cs, _cur), _bs(1, _fixed), _bs(1, _fixed)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, _SUBL, _LANES), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(x3, a3, zb3)
    return outs, (x3, a3, zb3)


def _ewma_bwd_call(interpret, res, g, want_gx):
    """Shared EWMA adjoint dispatch -> ``(g_alpha [B], g_x [B, T] | None)``."""
    x3, a3, zb3, s3, b, t = res
    tp = x3.shape[0]
    _, cs, nchunk = _time_layout(t)
    g3 = _fold(jnp.pad(g, ((0, 0), (0, tp - t))))
    nblk = x3.shape[1] // _SUBL
    hp = nchunk > 1
    if hp:
        ins = [_bs(cs, _rev(nchunk)), _bs(1, _fixed), _bs(1, _fixed),
               _bs(cs, _rev(nchunk)), _bs(cs, _rev_prev(nchunk)),
               _bs(cs, _rev(nchunk))]
        args = (x3, a3, zb3, s3, s3, g3)
    else:
        ins = [_bs(cs, _rev(nchunk)), _bs(1, _fixed), _bs(1, _fixed),
               _bs(cs, _rev(nchunk)), _bs(cs, _rev(nchunk))]
        args = (x3, a3, zb3, s3, g3)
    out_specs = [_bs(1, _fixed)]
    out_shape = [jax.ShapeDtypeStruct(a3.shape, g.dtype)]
    if want_gx:
        out_specs.append(_bs(cs, _rev(nchunk)))
        out_shape.append(jax.ShapeDtypeStruct(x3.shape, g.dtype))
    outs = pl.pallas_call(
        functools.partial(_ewma_bwd_kernel, t, cs, nchunk, hp, want_gx),
        grid=(nblk, nchunk),
        in_specs=ins,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((1, _SUBL, _LANES), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(*args)
    ga = _unfold(outs[0], b)[:, 0]
    gx = _unfold(outs[1], b)[:, :t] if want_gx else None
    return ga, gx


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ewma_s(interpret: bool, alpha, x, zb):
    b, t = x.shape
    (s3,), _ = _ewma_fwd_call(interpret, "e", alpha, x, zb)
    return _unfold(s3, b)[:, :t]


def _ewma_s_fwd(interpret, alpha, x, zb):
    # symbolic_zeros: args are CustomVJPPrimal; .perturbed says whether the
    # caller differentiates w.r.t. each input.  The x cotangent is computed
    # only when x is perturbed (an extra [B, T] kernel output otherwise
    # wasted on the alpha-only fit path).  The marker is structural
    # (None vs ()) so the bwd branch is resolved at trace time.
    alpha_p, x_p, zb_p = alpha.value, x.value, zb.value
    b, t = x_p.shape
    (s3,), (x3, a3, zb3) = _ewma_fwd_call(interpret, "e", alpha_p, x_p, zb_p)
    marker = () if x.perturbed else None
    return _unfold(s3, b)[:, :t], (x3, a3, zb3, s3, b, t, marker)


def _ewma_s_bwd(interpret, res, g):
    x3, a3, zb3, s3, b, t, marker = res
    if isinstance(g, SymbolicZero):  # output provably unused: all-zero grads
        return (jnp.zeros((b,), g.dtype), jnp.zeros((b, t), g.dtype),
                jnp.zeros((b,), g.dtype))
    want_gx = marker is not None
    ga, gx = _ewma_bwd_call(interpret, (x3, a3, zb3, s3, b, t), g, want_gx)
    if gx is None:
        gx = jnp.zeros((b, t), g.dtype)
    return ga, gx, jnp.zeros((b,), g.dtype)


_ewma_s.defvjp(_ewma_s_fwd, _ewma_s_bwd, symbolic_zeros=True)


def ewma_smooth(alpha, x, zb, *, interpret: bool = False):
    """Batched EWMA smoothing ``[B, T]`` on a fused kernel.

    ``alpha``: ``[B]``; ``x``: ``[B, T]`` with the invalid prefix zeroed;
    ``zb``: ``[B]`` first live position.  Differentiable in ``alpha`` AND
    ``x`` (the data cotangent is computed only when x is perturbed).
    """
    return _ewma_s(interpret, alpha, x, zb)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ewma_ssq(interpret: bool, alpha, xz, zb):
    """One-step-ahead SSE ``[B]`` of the EWMA recursion.

    Primal path: sum-only kernel (the smoothed series never reaches HBM);
    vjp path saves it and chains the error partials into the hand-derived
    smoothing adjoint, with the VALUE accumulated in the identical
    in-kernel order (see ``_css_ss_f``).
    """
    b, t = xz.shape
    (ss3,), _ = _ewma_fwd_call(interpret, "sum", alpha, xz, zb)
    return _unfold(ss3, b)[:, 0]


def _ewma_ssq_fwd(interpret, alpha, xz, zb):
    alpha_p, x_p, zb_p = alpha.value, xz.value, zb.value
    b, t = x_p.shape
    (s3, ss3), (x3, a3, zb3) = _ewma_fwd_call(interpret, "both", alpha_p,
                                              x_p, zb_p)
    marker = () if xz.perturbed else None  # see _ewma_s_fwd
    return _unfold(ss3, b)[:, 0], (x3, a3, zb3, s3, x_p, zb_p, b, t, marker)


def _ewma_ssq_bwd(interpret, resid, gbar):
    x3, a3, zb3, s3, xz, zb, b, t, marker = resid
    if isinstance(gbar, SymbolicZero):  # output provably unused
        return (jnp.zeros((b,), xz.dtype), jnp.zeros_like(xz),
                jnp.zeros_like(zb))
    want_gx = marker is not None
    s = _unfold(s3, b)[:, :t]
    t_idx = jnp.arange(t, dtype=xz.dtype)
    live_e = t_idx[None, 1:] > zb[:, None]  # err_t = x_t - s_{t-1}, t > seed
    err = jnp.where(live_e, xz[:, 1:] - s[:, :-1], 0.0)
    # d sse / d s_{t-1} = -2 err_t; the last position feeds no error
    g_s = jnp.concatenate(
        [-2.0 * err * gbar[:, None], jnp.zeros((b, 1), xz.dtype)], axis=1
    )
    g_alpha, gx_chain = _ewma_bwd_call(
        interpret, (x3, a3, zb3, s3, b, t), g_s, want_gx
    )
    if want_gx:
        # direct term: d err_t^2 / d x_t = 2 err_t (the smoothing-path term
        # -2 err_t * d s_{t-1}/dx came through the adjoint kernel above)
        gx = gx_chain + jnp.concatenate(
            [jnp.zeros((b, 1), xz.dtype), 2.0 * err * gbar[:, None]], axis=1
        )
    else:
        gx = jnp.zeros_like(xz)
    return g_alpha, gx, jnp.zeros_like(zb)


_ewma_ssq.defvjp(_ewma_ssq_fwd, _ewma_ssq_bwd, symbolic_zeros=True)


@_scoped("pallas.ewma_sse")
def ewma_sse(alpha, x, n_valid=None, *, interpret: bool = False):
    """Batched one-step-ahead EWMA SSE ``[B]`` (matches ``models.ewma.sse``).
    Differentiable in ``alpha`` AND ``x`` (the data cotangent is computed
    only when x is perturbed, so the alpha-only fit path pays nothing)."""
    b, n = x.shape
    nv = (
        jnp.full((b,), n, jnp.int32)
        if n_valid is None
        else n_valid.astype(jnp.int32)
    )
    start = (n - nv).astype(x.dtype)
    t_idx = jnp.arange(n, dtype=x.dtype)
    xz = jnp.where(t_idx[None, :] >= start[:, None], x, 0.0)
    return _ewma_ssq(interpret, alpha, xz, start)


# ---------------------------------------------------------------------------
# Holt-Winters smoothing, additive & multiplicative, ragged-aware
# (forward + hand-derived adjoint)
# ---------------------------------------------------------------------------
#
# Per series (reference HoltWinters.scala; matches models.holtwinters._run
# with a right-aligned valid span starting at zb).  Additive:
#   pred_t = L_{t-1} + T_{t-1} + S_t          with S_t = ring[t mod m]
#   L_t    = a (y_t - S_t) + (1-a)(L_{t-1} + T_{t-1})
#   T_t    = b (L_t - L_{t-1}) + (1-b) T_{t-1}
#   ring[t mod m] = g (y_t - L_t) + (1-g) S_t
# Multiplicative:
#   pred_t = (L_{t-1} + T_{t-1}) * S_t
#   L_t    = a y_t / S_t + (1-a)(L_{t-1} + T_{t-1})
#   ring[t mod m] = g y_t / L_t + (1-g) S_t        (denominators eps-clamped)
#   e_t    = [zb + m <= t < t_limit] * (y_t - pred_t)
# State is frozen outside [zb, t_limit): the recursion effectively starts at
# the first valid observation.  The ring is indexed by t mod m with PER-ROW
# zb, so the caller pre-rotates the seed ring (seed element j lands at slot
# (zb + j) mod m) — scratch indices must be scalar per block.
#
# The seasonal ring lives in a [m, 8, 128] VMEM scratch and persists across
# time chunks.  Seeds (L_0, T_0, ring init) are computed OUTSIDE the kernel
# from the first two valid seasons — they depend on the data only, so the
# adjoint propagates to the three smoothing parameters alone.
#
# ADDITIVE: the forward saves ONE panel and the adjoint reads ONE.  With
#   r_t = y_t - (L_{t-1} + T_{t-1} + S_t)
# the raw one-step error of a live step (zero outside [zb, t_limit); e_t is
# r_t on the live-err steps and 0 in the first season after zb), the update
# is L_t = L_{t-1} + T_{t-1} + a r_t, so every data-dependent factor of the
# reverse pass is a multiple of r_t and its state recursion has coefficients
# constant in (a, b, g) — no trajectory, no y, no seed, no neighbour chunk:
#   gp        = -2 r_t gbar on live-err steps, else 0
#   vL        = uL + b uT - g uS
#   da       += r_t vL            (y_t - S_t - L_{t-1} - T_{t-1} = r_t)
#   db       += a r_t uT          (L_t - L_{t-1} - T_{t-1}       = a r_t)
#   dg       += (1-a) r_t uS      (y_t - L_t - S_t               = (1-a) r_t)
#                                  summed as r_t uS, times (1-a) a chunk
#   uL'       = -b uT + (1-a) vL + gp
#   uT'       = (1-b) uT + (1-a) vL + gp
#   rho[slot] = (1-g) uS - a vL + gp
# with rho a ring of seasonal adjoints.  (Three panel moves a gradient — y
# read and r written by the forward, r read by the adjoint — where the
# replay of saved trajectories moved ten; PERF.md §6, PR 43.  WHICH of the
# algebraically equal forms these sums take is not free: in f32 the objective
# is jagged where a series' alpha is tiny (L += a r is a few ulp of L), the
# gradient's last place decides where about 1% of a million fits stop, and
# of some twenty equal forms measured on the chip — three last-place variants
# of the replay's own among them — every one but this left 1-6 rows of the
# benchmark's million with an exhausted line search, on the retry ladder.)
#
# MULTIPLICATIVE: the forward saves TWO panels and the adjoint reads THREE.
# Write P_t = L_{t-1} + T_{t-1} (the forward's ``lt_sum``) and S_t for the
# ring's value before the step.  Every quantity of the reverse pass is a
# function of (y_t, S_t, P_t) and the three parameters:
#   e_t  = y_t - P_t S_t                  the forward's own ``yt - pred``
#   L_t  = a y_t / max(S_t, eps) + (1-a) P_t      the forward's own ``nl``
#          (:func:`_hw_mult_level`, ONE expression for both kernels: the
#          recomputed level is the forward's bit for bit, so the clamp's
#          subgradient ``l_pass = [L_t >= eps]`` is the forward's too)
#   sc   = max(S_t, eps), ltc = max(L_t, eps), s_pass = [S_t >= eps]
#   gp   = -2 e_t gbar on live-err steps, else 0
#   ys   = y_t / sc, ys2 = ys / sc, yl = y_t / ltc, yl2 = yl / ltc
#   vL   = uL + b uT - g yl2 uS l_pass
#   da  += (ys - P_t) vL
#   db  += (L_t - P_t) uT
#   dg  += (yl - S_t) uS
#   uL'  = -b uT + (1-a) vL + S_t gp
#   uT'  = (1-b) uT + (1-a) vL + S_t gp
#   rho[slot] = (1-g) uS - a ys2 vL s_pass + P_t gp
# L_{t-1} and T_{t-1} are never needed apart, so no seed and, past one time
# chunk, no neighbour block is an operand.  (Six panel moves a gradient — y
# read and S, P written by the forward, y, S, P read by the adjoint — where
# the replay of (e, L, T, S_old) moved ten: four written, five read;
# PERF.md §6, PR 45.  No step is recomputed and no recursion inverted.  Of
# the equal forms: ``e_t`` and ``L_t`` stay the forward's expressions, and
# ``da`` / ``db`` take ``P_t`` where the replay subtracted ``L_{t-1}`` and
# ``T_{t-1}`` one after the other.  The squares' quotients are written
# ``(y / sc) / sc``, not ``y / (sc sc)``: Mosaic divides by a refined
# reciprocal of the DENOMINATOR, so the second form pays four reciprocals a
# step and the first two — 131 bundles a step for four registers against
# 104, 2.83 ms against 2.24 over [131072, 960], where three panels at the
# HBM's pace are 2.2.  Both forms left no row of the benchmark's 524,288 on
# the retry ladder in any run.)
# The two models part on the STATIC ``mult`` the kernels are specialised by.
# Level/trend carries cross chunks through 1-slot scratches; both rings
# (seasonal state forward, seasonal adjoint backward) persist untouched.


def _hw_mult_level(a, yt, s, lt_sum):
    """The multiplicative level update ``L_t`` from ``(y_t, S_t, L_{t-1} +
    T_{t-1})``: the forward kernel steps with it and the adjoint kernel
    recomputes the level by it, so the two agree bit for bit."""
    return a * yt / jnp.maximum(s, 1e-12) + (1.0 - a) * lt_sum


def _hw_fwd_kernel(m, mult, save_resid, t_limit, cs, y_ref, par_ref, l0_ref,
                   t0_ref, s0_ref, zb_ref, *refs):
    # vjp path: what the adjoint reads beside y — the raw errors alone
    # (additive) or the old season and L + T (multiplicative) — + the SSE,
    # accumulated in the same in-kernel order as the primal variant.
    # Primal path (linesearch evals): ONLY the per-series SSE leaves the
    # kernel — the residual stores are the HBM bill
    r_ref = so_ref = p_ref = None
    if save_resid and mult:
        so_ref, p_ref, ss_ref, seas_ref, clt_ref = refs
    elif save_resid:
        r_ref, ss_ref, seas_ref, clt_ref = refs
    else:
        ss_ref, seas_ref, clt_ref = refs
    c = pl.program_id(1)
    base = c * cs
    a = par_ref[0]
    b = par_ref[1]
    g = par_ref[2]
    zb = zb_ref[0]

    @pl.when(c == 0)
    def _():
        for j in range(m):
            seas_ref[j] = s0_ref[j]
        clt_ref[0] = l0_ref[0]
        clt_ref[1] = t0_ref[0]
        ss_ref[0] = _plane_zero(zb_ref)

    def body(tl, carry):
        level, trend, acc = carry
        t = base + tl
        tf = t.astype(jnp.float32)
        slot = lax.rem(t, jnp.asarray(m, t.dtype))
        s = seas_ref[slot]
        yt = y_ref[tl]
        live = (tf >= zb) & (t < t_limit)
        live_err = (tf >= zb + m) & (t < t_limit)
        lt_sum = level + trend
        if mult:
            pred = lt_sum * s
            nl = _hw_mult_level(a, yt, s, lt_sum)
            snew = g * yt / jnp.maximum(nl, 1e-12) + (1.0 - g) * s
        else:
            pred = lt_sum + s
            nl = a * (yt - s) + (1.0 - a) * lt_sum
            snew = g * (yt - nl) + (1.0 - g) * s
        nt = b * (nl - level) + (1.0 - b) * trend
        err = yt - pred
        e = jnp.where(live_err, err, 0.0)
        nl_o = jnp.where(live, nl, level)
        nt_o = jnp.where(live, nt, trend)
        seas_ref[slot] = jnp.where(live, snew, s)
        if r_ref is not None:
            r_ref[tl] = jnp.where(live, err, 0.0)
        elif save_resid:
            so_ref[tl] = s
            p_ref[tl] = lt_sum
        return nl_o, nt_o, acc + e * e

    level, trend, acc = _fori(
        cs, body, (clt_ref[0], clt_ref[1], _plane_zero(zb_ref)))
    clt_ref[0] = level
    clt_ref[1] = trend
    ss_ref[0] = ss_ref[0] + acc


def _hw_bwd_kernel(m, mult, t_limit, cs, nchunk, *refs):
    if mult:  # the panel, the old season and L + T
        (y_ref, par_ref, zb_ref, gb_ref, so_ref, p_ref, gpar_ref, rho_ref,
         clam_ref) = refs
    else:  # the raw errors are all the additive reverse pass reads
        r_ref, par_ref, zb_ref, gb_ref, gpar_ref, rho_ref, clam_ref = refs
    c = pl.program_id(1)
    base = (nchunk - 1 - c) * cs
    a = par_ref[0]
    b = par_ref[1]
    g = par_ref[2]
    zb = zb_ref[0]
    gb = gb_ref[0]  # the SSE's cotangent: the error's is 2 e gb, formed here
    zero = _plane_zero(zb_ref)

    @pl.when(c == 0)
    def _():
        for j in range(m):
            rho_ref[j] = zero
        clam_ref[0] = zero
        clam_ref[1] = zero
        for r in range(3):
            gpar_ref[r] = zero

    def body(i, carry):
        lamL, lamT, da, db, dg = carry
        tl = cs - 1 - i
        t = base + tl
        tf = t.astype(jnp.float32)
        slot = lax.rem(t, jnp.asarray(m, t.dtype))
        live = (tf >= zb) & (t < t_limit)
        live_err = (tf >= zb + m) & (t < t_limit)
        uS = rho_ref[slot]
        uL = lamL
        uT = lamT
        if mult:
            so = so_ref[tl]
            lt_sum = p_ref[tl]
            yt = y_ref[tl]
            # the error and the level in the forward's own expressions
            gp = jnp.where(live_err, -(2.0 * (yt - lt_sum * so) * gb), 0.0)
            lt = _hw_mult_level(a, yt, so, lt_sum)
            sc = jnp.maximum(so, 1e-12)
            ltc = jnp.maximum(lt, 1e-12)
            # eps-clamp subgradients: no flow through a clamped denominator
            s_pass = (so >= 1e-12).astype(jnp.float32)
            l_pass = (lt >= 1e-12).astype(jnp.float32)
            # two reciprocals a step, not four: y / sc^2 as (y / sc) / sc
            ys = yt / sc
            ys2 = ys / sc
            yl = yt / ltc
            yl2 = yl / ltc
            vL = uL + b * uT - g * yl2 * uS * l_pass
            da_t = (ys - lt_sum) * vL
            dg_t = (yl - so) * uS
            new_lamL = -b * uT + (1.0 - a) * vL + so * gp
            new_lamT = (1.0 - b) * uT + (1.0 - a) * vL + so * gp
            rho_new = (
                (1.0 - g) * uS
                - a * ys2 * vL * s_pass
                + lt_sum * gp
            )
            db_t = (lt - lt_sum) * uT
        else:
            rt = r_ref[tl]
            gp = jnp.where(live_err, -(2.0 * rt * gb), 0.0)
            vL = uL + b * uT - g * uS
            da_t = rt * vL
            db_t = a * rt * uT
            dg_t = rt * uS  # its factor (1 - a) after the loop
            new_lamL = -b * uT + (1.0 - a) * vL + gp
            new_lamT = (1.0 - b) * uT + (1.0 - a) * vL + gp
            rho_new = (1.0 - g) * uS - a * vL + gp
        da = da + jnp.where(live, da_t, 0.0)
        db = db + jnp.where(live, db_t, 0.0)
        dg = dg + jnp.where(live, dg_t, 0.0)
        lamL_o = jnp.where(live, new_lamL, uL)
        lamT_o = jnp.where(live, new_lamT, uT)
        rho_ref[slot] = jnp.where(live, rho_new, uS)
        return lamL_o, lamT_o, da, db, dg

    lamL, lamT, da, db, dg = lax.fori_loop(
        0, cs, body, (clam_ref[0], clam_ref[1], zero, zero, zero)
    )
    clam_ref[0] = lamL
    clam_ref[1] = lamT
    gpar_ref[0] = gpar_ref[0] + da
    gpar_ref[1] = gpar_ref[1] + db
    gpar_ref[2] = gpar_ref[2] + (dg if mult else (1.0 - a) * dg)


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["y3", "l03", "t03", "s03", "zb3"], meta_fields=["t"])
@dataclasses.dataclass(frozen=True)
class HWFolded:
    """A Holt-Winters panel and its seed state in kernel layout
    (:func:`hw_prefold`): ``y3 [Tp, Bp/128, 128]`` zero-padded by
    :func:`_time_layout`, the level / trend seeds and ``zb`` as
    ``[1, Bp/128, 128]`` planes, the pre-rotated ring ``s03 [m, Bp/128,
    128]``; ``t`` is the true series length (static: it rides the treedef
    through a ``jit`` boundary)."""

    y3: jax.Array
    l03: jax.Array
    t03: jax.Array
    s03: jax.Array
    zb3: jax.Array
    t: int

    def take(self, idxc):
        """The series ``idxc`` (a multiple of 1024 of them) as folded
        COLUMNS: the straggler compaction re-folds nothing."""
        return take_series(self, idxc)


# by ``save_resid``, then ``mult``; see _CSS_R: value-only 2.58 / 1.43 / 0.87
# ms over [131072, 960]; the additive save_resid writes one panel, 2.68 / 1.55
# / 1.53 ms (1 GB at the HBM's pace from R = 2 on; PERF.md §6, PR 43); the
# multiplicative one writes two, 4.73 / 2.86 / 2.23 ms: two divisions deep a
# step, it waits out their latency until four chains share it, and 1.5 GB at
# 2.23 ms is the HBM's pace (0.63 / 0.48 / 0.47-0.52 ms over the 16,384-row
# compaction; PERF.md §6, PR 45)
_HW_R = {False: {False: 4, True: 4}, True: {False: 4, True: 4}}


def _hw_fwd_layout(m, mult, save_resid, t):
    """The forward Holt-Winters call's blocks (see
    :func:`_css_fwd_layout`)."""
    _, cs, _ = _time_layout(t)
    ins = [(cs, _cur), (3, _fixed), (1, _fixed), (1, _fixed), (m, _fixed),
           (1, _fixed)]
    # what the adjoint reads beside the panel — the raw errors, or the old
    # season and L + T: 1 / 2 panels written — then the per-series SSE
    saved = [(cs, _cur)] * (2 if mult else 1) if save_resid else []
    # scratch: the seasonal ring, level / trend
    return ins, saved + [(1, _fixed)], [m, 2]


def hw_series_block(rows: int, t: int, period: int, mode: str = "sum",
                    mult: bool = False) -> int:
    """Series per grid step of the Holt-Winters kernel (see
    :func:`css_series_block`); ``mode``: ``"sum"``, ``"save_resid"`` or
    ``"adjoint"``, of the additive or the multiplicative model."""
    if mode == "adjoint":
        layout, best = _hw_bwd_layout(period, mult, t), _ADJOINT_R["hw"][mult]
    else:
        save_resid = {"sum": False, "save_resid": True}[mode]
        layout, best = (_hw_fwd_layout(period, mult, save_resid, t),
                        _HW_R[save_resid][mult])
    return _SBLK * series_rows(_nsub(rows), layout, best)


def _hw_fwd_call_f(interpret, m, mult, save_resid, params, f: HWFolded,
                   _r=None):
    # pre-FOLDED entry (see _css_fwd_call_f): only the [B, 3] parameters are
    # folded per call; the panel and its seeds arrive in kernel layout
    _, cs, _ = _time_layout(f.t)
    par3 = _fold(params)
    layout = _hw_fwd_layout(m, mult, save_resid, f.t)
    r = _r or series_rows(f.y3.shape[1], layout, _HW_R[save_resid][mult])
    outs = _block_call(
        functools.partial(_hw_fwd_kernel, m, mult, save_resid, f.t, cs),
        layout, r, interpret, (f.y3, par3, f.l03, f.t03, f.s03, f.zb3))
    return outs, par3


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _hw_ss_f(interpret: bool, m: int, mult: bool, params, f: HWFolded):
    """Per-series one-step-ahead SSE ``[B]`` from the FOLDED layout (the
    true unpadded sizes are ``params.shape[0]`` and ``f.t``).

    Primal (no-gradient) path: sum-only kernel — a linesearch objective
    evaluation pays one panel read and no error/trajectory stores.  The vjp
    path saves what the hand-derived adjoint reads, folded: the raw errors
    (additive: 1 panel written, 1 read), or the old season and ``L + T``
    (multiplicative: 2 written, 3 read, the panel itself the third).
    The unfolded API (:func:`hw_sse_seeded`) is a thin fold-then-delegate
    wrapper: ONE forward call, ONE adjoint.
    """
    (ss3,), _ = _hw_fwd_call_f(interpret, m, mult, False, params, f)
    return _unfold(ss3, params.shape[0])[:, 0]


def _hw_ss_f_fwd(interpret, m, mult, params, f):
    # additive: (r3, ss3); multiplicative: (so3, p3, ss3)
    (*saved, ss3), par3 = _hw_fwd_call_f(interpret, m, mult, True, params, f)
    # the value is accumulated in the same in-kernel order as the primal
    # variant — see _css_ss_f: mixed accumulation orders stall rows
    return _unfold(ss3, params.shape[0])[:, 0], (f, par3, *saved)


# the panel-sized operands of the objective's adjoint call, by ``mult`` (a
# stage span's ``adjoint_panels``): the raw errors r3 alone, or y3, the old
# season so3 and p3 = L + T
HW_ADJOINT_PANELS = {False: 1, True: 3}


def _hw_bwd_layout(m, mult, t):
    """The Holt-Winters adjoint call's blocks (see :func:`_css_bwd_layout`).
    Additive: the raw errors, parameters, mask and the cotangent's plane:
    1 panel read.  Multiplicative: the panel, parameters, mask and the
    plane, then the old season and ``L + T``: 3 read.  No lag reaches past
    the step, so a series past one chunk brings no neighbour block."""
    panel = _rev_panel(t)[:1]
    ins = panel + [(3, _fixed)] + [(1, _fixed)] * 2 + (panel * 2 if mult
                                                        else [])
    # scratch: the seasonal ring's adjoint, level / trend across chunks
    return ins, [(3, _fixed)], [m, 2]


def _hw_ss_f_bwd(interpret, m, mult, resid, gbar, _r=None):
    f, par3, *saved = resid
    b = gbar.shape[0]
    # gbar [B] folds to a plane, and the plane is what the adjoint kernel is
    # handed beside the errors (see _css_ss_f_bwd): it forms the error
    # cotangent 2 e gbar itself, no panel-sized XLA pass per gradient;
    # padded series carry a zero gbar, padded time a zero error
    gb3 = _fold(gbar[:, None].astype(par3.dtype))
    _, cs, nchunk = _time_layout(f.t)
    # additive: r3 alone; multiplicative: y3, then so3 and p3
    panels = (f.y3, *saved) if mult else saved
    args = (panels[0], par3, f.zb3, gb3, *panels[1:])
    layout = _hw_bwd_layout(m, mult, f.t)
    # ``_r`` forces the block width (tests and the sweep)
    r = _r or series_rows(f.y3.shape[1], layout, _ADJOINT_R["hw"][mult])
    (gpar3,) = _block_call(
        functools.partial(_hw_bwd_kernel, m, mult, f.t, cs, nchunk),
        layout, r, interpret, args)
    # seeds and data are constants of the objective: zero cotangents
    return _unfold(gpar3, b), jax.tree_util.tree_map(jnp.zeros_like, f)


_hw_ss_f.defvjp(_hw_ss_f_fwd, _hw_ss_f_bwd)


# ---------------------------------------------------------------------------
# Fused fill-linear feature chain (forward-only transform, no adjoint)
# ---------------------------------------------------------------------------
#
# The portable fills (ops.univariate.fill_linear) are built from FOUR
# log2(T)-step associative scans — ~40 full-panel HBM round trips for the
# fillLinear -> difference -> lag feature chain that the reference runs as
# one per-series pass (UnivariateTimeSeries.fillLinear, SURVEY.md §2.1).
# ONE kernel, two phases over the time-chunk grid (VERDICT r4 weak item 1:
# the old two-kernel version streamed its (next-valid value, index)
# intermediates through HBM — 2 full panel writes + 2 reads that never
# belonged to the interface):
#   phase 0 (chunks last->first) records only the per-chunk backward carry
#     in VMEM scratch — a vectorized first-valid reduction, no HBM writes;
#   phase 1 (chunks first->last) rebuilds the chunk-local next-valid arrays
#     in VMEM from the recorded carry (sequential backward minisweep), then
#     runs the forward fill sweep emitting ONLY the requested outputs.
# Total HBM traffic: 2 panel reads + one write per requested output (1 read
# when the series fits a single chunk — phase 0 is skipped entirely).


def _fillchain_fused_kernel(t_limit, cs, nchunk, which, *refs):
    n_out = sum(which)
    y_ref = refs[0]
    out_refs = list(refs[1 : 1 + n_out])
    carry_ref, nv_ref, ni_ref, fwd_ref = refs[1 + n_out :]
    single = nchunk == 1
    s = pl.program_id(1)
    nan = jnp.float32(jnp.nan)
    f_ref = out_refs.pop(0) if which[0] else None
    d_ref = out_refs.pop(0) if which[1] else None
    l_ref = out_refs.pop(0) if which[2] else None

    if not single:
        # live backward carry rides the last two scratch slots
        @pl.when(s == 0)
        def _():
            carry_ref[2 * nchunk] = _ZERO()
            carry_ref[2 * nchunk + 1] = jnp.full(
                (_SUBL, _LANES), 1e30, jnp.float32
            )

        @pl.when(s < nchunk)
        def _():  # phase 0, chunk c = nchunk-1-s: record + merge, no stores
            c = nchunk - 1 - s
            y = y_ref[:]
            tf = (c * cs + lax.broadcasted_iota(jnp.int32, (cs, 1, 1), 0)
                  ).astype(jnp.float32)
            valid = (y == y) & (tf < t_limit)
            # first valid element of the chunk, vectorized (tf is unique
            # along the time axis, so the masked sum selects exactly one)
            tmin = jnp.min(jnp.where(valid, tf, 1e30), axis=0)
            vsel = jnp.sum(jnp.where(valid & (tf == tmin[None]), y, 0.0), axis=0)
            carry_ref[2 * c] = carry_ref[2 * nchunk]
            carry_ref[2 * c + 1] = carry_ref[2 * nchunk + 1]
            has = tmin < 1e30
            carry_ref[2 * nchunk] = jnp.where(has, vsel, carry_ref[2 * nchunk])
            carry_ref[2 * nchunk + 1] = jnp.where(
                has, tmin, carry_ref[2 * nchunk + 1]
            )

    first_fwd = 0 if single else nchunk

    @pl.when(s >= first_fwd)
    def _():  # phase 1, chunk c = s - first_fwd
        c = s - first_fwd
        base = c * cs

        @pl.when(s == first_fwd)
        def _():
            fwd_ref[0] = _ZERO()  # prev-valid value
            fwd_ref[1] = jnp.full((_SUBL, _LANES), -1e30, jnp.float32)
            fwd_ref[2] = jnp.full((_SUBL, _LANES), nan, jnp.float32)  # fill[t-1]

        def bwd(i, carry):
            cnv, cni = carry
            tl = cs - 1 - i
            yt = y_ref[tl]
            tf = (base + tl).astype(jnp.float32)
            valid = (yt == yt) & (base + tl < t_limit)  # NaN != NaN
            cnv = jnp.where(valid, yt, cnv)
            cni = jnp.where(valid, tf, cni)
            nv_ref[tl] = cnv
            ni_ref[tl] = cni
            return cnv, cni

        if single:
            seed = (_ZERO(), jnp.full((_SUBL, _LANES), 1e30, jnp.float32))
        else:
            seed = (carry_ref[2 * c], carry_ref[2 * c + 1])
        _fori(cs, bwd, seed, unroll=8)

        def fwd(tl, carry):
            pv, pi, fprev = carry
            t = base + tl
            tf = t.astype(jnp.float32)
            yt = y_ref[tl]
            valid = (yt == yt) & (t < t_limit)
            interior = (pi >= 0.0) & (ni_ref[tl] < t_limit)
            span = jnp.maximum(ni_ref[tl] - pi, 1.0)
            w = (tf - pi) / span
            interp = pv * (1.0 - w) + nv_ref[tl] * w
            fill = jnp.where(valid, yt, jnp.where(interior, interp, nan))
            if f_ref is not None:
                f_ref[tl] = fill
            if d_ref is not None:
                d_ref[tl] = fill - fprev  # NaN fprev poisons t=0 as required
            if l_ref is not None:
                l_ref[tl] = fprev
            pv = jnp.where(valid, yt, pv)
            pi = jnp.where(valid, tf, pi)
            return pv, pi, fill

        pv, pi, fprev = _fori(cs, fwd, (fwd_ref[0], fwd_ref[1], fwd_ref[2]),
                              unroll=8)
        fwd_ref[0] = pv
        fwd_ref[1] = pi
        fwd_ref[2] = fprev


def _fill_linear_call_folded(y3, t: int, which, interpret: bool):
    """Core fused chain on a FOLDED panel -> folded outputs (no layout
    conversion: the resident-layout entry point)."""
    tp, cs, nchunk = _time_layout(t)
    if y3.shape[0] != tp:
        raise ValueError(
            f"folded panel has time dim {y3.shape[0]}, layout wants {tp}"
        )
    nblk = y3.shape[1] // _SUBL
    n_out = sum(which)
    single = nchunk == 1
    steps = nchunk if single else 2 * nchunk

    if single:
        ymap = _cur
        omap = _cur
    else:
        def ymap(blk, s):
            return (jnp.where(s < nchunk, nchunk - 1 - s, s - nchunk), blk, 0)

        def omap(blk, s):
            # park output windows on chunk 0 through phase 0 (no stores);
            # every window is fully written during its phase-1 visit
            return (jnp.where(s < nchunk, 0, s - nchunk), blk, 0)

    outs = pl.pallas_call(
        functools.partial(_fillchain_fused_kernel, t, cs, nchunk, which),
        grid=(nblk, steps),
        in_specs=[_bs(cs, ymap)],
        out_specs=[_bs(cs, omap)] * n_out,
        out_shape=[jax.ShapeDtypeStruct(y3.shape, jnp.float32)] * n_out,
        scratch_shapes=[
            pltpu.VMEM((2 * nchunk + 2, _SUBL, _LANES), jnp.float32),
            pltpu.VMEM((cs, _SUBL, _LANES), jnp.float32),
            pltpu.VMEM((cs, _SUBL, _LANES), jnp.float32),
            pltpu.VMEM((3, _SUBL, _LANES), jnp.float32),
        ],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(y3)
    return outs  # list even when singleton


_CHAIN_OUTPUTS = ("filled", "diff", "lag")


def fill_linear_chain_folded(fp, outputs=_CHAIN_OUTPUTS, *,
                             interpret: bool = False):
    """Fused fill chain on a resident :class:`~.layout.FoldedPanel`,
    emitting ONLY the requested outputs as folded panels (VERDICT r4: the
    old chain wrote all three whether or not the caller wanted them).

    ``outputs`` is an ordered subset of ``("filled", "diff", "lag")``; the
    result tuple matches its order.
    """
    from .layout import FoldedPanel

    bad = [o for o in outputs if o not in _CHAIN_OUTPUTS]
    if bad or not outputs:
        raise ValueError(f"outputs must be a non-empty subset of "
                         f"{_CHAIN_OUTPUTS}, got {outputs!r}")
    which = tuple(o in outputs for o in _CHAIN_OUTPUTS)
    outs = _fill_linear_call_folded(fp.data, fp.t, which, interpret)
    by_name = dict(zip([o for o, w in zip(_CHAIN_OUTPUTS, which) if w], outs))
    return tuple(FoldedPanel(by_name[o], fp.b, fp.t) for o in outputs)


def _fill_linear_call(y, chain: bool, interpret: bool):
    b, t = y.shape
    tp, _, _ = _time_layout(t)
    # pad with NaN so padded tail positions read as invalid
    y3 = _fold(jnp.pad(y, ((0, 0), (0, tp - t)), constant_values=jnp.nan))
    which = (True, chain, chain)
    outs = _fill_linear_call_folded(y3, t, which, interpret)
    return tuple(_unfold(o, b)[:, :t] for o in outs)


@_scoped("pallas.fill_linear_chain")
def fill_linear_chain(y, *, interpret: bool = False):
    """Fused fillLinear -> (filled, lag-1 difference, lag-1 shift) on ``[B, T]``.

    Matches ``vmap(fill_linear)``, ``vmap(differences_at_lag(., 1))`` and
    ``vmap(lag(., 1))`` composed (same NaN semantics: edge NaNs survive the
    fill; position 0 of the difference and the shift is NaN).
    """
    return _fill_linear_call(y, True, interpret)


@_scoped("pallas.fill_linear")
def fill_linear(y, *, interpret: bool = False):
    """Batched linear-interpolation fill ``[B, T]`` on the fused kernel
    (fill output only — no difference/lag stores)."""
    return _fill_linear_call(y, False, interpret)[0]


# ---------------------------------------------------------------------------
# Fused Hannan-Rissanen moment kernels (forward-only, no adjoint)
# ---------------------------------------------------------------------------
#
# The ARIMA fit's startup values come from two weighted OLS stages
# (models.arima.hannan_rissanen).  Their normal equations need only masked
# lagged inner products of the series (and of the stage-1 residuals) — a
# handful of [B] moments.  The XLA construction (hannan_rissanen_batched)
# assembles them from ~30 shifted-elementwise-reduce passes over the panel;
# here each stage is ONE sweep with lag rings in VMEM and the moment
# accumulators in a revisited output block, after which XLA solves the tiny
# [k, k] systems.  Stage 2 recomputes the stage-1 residuals on the fly from
# beta1 (no [B, T] residual array ever lands in HBM).


def _hr_kernel(lag_y, lag_e, intercept, woff, beta_m, t_limit, cs, *refs):
    """Shared moment-sweep body.  Column streams at step t:
    ``[1 (if intercept), y_{t-1}..y_{t-lag_y}, e_{t-1}..e_{t-lag_e}]``
    where ``e`` is the AR(beta_m) residual (stage 2 only, ``lag_e > 0``).
    Accumulates sum(w * c_a * c_b) for a <= b and sum(w * c_a * y_t) with
    ``w = [zb + woff <= t < t_limit]``.

    Nothing here is recursive, so the whole chunk runs as full-tile VPU ops
    with STATIC time-axis slices (a per-step loop is bounded by loop
    machinery, not arithmetic); lag reads crossing the chunk boundary come
    from halo scratches holding the previous chunk's trailing tiles."""
    if lag_e:
        y_ref, zb_ref, beta_ref, acc_ref, yhalo_ref, ehalo_ref = refs
    else:
        y_ref, zb_ref, acc_ref, yhalo_ref = refs
        beta_ref = ehalo_ref = None
    c = pl.program_id(1)
    base = c * cs
    zb = zb_ref[0]
    ncols = int(intercept) + lag_y + lag_e
    nacc = ncols * (ncols + 1) // 2 + ncols
    ydepth = max(lag_y, beta_m, 1)
    edepth = max(lag_e, 1)

    @pl.when(c == 0)
    def _():
        for r_ in range(nacc):
            acc_ref[r_] = _ZERO()
        for j in range(ydepth):
            yhalo_ref[j] = _ZERO()  # values before the global start are 0
        if lag_e:
            for j in range(edepth):
                ehalo_ref[j] = _ZERO()

    y = y_ref[:]  # [cs, 8, 128]
    t_id = base + lax.broadcasted_iota(jnp.int32, (cs, 1, 1), 0)
    tf = t_id.astype(jnp.float32)
    w = ((tf >= zb + woff) & (t_id < t_limit)).astype(jnp.float32)

    def shifted(tile, halo_ref_, depth, k):
        """tile value at t - k (zero-filled before the global start)."""
        if k == 0:
            return tile
        top = jnp.stack([halo_ref_[depth - k + i] for i in range(k)])
        return jnp.concatenate([top, tile[: cs - k]], axis=0)

    cols = []
    if intercept:
        cols.append(None)  # the constant-1 stream, handled symbolically
    for i in range(1, lag_y + 1):
        cols.append(shifted(y, yhalo_ref, ydepth, i))
    if lag_e:
        # stage-1 residual stream (zero outside its own live window)
        w1 = ((tf >= zb + beta_m) & (t_id < t_limit)).astype(jnp.float32)
        pred = beta_ref[0][None]
        for i in range(1, beta_m + 1):
            pred = pred + beta_ref[i][None] * shifted(y, yhalo_ref, ydepth, i)
        ehat = w1 * (y - pred)
        for j in range(1, lag_e + 1):
            cols.append(shifted(ehat, ehalo_ref, edepth, j))

    def cval(a):
        return 1.0 if cols[a] is None else cols[a]

    r_ = 0
    for a in range(ncols):
        for b_ in range(a, ncols):
            prod = w if (cols[a] is None and cols[b_] is None) else (
                w * cval(b_) if cols[a] is None else
                (w * cval(a) if cols[b_] is None else w * cval(a) * cval(b_))
            )
            acc_ref[r_] = acc_ref[r_] + jnp.sum(prod, axis=0)
            r_ += 1
    for a in range(ncols):
        prod = w * y if cols[a] is None else w * cval(a) * y
        acc_ref[r_] = acc_ref[r_] + jnp.sum(prod, axis=0)
        r_ += 1

    # write halos AFTER all shifted() reads of the previous chunk's tiles
    for j in range(ydepth):
        yhalo_ref[j] = y[cs - ydepth + j]
    if lag_e:
        for j in range(edepth):
            ehalo_ref[j] = ehat[cs - edepth + j]


def _hr_moments(y3, zb3, t, cs, nchunk, nblk, lag_y, lag_e, intercept,
                woff, beta_m, beta3, interpret):
    ncols = int(intercept) + lag_y + lag_e
    nacc = ncols * (ncols + 1) // 2 + ncols
    ydepth = max(lag_y, beta_m, 1)
    ins = [_bs(cs, _cur), _bs(1, _fixed)]
    args = [y3, zb3]
    scratch = [pltpu.VMEM((ydepth, _SUBL, _LANES), jnp.float32)]
    if lag_e:
        ins.append(_bs(beta_m + 1, _fixed))
        args.append(beta3)
        scratch.append(pltpu.VMEM((max(lag_e, 1), _SUBL, _LANES), jnp.float32))
    return pl.pallas_call(
        functools.partial(_hr_kernel, lag_y, lag_e, intercept, woff, beta_m,
                          t, cs),
        grid=(nblk, nchunk),
        in_specs=ins,
        out_specs=_bs(nacc, _fixed),
        out_shape=jax.ShapeDtypeStruct((nacc, y3.shape[1], _LANES), jnp.float32),
        scratch_shapes=scratch,
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(*args)


def _solve_moments(acc, ncols, dtype, ridge=1e-8):
    """[B, nacc] moment rows -> ridge-stabilized OLS solutions [B, ncols]
    (the ONE stabilization rule: utils.linalg.ridge_solve)."""
    from ..utils.linalg import ridge_solve

    b = acc.shape[0]
    XtX = jnp.zeros((b, ncols, ncols), dtype)
    r_ = 0
    for a in range(ncols):
        for b_ in range(a, ncols):
            XtX = XtX.at[:, a, b_].set(acc[:, r_])
            if a != b_:
                XtX = XtX.at[:, b_, a].set(acc[:, r_])
            r_ += 1
    Xty = acc[:, r_ : r_ + ncols]
    return ridge_solve(XtX, Xty, ridge)


def hr_structural_ok(p: int, q: int) -> bool:
    """Ring depths must stay tiny (VMEM planes grow O((p+q)^2))."""
    return 0 <= p <= 8 and 0 <= q <= 8


def hr_init(yd, order: Order, include_intercept: bool, n_valid=None, *,
            interpret: bool = False):
    """Batched Hannan-Rissanen startup values ``[B, k]`` on fused kernels.

    Matches ``models.arima.hannan_rissanen_batched`` (identical weighted
    normal equations and ridge stabilization) in two panel sweeps: stage-1
    AR(m) moments -> solve -> stage-2 moments with on-the-fly residuals ->
    solve.  ``yd``: differenced panel with the invalid prefix zeroed.
    """
    b, t = yd.shape
    tp, _, _ = _time_layout(t)
    return hr_init_folded(_fold(jnp.pad(yd, ((0, 0), (0, tp - t)))), b, t,
                          order, include_intercept, n_valid,
                          interpret=interpret)


@_scoped("pallas.hr_init")
def hr_init_folded(y3, b: int, t: int, order: Order, include_intercept: bool,
                   n_valid=None, *, interpret: bool = False):
    """:func:`hr_init` from the already-folded panel of ``b`` series of
    true length ``t`` (:func:`css_prefold`'s first output — its extra zero
    at ``start - 1`` is never read by a weighted row), so one fit folds the
    panel exactly once and nothing reads it row-major."""
    p, _, q = order
    if not hr_structural_ok(p, q):
        raise ValueError(f"fused HR kernel supports p, q <= 8 (got {p}, {q})")
    n = t
    m = min(p + q + 1, max(n // 4, 1))
    nv = jnp.full((b,), n, jnp.int32) if n_valid is None else n_valid
    zb = (n - nv).astype(y3.dtype)
    _, cs, nchunk = _time_layout(t)
    zb3 = _fold(zb[:, None])
    nblk = y3.shape[1] // _SUBL

    acc1 = _hr_moments(y3, zb3, t, cs, nchunk, nblk, m, 0, True, m, 0, None,
                       interpret)
    beta1 = _solve_moments(_unfold(acc1, b), m + 1, y3.dtype)  # [B, m+1]

    ncols2 = int(include_intercept) + p + q
    if ncols2 == 0:
        return jnp.zeros((b, 0), y3.dtype)
    beta3 = _fold(beta1)
    acc2 = _hr_moments(y3, zb3, t, cs, nchunk, nblk, p, q, include_intercept,
                       m + q, m, beta3, interpret)
    return _solve_moments(_unfold(acc2, b), ncols2, y3.dtype)


# ---------------------------------------------------------------------------
# Fused multi-lag autocorrelation (forward-only transform, no adjoint)
# ---------------------------------------------------------------------------
#
# autocorr(num_lags) reads the panel once: d_t = valid ? x_t - mean : 0 is
# computed on the fly, the last ``num_lags`` d values stay in a VMEM ring,
# and num_lags+1 accumulators (lag products + denominator) land in a
# revisited output block — versus ~num_lags full-panel passes for the XLA
# lowering of the vmapped kernel (ops.univariate.autocorr).  The mean is a
# single cheap XLA reduction beforehand (it must complete before any
# product term, so fusing it would force a second sequential sweep anyway).


def _autocorr_kernel(nl, t_limit, cs, mean_inside, *refs):
    # autocorrelation has NO serial recursion, so the whole chunk runs as
    # full-tile VPU ops with STATIC time-axis slices — a per-step loop (even
    # with carried registers) is bounded by loop machinery, not arithmetic.
    # Cross-chunk lag pairs read the previous chunk's last nl centered
    # values from a halo scratch (static indices, touched once per chunk).
    # (A fold-free lane-layout variant — series on sublanes, time on lanes,
    # no transpose — was measured 2-3x SLOWER on a v5e: the misaligned lane
    # slices for the lag products relayout on every term, while this
    # layout's time-axis shifts are free register re-indexing.)
    if mean_inside:  # single-chunk: the tile IS the series; fuse the mean
        y_ref, acc_ref, halo_ref = refs
        mean = None
    else:
        y_ref, mean_ref, acc_ref, halo_ref = refs
        mean = mean_ref[0]
    c = pl.program_id(1)
    base = c * cs

    @pl.when(c == 0)
    def _():
        for r in range(nl + 1):
            acc_ref[r] = _ZERO()
        for j in range(nl):
            halo_ref[j] = _ZERO()  # d before the global start is zero

    y = y_ref[:]  # [cs, 8, 128]
    t_id = base + lax.broadcasted_iota(jnp.int32, (cs, 1, 1), 0)
    valid = (y == y) & (t_id < t_limit)
    if mean_inside:
        vf = valid.astype(jnp.float32)
        n = jnp.sum(vf, axis=0)
        mean = jnp.sum(jnp.where(valid, y, 0.0), axis=0) / jnp.maximum(n, 1.0)
    d = jnp.where(valid, y - mean, 0.0)
    acc_ref[0] = acc_ref[0] + jnp.sum(d * d, axis=0)
    for k_ in range(1, nl + 1):
        main = jnp.sum(d[k_:] * d[: cs - k_], axis=0)
        # boundary pairs: local t < k_ partners with halo[nl - k_ + t]
        bsum = _ZERO()
        for t_ in range(k_):
            bsum = bsum + d[t_] * halo_ref[nl - k_ + t_]
        acc_ref[k_] = acc_ref[k_] + main + bsum
    for j in range(nl):
        halo_ref[j] = d[cs - nl + j]


def _batch_autocorr_call(y3, b: int, t: int, num_lags: int, interpret: bool):
    if not 0 < num_lags < min(t, _CHUNK_T):
        raise ValueError(
            f"num_lags must be in (0, min(T, {_CHUNK_T})) = "
            f"(0, {min(t, _CHUNK_T)}), got {num_lags}"
        )
    tp, cs, nchunk = _time_layout(t)
    if y3.shape[0] != tp:
        raise ValueError(
            f"folded panel has time dim {y3.shape[0]}, layout wants {tp}"
        )
    mean_inside = nchunk == 1  # the tile holds the whole series: fuse the
    # mean into the kernel (saves one full XLA panel pass)
    args = [y3]
    ins = [_bs(cs, _cur)]
    if not mean_inside:
        t_ok = jnp.arange(tp)[:, None, None] < t
        valid = (y3 == y3) & t_ok
        n = jnp.sum(valid, axis=0)
        mean = jnp.sum(jnp.where(valid, y3, 0.0), axis=0) / jnp.maximum(n, 1)
        args.append(mean[None].astype(jnp.float32))
        ins.append(_bs(1, _fixed))
    nblk = y3.shape[1] // _SUBL
    acc3 = pl.pallas_call(
        functools.partial(_autocorr_kernel, num_lags, t, cs, mean_inside),
        grid=(nblk, nchunk),
        in_specs=ins,
        out_specs=_bs(num_lags + 1, _fixed),
        out_shape=jax.ShapeDtypeStruct(
            (num_lags + 1, y3.shape[1], _LANES), jnp.float32
        ),
        scratch_shapes=[pltpu.VMEM((num_lags, _SUBL, _LANES), jnp.float32)],
        compiler_params=_VMEM_PARAMS,
        interpret=interpret,
    )(*args)
    acc = _unfold(acc3, b)  # [B, num_lags + 1]
    return acc[:, 1:] / acc[:, :1]


@_scoped("pallas.batch_autocorr")
def batch_autocorr(y, num_lags: int, *, interpret: bool = False):
    """Batched sample autocorrelation ``[B, num_lags]`` on a fused kernel.

    Matches ``vmap(ops.univariate.autocorr)`` (valid-sample mean/denominator
    convention) to float tolerance.
    """
    b, t = y.shape
    tp, _, _ = _time_layout(t)
    y3 = _fold(jnp.pad(y, ((0, 0), (0, tp - t)), constant_values=jnp.nan))
    return _batch_autocorr_call(y3, b, t, num_lags, interpret)


@_scoped("pallas.batch_autocorr")
def batch_autocorr_folded(fp, num_lags: int, *, interpret: bool = False):
    """:func:`batch_autocorr` on a resident :class:`~.layout.FoldedPanel` —
    no per-dispatch layout conversion: the kernel streams the panel once
    (measured 79% of HBM peak vs 19% with the fold in the dispatch)."""
    return _batch_autocorr_call(fp.data, fp.b, fp.t, num_lags, interpret)


def hw_seeds(y, period: int, multiplicative: bool = False, n_valid=None):
    """Level/trend/seasonal-ring seeds for :func:`hw_prefold`.

    Returns ``(l0, t0, s0r, zb)``: the first-two-valid-seasons seed scheme
    shared with the scan path (``models.holtwinters._init_state`` — pallas/
    scan fit parity depends on these being identical), with the seasonal
    ring PRE-ROTATED for the kernel's ``t mod m`` indexing (scratch indices
    are scalar per block, ``zb`` is per row): seed element ``j`` sits at
    slot ``(start + j) mod m``, i.e. ``ring[p] = s0[(p - start) mod m]``.

    Seeds depend on the data only — they are constants of the fit objective.
    Compute them ONCE per fit and close over them: the vmapped dynamic
    slices lower to batched gathers, expensive enough at panel scale to
    dominate an objective evaluation if recomputed inside the optimizer.

    ``n_valid=None`` asserts a DENSE panel (every row starts at t=0): the
    per-row slices are then static and the whole computation vectorizes
    with no gathers — measured ~0.5 s of device time saved per 131k x 960
    fit versus the general path with a zero start vector.
    """
    m = period
    b, t = y.shape
    from ..models.holtwinters import _init_state

    if n_valid is None:  # dense: _init_state's static-slice path (no
        # gathers), identity ring rotation — one seeding scheme, one place
        l0, t0, s0 = jax.vmap(
            lambda yv: _init_state(yv, m, multiplicative, None)
        )(y)
        return l0, t0, s0, jnp.zeros((b,), y.dtype)
    start = (t - n_valid).astype(jnp.int32)

    l0, t0, s0 = jax.vmap(
        lambda yv, st: _init_state(yv, m, multiplicative, st)
    )(y, start)
    pos = (jnp.arange(m)[None, :] - start[:, None]) % m
    s0r = jnp.take_along_axis(s0, pos, axis=1)
    return l0, t0, s0r, start.astype(y.dtype)


def hw_prefold(y, seeds) -> HWFolded:
    """Fold a panel and its :func:`hw_seeds` into the Holt-Winters kernel
    layout ONCE -> the operand of :func:`hw_sse_folded`.

    The fit objective runs hundreds of evaluations inside ``lax.while_loop``
    bodies (the iteration loop and the line search in it), and XLA does not
    hoist the [B, T] pad + layout transpose out of them: a fit folds once,
    before its first start, and closes over the result."""
    l0, t0, s0r, zb = seeds
    t = y.shape[1]
    tp, _, _ = _time_layout(t)
    return HWFolded(
        _fold(jnp.pad(y, ((0, 0), (0, tp - t)))),
        _fold(l0[:, None].astype(y.dtype)),
        _fold(t0[:, None].astype(y.dtype)),
        _fold(s0r),
        _fold(zb.astype(y.dtype)[:, None]),
        t,
    )


@_scoped("pallas.hw_sse")
def hw_sse_folded(params, folded: HWFolded, period: int,
                  multiplicative: bool = False, *, interpret: bool = False):
    """Batched Holt-Winters one-step-ahead SSE ``[B]`` on a fused kernel
    from a pre-folded panel (:func:`hw_prefold`) — the fit-loop entry point.

    Matches ``models.holtwinters.sse`` (vmapped) for additive AND
    multiplicative seasonality with a right-aligned valid span (the invalid
    prefix of ``y`` must already be zeroed — ``base.align_right``).
    Differentiable in ``params``; data and seeds are constants of the
    objective.
    """
    m = period
    if not hw_structural_ok(m):
        raise ValueError(
            f"fused Holt-Winters kernel supports period <= {_CHUNK_T} "
            f"(got {m}); use backend='scan'"
        )
    return _hw_ss_f(interpret, m, multiplicative, params, folded)


def hw_sse_seeded(params, y, seeds, period: int,
                  multiplicative: bool = False, *, interpret: bool = False):
    """:func:`hw_sse_folded` on a natural-layout panel: folds per call.
    Inside an optimizer loop use :func:`hw_prefold` + :func:`hw_sse_folded`."""
    return hw_sse_folded(params, hw_prefold(y, seeds), period, multiplicative,
                         interpret=interpret)


def hw_sse(params, y, period: int, multiplicative: bool = False,
           n_valid=None, *, interpret: bool = False):
    """One-shot entry: compute seeds then the SSE (tests / single calls).
    Inside an optimizer loop use :func:`hw_seeds`, :func:`hw_prefold` and
    :func:`hw_sse_folded`."""
    if not hw_structural_ok(period):  # before seeds: a clear error, not a
        raise ValueError(             # dynamic_slice TypeError from the seed
            f"fused Holt-Winters kernel supports period <= {_CHUNK_T} "
            f"(got {period}); use backend='scan'"
        )
    seeds = hw_seeds(y, period, multiplicative, n_valid)
    return hw_sse_seeded(params, y, seeds, period, multiplicative,
                         interpret=interpret)


def hw_additive_sse(params, y, period: int, *, interpret: bool = False):
    """Additive dense-panel entry (kept for compatibility): see :func:`hw_sse`."""
    return hw_sse(params, y, period, False, None, interpret=interpret)
