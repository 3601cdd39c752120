"""Sequence (time-axis) parallelism — long-series support.

The reference never shards a single series: a series is one JVM vector, so
its maximum length is bounded by executor memory (SURVEY.md Section 5.7).
This module removes that bound: on a 2-D ``(series, time)`` mesh, one series'
``[time]`` axis is split across chips and within-series reductions and scans
are rebuilt from local work + ICI collectives under ``shard_map``:

- moments / autocovariance:  local partial sums + ``psum`` over the ``time``
  axis; lagged cross terms at shard boundaries come from a halo exchange
  (``ppermute`` of each shard's tail to its right neighbor) — a ring
  transfer over ICI, the time-series analog of ring attention's
  neighbor hand-off.
- prefix scans (cumsum — the integration step of differencing):  local scan
  + exclusive all-shard offset, computed via ``psum`` of masked shard totals
  (carry hand-off without serializing shards).

Every function here takes and returns arrays laid out ``[keys, time]`` and
is meant to be called under ``shard_map`` with spec
``P(SERIES_AXIS, TIME_AXIS)`` — see ``sp_*_sharded`` wrappers which bind the
mesh. On a 1-D mesh the plain kernels in ``ops.univariate`` are the right
tool; these exist for series too long for one chip's HBM.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..parallel.mesh import SERIES_AXIS, TIME_AXIS
from ..utils import compile_cache

Order = Tuple[int, int, int]


@contextlib.contextmanager
def _sp_fit_span(model: str, values):
    """Telemetry span for one time-sharded fit dispatch (ROADMAP: span
    coverage for the sharded fit paths).  Tagged as the chunk driver tags
    its ``chunk``, from ``compile_cache``'s build log: ``compile+execute``
    where a build closed on this thread inside the dispatch (the
    ``lru_cache``d ``_sp_*_fit_program`` builders trace on first use), with
    ``builds`` / ``build_s``; ``execute`` where it ran loaded programs.
    Free no-op when the plane is disabled."""
    if not obs.enabled():
        yield
        return
    mark = compile_cache.thread_builds()
    with obs.span("sp_fit", model=model, keys=int(values.shape[0]),
                  n_time=int(values.shape[1])) as sp:
        try:
            yield
        finally:
            sp.set(**compile_cache.built_since(mark))


# ---------------------------------------------------------------------------
# Inside-shard_map kernels (axis_name = TIME_AXIS)
# ---------------------------------------------------------------------------


def _axis_index():
    return lax.axis_index(TIME_AXIS)


def _axis_size():
    # a STATIC python int: several callers build ppermute tables with
    # range() over it
    return lax.axis_size(TIME_AXIS)


def sp_moments(block: jax.Array) -> Dict[str, jax.Array]:
    """NaN-aware per-series count/mean/var across a time-sharded axis.

    ``block``: this shard's ``[keys_local, time_local]`` slice.  Returns
    per-series ``[keys_local]`` stats, identical on every time shard.
    """
    valid = ~jnp.isnan(block)
    n = lax.psum(jnp.sum(valid, axis=1), TIME_AXIS)
    s = lax.psum(jnp.sum(jnp.where(valid, block, 0.0), axis=1), TIME_AXIS)
    mean = s / jnp.maximum(n, 1)
    ss = lax.psum(
        jnp.sum(jnp.where(valid, (block - mean[:, None]) ** 2, 0.0), axis=1), TIME_AXIS
    )
    var = ss / jnp.maximum(n - 1, 1)
    return {"count": n, "mean": mean, "var": var}


def _halo_from_left(block: jax.Array, halo: int) -> jax.Array:
    """Each shard receives the previous shard's last ``halo`` columns
    (zeros for the first shard) — the ring hand-off for lagged terms."""
    nshards = _axis_size()
    tail = block[:, -halo:]
    perm = [(i, (i + 1) % nshards) for i in range(nshards)]
    received = lax.ppermute(tail, TIME_AXIS, perm)
    first = _axis_index() == 0
    return jnp.where(first, jnp.zeros_like(received), received)


def sp_autocov(block: jax.Array, max_lag: int) -> jax.Array:
    """Autocovariance at lags 1..max_lag of time-sharded series.

    Cross-shard lagged products use a halo exchange of ``max_lag`` columns
    from the left neighbor.  Assumes no NaNs (fill first).  Returns
    ``[keys_local, max_lag]`` (plus the lag-0 variance as column 0 of the
    companion ``sp_autocorr``).
    """
    stats = sp_moments(block)
    d = block - stats["mean"][:, None]
    halo = _halo_from_left(d, max_lag)  # [k, max_lag] from left neighbor
    ext = jnp.concatenate([halo, d], axis=1)  # [k, max_lag + t_local]
    t_local = d.shape[1]
    covs = []
    for k in range(1, max_lag + 1):
        lagged = lax.dynamic_slice_in_dim(ext, max_lag - k, t_local, axis=1)
        # products whose lagged partner falls before the global start are
        # zero because the first shard's halo is zeroed
        covs.append(lax.psum(jnp.sum(d * lagged, axis=1), TIME_AXIS))
    return jnp.stack(covs, axis=1)


def sp_autocorr(block: jax.Array, max_lag: int) -> jax.Array:
    """Autocorrelation at lags 1..max_lag (matches ``univariate.autocorr``
    on unsharded data)."""
    stats = sp_moments(block)
    d = block - stats["mean"][:, None]
    denom = lax.psum(jnp.sum(d * d, axis=1), TIME_AXIS)
    return sp_autocov(block, max_lag) / denom[:, None]


def sp_cumsum(block: jax.Array) -> jax.Array:
    """Cumulative sum along a time-sharded axis (differencing inversion).

    Local cumsum + exclusive prefix of shard totals.  The prefix is computed
    collective-only: psum of shard totals masked to strictly-lower shard
    indices — no serialization across shards.
    """
    local = jnp.cumsum(block, axis=1)
    total = local[:, -1:]  # [k, 1] this shard's sum
    idx = _axis_index()
    nshards = _axis_size()
    # all_gather shard totals, then sum those before this shard
    gathered = lax.all_gather(total, TIME_AXIS, axis=1, tiled=True)  # [k, nshards]
    mask = jnp.arange(nshards) < idx
    offset = jnp.sum(jnp.where(mask[None, :], gathered, 0.0), axis=1, keepdims=True)
    return local + offset


def sp_differences(block: jax.Array, k_lag: int = 1) -> jax.Array:
    """Lag-k differencing across shard boundaries via halo exchange; the
    first ``k_lag`` global positions are NaN (matches
    ``univariate.differences_at_lag``)."""
    halo = _halo_from_left(block, k_lag)
    ext = jnp.concatenate([halo, block], axis=1)
    lagged = ext[:, : block.shape[1]]
    out = block - lagged
    # global positions < k_lag are NaN
    t0 = _axis_index() * block.shape[1]
    gpos = t0 + jnp.arange(block.shape[1])
    return jnp.where(gpos[None, :] < k_lag, jnp.nan, out)


def _affine_scan_sharded(m_elem: jax.Array, b_elem: jax.Array) -> jax.Array:
    """Inclusive scan of the affine recursion ``s_t = m_t * s_{t-1} + b_t``
    along a time-sharded axis, carry entering the global front = 0.

    Affine maps compose associatively, so BOTH levels parallelize: inside a
    shard a log-depth ``associative_scan`` over the (m, b) pairs, across
    shards one tiny fold of each shard's composed exit pair over the
    all-gathered values (generalizing :func:`sp_cumsum`'s offset trick to
    model recursions).  A global seed or dead prefix is encoded in the
    ELEMENTS (``m = 0`` cuts the incoming carry).
    """
    def comp(l, r):  # apply l then r: r(l(s)) = (rm*lm) s + (rb + rm*lb)
        lm, lb = l
        rm, rb = r
        return lm * rm, rb + rm * lb

    decay, p = lax.associative_scan(comp, (m_elem, b_elem), axis=1)
    # s_t = decay_t * s_in + p_t for the carry s_in entering this shard
    gm = lax.all_gather(decay[:, -1:], TIME_AXIS, axis=1, tiled=True)
    gb = lax.all_gather(p[:, -1:], TIME_AXIS, axis=1, tiled=True)

    def fold(c, mb):
        m, b = mb
        c = m * c + b
        return c, c

    _, carries = lax.scan(fold, jnp.zeros_like(gm[:, 0]), (gm.T, gb.T))
    carries = carries.T  # [k, nshards]: carry EXITING each shard
    idx = _axis_index()
    first = idx == 0
    entering = jnp.where(
        first, jnp.zeros_like(carries[:, 0]), carries[:, jnp.maximum(idx - 1, 0)]
    )
    return decay * entering[:, None] + p


def sp_ewma_smooth(block: jax.Array, alpha: jax.Array) -> jax.Array:
    """EWMA smoothing of time-sharded series (matches ``ewma.smooth`` on
    unsharded data; seeds ``s_0 = x_0``).

    Every step is the affine map ``s -> (1-a) s + a x_t`` (the global seed
    ``s_0 = x_0`` is just ``(0, x_0)``) — see :func:`_affine_scan_sharded`.
    ``alpha``: ``[keys_local]`` smoothing weights (one per series).

    Assumes dense data (fill first) — the seed position is global t = 0.
    """
    k, tl = block.shape
    a = alpha[:, None]
    first = _axis_index() == 0
    pos0 = jnp.arange(tl)[None, :] == 0
    seed = first & pos0  # global t = 0: s = x_0 regardless of the carry
    m_elem = jnp.where(seed, 0.0, jnp.broadcast_to(1.0 - a, (k, tl)))
    b_elem = jnp.where(seed, block, a * block)
    return _affine_scan_sharded(m_elem, b_elem)


def _shift1_from_left(block: jax.Array) -> jax.Array:
    """``x_{t-1}`` along the sharded time axis (global position 0 gets 0)."""
    halo = _halo_from_left(block, 1)
    return jnp.concatenate([halo, block], axis=1)[:, : block.shape[1]]


def _gpos(tl: int):
    """Global time positions of this shard's columns ``[1, tl]``."""
    return (_axis_index() * tl + jnp.arange(tl, dtype=jnp.int32))[None, :]


def sp_ewma_sse(block: jax.Array, alpha: jax.Array) -> jax.Array:
    """One-step-ahead EWMA SSE of time-sharded series ``[keys_local]``
    (matches ``ewma.sse`` on dense unsharded data): the distributed FIT
    objective — smoothing via the affine scan, the ``s_{t-1}`` lag via a
    1-column halo, the sum via ``psum`` over the time axis."""
    s = sp_ewma_smooth(block, alpha)
    sprev = _shift1_from_left(s)
    err = jnp.where(_gpos(block.shape[1]) >= 1, block - sprev, 0.0)
    return lax.psum(jnp.sum(err * err, axis=1), TIME_AXIS)


def sp_garch_neg_loglik(params: jax.Array, r: jax.Array, h0: jax.Array,
                        start: int = 0) -> jax.Array:
    """Gaussian GARCH(1,1) negative log-likelihood on a time-sharded dense
    returns panel -> ``[keys_local]`` (matches ``models.garch.
    neg_log_likelihood``).

    ``params``: ``[keys_local, 3]`` natural rows ``[omega, alpha, beta]``;
    ``h0``: ``[keys_local]`` per-series sample variance (the seed, which
    also stands in for the unobserved ``r_{start-1}^2``).  The variance
    recursion ``h_t = omega + alpha r^2_{t-1} + beta h_{t-1}`` is affine in
    the carry, so it runs as a log-depth :func:`_affine_scan_sharded`; the
    seed is folded into the element at global position ``start`` (a static
    dead prefix — ARGARCH excludes the first residual; positions before
    ``start`` contribute nothing).
    """
    omega = params[:, 0:1]
    alpha = params[:, 1:2]
    beta = params[:, 2:3]
    rsq = r * r
    rsq_prev = _shift1_from_left(rsq)
    gp = _gpos(r.shape[1])
    first = gp == start
    rsq_prev = jnp.where(first, h0[:, None], rsq_prev)
    b_elem = omega + alpha * rsq_prev
    # the seed step absorbs the carry: h_start = omega + (alpha + beta) h0
    b_elem = jnp.where(first, b_elem + beta * h0[:, None], b_elem)
    b_elem = jnp.where(gp < start, 0.0, b_elem)
    m_elem = jnp.where(gp <= start, 0.0, jnp.broadcast_to(beta, b_elem.shape))
    h = jnp.maximum(_affine_scan_sharded(m_elem, b_elem), 1e-12)
    ll_t = jnp.where(gp >= start, jnp.log(2.0 * jnp.pi * h) + rsq / h, 0.0)
    return 0.5 * lax.psum(jnp.sum(ll_t, axis=1), TIME_AXIS)


def _affine_scan_sharded_vec(A_elem: jax.Array, b_elem: jax.Array) -> jax.Array:
    """Vector generalization of :func:`_affine_scan_sharded`: inclusive scan
    of ``s_t = A_t s_{t-1} + b_t`` with ``s`` in R^q along a time-sharded
    axis, carry entering the global front = 0.

    ``A_elem``: ``[k, tl, q, q]``; ``b_elem``: ``[k, tl, q]``.  Affine maps
    on R^q compose associatively (``(A2, b2) o (A1, b1) =
    (A2 A1, b2 + A2 b1)``, O(q^3) per element — cheap for the small-q ARMA
    carries this serves), so both levels parallelize exactly as the scalar
    case: log-depth ``associative_scan`` in shard, one tiny fold of composed
    exit pairs across shards.
    """
    def comp(l, r):  # apply l then r
        lA, lb = l
        rA, rb = r
        return (jnp.einsum("...ij,...jk->...ik", rA, lA),
                rb + jnp.einsum("...ij,...j->...i", rA, lb))

    decay, pfx = lax.associative_scan(comp, (A_elem, b_elem), axis=1)
    gA = lax.all_gather(decay[:, -1:], TIME_AXIS, axis=1, tiled=True)
    gb = lax.all_gather(pfx[:, -1:], TIME_AXIS, axis=1, tiled=True)

    def fold(c, Ab):
        A, b = Ab
        c = jnp.einsum("...ij,...j->...i", A, c) + b
        return c, c

    _, carries = lax.scan(
        fold, jnp.zeros_like(gb[:, 0]),
        (jnp.moveaxis(gA, 1, 0), jnp.moveaxis(gb, 1, 0)),
    )
    carries = jnp.moveaxis(carries, 0, 1)  # [k, nshards, q]: carry EXITING
    idx = _axis_index()
    entering = jnp.where(
        idx == 0,
        jnp.zeros_like(carries[:, 0]),
        carries[:, jnp.maximum(idx - 1, 0)],
    )
    return jnp.einsum("ktij,kj->kti", decay, entering) + pfx


def _lags_from_left(block: jax.Array, nlags: int) -> list:
    """Columns ``x_{t-1} .. x_{t-nlags}`` along the sharded time axis via one
    ``nlags``-column halo exchange (positions reaching below global 0 are
    zero — the first shard's halo is zeroed)."""
    if nlags == 0:
        return []
    tl = block.shape[1]
    ext = jnp.concatenate([_halo_from_left(block, nlags), block], axis=1)
    return [lax.dynamic_slice_in_dim(ext, nlags - i, tl, axis=1)
            for i in range(1, nlags + 1)]


def sp_css_neg_loglik(params: jax.Array, yd: jax.Array, d_dead: int,
                      p: int = 1, q: int = 1) -> jax.Array:
    """Conditional-sum-of-squares negative log-likelihood of ARMA(p, q) with
    intercept on a time-sharded differenced panel -> ``[keys_local]``.

    ``params``: ``[keys_local, 1 + p + q]`` rows ``[c, phi_1..p,
    theta_1..q]``; ``yd``: this shard of the differenced series laid out on
    the ORIGINAL time grid with the first ``d_dead`` global positions zeroed
    (order-d differencing keeps shapes static by leaving a dead prefix).
    Matches ``models.arima.css_neg_loglik`` with order (p, 0, q) on the
    trimmed vector.

    The AR part ``u_t = yd_t - c - sum_i phi_i yd_{t-i}`` is recursion-free
    (a p-column halo).  The MA recursion ``e_t = u_t - sum_j theta_j
    e_{t-j}`` is affine in the carry ``s_t = (e_t .. e_{t-q+1})``: scalar
    for q = 1 (:func:`_affine_scan_sharded`), a companion-matrix carry for
    q > 1 (:func:`_affine_scan_sharded_vec`, O(q^3)-per-element composition
    — the VERDICT r4 general-order path).  Errors in the conditional
    prefix (the first p valid steps) are zeroed.
    """
    tl = yd.shape[1]
    c = params[:, 0:1]
    u = yd - c
    for i, lag in enumerate(_lags_from_left(yd, p), start=1):
        # lags reaching into the dead prefix read the zeros the grid keeps
        # there — exactly the zero-padded lags of the unsharded recursion
        u = u - params[:, i:i + 1] * lag
    live = _gpos(tl) >= d_dead + p  # dead prefix + conditional p-step zero
    if q == 0:
        e = jnp.where(live, u, 0.0)
    elif q == 1:
        theta = params[:, 1 + p:2 + p]
        m_elem = jnp.where(live, jnp.broadcast_to(-theta, u.shape), 0.0)
        b_elem = jnp.where(live, u, 0.0)
        e = _affine_scan_sharded(m_elem, b_elem)
    else:
        k = yd.shape[0]
        theta = params[:, 1 + p:1 + p + q]  # [k, q]
        # companion element: row 0 applies -theta, rows 1..q-1 shift
        row0 = jnp.broadcast_to(-theta[:, None, None, :], (k, tl, 1, q))
        rows = jnp.broadcast_to(
            jnp.eye(q, k=-1, dtype=yd.dtype)[1:][None, None],
            (k, tl, q - 1, q),
        )
        A_elem = jnp.where(live[..., None, None],
                           jnp.concatenate([row0, rows], axis=2), 0.0)
        b_elem = jnp.concatenate(
            [jnp.where(live, u, 0.0)[..., None],
             jnp.zeros((k, tl, q - 1), yd.dtype)], axis=-1,
        )
        e = _affine_scan_sharded_vec(A_elem, b_elem)[..., 0]
    css = lax.psum(jnp.sum(e * e, axis=1), TIME_AXIS)
    n = tl * _axis_size()
    n_eff = (n - d_dead) - p
    sigma2 = css / n_eff
    return 0.5 * n_eff * (jnp.log(2.0 * jnp.pi * sigma2) + 1.0)


def _sp_wols(cols, y2, w, ridge: float = 1e-8):
    """Weighted OLS across a time-sharded axis: the normal equations of
    ``models.arima._wols_cols`` with every Gram entry a ``psum``'d masked
    inner product, then the shared ridge-stabilized solve (replicated per
    time shard — a (k x k) solve per series is noise next to the panel
    reductions)."""
    from ..utils.linalg import ridge_solve

    XtX = jnp.stack(
        [jnp.stack([lax.psum(jnp.sum(w * ci * cj, axis=1), TIME_AXIS)
                    for cj in cols], -1) for ci in cols], -2,
    )  # [keys_local, k, k]
    Xty = jnp.stack(
        [lax.psum(jnp.sum(w * ci * y2, axis=1), TIME_AXIS) for ci in cols],
        -1,
    )
    return ridge_solve(XtX, Xty, ridge)


def sp_hannan_rissanen(ydb: jax.Array, d_dead: int, p: int, q: int,
                       n: int) -> jax.Array:
    """Distributed Hannan-Rissanen startup values ``[keys_local, 1+p+q]``
    (intercept first) on a time-sharded differenced panel.

    The REAL two-stage HR of ``models.arima.hannan_rissanen_batched`` —
    long-AR(m) OLS, residuals stand in for the innovations, one more OLS on
    ``[1, y-lags, e-lags]`` — not a Yule-Walker stand-in (VERDICT r4): every
    normal-equation moment is a psum'd masked product, the lag columns are
    halo exchanges, and the dead grid prefix reproduces the unsharded
    zero-padded lags exactly, so the weighted normal equations are
    identical to the unsharded ones.  ``n`` is the static global length.
    """
    n_trim = n - d_dead
    m = min(p + q + 1, max(n_trim // 4, 1))
    tl = ydb.shape[1]
    gp = _gpos(tl)
    ylag = _lags_from_left(ydb, max(m, p))
    ones = jnp.ones_like(ydb)

    # stage 1: AR(m) of yd on [1, lags 1..m] -> innovation estimates
    w1 = (gp >= d_dead + m).astype(ydb.dtype)
    cols1 = [ones] + ylag[:m]
    beta1 = _sp_wols(cols1, ydb, w1)
    pred = sum(beta1[:, j, None] * cj for j, cj in enumerate(cols1))
    ehat = (ydb - pred) * w1

    # stage 2: OLS of yd on [1, y-lags 1..p, e-lags 1..q]
    cols2 = [ones] + ylag[:p] + _lags_from_left(ehat, q)
    w2 = (gp >= d_dead + m + q).astype(ydb.dtype)
    return _sp_wols(cols2, ydb, w2)


def _carry_fold_across_shards(exit_v, exit_i, exit_f, reverse: bool):
    """Combine per-shard "latest valid (value, index)" summaries into the
    carry ENTERING each shard: a tiny fold over the all-gathered exits
    (``nshards`` elements per series), rightmost-valid-wins — or
    leftmost-valid-wins when walking ``reverse`` for the next-valid side."""
    # exits arrive as [k, 1] columns -> gathered [k, nshards] in shard order
    gv = lax.all_gather(exit_v, TIME_AXIS, axis=1, tiled=True)
    gi = lax.all_gather(exit_i, TIME_AXIS, axis=1, tiled=True)
    gf = lax.all_gather(exit_f, TIME_AXIS, axis=1, tiled=True)
    if reverse:
        gv, gi, gf = gv[:, ::-1], gi[:, ::-1], gf[:, ::-1]

    def fold(c, x):
        cv, ci, cf = c
        xv, xi, xf = x
        nv = jnp.where(xf, xv, cv)
        ni = jnp.where(xf, xi, ci)
        nf = xf | cf
        return (nv, ni, nf), (nv, ni, nf)

    _, (cv, ci, cf) = lax.scan(
        fold,
        (jnp.zeros_like(gv[:, 0]), jnp.zeros_like(gi[:, 0]),
         jnp.zeros_like(gf[:, 0])),
        (gv.T, gi.T, gf.T),
    )
    # carries[j] = combined summary of shards 0..j (walk order); entering
    # shard j is carries[j-1] (none for the walk's first shard)
    cv, ci, cf = cv.T, ci.T, cf.T  # [k, nshards]
    idx = _axis_index()
    nshards = _axis_size()
    pos = (nshards - 1 - idx) if reverse else idx
    first = pos == 0
    prev = jnp.maximum(pos - 1, 0)
    ev = jnp.where(first, jnp.zeros_like(cv[:, 0]), cv[:, prev])
    ei = jnp.where(first, jnp.zeros_like(ci[:, 0]), ci[:, prev])
    ef = jnp.where(first, False, cf[:, prev])
    return ev, ei, ef


def sp_fill_linear(block: jax.Array) -> jax.Array:
    """Linear-interpolation fill of time-sharded series (matches
    ``univariate.fill_linear`` on unsharded data: interior NaN gaps are
    interpolated between the GLOBAL bracketing valid points — which may live
    on other shards — and edge NaNs survive).

    Per shard: the gather-free prev/next-valid associative scans of the
    unsharded kernel run locally with global indices; each shard's exit
    summary (latest/earliest valid value + index) is all-gathered and folded
    into the entering carry — the prefix-combine trick of :func:`sp_cumsum`
    generalized to the "nearest valid observation" semigroup.
    """
    k, tl = block.shape
    idx = _axis_index()
    t0 = idx * tl
    # indices stay int32 end to end: f32 cannot represent positions beyond
    # 2^24, exactly the long-series regime this module exists for — only
    # the SMALL differences (t - prev_idx, span) are cast for the weights
    gpos = (t0 + jnp.arange(tl, dtype=jnp.int32))[None, :]
    valid = ~jnp.isnan(block)
    vals = jnp.where(valid, jnp.nan_to_num(block), 0.0)
    gidx = jnp.where(valid, jnp.broadcast_to(gpos, (k, tl)), 0)

    def comb(a, b):
        av, ai, af = a
        bv, bi, bf = b
        return (jnp.where(bf, bv, av), jnp.where(bf, bi, ai), af | bf)

    pv, pi, pf = lax.associative_scan(comb, (vals, gidx, valid), axis=1)
    nv, ni, nf = lax.associative_scan(comb, (vals, gidx, valid), axis=1, reverse=True)

    epv, epi, epf = _carry_fold_across_shards(
        pv[:, -1:], pi[:, -1:], pf[:, -1:], False
    )
    env, eni, enf = _carry_fold_across_shards(
        nv[:, :1], ni[:, :1], nf[:, :1], True
    )

    pv = jnp.where(pf, pv, epv[:, None])
    pi = jnp.where(pf, pi, epi[:, None])
    pf = pf | epf[:, None]
    nv = jnp.where(nf, nv, env[:, None])
    ni = jnp.where(nf, ni, eni[:, None])
    nf = nf | enf[:, None]

    interior = pf & nf
    span = jnp.maximum(ni - pi, 1).astype(block.dtype)
    w = (gpos - pi).astype(block.dtype) / span
    interp = pv * (1.0 - w) + nv * w
    nan = jnp.asarray(jnp.nan, block.dtype)
    return jnp.where(valid, block, jnp.where(interior, interp, nan))


def sp_fill_linear_chain(block: jax.Array):
    """Time-sharded fillLinear -> (filled, lag-1 difference, lag-1 shift):
    the distributed form of ``univariate.batch_fill_linear_chain`` (the lag
    crosses shard boundaries through a 1-column halo exchange)."""
    f = sp_fill_linear(block)
    halo = _halo_from_left(f, 1)
    lagged = jnp.concatenate([halo, f], axis=1)[:, : block.shape[1]]
    t0 = _axis_index() * block.shape[1]
    gpos = t0 + jnp.arange(block.shape[1])
    lagged = jnp.where(gpos[None, :] < 1, jnp.nan, lagged)
    return f, f - lagged, lagged


# ---------------------------------------------------------------------------
# Mesh-bound wrappers
# ---------------------------------------------------------------------------


def _bind(mesh: Mesh, fn, out_specs):
    spec = P(SERIES_AXIS, TIME_AXIS)
    return shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=out_specs)


def sp_autocorr_sharded(mesh: Mesh, values: jax.Array, max_lag: int) -> jax.Array:
    """``[keys, time]`` (sharded on a 2-D mesh) -> ``[keys, max_lag]``."""
    fn = _bind(mesh, functools.partial(sp_autocorr, max_lag=max_lag), P(SERIES_AXIS, None))
    return jax.jit(fn)(values)


def sp_moments_sharded(mesh: Mesh, values: jax.Array) -> Dict[str, jax.Array]:
    fn = _bind(mesh, sp_moments, {k: P(SERIES_AXIS) for k in ("count", "mean", "var")})
    return jax.jit(fn)(values)


def sp_cumsum_sharded(mesh: Mesh, values: jax.Array) -> jax.Array:
    fn = _bind(mesh, sp_cumsum, P(SERIES_AXIS, TIME_AXIS))
    return jax.jit(fn)(values)


def sp_differences_sharded(mesh: Mesh, values: jax.Array, k_lag: int = 1) -> jax.Array:
    fn = _bind(mesh, functools.partial(sp_differences, k_lag=k_lag), P(SERIES_AXIS, TIME_AXIS))
    return jax.jit(fn)(values)


def sp_fill_linear_sharded(mesh: Mesh, values: jax.Array) -> jax.Array:
    fn = _bind(mesh, sp_fill_linear, P(SERIES_AXIS, TIME_AXIS))
    return jax.jit(fn)(values)


def sp_fill_linear_chain_sharded(mesh: Mesh, values: jax.Array):
    fn = _bind(mesh, sp_fill_linear_chain, (P(SERIES_AXIS, TIME_AXIS),) * 3)
    return jax.jit(fn)(values)


def sp_ewma_smooth_sharded(mesh: Mesh, values: jax.Array, alpha: jax.Array) -> jax.Array:
    """EWMA smoothing of a ``[keys, time]`` panel time-sharded on a 2-D mesh;
    ``alpha``: ``[keys]``."""
    fn = shard_map(
        sp_ewma_smooth,
        mesh=mesh,
        in_specs=(P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS)),
        out_specs=P(SERIES_AXIS, TIME_AXIS),
    )
    return jax.jit(fn)(values, alpha)


# ---------------------------------------------------------------------------
# Time-sharded model FITS (SURVEY.md §5.7 stretch: the reference cannot fit
# a series longer than one executor's memory; here the fit OBJECTIVE itself
# runs on the 2-D mesh, so the optimizer never materializes a whole series)
#
# Family boundary: EWMA, ARMA(1,d,1) CSS, GARCH, and ARGARCH all have
# SCALAR affine carries, so their recursions parallelize as log-depth
# associative scans with O(1) state per element.  Holt-Winters' carry is
# (level, trend, seasonal ring) — dimension m + 2 — and composing affine
# maps on R^(m+2) costs O(m^2) memory per scan element (~676 floats at
# m = 24): time-sharding it would cost far more than it saves, so HW
# long-series fits stay series-sharded by design.
# ---------------------------------------------------------------------------


def _too_short_program(k: int):
    """NaN / not-converged ``FitResult`` with ``params [keys, k]`` for panels
    statically too short to identify a model — the identifiability gates are
    decided at program-build time (panel length is static), so the too-short
    case never pays the distributed L-BFGS (ADVICE r4)."""
    from ..models.base import FitResult

    @jax.jit
    def too_short(vals):
        b = vals.shape[0]
        return FitResult(
            jnp.full((b, k), jnp.nan, vals.dtype),
            jnp.full((b,), jnp.nan, vals.dtype),
            jnp.zeros((b,), bool),
            jnp.zeros((b,), jnp.int32),
        )

    return too_short


@functools.lru_cache(maxsize=64)
def _sp_ewma_fit_program(mesh: Mesh, n: int, max_iters: int, tol: float):
    """One compiled distributed-fit program per (mesh, length, budget) —
    the ``jit_program`` discipline (``models.base``): without this every
    call would re-trace and re-compile the whole distributed L-BFGS."""
    from ..models.base import FitResult
    from ..utils import optim

    sse_sh = shard_map(
        sp_ewma_sse, mesh=mesh,
        in_specs=(P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS)),
        out_specs=P(SERIES_AXIS),
    )
    n_eff = float(max(n - 1, 1))

    @jax.jit
    def run(vals):
        def fb(u):
            alpha = optim.sigmoid_to_interval(u[:, 0], 0.0, 1.0)
            return sse_sh(vals, alpha) / n_eff

        u0 = jnp.zeros((vals.shape[0], 1), vals.dtype)
        res = optim.minimize_lbfgs_batched(fb, u0, max_iters=max_iters, tol=tol)
        alpha = optim.sigmoid_to_interval(res.x, 0.0, 1.0)
        return FitResult(alpha, res.f * n_eff, res.converged, res.iters)

    return run


def sp_ewma_fit(mesh: Mesh, values: jax.Array, *, max_iters: int = 40,
                tol: float | None = None):
    """Fit EWMA ``alpha`` per series on a time-sharded dense panel.

    Matches ``models.ewma.fit`` (dense case) to optimizer tolerance: the
    same sigmoid-transformed mean-SSE objective and batched L-BFGS, with
    every objective/gradient evaluation a ``shard_map`` program over the
    2-D mesh (collectives ride ICI).  Returns a ``FitResult`` with
    ``params [keys, 1]``.
    """
    if tol is None:  # same dtype-dependent default as models.ewma.fit
        tol = 1e-8 if values.dtype == jnp.float64 else 1e-4
    with _sp_fit_span("ewma", values):
        return _sp_ewma_fit_program(
            mesh, values.shape[1], max_iters, float(tol)
        )(values)


@functools.lru_cache(maxsize=64)
def _sp_garch_fit_program(mesh: Mesh, n: int, max_iters: int, tol: float):
    """One compiled distributed GARCH-fit program per configuration (see
    :func:`_sp_ewma_fit_program`)."""
    from ..models import garch as _garch
    from ..models.base import FitResult
    from ..utils import optim

    if n < 10:
        # same identifiability gate as models.garch.fit (nv >= 10), decided
        # at program-build time (n is static): short panels come back
        # NaN / not-converged WITHOUT paying the distributed L-BFGS
        return _too_short_program(3)

    spec2, spec1 = P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS)

    def var_local(rb):
        # population variance (the dense-case seed, models.garch.variances)
        mean = lax.psum(jnp.sum(rb, axis=1), TIME_AXIS) / n
        return lax.psum(jnp.sum((rb - mean[:, None]) ** 2, axis=1),
                        TIME_AXIS) / n

    var_sh = shard_map(var_local, mesh=mesh, in_specs=(spec2,),
                       out_specs=spec1)
    nll_sh = shard_map(
        sp_garch_neg_loglik, mesh=mesh,
        in_specs=(P(SERIES_AXIS, None), spec2, spec1),
        out_specs=spec1,
    )

    @jax.jit
    def run(vals):
        var0 = var_sh(vals)
        nat0 = jnp.stack(
            [0.1 * jnp.maximum(var0, 1e-10), jnp.full_like(var0, 0.1),
             jnp.full_like(var0, 0.8)], axis=1,
        )
        u0 = jax.vmap(_garch._from_natural)(nat0)

        def fb(u):
            nat = jax.vmap(_garch._to_natural)(u)
            return nll_sh(nat, vals, var0) / n

        res = optim.minimize_lbfgs_batched(fb, u0, max_iters=max_iters,
                                           tol=tol)
        nat = jax.vmap(_garch._to_natural)(res.x)
        return FitResult(nat, res.f * n, res.converged, res.iters)

    return run


def sp_garch_fit(mesh: Mesh, values: jax.Array, *, max_iters: int = 80,
                 tol: float | None = None):
    """Fit GARCH(1,1) per series on a time-sharded dense returns panel ->
    ``FitResult`` with natural ``params [keys, 3]`` (omega, alpha, beta).

    Same transform-parameterized mean-NLL objective and batched L-BFGS as
    ``models.garch.fit`` (dense case), with every evaluation a
    ``shard_map`` program on the 2-D mesh via :func:`sp_garch_neg_loglik`.
    """
    if tol is None:  # same dtype-dependent default as models.garch.fit
        tol = 1e-7 if values.dtype == jnp.float64 else 1e-4
    with _sp_fit_span("garch", values):
        return _sp_garch_fit_program(
            mesh, values.shape[1], max_iters, float(tol)
        )(values)


@functools.lru_cache(maxsize=64)
def _sp_argarch_fit_program(mesh: Mesh, n: int, max_iters: int, tol: float):
    """One compiled distributed ARGARCH-fit program per configuration (see
    :func:`_sp_ewma_fit_program`)."""
    from ..models import garch as _garch
    from ..models.base import FitResult
    from ..utils import optim

    if n < 12:
        # AR(1) + GARCH needs a few more rows than GARCH alone; decided at
        # program-build time (n is static) so the too-short case never pays
        # the distributed L-BFGS (ADVICE r4)
        return _too_short_program(5)

    spec2, spec1 = P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS)

    def init_local(yb):
        # AR(1) moments (matches models.garch._fit_argarch_program, dense)
        mean = lax.psum(jnp.sum(yb, axis=1), TIME_AXIS) / n
        yc = yb - mean[:, None]
        ycprev = _shift1_from_left(yc)
        num = lax.psum(jnp.sum(yc * ycprev, axis=1), TIME_AXIS)
        den = lax.psum(jnp.sum(yc * yc, axis=1), TIME_AXIS)
        phi0 = jnp.clip(num / jnp.maximum(den, 1e-12), -0.95, 0.95)
        c0 = mean * (1.0 - phi0)
        prev = _shift1_from_left(yb)
        gp = _gpos(yb.shape[1])
        r = jnp.where(gp < 1, 0.0, yb - c0[:, None] - phi0[:, None] * prev)
        rvar = lax.psum(jnp.sum(r * r, axis=1), TIME_AXIS) / n
        return jnp.stack(
            [c0, phi0, 0.1 * jnp.maximum(rvar, 1e-8),
             jnp.full_like(c0, 0.1), jnp.full_like(c0, 0.8)], axis=1)

    def nll_local(nat, yb, prev):
        # ``prev`` (the 1-column lag halo, a ppermute) is loop-invariant and
        # hoisted by the caller: XLA does not reliably lift collectives out
        # of the optimizer's while_loop body (same lesson as css_prefold)
        c, phi = nat[:, 0:1], nat[:, 1:2]
        gp = _gpos(yb.shape[1])
        live = (gp >= 1).astype(yb.dtype)
        r = jnp.where(gp < 1, 0.0, yb - c - phi * prev)
        # masked population variance of the residuals over t >= 1 — the
        # GARCH seed is recomputed from the CURRENT (c, phi) every
        # evaluation, exactly as the unsharded objective does
        nv = n - 1
        mean = lax.psum(jnp.sum(r * live, axis=1), TIME_AXIS) / nv
        h0 = lax.psum(jnp.sum(live * (r - mean[:, None]) ** 2, axis=1),
                      TIME_AXIS) / nv
        return sp_garch_neg_loglik(nat[:, 2:], r, h0, start=1)

    init_sh = shard_map(init_local, mesh=mesh, in_specs=(spec2,),
                        out_specs=spec1)
    prev_sh = shard_map(_shift1_from_left, mesh=mesh, in_specs=(spec2,),
                        out_specs=spec2)
    nll_sh = shard_map(nll_local, mesh=mesh,
                       in_specs=(P(SERIES_AXIS, None), spec2, spec2),
                       out_specs=spec1)
    n_eff = float(max(n - 1, 1))

    @jax.jit
    def run(vals):
        nat0 = init_sh(vals)
        u0 = jax.vmap(_garch._argarch_from_natural)(nat0)
        prev = prev_sh(vals)

        def fb(u):
            nat = jax.vmap(_garch._argarch_to_natural)(u)
            return nll_sh(nat, vals, prev) / n_eff

        res = optim.minimize_lbfgs_batched(fb, u0, max_iters=max_iters,
                                           tol=tol)
        nat = jax.vmap(_garch._argarch_to_natural)(res.x)
        return FitResult(nat, res.f * n_eff, res.converged, res.iters)

    return run


def sp_argarch_fit(mesh: Mesh, values: jax.Array, *, max_iters: int = 100,
                   tol: float | None = None):
    """Fit AR(1)+GARCH(1,1) per series on a time-sharded dense panel ->
    ``FitResult`` with natural ``params [keys, 5]``
    ``[c, phi, omega, alpha, beta]``.

    Same transform-parameterized mean-NLL objective and batched L-BFGS as
    ``models.garch.fit_argarch`` (dense case): the AR(1) mean removal is a
    1-column halo, the GARCH seed is a psum'd masked variance of the
    current residuals, and the variance recursion runs as the log-depth
    affine scan of :func:`sp_garch_neg_loglik` with its first residual
    excluded (``start=1``).
    """
    if tol is None:  # same dtype-dependent default as models.garch.fit_argarch
        tol = 1e-7 if values.dtype == jnp.float64 else 1e-4
    with _sp_fit_span("argarch", values):
        return _sp_argarch_fit_program(
            mesh, values.shape[1], max_iters, float(tol)
        )(values)


@functools.lru_cache(maxsize=64)
def _sp_arima_fit_program(mesh: Mesh, n: int, order: tuple, max_iters: int,
                          tol: float):
    """One compiled distributed ARIMA-fit program per configuration (see
    :func:`_sp_ewma_fit_program`)."""
    from ..models.base import FitResult
    from ..utils import optim

    p, d, q = order
    k = 1 + p + q
    nvd = n - d
    # same identifiability gate as models.arima.fit (self-initialized
    # branch), decided at program-build time: lags + dof for the CSS fit,
    # plus enough span that HR's long-AR order m equals p+q+1
    if nvd < max(p + q + max(p + q + 1, 1) + k + 2, 4 * (p + q + 1)):
        return _too_short_program(k)

    # a halo exchange delivers at most ONE neighbor's columns, so every lag
    # reach (AR lags, HR's long-AR order m, HR's e-lags) must fit inside a
    # single shard — checkable at program-build time (all static)
    tl = n // mesh.shape[TIME_AXIS]
    m = min(p + q + 1, max(nvd // 4, 1))
    if max(m, p, q) > tl:
        raise ValueError(
            f"time-shard length {tl} is shorter than the longest lag reach "
            f"{max(m, p, q)} for order {order}; use fewer time shards or a "
            "longer panel"
        )

    spec2, spec1 = P(SERIES_AXIS, TIME_AXIS), P(SERIES_AXIS)

    def diff_dead(v):
        # order-d differencing on the original grid: position t holds
        # yd_t = sum_j (-1)^j C(d,j) y_{t-j}; the first d positions are dead
        for _ in range(d):
            prev = _shift1_from_left(v)
            v = v - prev
        return jnp.where(_gpos(v.shape[1]) >= d, v, 0.0)

    diff_sh = shard_map(diff_dead, mesh=mesh, in_specs=(spec2,),
                        out_specs=spec2)
    init_sh = shard_map(
        functools.partial(sp_hannan_rissanen, d_dead=d, p=p, q=q, n=n),
        mesh=mesh, in_specs=(spec2,), out_specs=spec1,
    )
    nll_sh = shard_map(
        functools.partial(sp_css_neg_loglik, d_dead=d, p=p, q=q), mesh=mesh,
        in_specs=(P(SERIES_AXIS, None), spec2),
        out_specs=spec1,
    )
    n_eff = float(max(nvd - p, 1))

    @jax.jit
    def run(vals):
        yd = diff_sh(vals)
        p0 = init_sh(yd)

        def fb(params):
            return nll_sh(params, yd) / n_eff

        res = optim.minimize_lbfgs_batched(fb, p0, max_iters=max_iters, tol=tol)
        return FitResult(res.x, res.f * n_eff, res.converged, res.iters)

    return run


def sp_arima_fit(mesh: Mesh, values: jax.Array, order: Order = (1, 1, 1), *,
                 max_iters: int = 60, tol: float | None = None):
    """Fit ARIMA(p, d, q) with intercept per series on a time-sharded dense
    panel -> ``FitResult`` with ``params [keys, 1+p+q]`` rows
    ``[c, phi_1..p, theta_1..q]``.

    The headline model family, time-sharded end to end for any small order
    (VERDICT r4): order-d differencing (halo exchanges, dead prefix kept on
    the grid), the REAL two-stage Hannan-Rissanen init from psum'd normal
    equations (:func:`sp_hannan_rissanen`), then batched L-BFGS on
    :func:`sp_css_neg_loglik` — every evaluation one ``shard_map`` program
    whose MA recursion is a log-depth (companion-matrix for q > 1) affine
    scan.  Matches ``models.arima.fit`` backends to optimizer tolerance on
    the same panel (both minimize the identical CSS objective).  Panels too
    short for the order come back NaN / not-converged without paying the
    optimizer (same gate as the unsharded fit).
    """
    if tol is None:  # same dtype-dependent default as models.arima.fit
        tol = 1e-6 if values.dtype == jnp.float64 else 1e-4
    with _sp_fit_span("arima", values):
        return _sp_arima_fit_program(
            mesh, values.shape[1], tuple(order), max_iters, float(tol)
        )(values)
