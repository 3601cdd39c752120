"""GARCH(1,1) and AR(1)+GARCH(1,1) volatility models (L4).

Rebuild of the reference's ``sparkts/models/GARCH.scala`` (SURVEY.md
Section 2.2, upstream path unverified).  Variance recursion
``h_t = omega + alpha * r_{t-1}^2 + beta * h_{t-1}`` with Gaussian
log-likelihood; the reference maximizes per series with Commons-Math
gradient ascent/CG.  Here the likelihood is a ``lax.scan`` and the
constraints (omega > 0, alpha, beta >= 0, alpha + beta < 1) are enforced by
a softplus/sigmoid reparameterization through the shared vmapped L-BFGS
(the BOBYQA-replacement strategy, SURVEY.md Section 7).

Parameter layouts (natural space):
- GARCH:   ``[omega, alpha, beta]``
- ARGARCH: ``[c, phi, omega, alpha, beta]``
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import optim
from . import lockstep
from .base import (FitResult, align_right, debatch, debatch_fit,
                   ensure_batched, jit_program, maybe_align,
                   require_pallas_for_count_evals, resolve_align_mode,
                   resolve_backend)


# -- transforms -------------------------------------------------------------


def _to_natural(u):
    """R^3 -> constrained (omega, alpha, beta)."""
    omega = jax.nn.softplus(u[0]) + 1e-12
    persistence = jax.nn.sigmoid(u[1]) * (1.0 - 1e-6)  # alpha + beta
    frac = jax.nn.sigmoid(u[2])  # alpha share
    alpha = persistence * frac
    beta = persistence * (1.0 - frac)
    return jnp.stack([omega, alpha, beta])


def _from_natural(params):
    omega, alpha, beta = params[0], params[1], params[2]
    u0 = optim.softplus_inverse(omega)
    pers = jnp.clip(alpha + beta, 1e-6, 1.0 - 1e-6)
    u1 = optim.interval_to_sigmoid(pers, 0.0, 1.0)
    u2 = optim.interval_to_sigmoid(alpha / pers, 0.0, 1.0)
    return jnp.stack([u0, u1, u2])


# -- likelihood -------------------------------------------------------------


def _variance_scan(params, h0, r_sq_prev):
    """The single GARCH recursion used everywhere:
    h_t = omega + alpha * r_sq_prev_t + beta * h_{t-1}."""
    omega, alpha, beta = params[0], params[1], params[2]

    def step(h, rt_prev_sq):
        h = omega + alpha * rt_prev_sq + beta * h
        return h, h

    _, h = lax.scan(step, h0, r_sq_prev)
    return h


def _unconditional_var(params):
    return params[0] / jnp.maximum(1.0 - params[1] - params[2], 1e-6)


def _masked_var(r, n_valid):
    """Variance over the right-aligned valid span."""
    t = jnp.arange(r.shape[0])
    m = (t >= r.shape[0] - n_valid).astype(r.dtype)
    n = jnp.maximum(n_valid, 1)
    mean = jnp.sum(r * m) / n
    return jnp.sum(m * (r - mean) ** 2) / n


def variances(params, r, n_valid=None):
    """Conditional variances h_t (h_0 = sample variance of r, which also
    stands in for the unobserved r_{-1}^2).

    ``n_valid`` marks a right-aligned valid span (``base.align_right``): the
    recursion holds h = h_0 through the zero prefix and seeds at the first
    valid step exactly as the full-series recursion seeds at t=0.
    """
    if n_valid is None:
        h0 = jnp.var(r)
        return _variance_scan(params, h0, jnp.concatenate([h0[None], r[:-1] ** 2]))

    h0 = _masked_var(r, n_valid)
    start = r.shape[0] - n_valid
    t = jnp.arange(r.shape[0])
    r_sq_prev = jnp.where(
        t == start, h0, jnp.concatenate([jnp.zeros((1,), r.dtype), r[:-1] ** 2])
    )
    omega, alpha, beta = params[0], params[1], params[2]

    def step(h, inp):
        rsq, ti = inp
        h = jnp.where(ti < start, h0, omega + alpha * rsq + beta * h)
        return h, h

    _, h = lax.scan(step, h0, (r_sq_prev, t))
    return h


def log_likelihood(params, r, n_valid=None):
    """Gaussian log-likelihood of returns under the variance recursion
    (summed over the valid span when ``n_valid`` is given)."""
    h = variances(params, r, n_valid)
    h = jnp.maximum(h, 1e-12)
    ll_t = jnp.log(2.0 * jnp.pi * h) + (r * r) / h
    if n_valid is not None:
        ll_t = jnp.where(jnp.arange(r.shape[0]) >= r.shape[0] - n_valid, ll_t, 0.0)
    return -0.5 * jnp.sum(ll_t)


def neg_log_likelihood(params, r, n_valid=None):
    return -log_likelihood(params, r, n_valid)


# -- fitting ----------------------------------------------------------------


def fit(r, *, max_iters: int = 80, tol: Optional[float] = None,
        backend: str = "auto", count_evals: bool = False,
        compact: bool = True, align_mode: Optional[str] = None,
        init_params: Optional[jax.Array] = None) -> FitResult:
    """Fit GARCH(1,1) per series -> natural params ``[batch?, 3]``.

    ``init_params`` (natural ``[omega, alpha, beta]``, ``[3]`` or
    ``[batch, 3]``: what an earlier ``FitResult.params`` holds) starts each
    row there instead of at the moment start (``omega = 0.1 var``, ``alpha``
    0.1, ``beta`` 0.8) — the retry ladder's continuation and a warm refit's
    start.  A row whose init is not finite or not a GARCH point (``omega <=
    0``, a negative ``alpha`` or ``beta``, ``alpha + beta >= 1``) takes the
    moment start, so a batch may mix both.  Without the keyword the
    programs are the ones that have no such operand.

    ``count_evals=True`` (pallas backend only) returns ``(FitResult, info)``
    with the optimizer's pass-accounting dict (``utils.optim``); the fit
    that is counted is the fit that runs without the flag.

    ``compact=False`` disables straggler compaction for run-to-run
    reproducibility (it engages on the pallas backend at batches >=
    ``utils.optim.COMPACT_MIN_BATCH`` = 4096 and is a different compiled
    program — bitwise outputs can differ from the uncompacted run).

    ``align_mode`` is the static alignment hint (``base.resolve_align_mode``)
    the chunk driver threads through sliced walks to skip the per-chunk NaN
    probe; a hint too strong for the data flags the violating rows
    (DIVERGED / EXCLUDED) instead of silently misfitting them.
    ``FitResult.status`` carries per-row ``reliability.FitStatus`` codes."""
    rb, single = ensure_batched(r)
    if tol is None:
        tol = 1e-7 if rb.dtype == jnp.float64 else 1e-4
    backend = resolve_backend(backend, rb.dtype, rb.shape[1])
    require_pallas_for_count_evals(count_evals, backend)
    align_mode = resolve_align_mode(rb, align_mode)
    static = (max_iters, float(tol), backend)
    # the programs with the start as an operand are named only where one
    # is given: without it the lookups are what they were
    args, init = (rb,), ()
    if init_params is not None:
        args, init = (rb, jnp.asarray(init_params)), (True,)
    out = lockstep.fit(
        args, backend=backend, compact=compact, max_iters=max_iters,
        inline=lambda: _fit_program(*static, align_mode, count_evals,
                                    compact, *init),
        stage1=lambda: _fit_stage1_program(*static, align_mode, count_evals,
                                           *init),
        stage2=lambda: _fit_stage2_program(*static),
        **_garch_kernel_attrs(rb.shape[1]))
    return debatch_fit(out, single, count_evals)


def _garch_kernel_attrs(t, mean_in_kernel=None):
    """What the stage spans say of the GARCH kernels (``lockstep.fit``'s
    ``series_block`` / ``stage_attrs``); ``mean_in_kernel`` (``None``: no
    mean equation): of a fit with one, formed in the calls or in XLA."""
    from ..ops import pallas_kernels as pk

    mean = {} if mean_in_kernel is None else {
        "mean_terms": ARGARCH_MEAN_TERMS,
        "mean_panel_moves": (ARGARCH_MEAN_PANEL_MOVES if mean_in_kernel
                             else _XLA_RETURNS_PANEL_MOVES)}
    block = (pk.argarch_series_block if mean_in_kernel
             else pk.garch_series_block)
    return {"series_block": lambda rows, mode: block(rows, t, mode),
            "stage_attrs": {"adjoint_panels": pk.GARCH_ADJOINT_PANELS,
                            **mean}}


def _start_from(init, nat0):
    """``init`` ``[B, 3]`` natural where a row's is a GARCH point (finite,
    ``omega > 0``, ``alpha, beta >= 0``, ``alpha + beta < 1``: what
    :func:`_from_natural`'s clips keep finite), the start ``nat0`` where it
    is not."""
    omega, alpha, beta = init[:, 0], init[:, 1], init[:, 2]
    usable = (jnp.all(jnp.isfinite(init), axis=1) & (omega > 0.0)
              & (alpha >= 0.0) & (beta >= 0.0) & (alpha + beta < 1.0))
    return jnp.where(usable[:, None], init, nat0)


def _garch_family(backend, align_mode=None,
                  has_init: bool = False) -> lockstep.Family:
    from ..ops import pallas_kernels as pk

    def prep(rb, init_params=None):
        ra, nv = maybe_align(rb, align_mode)
        # moment-ish start: omega = 0.1*var, alpha = 0.1, beta = 0.8
        var0 = jax.vmap(_masked_var)(ra, nv)
        nat0 = jnp.stack(
            [0.1 * jnp.maximum(var0, 1e-10), jnp.full_like(var0, 0.1),
             jnp.full_like(var0, 0.8)], axis=1
        )
        if has_init:
            nat0 = _start_from(
                jnp.broadcast_to(init_params, nat0.shape).astype(nat0.dtype),
                nat0)
        u0 = jax.vmap(_from_natural)(nat0)
        n_eff = jnp.maximum(nv, 1).astype(ra.dtype)
        folded = ()
        if backend in lockstep.PALLAS:
            folded = pk.garch_prefold(ra, nv)
        # GARCH needs a handful of observations to identify
        return lockstep.Prepared((u0,), nv >= 10, n_eff, (ra, nv), folded)

    def objective(folded, _):
        return lambda u: pk.garch_neg_loglik_folded(
            jax.vmap(_to_natural)(u), folded,
            interpret=backend == "pallas-interpret")

    def scan_objective(u, data):
        rv, n = data
        return neg_log_likelihood(_to_natural(u), rv, n)

    return lockstep.Family(backend, prep, objective, scan_objective,
                           jax.vmap(_to_natural))


@jit_program
def _fit_program(max_iters, tol, backend, align_mode="general",
                 count_evals=False, compact=True, has_init=False):
    return lockstep.fit_program(
        _garch_family(backend, align_mode, has_init), max_iters, tol,
        count_evals, compact)


@jit_program
def _fit_stage1_program(max_iters, tol, backend, align_mode="general",
                        count_evals=False, has_init=False):
    return lockstep.stage1_program(
        _garch_family(backend, align_mode, has_init), max_iters, tol,
        count_evals)


@jit_program
def _fit_stage2_program(max_iters, tol, backend):
    return lockstep.stage2_program(_garch_family(backend), max_iters, tol)


def forecast(params, r, n_future: int):
    """Variance-path forecast -> ``[batch?, n_future]`` conditional variances.

    GARCH's mean forecast is identically zero; what users forecast is the
    VOLATILITY path: ``h_{T+1} = omega + alpha r_T^2 + beta h_T`` from the
    in-sample recursion's end state, then — future squared returns
    entering at their conditional expectation ``E[r^2] = h`` —
    ``h_{T+k} = omega + (alpha + beta) h_{T+k-1}``, decaying geometrically
    toward the unconditional variance.  Leading/trailing NaNs are
    tolerated (right-aligned span, same contract as :func:`fit`); rows
    with non-finite params or fewer than 2 valid observations come back
    NaN rather than a plausible-looking zero.
    """
    rb, single = ensure_batched(r)
    pb = jnp.atleast_2d(params)
    out = _forecast_program(n_future)(pb, rb)
    return out[0] if single else out


@jit_program
def _forecast_program(n_future):
    def run(pb, rb):
        def one(pr, rv):
            ra, nv = align_right(rv)
            h = variances(pr, ra, nv)
            omega, alpha, beta = pr[0], pr[1], pr[2]
            h1 = omega + alpha * ra[-1] ** 2 + beta * h[-1]

            def step(hp, _):
                return omega + (alpha + beta) * hp, hp

            _, hs = lax.scan(step, h1, None, length=n_future)
            ok = (nv >= 2) & jnp.all(jnp.isfinite(pr))
            return jnp.where(ok, hs, jnp.nan)

        return jax.vmap(one)(pb, rb)

    return run


def sample(params, key, n: int):
    """Simulate n returns from GARCH(1,1) (reference ``GARCHModel.sample``):
    standard-normal innovations through :func:`add_time_dependent_effects`."""
    params = jnp.asarray(params, jnp.result_type(float))
    eps = jax.random.normal(key, (n,), params.dtype)
    return add_time_dependent_effects(params, eps)


def add_time_dependent_effects(params, x):
    """White noise -> GARCH returns: scale by the running conditional vol.

    The recursion needs r_{t-1}, which it itself produces, so this scan
    carries (h, r_prev); the variance path it induces is exactly
    ``_variance_scan`` seeded with r_prev = 0 and h_0 = the unconditional
    variance — which is what :func:`remove_time_dependent_effects` replays.
    """
    xb, single = ensure_batched(x)
    pb = jnp.atleast_2d(params)
    out = _add_effects_batched(pb, xb)
    return out[0] if single else out


@jax.jit
def _add_effects_batched(pb, xb):
    def one(pr, xv):
        omega, alpha, beta = pr[0], pr[1], pr[2]

        def step(carry, e):
            h, r_prev = carry
            h = omega + alpha * r_prev**2 + beta * h
            r = jnp.sqrt(jnp.maximum(h, 1e-12)) * e
            return (h, r), r

        _, r = lax.scan(step, (_unconditional_var(pr), jnp.zeros((), xv.dtype)), xv)
        return r

    return jax.vmap(one)(pb, xb)


def remove_time_dependent_effects(params, r):
    """GARCH returns -> standardized residuals r_t / sqrt(h_t), replaying
    :func:`add_time_dependent_effects`'s variance path so the pair
    round-trips exactly."""
    rb, single = ensure_batched(r)
    pb = jnp.atleast_2d(params)
    out = _remove_effects_batched(pb, rb)
    return out[0] if single else out


@jax.jit
def _remove_effects_batched(pb, rb):
    def one(pr, rv):
        r_sq_prev = jnp.concatenate([jnp.zeros((1,), rv.dtype), rv[:-1] ** 2])
        h = _variance_scan(pr, _unconditional_var(pr), r_sq_prev)
        return rv / jnp.sqrt(jnp.maximum(h, 1e-12))

    return jax.vmap(one)(pb, rb)


# ---------------------------------------------------------------------------
# AR(1) + GARCH(1,1)
# ---------------------------------------------------------------------------


def _argarch_to_natural(u):
    return jnp.concatenate([u[:2], _to_natural(u[2:])])


def _argarch_from_natural(params):
    return jnp.concatenate([params[:2], _from_natural(params[2:])])


def _argarch_point(u, c0, units):
    """The fit's optimizer space -> natural ``[c, phi, omega, alpha, beta]``:
    ``c`` is an offset from the start's ``c0`` in ``units`` of the start's
    residual scale (``Prepared.natural``), ``phi`` is free, the variance
    triple goes through :func:`_to_natural`.  A free ``c`` is in the DATA's
    units: on daily returns in decimals (``c`` ~ 5e-4, d nll / d c ~ 1e2 an
    observation) the first line search shrank every step to nothing and the
    relative-decrease test stopped the row at its start (PERF.md §6, PR
    52)."""
    return jnp.concatenate([(c0 + units * u[0])[None], u[1:2],
                            _to_natural(u[2:])])


def argarch_neg_log_likelihood(params, y, n_valid=None):
    """y_t = c + phi y_{t-1} + r_t with GARCH(1,1) innovations r."""
    c, phi = params[0], params[1]
    n = y.shape[0]
    prev = jnp.concatenate([y[:1], y[:-1]])
    r = y - c - phi * prev
    # one code path for trimmed and padded series: condition on the first
    # valid observation, whose residual is excluded from both the variance
    # seed and the likelihood sum (one fewer residual than observations)
    nv = jnp.asarray(n, jnp.int32) if n_valid is None else n_valid
    start = n - nv
    r = jnp.where(jnp.arange(n) <= start, 0.0, r)
    return neg_log_likelihood(params[2:], r, nv - 1)


# the mean equation's terms a kernel step (c and phi y_{t-1}: a stage span's
# ``mean_terms``), and the panel-sized operands and results a GRADIENT pays
# for the mean equation beside the kernel pair's own (``mean_panel_moves``):
# none where the calls form the returns themselves (``pallas.
# argarch_neg_loglik``, series of one time chunk; ``tests/
# test_pallas_argarch.py`` holds the traced programs to it).  Past one chunk
# the returns are an XLA panel and the least a gradient moves, every
# fusion's panels counted once, is 18: the series and its shifted copy read
# and the returns written (3); ``garch_prefold``'s two reductions, square
# and fold (3 read, 1 written);
# the adjoint's r^2 cotangent written (1), read with ``h3`` and rewritten
# for the likelihood's direct part (3), unfolded (2), chained through the
# square against the returns (3) and reduced into c and phi against the
# shifted copy (2) — a reading of the program, not a measurement
ARGARCH_MEAN_TERMS = 2
ARGARCH_MEAN_PANEL_MOVES = 0
_XLA_RETURNS_PANEL_MOVES = 18


def _mean_in_kernel(backend, n_time: int) -> bool:
    """Whether :func:`fit_argarch`'s objective forms its returns inside the
    kernel calls: a Pallas backend and a series of one time chunk."""
    from ..ops import pallas_kernels as pk

    return (backend in lockstep.PALLAS
            and pk.garch_mean_structural_ok(n_time))


def fit_argarch(y, *, max_iters: int = 100, tol: Optional[float] = None,
                backend: str = "auto", compact: bool = True,
                align_mode: Optional[str] = None) -> FitResult:
    """Fit AR(1)+GARCH(1,1) -> natural params ``[batch?, 5]``
    (reference ``ARGARCH.fitModel``): ``y_t = c + phi y_{t-1} + r_t`` with
    Gaussian GARCH(1,1) returns ``r``.

    The fit CONDITIONS on the first valid observation: its return is in
    neither the likelihood nor the variance seed (one return fewer than
    observations).  The seed ``h_0`` — standing in for ``h_{-1}`` and the
    unobserved ``r_{-1}^2`` — is the sample variance of the returns, so it
    moves with ``phi`` (not with ``c``, which shifts the returns and their
    mean alike) and the gradient carries that dependence.

    Which path a size takes (``resolve_backend`` and the series' length
    decide, no option does): on a Pallas backend a series of at most one
    time chunk (1,024 steps) is folded once a fit and the returns are
    formed inside the GARCH kernel calls (``pallas.argarch_neg_loglik``:
    one panel read a value pass, two a gradient, none written beside the
    variance path); a longer one builds the returns panel in XLA each pass
    and differentiates through :func:`pallas_kernels.garch_neg_loglik`'s
    data cotangents; the ``scan`` backend is the portable ``lax.scan``.

    ``compact=False`` disables straggler compaction (see :func:`fit`);
    ``align_mode`` is the static alignment hint (``base.resolve_align_mode``)
    — a hint too strong for the data flags the violating rows instead of
    silently misfitting them;
    ``FitResult.status`` carries per-row ``reliability.FitStatus`` codes."""
    yb, single = ensure_batched(y)
    if tol is None:
        tol = 1e-7 if yb.dtype == jnp.float64 else 1e-4
    backend = resolve_backend(backend, yb.dtype, yb.shape[1])
    align_mode = resolve_align_mode(yb, align_mode)
    static = (max_iters, float(tol), backend)
    in_kernel = _mean_in_kernel(backend, yb.shape[1])
    out = lockstep.fit(
        (yb,), backend=backend, compact=compact, max_iters=max_iters,
        inline=lambda: _fit_argarch_program(*static, compact, align_mode),
        stage1=lambda: _fit_argarch_stage1_program(*static, align_mode),
        stage2=lambda: _fit_argarch_stage2_program(*static),
        **_garch_kernel_attrs(yb.shape[1], in_kernel))
    return debatch(out, single)


def _argarch_family(backend, align_mode=None) -> lockstep.Family:
    from ..ops import pallas_kernels as pk

    def prep(yb):
        ya, nv = maybe_align(yb, align_mode)

        # init: OLS-ish AR(1) by autocorrelation, then GARCH moments on resid
        # (masked over each right-aligned valid span)
        T = ya.shape[1]
        m = (jnp.arange(T)[None, :] >= (T - nv)[:, None]).astype(ya.dtype)
        nvf = jnp.maximum(nv, 1).astype(ya.dtype)
        mean = jnp.sum(ya * m, axis=1) / nvf
        yc = (ya - mean[:, None]) * m
        phi0 = jnp.sum(yc[:, 1:] * yc[:, :-1], axis=1) / jnp.maximum(
            jnp.sum(yc * yc, axis=1), 1e-12
        )
        phi0 = jnp.clip(phi0, -0.95, 0.95)
        c0 = mean * (1.0 - phi0)
        resid = (ya[:, 1:] - c0[:, None] - phi0[:, None] * ya[:, :-1]) * m[:, 1:]
        resid_var = jnp.sum(resid**2, axis=1) / nvf
        var0 = jnp.stack(
            [0.1 * jnp.maximum(resid_var, 1e-8), jnp.full_like(c0, 0.1),
             jnp.full_like(c0, 0.8)], axis=1)
        # the optimizer's point (see _argarch_point): c at offset 0 from c0
        units = jnp.sqrt(jnp.maximum(resid_var, 1e-30))
        u0 = jnp.concatenate(
            [jnp.zeros_like(c0)[:, None], phi0[:, None],
             jax.vmap(_from_natural)(var0)], axis=1)
        n_eff = jnp.maximum(nv - 1, 1).astype(ya.dtype)
        folded, rows = (), ()
        if _mean_in_kernel(backend, T):
            # the returns depend on the iterate, the series does not: it is
            # masked and folded ONCE, the kernel calls form r_t from it, and
            # the seed variance is a quadratic in phi of three row moments —
            # a straggler subset is a gather of the folded COLUMNS
            folded, mom = pk.argarch_prefold(ya, nv)
            rows = (mom, c0, units)
        elif backend in lockstep.PALLAS:
            # past one time chunk the returns are an XLA panel a pass, built
            # from the NATURAL-layout series and its shifted copy (a
            # straggler subset is a row gather of each array)
            prev = jnp.concatenate([ya[:, :1], ya[:, :-1]], axis=1)
            rows = (ya, prev, nv, c0, units)
        return lockstep.Prepared((u0,), nv >= 12, n_eff,
                                 (ya, nv, c0, units), folded, rows,
                                 natural=(c0, units))

    to_natural = jax.vmap(_argarch_point)

    def objective(folded, rows):
        if isinstance(folded, pk.ArgarchFolded):
            mom, *point = rows
            return lambda u: pk.argarch_neg_loglik_folded(
                to_natural(u, *point), folded, mom,
                interpret=backend == "pallas-interpret")
        ya, prev, nv, *point = rows
        t_idx = jnp.arange(ya.shape[1])
        start = ya.shape[1] - nv

        def fb(u):
            nat = to_natural(u, *point)
            r = ya - nat[:, 0:1] - nat[:, 1:2] * prev
            # condition on the first valid observation (see
            # argarch_neg_log_likelihood): its residual is excluded
            r = jnp.where(t_idx[None, :] <= start[:, None], 0.0, r)
            return pk.garch_neg_loglik(
                nat[:, 2:], r, nv - 1,
                interpret=backend == "pallas-interpret")

        return fb

    def scan_objective(u, data):
        yv, n, *point = data
        return argarch_neg_log_likelihood(_argarch_point(u, *point), yv, n)

    return lockstep.Family(backend, prep, objective, scan_objective,
                           to_natural)


@jit_program
def _fit_argarch_program(max_iters, tol, backend, compact=True,
                         align_mode="general"):
    return lockstep.fit_program(_argarch_family(backend, align_mode),
                                max_iters, tol, compact=compact)


@jit_program
def _fit_argarch_stage1_program(max_iters, tol, backend, align_mode="general"):
    return lockstep.stage1_program(_argarch_family(backend, align_mode),
                                   max_iters, tol)


@jit_program
def _fit_argarch_stage2_program(max_iters, tol, backend):
    return lockstep.stage2_program(_argarch_family(backend), max_iters, tol)


def argarch_sample(params, key, n: int):
    """Simulate AR(1)+GARCH(1,1)."""
    return _argarch_sample_program(n)(params, key)


@jit_program
def _argarch_sample_program(n):
    def run(params, key):
        params = jnp.asarray(params, jnp.result_type(float))
        c, phi = params[0], params[1]
        r = sample(params[2:], key, n)

        def step(y_prev, rt):
            y = c + phi * y_prev + rt
            return y, y

        _, y = lax.scan(step, c / jnp.maximum(1.0 - phi, 1e-6), r)
        return y

    return run
