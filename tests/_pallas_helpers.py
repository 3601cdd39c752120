"""What the Pallas kernel tests share: the panel makers, the pinned fits and
their digests, the jaxpr walkers and each family's stage programs on shapes
alone.  Imported by ``tests/test_pallas_*.py`` and by the lockstep files that
hold a lazy fit to its reference (``_dist_parity``); a helper more than one
test file uses lives here, once.  Test files import helper modules (this
one, ``_dag_hash``), never each other.
"""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from spark_timeseries_tpu.models import arima
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def _arma_panel(b, t, phi=0.6, theta=0.3, d_int=False, seed=0):
    rng = np.random.default_rng(seed)
    e = rng.normal(size=(b, t)).astype(np.float32)
    y = np.zeros_like(e)
    y[:, 0] = e[:, 0]
    for i in range(1, t):
        y[:, i] = phi * y[:, i - 1] + e[:, i] + theta * e[:, i - 1]
    if d_int:
        y = np.cumsum(y, axis=1)
    return jnp.asarray(y)


def _returns_panel(b, t, seed=11):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(scale=0.02, size=(b, t)).astype(np.float32))


def _garch_params(b, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.column_stack([
        rng.uniform(1e-5, 2e-4, b), rng.uniform(0.05, 0.2, b),
        rng.uniform(0.5, 0.8, b)]).astype(np.float32))


def _scan_nll(params, rz, nv):
    from spark_timeseries_tpu.models import garch

    return jax.vmap(garch.neg_log_likelihood)(params, rz, nv)


def _scan_nll_sum(params, rz, nv):
    return jnp.sum(_scan_nll(params, rz, nv))


def _garch_pin_fit(path, backend="pallas-interpret"):
    """One fit of the fit-level pin: ``inline-*`` (24 rows, under the
    compaction gate) or ``lazy-*`` (2048 rows through stage 1 / stage 2;
    the caller lowers the gate), ``*-dense`` or ``*-ragged`` (NaN heads and
    a NaN tail: ``align_mode="general"``)."""
    from spark_timeseries_tpu.models import garch

    b, t = (2048, 64) if path.startswith("lazy") else (24, 120)
    rng = np.random.default_rng(29)
    omega = rng.uniform(1e-5, 6e-5, size=b)
    alpha = rng.uniform(0.03, 0.25, size=b)
    beta = rng.uniform(0.4, 0.7, size=b)
    z = rng.normal(size=(b, t))
    r = np.zeros((b, t))
    h = omega / (1.0 - alpha - beta)
    for i in range(t):
        r[:, i] = np.sqrt(h) * z[:, i]
        h = omega + alpha * r[:, i] ** 2 + beta * h
    r = r.astype(np.float32)
    if path.endswith("ragged"):
        r[1, :13] = np.nan
        r[3, -9:] = np.nan
        r[5, :3] = np.nan
    return garch.fit(jnp.asarray(r), backend=backend)


def _seasonal_panel(b, t, m, seed=31):
    rng = np.random.default_rng(seed)
    tt = np.arange(t)
    base = 10.0 + 0.05 * tt[None, :]
    seas = 2.0 * np.sin(2 * np.pi * tt[None, :] / m)
    noise = rng.normal(scale=0.3, size=(b, t))
    return jnp.asarray((base + seas + noise).astype(np.float32))


def _panel_relayouts_in_loops(jaxpr, n_panel, in_loop=False):
    """``(primitive, operand shape)`` of every ``transpose`` / ``pad`` /
    ``copy`` of an operand with at least ``n_panel`` elements inside a
    ``while`` of ``jaxpr`` (kernel bodies aside: a ``pallas_call`` works on
    blocks)."""
    found = []
    for eqn in jaxpr.eqns:
        if (in_loop and eqn.primitive.name in ("transpose", "pad", "copy")
                and eqn.invars[0].aval.size >= n_panel):
            found.append((eqn.primitive.name, eqn.invars[0].aval.shape))
        if eqn.primitive.name == "pallas_call":
            continue
        inner = in_loop or eqn.primitive.name == "while"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _panel_relayouts_in_loops(sub, n_panel, inner)
    return found


def _panel_ops(eqns, n_panel, names=("mul", "select_n", "div")):
    """``(primitive, result shape)`` of every ``names`` equation among
    ``eqns``, nested jaxprs included (kernel bodies aside), with a result of
    at least ``n_panel`` elements."""
    found = []
    for eqn in eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name in names:
            found += [(eqn.primitive.name, v.aval.shape)
                      for v in eqn.outvars if v.aval.size >= n_panel]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _panel_ops(sub.eqns, n_panel, names)
    return found


def _panel_moves_in_loops(jaxpr, n_panel, in_loop=False):
    """``(primitive, shape)`` of every operand and result of at least
    ``n_panel`` elements that an equation inside a ``while`` of ``jaxpr``
    takes or gives OUTSIDE the kernel calls: what a pass moves beside its
    ``pallas_call``s.  Equations that only wrap a jaxpr (``pjit``, a custom
    derivative's call, the loops themselves) are looked through, and the
    line search's tail is let be: its ``gather`` (behind a ``reshape``, a
    bitcast) takes the folded panel as its operand and reads the straggler
    cap's columns of it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pallas_call", "reshape", "gather"):
            continue
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        inner = in_loop or eqn.primitive.name == "while"
        for sub in subs:
            found += _panel_moves_in_loops(sub, n_panel, inner)
        if in_loop and not subs:
            found += [(eqn.primitive.name, v.aval.shape)
                      for v in (*eqn.invars, *eqn.outvars)
                      if not isinstance(v, jax.extend.core.Literal)
                      and v.aval.size >= n_panel]
    return found


def _objective_adjoints(jaxpr, n_panel):
    """Every objective gradient of ``jaxpr`` at any depth, as ``(panel
    operands of the adjoint call, panel-sized mul / select_n / div between
    the forward call and it)``: an adjoint ``pallas_call`` is one that reads
    a panel an earlier ``pallas_call`` of the same jaxpr wrote (the saved
    residuals), the forward call the latest such."""
    found, wrote = [], {}
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _objective_adjoints(sub, n_panel)
            continue
        panels = {v for v in eqn.invars
                  if not isinstance(v, jax.extend.core.Literal)
                  and v.aval.size >= n_panel}
        fwd = [wrote[v] for v in panels if v in wrote]
        if fwd:
            found.append((len(panels), _panel_ops(
                jaxpr.eqns[max(fwd) + 1:i], n_panel)))
        wrote.update({v: i for v in eqn.outvars if v.aval.size >= n_panel})
    return found


def _stage_programs(family, b, t):
    """-> ``adjoint_panels``, then stage 1, stage 2 and the inline program
    of a family's lazy fit as ``(fn, args, rows)``, on shapes alone."""
    from spark_timeseries_tpu.models import garch
    from spark_timeseries_tpu.models import holtwinters as hw

    y = jax.ShapeDtypeStruct((b, t), jnp.float32)
    if family == "harmonic-arma":
        # a shared design of 1 + 2 (3 + 2) columns (periods 12 and 48 at 3
        # and 2 harmonics) beside ARMA(1,1) errors: the design, its
        # projector and its columns' autocovariances ride as operands
        from spark_timeseries_tpu.models import regression_arima as ra

        k, f32 = 11, jnp.float32
        args = (y, jax.ShapeDtypeStruct((t, k), f32),
                jax.ShapeDtypeStruct((k, t), f32),
                jax.ShapeDtypeStruct((k, 1 + ra._UNIT_LAGS), f32))
        static = ((1, 0, 1), "pallas-interpret", 13, 1e-4)
        stage1 = ra._shared_stage1_program.__wrapped__(*static, "dense")
        aux = jax.eval_shape(stage1, *args)[1]
        return pk.CSS_ADJOINT_PANELS, (
            (stage1, args, b),
            (ra._shared_fit_program.__wrapped__(*static, "dense", True),
             args, b),
            (ra._shared_stage2_program.__wrapped__(*static),
             (aux["starts"][0], aux["fin"]), optim.compaction_cap(b)))
    if family == "arima-grid3":
        # a fused order search: 3 orders a row, so a third of the rows make
        # the same cells; the adjoint reads the ONE panel and the cells'
        # error panels
        specs = (((1, 1, 0), None), ((0, 1, 1), None), ((2, 1, 2), None))
        static = (specs, True, "pallas-interpret", 13, 1e-4)
        y = jax.ShapeDtypeStruct((b // 2, t), jnp.float32)
        stage1 = arima._grid_stage1_program.__wrapped__(*static, "dense")
        aux = jax.eval_shape(stage1, y)[1]
        cap = arima._grid_cap(3 * b // 2, "pallas-interpret", True)
        return pk.CSS_ADJOINT_PANELS, (
            (stage1, (y,), b // 2),
            (arima._grid_fit_program.__wrapped__(*static, "dense"), (y,),
             b // 2),
            (arima._grid_stage2_program.__wrapped__(*static),
             (aux["starts"][0], aux["fin"]), cap))
    if family in ("arima111", "sarima-airline4"):
        seasonal = (0, 1, 1, 4) if family == "sarima-airline4" else None
        order = (0, 1, 1) if seasonal else (1, 1, 1)
        static = (order, True, "pallas-interpret", 13, 1e-4)
        stage1 = arima._fit_stage1_program.__wrapped__(
            *static, False, "dense", False, seasonal)
        stage2 = arima._fit_stage2_program.__wrapped__(*static, seasonal)
        inline = arima._fit_program.__wrapped__(
            order, True, "css-lbfgs", *static[2:], False, "dense", False,
            True, seasonal)
        panels = pk.CSS_ADJOINT_PANELS
    elif family.startswith("hw"):
        mult = family == "hw-mult"
        n_starts = 3 if mult else 1
        static = (4, mult, 13, 1e-4, "pallas-interpret")
        stage1 = hw._fit_stage1_program.__wrapped__(*static, "dense",
                                                    n_starts)
        stage2 = hw._fit_stage2_program.__wrapped__(*static)
        inline = hw._fit_program.__wrapped__(*static, "dense", False, True,
                                             n_starts)
        panels = pk.HW_ADJOINT_PANELS[mult]
    elif family == "argarch":
        # the GARCH pair with the mean equation in its calls
        static = (13, 1e-4, "pallas-interpret")
        stage1 = garch._fit_argarch_stage1_program.__wrapped__(*static,
                                                               "dense")
        stage2 = garch._fit_argarch_stage2_program.__wrapped__(*static)
        inline = garch._fit_argarch_program.__wrapped__(*static, True,
                                                        "dense")
        panels = pk.GARCH_ADJOINT_PANELS
    else:
        static = (13, 1e-4, "pallas-interpret")
        stage1 = garch._fit_stage1_program.__wrapped__(*static, "dense")
        stage2 = garch._fit_stage2_program.__wrapped__(*static)
        inline = garch._fit_program.__wrapped__(*static, "dense", False, True)
        panels = pk.GARCH_ADJOINT_PANELS
    aux = jax.eval_shape(stage1, y)[1]
    start, cap = aux["starts"][0], optim.compaction_cap(b)
    args2 = (start,) if family.startswith("hw") else (start, aux["fin"])
    return panels, ((stage1, (y,), b), (inline, (y,), b),
                    (stage2, args2, cap))


def _hw_pin_fit(path, model_type, backend="pallas-interpret"):
    """One fit of the fit-level pin: ``inline`` (24 rows, under the
    compaction gate), ``ragged`` (the same with NaN heads and a NaN tail:
    ``align_mode="general"``) or ``lazy`` (2048 rows through stage 1 /
    stage 2; the caller lowers the gate)."""
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = (2048, 48, 8) if path == "lazy" else (24, 72, 6)
    rng = np.random.default_rng(7)
    tt = np.arange(t, dtype=np.float32)
    amp = rng.uniform(0.5, 3.0, size=(b, 1))
    y = (10.0 + 0.05 * tt[None, :] + amp * np.sin(2 * np.pi * tt[None, :] / m)
         + rng.uniform(0.05, 0.6, size=(b, 1)) * rng.normal(size=(b, t)))
    y = (y + (25.0 if model_type == "multiplicative" else 0.0)).astype(
        np.float32)
    if path == "ragged":
        y[1, :13] = np.nan
        y[3, -9:] = np.nan
        y[5, :3] = np.nan
    return hw.fit(jnp.asarray(y), m, model_type, backend=backend)


def _sha(*arrays):
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


def _fit_pin_digest(r):
    return (_sha(r.params), _sha(r.neg_log_likelihood),
            int(np.sum(np.asarray(r.converged))),
            int(np.sum(np.asarray(r.iters))))


def _dist_parity(ref, got, conv_floor=0.45):
    conv_ref = np.asarray(ref.converged)
    conv_got = np.asarray(got.converged)
    assert abs(conv_ref.mean() - conv_got.mean()) < 0.02
    both = conv_ref & conv_got
    assert both.mean() > conv_floor
    nll_r = np.asarray(ref.neg_log_likelihood)[both]
    nll_g = np.asarray(got.neg_log_likelihood)[both]
    rel = np.abs(nll_r - nll_g) / np.maximum(np.abs(nll_r), 1e-6)
    assert float(np.percentile(rel, 99)) < 1e-2
    med = float(np.nanmedian(np.abs(
        np.asarray(ref.params)[both] - np.asarray(got.params)[both])))
    assert med < 1e-2


def _traced_fit_parity(lazy, fit, panel, **kw):
    # the same fit under a caller's jit (the panel a Tracer: stage 1 and
    # stage 2 composed in one trace) against the eager lazy pair
    _dist_parity(lazy, jax.jit(fit)(panel), **kw)


_DESIGN_ORDER = (1, 0, 1)


def _design_case(k, r, t, seed=0):
    """One block of ``1024 r`` series: a level, the design's part and AR(1)
    noise; the design's columns padded to ``nx`` (zero columns, zero
    coefficients), its rows past ``t`` zero."""
    b = 1024 * r
    rng = np.random.default_rng(seed + 7 * k + t)
    nx = k + pk._pad_to(k, 8)
    tp = pk._time_layout(t)[0]
    x = np.zeros((tp, nx), np.float32)
    x[:t, :k] = rng.normal(size=(t, k))
    beta = np.zeros((b, nx), np.float32)
    beta[:, :k] = rng.normal(size=(b, k))
    noise = rng.normal(size=(b, t)).astype(np.float32)
    for i in range(1, t):
        noise[:, i] += 0.5 * noise[:, i - 1]
    y = jnp.asarray(10.0 + beta @ x[:t].T + noise)
    # the coefficients the kernels are handed are NOT the generating ones
    beta = beta + np.where(beta != 0, 0.1 * rng.normal(size=beta.shape), 0.0)
    arma = np.column_stack([np.zeros(b), rng.uniform(0.2, 0.8, b),
                            rng.uniform(-0.4, 0.4, b)])
    y3, zb3 = pk.css_prefold(y, _DESIGN_ORDER)
    return (y, y3, zb3, jnp.asarray(x), jnp.asarray(beta, jnp.float32),
            jnp.asarray(arma, jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.5, b), jnp.float32))


def _close_to(got, want, rel):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rel * np.abs(want).max())


def _check_fused_design(k, r, t):
    """The CSS kernels with a shared design of ``k`` columns as an operand,
    at the forced width ``r`` over ``t`` steps, against (a) the composition
    they replace — an XLA residual ``y3 + design_plane``, the plain kernels,
    ``x' g_u`` over the adjoint's data-cotangent panel — and (b) the float64
    ``lax.scan`` errors (``tests/test_pallas_css_design*.py``)."""
    y, y3, zb3, x, beta, arma, gbar = _design_case(k, r, t)
    b, nx = y.shape[0], x.shape[1]
    par = jnp.concatenate([arma, beta], axis=1)

    # fused: "sum" and "both" give one value bit for bit (_css_ss_f's rule)
    (css_s,), _ = pk._css_fwd_call_f(1, 1, True, "sum", par, y3, zb3, t,
                                     _r=r, x=x)
    (e3, u3, css_b), (_, par3, _) = pk._css_fwd_call_f(
        1, 1, True, "both", par, y3, zb3, t, _r=r, x=x)
    assert np.asarray(css_s).tobytes() == np.asarray(css_b).tobytes()
    g_f = pk._css_ss_x_bwd(1, 1, True, t, b, (u3, par3, zb3, e3, x), gbar,
                           _r=r)
    assert g_f[0].shape == (b, 3 + nx) and g_f[1:] == (None, None, None)
    assert not np.asarray(g_f[0])[:, 3 + k:].any()  # the padding's columns

    # (a) the composition: an XLA residual, the plain kernels, x' g_u
    u3_c = y3 + pk.design_plane(x, -beta)
    (e3_c, css_c), (_, par3_c, _) = pk._css_fwd_call_f(
        1, 1, True, "both", arma, u3_c, zb3, t, _r=r)
    g_arma, g_u, *_ = pk._css_ss_f_bwd(1, 1, True, t, b,
                                      (u3_c, par3_c, zb3, e3_c, ()), gbar,
                                      _r=min(r, 2))
    g_beta = -pk._unfold(jnp.einsum("tk,tns->kns", x, g_u,
                                    precision=jax.lax.Precision.HIGHEST), b)
    _close_to(u3, u3_c, 1e-6)
    # the start's residual panel is the same prologue and nothing after it
    u3_p = pk.css_design_residual(y3, x[:, :k], beta[:, :k], t,
                                  interpret=True)
    assert np.asarray(u3_p).tobytes() == np.asarray(u3).tobytes()
    _close_to(e3, e3_c, 1e-5)
    np.testing.assert_allclose(css_b, css_c, rtol=1e-5)
    _close_to(g_f[0][:, :3], g_arma, 1e-5)
    _close_to(g_f[0][:, 3:], g_beta, 1e-5)

    # (b) the float64 scan, on a few rows of the block
    rows = np.r_[0:4, b - 4:b]
    y64, x64 = np.asarray(y, np.float64)[rows], np.asarray(x, np.float64)[:t]

    def css64(arma_r, beta_r, y_r):
        e = arima._css_errors(arma_r[1:], y_r - x64 @ beta_r, _DESIGN_ORDER,
                              False)
        return jnp.sum(e * e)

    v64, (ga64, gb64) = jax.vmap(jax.value_and_grad(css64, (0, 1)))(
        jnp.asarray(arma, jnp.float64)[rows],
        jnp.asarray(beta, jnp.float64)[rows], jnp.asarray(y64))
    np.testing.assert_allclose(pk._unfold(css_s, b)[rows, 0], v64, rtol=2e-4)
    scale = np.asarray(gbar, np.float64)[rows, None]
    _close_to(np.asarray(g_f[0])[rows, 1:3], scale * ga64[:, 1:], 2e-3)
    _close_to(np.asarray(g_f[0])[rows, 3:], scale * gb64, 2e-3)


def _check_design_entry(t):
    """``css_neg_loglik_folded(design=)`` over ``t`` steps: 31 columns as they
    come, against autodiff through the composition."""
    # the entry pads the columns to whole sublane tiles; the likelihood and
    # its gradient in (arma, beta); the width is the rule's
    y, y3, zb3, x, beta, arma, _ = _design_case(31, 1, t, seed=3)
    x, beta, arma = x[:, :31], beta[:, :31], arma[:, 1:]

    def fused(a, c):
        return jnp.sum(pk.css_neg_loglik_folded(
            a, y3, zb3, t, _DESIGN_ORDER, False, design=(x, c),
            interpret=True))

    def composed(a, c):
        return jnp.sum(pk.css_neg_loglik_folded(
            a, y3 + pk.design_plane(x, -c), zb3, t, _DESIGN_ORDER, False,
            interpret=True))

    f, g = jax.value_and_grad(fused, (0, 1))(arma, beta)
    f_c, g_c = jax.value_and_grad(composed, (0, 1))(arma, beta)
    assert float(f) == pytest.approx(float(f_c), rel=1e-6)
    for got, want in zip(g, g_c):
        _close_to(got, want, 1e-5)
    # value-only and value-and-gradient agree to the bit, row by row
    nll = lambda a: pk.css_neg_loglik_folded(  # noqa: E731
        a, y3, zb3, t, _DESIGN_ORDER, False, design=(x, beta),
        interpret=True)
    both, _ = jax.vjp(nll, arma)
    assert np.asarray(nll(arma)).tobytes() == np.asarray(both).tobytes()
    # the data and the design are constants of this objective
    with pytest.raises(NotImplementedError, match="parameters alone"):
        jax.grad(lambda v: jnp.sum(pk.css_neg_loglik_folded(
            arma, v, zb3, t, _DESIGN_ORDER, False, design=(x, beta),
            interpret=True)))(y3)
