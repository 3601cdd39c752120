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
