"""The GARCH kernels against the portable ``lax.scan`` implementations: the
fused likelihood, its adjoint and data cotangents, the folded fit objective,
the fit programs' fold and the fit-level pin.  Interpret mode, as
``test_pallas_css.py`` says.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import (
    _dist_parity, _fit_pin_digest, _garch_params, _garch_pin_fit,
    _panel_relayouts_in_loops, _returns_panel, _scan_nll, _scan_nll_sum,
    _traced_fit_parity)
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def test_garch_variances_matches_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 4, 37
    rng = np.random.default_rng(7)
    r = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
    params = jnp.asarray(
        np.tile([[0.1, 0.15, 0.7]], (b, 1)).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 5, t, t - 2], jnp.int32)
    ref = jax.vmap(lambda pr, rv, n: garch.variances(pr, rv, n))(params, r, nv)

    start = (t - nv).astype(jnp.float32)
    t_idx = jnp.arange(t, dtype=jnp.float32)
    rz = jnp.where(t_idx[None, :] >= start[:, None], r, 0.0)
    h0 = jax.vmap(garch._masked_var)(r, nv)
    got = pk.garch_variances(params, rz, h0, start, interpret=True)
    # compare only the live span: the scan reference seeds the prefix with
    # its own start-variance convention
    mask = t_idx[None, :] >= start[:, None]
    np.testing.assert_allclose(
        np.asarray(jnp.where(mask, got, 0.0)),
        np.asarray(jnp.where(mask, ref, 0.0)),
        rtol=2e-5,
        atol=2e-5,
    )


def test_garch_neg_loglik_matches_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 5, 47
    r = _returns_panel(b, t)
    rng = np.random.default_rng(12)
    params = jnp.asarray(
        np.column_stack(
            [
                rng.uniform(0.01, 0.2, b),
                rng.uniform(0.05, 0.2, b),
                rng.uniform(0.5, 0.8, b),
            ]
        ).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 4, t, t - 9, t - 1], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    rz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], r, 0.0)

    ref = jax.vmap(lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n))(
        params, rz, nv
    )
    got = pk.garch_neg_loglik(params, rz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5, atol=3e-5)


def test_garch_gradient_matches_autodiff_of_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 4, 39
    r = _returns_panel(b, t, seed=13)
    rng = np.random.default_rng(14)
    params = jnp.asarray(
        np.column_stack(
            [
                rng.uniform(0.01, 0.2, b),
                rng.uniform(0.05, 0.2, b),
                rng.uniform(0.5, 0.8, b),
            ]
        ).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 5, t - 2, t], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    rz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], r, 0.0)

    def loss_scan(P):
        return jnp.sum(
            jax.vmap(lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n))(
                P, rz, nv
            )
        )

    def loss_pal(P):
        return jnp.sum(pk.garch_neg_loglik(P, rz, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-4, atol=2e-4)


def test_argarch_objective_gradient_matches_scan():
    """Exercises the r^2 / h0 cotangent paths of the GARCH adjoint: the AR(1)
    mean parameters reach the variance recursion through the residuals."""
    from spark_timeseries_tpu.models import garch

    b, t = 4, 45
    key = jax.random.PRNGKey(0)
    pars_nat = jnp.asarray(
        np.tile([[0.05, 0.4, 0.02, 0.1, 0.7]], (b, 1)).astype(np.float32)
    )
    y = jax.vmap(lambda pr, k: garch.argarch_sample(pr, k, t))(
        pars_nat, jax.random.split(key, b)
    ).astype(jnp.float32)
    nv = jnp.asarray([t, t - 3, t, t - 7], jnp.int32)
    start = (t - nv)[:, None]
    t_idx = jnp.arange(t)[None, :]
    ya = jnp.where(t_idx >= start, y, 0.0)
    rng = np.random.default_rng(15)
    u = jnp.asarray(rng.normal(scale=0.3, size=(b, 5)).astype(np.float32))

    def loss_scan(U):
        nat = jax.vmap(garch._argarch_to_natural)(U)
        return jnp.sum(
            jax.vmap(lambda pr, yv, n: garch.argarch_neg_log_likelihood(pr, yv, n))(
                nat, ya, nv
            )
        )

    def loss_pal(U):
        nat = jax.vmap(garch._argarch_to_natural)(U)
        prev = jnp.concatenate([ya[:, :1], ya[:, :-1]], axis=1)
        r = ya - nat[:, 0:1] - nat[:, 1:2] * prev
        r = jnp.where(t_idx <= start, 0.0, r)
        return jnp.sum(pk.garch_neg_loglik(nat[:, 2:], r, nv - 1, interpret=True))

    np.testing.assert_allclose(
        np.asarray(loss_pal(u)), np.asarray(loss_scan(u)), rtol=3e-5
    )
    g_ref = jax.grad(loss_scan)(u)
    g_got = jax.grad(loss_pal)(u)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=3e-4, atol=3e-4)


def test_garch_fit_backend_pallas_matches_scan():
    from spark_timeseries_tpu.models import garch

    b, t = 6, 200
    key = jax.random.PRNGKey(3)
    pars = jnp.asarray(np.tile([[0.05, 0.15, 0.7]], (b, 1)).astype(np.float32))
    r = jax.vmap(lambda pr, k: garch.sample(pr, k, t))(
        pars, jax.random.split(key, b)
    ).astype(jnp.float32)
    r_scan = garch.fit(r, backend="scan", max_iters=50)
    r_pal = garch.fit(r, backend="pallas-interpret", max_iters=50)
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=5e-2, atol=5e-3
    )


@pytest.mark.parametrize("ragged,t", [
    (False, 80), (True, 80),
    (True, 1100),  # two time chunks: the adjoint's ``hp`` path
])
def test_garch_neg_loglik_folded_matches_unfolded(ragged, t):
    # the pre-folded objective (garch_prefold + garch_neg_loglik_folded) is
    # the fit hot path; it must agree with the fold-per-call API bit for
    # bit, both with the scan, and its straggler gather (folded COLUMNS)
    # with a row gather of the panel
    b = 5
    r = _returns_panel(b, t, seed=61)
    nv = jnp.full((b,), t, jnp.int32)
    if ragged:
        nv = jnp.asarray([t, t - 11, t - 29, t - 3, t - 1], jnp.int32)
        r = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], r, 0.0)
    params = _garch_params(b, 62)
    folded = pk.garch_prefold(r, nv if ragged else None)
    assert folded.t == t and folded.r23.shape[1:] == (8, 128)
    ref = pk.garch_neg_loglik(params, r, nv, interpret=True)
    got = pk.garch_neg_loglik_folded(params, folded, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_scan_nll(params, r, nv)), rtol=3e-5)
    g_ref = jax.grad(lambda P: jnp.sum(
        pk.garch_neg_loglik(P, r, nv, interpret=True)))(params)
    g_got = jax.grad(lambda P: jnp.sum(
        pk.garch_neg_loglik_folded(P, folded, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
    g_scan = np.asarray(jax.grad(_scan_nll_sum)(params, r, nv))
    np.testing.assert_allclose(np.asarray(g_got), g_scan, rtol=2e-3,
                               atol=2e-3 * np.abs(g_scan).max())
    idx = jnp.asarray(np.random.default_rng(63).integers(0, b, 1024))
    ref_s = pk.garch_neg_loglik(params[idx], r[idx], nv[idx], interpret=True)
    got_s = pk.garch_neg_loglik_folded(params[idx], folded.take(idx),
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ref_s))


def _pallas_call_outputs(jaxpr):
    """The output shapes of every ``pallas_call`` of ``jaxpr``, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([v.aval.shape for v in eqn.outvars])
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_call_outputs(sub)
    return found


@pytest.mark.parametrize("t", [80, 1100])
def test_garch_data_cotangent_only_on_demand(t):
    # garch.fit differentiates in the parameters alone: its adjoint kernel
    # has ONE output and writes no panel; a caller whose returns depend on
    # what it differentiates (ARGARCH's AR(1) mean) gets the cotangents of
    # r^2 and h0 from the same adjoint, exact against the scan's autodiff
    b = 4
    r = _returns_panel(b, t, seed=71)
    nv = jnp.asarray([t, t - 7, t - 2, t], jnp.int32)
    rz = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], r, 0.0)
    params = _garch_params(b, 72)
    folded = pk.garch_prefold(rz, nv)
    panel, plane = folded.r23.shape, folded.h03.shape
    par3 = (3,) + plane[1:]

    def loss(P, f):
        return jnp.sum(pk.garch_neg_loglik_folded(P, f, interpret=True))

    calls = _pallas_call_outputs(
        jax.make_jaxpr(jax.grad(loss))(params, folded).jaxpr)
    assert calls == [[panel, plane], [par3]]  # forward (h3, ll3); adjoint
    calls = _pallas_call_outputs(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, folded).jaxpr)
    assert calls == [[panel, plane], [par3, panel, plane]]
    # the natural-layout entry differentiates through the fold
    g_p, g_r = jax.grad(lambda P, rv: jnp.sum(pk.garch_neg_loglik(
        P, rv, nv, interpret=True)), argnums=(0, 1))(params, rz)
    s_p, s_r = jax.grad(_scan_nll_sum, argnums=(0, 1))(params, rz, nv)
    live = np.asarray(jnp.arange(t)[None, :] >= (t - nv)[:, None])
    scale = np.abs(np.asarray(s_r)).max()
    np.testing.assert_allclose(np.where(live, np.asarray(g_r), 0.0) / scale,
                               np.where(live, np.asarray(s_r), 0.0) / scale,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(s_p), rtol=2e-3,
                               atol=2e-3 * np.abs(np.asarray(s_p)).max())


@pytest.mark.parametrize("align_mode", ["dense", "general"])
def test_garch_fit_programs_fold_outside_their_loops(monkeypatch, align_mode):
    # the CPU's stand-in for "``copy`` left the optimizer's loops" (PERF.md
    # S6, PR 29): the panel is folded once per fit program, so no while
    # body of stage 1, stage 2 or the inline program (with its straggler
    # compaction) relayouts a panel-sized operand
    from spark_timeseries_tpu.models import garch

    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48
    y = jax.ShapeDtypeStruct((b, t), jnp.float32)
    static = (13, 1e-4, "pallas-interpret")
    stage1 = garch._fit_stage1_program.__wrapped__(*static, align_mode)
    inline = garch._fit_program.__wrapped__(*static, align_mode, False, True)
    stage2 = garch._fit_stage2_program.__wrapped__(*static)
    aux = jax.eval_shape(stage1, y)[1]
    (start,), cap = aux["starts"], optim.compaction_cap(b)
    folded_s, rows_s, scale_s = start["sub"]
    assert folded_s.r23.shape == (t, cap // 128, 128)
    # beside the fold stage 2 is handed one row vector, no panel
    assert rows_s == () and scale_s.shape == (cap,)
    for fn, args, n_panel in ((stage1, (y,), b * t), (inline, (y,), b * t),
                              (stage2, (start, aux["fin"]), cap * t)):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        assert any(e.primitive.name == "while" for e in jaxpr.eqns)
        assert _panel_relayouts_in_loops(jaxpr, n_panel) == []
    # the detector sees what it is for: the fold-per-call API in a loop
    f32 = jnp.float32
    per_call = jax.make_jaxpr(lambda yv: jax.lax.while_loop(
        lambda acc: acc[0] < 1.0, lambda acc: acc + pk.garch_neg_loglik(
            jnp.full((b, 3), 0.1, f32), yv, interpret=True),
        jnp.zeros((b,), f32)))(y).jaxpr
    assert ("transpose", (b, t)) in _panel_relayouts_in_loops(per_call, b * t)


# One fit a path, digested, on PR 29's parent (commit ebc6e06), f32 under this
# suite's jax_enable_x64 on this container's XLA:CPU; a miss means what
# ``_HW_PIN`` (``test_pallas_hw.py``) says it means.  Re-recorded by PR 50 for
# the reason given there (before: 285, 278, 22242, 22243 iterations; the parent
# with nothing but its two-loop dots rewritten as multiply-and-reduce reads
# this table's 287, 277, 22217, 22219).
_GARCH_PIN = {  # params sha, objective sha, rows converged, sum of iters
    "inline-dense": ("bc40baf06415621c", "1e79c9d5ed435987", 24, 287),
    "inline-ragged": ("45ed254a36d07043", "97c0baacb998f6ab", 24, 277),
    "lazy-dense": ("3999a1d48e61d666", "6584af847dd79ba7", 2042, 22217),
    "lazy-ragged": ("94b59a9c5eebf3fe", "59600f8ea3c2cd2f", 2042, 22219),
}
# the scan backend's digest of inline-dense there: no Pallas code in it, so
# it tells the recording's code generator from another
_GARCH_PIN_HOST = ("dc07ad11499b9407", "1535d07ed973ef1a", 24, 285)


@pytest.mark.parametrize("path", sorted(_GARCH_PIN))
def test_garch_fit_pinned_to_the_fold_per_call_parent(monkeypatch, path):
    # PR 29 moved the mask, the variance seed and the fold out of the
    # optimizer's loops and the likelihood's cotangent into the folded
    # layout; the kernels' arithmetic, their operands and the cotangent's
    # formula are the same, so a fit takes the same path through the
    # optimizer: params and objective bit-equal to the parent's, row for row
    # the same iterations
    from spark_timeseries_tpu.models import garch

    host = _fit_pin_digest(_garch_pin_fit("inline-dense", "scan"))
    if host != _GARCH_PIN_HOST:
        pytest.skip("another XLA:CPU code generator than the recording's")
    if path.startswith("lazy"):
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    assert _fit_pin_digest(_garch_pin_fit(path)) == _GARCH_PIN[path]


@pytest.mark.slow  # tier-1 budget: the big grid runs in ci.sh's unfiltered pass
def test_chunked_garch_matches_scan_long_series():
    from spark_timeseries_tpu.models import garch

    b, t = 3, 2100
    r = _returns_panel(b, t, seed=43)
    params = jnp.asarray(
        np.tile([[0.02, 0.1, 0.8]], (b, 1)).astype(np.float32)
    )
    nv = jnp.asarray([t, t - 1200, t - 41], jnp.int32)
    start = (t - nv).astype(jnp.float32)
    rz = jnp.where(jnp.arange(t)[None, :] >= start[:, None], r, 0.0)

    ref = jax.vmap(lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n))(
        params, rz, nv
    )
    got = pk.garch_neg_loglik(params, rz, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=3e-5)

    def loss_scan(P):
        return jnp.sum(jax.vmap(
            lambda pr, rv, n: garch.neg_log_likelihood(pr, rv, n)
        )(P, rz, nv))

    def loss_pal(P):
        return jnp.sum(pk.garch_neg_loglik(P, rz, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=3e-4, atol=3e-4)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_garch_fit_straggler_compaction_parity(monkeypatch):
    from spark_timeseries_tpu.models import garch

    rng = np.random.default_rng(31)
    r = jnp.asarray((rng.normal(size=(2048, 96)) * 0.1).astype(np.float32))
    ref = garch.fit(r, backend="pallas-interpret", max_iters=13)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got, info = garch.fit(r, backend="pallas-interpret", max_iters=13,
                          count_evals=True)
    assert int(info["cap"]) == 1024
    assert int(info["compact_at"]) < 13
    _dist_parity(ref, got)
    _traced_fit_parity(got, lambda v: garch.fit(
        v, backend="pallas-interpret", max_iters=13, align_mode="dense"), r)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_argarch_lazy_stage2_split_parity(monkeypatch):
    # ISSUE 5 satellite: ARGARCH through optim.lbfgs_batched_stage1/2,
    # matching arima/garch — same parity contract as the tests above
    from spark_timeseries_tpu.models import garch

    rng = np.random.default_rng(33)
    y = jnp.asarray((rng.normal(size=(2048, 96)) * 0.1).astype(np.float32))
    ref = garch.fit_argarch(y, backend="pallas-interpret", max_iters=13,
                            compact=False)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got = garch.fit_argarch(y, backend="pallas-interpret", max_iters=13)
    # the 5-param AR(1)+GARCH objective converges ~37% of rows in a
    # 13-iteration test budget (~760 rows both-converged — still a
    # meaningful parity sample; the quality gates carry the claim)
    _dist_parity(ref, got, conv_floor=0.30)
    _traced_fit_parity(got, lambda v: garch.fit_argarch(
        v, backend="pallas-interpret", max_iters=13, align_mode="dense"), y,
        conv_floor=0.30)
