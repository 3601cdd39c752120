"""The Holt-Winters kernels, additive and multiplicative, against the
portable ``lax.scan`` implementations: the fused objective, the save-resid
forward and the adjoint's panels, the folded fit objective, the fit
programs' fold and the fit-level pin.  Interpret mode, as
``test_pallas_css.py`` says.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import (
    _dist_parity, _fit_pin_digest, _hw_pin_fit, _panel_relayouts_in_loops,
    _seasonal_panel, _traced_fit_parity)
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


def test_hw_sse_and_grad_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 4, 73, 7
    y = _seasonal_panel(b, t, m)
    rng = np.random.default_rng(32)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(params, y)
    got = pk.hw_additive_sse(params, y, m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=1e-3)

    def loss_scan(P):
        return jnp.sum(jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(P, y))

    def loss_pal(P):
        return jnp.sum(pk.hw_additive_sse(P, y, m, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-3, atol=1e-2)


def test_hw_fit_backend_pallas_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 5, 96, 8
    y = _seasonal_panel(b, t, m, seed=33)
    r_scan = hw.fit(y, m, "additive", backend="scan", max_iters=40)
    r_pal = hw.fit(y, m, "additive", backend="pallas-interpret", max_iters=40)
    np.testing.assert_allclose(
        np.asarray(r_pal.params), np.asarray(r_scan.params), rtol=2e-2, atol=2e-2
    )


def test_hw_multiplicative_sse_and_grad_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 4, 73, 7
    y = _seasonal_panel(b, t, m, seed=35) + 25.0  # positive level
    rng = np.random.default_rng(36)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v: hw.sse(pr, v, m, True))(params, y)
    got = pk.hw_sse(params, y, m, True, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=1e-3)

    def loss_scan(P):
        return jnp.sum(jax.vmap(lambda pr, v: hw.sse(pr, v, m, True))(P, y))

    def loss_pal(P):
        return jnp.sum(pk.hw_sse(P, y, m, True, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-3, atol=1e-2)


def _hw_mult_hard_case(case):
    """A few multiplicative rows the two-panel forward and its adjoint
    (ISSUE 45) have to hold: ``past-one-chunk`` (T > 1024: level, trend and
    both rings cross a time chunk, and no neighbour block is read any more),
    ``eps-clamp`` (one hour of every day structurally ZERO, so that slot's
    seasonal factor is 0 from its seed on — ``s_pass`` 0 there — and with
    ``alpha`` = 1 the level is 0 after it — ``l_pass`` 0 from the RECOMPUTED
    level) -> ``(y, params, m)``."""
    rng = np.random.default_rng(451)
    if case == "past-one-chunk":
        b, t, m = 3, 1100, 24
        y = _seasonal_panel(b, t, m, seed=452) + 25.0
        par = rng.uniform(0.05, 0.6, (b, 3))
    else:
        b, t, m = 6, 64, 4
        y = np.array(_seasonal_panel(b, t, m, seed=453)) + 25.0
        y[:, 2::m] = 0.0
        par = rng.uniform(0.05, 0.9, (b, 3))
        par[:2, 0] = 1.0  # nl = y / s: 0 at the zero hour
        par[1:3, 2] = 1.0  # snew = y / nl
    return jnp.asarray(y), jnp.asarray(par.astype(np.float32)), m


@pytest.mark.parametrize("case", ["past-one-chunk", "eps-clamp"])
def test_hw_multiplicative_two_panel_gradient_matches_scan(case):
    from spark_timeseries_tpu.models import holtwinters as hw

    y, params, m = _hw_mult_hard_case(case)

    def scan(P):
        return jax.vmap(lambda pr, v: hw.sse(pr, v, m, True))(P, y)

    def pal(P):
        return pk.hw_sse(P, y, m, True, interpret=True)

    ref, got = np.asarray(scan(params)), np.asarray(pal(params))
    assert np.isfinite(ref).all() and (ref > 0).all()
    np.testing.assert_allclose(got, ref, rtol=5e-4)
    w = jnp.asarray(1.0 / ref)  # every row's gradient at its own scale
    g_ref = np.asarray(jax.grad(lambda P: jnp.sum(w * scan(P)))(params))
    g_got = np.asarray(jax.grad(lambda P: jnp.sum(w * pal(P)))(params))
    assert np.isfinite(g_got).all() and np.abs(g_ref).max() > 0
    np.testing.assert_allclose(g_got, g_ref, rtol=2e-3,
                               atol=2e-3 * np.abs(g_ref).max())
    if case == "eps-clamp":
        # the clamps are AT WORK in these rows: the zero hour's factor is
        # under eps at every visit, and alpha = 1 leaves a level of 0
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, True, None))
        (so3, p3, _), _ = pk._hw_fwd_call_f(True, m, True, True, params, f)
        so, p = (np.asarray(pk._unfold(x, y.shape[0])) for x in (so3, p3))
        assert (so[:, 2::m] < 1e-12).all() and (so[:, 1::m] > 0.1).all()
        lt = np.asarray(pk._hw_mult_level(params[:, :1], y, so, p))
        assert (lt[:2, 2::m] < 1e-12).all() and (lt[3:] > 1.0).all()


@pytest.mark.parametrize("case", ["past-one-chunk", "eps-clamp"])
def test_hw_multiplicative_recomputed_level_is_the_forwards(monkeypatch,
                                                            case):
    # ISSUE 45: the adjoint recomputes L_t from (y_t, S_t, P_t = L_{t-1} +
    # T_{t-1}) by the forward's own expression, and the clamp's subgradient
    # hangs on it.  With beta = 0 and a zero trend seed the trend stays an
    # exact 0, so the forward's carried level — what the replay saved as
    # ``lv3`` — IS the next step's saved P: L_t = P_{t+1} bit for bit, and
    # the recomputation from the two saved panels has to reproduce it
    y, params, m = _hw_mult_hard_case(case)
    if case == "past-one-chunk":  # two chunks, a short interpreted loop
        monkeypatch.setattr(pk, "_CHUNK_T", 16)
        y = y[:, :29]
    b, t = y.shape
    params = params.at[:, 1].set(0.0)
    f = pk.hw_prefold(y, pk.hw_seeds(y, m, True, None))
    f = dataclasses.replace(f, t03=jnp.zeros_like(f.t03))
    (so3, p3, _), par3 = pk._hw_fwd_call_f(True, m, True, True, params, f)
    assert pk._time_layout(t)[2] == (2 if case == "past-one-chunk" else 1)
    lt3 = jax.jit(pk._hw_mult_level)(par3[0], f.y3, so3, p3)
    lt, p = (np.asarray(x)[:t] for x in (lt3, p3))
    assert np.isfinite(lt).all() and np.abs(lt).max() > 1.0
    assert lt[:-1].tobytes() == p[1:].tobytes()


@pytest.mark.parametrize("mult", [False, True])
def test_hw_ragged_sse_and_grad_matches_scan(mult):
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 4, 80, 6
    y = _seasonal_panel(b, t, m, seed=37) + (25.0 if mult else 0.0)
    nv = jnp.asarray([t, t - 11, t - 29, t - 3], jnp.int32)
    # right-aligned convention: zero the invalid prefix (align_right output)
    tt = jnp.arange(t)[None, :]
    y = jnp.where(tt >= (t - nv)[:, None], y, 0.0)
    rng = np.random.default_rng(38)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v, n: hw.sse(pr, v, m, mult, n))(params, y, nv)
    got = pk.hw_sse(params, y, m, mult, nv, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-4, atol=1e-3)

    def loss_scan(P):
        return jnp.sum(jax.vmap(
            lambda pr, v, n: hw.sse(pr, v, m, mult, n))(P, y, nv))

    def loss_pal(P):
        return jnp.sum(pk.hw_sse(P, y, m, mult, nv, interpret=True))

    g_ref = jax.grad(loss_scan)(params)
    g_got = jax.grad(loss_pal)(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=1e-3, atol=1e-2)


@pytest.mark.parametrize("mult,ragged,t", [
    (False, False, 80), (False, True, 80), (True, False, 80),
    (True, True, 80),
    (False, True, 1100),  # two time chunks: the adjoint's ``hp`` path
])
def test_hw_sse_folded_matches_unfolded(mult, ragged, t):
    # the pre-folded objective (hw_prefold + hw_sse_folded) is the fit hot
    # path; it must agree with the fold-per-call API bit-for-bit, and its
    # straggler gather (folded COLUMNS) with a row gather of the panel
    b, m = 5, 6
    y = _seasonal_panel(b, t, m, seed=51) + (25.0 if mult else 0.0)
    nv = None
    if ragged:
        nv = jnp.asarray([t, t - 11, t - 29, t - 3, t - 1], jnp.int32)
        y = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], y, 0.0)
    rng = np.random.default_rng(52)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))
    seeds = pk.hw_seeds(y, m, mult, nv)
    folded = pk.hw_prefold(y, seeds)
    ref = pk.hw_sse_seeded(params, y, seeds, m, mult, interpret=True)
    got = pk.hw_sse_folded(params, folded, m, mult, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    g_ref = jax.grad(lambda P: jnp.sum(
        pk.hw_sse_seeded(P, y, seeds, m, mult, interpret=True)))(params)
    g_got = jax.grad(lambda P: jnp.sum(
        pk.hw_sse_folded(P, folded, m, mult, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=1e-6, atol=1e-6)
    idx = jnp.asarray(rng.integers(0, b, 1024))
    ref_s = pk.hw_sse_seeded(params[idx], y[idx],
                             tuple(x[idx] for x in seeds), m, mult,
                             interpret=True)
    got_s = pk.hw_sse_folded(params[idx], folded.take(idx), m, mult,
                             interpret=True)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(ref_s))


@pytest.mark.parametrize("align_mode", ["dense", "general"])
@pytest.mark.parametrize("model_type", ["additive", "multiplicative"])
def test_hw_fit_programs_fold_outside_their_loops(monkeypatch, align_mode,
                                                  model_type):
    # the CPU's stand-in for "``copy`` left the optimizer's loops" (PERF.md
    # S6, PR 26): the panel is folded once per fit program, so no while
    # body of stage 1, stage 2 or the inline program (with its straggler
    # compaction) relayouts a panel-sized operand
    from spark_timeseries_tpu.models import holtwinters as hw

    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t, m = 2048, 48, 6
    mult = model_type == "multiplicative"
    n_starts = 3 if mult else 1
    y = jax.ShapeDtypeStruct((b, t), jnp.float32)
    static = (m, mult, 13, 1e-4, "pallas-interpret")
    stage1 = hw._fit_stage1_program.__wrapped__(*static, align_mode, n_starts)
    inline = hw._fit_program.__wrapped__(*static, align_mode, False, True,
                                         n_starts)
    stage2 = hw._fit_stage2_program.__wrapped__(*static)
    aux = jax.eval_shape(stage1, y)[1]["starts"][0]
    cap = optim.compaction_cap(b)
    assert aux["sub"][0].y3.shape == (t, cap // 128, 128)
    for fn, arg, n_panel in ((stage1, y, b * t), (inline, y, b * t),
                             (stage2, aux, cap * t)):
        jaxpr = jax.make_jaxpr(fn)(arg).jaxpr
        assert any(e.primitive.name == "while" for e in jaxpr.eqns)
        assert _panel_relayouts_in_loops(jaxpr, n_panel) == []
    # the detector sees what it is for: the fold-per-call API in a loop
    f32 = jnp.float32
    seeds = pk.hw_seeds(jnp.ones((b, t), f32), m, mult, None)
    per_call = jax.make_jaxpr(lambda yv: jax.lax.while_loop(
        lambda acc: acc[0] < 1.0, lambda acc: acc + pk.hw_sse_seeded(
            jnp.full((b, 3), 0.5, f32), yv, seeds, m, mult, interpret=True),
        jnp.zeros((b,), f32)))(y).jaxpr
    assert ("transpose", (b, t)) in _panel_relayouts_in_loops(per_call, b * t)


def test_hw_additive_gradient_moves_one_panel_each_way():
    # ISSUE 43: the additive ``save_resid`` forward writes ONE panel-sized
    # output (the raw one-step errors) and the adjoint call reads ONE
    # panel-sized operand (it).  ISSUE 45: the multiplicative forward writes
    # TWO (the old season, L + T) and its adjoint reads THREE (them and the
    # panel) where the replay wrote four and read five.  The value a
    # gradient pass returns is the value-only call's
    b, t, m = 1024, 29, 4
    par = jnp.asarray(np.random.default_rng(92).uniform(
        0.05, 0.9, (b, 3)).astype(np.float32))
    for mult, wrote, read in ((False, 1, 1), (True, 2, 3)):
        y = _seasonal_panel(b, t, m, seed=91) + (25.0 if mult else 0.0)
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, None))
        sse = functools.partial(pk._hw_ss_f, True, m, mult)
        ones = jnp.ones((b,), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda P: jax.vjp(lambda q: sse(q, f), P)[1](ones))(par)
        fwd, adj = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        n_panel = f.y3.size
        assert sum(v.aval.size >= n_panel for v in fwd.outvars) == wrote
        assert sum(v.aval.size >= n_panel for v in adj.invars) == read
        assert read == pk.HW_ADJOINT_PANELS[mult]
        # what the forward saved is all the adjoint reads of that size, but
        # the multiplicative panel itself
        assert ({v for v in adj.invars if v.aval.size >= n_panel}
                - set(fwd.outvars) == ({fwd.invars[0]} if mult else set()))
        value, _ = jax.vjp(lambda q: sse(q, f), par)
        assert np.asarray(value).tobytes() == np.asarray(sse(par, f)).tobytes()
        assert np.isfinite(np.asarray(value)).all()


# One fit a path, digested, f32 under this suite's jax_enable_x64 on this
# container's XLA:CPU: PR 26's parent (commit 31c2558) for as long as a PR
# leaves the kernels' arithmetic alone; the ``-additive`` lines are PR 43's
# re-recording, the ``-multiplicative`` ones PR 45's (each moved its adjoint's
# rounding on purpose, ``PERF.md`` §6).  A miss means a fit took another path
# through the optimizer, i.e. a value or a gradient moved in its last place:
# a PR that means that re-records the line and says so, any other has a bug.
# PR 50 re-recorded all six (and ``_GARCH_PIN``'s four): the batched two-loop
# recursion is a multiply and a reduce over ``[d, B]`` planes where it was a
# ``vmap`` of ``jnp.dot``, which XLA:CPU contracts otherwise in the last
# place — every fit converges the rows it converged, the iterations summed
# move by under 0.3% (178, 212, 178, 208, 22871, 19043 before), the
# objectives by 1e-4 to 1e-3 of themselves at the 99th row of a hundred; the
# parent with nothing but its dots rewritten reads 178 / 212 / 178 / 207 too.
_HW_PIN = {  # params sha, objective sha, rows converged, sum of iters
    "inline-additive": ("3c3241522fa9e8c6", "bcf5b2c589741063", 24, 178),
    "inline-multiplicative": ("03a4decec774b06c", "8b1bc11ba35849fa", 24, 212),
    "ragged-additive": ("00332880e5b11caa", "9d20f84d8cbfb4bc", 24, 178),
    "ragged-multiplicative": ("acb3ea851d26820b", "756059d1644bc88c", 24, 207),
    "lazy-additive": ("e365a29c6b56aa1a", "6f79a66c8befda36", 2014, 22922),
    "lazy-multiplicative": ("69b352766401175a", "16e4bc469e167d97", 2048, 19083),
}
# the scan backend's digest of inline-additive there: no Pallas code in it,
# so it tells the recording's code generator from another
_HW_PIN_HOST = ("f1b7f4abc1c47fbd", "656b298ad31a845d", 24, 178)


@pytest.mark.parametrize("path", ["inline", "ragged", "lazy"])
@pytest.mark.parametrize("model_type", ["additive", "multiplicative"])
def test_hw_fit_pinned_to_the_fold_per_call_parent(monkeypatch, path,
                                                   model_type):
    # PR 26 moved the fold out of the optimizer's loops; the kernels, their
    # operands and the adjoint's product are the same, so a fit takes the
    # same path through the optimizer: params and objective bit-equal to
    # the parent's, row for row the same iterations.  (The lazy panel is
    # one where XLA:CPU compiles the interpreted kernel alike for both
    # placements of the fold: on 3 of 4 other additive panels tried, f
    # after the first iteration differed in its last bit at the SAME x,
    # and tracing the fold back into the loop reproduced the parent's bits
    # with the new adjoint -- the CPU compiler's contraction choice, which
    # a Mosaic kernel on the chip does not share.)
    from spark_timeseries_tpu.models import holtwinters as hw

    host = _fit_pin_digest(_hw_pin_fit("inline", "additive", "scan"))
    if host != _HW_PIN_HOST:
        pytest.skip("another XLA:CPU code generator than the recording's")
    if path == "lazy":
        monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
        # the pin is the fit of the ONE-LOOP line search: without a
        # ``tail_fun`` stage 1 is still the parent's program, bit for bit.
        # With its tail (ISSUE 39) it is another compiled program whose
        # [cap]-row passes XLA:CPU contracts differently in the last place
        # (tests/test_linesearch_tail.py holds the two row for row)
        from spark_timeseries_tpu.models import lockstep

        monkeypatch.setattr(lockstep, "_straggler_fun",
                            lambda family, p: None)
        build = hw._fit_stage1_program.__wrapped__  # past the program cache
        monkeypatch.setattr(hw, "_fit_stage1_program",
                            lambda *static: jax.jit(build(*static)))
    assert _fit_pin_digest(_hw_pin_fit(path, model_type)) == _HW_PIN[
        f"{path}-{model_type}"]


@pytest.mark.slow  # tier-1 budget: the big grid runs in ci.sh's unfiltered pass
def test_hw_fit_multiplicative_and_ragged_pallas_matches_scan():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 5, 96, 8
    y = np.array(_seasonal_panel(b, t, m, seed=39)) + 25.0
    y[1, :13] = np.nan  # ragged head
    y[3, -9:] = np.nan  # ragged tail
    y = jnp.asarray(y)
    r_scan = hw.fit(y, m, "multiplicative", backend="scan", max_iters=40)
    r_pal = hw.fit(y, m, "multiplicative", backend="pallas-interpret", max_iters=40)
    both = np.asarray(r_scan.converged & r_pal.converged)
    assert both.mean() > 0.5
    np.testing.assert_allclose(
        np.asarray(r_pal.params)[both], np.asarray(r_scan.params)[both],
        rtol=5e-2, atol=5e-2,
    )


@pytest.mark.slow  # tier-1 budget: the big grid runs in ci.sh's unfiltered pass
def test_chunked_hw_matches_scan_long_series():
    from spark_timeseries_tpu.models import holtwinters as hw

    b, t, m = 2, 2112, 24  # 2112 = 88 seasons; > 2 chunks
    y = _seasonal_panel(b, t, m, seed=45)
    rng = np.random.default_rng(46)
    params = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

    ref = jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(params, y)
    got = pk.hw_additive_sse(params, y, m, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=5e-4)

    g_ref = jax.grad(lambda P: jnp.sum(
        jax.vmap(lambda pr, v: hw.sse(pr, v, m, False))(P, y)))(params)
    g_got = jax.grad(lambda P: jnp.sum(pk.hw_additive_sse(P, y, m, interpret=True)))(params)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref), rtol=2e-3, atol=5e-2)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
def test_hw_fit_straggler_compaction_parity(monkeypatch):
    from spark_timeseries_tpu.models import holtwinters as hw

    rng = np.random.default_rng(32)
    tt = np.arange(96, dtype=np.float32)
    w = (10 + 0.02 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 24)
         + 0.3 * rng.normal(size=(2048, 96))).astype(np.float32)
    w = jnp.asarray(w)
    ref = hw.fit(w, 24, "additive", backend="pallas-interpret", max_iters=13)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got, info = hw.fit(w, 24, "additive", backend="pallas-interpret",
                       max_iters=13, count_evals=True)
    assert int(info["cap"]) == 1024
    assert int(info["compact_at"]) < 13
    _dist_parity(ref, got)


@pytest.mark.slow  # minutes-scale interpret-mode sweep: tier-2 (`-m slow`), see pyproject markers
@pytest.mark.parametrize("model_type", ["additive", "multiplicative"])
def test_hw_lazy_stage2_split_parity(monkeypatch, model_type):
    # ISSUE 5 satellite: Holt-Winters through optim.lbfgs_batched_stage1/2
    # with a PER-START carry (the seeded multi-start runs several optimizer
    # passes per fit; multiplicative exercises n_starts=3 and the
    # _merge_starts_program re-merge).  Same distribution-level parity
    # contract as test_arima_lazy_stage2_split_parity — the split is a
    # different set of compiled programs, so bitwise is out of scope.
    from spark_timeseries_tpu.models import holtwinters as hw

    rng = np.random.default_rng(32)
    tt = np.arange(96, dtype=np.float32)
    w = (10 + 0.02 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 24)
         + 0.3 * rng.normal(size=(2048, 96))).astype(np.float32)
    w = jnp.asarray(w)
    ref = hw.fit(w, 24, model_type, backend="pallas-interpret", max_iters=13,
                 compact=False)
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    got = hw.fit(w, 24, model_type, backend="pallas-interpret", max_iters=13)
    _dist_parity(ref, got)
    _traced_fit_parity(got, lambda v: hw.fit(
        v, 24, model_type, backend="pallas-interpret", max_iters=13,
        align_mode="dense"), w)


@pytest.mark.parametrize("mult", [False, True])
def test_hw_seeds_dense_path_matches_general(mult):
    # n_valid=None takes the gather-free static-slice path; it must produce
    # the exact seeds of the general path with a zero start vector
    rng = np.random.default_rng(41)
    tt = np.arange(120, dtype=np.float32)
    y = (10 + 0.05 * tt[None, :] + 2 * np.sin(2 * np.pi * tt[None, :] / 24)
         + 0.2 * rng.normal(size=(7, 120))).astype(np.float32)
    y = jnp.asarray(y)
    nv = jnp.full((7,), 120, jnp.int32)
    dense = pk.hw_seeds(y, 24, mult, None)
    general = pk.hw_seeds(y, 24, mult, nv)
    for d, g in zip(dense, general):
        np.testing.assert_allclose(np.asarray(d), np.asarray(g),
                                   rtol=1e-6, atol=1e-6)
