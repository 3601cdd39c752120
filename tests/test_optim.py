"""Optimizer tests: convergence on classic problems, batched via vmap."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spark_timeseries_tpu.utils import optim


class TestLBFGS:
    def test_quadratic(self):
        A = jnp.asarray(np.diag([1.0, 10.0, 100.0]))
        b = jnp.asarray([1.0, -2.0, 3.0])
        res = optim.minimize_lbfgs(lambda x: 0.5 * x @ A @ x - b @ x, jnp.zeros(3))
        np.testing.assert_allclose(np.asarray(res.x), np.linalg.solve(np.asarray(A), b), atol=1e-5)
        assert bool(res.converged)

    def test_rosenbrock(self):
        def rosen(x):
            return jnp.sum(100.0 * (x[1:] - x[:-1] ** 2) ** 2 + (1.0 - x[:-1]) ** 2)

        res = optim.minimize_lbfgs(rosen, jnp.zeros(4), max_iters=200)
        np.testing.assert_allclose(np.asarray(res.x), np.ones(4), atol=1e-4)

    def test_vs_scipy(self):
        from scipy.optimize import minimize as sp_minimize

        def f_np(x):
            return float(np.sum((x - np.array([3.0, -1.0])) ** 4) + np.sum(x**2))

        def f_jnp(x):
            return jnp.sum((x - jnp.asarray([3.0, -1.0])) ** 4) + jnp.sum(x**2)

        sp = sp_minimize(f_np, np.zeros(2), method="L-BFGS-B")
        res = optim.minimize_lbfgs(f_jnp, jnp.zeros(2), max_iters=100, tol=1e-8)
        np.testing.assert_allclose(np.asarray(res.x), sp.x, atol=1e-3)

    def test_batched_independent_problems(self):
        # each row solves min (x - target_i)^2 with its own target
        targets = jnp.asarray(np.arange(6.0).reshape(6, 1))
        res = optim.batched_minimize(
            lambda x, t: jnp.sum((x - t) ** 2),
            jnp.zeros((6, 1)),
            targets,
        )
        np.testing.assert_allclose(np.asarray(res.x), np.asarray(targets), atol=1e-6)
        assert bool(jnp.all(res.converged))

    def test_nonfinite_guard(self):
        # objective returns NaN away from a basin: solver must not blow up
        def f(x):
            v = jnp.sum(x**2)
            return jnp.where(v < 100.0, v + jnp.sum(jnp.log(x + 10.0)), jnp.nan)

        res = optim.minimize_lbfgs(f, jnp.asarray([5.0]), max_iters=60)
        assert bool(jnp.isfinite(res.f))

    def test_interval_transforms(self):
        u = jnp.linspace(-5, 5, 11)
        x = optim.sigmoid_to_interval(u, 0.1, 0.9)
        assert float(x.min()) > 0.1 and float(x.max()) < 0.9
        back = optim.interval_to_sigmoid(x, 0.1, 0.9)
        np.testing.assert_allclose(np.asarray(back), np.asarray(u), atol=1e-5)

    def test_returned_f_is_best_seen(self):
        # ADVICE r3: the noise-floor-relaxed accept may adopt a step that
        # RAISES f slightly; the returned (x, f) must be the best visited
        # point, so f(returned) <= f(x0) and f == fun(x) exactly
        rng = np.random.default_rng(31)
        targets = jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))

        def fun_b(X):
            return jnp.sum((X - targets) ** 2, axis=-1)

        x0 = jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32) * 3)
        res = optim.minimize_lbfgs_batched(fun_b, x0, max_iters=50)
        f0 = fun_b(x0)
        assert bool(jnp.all(res.f <= f0 + 1e-6))
        np.testing.assert_allclose(
            np.asarray(fun_b(res.x)), np.asarray(res.f), rtol=1e-6, atol=1e-6
        )
        # per-series variant holds the same contract
        one = optim.minimize_lbfgs(
            lambda x: jnp.sum((x - targets[0]) ** 2), x0[0], max_iters=50
        )
        assert float(one.f) <= float(fun_b(x0)[0]) + 1e-6
        np.testing.assert_allclose(
            float(jnp.sum((one.x - targets[0]) ** 2)), float(one.f), rtol=1e-6
        )


def _straggler_problem(bsz=64, d=3, seed=0, spread=True):
    rng = np.random.default_rng(seed)
    # per-row quartic bowls with very different conditioning so rows
    # converge at very different iterations (stragglers exist); with
    # spread=False every row is the SAME well-conditioned problem, so the
    # whole batch converges on one iteration (no stragglers ever remain)
    if spread:
        scales = jnp.asarray(
            rng.uniform(0.05, 50.0, size=(bsz, d)).astype(np.float32))
        target = jnp.asarray(rng.normal(size=(bsz, d)).astype(np.float32))
    else:
        scales = jnp.ones((bsz, d), jnp.float32)
        target = jnp.broadcast_to(
            jnp.asarray(rng.normal(size=(1, d)).astype(np.float32)),
            (bsz, d))

    def fb_rows(x, sc, tg):
        r = (x - tg) * sc
        return jnp.sum(r**2 + 0.1 * r**4, axis=-1)

    fun = lambda x: fb_rows(x, scales, target)

    def straggler_fun(idx):
        sc, tg = scales[idx], target[idx]
        return lambda x: fb_rows(x, sc, tg)

    x0 = jnp.zeros((bsz, d), jnp.float32)
    return fun, straggler_fun, x0, target


class TestStragglerCompaction:
    """minimize_lbfgs_batched with straggler compaction must reproduce the
    uncompacted run exactly: per-row trajectories are independent of batch
    composition, so gathering the unconverged tail changes where rows live,
    not what they compute."""

    def _problem(self, bsz=64, d=3, seed=0):
        return _straggler_problem(bsz=bsz, d=d, seed=seed)

    def test_matches_uncompacted(self):
        fun, straggler_fun, x0, _ = self._problem()
        ref = optim.minimize_lbfgs_batched(fun, x0, max_iters=80)
        got = optim.minimize_lbfgs_batched(
            fun, x0, max_iters=80, straggler_fun=straggler_fun,
            straggler_cap=16)
        np.testing.assert_array_equal(np.asarray(ref.converged),
                                      np.asarray(got.converged))
        np.testing.assert_allclose(np.asarray(ref.x), np.asarray(got.x),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(ref.f), np.asarray(got.f),
                                   rtol=0, atol=0)
        np.testing.assert_array_equal(np.asarray(ref.iters),
                                      np.asarray(got.iters))

    def test_compaction_engages_and_counts(self):
        fun, straggler_fun, x0, _ = self._problem()
        got, info = optim.minimize_lbfgs_batched(
            fun, x0, max_iters=80, straggler_fun=straggler_fun,
            straggler_cap=16, count_evals=True)
        assert int(info["cap"]) == 16
        # with wildly mixed conditioning the batch cannot finish before the
        # straggler count drops under the cap, so compaction must engage
        # strictly before the final iteration
        assert int(info["compact_at"]) < int(np.asarray(got.iters).max())
        assert bool(np.asarray(got.converged).all())

    def test_cap_larger_than_stragglers_is_safe(self):
        fun, straggler_fun, x0, _ = self._problem(bsz=8)
        got = optim.minimize_lbfgs_batched(
            fun, x0, max_iters=80, straggler_fun=straggler_fun,
            straggler_cap=6)
        ref = optim.minimize_lbfgs_batched(fun, x0, max_iters=80)
        np.testing.assert_allclose(np.asarray(ref.x), np.asarray(got.x),
                                   rtol=0, atol=0)

    def test_under_jit(self):
        # compare compacted vs uncompacted under the SAME compilation
        # context (one outer jit each): eager-vs-jit comparisons differ by
        # fma/fusion reassociation noise that ill-conditioned rows amplify,
        # which is orthogonal to compaction
        fun, straggler_fun, x0, _ = self._problem()

        @jax.jit
        def run_compact(x0):
            return optim.minimize_lbfgs_batched(
                fun, x0, max_iters=60, straggler_fun=straggler_fun,
                straggler_cap=16)

        @jax.jit
        def run_plain(x0):
            return optim.minimize_lbfgs_batched(fun, x0, max_iters=60)

        ref = run_plain(x0)
        got = run_compact(x0)
        both = np.asarray(ref.converged) & np.asarray(got.converged)
        assert both.mean() > 0.9
        np.testing.assert_allclose(np.asarray(ref.x)[both],
                                   np.asarray(got.x)[both],
                                   rtol=2e-4, atol=2e-4)


class TestLazyStage2Split:
    """``minimize_lbfgs_batched`` with a ``straggler_fun`` IS stage 1
    followed by stage 2 in one trace; run apart (a model fit's lazily
    compiled pair) they must return the same bits and the same pass
    counts — only WHERE the stage-2 program is traced/compiled moves (to
    the first call that actually has stragglers)."""

    def test_split_matches_inline_compaction(self):
        fun, straggler_fun, x0, _ = _straggler_problem()
        ref = optim.minimize_lbfgs_batched(
            fun, x0, max_iters=80, straggler_fun=straggler_fun,
            straggler_cap=16)
        res1, carry = optim.lbfgs_batched_stage1(
            fun, x0, straggler_cap=16, max_iters=80)
        # mixed conditioning leaves stragglers at stage-1 exit
        assert int(carry.undone) > 0
        assert int(carry.k) < 80
        got = optim.lbfgs_batched_stage2(
            straggler_fun(carry.idxc), res1, carry, max_iters=80)
        np.testing.assert_array_equal(np.asarray(ref.converged),
                                      np.asarray(got.converged))
        np.testing.assert_allclose(np.asarray(ref.x), np.asarray(got.x),
                                   rtol=0, atol=0)
        np.testing.assert_allclose(np.asarray(ref.f), np.asarray(got.f),
                                   rtol=0, atol=0)
        np.testing.assert_array_equal(np.asarray(ref.iters),
                                      np.asarray(got.iters))
        np.testing.assert_allclose(np.asarray(ref.grad_norm),
                                   np.asarray(got.grad_norm),
                                   rtol=0, atol=0)

    def test_split_counts_the_passes_of_the_composed_fit(self):
        # count_evals adds a carry element and selects no program: the
        # counted split returns the uncounted split's result, and stage 1's
        # history, handed on by the carry and finished by stage 2, is the
        # info of the composed call
        fun, straggler_fun, x0, _ = _straggler_problem()
        ref, info = optim.minimize_lbfgs_batched(
            fun, x0, max_iters=80, straggler_fun=straggler_fun,
            straggler_cap=16, count_evals=True)
        plain1, plain_carry = optim.lbfgs_batched_stage1(
            fun, x0, straggler_cap=16, max_iters=80)
        assert plain_carry.ls_hist is None
        plain = optim.lbfgs_batched_stage2(
            straggler_fun(plain_carry.idxc), plain1, plain_carry,
            max_iters=80)
        res1, carry = optim.lbfgs_batched_stage1(
            fun, x0, straggler_cap=16, max_iters=80, count_evals=True)
        at = int(carry.k)
        hist1 = np.asarray(carry.ls_hist)
        assert hist1[:at].min() >= 1 and not hist1[at:].any()
        assert set(optim.pass_info(carry)) == set(info)
        got, got_info = optim.lbfgs_batched_stage2(
            straggler_fun(carry.idxc), res1, carry, max_iters=80)
        for a, b, c in zip(ref, got, plain):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert int(got_info["cap"]) == int(info["cap"]) == 16
        assert int(got_info["compact_at"]) == int(info["compact_at"]) == at
        np.testing.assert_array_equal(np.asarray(got_info["ls_evals"]),
                                      np.asarray(info["ls_evals"]))
        hist = np.asarray(got_info["ls_evals"])
        np.testing.assert_array_equal(hist[:at], hist1[:at])
        assert hist[at:].any()  # stage 2's passes are counted too

    def test_no_stragglers_means_no_stage2(self):
        # uniform conditioning: every row converges on the same iteration,
        # so the straggler count jumps straight from "all" to zero and the
        # host gate (carry.undone == 0) skips — and therefore never
        # compiles — stage 2; stage 1's result must already be final
        fun, straggler_fun, x0, _ = _straggler_problem(spread=False)
        ref = optim.minimize_lbfgs_batched(
            fun, x0, max_iters=80, straggler_fun=straggler_fun,
            straggler_cap=16)
        res1, carry = optim.lbfgs_batched_stage1(
            fun, x0, straggler_cap=16, max_iters=80)
        assert int(carry.undone) == 0
        np.testing.assert_allclose(np.asarray(ref.x), np.asarray(res1.x),
                                   rtol=0, atol=0)
        np.testing.assert_array_equal(np.asarray(ref.converged),
                                      np.asarray(res1.converged))
        np.testing.assert_array_equal(np.asarray(ref.iters),
                                      np.asarray(res1.iters))

    def test_stage1_requires_compacting_cap(self):
        fun, _, x0, _ = _straggler_problem(bsz=8)
        with pytest.raises(ValueError, match="straggler_cap"):
            optim.lbfgs_batched_stage1(fun, x0, straggler_cap=8, max_iters=10)

    def test_exhausted_budget_stage2_is_identity(self):
        # stage 1 exits at max_iters with > cap rows undone: the truncated
        # gather is benign because stage 2 shares the exhausted budget —
        # dispatching it anyway must scatter the state back unchanged
        fun, straggler_fun, x0, _ = _straggler_problem()
        res1, carry = optim.lbfgs_batched_stage1(
            fun, x0, straggler_cap=4, max_iters=3)
        assert int(carry.k) == 3 and int(carry.undone) > 4
        got = optim.lbfgs_batched_stage2(
            straggler_fun(carry.idxc), res1, carry, max_iters=3)
        np.testing.assert_allclose(np.asarray(res1.x), np.asarray(got.x),
                                   rtol=0, atol=0)
        np.testing.assert_array_equal(np.asarray(res1.iters),
                                      np.asarray(got.iters))


# -- the batched optimizer against the per-series one (ISSUE 50) --------------
#
# ``minimize_lbfgs_batched`` holds its state with the rows on the last axis
# (``x [d, B]``, the history ``[m, d, B]``) and runs a two-loop recursion
# written for the batch; ``jax.vmap(minimize_lbfgs)`` is the per-series
# algorithm it has to be, at a narrow and at a wide ``d``.


def _rows_problem(kind, d, bsz=256, seed=0):
    """``(row objective f(x[..., d], *data), data [B, ...], x0 [B, d])``: an
    ill-scaled quadratic (curvatures log-uniform over 0.1..10 per row and
    coordinate) or a Rosenbrock-like chain with per-row constants."""
    rng = np.random.default_rng(seed)
    if kind == "quadratic":
        data = (np.exp(rng.uniform(np.log(0.1), np.log(10.0), (bsz, d))),
                rng.normal(size=(bsz, d)))
        row = lambda x, a, c: 0.5 * jnp.sum(a * (x - c) ** 2, axis=-1)  # noqa: E731
    else:
        data = (rng.uniform(0.5, 2.0, (bsz, 1)),
                rng.uniform(0.5, 1.5, (bsz, 1)))
        row = lambda x, b, c: jnp.sum(  # noqa: E731
            b * (x[..., 1:] - x[..., :-1] ** 2) ** 2
            + (c - x[..., :-1]) ** 2, axis=-1)
    data = tuple(jnp.asarray(a.astype(np.float32)) for a in data)
    return row, data, jnp.zeros((bsz, d), jnp.float32)


_ROWS_CASES = [(kind, d) for kind in ("quadratic", "rosenbrock")
               for d in (3, 33)]


@pytest.mark.parametrize("kind,d", _ROWS_CASES)
def test_batched_is_the_vmapped_per_series_optimizer(kind, d):
    # the f32 tolerance, stated: the two are other compiled programs and sum
    # a row's d products in another order, so a row at the edge of a
    # stopping test may take another iteration — ``converged`` is equal on
    # every row, ``iters`` on 19 rows of 20 and never 8 apart, ``f`` to 1e-3
    # and ``x`` to 2e-2 (5e-3 on 99 rows of 100) of optima of order one
    row, data, x0 = _rows_problem(kind, d)
    knobs = dict(max_iters=200, tol=1e-4)
    got = jax.jit(lambda x0: optim.minimize_lbfgs_batched(
        lambda x: row(x, *data), x0, **knobs))(x0)
    ref = jax.jit(jax.vmap(lambda x, *r: optim.minimize_lbfgs(
        lambda p: row(p, *r), x, **knobs)))(x0, *data)
    assert got.x.shape == (256, d) and got.grad_norm.shape == (256,)
    assert bool(jnp.all(ref.converged))
    np.testing.assert_array_equal(np.asarray(got.converged),
                                  np.asarray(ref.converged))
    apart = np.abs(np.asarray(got.iters) - np.asarray(ref.iters))
    assert (apart == 0).mean() >= 0.95 and apart.max() < 8
    np.testing.assert_allclose(np.asarray(got.f), np.asarray(ref.f),
                               rtol=1e-3, atol=1e-3)
    dx = np.abs(np.asarray(got.x) - np.asarray(ref.x)).max(axis=-1)
    assert dx.max() < 2e-2 and np.quantile(dx, 0.99) < 5e-3


@pytest.mark.parametrize("kind,d", _ROWS_CASES)
def test_two_stages_are_the_one_stage_fit_on_the_same_rows(kind, d):
    # stage 1 -> compaction -> stage 2, run apart as a model fit runs them,
    # against the uncompacted loop: the gather moves rows, it computes
    # nothing, so on the CPU the results are the same bits; the carried
    # state has its rows LAST, the history a ring of ``[d, cap]`` planes
    row, data, x0 = _rows_problem(kind, d)
    knobs = dict(max_iters=200, tol=1e-4)
    m, cap = 8, 32
    fun = lambda x: row(x, *data)  # noqa: E731
    sub_fun = lambda idxc: (  # noqa: E731
        lambda x: row(x, *(a[idxc] for a in data)))
    ref = optim.minimize_lbfgs_batched(fun, x0, **knobs)
    res1, carry = optim.lbfgs_batched_stage1(
        fun, x0, straggler_cap=cap, tail_fun=sub_fun, **knobs)
    assert 0 < int(carry.undone) <= cap and int(carry.k) < 200
    state = carry.state
    assert state.s_hist.shape == state.y_hist.shape == (m, d, cap)
    assert state.rho_hist.shape == (m, cap)
    assert all(getattr(state, name).shape == (d, cap)
               for name in ("x", "g", "bx", "bg"))
    assert all(getattr(state, name).shape == (cap,)
               for name in ("f", "bf", "tprev", "converged", "failed"))
    assert res1.x.shape == (256, d)
    got = optim.lbfgs_batched_stage2(sub_fun(carry.idxc), res1, carry,
                                     **knobs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
