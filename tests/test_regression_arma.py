"""Regression on a SHARED design with ARMA errors (ISSUE 49):
``regression_arima.fit_shared`` / ``fit_harmonic`` through ``lockstep.fit``
— on ``lax.scan`` and on the interpreted CSS kernels against the plain
reference's profiled optimum (``benchmark/reference/regression_arma_css.py``),
the kernel path's gradient (since ISSUE 51 both design products inside the CSS
kernel calls) against the scan's, the lazy stage pair and its spans, a
journaled walk, what is refused, and what the traced programs move: no ``[B,
T, k]`` array, no panel-sized product beside a kernel call, and the design's
panel moves the stage spans report.  Small, seeded:
64 x 192 with periods (12, 48) and harmonics (3, 2)."""

import collections

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from _obs_helpers import _assert_bitwise, _span_lines
from _pallas_helpers import _dist_parity, _panel_ops, _stage_programs
from benchmark.reference import check
from benchmark.reference import regression_arma_css as ref
from spark_timeseries_tpu import obs
from spark_timeseries_tpu import reliability as rel
from spark_timeseries_tpu.models import lockstep
from spark_timeseries_tpu.models import regression_arima as ra
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim

KW = {"periods": (12, 48), "harmonics": (3, 2), "order": (1, 0, 1)}
K = 1 + 2 * sum(KW["harmonics"])
GAP_MAX = 0.1  # the CSS family's (benchmark/reference/README.md)


def panel(rows, n_time, seed=3):
    """A level, both cycles and ARMA(1,1) noise, one draw of everything a
    row: the benchmark's process in numpy, at these periods."""
    rng = np.random.default_rng(seed)
    x = ref.design(n_time, KW)
    mu = np.exp(rng.uniform(np.log(10), np.log(100), (rows, 1)))
    beta = np.concatenate(
        [mu, 0.15 * mu * rng.normal(size=(rows, K - 1))], axis=1)
    phi = rng.uniform(0.5, 0.9, rows)
    theta = rng.uniform(-0.3, 0.3, rows)
    e = rng.normal(size=(rows, n_time + 50)) * rng.uniform(0.01, 0.05,
                                                           (rows, 1)) * mu
    u = np.zeros_like(e)
    for t in range(1, e.shape[1]):
        u[:, t] = phi * u[:, t - 1] + e[:, t] + theta * e[:, t - 1]
    return jnp.asarray((beta @ x.T + u[:, 50:]).astype(np.float32))


# -- the design ----------------------------------------------------------------


def test_harmonic_columns_are_the_references():
    x = ra.harmonic_design(960, (24, 168), (10, 5))
    assert x.shape == (960, 31) and x.dtype == np.float64
    np.testing.assert_allclose(
        x, ref.design(960, {"periods": (24, 168), "harmonics": (10, 5)}),
        rtol=0, atol=1e-11)
    # the constant, then sin before cos, the day's group before the week's
    np.testing.assert_array_equal(x[:, 0], 1.0)
    t = np.arange(960)
    np.testing.assert_allclose(x[:, 1], np.sin(2 * np.pi * t / 24), atol=1e-12)
    np.testing.assert_allclose(x[:, 22], np.cos(2 * np.pi * t / 168),
                               atol=1e-12)
    # no weekly harmonic is a daily one: the Gram factors
    np.linalg.cholesky(x.T @ x)


@pytest.mark.parametrize("bad,match", [
    (dict(order=(1, 1, 1)), "differenced design"),
    (dict(order=(1, 0, 1, 24)), "seasonal error"),
    (dict(X=np.zeros((8, 192, 3))), "per-row design"),
    (dict(X=np.ones((192, 2))), "collinear"),
    (dict(X=np.ones((100, 2))), "does not pair"),
    (dict(backend="mxu"), "unknown backend"),
    (dict(align_mode="ragged"), "unknown align_mode"),
])
def test_what_is_not_written_raises(bad, match):
    kw = dict(X=ref.design(192, KW), order=(1, 0, 1), backend="scan")
    with pytest.raises(ValueError, match=match):
        ra.fit_shared(panel(8, 192), **{**kw, **bad})


def test_harmonic_arguments_are_checked():
    y = panel(8, 192)
    for kw in (dict(periods=(12,), harmonics=(3, 2)),
               dict(periods=(12,), harmonics=(6,)),
               dict(periods=(12, 24), harmonics=(3, 6))):  # 24/2 = 12/1
        with pytest.raises(ValueError):
            ra.fit_harmonic(y, **kw, backend="scan")
    with pytest.raises(ValueError, match="too short"):
        ra.fit_harmonic(y[:, :16], periods=(12,), harmonics=(5,),
                        backend="scan")
    # the older entry and its dispatcher stand as they were
    with pytest.raises(ValueError, match="unknown method"):
        ra.fit(jnp.zeros(10), jnp.zeros((10, 1)), method="shared")


# -- the fit against the plain reference ---------------------------------------


@pytest.mark.parametrize("backend", ["scan", "pallas-interpret"])
def test_fit_is_within_the_gap_of_the_profiled_optimum(backend):
    y = panel(64, 192)
    res = ra.fit_shared(y, X=ref.design(192, KW), order=KW["order"],
                        backend=backend)
    assert res.params.shape == (64, K + 2) and bool(res.converged.all())
    rows = slice(0, 16)
    gaps = check.loglik_gaps(ref, KW, np.asarray(y)[rows],
                             np.asarray(res.params)[rows])
    assert gaps.max() <= GAP_MAX, gaps
    # the reported objective is the concentrated likelihood of the
    # reference's sum of squares at the fitted point
    for par, row, nll in zip(np.asarray(res.params)[:4], np.asarray(y)[:4],
                             np.asarray(res.neg_log_likelihood)[:4]):
        ss, n_eff = ref.objective(par, row, KW)
        assert n_eff == 191
        assert nll == pytest.approx(
            0.5 * n_eff * (np.log(2 * np.pi * ss / n_eff) + 1), rel=2e-4)
    # one series, and the entry a configuration names
    one = ra.fit_harmonic(y[0], **KW, backend=backend)
    np.testing.assert_allclose(one.params, res.params[0], rtol=1e-3,
                               atol=2e-3)  # each stops where its own f32 does
    # the start alone is not the answer: the optimizer moved the rows
    assert int(np.asarray(res.iters).max()) > 3


def test_rows_are_never_shifted_and_a_gap_excludes_its_row():
    y = np.array(panel(8, 192))
    y[1, :7] = np.nan
    y[5, -3:] = np.nan
    res = ra.fit_harmonic(jnp.asarray(y), **KW, backend="scan")
    from spark_timeseries_tpu.reliability.status import FitStatus

    status = np.asarray(res.status)
    assert list(np.nonzero(status == FitStatus.EXCLUDED)[0]) == [1, 5]
    assert np.isnan(np.asarray(res.params)[[1, 5]]).all()
    dense = ra.fit_harmonic(jnp.asarray(np.delete(y, [1, 5], axis=0)), **KW,
                            backend="scan")
    np.testing.assert_allclose(np.delete(np.asarray(res.params), [1, 5], 0),
                               dense.params, rtol=1e-5, atol=1e-5)


# -- the kernel path's gradient: the data cotangent through the product --------


def _prepared(backend, y, x):
    operands = ra._design_operands(x, y.shape[1], y.dtype)
    family = ra._shared_family(KW["order"], backend, "dense", operands[0])
    return family, family.prep(y, *operands)


@pytest.mark.parametrize("n_time", [192, 77])  # 77: a padded tail of 3 rows
def test_kernel_gradient_is_the_scans(n_time):
    y = panel(24, n_time, seed=9)
    x = ref.design(n_time, KW)
    fam_k, pk_ = _prepared("pallas-interpret", y, x)
    fam_s, ps = _prepared("scan", y, x)
    rng = np.random.default_rng(1)
    point = jnp.asarray(np.concatenate([
        rng.normal(scale=0.3, size=(24, K)), rng.uniform(0.2, 0.8, (24, 1)),
        rng.uniform(-0.4, 0.4, (24, 1))], axis=1), jnp.float32)
    f_k, g_k = jax.value_and_grad(
        lambda v: jnp.sum(fam_k.objective(pk_.folded, pk_.rows)(v)))(point)
    f_s, g_s = jax.value_and_grad(lambda v: jnp.sum(jax.vmap(
        fam_s.scan_objective)(v, ps.series)))(point)
    assert float(f_k) == pytest.approx(float(f_s), rel=1e-5)
    g_k, g_s = np.asarray(g_k), np.asarray(g_s)
    for cols in (slice(0, K), slice(K, K + 1), slice(K + 1, K + 2)):
        scale = np.abs(g_s[:, cols]).max()
        np.testing.assert_allclose(g_k[:, cols], g_s[:, cols], rtol=2e-3,
                                   atol=2e-4 * scale)
    # both preps agree on the start, the units and the change of variables
    for a, b in zip(pk_.natural + pk_.x0s, ps.natural + ps.x0s):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


# -- the lazy pair, its spans, the walk ----------------------------------------

LAZY = dict(**KW, backend="pallas-interpret")


@pytest.fixture()
def low_gate(monkeypatch):
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)


def test_compaction_engages_and_the_spans_say_the_design(low_gate, tmp_path):
    y = panel(2048, 96, seed=5)
    path = str(tmp_path / "obs.jsonl")
    obs.enable(path)
    try:
        lazy = rel.resilient_fit(ra.fit_harmonic, y, **LAZY)
    finally:
        obs.disable()
    spans = collections.defaultdict(list)
    for s in _span_lines(path):
        spans[s["name"]].append(s)
    # once a call, the ladder's calls on the few rows it retries included
    design = spans["fit.design"][0]
    assert all(d["attrs"] == {"periods": [12, 48], "harmonics": [3, 2],
                              "columns": K} for d in spans["fit.design"])
    (primary,) = spans["fit.primary"]
    assert design["parent"] == primary["id"]
    (stage1,) = spans["fit.stage1"]
    said = {"xreg_columns": K, "xreg_panel_moves": ra.XREG_PANEL_MOVES,
            "lag_terms": 2, "lag_span": 1,
            "adjoint_panels": pk.CSS_ADJOINT_PANELS}
    assert said.items() <= stage1["attrs"].items()
    assert stage1["attrs"]["rows"] == 2048 and stage1["attrs"]["undone"] > 0
    # the calls that take the design hold more in VMEM than arima's (the
    # residual, the data cotangent), and the rule that sizes their blocks
    # knows: both widths are the rule's WITH the design
    for attr, mode in (("series_block", "sum"),
                       ("adjoint_series_block", "adjoint")):
        assert stage1["attrs"][attr] == pk.css_series_block(
            2048, 96, KW["order"], mode, design=K)
    (stage2,) = spans["fit.stage2"]
    assert stage2["attrs"]["rows"] == optim.compaction_cap(2048)
    assert said.items() <= stage2["attrs"].items()
    (readback,) = spans["fit.readback"]
    assert readback["attrs"]["stage2_iters"] > 0
    # the lazy pair lands where the uncompacted program does
    assert np.asarray(lazy.converged).mean() > 0.99
    _dist_parity(ra.fit_harmonic(y, **LAZY, compact=False), lazy)
    gaps = check.loglik_gaps(ref, KW, np.asarray(y)[:8],
                             np.asarray(lazy.params)[:8])
    assert gaps.max() <= GAP_MAX, gaps


def test_journaled_walk_rereads_bitwise(tmp_path):
    y = panel(64, 192)
    kw = dict(**KW, backend="scan", chunk_rows=16,
              checkpoint_dir=str(tmp_path / "journal"))
    first = rel.fit_chunked(ra.fit_harmonic, y, **kw)
    again = rel.fit_chunked(ra.fit_harmonic, y, **kw)
    assert again.meta["journal"]["chunks_resumed"] == 4
    _assert_bitwise(first, again)
    assert first.meta["status_counts"]["OK"] == 64
    # a chunk of the walk is the fit of its rows
    direct = ra.fit_harmonic(y[16:32], **KW, backend="scan")
    np.testing.assert_allclose(first.params[16:32], direct.params, rtol=1e-5,
                               atol=1e-5)


# -- what the traced programs move ---------------------------------------------


def _programs(b, t):
    """Stage 1, the inline program and stage 2 as ``(fn, args, rows)``, on
    shapes alone (``_pallas_helpers._stage_programs``' family)."""
    assert K == 11  # the helper's design is this file's
    return _stage_programs("harmonic-arma", b, t)[1]


def _largest(jaxpr):
    """The largest array any equation of ``jaxpr`` forms, nested jaxprs
    included (kernel bodies aside: a ``pallas_call`` works on blocks)."""
    size = 0
    for eqn in jaxpr.eqns:
        size = max([size] + [v.aval.size for v in eqn.outvars])
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                size = max(size, _largest(sub))
    return size


def _design_moves(jaxpr, n_panel):
    """Per objective gradient of ``jaxpr`` at any depth, the panel-sized
    operands and results the DESIGN adds around the CSS pair, counted against
    a plain fit's pair (the forward reads the panel and writes the errors, the
    adjoint reads both and writes planes): here the both-mode forward writes
    ONE panel more, the residual it formed, and the adjoint reads it where a
    plain fit's reads the panel.  No panel-sized ``dot_general`` or ``add``
    feeds a CSS ``pallas_call`` or reads one's result (the composition's
    residual and its ``x' g_u``), and a value-only call (the line search's)
    reads the ONE panel and writes none — or, the start's once a program,
    the residual alone for the Hannan-Rissanen kernels."""
    big = lambda v: (not isinstance(v, jax.extend.core.Literal)  # noqa: E731
                     and v.aval.size >= n_panel)
    made = {v: eqn for eqn in jaxpr.eqns for v in eqn.outvars}
    readers = collections.defaultdict(list)
    for eqn in jaxpr.eqns:
        for v in filter(big, eqn.invars):
            readers[v].append(eqn)
    found, wrote = [], set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _design_moves(sub, n_panel)
            continue
        if "_css_" not in eqn.params["jaxpr"].debug_info.func_name:
            continue
        panels_in = list(filter(big, eqn.invars))
        panels_out = list(filter(big, eqn.outvars))
        for v in panels_in:
            assert v not in made or made[v].primitive.name not in (
                "dot_general", "add"), made[v]
        for v in panels_out:
            assert not [r for r in readers[v] if r.primitive.name in (
                "dot_general", "add", "neg")], readers[v]
        if wrote & set(panels_in):  # the adjoint: it reads the saved errors
            assert set(panels_in) <= wrote
            assert not panels_out  # no data cotangent leaves the kernel
            # beyond a plain pair's: the errors out, the panel and they in
            found.append(len(forward_out) - 1 + len(panels_in) - 2)
        elif len(panels_out) > 1:  # a gradient's forward: the errors, and u
            forward_out = panels_out
            assert len(panels_in) == 1
        wrote.update(panels_out)
    return found


def test_programs_form_no_per_row_design_and_move_what_the_spans_say(
        low_gate):
    b, t = 2048, 96
    for fn, args, rows in _programs(b, t):
        jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
        # never a [B, T, k] array: nothing is a third of one
        assert _largest(jaxpr) < rows * t * K // 3
        moves = _design_moves(jaxpr, rows * t)
        assert moves, "every program takes gradients"
        assert set(moves) == {ra.XREG_PANEL_MOVES} == {1}
        # XLA forms no panel-sized product or sum anywhere in the program
        assert _panel_ops(jaxpr.eqns, rows * t, ("dot_general", "add")) == []
    # the fit-level statement of the same, on the portable objective
    y = jax.ShapeDtypeStruct((64, t), jnp.float32)
    scan = ra._shared_fit_program.__wrapped__(KW["order"], "scan", 13, 1e-4,
                                              "dense", True)
    assert _largest(jax.make_jaxpr(scan)(y, *_programs(b, t)[0][1][1:]).jaxpr
                    ) < 64 * t * K // 3


def test_other_families_programs_take_no_notice():
    # ``Prepared.natural`` is empty for every family but this one: their
    # ``fin`` carries no leaf more and ``to_natural`` is called as before
    assert lockstep.Prepared((), None, None, ()).natural == ()
    res = optim.LBFGSResult(jnp.ones((2, 3)), jnp.ones(2),
                            jnp.ones(2, bool), jnp.ones(2, jnp.int32),
                            jnp.ones(2))
    out = lockstep.finalize(res, jnp.array([True, False]), jnp.full(2, 7.0),
                            (), lambda v: 2 * v)
    np.testing.assert_array_equal(out.params[0], 2.0)
    assert np.isnan(np.asarray(out.params[1])).all()
    assert float(out.neg_log_likelihood[0]) == 7.0
