"""The objective's cotangent is formed INSIDE the adjoint kernels (PR 35): the
folded objectives hand their adjoint the plane ``gb3``, not a panel.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import (
    _garch_params, _returns_panel, _scan_nll, _seasonal_panel, _sha)
from spark_timeseries_tpu.ops import pallas_kernels as pk


def _cotangent_case(family, variant, ragged, nchunk):
    """One small gradient of a folded fit objective -> ``(chunk, run)``:
    ``_CHUNK_T`` to patch (``nchunk`` time chunks) and ``run() -> dict`` of
    gradients — ``plane_*`` through the objective's ``custom_vjp`` (its
    adjoint forms the cotangent), ``panel_*`` the same gradient composed
    from the general-cotangent ``custom_vjp`` where the family has one
    (``css_errors``, ``garch_variances``), ``scan_*`` the scan backend's."""
    b, m = 40, 4
    rng = np.random.default_rng(351)
    w = jnp.asarray(rng.normal(size=b).astype(np.float32))
    chunk = 64 if variant == "lagset" else 16
    t = (chunk if nchunk == 1 else 2 * chunk) - 3
    nv = (jnp.asarray(rng.integers(t - 4, t + 1, b), jnp.int32) if ragged
          else jnp.full((b,), t, jnp.int32))
    start = (t - nv).astype(jnp.float32)
    live = jnp.arange(t)[None, :] >= start[:, None]
    if family == "css":
        p, q = ((), (1, 24, 25)) if variant == "lagset" else (1, 1)
        k = 1 + len(pk._lags(p)) + len(pk._lags(q))
        yd = jnp.where(live, jnp.asarray(
            rng.normal(size=(b, t)).astype(np.float32)), 0.0)
        par = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.3)
        zb = start + pk._span(pk._lags(p))

        def run():
            y3, zb3 = pk.css_prefold(yd, (pk._span(pk._lags(p)), 0, 0), nv)
            g_p, g_y3 = jax.grad(lambda P, Y3: jnp.sum(w * pk._css_ss_f(
                p, q, True, t, b, P, Y3, zb3)), argnums=(0, 1))(par, y3)
            g_only = jax.grad(lambda P: jnp.sum(w * pk._css_ss_f(
                p, q, True, t, b, P, y3, zb3)))(par)
            r_p, r_y = jax.grad(lambda P, Y: jnp.sum(w * jnp.sum(
                pk.css_errors(p, q, True, P, Y, zb) ** 2, axis=1)),
                argnums=(0, 1))(par, yd)
            return {"plane_params": g_only, "plane_params_gy": g_p,
                    "plane_data": pk._unfold(g_y3, b)[:, :t],
                    "panel_params": r_p, "panel_data": r_y}
    elif family == "garch":
        r = jnp.where(live, _returns_panel(b, t, seed=352), 0.0)
        par = _garch_params(b, 353)

        def general(P, rv):
            # the likelihood written over ``garch_variances``, masked and
            # seeded as ``garch_prefold`` has it
            rz = jnp.where(live, rv, 0.0)
            nvf = nv.astype(rv.dtype)
            mean = jnp.sum(rz, axis=1) / nvf
            h0 = jnp.sum(jnp.where(live, (rz - mean[:, None]) ** 2, 0.0),
                         axis=1) / nvf
            hc = jnp.maximum(pk.garch_variances(P, rz, h0, start,
                                                interpret=True), 1e-12)
            return jnp.sum(w * jnp.sum(jnp.where(
                live, jnp.log(2.0 * jnp.pi * hc) + rz * rz / hc, 0.0),
                axis=1))

        def run():
            f = pk.garch_prefold(r, nv)
            g_only = jax.grad(lambda P: jnp.sum(
                w * pk._garch_ll_f(True, P, f)))(par)
            g_p, g_r = jax.grad(lambda P, rv: jnp.sum(w * pk._garch_ll_f(
                True, P, pk.garch_prefold(rv, nv))), argnums=(0, 1))(par, r)
            r_p, r_r = jax.grad(general, argnums=(0, 1))(par, r)
            s_p = jax.grad(lambda P: 2.0 * jnp.sum(
                w * _scan_nll(P, r, nv)))(par)
            return {"plane_params": g_only, "plane_params_gy": g_p,
                    "plane_data": g_r, "panel_params": r_p,
                    "panel_data": r_r, "scan_params": s_p}
    else:
        from spark_timeseries_tpu.models import holtwinters as hw

        mult = variant == "mult"
        y = jnp.where(live, _seasonal_panel(b, t, m, seed=354)
                      + (25.0 if mult else 0.0), 0.0)
        par = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))

        def run():
            f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, nv))
            g = jax.grad(lambda P: jnp.sum(
                w * pk._hw_ss_f(True, m, mult, P, f)))(par)
            s = jax.grad(lambda P: jnp.sum(w * jax.vmap(
                lambda pr, v, n: hw.sse(pr, v, m, mult, n))(P, y, nv)))(par)
            return {"plane_params": g, "scan_params": s}

    return chunk, run


def _cotangent_cases():
    for family, variants in (("css", ("plain", "lagset")),
                             ("garch", ("g11",)), ("hw", ("add", "mult"))):
        for variant in variants:
            for ragged in (False, True):
                for nchunk in (1, 2):
                    yield pytest.param(
                        family, variant, ragged, nchunk,
                        id=f"{family}-{variant}-"
                           f"{'ragged' if ragged else 'dense'}-nchunk{nchunk}")


# recorded on the PARENT of PR 35 (commit 63975b1: XLA formed every
# objective's cotangent as a panel and the adjoint kernels read it back), f32
# under this suite's jax_enable_x64, XLA:CPU of this container: the sha of
# (the parameter gradient, and where the family has them the parameter and
# data gradients of the data-perturbed branch — ``want_gy`` / ``want_gdata``).
# The four ``hw-add-*`` digests were RE-RECORDED by PR 43 on the same host
# (``_COTANGENT_PIN_HOST``, the scan's gradient, matched): the additive
# adjoint reads the raw errors alone and forms ``a r_t`` where the replay
# formed ``L_t - L_{t-1} - T_{t-1}`` (and ``r_t``, ``(1 - a) r_t`` for the
# other two factors, the last as ``(1 - a) sum(r_t uS)``) — equal
# algebraically, another rounding in the last place; against the scan they
# hold the tolerance of the test above.  The digests also hold the FORM of
# those sums: which equal form it is decides whether a row of the
# benchmark's million exhausts its line search (PERF.md §6, PR 43) (the
# parent's: 794754ac43b85e34, 022899e4bbecafc5, 5e3f0ec1fe2454dd,
# ea2e77ce3985d914).  The four ``hw-mult-*`` digests were RE-RECORDED by PR
# 45 on the same host: the multiplicative adjoint reads ``y``, the old
# season and ``P = L + T``, recomputes the error and the level in the
# forward's own expressions and forms ``y / sc - P`` and ``L - P`` where the
# replay formed ``y / sc - L_{t-1} - T_{t-1}`` and ``L - L_{t-1} - T_{t-1}``
# (the parent's: 25b747b5614beeae, 901837f5bd588f9d, 69f822208684638d,
# cdb183f37db397a9).  Every ``css-*`` and ``garch-*`` digest is the
# recording's
_COTANGENT_PIN = {
    "css-plain-0-1": "83949e9651f167db",
    "css-plain-0-2": "0442ba21effdc497",
    "css-plain-1-1": "f82903eed5f82691",
    "css-plain-1-2": "d01b5cf74329ae3e",
    "css-lagset-0-1": "0b2ca0da662a82c4",
    "css-lagset-0-2": "ff4657ac8522248f",
    "css-lagset-1-1": "1ba1fd76c938eae9",
    "css-lagset-1-2": "ba8134aceb9894be",
    "garch-g11-0-1": "9794d51a0dbaf612",
    "garch-g11-0-2": "b6a8fadac3fe0ad4",
    "garch-g11-1-1": "7a62e4568a65c167",
    "garch-g11-1-2": "ea2428b91ae3dd98",
    "hw-add-0-1": "71098cec072acb50",
    "hw-add-0-2": "3205a7a9b39bb4d4",
    "hw-add-1-1": "44876bd5d08fb223",
    "hw-add-1-2": "1bd57283ca296e35",
    "hw-mult-0-1": "2d070198e90dce8a",
    "hw-mult-0-2": "1db70821a6c1eb10",
    "hw-mult-1-1": "c078bb7d569516c7",
    "hw-mult-1-2": "f2dd645fd48e2971",
}
# the scan backend's digest of one case there: no Pallas code in it, so it
# tells the recording's code generator from another
_COTANGENT_PIN_HOST = "640b9ad50e48fcd1"


@functools.lru_cache(maxsize=None)
def _cotangent_out(family, variant, ragged, nchunk):
    """One case's gradients as numpy, run once a process: the comparison and
    the pin below read the same run (every run lowers its interpreted
    kernels anew, some 5 s a case)."""
    chunk, run = _cotangent_case(family, variant, ragged, nchunk)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pk, "_CHUNK_T", chunk)
        return {k: np.asarray(v) for k, v in run().items()}


def _cotangent_pin_host():
    return _sha(_cotangent_out("hw", "add", False, 1)["scan_params"])


def _cotangent_digest(out):
    return _sha(out["plane_params"], *(
        (out["plane_params_gy"], out["plane_data"])
        if "plane_data" in out else ()))


@pytest.mark.parametrize("family,variant,ragged,nchunk",
                         list(_cotangent_cases()))
def test_objective_adjoint_forms_the_cotangent_itself(family, variant,
                                                      ragged, nchunk):
    # the gradient of each folded objective through the plane-taking
    # adjoint against the SAME gradient composed from the untouched
    # general-cotangent path: (2 e) gb rounds alike wherever it is formed,
    # so CSS is bit-equal in the parameters AND the data; GARCH's quotient
    # is another expression than autodiff's, so it is close.  Holt-Winters'
    # adjoint has one caller and no panel mode: its gradient against the
    # scan backend's here, against the parent's digits below.
    out = _cotangent_out(family, variant, ragged, nchunk)
    assert all(np.isfinite(v).all() for v in out.values())
    assert np.abs(out["plane_params"]).max() > 0
    if family == "css":
        for a, b_ in (("plane_params", "panel_params"),
                      ("plane_params_gy", "panel_params"),
                      ("plane_data", "panel_data")):
            assert out[a].tobytes() == out[b_].tobytes(), a
        assert np.abs(out["plane_data"]).max() > 0
    elif family == "garch":
        # the params-only branch and the data-perturbed one run the same
        # recursion adjoint on the same in-kernel cotangent
        assert out["plane_params"].tobytes() == out[
            "plane_params_gy"].tobytes()
        for a, b_ in (("plane_params", "panel_params"),
                      ("plane_data", "panel_data")):
            scale = np.abs(out[b_]).max(axis=0, keepdims=True)
            np.testing.assert_allclose(out[a] / scale, out[b_] / scale,
                                       rtol=1e-6, atol=1e-6, err_msg=a)
    scan = out.get("scan_params")
    if scan is not None:
        np.testing.assert_allclose(out["plane_params"], scan, rtol=2e-3,
                                   atol=2e-3 * np.abs(scan).max())


@pytest.mark.parametrize("family,variant,ragged,nchunk",
                         list(_cotangent_cases()))
def test_objective_gradient_pinned_to_the_panel_cotangent_parent(
        family, variant, ragged, nchunk):
    # the parent's digits, bit for bit on this code generator: the parameter
    # gradient of every folded objective and the data-perturbed branches
    # (``want_gy``, ``want_gdata``: forecasting, ``fit_argarch`` past one
    # time chunk)
    if _cotangent_pin_host() != _COTANGENT_PIN_HOST:
        pytest.skip("another XLA:CPU code generator than the recording's")
    key = f"{family}-{variant}-{int(ragged)}-{nchunk}"
    assert _cotangent_digest(
        _cotangent_out(family, variant, ragged, nchunk)) == _COTANGENT_PIN[key]
