"""The FORWARD objective kernels' series-block width (``pk.series_rows``): R
vector registers of series a time step is the SAME arithmetic per series —
value, saved residuals and the gradient through the unchanged adjoint, bit for
bit at every forced width.  ``test_pallas_widths.py`` holds the rule and the
adjoint calls' matrix.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import _seasonal_panel
from spark_timeseries_tpu.ops import pallas_kernels as pk


def _block_width_cases():
    for ragged in (False, True):
        for nchunk in (1, 2):
            tag = f"{'ragged' if ragged else 'dense'}-nchunk{nchunk}"
            for mode in ("sum", "both", "e", "tail"):
                yield pytest.param("css", mode, False, ragged, nchunk,
                                   id=f"css-{mode}-{tag}")
            for mode in ("sum", "both", "e"):
                yield pytest.param("garch", mode, False, ragged, nchunk,
                                   id=f"garch-{mode}-{tag}")
            for mult in (False, True):
                for mode in ("sum", "save_resid"):
                    yield pytest.param(
                        "hw", mode, mult, ragged, nchunk,
                        id=f"hw-{'mult' if mult else 'add'}-{mode}-{tag}")


def _block_width_runner(family, mode, mult, ragged, nchunk):
    """-> run(r): every output of the forward call at forced width ``r``
    and, where the mode saves residuals, the gradient through the adjoint."""
    # R = 4 needs Bp / 128 divisible by 32: 4096 series.  Chunks of 16
    # steps (patched by the caller) keep the interpreted loops short
    b, m = 4096, 4
    t = 13 if nchunk == 1 else 29
    rng = np.random.default_rng(71)
    nv = None
    if ragged:
        nv = jnp.asarray(rng.integers(t - 4, t + 1, b), jnp.int32)
    gbar = jnp.asarray(rng.normal(size=b).astype(np.float32))
    if family == "css":
        y = jnp.asarray(rng.normal(size=(b, t)).astype(np.float32))
        par = jnp.asarray(rng.normal(size=(b, 3)).astype(np.float32) * 0.3)
        y3, zb3 = pk.css_prefold(y, (1, 0, 1), nv)

        def run(r):
            outs, (_, par3, _) = pk._css_fwd_call_f(
                1, 1, True, mode, par, y3, zb3, t, _r=r)
            if mode != "both":
                return list(outs)
            return list(outs) + list(pk._css_ss_f_bwd(
                1, 1, True, t, b, (y3, par3, zb3, outs[0], None), gbar))
    elif family == "garch":
        r_ = jnp.asarray(0.01 * rng.normal(size=(b, t)).astype(np.float32))
        par = jnp.asarray(np.stack(
            [rng.uniform(1e-6, 1e-5, b), rng.uniform(0.03, 0.15, b),
             rng.uniform(0.7, 0.8, b)], axis=1).astype(np.float32))
        f = pk.garch_prefold(r_, nv)

        def run(r):
            outs, par3 = pk._garch_fwd_call_f(True, mode, par, f, _r=r)
            if mode != "both":
                return list(outs)
            gpar, _ = pk._garch_ll_f_bwd(True, (f, par3, outs[0], None), gbar)
            return list(outs) + [gpar]
    else:
        y = _seasonal_panel(b, t, m, seed=72) + (25.0 if mult else 0.0)
        if ragged:
            y = jnp.where(jnp.arange(t)[None, :] >= (t - nv)[:, None], y, 0.0)
        par = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, nv))

        def run(r):
            save = mode == "save_resid"
            outs, par3 = pk._hw_fwd_call_f(True, m, mult, save, par, f, _r=r)
            if not save:
                return list(outs)
            gpar, _ = pk._hw_ss_f_bwd(True, m, mult, (f, par3, *outs[:-1]),
                                      gbar)
            return list(outs) + [gpar]

    return run


@pytest.mark.parametrize("family,mode,mult,ragged,nchunk",
                         list(_block_width_cases()))
def test_forward_block_width_is_bit_equal(monkeypatch, family, mode, mult,
                                          ragged, nchunk):
    # value, saved residuals and the gradient through the unchanged adjoint
    # at forced R = 2 and R = 4 against R = 1, bit for bit
    monkeypatch.setattr(pk, "_CHUNK_T", 16)
    run = _block_width_runner(family, mode, mult, ragged, nchunk)
    ref = [np.asarray(x) for x in run(1)]
    assert all(np.isfinite(x).all() for x in ref)
    assert any(np.abs(x).max() > 0 for x in ref)
    for r in (2, 4):
        got = [np.asarray(x) for x in run(r)]
        assert len(got) == len(ref)
        for x, y in zip(got, ref):
            assert x.shape == y.shape and x.tobytes() == y.tobytes(), r
