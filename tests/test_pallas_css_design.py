"""The CSS kernels with a SHARED DESIGN as an operand (ISSUE 51): the forward
calls form ``u = y - x @ beta'`` and the adjoint ``-x' dS/du`` in VMEM.  Held
to (a) the composition they replace — an XLA residual ``y3 + design_plane``
before the plain kernels, ``x' g_u`` over the adjoint's data-cotangent panel
after — and (b) the float64 ``lax.scan`` errors, at every block width, with
one column and with thirty-one (padded to whole sublane tiles by the entry),
under one time chunk here and across two in
``test_pallas_css_design_chunks.py`` (the design moves with the chunk, ``u``'s
lag reads and the ``x' g_u`` sums cross it; a file of its own for the tier-1
budget: the interpreter copies a chunk's buffers every step).  Interpret
mode, like the rest of ``tests/test_pallas_css*.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import (_DESIGN_ORDER as ORDER, _check_design_entry,
                             _check_fused_design, _design_case as _case)
from spark_timeseries_tpu.ops import pallas_kernels as pk


@pytest.mark.parametrize("t", [96, 960])
@pytest.mark.parametrize("r", [1, 2, 4])
@pytest.mark.parametrize("k", [1, 31])
def test_fused_design_is_the_composition_and_the_f64_scan(k, r, t):
    _check_fused_design(k, r, t)


def test_entry_differentiates_the_coefficients_through_params_path():
    _check_design_entry(77)  # a padded tail of three rows


def test_layouts_declare_what_the_design_calls_hold():
    t, nx = 960, 32
    plain = pk._css_fwd_layout(1, 1, "sum", t)
    assert pk._css_fwd_layout(1, 1, "sum", t, 0) == plain
    # "sum": the design's rows in, its planes beside the three, u a scratch
    ins, outs, scratch = pk._css_fwd_layout(1, 1, "sum", t, nx)
    assert [e[0] for e in ins] == [t, 3 + nx, 1, t // 8]
    assert ins[3][2] == (t, nx)
    assert outs == plain[1] and scratch == [t, t, 1]
    # "both": u is a panel OUT beside the errors (the adjoint's operand)
    _, outs, scratch = pk._css_fwd_layout(1, 1, "both", t, nx)
    assert [n for n, _ in outs] == [t, t, 1] and scratch == [1]
    # "u": the residual alone, a panel out and no recurrence's scratch
    _, outs, scratch = pk._css_fwd_layout(0, 0, "u", t, nx)
    assert [n for n, _ in outs] == [t] and scratch == [1]
    # the adjoint: two panels in as a plain fit's, [-x, x shifted] in, no
    # panel out and no scratch but a plain fit's: no data cotangent is formed
    ins, outs, scratch = pk._css_bwd_layout(1, 1, t, nx=nx)
    assert [e[0] for e in ins] == [t, t, 3 + nx, 1, 1, t // 8]
    assert ins[5][2] == (t, 2 * nx)
    assert [n for n, _ in outs] == [3 + nx]
    assert scratch == pk._css_bwd_layout(1, 1, t)[2] == [t, 1]
    # past one chunk the forward carries u and reads no neighbour block of y
    ins, _, scratch = pk._css_fwd_layout(1, 1, "sum", 2048, nx)
    assert [e[0] for e in ins] == [1024, 3 + nx, 1, 128]
    assert scratch == [1024, 1024, 1, 1]
    assert ins[3][1](5, 1) == (1, 0)  # the design moves with the time chunk
    assert pk._css_bwd_layout(1, 1, 2048, nx=nx)[0][-1][1](5, 0) == (1, 0)
    # the width: the chip's best with the products in the call is two
    # registers of series in the forward calls, whatever VMEM would hold,
    # and four in the adjoint, which holds no more than a plain fit's
    for mode, block in (("sum", 2048), ("both", 2048), ("adjoint", 4096)):
        assert pk.css_series_block(131072, t, ORDER, mode,
                                   design=31) == block
        assert pk.css_series_block(131072, t, ORDER, mode) == 4096
        assert pk.css_series_block(1024, t, ORDER, mode, design=31) == 1024
    assert pk._vmem_bytes(pk._css_fwd_layout(1, 1, "both", t, nx), 4) \
        > pk._VMEM_BLOCK_BUDGET  # three panels double-buffered: refused at 4
    # a product takes the chunk in slabs of whole tiles, at most 64 steps
    assert [pk._design_slab(cs) for cs in (960, 1024, 96, 80, 104)] == [
        64, 64, 48, 40, 8]


def test_traced_design_calls_move_no_panel_but_the_residual():
    # the programs' statement of "formed in VMEM": the value-only call reads
    # ONE panel and writes none; a gradient's forward writes the errors and
    # u, its adjoint reads those two and writes planes alone
    t, b = 96, 2048
    _, y3, zb3, x, beta, arma, _ = _case(31, 2, t)
    n_panel = t * b

    def calls(jaxpr):
        out = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(tuple(
                    sum(v.aval.size >= n_panel for v in vs)
                    for vs in (eqn.invars, eqn.outvars)))
                continue
            subs = list(jax.core.jaxprs_in_params(eqn.params))
            for sub in subs:
                out += calls(sub)
            # XLA itself forms no panel beside the calls
            assert subs or all(v.aval.size < n_panel for v in eqn.outvars), eqn
        return out

    nll = lambda a, c: jnp.sum(pk.css_neg_loglik_folded(  # noqa: E731
        a, y3, zb3, t, ORDER, False, design=(x[:, :31], c), interpret=True))
    args = arma[:, 1:], beta[:, :31]
    assert calls(jax.make_jaxpr(nll)(*args).jaxpr) == [(1, 0)]
    assert calls(jax.make_jaxpr(jax.grad(nll, (0, 1)))(*args).jaxpr) == [
        (1, 2), (2, 0)]
