"""What is true of EVERY family's fit programs, on shapes alone (no compile):
no cotangent panel between an objective's forward and adjoint calls, and each
stage program's dataflow is its recorded parent's (``_dag_hash``).
"""

import jax
import jax.numpy as jnp
import pytest

from _pallas_helpers import _objective_adjoints, _stage_programs
from spark_timeseries_tpu.ops import pallas_kernels as pk
from spark_timeseries_tpu.utils import optim


@pytest.mark.parametrize("family", ["arima111", "sarima-airline4", "hw-add",
                                    "hw-mult", "garch11", "arima-grid3",
                                    "harmonic-arma", "argarch"])
def test_fit_programs_form_no_cotangent_panel(monkeypatch, family):
    # the CPU's stand-in for "``broadcast_multiply_fusion`` /
    # ``multiply_select_fusion`` left the device's ops" (PERF.md §6, PR 35):
    # in stage 1, stage 2 and the inline program no panel-sized mul /
    # select_n / div sits between an objective's forward call and its
    # adjoint call — the kernel forms the cotangent from the plane — and
    # the adjoint takes the panels the stage spans report
    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    b, t = 2048, 48
    panels, programs = _stage_programs(family, b, t)
    for fn, args, rows in programs:
        # the differenced panel is the shortest: d = 1 + 1 + 4 at most
        n_panel = rows * (t - 6)
        adjoints = _objective_adjoints(jax.make_jaxpr(fn)(*args).jaxpr,
                                       n_panel)
        if family == "harmonic-arma":
            # the start's Hannan-Rissanen kernels read the residual PANEL a
            # CSS call wrote (mode "u", once a program): the detector's
            # "adjoint" by its definition, one panel and nothing between
            start = [a for a in adjoints if a[0] == 1]
            assert start == [(1, [])] * len(start) and len(start) <= 2
            adjoints = [a for a in adjoints if a[0] != 1]
        assert adjoints, "every program takes gradients"
        assert adjoints == [(panels, [])] * len(adjoints)
    # the detector sees what it is for: the parent's idiom, a cotangent
    # panel formed by XLA between the two calls
    f32 = jnp.float32
    y3 = jnp.zeros((t, b // 128, 128), f32)
    zb3 = jnp.zeros((1, b // 128, 128), f32)
    par = jnp.zeros((b, 3), f32)

    def parent_idiom(P):
        (e3, css3), (_, par3, _) = pk._css_fwd_call_f(
            1, 1, True, "both", P, y3, zb3, t)
        return pk._css_errors_bwd_f(1, 1, True, (y3, par3, zb3, e3),
                                    2.0 * e3 * css3, b, t)

    assert _objective_adjoints(jax.make_jaxpr(parent_idiom)(par).jaxpr,
                               b * t) == [
        (3, [("mul", y3.shape), ("mul", y3.shape)])]


# What each stage program computes, as a dataflow DAG (``_dag_hash``), on
# the parent of PR 44 (``git archive e3e06aa``, the same shapes, this
# container's jax): that PR gave a several-start family's merge a second
# return value and its stage-1 program one more output, and a ONE-start
# family's programs had to stay what they were — ARIMA, the seasonal orders,
# the order grid, GARCH and the additive Holt-Winters, whose "merge" is its
# finalize.  The multiplicative model's stage 1 gains exactly the
# ``merge_switched`` leaf: every other output is the parent's.  A PR that
# means to change a program re-records its line: PR 45 moved the three
# ``hw-mult`` lines (its parent's: 6284d0f464699f72, 7eb401bf999fa167,
# edfa229a270e3d3c) — the multiplicative ``save_resid`` forward writes two
# panels where it wrote four and the adjoint call takes three panel
# operands and no seed where it took five and two — and no other.  PR 46
# moved the stage-1 and inline lines of the three families that fold through
# ``css_prefold`` (its parent's: ``arima111`` 7638fd7c49350acf,
# 619ba31e5ca33f0d; ``sarima-airline4`` d496a5a60103e978, e89b410f8d8ac809;
# ``arima-grid3`` 8f73780391495fd1, 705ce5312a4620d1) — the panel is folded
# first and differenced, masked and padded in that layout — and no stage 2,
# no Holt-Winters and no GARCH line.  PR 50 moved ALL eighteen at once:
# every one of these programs holds ``utils/optim.py``'s lockstep loop, whose
# state went from ``[B, d]`` / ``[B, m, d]`` to ``[d, B]`` / ``[m, d, B]`` (the
# rows on the last axis, pinned there by a layout constraint) and whose
# two-loop recursion is written for the batch and no longer a ``vmap`` of the
# per-series one: every loop's carry has other shapes, so no program can be
# its parent's dataflow (its parent's lines, in this table's order:
# a19ed580c0b9f8c9, f5dcf78cae09274c, db7b40ae1b1950c5; ae607e8a25cfb1da,
# 4bc27d111ab4968f, 584758d6364c5372; 31a63398f6c929bc, 2c21ba1b533f9c39,
# 05e3ef9b497efa76; 8c8d22c437eea059, fd2ddb045539d854, 716af38572ef7c22;
# bd9f6f6b08b284c4, c8f9ca653165f8a7, 5aeb3b8446744b3c; 7a3e4b9d7f71986d,
# 0a6f1ae43d2ee7ac, 72b9c939507db870).  What holds those programs to the
# parent's RESULTS is ``tests/test_optim.py`` (the batched optimizer against
# ``vmap`` of the per-series one, the two stages against the one loop) and
# the families' own fit tests, unedited.  PR 51 ADDED the three
# ``harmonic-arma`` lines (the shared-design family of PR 49, which had none),
# recorded from ITS OWN tree and not from its parent's: that PR moved both
# design products into the CSS kernel calls, so the family's programs are
# the first of their kind, and the next PR that is not meant to move them is
# held to these; the eighteen standing lines it left as they were (with no
# design operand the CSS calls trace the parent's equations).  PR 52 ADDED the
# three ``argarch`` lines the same way, from its own tree (the mean equation
# moved into the GARCH kernel calls and the optimizer's ``c`` into the row's
# units: the family's programs are the first of their kind), and left the
# twenty-one standing lines as they were: without ``mean`` the two GARCH
# kernel bodies trace the parent's equations.
_PARENT_DAG = {
    ("arima111", "stage1"): "ba609c5d67d3d15e",
    ("arima111", "inline"): "d1a918d4cedaad68",
    ("arima111", "stage2"): "e502d4e8fba50121",
    ("sarima-airline4", "stage1"): "313b857477a6a6b3",
    ("sarima-airline4", "inline"): "16d68f9855efbd2f",
    ("sarima-airline4", "stage2"): "fbdcdea8b75db9ca",
    ("hw-add", "stage1"): "6055dd68113be2f4",
    ("hw-add", "inline"): "4ab7431ef7a4e4ae",
    ("hw-add", "stage2"): "774156ea1af6721d",
    ("garch11", "stage1"): "1c0be67d93014958",
    ("garch11", "inline"): "600bbd690a6b96ad",
    ("garch11", "stage2"): "db5fe596afad6e5c",
    ("arima-grid3", "stage1"): "e702c59723c4b38f",
    ("arima-grid3", "inline"): "2286f0a20e51c90a",
    ("arima-grid3", "stage2"): "18751df954540e11",
    ("hw-mult", "stage1"): "e25fd2b2ceb00f9f",
    ("hw-mult", "inline"): "34867cfc11f32a3e",
    ("hw-mult", "stage2"): "8c9bf27023bfc455",
    ("harmonic-arma", "stage1"): "a027ee96c524e778",
    ("harmonic-arma", "inline"): "f13b958143fa89e7",
    ("harmonic-arma", "stage2"): "e7f54fc5835505f5",
    ("argarch", "stage1"): "0fb808ed19d0a141",
    ("argarch", "inline"): "7970764e868e76fe",
    ("argarch", "stage2"): "27a0bd09d37074c4",
}


@pytest.mark.parametrize("family,program", sorted(_PARENT_DAG))
def test_stage_programs_are_the_parents_dataflow(monkeypatch, family,
                                                 program):
    from _dag_hash import dag_hash

    monkeypatch.setattr(optim, "COMPACT_MIN_BATCH", 2048)
    _, programs = _stage_programs(family, 2048, 48)
    fn, args, _ = programs[("stage1", "inline", "stage2").index(program)]
    paths = [jax.tree_util.keystr(path) for path, _ in
             jax.tree_util.tree_flatten_with_path(
                 jax.eval_shape(fn, *args))[0]]
    new = [i for i, path in enumerate(paths) if "merge_switched" in path]
    assert len(new) == ((family, program) == ("hw-mult", "stage1"))
    assert dag_hash(fn, *args, drop=new) == _PARENT_DAG[family, program]
