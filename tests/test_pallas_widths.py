"""The objective kernels' series-block width (``pk.series_rows``) as a rule on
static facts, and the ADJOINT calls' width matrix: R vector registers of
series a time step is the SAME arithmetic per series.
``test_pallas_widths_forward.py`` holds the forward calls' matrix.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _pallas_helpers import _garch_params, _seasonal_panel
from spark_timeseries_tpu.ops import pallas_kernels as pk


def _adjoint_width_cases():
    for case in ("css-dense", "css-lagset", "css-nchunk2", "css-want-gy",
                 "css-panel-want-gy", "garch", "garch-nchunk2",
                 "garch-want-gdata", "garch-panel", "grid-k9", "grid-cells",
                 "hw-add", "hw-mult-nchunk2"):
        yield pytest.param(case, id=case)


def _adjoint_width_runner(case):
    """-> ``(widths, run)`` under the caller's ``_CHUNK_T``: the forced
    blocks (R, or the grid's ``(G, R)``; the first is the reference) and
    ``run(width)``, every output of that adjoint call on the residuals its
    own ``both`` / ``save_resid`` forward saved."""
    b, m = 4096, 4  # R = 4 needs Bp / 128 divisible by 32
    rng = np.random.default_rng(371)
    chunk = pk._CHUNK_T
    t = 2 * chunk - 3 if "nchunk2" in case else chunk - 3
    gbar = jnp.asarray(rng.normal(size=b).astype(np.float32))
    panel = lambda: jnp.asarray(  # noqa: E731
        rng.normal(size=(b, t)).astype(np.float32))
    widths = (1, 2, 4)
    if case.startswith("css"):
        p, q = ((), (1, 24, 25)) if case == "css-lagset" else (1, 1)
        k = 1 + len(pk._lags(p)) + len(pk._lags(q))
        par = jnp.asarray(rng.normal(size=(b, k)).astype(np.float32) * 0.3)
        y3, zb3 = pk.css_prefold(panel(), (pk._span(pk._lags(p)), 0, 0))
        g3 = pk._fold(jnp.pad(panel(), ((0, 0), (0, y3.shape[0] - t))))

        (e3, _), (_, par3, _) = pk._css_fwd_call_f(
            p, q, True, "both", par, y3, zb3, t)

        def run(r):
            if case == "css-panel-want-gy":  # ``css_errors``' own rule
                return pk._css_errors_bwd_f(
                    p, q, True, (y3, par3, zb3, e3), g3, b, t, want_gy=True,
                    _r=r)
            marker = () if case == "css-want-gy" else None
            return pk._css_ss_f_bwd(p, q, True, t, b,
                                    (y3, par3, zb3, e3, marker), gbar, _r=r)
    elif case.startswith("garch"):
        par = _garch_params(b, 372)
        f = pk.garch_prefold(0.01 * panel())
        g3 = pk._fold(jnp.pad(panel(), ((0, 0), (0, f.r23.shape[0] - t))))

        (h3, _), par3 = pk._garch_fwd_call_f(True, "both", par, f)

        def run(r):
            if case == "garch-panel":  # ``garch_variances``' own rule
                return pk._garch_bwd_call_f(True, f, par3, h3, g3, True,
                                            _r=r)
            marker = () if case == "garch-want-gdata" else None
            gpar, gf = pk._garch_ll_f_bwd(True, (f, par3, h3, marker), gbar,
                                          _r=r)
            return gpar, gf.r23, gf.h03
    elif case.startswith("grid"):
        # the order search's cells: nine orders over one panel at every
        # (G, R) the rule can return, or one order over gathered cells
        kk, rows = 9, b if case == "grid-k9" else b // 8
        f = pk.css_grid_prefold(
            jnp.asarray(rng.normal(size=(rows, t)).astype(np.float32)),
            [max(o // 3, 0) for o in range(kk)])
        par = jnp.asarray(
            rng.normal(size=(kk * rows, 5)).astype(np.float32) * 0.2)
        gb = jnp.asarray(rng.normal(size=kk * rows).astype(np.float32))
        if case == "grid-cells":
            idx = jnp.asarray(rng.permutation(kk * rows)[:b])
            f, par, gb = pk.take_cells(f, idx), par[idx], gb[idx]
            widths = ((1, 1), (1, 2), (1, 4))
        else:
            widths = tuple((g, r) for g in (1, 3) for r in (1, 2, 4))
        gb4 = pk._fold_cells(gb[:, None], f.k)

        (e4, _), par4 = pk._css_grid_fwd_call(
            (1, 2), (1, 2), True, "both", par, f)

        def run(gr):
            return [pk._css_grid_bwd_call((1, 2), (1, 2), True, f, par4, e4,
                                          gb4, _g=gr[0], _r=gr[1])]
    else:
        mult = "mult" in case
        y = _seasonal_panel(b, t, m, seed=373) + (25.0 if mult else 0.0)
        par = jnp.asarray(rng.uniform(0.05, 0.9, (b, 3)).astype(np.float32))
        f = pk.hw_prefold(y, pk.hw_seeds(y, m, mult, None))

        outs, par3 = pk._hw_fwd_call_f(True, m, mult, True, par, f)

        def run(r):
            return [pk._hw_ss_f_bwd(True, m, mult, (f, par3, *outs[:-1]),
                                    gbar, _r=r)[0]]

    return widths, run


@pytest.mark.parametrize("case", list(_adjoint_width_cases()))
def test_adjoint_block_width_is_bit_equal(monkeypatch, case):
    # every output of each objective's adjoint call — parameter gradients,
    # and the data cotangents where a caller perturbs the data — at forced
    # R = 2 and R = 4 (the grid: every (G, R)) against one register of
    # series a step, bit for bit: the chains never mix
    monkeypatch.setattr(pk, "_CHUNK_T", 64 if case == "css-lagset" else 16)
    widths, run = _adjoint_width_runner(case)
    ref = [np.asarray(x) for x in run(widths[0])]
    assert all(np.isfinite(x).all() for x in ref)
    # (a zero is a cotangent the rule does not form: ``zb``'s, the data's
    # on the params-only path)
    live = [np.abs(x).max() > 0 for x in ref]
    assert live[0] and sum(live) >= (2 if "want" in case or "panel" in case
                                     else 1)
    for width in widths[1:]:
        got = [np.asarray(x) for x in run(width)]
        assert len(got) == len(ref)
        for x, y in zip(got, ref):
            assert x.shape == y.shape and x.tobytes() == y.tobytes(), width


@pytest.mark.parametrize("what,block,layout", [
    # a 256-row serving batch pads to one 1,024-series block
    ("serving-256", lambda: pk.css_series_block(256, 999, (1, 1, 1)), None),
    ("ladder-1-row", lambda: pk.hw_series_block(1, 960, 24), None),
    # a compaction cap that is 1,024- but not 2,048-aligned
    ("cap-3072", lambda: pk.garch_series_block(3072, 1000), None),
    ("cap-2048-takes-2", lambda: pk.css_series_block(2048, 999, (1, 1, 1)),
     None),
    # the cells' chunk and stage-2 compaction take the widest block
    ("arima-chunk", lambda: pk.css_series_block(131072, 999, (1, 1, 1)),
     lambda: pk._css_fwd_layout(1, 1, "sum", 999)),
    ("arima-chunk-both",
     lambda: pk.css_series_block(131072, 999, (1, 1, 1), "both"),
     lambda: pk._css_fwd_layout(1, 1, "both", 999)),
    ("garch-stage2", lambda: pk.garch_series_block(16384, 1000),
     lambda: pk._garch_fwd_layout("sum", 1000)),
    # HW save_resid, additive: 1 input + 1 output (the raw errors), four
    # buffers of 3.9 MB a register of series; the multiplicative model's
    # 1 input + 2 outputs (ISSUE 45) are six: 91.4 MiB at R = 4
    ("hw-save-resid",
     lambda: pk.hw_series_block(131072, 960, 24, "save_resid"),
     lambda: pk._hw_fwd_layout(24, False, True, 960)),
    ("hw-mult-save-resid",
     lambda: pk.hw_series_block(131072, 960, 24, "save_resid", True),
     lambda: pk._hw_fwd_layout(24, True, True, 960)),
    # the ring is m x 4 KB x R, thrice (input twice, scratch once)
    ("hw-m1024-T4096", lambda: pk.hw_series_block(131072, 4096, 1024),
     lambda: pk._hw_fwd_layout(1024, False, False, 4096)),
    ("hw-m1024-T4096-save",
     lambda: pk.hw_series_block(131072, 4096, 1024, "save_resid"),
     lambda: pk._hw_fwd_layout(1024, False, True, 4096)),
    # series past one chunk: the _prev neighbour doubles the input buffers
    ("css-T4096-both",
     lambda: pk.css_series_block(131072, 4096, (1, 1, 1), "both"),
     lambda: pk._css_fwd_layout(1, 1, "both", 4096)),
    # the ADJOINT calls by the same rule (ISSUE 37): a serving batch and a
    # one-row retry keep today's block
    ("adjoint-serving-256",
     lambda: pk.css_series_block(256, 999, (1, 1, 1), "adjoint"), None),
    ("adjoint-ladder-1-row",
     lambda: pk.garch_series_block(1, 1000, "adjoint"), None),
    # the cells' chunk and stage-2 compaction take the table's width
    ("adjoint-arima-chunk",
     lambda: pk.css_series_block(131072, 999, (1, 1, 1), "adjoint"),
     lambda: pk._css_bwd_layout(1, 1, 999)),
    ("adjoint-arima-stage2",
     lambda: pk.css_series_block(16384, 999, (1, 1, 1), "adjoint"),
     lambda: pk._css_bwd_layout(1, 1, 999)),
    ("adjoint-seasonal-chunk",
     lambda: pk.css_series_block(131072, 935, ((), 0, (1, 24, 25)),
                                 "adjoint"),
     lambda: pk._css_bwd_layout((), (1, 24, 25), 935)),
    ("adjoint-garch-chunk",
     lambda: pk.garch_series_block(131072, 1000, "adjoint"),
     lambda: pk._garch_bwd_layout(1000)),
    ("adjoint-garch-stage2",
     lambda: pk.garch_series_block(16384, 1000, "adjoint"),
     lambda: pk._garch_bwd_layout(1000)),
    # a perturbed panel adds a panel out (``want_gy`` / ``want_gdata``), a
    # series past one chunk the neighbour blocks: 27.4 / 23.5 / 36.1 / 32.1
    # MiB a register of series, so VMEM stops each at two
    ("adjoint-css-want-gy",
     lambda: _rule_block(pk._css_bwd_layout(1, 1, 999, want_gy=True), "css"),
     lambda: pk._css_bwd_layout(1, 1, 999, want_gy=True)),
    ("adjoint-garch-want-gdata",
     lambda: _rule_block(pk._garch_bwd_layout(1000, True), "garch"),
     lambda: pk._garch_bwd_layout(1000, True)),
    ("adjoint-css-T4096",
     lambda: pk.css_series_block(131072, 4096, (1, 1, 1), "adjoint"),
     lambda: pk._css_bwd_layout(1, 1, 4096)),
    ("adjoint-garch-T4096",
     lambda: pk.garch_series_block(131072, 4096, "adjoint"),
     lambda: pk._garch_bwd_layout(4096)),
    # Holt-Winters' additive adjoint reads one panel and takes the table's
    # width; the multiplicative one reads three (ISSUE 45), 90.7 MiB at
    # R = 4, and takes it too; a series past one chunk brings no neighbour
    # block, 96 MiB of panel blocks at R = 4: two
    ("adjoint-hw", lambda: pk.hw_series_block(131072, 960, 24, "adjoint"),
     lambda: pk._hw_bwd_layout(24, False, 960)),
    ("adjoint-hw-mult",
     lambda: pk.hw_series_block(131072, 960, 24, "adjoint", True),
     lambda: pk._hw_bwd_layout(24, True, 960)),
    ("adjoint-hw-mult-T4096",
     lambda: pk.hw_series_block(131072, 4096, 24, "adjoint", True),
     lambda: pk._hw_bwd_layout(24, True, 4096)),
    # the order search: stage 2's one order over the cap's gathered cells
    # takes the plain rule's width, stage 1's nine orders what fits beside G
    ("adjoint-grid-stage2",
     lambda: pk.css_grid_series_block(1, 73728, 999, 2, 2, "adjoint"),
     lambda: pk._css_bwd_layout(2, 2, 999)),
    ("adjoint-grid-stage1",
     lambda: pk.css_grid_series_block(9, 131072, 999, 2, 2, "adjoint"),
     None),
])
def test_series_block_rule_on_shapes(what, block, layout):
    # the width rule from static facts alone: no kernel runs
    sb = block()
    r = sb // pk._SBLK
    assert sb == r * pk._SBLK and r in (1, 2, 4)
    if what in ("serving-256", "ladder-1-row", "cap-3072",
                "adjoint-serving-256", "adjoint-ladder-1-row"):
        assert r == 1
    if what == "adjoint-hw":
        assert r == pk._ADJOINT_R["hw"][False]
    if what == "adjoint-hw-mult":
        assert r == pk._ADJOINT_R["hw"][True]
    if what.startswith(("adjoint-arima", "adjoint-seasonal")):
        assert r == pk._ADJOINT_R["css"]
    if what.startswith("adjoint-garch-") and what[14:] in ("chunk", "stage2"):
        assert r == pk._ADJOINT_R["garch"]
    if what in ("adjoint-css-want-gy", "adjoint-garch-want-gdata",
                "adjoint-css-T4096", "adjoint-garch-T4096",
                "adjoint-hw-mult-T4096"):
        assert r == 2
    if what == "adjoint-grid-stage2":
        assert sb == pk.css_series_block(73728, 999, (2, 1, 2), "adjoint")
    if what == "adjoint-grid-stage1":
        g, r_ = pk.css_grid_block(9, 1024, pk._css_bwd_layout(2, 2, 999),
                                  "adjoint")
        assert (g, r_) == (3, r) and r <= 2  # (3, 4) is 173 MiB
    if what == "cap-2048-takes-2":
        assert r == min(2, pk._CSS_R["sum"])
    if what == "hw-save-resid":
        assert r == pk._HW_R[True][False]
    if what == "hw-mult-save-resid":
        assert r == pk._HW_R[True][True]
    if layout is not None:
        assert pk._vmem_bytes(layout(), r) <= pk._VMEM_BLOCK_BUDGET
        assert pk._VMEM_BLOCK_BUDGET < pk._VMEM_PARAMS.vmem_limit_bytes
        # the next wider block is refused for a stated reason
        wider = {1: 2, 2: 4}.get(r)
        if wider and what != "garch-stage2":
            best = {"arima-chunk": pk._CSS_R["sum"],
                    "arima-chunk-both": pk._CSS_R["both"],
                    "css-T4096-both": pk._CSS_R["both"],
                    "hw-save-resid": pk._HW_R[True][False],
                    "hw-mult-save-resid": pk._HW_R[True][True],
                    "hw-m1024-T4096": pk._HW_R[False][False],
                    "hw-m1024-T4096-save": pk._HW_R[True][False],
                    "adjoint-hw": pk._ADJOINT_R["hw"][False],
                    "adjoint-hw-mult": pk._ADJOINT_R["hw"][True],
                    "adjoint-hw-mult-T4096": pk._ADJOINT_R["hw"][True],
                    "adjoint-grid-stage2": pk._ADJOINT_R["css"],
                    **{f"adjoint-{k}": pk._ADJOINT_R[k.split("-")[0]]
                       for k in ("css-want-gy", "garch-want-gdata",
                                 "css-T4096", "garch-T4096", "garch-chunk",
                                 "garch-stage2")},
                    **{f"adjoint-{k}": pk._ADJOINT_R["css"]
                       for k in ("arima-chunk", "arima-stage2",
                                 "seasonal-chunk")}}[what]
            assert (wider > best or pk._vmem_bytes(layout(), wider)
                    > pk._VMEM_BLOCK_BUDGET)


def _rule_block(layout, kernel, rows=131072):
    """The block of an adjoint call that no ``*_series_block`` names."""
    return pk._SBLK * pk.series_rows(pk._nsub(rows), layout,
                                     pk._ADJOINT_R[kernel])


def test_series_rows_is_a_function_of_static_facts():
    # divisibility, the VMEM budget, the chip's best: in that order of refusal
    lay = pk._css_fwd_layout(1, 1, "sum", 999)
    tiles = 2 * (1000 + 3 + 1 + 1) + 1000 + 1
    assert pk._vmem_bytes(lay) == tiles * 4096
    assert pk._vmem_bytes(lay, 4) == 4 * tiles * 4096
    assert pk.series_rows(1024, lay, 4) == 4
    assert pk.series_rows(1024, lay, 2) == 2
    assert pk.series_rows(1024, lay, 1) == 1
    assert pk.series_rows(16, lay, 4) == 2  # 2,048 series
    assert pk.series_rows(24, lay, 4) == 1  # 3,072 series
    assert pk.series_rows(8, lay, 4) == 1
    over = pk._VMEM_BLOCK_BUDGET // pk._TILE_BYTES
    scratch_only = lambda n: ([], [], [n])  # noqa: E731
    assert pk.series_rows(1024, scratch_only(over // 4), 4) == 4
    assert pk.series_rows(1024, scratch_only(over // 4 + 1), 4) == 2
    assert pk.series_rows(1024, scratch_only(over // 2 + 1), 4) == 1
    # an adjoint's layout by the same count: two panels and the mask, the
    # parameter planes in and out, the cotangent's plane; the adjoint path
    # and its carry in scratch
    adj = pk._css_bwd_layout(1, 1, 999)
    tiles = 2 * (2 * 1000 + 3 + 1 + 1 + 3) + 1000 + 1
    assert pk._vmem_bytes(adj) == tiles * 4096
    assert [pk.series_rows(n, adj, 4) for n in (1024, 128, 16, 24, 8)] == [
        4, 4, 2, 1, 1]
    assert pk.series_rows(1024, adj, 1) == 1
    # a panel more (``want_gy``) is refused R = 4 by VMEM, not by the table
    assert pk.series_rows(1024, pk._css_bwd_layout(1, 1, 999, True), 4) == 2


def test_forward_call_grid_follows_the_rule():
    # the pallas_call the fit objective traces takes the rule's block: at
    # 4,096 series one grid step of (cs, 8 R, 128) where R = 1 takes four
    b, t = 4096, 40
    y3 = jnp.zeros((t, b // 128, 128), jnp.float32)
    zb3 = jnp.ones((1, b // 128, 128), jnp.float32)
    par = jnp.zeros((b, 3), jnp.float32)

    def grid(**kw):
        jaxpr = jax.make_jaxpr(lambda P: pk._css_fwd_call_f(
            1, 1, True, "sum", P, y3, zb3, t, **kw)[0])(par)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        gm = eqn.params["grid_mapping"]
        return tuple(gm.grid), gm.block_mappings[0].block_shape

    r = pk.css_series_block(b, t, (1, 0, 1)) // pk._SBLK
    g, blk = grid()
    assert g == (4 // r, 1)
    assert tuple(getattr(x, "block_size", x) for x in blk) == (40, 8 * r, 128)
    assert grid(_r=1)[0] == (4, 1) and grid(_r=4)[0] == (1, 1)


def test_kernel_block_sweep_cases_trace():
    # tools/kernel_block_sweep.py (the chip-side R sweep, and each
    # objective's adjoint as its custom_vjp calls it): every case's
    # arguments and call trace at every width, on shapes alone
    from tools import kernel_block_sweep as sweep

    def series_grid(call, r, args):
        # -> (series-axis grid steps, sublane rows of the panel's block)
        jaxpr = jax.make_jaxpr(functools.partial(call, r))(*args)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        gm = eqn.params["grid_mapping"]
        blk = gm.block_mappings[0].block_shape
        return gm.grid[0], getattr(blk[1], "block_size", blk[1])

    seen, grid = set(), set()
    for name, mode, rows, t, make, call in sweep.cases():
        args = jax.eval_shape(make, jax.random.key(0))
        tp, _, _ = pk._time_layout(t)
        if mode.startswith("adjoint"):
            # the adjoint cases force their width too (ISSUE 37): 8 R
            # sublane rows a block, G orders a group of the grid's nine
            cells = rows * 9 // 4 if mode.endswith("cells") else rows
            kg = 9 // int(mode[-1]) if ".g" in mode else 1
            assert [series_grid(call, r, args) for r in (1, 2, 4)] == [
                (cells // (1024 * r) * kg, 8 * r) for r in (1, 2, 4)]
        if name == "css_grid_neg_loglik":
            # the order search's cells: 9 orders over one panel at G orders
            # a grid step, or one order over a quarter of the cells
            k, b = (1, 9 * rows // 4) if mode.endswith("cells") else (9, rows)
            for r in (1, 2, 4):
                outs = jax.eval_shape(functools.partial(call, r), *args)
                if mode.startswith("adjoint"):  # five planes, folded flat
                    assert [o.shape for o in outs] == [(5, k * b // 128, 128)]
                else:
                    assert all(o.shape[1:] == (k, b // 128, 128)
                               and o.shape[0] in (1, tp) for o in outs)
            grid.add(mode)
            continue
        for r in (1, 2, 4):
            outs = jax.eval_shape(functools.partial(call, r), *args)
            assert outs[-1].shape[1:] == (rows // 128, 128)
            # a panel or a plane; an adjoint's parameter planes, folded
            # (35 with a shared design's 32 columns beside ARMA(1,1)'s; 5
            # and the seed's 1 with GARCH's mean equation)
            assert all(o.shape[0] in ((1, 3, 4, 5, 35)
                                      if mode.startswith("adjoint")
                                      else (1, tp)) for o in outs)
        seen.add((name, mode))
    assert grid == {f"{m}.{tag}" for m in ("sum", "both", "adjoint")
                    for tag in ("g1", "g3", "g9", "cells")}
    kernels = {"css_neg_loglik", "hw_sse", "garch_neg_loglik",
               "argarch_neg_loglik"}  # the GARCH pair with a mean (ISSUE 52)
    assert {(n, m) for n, m in seen if m == "adjoint"} == {
        (n, "adjoint") for n in kernels | {"css_seasonal_neg_loglik"}}
    # Holt-Winters' additive calls, and the multiplicative model's pair
    assert {m for n, m in seen if n == "hw_sse"} == {
        "sum", "save_resid", "adjoint", "save_resid.mult", "adjoint.mult"}
    # the CSS calls that take a shared design as an operand (ISSUE 51)
    assert {m for n, m in seen if m.endswith(".x")} == {
        "sum.x", "both.x", "u.x", "adjoint.x"}
    assert len(seen) == 21 and {n for n, m in seen
                                if not m.startswith("adjoint")} == kernels
