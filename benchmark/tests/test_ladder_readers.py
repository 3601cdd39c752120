"""The two readers of the ladder's rule (ISSUE 53),
``ladder_continued_row_share`` and ``ladder_fallback_iters_per_chunk``: on
fakes of what a run hands them — the rung spans as the program writes them
now, as its parent wrote them (``rows`` and ``cap`` alone: nothing to read),
and a window in which no rung ran — and what ``BENCHMARK.json`` says of
them."""

import types

import pytest

from benchmark import manifest as mf

CELLS = ["garch11.walk-dense", "harmonic-arma24x168.walk-dense"]
OWN = {"ladder_continued_row_share": ("share", "higher"),
       "ladder_fallback_iters_per_chunk": ("iters", "lower")}


def line(name, walk, **attrs):
    return {"kind": "span", "name": name, "walk": walk, "attrs": attrs}


def fake_run(spans, traced=(1, 2)):
    return types.SimpleNamespace(
        trace=None, spans=list(spans),
        result={"traced_walks": list(traced),
                "walks": [{"n_chunks": 2}] * 4})


def walks(rungs_of_a_walk, n=4):
    """``n`` walks of two chunks each, every walk with the same rungs."""
    spans = []
    for w in range(1, n + 1):
        spans += [line("walk", w), line("chunk", w, lo=0, hi=8),
                  line("chunk", w, lo=8, hi=16)]
        spans += [line(name, w, **attrs) for name, attrs in rungs_of_a_walk]
    return spans


@pytest.fixture(scope="module")
def readers():
    m = mf.load_manifest()
    return {n: mf.load_plugin(m, mf.ROOT, "layer_metrics", n) for n in OWN}


CASES = {
    # garch11's one row, the scan rung finishing what the retry rung left
    "continued-through-both": (
        [("fit.rung.retry", dict(rows=1, cap=8, continued=1, iters=160,
                                 rescued=0)),
         ("fit.rung.fallback", dict(rows=1, cap=8, continued=1, iters=6,
                                    rescued=1))], 1.0, 3.0),
    # ... or rescued on the kernels: the expensive rung does nothing
    "rescued-by-the-retry-rung": (
        [("fit.rung.retry", dict(rows=1, cap=8, continued=1, iters=23,
                                 rescued=1))], 1.0, 0.0),
    # the harmonic cell's rows stall after 5-6 iterations: perturbed starts
    "stalled-rows": (
        [("fit.rung.retry", dict(rows=4, cap=8, continued=0, iters=9,
                                 rescued=4))], 0.0, 0.0),
    "a-mixed-bucket": (
        [("fit.rung.retry", dict(rows=4, cap=8, continued=1, iters=160,
                                 rescued=3)),
         ("fit.rung.fallback", dict(rows=1, cap=8, continued=1, iters=40,
                                    rescued=1))], 0.4, 20.0),
    # a program before the rule: its rung spans say rows and cap alone
    "the-parents-spans": (
        [("fit.rung.retry", dict(rows=1, cap=8)),
         ("fit.rung.fallback", dict(rows=1, cap=8))], None, None),
    "no-rung-ran": ([], None, None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_readers_on_rung_spans(readers, case):
    rungs, share, iters = CASES[case]
    run = fake_run(walks(rungs))
    assert readers["ladder_continued_row_share"].read(run) == \
        (None if share is None else pytest.approx(share))
    assert readers["ladder_fallback_iters_per_chunk"].read(run) == \
        (None if iters is None else pytest.approx(iters))


def test_readers_take_the_traced_walks_only(readers):
    spans = walks([("fit.rung.retry", dict(rows=1, cap=8, continued=1,
                                           iters=160, rescued=0)),
                   ("fit.rung.fallback", dict(rows=1, cap=8, continued=1,
                                              iters=6, rescued=1))])
    # an untraced walk (the warm-up's, the first of the window) that did
    # something else is not read
    spans += [line("fit.rung.retry", 1, rows=64, cap=64, continued=0,
                   iters=1, rescued=64),
              line("fit.rung.fallback", 4, rows=8, cap=8, continued=0,
                   iters=99, rescued=8)]
    run = fake_run(spans, traced=(1, 2))  # the second and third walk
    assert readers["ladder_continued_row_share"].read(run) == 1.0
    assert readers["ladder_fallback_iters_per_chunk"].read(run) == 3.0
    for reader in readers.values():
        assert reader.read(fake_run(spans, traced=(9,))) is None
        assert reader.read(fake_run([])) is None


@pytest.mark.parametrize("cell", CELLS)
def test_manifest_gives_the_two_to_the_cells_with_a_ladder(cell):
    m = mf.load_manifest()
    by_name = {p["name"]: p for p in m["per_layer"]}
    for name, (unit, better) in OWN.items():
        entry = by_name[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"], entry["workloads"]) == (
            unit, better, "program_span", "sanitize_ladder",
            "series_per_s_chip", CELLS)
    resolved = mf.resolve_cell(m, cell)
    assert set(OWN) <= {p["name"] for p in resolved.per_layer}
    other = mf.resolve_cell(m, "arima111.walk-dense")
    assert not set(OWN) & {p["name"] for p in other.per_layer}
