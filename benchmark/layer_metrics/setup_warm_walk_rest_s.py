"""Walk driver, set-up: the warm-up walk's wall less its first chunk
(``setup_first_chunk_s``), kept by the kind's ``setup`` — the other chunks
at the pace of the window's (in GARCH the ladder's chunk with its
programs' cache reads, unless the seed put that chunk first), the walk's
open, close and final commit drain.  The third stretch of ``setup_s``;
what is left of ``setup_s`` after the three is the warm-up journal's
read-back and removal and, on a traced run, ``obs.enable``.  ``None`` for
a kind without a warm-up walk."""


def read(run):
    return (run.state or {}).get("setup_warm_walk_rest_s")
