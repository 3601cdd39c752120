"""Walk driver, set-up: the wall of the warm-up walk's FIRST chunk, from
the journal's own per-chunk walls (the slowest lane's where there are
several), kept by the kind's ``setup``.  The programs every chunk needs
are compiled or fetched from the persistent compile cache inside it: 5-6 s
of cache reads on a warm run, 17-60 s of compiling on a first one.  The
second stretch of ``setup_s``, and the one a new program family or a
re-keyed cache moves.  Programs only some chunk needs (GARCH's retry
ladder, for ONE row of the panel: 5 s of cache reads) are fetched in that
chunk, which ``--seed`` puts first or later: read this metric and
``setup_warm_walk_rest_s`` together, their sum does not depend on the
seed.  ``None`` for a kind without a warm-up walk."""


def read(run):
    return (run.state or {}).get("setup_first_chunk_s")
