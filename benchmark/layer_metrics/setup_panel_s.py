"""Walk driver, set-up: wall seconds from the device mark to the panel
ready on the device (the kind's ``panel`` line) — the output directory,
the kind's and the process's modules, the generator's program fetched from
the compile cache (compiled, on a first run) and run.  The first of the
three stretches ``setup_s`` is made of, kept by the kind's ``setup``;
``None`` for a kind that does not record it."""


def read(run):
    return (run.state or {}).get("setup_panel_s")
