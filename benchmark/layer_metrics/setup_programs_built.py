"""Walk driver, set-up: how many executables the process built or loaded
between the device mark and the window — one ``program.build`` line each
(``utils.compile_cache``'s build log: the stage, rung, sanitizer and panel
programs, and every eagerly dispatched ``jnp`` operation, each a file of its
own in a cache that keeps sub-second programs).  Each pays a trace, a
lowering and a cache read on the warmest run; fewer programs is the first
way to a shorter ``setup_s``.  ``None`` from a program without the log."""

from benchmark import setup_builds


def read(run):
    built = setup_builds.setup_builds(run)
    return None if built is None else len(built)
