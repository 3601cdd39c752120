"""Walk driver, set-up: the builds of set-up that the persistent cache did
NOT answer (``cache != "hit"`` on the ``program.build`` line: compiled, or
no cache directory).  0 on a warm run and most of ``setup_programs_built``
on a cold one; the first thing to look at when a warm ``setup_s`` jumps — a
program re-keyed (an edit above a kernel's frame moves its source
locations) or a new one.  ``None`` from a program without the log."""

from benchmark import setup_builds


def read(run):
    built = setup_builds.setup_builds(run)
    if built is None:
        return None
    return sum(1 for s in built if s["attrs"].get("cache") != "hit")
