"""Process: the USER part of ``process_start_cpu_s`` (``os.times().user`` at
the device mark) — the CPU seconds the interpreter, the imports and the
runtime's start spent in their own code, without the kernel's time under
their reads of a shared filesystem.  The one steady number of the stretch
that precedes ``setup_s``: 4.22-4.49 s over eleven runs in which the
system part read 2.0-5.5 s and the wall 10.8-15.3 s (my chip run 6, PR
33).  An import that grows heavy, or made lazy, shows here."""


def read(run):
    return run.process_start_cpu_user_s
