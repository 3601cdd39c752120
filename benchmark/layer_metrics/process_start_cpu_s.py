"""Process: CPU seconds of the process at the device mark (user and
system, every thread: what ``time.process_time()`` reads, taken from
``os.times()`` so that the ``start`` line gives the user part beside it)
— what the interpreter, the imports and the runtime's start computed,
without what they waited for on the filesystem.  The part of
``process_start_s`` a program owns: an import that grows heavy, a module
that works at import, the package importing every subpackage eagerly.
Precedes ``setup_s`` and is read beside it.  NOT steady on a shared host:
its system part moves with the machine (2.0-5.5 s in one call);
``process_start_cpu_user_s`` is the part that holds still."""


def read(run):
    return run.process_start_cpu_s
