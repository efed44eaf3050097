"""Host-clock seconds around ``lower().compile()`` of the step when the
program comes from the persistent cache.  A run that had to compile
fetches the same program once more, now from the cache, to read it."""


def read(run) -> "float | None":
    return run.compile_warm_s
