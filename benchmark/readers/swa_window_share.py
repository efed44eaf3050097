"""Share of chip 0's busy time under a sliding layer's ``swa_window``:
the windowed flash call, forward, backward and what remat recomputes of
it (``benchmark/swa_scopes.py``)."""

from benchmark import swa_scopes


def read(run) -> "float | None":
    return swa_scopes.share(run, (swa_scopes.SWA_WINDOW,))
