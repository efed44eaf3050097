"""Share of chip 0's busy time under the scope ``kda_recurrence``,
forward and backward: the chunkwise gated delta rule of every KDA layer,
its loop bodies and what remat recomputes of it
(``benchmark/kda_scopes.py``)."""

from benchmark import kda_scopes


def read(run) -> "float | None":
    return kda_scopes.share(run, (kda_scopes.KDA_RECURRENCE,))
