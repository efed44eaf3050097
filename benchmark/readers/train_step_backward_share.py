"""Share of chip 0's busy time in the backward pass: the instructions
autodiff stamped ``transpose(jvp(...))`` or ``vjp(...)``, recomputation
under remat among them (``benchmark/scopes.py`` ``phase``)."""

from benchmark import scopes


def read(run) -> "float | None":
    return scopes.share(run, scopes.phase_seconds(run).get("backward"))
