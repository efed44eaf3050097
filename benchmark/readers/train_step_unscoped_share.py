"""How much of the step the program's names do not reach: the share of
chip 0's busy time in instructions that are no collective and carry
neither autodiff's stamp nor a scope of ``benchmark/scopes.py``
``TRAIN_STEP_SCOPES`` (a copy the compiler made with no metadata, for
instance).  Reported as 0 where the names reach everything."""

from benchmark import scopes


def read(run) -> "float | None":
    by_phase = scopes.phase_seconds(run)
    if not by_phase or not run.busy_s0:
        return None
    return 100.0 * by_phase.get("unscoped", 0.0) / run.busy_s0
