"""Per step, on chip 0: milliseconds under the program's ``amp_reduce``
scope that are not the collectives themselves: the casts, the
predivide and the copies the gradient exchange adds beside them
(``benchmark/scopes.py`` phase ``reduce``).  Nothing to read on one
chip."""

from benchmark import scopes


def read(run) -> "float | None":
    seconds = scopes.phase_seconds(run).get("reduce")
    if run.chips < 2 or not seconds or not run.steps_traced:
        return None
    return 1e3 * seconds / run.steps_traced
