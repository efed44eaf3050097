"""Per step, on chip 0: milliseconds inside collective operations during
which no other operation runs on that chip.  Nothing to read on one
chip."""


def read(run) -> "float | None":
    if run.chips < 2 or run.exposed_collective_s is None \
            or not run.steps_traced:
        return None
    return 1e3 * run.exposed_collective_s / run.steps_traced
