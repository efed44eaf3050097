"""1 - (union of device-operation intervals) / traced window, on the
chip that was busiest."""


def read(run) -> "float | None":
    if not run.busy_by_chip:
        return None
    return 100.0 * (1.0 - max(b / w for b, w in run.busy_by_chip))
