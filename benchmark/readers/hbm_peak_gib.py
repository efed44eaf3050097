"""``memory_stats()["peak_bytes_in_use"]`` after the window, the largest
over the cell's chips, in GiB."""


def read(run) -> "float | None":
    return run.memory_peak_bytes / 2 ** 30 if run.memory_peak_bytes else None
