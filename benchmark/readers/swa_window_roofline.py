"""Least time the sliding layers' window could take over the time its
kernels took.

Kernel time: chip 0's device self seconds of the events whose kernel
name starts ``flash_window_`` (the window's own Pallas kernels), per
traced step.  Least time: the six passes over the band's keys
(``benchmark/window_flops.py``: the mean of ``min(i + 1, W)`` keys a
query) against the bf16 peak, or the twelve rows a token and layer at
the q and KV heads' widths against the HBM peak, whichever is longer
(``run.notes`` says which); both from shapes alone, the same count
whatever implements the window."""

from benchmark import flops, window_flops

PREFIX = "flash_window_"


def read(run) -> "float | None":
    seconds = sum(s for k, s in run.kernel_seconds.items()
                  if k.startswith(PREFIX))
    if not seconds or not run.steps_traced \
            or not hasattr(run.family, "windows"):
        return None
    w = run.family.windows(run.cfg, run.traffic)
    tokens = run.tokens_per_step / run.chips      # chip 0's share
    least, bound = flops.roofline_seconds(
        tokens * window_flops.train_flops_per_token(
            w["seq"], w["window"], w["hidden"], w["layers"]),
        tokens * window_flops.train_bytes_per_token(
            w["hidden"], w["kv_hidden"], w["layers"]),
        run.peak)
    run.notes["swa.window_roofline.bound"] = bound
    return 100.0 * least / (seconds / run.steps_traced)
