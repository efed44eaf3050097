"""Share of chip 0's busy time spent in the LayerNorm kernels
(``layer_norm_fwd``, ``layer_norm_bwd``).

Not a share of the HBM roofline: on the v5e the compiler keeps half of
these kernels' operands in a faster memory (``S(1)`` in the instruction's
layout), where a 32 MiB pass takes 19 us, twice what HBM's peak allows,
so bytes over 819 GB/s do not bound them (PERF.md, Findings PR 24)."""

KERNELS = ("layer_norm_fwd", "layer_norm_bwd")


def read(run) -> "float | None":
    seconds = sum(run.kernel_seconds.get(k, 0.0) for k in KERNELS)
    if not seconds or not run.busy_s0:
        return None
    return 100.0 * seconds / run.busy_s0
