"""Least time attention could take over the time its kernels took.

Kernel time: device self time of the events named ``flash_fwd``,
``flash_bwd_fused``, ``flash_bwd_dq``, ``flash_bwd_dkv`` on chip 0, per
traced step.  Least time: the six matmul passes a training step
requires against the bf16 peak, or the q/k/v/o/do/dq/dk/dv bytes
against the HBM peak, whichever is longer (``run.notes`` says which)."""

from benchmark import flops

KERNELS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")


def read(run) -> "float | None":
    seconds = sum(run.kernel_seconds.get(k, 0.0) for k in KERNELS)
    if not seconds or not run.steps_traced:
        return None
    a = run.family.attention(run.cfg, run.traffic)
    tokens = run.tokens_per_step / run.chips      # chip 0's share
    least, bound = flops.roofline_seconds(
        tokens * flops.attention_train_flops_per_token(
            a["seq"], a["hidden"], a["layers"], a["causal"]),
        tokens * flops.attention_train_bytes_per_token(
            a["hidden"], a["layers"]),
        run.peak)
    run.notes["flash_attention_roofline.bound"] = bound
    return 100.0 * least / (seconds / run.steps_traced)
