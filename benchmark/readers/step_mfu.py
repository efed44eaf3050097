"""Model FLOPs per second over the chips' peak: tokens per second (host
clock, this run's untraced window) times the FLOPs per token the
family's shapes require (``benchmark/flops.py``)."""


def read(run) -> "float | None":
    rate = run.tokens_per_s
    if not rate:
        return None
    return 100.0 * rate * run.flops_per_token / (
        run.chips * run.peak.bf16_flops_per_s)
