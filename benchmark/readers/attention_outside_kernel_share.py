"""Share of chip 0's busy time that the ``attention`` module spends
outside its flash kernels, forward and backward: the q/k/v and output
projections, the split and the relayouts around the kernel
(``benchmark/scopes.py`` ``block``, less the ``flash_*`` instructions
that ``flash_attention_roofline`` times)."""

from benchmark import scopes


def read(run) -> "float | None":
    return scopes.share(run, scopes.block_seconds(
        run, skip_kernels=("flash_",)).get("attention"))
