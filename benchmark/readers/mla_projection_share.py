"""Share of chip 0's busy time under latent attention's ``mla_project``
scope, forward and backward: the five projections, the latent RMSNorm,
the rotary and the assembly of q and k (``benchmark/moe_scopes.py``)."""

from benchmark import moe_scopes


def read(run) -> "float | None":
    return moe_scopes.share(run, (moe_scopes.MLA_PROJECT,))
