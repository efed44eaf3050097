"""Share of chip 0's busy time under a KDA layer's ``kda_project`` (the
five input projections, the two low-rank gates, the gated head norm,
``o_proj``) and ``kda_conv`` (the short convolutions, SiLU, the L2 norm
of q and k), forward and backward (``benchmark/kda_scopes.py``)."""

from benchmark import kda_scopes


def read(run) -> "float | None":
    return kda_scopes.share(run, (kda_scopes.KDA_PROJECT,
                                  kda_scopes.KDA_CONV))
