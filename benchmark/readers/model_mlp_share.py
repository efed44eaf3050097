"""Share of chip 0's busy time under the models' ``mlp`` scope
(``ffn_in``, the activation, ``ffn_out``), forward and backward
(``benchmark/scopes.py`` ``block``)."""

from benchmark import scopes


def read(run) -> "float | None":
    return scopes.share(run, scopes.block_seconds(run).get("mlp"))
