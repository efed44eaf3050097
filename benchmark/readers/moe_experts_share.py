"""Share of chip 0's busy time under the ``moe_experts`` scope, forward
and backward: the grouped matrix products of the routed experts held on
this chip (``benchmark/moe_scopes.py``)."""

from benchmark import moe_scopes


def read(run) -> "float | None":
    return moe_scopes.share(run, (moe_scopes.MOE_EXPERTS,))
