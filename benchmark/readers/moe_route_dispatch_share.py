"""Share of chip 0's busy time under the expert layer's ``moe_route`` and
``moe_dispatch`` scopes, forward and backward: the router's product,
scores, choice and weights; the sort of (token, expert) pairs, the
gathers into expert order and back, and the weighted sum.  What routing
costs beside the work it routes (``benchmark/moe_scopes.py``)."""

from benchmark import moe_scopes


def read(run) -> "float | None":
    return moe_scopes.share(run, (moe_scopes.MOE_ROUTE,
                                  moe_scopes.MOE_DISPATCH))
