"""The scopes of a Kimi Delta Attention layer, as the benchmark reads
them.

The program's are ``apex_tpu/utils/profiling.py`` ``KDA_SCOPES`` (a
tier-1 test fails when the two differ): ``kda_project`` (the five input
projections, the two low-rank gates, the gated head norm, ``o_proj``),
``kda_conv`` (the short convolutions, SiLU, the L2 norm of q and k) and
``kda_recurrence`` (the chunkwise gated delta rule, forward and
backward, loop bodies included).  All lie inside the ``attention``
module.  A program that opens none of them (a commit before they were
added, or another model) gives nothing to read, and the metric is left
out of the line.
"""

from __future__ import annotations

from benchmark import moe_scopes

KDA_PROJECT, KDA_CONV, KDA_RECURRENCE = KDA_SCOPES = (
    "kda_project", "kda_conv", "kda_recurrence")

seconds = moe_scopes.seconds
share = moe_scopes.share
