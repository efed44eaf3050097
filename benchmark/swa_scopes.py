"""The scope of a sliding-window attention layer, as the benchmark reads
it.

The program's is ``apex_tpu/utils/profiling.py`` ``SWA_SCOPES`` (a
tier-1 test fails when the two differ): ``swa_window``, the windowed
flash call of a sliding layer (its kernels and what the call reshapes,
forward, backward and what remat recomputes), inside the ``attention``
module.  A program that opens none (a commit before it was added, or
another model) gives nothing to read, and the metric is left out of the
line.
"""

from __future__ import annotations

from benchmark import moe_scopes

SWA_WINDOW, = SWA_SCOPES = ("swa_window",)

seconds = moe_scopes.seconds
share = moe_scopes.share
