"""The scopes of the DeepSeek-V3-shaped decoder and of its expert layer,
as the benchmark reads them.

The program's are ``apex_tpu/utils/profiling.py`` ``MOE_SCOPES`` (a
tier-1 test fails when the two differ).  :func:`share` is the share of
chip 0's busy time spent in the instructions that carry one of the given
scopes on their ``op_name`` path, forward and backward alike.  A program
that opens none of them (a commit before they were added, or another
model) gives nothing to read, and the metric is left out of the line.
"""

from __future__ import annotations

from benchmark import scopes

(MOE_ROUTE, MOE_DISPATCH, MOE_EXPERTS, MOE_SHARED,
 MLA_PROJECT) = MOE_SCOPES = (
    "moe_route", "moe_dispatch", "moe_experts", "moe_shared", "mla_project")


def seconds(run, names: tuple) -> float:
    """Chip 0's self seconds under any scope of ``names``; nought where
    there is no trace or the program opened no scope."""
    if not (run.instruction_seconds and scopes.named(run.op_names)):
        return 0.0
    wanted = set(names)
    return sum(s for instr, s in run.instruction_seconds.items()
               if wanted.intersection(
                   scopes.segments(run.op_names.get(instr) or "")))


def share(run, names: tuple) -> "float | None":
    return scopes.share(run, seconds(run, names))
