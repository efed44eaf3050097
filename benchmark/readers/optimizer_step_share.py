"""Share of chip 0's busy time spent in the optimizer and scaler update:
the named optimizer kernels, plus the instructions whose ``op_name``
scope in the compiled step carries an optimizer marker
(``benchmark/trace.py`` ``is_optimizer``)."""

from benchmark import trace


def read(run) -> "float | None":
    if not run.instruction_seconds or not run.busy_s0:
        return None
    seconds = sum(s for name, s in run.instruction_seconds.items()
                  if trace.is_optimizer(name, run.op_names.get(name)))
    return 100.0 * seconds / run.busy_s0 if seconds else None
