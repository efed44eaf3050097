"""Share of chip 0's busy time in the forward pass: the instructions
autodiff stamped ``jvp(...)`` and not ``transpose(jvp(...))``, Mosaic
kernels included by their ``op_name`` (``benchmark/scopes.py``
``phase``).  A ``note:`` line gives every phase's share and their sum,
which is 100 where the phases partition the step."""

from benchmark import scopes


def read(run) -> "float | None":
    by_phase = scopes.phase_seconds(run)
    if by_phase and run.busy_s0:
        shares = {p: 100.0 * by_phase.get(p, 0.0) / run.busy_s0
                  for p in scopes.PHASES}
        run.notes["train_step.phases"] = " + ".join(
            f"{p} {v:.3f}" for p, v in shares.items()) \
            + f" = {sum(shares.values()):.3f}% of busy time"
    return scopes.share(run, by_phase.get("forward"))
