"""Least time the chunk-64 gated delta rule could take over the time the
program spent on it.

Time: chip 0's device self seconds of the instructions under the scope
``kda_recurrence`` (``benchmark/kda_scopes.py``), forward and backward,
loop bodies and recomputation included, per traced step.  Least time:
the rule's products against the bf16 peak, or its q, k, v, g, beta, o,
their cotangents and one state a chunk against the HBM peak, whichever
is longer (``run.notes`` says which); both from shapes alone
(``benchmark/kda_flops.py``), for the algorithm at chunk 64 whatever
implements it."""

from benchmark import flops, kda_flops, kda_scopes


def read(run) -> "float | None":
    seconds = kda_scopes.seconds(run, (kda_scopes.KDA_RECURRENCE,))
    if not seconds or not run.steps_traced \
            or not hasattr(run.family, "recurrence"):
        return None
    shape = run.family.recurrence(run.cfg, run.traffic)
    tokens = run.tokens_per_step / run.chips      # chip 0's share
    least, bound = flops.roofline_seconds(
        tokens * kda_flops.recurrence_train_flops_per_token(**shape),
        tokens * kda_flops.recurrence_train_bytes_per_token(**shape),
        run.peak)
    run.notes["kda.recurrence_roofline.bound"] = bound
    return 100.0 * least / (seconds / run.steps_traced)
