"""The (token, expert) pairs the experts held on this chip served, over
the ``tokens x k x held / n`` they get when routing is even: the mean
over the expert layers and the traced steps of the program's own counter
``pairs`` (``moe_apply``'s ``stats``, carried out of the compiled step
as its ``aux``).  Near 1 the cell measures its expert layer at the load
the deployment gives it; near 0, or in whole multiples, a router has
collapsed and the step's length is its seed's luck.  A ``note:`` line
gives every layer's ratio, its fullest held expert over the held mean,
and the windows of the sorted buffer it ran.  A step that carries no
such counters gives nothing to read."""

import numpy as np


def read(run) -> "float | None":
    steps = [a for a in run.aux_traced if "pairs" in a]
    if not steps or not hasattr(run.family, "expected_held_pairs"):
        return None
    expected = run.family.expected_held_pairs(run.cfg, run.traffic)
    ratio = np.array([a["pairs"] for a in steps], np.float64) / expected
    run.notes["moe.held_load_ratio.by_layer"] = [
        round(float(r), 4) for r in ratio.mean(axis=0)]
    run.notes["moe.held_load_ratio.fullest_expert_by_layer"] = [
        round(float(x), 3) for x in
        np.max([a["load_peak"] for a in steps], axis=0)]
    run.notes["moe.held_load_ratio.windows_by_layer"] = [
        int(x) for x in np.max([a["windows"] for a in steps], axis=0)]
    return float(ratio.mean())
