"""The compiled step's HLO text, for a reader that needs more of it than
``Run.op_names``.

``drivers/train.py`` hands the readers the ``op_name``s of the step it
compiled and not the text itself.  :func:`text` builds the same step
again from what ``Run`` holds (the configuration, the traffic, the
chips, the family), through that module's own ``make_step``, and
lowers and compiles it with the arguments ``build`` gave it: the state as
shapes (``jax.eval_shape`` of the same initialiser, so nothing is put on
the device) and one batch from the same feed.  With the persistent
compilation cache this is a read of the executable the run compiled.
It runs only in a traced run, after the window and after the reference.
"""

from __future__ import annotations

import numpy as np

from benchmark import trace, weights


def text(run) -> "str | None":
    """``compiled.as_text()`` of the run's step, or ``None`` where the
    step built again is not the one that ran (its ``op_name``s differ
    from ``run.op_names``)."""
    import jax

    from apex_tpu.data import prefetch_to_device
    from benchmark.drivers import train

    cell = {"parameters": run.traffic, "chips": run.chips}
    made = train.make_step(cell, run.cfg, run.family,
                           jax.devices()[:run.chips])
    a, spec = made["a"], made["spec"]
    state = jax.eval_shape(
        jax.jit(lambda k: a.init(weights.make(spec, k)),
                out_shardings=made["replicated"]), weights.seed_key(0))
    rows = run.traffic["rows_per_chip"] * run.chips
    host = run.family.make_batch(np.random.default_rng(0), rows, run.cfg,
                                 run.traffic)
    batch = next(prefetch_to_device(iter([host]), lookahead=1,
                                    sharding=made["by_rows"]))
    hlo_text = jax.jit(made["step_fn"], donate_argnums=(0,)).lower(
        state, *batch).compile().as_text()
    return hlo_text if trace.op_names(hlo_text) == run.op_names else None
