"""One file per architecture family, found by the ``family`` key of a
configuration.  A family says how the program builds the model and its
loss, how a batch is drawn, which plain reference it is compared with,
and how many operations and bytes its step needs.  Every function
takes the configuration (``cfg``) and the cell's traffic as dicts.

Optional: ``step_state(cfg, traffic)`` for a step that keeps state no
gradient moves (a router's correction bias under its balance update),
with its counterpart in the family's reference; ``drivers/train.py``
says what it returns.  Where a family has them,
``planted_faults(cfg, traffic)`` is read by ``calibrate_faults.py`` and
``expected_held_pairs(cfg, traffic)`` by the ``moe.held_load_ratio``
reader."""
