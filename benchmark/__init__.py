"""apex_tpu's on-chip benchmark: one cell, one run, one JSON line.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.  Everything that belongs to one configuration, one
cell, one driver, one family or one per-layer metric sits in a file of
its own under this directory and is found by the name ``BENCHMARK.json``
gives; ``run.py`` holds no table of them.
"""
