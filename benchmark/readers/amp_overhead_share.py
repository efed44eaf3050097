"""What mixed precision itself costs a step: the share of chip 0's busy
time under the program's ``amp_cast`` (master weights to the compute
copy), ``amp_unscale`` (unscale and finite check) and
``amp_scaler_update`` scopes (``benchmark/scopes.py`` ``phase``)."""

from benchmark import scopes


def read(run) -> "float | None":
    return scopes.share(run, scopes.phase_seconds(run).get("amp"))
