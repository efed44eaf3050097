"""Share of chip 0's busy time in the output head and the loss, forward
and backward: ``lm_head`` and ``lm_loss`` for the decoder; the MLM and
NSP heads and ``pretraining_loss`` for BERT (``benchmark/scopes.py``
``block``)."""

from benchmark import scopes


def read(run) -> "float | None":
    return scopes.share(run, scopes.block_seconds(run).get("head_loss"))
