"""Operations and bytes of sliding-window attention, from shapes alone
(``benchmark/flops.py`` says why nothing here looks at a run).

Query ``i`` of a row of ``L`` sees ``min(i + 1, W)`` keys under a window
of ``W``: :func:`mean_keys` of them a query, 496.03 at ``L`` 8192 and
``W`` 512.  A training step takes six matmul passes over them (QK^T and
PV forward; dV, dP, dQ, dK backward), ``2 x keys`` FLOPs a pass per
token and q-head lane.  Its least HBM traffic is ``flops.py``'s twelve
rows a token and layer, split by width: q, o (read and written
forward, read backward), do and dq at the q heads' width; k, v (read
forward and backward), dk and dv at the KV heads'.  The same count
whatever implements the window, a band of key blocks or a masked
causal pass.
"""

from __future__ import annotations

from benchmark import flops


def mean_keys(seq: int, window: int) -> float:
    """The mean over a row's queries of the keys each sees."""
    w = min(window, seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def train_flops_per_token(seq: int, window: int, hidden: int,
                          layers: int) -> float:
    return (flops.ATTN_TRAIN_PASSES * layers * 2.0 * mean_keys(seq, window)
            * hidden)


def train_bytes_per_token(hidden: int, kv_hidden: int, layers: int,
                          itemsize: int = 2) -> float:
    return layers * 6.0 * (hidden + kv_hidden) * itemsize
