"""Operations and bytes of the work a cell does, from shapes alone.

Nothing here looks at a compiled program: the count must not move when
the program does, or an optimisation could lower its own utilization.

Model FLOPs are what the forward and backward passes *require*: a matrix
of ``p`` parameters applied to one token costs ``2 p`` forward and ``4 p``
backward; one attention matmul pass costs ``2 L d`` per token, head and
layer (halved when causal), and a training step needs six of them (QK^T
and PV forward; dV, dP, dQ, dK backward).  Recomputation (remat, the
fused backward's seventh pass) is the program's choice and is not
counted; nor is the embedding lookup.
"""

from __future__ import annotations

#: attention matmul passes a training step requires (2 forward, 4 backward)
ATTN_TRAIN_PASSES = 6


def matmul_train_flops(params_per_token: float) -> float:
    """Forward + backward FLOPs per token of matrices holding
    ``params_per_token`` weights each token is multiplied by."""
    return 6.0 * params_per_token


def attention_pass_flops_per_token(seq: int, hidden: int, layers: int,
                                   causal: bool) -> float:
    """One attention matmul pass, all heads and layers, per token:
    ``2 * L * hidden`` (``2 B H L^2 D`` over ``B L`` tokens)."""
    return layers * 2.0 * seq * hidden * (0.5 if causal else 1.0)


def attention_train_flops_per_token(seq: int, hidden: int, layers: int,
                                    causal: bool) -> float:
    return ATTN_TRAIN_PASSES * attention_pass_flops_per_token(
        seq, hidden, layers, causal)


def attention_train_bytes_per_token(hidden: int, layers: int,
                                    itemsize: int = 2) -> float:
    """Least HBM traffic of attention per token: forward reads q, k, v
    and writes o; backward reads q, k, v, o, do and writes dq, dk, dv.
    Twelve ``hidden``-wide rows per token and layer."""
    return layers * 12.0 * hidden * itemsize


def roofline_seconds(flops: float, nbytes: float, peak) -> "tuple[float, str]":
    """The least time the chip could take, and which peak bounds it."""
    by_flops = flops / peak.bf16_flops_per_s
    by_bytes = nbytes / peak.hbm_bytes_per_s
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
