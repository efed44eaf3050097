"""Operations and bytes of the gated delta rule at chunk 64, from shapes
alone (``benchmark/flops.py`` says why nothing here looks at a run), and
the matrix weights a token meets in a Kimi-Linear-shaped decoder.

The count is the chunkwise algorithm's, whatever implements it, so a
later kernel is read on the same work.  Per chunk of ``C`` tokens and
head of ``d_k = d_v = d``, forward, with a triangular product at half
its square: the two decayed score matrices ``K K^T`` and ``Q K^T`` (``C^2
d`` each), the unit-triangular solve applied to ``[beta V | beta K]``
(``2 C^2 d``) after its own elimination (``C^3 / 3``), the state's read
by ``W_k`` and by ``Q`` and its update by ``K^T U`` (``2 C d^2`` each)
and the scores applied to the pseudo-values (``C^2 d``).  A backward
pass costs two products for every forward one.  Recomputation is the
program's choice and is not counted.

Bytes, per token and head: forward reads q, k, v (``2 d`` each), g (``4
d``), beta (4) and writes o (``2 d``); backward reads them again with
o's cotangent and writes the cotangents of q, k, v (``2 d`` each), g (``4
d``) and beta (4); and one float32 state a chunk is written going
forward and read coming back (``2 x 4 d^2 / C`` a token), which is what
the backward pass of a chunked rule keeps.
"""

from __future__ import annotations

from benchmark import moe_flops

CHUNK = 64


def recurrence_train_flops_per_token(head_dim: int, heads: int,
                                     layers: int) -> float:
    c, d = CHUNK, head_dim
    forward = (2 * c * c * d            # K K^T and Q K^T, triangular
               + c ** 3 / 3             # the elimination
               + 2 * c * c * d          # T applied to [beta V | beta K]
               + 3 * 2 * c * d * d      # W_k S, Q S, K^T U
               + c * c * d)             # Aq U, triangular
    return layers * heads * 3.0 * forward / c


def recurrence_train_bytes_per_token(head_dim: int, heads: int,
                                     layers: int) -> float:
    d = head_dim
    forward = 3 * 2 * d + 4 * d + 4 + 2 * d
    backward = forward + (3 * 2 * d + 4 * d + 4)
    states = 2 * 4 * d * d / CHUNK
    return layers * heads * float(forward + backward + states)


def kda_weights(cfg: dict) -> int:
    """A KDA mixer's matrices: q, k, v and o, the two low-rank gates and
    beta.  The short convolutions (4 taps a channel) are no matrix
    product and are left out, as the embedding lookup is."""
    h, lin = cfg["hidden_size"], cfg["linear_attn_config"]
    wide, rank = lin["num_heads"] * lin["head_dim"], cfg["kda_gate_rank"]
    return (4 * h * wide + 2 * (h * rank + rank * wide)
            + h * lin["num_heads"])


def matrix_weights_per_token(cfg: dict, deepseek_cfg: dict, kda_layers: int,
                             latent_layers: int) -> float:
    """Every matrix weight one token is multiplied by in a forward pass.
    ``deepseek_cfg`` is the configuration under the names
    ``benchmark/moe_flops.py`` reads; its count is taken with no
    attention at all, and the two kinds of mixer are added here."""
    no_mixer = (moe_flops.matrix_weights_per_token(deepseek_cfg)
                - deepseek_cfg["num_hidden_layers"]
                * moe_flops.attention_weights(deepseek_cfg))
    return (no_mixer + kda_layers * kda_weights(cfg)
            + latent_layers * moe_flops.attention_weights(deepseek_cfg))
