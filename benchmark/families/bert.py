"""BERT pretraining (MLM + NSP) through ``apex_tpu.models.bert``."""

from __future__ import annotations

import numpy as np

from benchmark import flops
from benchmark.reference import bert as reference  # noqa: F401  (the plain reference)

CAUSAL = False


def program_loss(cfg: dict, traffic: dict):
    """``loss_fn(params, *batch)`` on the program's own model."""
    import jax.numpy as jnp

    from apex_tpu.models.bert import (BertConfig, BertForPreTraining,
                                      pretraining_loss)
    model = BertForPreTraining(BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
        remat=bool(cfg["program"].get("remat", False))))

    def loss_fn(params, ids, types, labels, nsp, mask):
        mlm_logits, nsp_logits = model.apply({"params": params}, ids, types)
        # the loss in float32 over the model's bfloat16 logits, as
        # ``lm_loss`` takes it for GPT: left alone under O2,
        # ``pretraining_loss`` takes its log-sum-exp in bfloat16
        # (PERF.md, Findings PR 24)
        return pretraining_loss(mlm_logits.astype(jnp.float32),
                                nsp_logits.astype(jnp.float32),
                                labels, nsp, mask)

    return loss_fn


def masked_per_row(traffic: dict) -> int:
    return max(1, round(traffic["mask_rate"] * traffic["seq"]))


def make_batch(rng: np.random.Generator, rows: int, cfg: dict,
               traffic: dict):
    """Rows of ``seq`` random word ids in two segments, ``mask_rate`` of
    the positions of every row replaced by the mask token and scored,
    and a next-sentence label: ``(ids, types, labels, nsp, mask)``.
    Every row masks the same number of positions, so every seed gives a
    step the same work."""
    seq, vocab = traffic["seq"], cfg["vocab_size"]
    words = rng.integers(min(1000, vocab // 8), vocab, (rows, seq),
                         dtype=np.int32)
    split = rng.integers(seq // 4, 3 * seq // 4, (rows, 1))
    types = (np.arange(seq)[None, :] >= split).astype(np.int32)
    chosen = np.argsort(rng.random((rows, seq)), axis=1)[
        :, :masked_per_row(traffic)]
    mask = np.zeros((rows, seq), np.int32)
    np.put_along_axis(mask, chosen, 1, axis=1)
    ids = np.where(mask == 1, np.int32(cfg["mask_token_id"] % vocab), words)
    labels = np.where(mask == 1, words, 0).astype(np.int32)
    nsp = rng.integers(0, 2, (rows,), dtype=np.int32)
    return ids.astype(np.int32), types, labels, nsp, mask


def tokens_per_row(traffic: dict) -> int:
    return traffic["seq"]


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """Model FLOPs a training step requires per token.  The encoder's
    matrices see every token; the MLM transform and decoder are needed
    only at the masked positions, the pooler and NSP head once a row."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    layers, seq = cfg["num_hidden_layers"], traffic["seq"]
    encoder = layers * (4 * h * h + 2 * h * i)
    mlm = (h * h + h * cfg["vocab_size"]) * masked_per_row(traffic) / seq
    nsp = (h * h + 2 * h) / seq
    return (flops.matmul_train_flops(encoder + mlm + nsp)
            + flops.attention_train_flops_per_token(seq, h, layers, CAUSAL))


def attention(cfg: dict, traffic: dict) -> dict:
    return {"seq": traffic["seq"], "hidden": cfg["hidden_size"],
            "layers": cfg["num_hidden_layers"], "causal": CAUSAL}


def layer_norms(cfg: dict, traffic: dict) -> dict:
    """LayerNorms over every token: the embedding's and two a layer (the
    MLM head's is needed at the masked positions only and is added as
    that share of one)."""
    return {"features": cfg["hidden_size"],
            "norms": 1 + 2 * cfg["num_hidden_layers"]
            + masked_per_row(traffic) / traffic["seq"]}
