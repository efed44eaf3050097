"""Causal language modelling through ``apex_tpu.models.gpt``."""

from __future__ import annotations

import numpy as np

from benchmark import flops
from benchmark.reference import gpt as reference  # noqa: F401  (the plain reference)

CAUSAL = True


def program_loss(cfg: dict, traffic: dict):
    """``loss_fn(params, ids)`` on the program's own model."""
    from apex_tpu.models.gpt import GPTConfig, GPTModel, lm_loss
    model = GPTModel(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        intermediate_size=cfg["n_inner"],
        layer_norm_eps=cfg["layer_norm_epsilon"],
        rope_theta=cfg["rope_theta"],
        remat=bool(cfg["program"].get("remat", False))))

    def loss_fn(params, ids):
        logits = model.apply({"params": params}, ids)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    return loss_fn


def make_batch(rng: np.random.Generator, rows: int, cfg: dict,
               traffic: dict):
    """``(ids,)``: rows of ``seq`` ids drawn evenly from the published
    vocabulary."""
    return (rng.integers(0, cfg["vocab_size"], (rows, traffic["seq"]),
                         dtype=np.int32),)


def tokens_per_row(traffic: dict) -> int:
    return traffic["seq"]


def flops_per_token(cfg: dict, traffic: dict) -> float:
    h, i, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    matrices = layers * (4 * h * h + 2 * h * i) + h * cfg["vocab_size"]
    return (flops.matmul_train_flops(matrices)
            + flops.attention_train_flops_per_token(
                traffic["seq"], h, layers, CAUSAL))


def attention(cfg: dict, traffic: dict) -> dict:
    return {"seq": traffic["seq"], "hidden": cfg["n_embd"],
            "layers": cfg["n_layer"], "causal": CAUSAL}


def layer_norms(cfg: dict, traffic: dict) -> dict:
    return {"features": cfg["n_embd"], "norms": 2 * cfg["n_layer"] + 1}
