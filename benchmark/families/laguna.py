"""Causal language modelling through ``apex_tpu.models.laguna``:
sliding-window attention beside full layers with YaRN partial rotary,
q head counts by layer, per-head output gates, a dense first layer, then
routed experts beside a shared one and an untied head, on one chip's
share of an expert-parallel deployment."""

from __future__ import annotations

import numpy as np

from benchmark import flops, moe_flops, scopes, window_flops
from benchmark.families import deepseek_v3 as _deepseek
from benchmark.families.deepseek_v3 import (  # noqa: F401
    make_batch, tokens_per_row)
from benchmark.reference import laguna as reference  # noqa: F401  (the plain reference)

CAUSAL = True

# the sliding layers' window opens ``swa_window`` inside ``attention``
scopes.BLOCK_SEGMENTS.setdefault("laguna", {
    "head_loss": ("lm_head", "lm_loss"),
    "mlp": ("mlp",),
    "attention": ("attention",),
    "norm": ("attn_norm", "ffn_norm", "final_norm"),
    "embed": ("tok_emb",)})


def program_model(cfg: dict):
    """The program's own model at this configuration's sizes."""
    from apex_tpu.models.laguna import LagunaConfig, LagunaModel
    if (cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["moe_apply_router_weight_on_input"]
            or cfg["gating"] is not True):
        raise ValueError("this model has no attention bias, an untied "
                         "head, the routing weights on the experts' "
                         "outputs and an output gate")
    names = reference.as_deepseek(cfg)
    rope = cfg["rope_parameters"]
    return LagunaModel(LagunaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=cfg["num_experts"],
        n_routed_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        n_shared_experts=names["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        num_attention_heads_per_layer=tuple(
            cfg["num_attention_heads_per_layer"]),
        sliding_window=cfg["sliding_window"],
        full_rope=tuple(rope["full_attention"].items()),
        sliding_rope=tuple(rope["sliding_attention"].items()),
        first_layer=cfg["first_layer_held"],
        remat=bool(cfg["program"].get("remat", False))))


def program_loss(cfg: dict, traffic: dict):
    """``loss_fn(params, ids)`` on the program's own model."""
    from apex_tpu.models.gpt import lm_loss
    model = program_model(cfg)

    def loss_fn(params, ids):
        logits = model.apply({"params": params}, ids)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    return loss_fn


def step_state(cfg: dict, traffic: dict):
    """The correction biases' balance update, as the DeepSeek-V3 family
    has it, on this model and under its keys; the window's ``step state``
    line also gives each expert layer's held load over the window."""
    kept = _deepseek.step_state(cfg, traffic, model=program_model(cfg),
                                names=reference.as_deepseek(cfg))
    if kept:
        describe, even = kept["describe"], expected_held_pairs(cfg, traffic)

        def with_mean(steps):
            mean = np.mean([a["pairs"] for a in steps], axis=0) / even
            ratios = [round(float(r), 3) for r in mean]
            return (f"{describe(steps)}; held pairs over even, the window's "
                    f"mean by expert layer {ratios}")

        kept["describe"] = with_mean
    return kept


def expected_held_pairs(cfg: dict, traffic: dict) -> float:
    return _deepseek.expected_held_pairs(reference.as_deepseek(cfg), traffic)


def planted_faults(cfg: dict, traffic: dict) -> dict:
    """Faults of this model's own for ``benchmark/calibrate_faults.py``:
    per name the configuration the reference is computed under in the
    program's place (``reference``'s ``planted``) and, for the recipe's
    two parts, what it is told of the traffic.  Each has to come out not
    ``correct``."""
    return {**{name: (dict(cfg, planted=name), None)
               for name in ("no_window", "no_gate", "no_yarn",
                            "full_rotary", "kv_group_modulo")},
            **_deepseek.recipe_faults(cfg, traffic)}


def attention_weights(cfg: dict, heads: int) -> int:
    """A layer's five projections at its q head count: q and the gate
    from the hidden width to ``heads`` heads and to ``heads``; k and v to
    the KV heads; ``o_proj`` back."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    return 2 * h * heads * d + 2 * h * cfg["num_key_value_heads"] * d \
        + h * heads


def matrix_weights_per_token(cfg: dict) -> float:
    """Every matrix weight one token is multiplied by in a forward pass:
    each layer's projections, the dense feed-forward, the routers, the
    shared expert, the expected ``k x held / n`` of the routed experts
    held here, and the head (the embedding lookup is no product)."""
    h, names = cfg["hidden_size"], reference.as_deepseek(cfg)
    total = float(h * cfg["vocab_size"])
    expert = moe_flops.expert_weights(names)
    for _, dense, heads in reference.kinds(cfg):
        total += attention_weights(cfg, heads)
        total += (3 * h * cfg["intermediate_size"] if dense else
                  h * cfg["num_experts"]
                  + names["n_shared_experts"] * expert
                  + moe_flops.routed_experts_per_token(names) * expert)
    return total


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """From shapes alone: the matrices every token meets (the routed
    experts at the *expected* ``k * held / n`` a token, never a count
    taken from the run), the full layers' six causal passes over their
    q heads and the sliding layers' six over their band
    (``benchmark/window_flops.py``)."""
    a, w = attention(cfg, traffic), windows(cfg, traffic)
    return (flops.matmul_train_flops(matrix_weights_per_token(cfg))
            + flops.attention_train_flops_per_token(
                a["seq"], a["hidden"], a["layers"], CAUSAL)
            + window_flops.train_flops_per_token(**{
                k: w[k] for k in ("seq", "window", "hidden", "layers")}))


def attention(cfg: dict, traffic: dict) -> dict:
    """What ``flash_attention_roofline`` counts: the full layers alone, at
    their q heads' width (the sliding layers' kernels are the window's
    own, ``swa.window_roofline``).  Its bytes take k, v, dk and dv that
    wide too, six times their grouped width; at 8192 keys the FLOPs
    bound the count either way."""
    full = [h for s, _, h in reference.kinds(cfg) if not s]
    if len(set(full)) > 1:
        raise ValueError("the full layers share one q head count")
    return {"seq": traffic["seq"], "hidden": full[0] * cfg["head_dim"],
            "layers": len(full), "causal": CAUSAL}


def windows(cfg: dict, traffic: dict) -> dict:
    """What ``swa.window_roofline`` counts: the sliding layers, their
    window, their q heads' and KV heads' widths."""
    slide = [h for s, _, h in reference.kinds(cfg) if s]
    if len(set(slide)) > 1:
        raise ValueError("the sliding layers share one q head count")
    d = cfg["head_dim"]
    return {"seq": traffic["seq"], "window": cfg["sliding_window"],
            "hidden": slide[0] * d,
            "kv_hidden": cfg["num_key_value_heads"] * d,
            "layers": len(slide)}


def layer_norms(cfg: dict, traffic: dict) -> dict:
    return {"features": cfg["hidden_size"],
            "norms": 2 * cfg["num_hidden_layers"] + 1}
