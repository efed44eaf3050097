"""Causal language modelling through ``apex_tpu.models.kimi_linear``:
Kimi Delta Attention on the layers the published list names, latent
attention without positions on the others, a dense first layer, then
routed and shared experts, on one chip's share of an expert-parallel
deployment."""

from __future__ import annotations

from benchmark import flops, kda_flops, scopes
from benchmark.families import deepseek_v3 as _deepseek
from benchmark.families.deepseek_v3 import (  # noqa: F401
    layer_norms, make_batch, tokens_per_row)
from benchmark.reference import kimi_linear as reference  # noqa: F401  (the plain reference)

CAUSAL = True

# the KDA mixer is the ``attention`` module of its block, as the latent
# one is; ``o_norm`` and ``kv_norm`` lie inside it and count there
scopes.BLOCK_SEGMENTS.setdefault("kimi_linear", {
    "head_loss": ("lm_head", "lm_loss"),
    "mlp": ("mlp",),
    "attention": ("attention",),
    "norm": ("attn_norm", "ffn_norm", "final_norm"),
    "embed": ("tok_emb",)})


def layer_counts(cfg: dict) -> "tuple[int, int]":
    """``(KDA layers, latent layers)`` among the layers held."""
    kinds = reference.kinds(cfg)
    return kinds.count("kda"), kinds.count("latent")


def program_model(cfg: dict):
    """The program's own model at this configuration's sizes."""
    from apex_tpu.models.kimi_linear import KimiLinearConfig, KimiLinearModel
    lin = cfg["linear_attn_config"]
    return KimiLinearModel(KimiLinearConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["num_experts"],
        n_routed_experts_held=cfg["num_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        n_shared_experts=cfg["num_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_token"],
        scoring_func=cfg["moe_router_activation_func"],
        norm_topk_prob=cfg["moe_renormalize"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        mla_use_nope=cfg["mla_use_nope"],
        kda_layers=tuple(n for n in lin["kda_layers"]
                         if n <= cfg["num_hidden_layers"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        gate_rank=cfg["kda_gate_rank"], chunk_size=cfg["kda_chunk_size"],
        l2_norm_eps=cfg["kda_l2_norm_eps"],
        dt_init_range=tuple(cfg["kda_dt_init_range"]),
        remat=bool(cfg["program"].get("remat", False))))


def with_dt_shift(params: dict, cfg: dict) -> dict:
    """``params`` with every KDA layer's ``dt_bias`` moved from the
    seed's small draw about nought (``benchmark/weights.py``) to where
    the published initialisation puts it, by the same constant the
    reference adds (``reference.dt_shift``): a shift of a parameter,
    which neither its gradient nor Adam's step sees."""
    shift = reference.dt_shift(cfg)
    params = dict(params)
    for n, kind in enumerate(reference.kinds(cfg)):
        if kind == "kda":
            block = dict(params[f"block_{n}"])
            block["attention"] = dict(
                block["attention"],
                dt_bias=block["attention"]["dt_bias"] + shift)
            params[f"block_{n}"] = block
    return params


def program_loss(cfg: dict, traffic: dict):
    """``loss_fn(params, ids)`` on the program's own model."""
    from apex_tpu.models.gpt import lm_loss
    model = program_model(cfg)

    def loss_fn(params, ids):
        logits = model.apply({"params": with_dt_shift(params, cfg)}, ids)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    return loss_fn


def step_state(cfg: dict, traffic: dict):
    """The correction biases' balance update, as the DeepSeek-V3 family
    has it, on this model and under its keys."""
    return _deepseek.step_state(
        cfg, traffic, model=program_model(cfg),
        prepare=lambda params: with_dt_shift(params, cfg),
        names=reference.as_deepseek(cfg))


def expected_held_pairs(cfg: dict, traffic: dict) -> float:
    return _deepseek.expected_held_pairs(reference.as_deepseek(cfg), traffic)


def planted_faults(cfg: dict, traffic: dict) -> dict:
    """Faults of this model's own for ``benchmark/calibrate_faults.py``:
    per name the configuration the reference is computed under in the
    program's place and what it sees of each batch.  Each has to come
    out not ``correct``, but for the two ``probe_*`` entries: those are
    readings, not faults.  They round the recurrence's ``g`` or its
    state to bfloat16 and read *under* the program's own gaps, which
    moved (token, expert) pairs set: no limit on the worst leaf of the
    whole model tells them from float32 (PERF.md section 7 row 6f)."""
    half = traffic["seq"] // 2
    return {
        "half_tokens": (cfg, lambda batch: tuple(a[:, :half]
                                                 for a in batch)),
        "no_decay": (dict(cfg, planted="no_decay"), None),
        "beta_one": (dict(cfg, planted="beta_one"), None),
        "rotary_on_latent": (dict(cfg, mla_use_nope=False), None),
        "unscaled": (dict(cfg, routed_scaling_factor=1.0), None),
        **_deepseek.recipe_faults(cfg, traffic),
        "probe_g_bfloat16": (dict(cfg, planted="g_bfloat16"), None),
        "probe_state_bfloat16": (dict(cfg, planted="state_bfloat16"),
                                 None)}


def recurrence(cfg: dict, traffic: dict) -> dict:
    """What ``kda.recurrence_roofline`` counts the rule's work from."""
    lin = cfg["linear_attn_config"]
    return {"head_dim": lin["head_dim"], "heads": lin["num_heads"],
            "layers": layer_counts(cfg)[0]}


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """From shapes alone: the matrices every token meets (the routed
    experts at the *expected* ``k * held / n`` a token), the latent
    layers' six attention passes at their own widths, and the chunk-64
    delta rule of the KDA layers."""
    a = attention(cfg, traffic)
    kda_layers, latent_layers = layer_counts(cfg)
    return (flops.matmul_train_flops(kda_flops.matrix_weights_per_token(
                cfg, reference.as_deepseek(cfg), kda_layers, latent_layers))
            + flops.attention_train_flops_per_token(
                a["seq"], a["hidden"], a["layers"], CAUSAL)
            + kda_flops.recurrence_train_flops_per_token(
                **recurrence(cfg, traffic)))


def attention(cfg: dict, traffic: dict) -> dict:
    """What ``flash_attention_roofline`` counts: the latent layers alone
    (the KDA layers run no flash kernel), at the widths
    ``families/deepseek_v3.py`` gives them."""
    return dict(_deepseek.attention(cfg, traffic),
                layers=layer_counts(cfg)[1])
