"""Causal language modelling through ``apex_tpu.models.deepseek_v3``:
latent attention, a dense first layer, then routed and shared experts,
on one chip's share of an expert-parallel deployment."""

from __future__ import annotations

import numpy as np

from benchmark import flops, moe_flops, scopes
from benchmark.reference import deepseek_v3 as reference  # noqa: F401  (the plain reference)

CAUSAL = True

# ``scopes.block`` finds a family's blocks here; a family the accepted
# benchmark does not know brings its own when it is imported, which
# ``run.resolve`` does before any reader runs.  The latent norm
# (``kv_norm``) lies inside ``attention`` and counts there.
scopes.BLOCK_SEGMENTS.setdefault("deepseek_v3", {
    "head_loss": ("lm_head", "lm_loss"),
    "mlp": ("mlp",),
    "attention": ("attention",),
    "norm": ("attn_norm", "ffn_norm", "final_norm"),
    "embed": ("tok_emb",)})


def program_model(cfg: dict):
    """The program's own model at this configuration's sizes."""
    from apex_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3Model
    return DeepseekV3Model(DeepseekV3Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        n_routed_experts=cfg["n_routed_experts"],
        n_routed_experts_held=cfg["n_routed_experts_held"],
        first_expert=cfg.get("first_expert_held", 0),
        n_shared_experts=cfg["n_shared_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        scoring_func=cfg["scoring_func"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rms_norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        remat=bool(cfg["program"].get("remat", False))))


def program_loss(cfg: dict, traffic: dict):
    """``loss_fn(params, ids)`` on the program's own model."""
    from apex_tpu.models.gpt import lm_loss
    model = program_model(cfg)

    def loss_fn(params, ids):
        logits = model.apply({"params": params}, ids)
        return lm_loss(logits[:, :-1], ids[:, 1:])

    return loss_fn


def balance_update(bias, counts, rate: float):
    """The published balance update of the correction bias (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2, after arXiv:2408.15664) on the
    program's side: ``bias + rate * sign(mean(counts) - counts)``,
    ``counts`` the (token, expert) pairs of the step's whole batch over
    *all* the router's experts, float32.  The program has no routine for
    it and its ``stats`` carry no such counts: a benchmark PR may not
    add them to ``apex_tpu/parallel/moe.py`` (PERF.md section 7), so the
    rule stands here and the counts are read from the router's own
    choice.  The reference has its own (``reference.balance_update``)."""
    import jax.numpy as jnp
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def _is_router_call(module, method: str) -> bool:
    return type(module).__name__ == "Router" and method == "__call__"


def expected_held_pairs(cfg: dict, traffic: dict) -> float:
    """What ``moe.held_load_ratio`` measures the held experts' pairs
    against: the pairs they get a step when routing is even."""
    return (traffic["rows_per_chip"] * traffic["seq"]
            * cfg["num_experts_per_tok"] * cfg["n_routed_experts_held"]
            / cfg["n_routed_experts"])


def step_state(cfg: dict, traffic: dict, model=None, prepare=None,
               names=None):
    """What the step keeps that no gradient moves
    (``drivers/train.py``): nothing without a ``balance_rate`` in the
    cell's traffic; with one, every expert layer's correction bias,
    moved by :func:`balance_update` once a step.  The loss returns as
    ``aux`` the program's own counters (``pairs``, ``load_peak``,
    ``windows``, one entry an expert layer) and ``counts``, what each of
    all the router's experts got, read from the experts the program's
    ``Router`` chose.  On one chip the counts are this chip's tokens';
    the deployment's all-reduce of them is left out.  ``model``,
    ``prepare`` (of the parameters) and ``names`` (the configuration
    under this family's keys) are for a family that shares the routine."""
    rate = traffic.get("balance_rate")
    if not rate:
        return None
    import jax
    import jax.numpy as jnp
    from apex_tpu.models.gpt import lm_loss
    model = model or program_model(cfg)
    names = names or cfg
    paths = reference.bias_paths(names)
    n_experts = names["n_routed_experts"]
    expected = expected_held_pairs(names, traffic)

    def loss_fn(params, ids):
        if prepare is not None:
            params = prepare(params)
        (logits, stats), seen = model.apply(
            {"params": params}, ids, True,
            capture_intermediates=_is_router_call,
            mutable=["intermediates"])
        stats = stats.get("experts", stats)
        chosen = [seen["intermediates"][p.split("/")[0]]["router"]
                  ["__call__"][0].experts for p in paths]
        counts = jnp.stack([
            jnp.sum(e.reshape(-1, 1) == jnp.arange(n_experts), axis=0,
                    dtype=jnp.int32) for e in chosen])
        aux = dict(counts=counts, pairs=stats["pairs"],
                   load_peak=stats["load_peak"], windows=stats["windows"])
        return lm_loss(logits[:, :-1], ids[:, 1:]), \
            jax.lax.stop_gradient(aux)

    def update(values: dict, aux: dict) -> dict:
        return {p: balance_update(values[p], aux["counts"][i], rate)
                for i, p in enumerate(paths)}

    def describe(steps: list) -> str:
        ratio = np.array([a["pairs"] for a in steps]) / expected
        fullest = max(float(np.max(a["load_peak"])) for a in steps)
        return (f"held pairs over the {expected:.0f} of even "
                f"routing, by expert layer, at the window's last step "
                f"{[round(float(r), 3) for r in ratio[-1]]}, least "
                f"{ratio.min():.3f} and most {ratio.max():.3f} over its "
                f"{len(steps)} steps; fullest held expert over the held "
                f"mean, most {fullest:.2f}; windows run in a layer, most "
                f"{max(int(np.max(a['windows'])) for a in steps)}")

    return {"loss": loss_fn, "paths": paths, "update": update,
            "describe": describe}


def make_batch(rng: np.random.Generator, rows: int, cfg: dict,
               traffic: dict):
    """``(ids,)``: rows of ``seq`` ids drawn evenly from the slice of the
    vocabulary held here."""
    return (rng.integers(0, cfg["vocab_size"], (rows, traffic["seq"]),
                         dtype=np.int32),)


def planted_faults(cfg: dict, traffic: dict) -> dict:
    """Faults of this model's own for ``benchmark/calibrate_faults.py``:
    per name the configuration the reference is computed under in the
    program's place, what it sees of each batch and (where there is a
    third entry) what it is told of the traffic.  Each has to come out
    not ``correct``."""
    half = traffic["seq"] // 2
    return {
        "half_tokens": (cfg, lambda batch: tuple(a[:, :half]
                                                 for a in batch)),
        "unnormalised": (dict(cfg, norm_topk_prob=False), None),
        "unscaled": (dict(cfg, routed_scaling_factor=1.0), None),
        **recipe_faults(cfg, traffic)}


def recipe_faults(cfg: dict, traffic: dict) -> dict:
    """The recipe's two parts left out, for a cell whose traffic has
    them: a fault's third entry is what it changes of the traffic."""
    return {name: (cfg, None, {key: 0})
            for name, key in (("no_warmup", "lr_warmup_steps"),
                              ("no_balance", "balance_rate"))
            if traffic.get(key)}


def tokens_per_row(traffic: dict) -> int:
    return traffic["seq"]


def flops_per_token(cfg: dict, traffic: dict) -> float:
    """From shapes alone: the matrices every token meets, the routed
    experts at the *expected* ``k * held / n`` a token (never a count
    taken from the run, so the share cannot move with the routing), and
    attention's six passes at their own widths."""
    a = attention(cfg, traffic)
    return (flops.matmul_train_flops(moe_flops.matrix_weights_per_token(cfg))
            + flops.attention_train_flops_per_token(
                a["seq"], a["hidden"], a["layers"], CAUSAL))


def attention(cfg: dict, traffic: dict) -> dict:
    """``hidden`` is heads times the *mean* width of the six passes: q
    and k score at ``qk_nope + qk_rope`` (192) and v, o are ``v_head_dim``
    (128) wide, so the passes QK^T, dP.. sum to 3 x 192 + 3 x 128 = 6 x
    160 a head, and the twelve rows of bytes (q, k, dq, dk at 192 twice
    over; v, o, do, dv at 128) to 12 x 160: 32 x 160 = 5120 is exact for
    both counts of ``flash_attention_roofline``."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {"seq": traffic["seq"],
            "hidden": cfg["num_attention_heads"]
            * (qk + cfg["v_head_dim"]) // 2,
            "layers": cfg["num_hidden_layers"], "causal": CAUSAL}


def layer_norms(cfg: dict, traffic: dict) -> dict:
    return {"features": cfg["hidden_size"],
            "norms": 2 * cfg["num_hidden_layers"] + 1}
