"""A Kimi-Linear-shaped decoder (Moonshot AI, 2025; ``model_type``
``kimi_linear``; the Kimi Linear report, arXiv 2510.26692), written from
the published description: pre-RMSNorm blocks whose mixer is Kimi Delta
Attention (KDA) on the layers ``linear_attn_config.kda_layers`` lists
and latent attention without positions on ``full_attn_layers`` (both
count from 1), a dense gated feed-forward in the first
``first_k_dense_replace`` layers and routed plus shared experts after,
next-token cross entropy.

KDA, per head of ``d_k = d_v = head_dim``, on the normed input ``x_t``::

    q, k, v = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))
    q_t, k_t = q_t / |q_t|, k_t / |k_t|;   q_t scaled by d_k ** -0.5
    g_t     = -exp(A_log) * softplus(W_fb (W_fa x_t) + dt_bias)
    beta_t  = sigmoid(W_b x_t)
    S_t     = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t     = S_t^T q_t
    y_t     = W_o [rms_norm(o_t) * sigmoid(W_gb (W_ga x_t))]

``conv`` is depthwise and causal over ``short_conv_kernel_size`` tokens
(four shifted products, zeros before the row's start).  **The recurrence
runs token by token** (``lax.scan`` over tokens, in blocks of 64 whose
inside is recomputed in the backward pass, so that one state a block is
kept and not one a token); nothing here is chunked, and nothing is
imported from the program.

The latent layers, the router and the experts are
``benchmark/reference/deepseek_v3.py``'s, given the same share of the
experts and of the vocabulary; with ``mla_use_nope`` the rotary is left
out (the 64 "rope" lanes of q and the one shared ``k_pe`` are scored as
they are).

Float32 throughout.  Departures from the published model, each stated in
the configuration file: the depth, the experts held and the vocabulary
are the chip's share; the correction bias is seeded and held fixed; the
seed's ``dt_bias`` is a small draw *about* the inverse softplus of a step
size spread geometrically over ``kda_dt_init_range`` along a head's
channels (:func:`dt_shift`; ``benchmark/weights.py`` draws every bias
about nought, which would have every channel forget within two tokens).
The control's lower precision (``q``) reaches the projections and q, k,
v as the recurrence meets them; the state, the decay and the router stay
float32.  ``cfg["planted"]`` names a fault ``calibrate_faults.py``
plants: ``no_decay`` (``g = 0``), ``beta_one``, or one of two
precision probes, which no limit of the cell tells from float32:
``g_bfloat16`` (the log-decay rounded before it is exponentiated) and
``state_bfloat16`` (the state rounded after every token).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import common as C
from benchmark.reference import deepseek_v3 as D

BLOCK = 64          #: tokens whose states the backward pass recomputes


def kinds(cfg: dict) -> list:
    """``"kda"`` or ``"latent"`` for each layer held, from the published
    lists (which count from 1)."""
    lin = cfg["linear_attn_config"]
    out = []
    for n in range(1, cfg["num_hidden_layers"] + 1):
        if (n in lin["kda_layers"]) == (n in lin["full_attn_layers"]):
            raise ValueError(f"layer {n} is in both lists or in neither")
        out.append("kda" if n in lin["kda_layers"] else "latent")
    return out


def as_deepseek(cfg: dict) -> dict:
    """This configuration under the names the DeepSeek-V3 reference reads."""
    return dict(cfg, n_routed_experts=cfg["num_experts"],
                n_routed_experts_held=cfg["num_experts_held"],
                n_shared_experts=cfg["num_shared_experts"],
                num_experts_per_tok=cfg["num_experts_per_token"],
                scoring_func=cfg["moe_router_activation_func"],
                norm_topk_prob=cfg["moe_renormalize"])


def param_spec(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    wide = lin["num_heads"] * lin["head_dim"]
    rank, taps = cfg["kda_gate_rank"], lin["short_conv_kernel_size"]
    whole = D.param_spec(as_deepseek(cfg))
    spec = {}
    for n, kind in enumerate(kinds(cfg)):
        a = f"block_{n}/attention"
        for path, leaf in whole.items():
            if path.startswith(f"block_{n}/") and not (
                    kind == "kda" and path.startswith(a + "/")):
                spec[path] = leaf
        if kind == "latent":
            continue
        for name in ("q", "k", "v"):
            spec[f"{a}/{name}_proj/kernel"] = ((h, wide), "matrix")
            spec[f"{a}/{name}_conv/kernel"] = ((taps, wide), "scale")
        spec[f"{a}/f_a_proj/kernel"] = ((h, rank), "matrix")
        spec[f"{a}/f_b_proj/kernel"] = ((rank, wide), "matrix")
        spec[f"{a}/A_log"] = ((lin["num_heads"],), "scale")
        spec[f"{a}/dt_bias"] = ((wide,), "bias")
        spec[f"{a}/b_proj/kernel"] = ((h, lin["num_heads"]), "matrix")
        spec[f"{a}/g_a_proj/kernel"] = ((h, rank), "matrix")
        spec[f"{a}/g_b_proj/kernel"] = ((rank, wide), "matrix")
        spec[f"{a}/o_norm/scale"] = ((lin["head_dim"],), "scale")
        spec[f"{a}/o_proj/kernel"] = ((wide, h), "matrix")
    spec.update({p: leaf for p, leaf in whole.items()
                 if not p.startswith("block_")})
    return spec


totals = D.totals


def dt_shift(cfg: dict):
    """Per channel of a head, ``softplus^-1`` of a step size spread
    geometrically over ``kda_dt_init_range``; the same for every head."""
    lo, hi = cfg["kda_dt_init_range"]
    lin = cfg["linear_attn_config"]
    dt = jnp.exp(jnp.linspace(math.log(lo), math.log(hi), lin["head_dim"]))
    return jnp.tile(jnp.log(jnp.expm1(dt)), lin["num_heads"])


def short_conv(x, p):
    """``y_t = sum_j w_j x_(t - (taps - 1) + j)``, per channel."""
    taps = p["kernel"].shape[0]
    l = x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, j:j + l] * p["kernel"][j] for j in range(taps))


def unit(x, eps):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + eps)


def delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """The recurrence, one token at a time: ``q, k, g (B, L, H, d_k)``,
    ``v (B, L, H, d_v)``, ``beta (B, L, H)`` -> ``o (B, L, H, d_v)``.
    ``state_dtype`` rounds the state after every token (a precision
    probe)."""
    b, l, h, d_k = q.shape
    pad = -l % BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = jnp.moveaxis(x, 1, 0)                  # tokens lead
        return x.reshape((l + pad) // BLOCK, BLOCK, *x.shape[1:])

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = beta_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t,
                                                  state))
        state = (state + k_t[..., None] * u[..., None, :]).astype(
            state_dtype).astype(jnp.float32)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(token, state, xs)

    _, o = jax.lax.scan(block, jnp.zeros((b, h, d_k, v.shape[-1]),
                                         jnp.float32),
                        tuple(map(blocks, (q, k, v, g, beta))))
    return jnp.moveaxis(o.reshape(l + pad, *o.shape[2:]), 0, 1)[:, :l]


def kda(x, p, cfg: dict, q=C.identity):
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    b, l = x.shape[:2]

    def mixed(name):
        y = D.silu(short_conv(C.dense(x, p[f"{name}_proj"], q),
                              p[f"{name}_conv"]))
        return y.reshape(b, l, heads, d)

    q_ = unit(mixed("q"), cfg["kda_l2_norm_eps"]) * d ** -0.5
    k_ = unit(mixed("k"), cfg["kda_l2_norm_eps"])
    v_ = mixed("v")
    f = C.dense(C.dense(x, p["f_a_proj"], q), p["f_b_proj"], q)
    g = -jnp.exp(jnp.repeat(p["A_log"], d)) * jax.nn.softplus(
        f + p["dt_bias"] + dt_shift(cfg))
    beta = jax.nn.sigmoid(C.dense(x, p["b_proj"], q))
    if cfg.get("planted") == "no_decay":
        g = jnp.zeros_like(g)
    if cfg.get("planted") == "beta_one":
        beta = jnp.ones_like(beta)
    if cfg.get("planted") == "g_bfloat16":
        g = g.astype(jnp.bfloat16).astype(jnp.float32)
    o = delta_rule(q(q_), q(k_), q(v_), g.reshape(b, l, heads, d), beta,
                   jnp.bfloat16 if cfg.get("planted") == "state_bfloat16"
                   else jnp.float32)
    gate = jax.nn.sigmoid(
        C.dense(C.dense(x, p["g_a_proj"], q), p["g_b_proj"], q))
    o = D.rms_norm(o, p["o_norm"], cfg["rms_norm_eps"]) \
        * gate.reshape(b, l, heads, d)
    return C.dense(o.reshape(b, l, heads * d), p["o_proj"], q)


def latent_attention(x, p, cfg: dict, q=C.identity):
    """``D.latent_attention`` with the rotary left out where the
    configuration says ``mla_use_nope``."""
    if not cfg["mla_use_nope"]:
        return D.latent_attention(x, p, cfg, q)
    heads = cfg["num_attention_heads"]
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    b, l = x.shape[:2]
    q_ = C.dense(x, p["q_proj"], q).reshape(b, l, heads, -1)
    kva = C.dense(x, p["kv_a_proj"], q)
    k_pe = kva[:, :, None, rank:]
    kv = C.dense(D.rms_norm(kva[..., :rank], p["kv_norm"],
                            cfg["rms_norm_eps"]), p["kv_b_proj"], q)
    kv = kv.reshape(b, l, heads, -1)
    k_ = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (b, l, heads,
                                                  k_pe.shape[-1]))], axis=-1)
    a = D.causal_attention(q_, k_, kv[..., nope:], q)
    return C.dense(a.reshape(b, l, -1), p["o_proj"], q)


def layer_and_choice(x, p, kind: str, cfg: dict, q=C.identity):
    """The layer's output and the experts its router chose ``(B, L, k)``,
    ``None`` for a dense layer."""
    eps = cfg["rms_norm_eps"]
    mixer = kda if kind == "kda" else latent_attention
    x = x + mixer(D.rms_norm(x, p["attn_norm"], eps), p["attention"], cfg, q)
    h = D.rms_norm(x, p["ffn_norm"], eps)
    if "ffn" in p:
        return x + D.gated(h, p["ffn"], q), None
    weights, experts = D.routing(h, p["router"], cfg)
    return x + D.gated(h, p["shared"], q) + D.routed_experts(
        h, p["experts"], weights, experts, cfg.get("first_expert_held", 0),
        q), experts


def logits_and_counts(params, ids, cfg: dict, q=C.identity):
    cfg = as_deepseek(cfg)
    x, counts = D.run_layers(
        [lambda x, p, kind=kind: layer_and_choice(x, p, kind, cfg, q)
         for kind in kinds(cfg)],
        params["tok_emb"]["embedding"][ids], params, cfg["n_routed_experts"])
    return C.dense(D.rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
                   params["lm_head"], q), counts


def logits(params, ids, cfg: dict, q=C.identity):
    return logits_and_counts(params, ids, cfg, q)[0]


def block_loss_and_counts(params, block, totals, cfg: dict, q=C.identity):
    return D.block_loss_and_counts(params, block, totals, cfg, q,
                                   forward=logits_and_counts)


def block_loss(params, block, totals, cfg: dict, q=C.identity):
    """This block of rows' share of the batch's mean next-token cross
    entropy over the vocabulary's slice."""
    return block_loss_and_counts(params, block, totals, cfg, q)[0]


def step_state(cfg: dict, traffic: dict):
    """The correction biases' balance update, as the DeepSeek-V3
    reference has it."""
    return D.step_state(cfg, traffic, loss=block_loss_and_counts)
