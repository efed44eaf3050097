"""A DeepSeek-V3-shaped decoder (DeepSeek-AI, 2024; ``model_type``
``deepseek_v3``), written from the published description: pre-RMSNorm
blocks, latent attention without the q latent, a dense gated
feed-forward in the first ``first_k_dense_replace`` layers and routed
plus shared experts after, next-token cross entropy.

One chip's share of an expert-parallel deployment: of the
``n_routed_experts`` the router chooses among, the
``n_routed_experts_held`` from ``first_expert_held`` on are here.  Every
one of them is applied densely to all tokens under its routing weight,
nought where the token did not choose it; what the experts held
elsewhere would add is left out, and that partial sum is the layer's
output.  No sort, no grouped product, no kernel.

Float32 throughout.  One row of 8192 tokens cannot be split into
blocks of rows, and one layer's scores would be 8.6 GB: attention goes
head by head and the experts one by one, each under ``jax.checkpoint``,
as each layer is.

Departures from the published model, each stated in the configuration
file: the depth, the experts held and the vocabulary are the chip's
share; no dropout.  The correction bias is seeded and gets no gradient;
where the cell's traffic gives a ``balance_rate`` it moves by the
published balance update after every step (:func:`step_state`), from
this chip's tokens' counts over all the router's experts.  The router's
product is float32 in the published code and
here, so the control's lower precision (``q``) does not reach it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common as C


def param_spec(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    heads = cfg["num_attention_heads"]
    nope, pe = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts_held"]
    spec = {"tok_emb/embedding": ((v, h), "embedding"),
            "final_norm/scale": ((h,), "scale"),
            "lm_head/kernel": ((h, v), "matrix")}

    def gated(path, width):
        spec[f"{path}/gate/kernel"] = ((h, width), "matrix")
        spec[f"{path}/up/kernel"] = ((h, width), "matrix")
        spec[f"{path}/down/kernel"] = ((width, h), "matrix")

    for n in range(cfg["num_hidden_layers"]):
        b, a = f"block_{n}", f"block_{n}/attention"
        spec[f"{b}/attn_norm/scale"] = ((h,), "scale")
        spec[f"{a}/q_proj/kernel"] = ((h, heads * (nope + pe)), "matrix")
        spec[f"{a}/kv_a_proj/kernel"] = ((h, rank + pe), "matrix")
        spec[f"{a}/kv_norm/scale"] = ((rank,), "scale")
        spec[f"{a}/kv_b_proj/kernel"] = ((rank, heads * (nope + vd)),
                                         "matrix")
        spec[f"{a}/o_proj/kernel"] = ((heads * vd, h), "matrix")
        spec[f"{b}/ffn_norm/scale"] = ((h,), "scale")
        if n < cfg["first_k_dense_replace"]:
            gated(f"{b}/ffn", cfg["intermediate_size"])
            continue
        spec[f"{b}/router/kernel"] = ((h, cfg["n_routed_experts"]), "matrix")
        spec[f"{b}/router/e_score_correction_bias"] = (
            (cfg["n_routed_experts"],), "bias")
        spec[f"{b}/experts/gate"] = ((held, h, f), "matrix")
        spec[f"{b}/experts/up"] = ((held, h, f), "matrix")
        spec[f"{b}/experts/down"] = ((held, f, h), "matrix")
        gated(f"{b}/shared", cfg["n_shared_experts"] * f)
    return spec


def totals(batch) -> dict:
    (ids,) = batch
    return {"targets": jnp.float32(ids.shape[0] * (ids.shape[1] - 1))}


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * p["scale"]


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def gated(x, p, q=C.identity):
    """``down(silu(gate(x)) * up(x))``; ``p`` holds three ``kernel``s."""
    return C.dense(silu(C.dense(x, p["gate"], q)) * C.dense(x, p["up"], q),
                   p["down"], q)


def rope_interleaved(x, theta: float):
    """Rotate ``(B, L, H, D)`` by position over interleaved pairs: lanes
    ``2i`` and ``2i + 1`` turn by ``position * theta ** (-2i / D)``."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def causal_attention(q_, k_, v_, q=C.identity):
    """``softmax(Q K^T / sqrt(d)) V`` over ``(B, L, H, D)`` tensors with
    v of its own width, one head at a time so that only one head's
    ``(L, L)`` scores exist, again in the backward pass."""
    @jax.checkpoint
    def head(qkv):
        qh, kh, vh = qkv                                  # (B, L, d)
        s = jnp.einsum("bqd,bkd->bqk", q(qh), q(kh)) / jnp.sqrt(
            jnp.float32(qh.shape[-1]))
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", q(jax.nn.softmax(s, axis=-1)),
                          q(vh))

    out = jax.lax.map(head, tuple(jnp.moveaxis(t, 2, 0)
                                  for t in (q_, k_, v_)))
    return jnp.moveaxis(out, 0, 2)


def latent_attention(x, p, cfg: dict, q=C.identity):
    heads, theta = cfg["num_attention_heads"], float(cfg["rope_theta"])
    nope, rank = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    b, l = x.shape[:2]
    q_ = C.dense(x, p["q_proj"], q).reshape(b, l, heads, -1)
    q_ = jnp.concatenate([q_[..., :nope],
                          rope_interleaved(q_[..., nope:], theta)], axis=-1)
    kva = C.dense(x, p["kv_a_proj"], q)
    k_pe = rope_interleaved(kva[:, :, None, rank:], theta)
    kv = C.dense(rms_norm(kva[..., :rank], p["kv_norm"],
                          cfg["rms_norm_eps"]), p["kv_b_proj"], q)
    kv = kv.reshape(b, l, heads, -1)
    k_ = jnp.concatenate([kv[..., :nope],
                          jnp.broadcast_to(k_pe, (b, l, heads,
                                                  k_pe.shape[-1]))], axis=-1)
    a = causal_attention(q_, k_, kv[..., nope:], q)
    return C.dense(a.reshape(b, l, -1), p["o_proj"], q)


def routing(x, p, cfg: dict):
    """``(weights (..., k), experts (..., k))``: sigmoid (or softmax)
    scores of all the experts; the ``k`` chosen by score plus correction
    bias; their scores, without the bias, over their sum, times the
    scaling factor."""
    logits = jnp.matmul(x, p["kernel"])
    scores = (jax.nn.sigmoid(logits) if cfg["scoring_func"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    choice = scores + jax.lax.stop_gradient(p["e_score_correction_bias"])
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(choice),
                               cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * cfg["routed_scaling_factor"], experts


def routed_experts(x, p, weights, experts, first: int, q=C.identity):
    """The experts held here, each on all tokens under its weight."""
    @jax.checkpoint
    def one(y, expert):
        index, leaves = expert
        w = jnp.sum(jnp.where(experts == first + index, weights, 0.0),
                    axis=-1)
        out = jnp.matmul(
            q(silu(jnp.matmul(q(x), q(leaves["gate"])))
              * jnp.matmul(q(x), q(leaves["up"]))), q(leaves["down"]))
        return y + w[..., None] * out, None

    held = p["gate"].shape[0]
    return jax.lax.scan(one, jnp.zeros_like(x), (jnp.arange(held), p))[0]


def layer_and_choice(x, p, cfg: dict, q=C.identity):
    """The layer's output and the experts its router chose ``(B, L, k)``,
    ``None`` for a dense layer."""
    eps = cfg["rms_norm_eps"]
    x = x + latent_attention(rms_norm(x, p["attn_norm"], eps),
                             p["attention"], cfg, q)
    h = rms_norm(x, p["ffn_norm"], eps)
    if "ffn" in p:
        return x + gated(h, p["ffn"], q), None
    weights, experts = routing(h, p["router"], cfg)
    return x + gated(h, p["shared"], q) + routed_experts(
        h, p["experts"], weights, experts, cfg.get("first_expert_held", 0),
        q), experts


def layer(x, p, cfg: dict, q=C.identity):
    return layer_and_choice(x, p, cfg, q)[0]


def chosen_experts(params, ids, cfg: dict, q=C.identity):
    """The experts every expert layer's router chose, ``(layers, B, L,
    k)``: forward only, for counting how many (token, expert) pairs
    move when the precision of what feeds the router does (``q``)."""
    x = params["tok_emb"]["embedding"][ids]
    chosen = []
    for n in range(cfg["num_hidden_layers"]):
        x, experts = layer_and_choice(x, params[f"block_{n}"], cfg, q)
        if experts is not None:
            chosen.append(experts)
    return jnp.stack(chosen)


def expert_counts(experts, n_experts: int):
    """``(n_experts,)`` float32: the (token, expert) pairs each of *all*
    the router's experts got."""
    return jnp.sum(experts.reshape(-1, 1) == jnp.arange(n_experts), axis=0,
                   dtype=jnp.float32)


def run_layers(layers, x, params, n_experts: int):
    """``x`` through ``layers`` (one ``(x, p) -> (x, experts or None)`` a
    block, each recomputed in the backward pass) and every expert
    layer's counts, ``(expert layers, n_experts)``."""
    counts = []
    for n, one in enumerate(layers):
        def counted(x, p, one=one):
            x, experts = one(x, p)
            return x, (None if experts is None
                       else expert_counts(experts, n_experts))
        x, c = jax.checkpoint(counted)(x, params[f"block_{n}"])
        if c is not None:
            counts.append(c)
    return x, jax.lax.stop_gradient(jnp.stack(counts)) if counts else None


def logits_and_counts(params, ids, cfg: dict, q=C.identity):
    x, counts = run_layers(
        [lambda x, p: layer_and_choice(x, p, cfg, q)]
        * cfg["num_hidden_layers"],
        params["tok_emb"]["embedding"][ids], params, cfg["n_routed_experts"])
    return C.dense(rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
                   params["lm_head"], q), counts


def logits(params, ids, cfg: dict, q=C.identity):
    return logits_and_counts(params, ids, cfg, q)[0]


def block_loss_and_counts(params, block, totals, cfg: dict, q=C.identity,
                          forward=logits_and_counts):
    """This block of rows' share of the batch's mean next-token cross
    entropy over the vocabulary's slice, and its tokens' share of every
    expert layer's counts (blocks add up)."""
    (ids,) = block
    out, counts = forward(params, ids, cfg, q)
    ce = C.cross_entropy(out[:, :-1], ids[:, 1:])
    return jnp.sum(ce) / totals["targets"], counts


def block_loss(params, block, totals, cfg: dict, q=C.identity):
    return block_loss_and_counts(params, block, totals, cfg, q)[0]


def balance_update(bias, counts, rate: float):
    """The published balance update of the correction bias (DeepSeek-V3,
    arXiv:2412.19437 section 2.1.2, after arXiv:2408.15664): up by
    ``rate`` for an expert that got fewer pairs than the mean over all
    the experts, down for one that got more, unmoved at the mean."""
    return bias + rate * jnp.sign(jnp.mean(counts) - counts)


def bias_paths(cfg: dict) -> list:
    """The correction biases, one an expert layer, in the layers' order."""
    return [f"block_{n}/router/e_score_correction_bias"
            for n in range(cfg["first_k_dense_replace"],
                           cfg["num_hidden_layers"])]


def step_state(cfg: dict, traffic: dict, loss=block_loss_and_counts):
    """What a step keeps that no gradient moves (``reference/train.py``
    ``follow``): nothing without a ``balance_rate`` in the cell's
    traffic; with one, the correction biases, moved after the optimizer
    by :func:`balance_update` from the counts of the step's own forward
    pass."""
    rate = traffic.get("balance_rate")
    if not rate:
        return None
    paths = bias_paths(cfg)

    def update(values: dict, counts) -> dict:
        return {p: balance_update(values[p], counts[i], rate)
                for i, p in enumerate(paths)}

    return {"paths": paths, "block_loss": loss, "update": update}
