"""A GPT-2-shaped decoder (Radford et al., 2019): pre-LayerNorm blocks,
causal attention, a four-times-wide tanh-GELU feed-forward, next-token
cross entropy.

Departures from the published model, each because the program's block
does the same and stated in the configuration file under ``assumed``:
rotary positions (half-split, theta 10000) in place of the learned
position table; an output head of its own, not tied to the embedding,
and without a bias; no dropout.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference import common as C


def param_spec(cfg: dict) -> dict:
    h, i, v = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]
    spec = {"tok_emb/embedding": ((v, h), "embedding"),
            "lm_head/kernel": ((h, v), "matrix")}

    def dense(path, n_in, n_out):
        spec[f"{path}/kernel"] = ((n_in, n_out), "matrix")
        spec[f"{path}/bias"] = ((n_out,), "bias")

    def norm(path):
        spec[f"{path}/scale"] = ((h,), "scale")
        spec[f"{path}/bias"] = ((h,), "bias")

    for n in range(cfg["n_layer"]):
        block = f"block_{n}"
        norm(f"{block}/ln1")
        dense(f"{block}/attention/qkv", h, 3 * h)
        dense(f"{block}/attention/out", h, h)
        norm(f"{block}/ln2")
        dense(f"{block}/ffn_in", h, i)
        dense(f"{block}/ffn_out", i, h)
    norm("ln_f")
    return spec


def totals(batch) -> dict:
    (ids,) = batch
    return {"targets": jnp.float32(ids.shape[0] * (ids.shape[1] - 1))}


def rope(x, theta: float):
    """Rotate ``(B, L, H, D)`` by position: the two halves of ``D`` are
    the pairs, frequency ``theta ** (-i / (D/2))``."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(-jnp.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[None, :, None], jnp.sin(angles)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block_loss(params, block, totals, cfg: dict, q=C.identity):
    """This block of rows' share of the batch's mean next-token cross
    entropy."""
    (ids,) = block
    eps, heads = cfg["layer_norm_epsilon"], cfg["n_head"]
    theta = cfg["rope_theta"]
    x = params["tok_emb"]["embedding"][ids]

    def layer(x, p):
        qkv = C.dense(C.layer_norm(x, p["ln1"], eps), p["attention"]["qkv"], q)
        q_, k_, v_ = (t.reshape(*t.shape[:2], heads, -1)
                      for t in jnp.split(qkv, 3, axis=-1))
        a = C.attention(rope(q_, theta), rope(k_, theta), v_, causal=True,
                        q=q).reshape(x.shape)
        x = x + C.dense(a, p["attention"]["out"], q)
        f = C.dense(C.layer_norm(x, p["ln2"], eps), p["ffn_in"], q)
        return x + C.dense(C.gelu_tanh(f), p["ffn_out"], q)

    x = C.scan_layers(layer, x, C.stack_layers(params, "block_",
                                               cfg["n_layer"]))
    logits = C.dense(C.layer_norm(x, params["ln_f"], eps), params["lm_head"],
                     q)
    ce = C.cross_entropy(logits[:, :-1], ids[:, 1:])
    return jnp.sum(ce) / totals["targets"]
