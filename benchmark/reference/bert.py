"""BERT (Devlin et al., 2018) with the MLM and NSP pretraining heads.

Departures from the published model, each because the program's block
does the same and stated in the configuration file under ``assumed``:
GELU by its tanh approximation; the MLM decoder is a matrix of its own,
not tied to the token embedding; no dropout.
"""

from __future__ import annotations

import jax.numpy as jnp

from benchmark.reference import common as C


def param_spec(cfg: dict) -> dict:
    h, i, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    spec = {
        "bert/tok_emb/embedding": ((v, h), "embedding"),
        "bert/pos_emb/embedding": ((cfg["max_position_embeddings"], h),
                                   "embedding"),
        "bert/seg_emb/embedding": ((cfg["type_vocab_size"], h), "embedding"),
    }

    def dense(path, n_in, n_out):
        spec[f"{path}/kernel"] = ((n_in, n_out), "matrix")
        spec[f"{path}/bias"] = ((n_out,), "bias")

    def norm(path):
        spec[f"{path}/scale"] = ((h,), "scale")
        spec[f"{path}/bias"] = ((h,), "bias")

    norm("bert/emb_ln")
    for n in range(cfg["num_hidden_layers"]):
        layer = f"bert/layer_{n}"
        dense(f"{layer}/attention/qkv", h, 3 * h)
        dense(f"{layer}/attention/out", h, h)
        norm(f"{layer}/attention_ln")
        dense(f"{layer}/ffn_in", h, i)
        dense(f"{layer}/ffn_out", i, h)
        norm(f"{layer}/ffn_ln")
    dense("mlm_transform", h, h)
    norm("mlm_ln")
    dense("mlm_decoder", h, v)
    dense("pooler", h, h)
    dense("nsp", h, 2)
    return spec


def totals(batch) -> dict:
    """What the loss divides by, over the whole batch."""
    ids, _types, _labels, _nsp, mask = batch
    return {"masked": jnp.maximum(jnp.sum(mask).astype(jnp.float32), 1.0),
            "rows": jnp.float32(ids.shape[0])}


def block_loss(params, block, totals, cfg: dict, q=C.identity):
    """This block of rows' share of the batch's loss: the blocks' shares
    add up to ``mean masked-LM cross entropy + mean NSP cross entropy``."""
    ids, types, labels, nsp, mask = block
    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    b = params["bert"]
    x = (b["tok_emb"]["embedding"][ids]
         + b["pos_emb"]["embedding"][jnp.arange(ids.shape[1])][None]
         + b["seg_emb"]["embedding"][types])
    x = C.layer_norm(x, b["emb_ln"], eps)

    def layer(x, p):
        qkv = C.dense(x, p["attention"]["qkv"], q)
        q_, k_, v_ = (t.reshape(*t.shape[:2], heads, -1)
                      for t in jnp.split(qkv, 3, axis=-1))
        a = C.attention(q_, k_, v_, causal=False, q=q).reshape(x.shape)
        x = C.layer_norm(x + C.dense(a, p["attention"]["out"], q),
                         p["attention_ln"], eps)
        f = C.dense(C.gelu_tanh(C.dense(x, p["ffn_in"], q)), p["ffn_out"], q)
        return C.layer_norm(x + f, p["ffn_ln"], eps)

    x = C.scan_layers(layer, x, C.stack_layers(
        b, "layer_", cfg["num_hidden_layers"]))
    t = C.layer_norm(C.gelu_tanh(C.dense(x, params["mlm_transform"], q)),
                     params["mlm_ln"], eps)
    mlm = C.cross_entropy(C.dense(t, params["mlm_decoder"], q), labels)
    pooled = jnp.tanh(C.dense(x[:, 0], params["pooler"], q))
    nsp_ce = C.cross_entropy(C.dense(pooled, params["nsp"], q), nsp)
    return (jnp.sum(mlm * mask) / totals["masked"]
            + jnp.sum(nsp_ce) / totals["rows"])
