"""A Laguna-shaped decoder (poolside, 2026; ``model_type`` ``laguna``),
written from the published configuration: pre-RMSNorm blocks whose
attention slides or is full by the published ``layer_types``, with the
q head count ``num_attention_heads_per_layer`` gives it, a dense gated
feed-forward or routed experts beside a shared one by
``mlp_layer_types``, a final RMSNorm, an untied head, next-token cross
entropy.

Attention, on the normed input ``z``: ``H`` q heads and
``num_key_value_heads`` KV heads of ``head_dim`` lanes; q head ``h``
reads KV head ``h // (H / Hkv)``;
softmax of the scores at ``head_dim ** -0.5``::

    sliding:  query i sees keys  i - W < j <= i     (W = sliding_window)
    full:     query i sees keys  j <= i

The rotary, rotate-half: lanes ``i`` and ``i + R/2`` of the first ``R``
of a head turn by ``position * inv_freq_i``, cos and sin times the
kind's ``attention_factor``; the other lanes pass.  Sliding: ``R`` the
whole head, ``theta ** (-2i / R)``.  Full: ``R = head_dim *
partial_rotary_factor``, YaRN's frequencies (:func:`yarn`, transcribed
here from the published formula, ``truncate`` true).  The output gate,
per head: ``o_h * sigmoid(z W_g)_h``, then ``o_proj``.

Nothing here is imported from the program.  The window is a mask over
blocks of queries (each block against the ``W`` keys before it and its
own), so that one row of 8192 fits; full layers go head by head.  Both
read each KV head for its group of q heads as it stands, never copied
out to every q head, so that the reference's step, and the float8
control's, fit one chip beside the float32 state of the cell's 32
experts.  The router, the experts held here, the shared expert, their
counts and the balance update are
``benchmark/reference/deepseek_v3.py``'s, given the same share of the
experts and of the vocabulary.  Float32 throughout.

``cfg["planted"]`` names a fault ``calibrate_faults.py`` plants:
``no_window`` (the sliding layers causal over every key), ``no_gate``
(the output gate left out), ``no_yarn`` (the full layers' rotary at the
default frequencies of their ``rope_theta``, no attention factor),
``full_rotary`` (the full layers' rotary over all of a head's lanes) or
``kv_group_modulo`` (q head ``h`` reads KV head ``h % Hkv``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common as C
from benchmark.reference import deepseek_v3 as D

#: rows of queries the window is computed for at a time
WINDOW_BLOCK = 512


def kinds(cfg: dict) -> list:
    """``(sliding, dense, q heads)`` for each layer held, by its
    published index."""
    first = cfg["first_layer_held"]
    return [(cfg["layer_types"][n] == "sliding_attention",
             cfg["mlp_layer_types"][n] == "dense",
             cfg["num_attention_heads_per_layer"][n])
            for n in range(first, first + cfg["num_hidden_layers"])]


def as_deepseek(cfg: dict) -> dict:
    """This configuration under the names the DeepSeek-V3 reference and
    ``benchmark/moe_flops.py`` read."""
    shared, width = (cfg["shared_expert_intermediate_size"],
                     cfg["moe_intermediate_size"])
    if shared % width:
        raise ValueError("the shared expert is a whole number of experts "
                         "wide")
    return dict(cfg, n_routed_experts=cfg["num_experts"],
                n_routed_experts_held=cfg["num_experts_held"],
                n_shared_experts=shared // width, scoring_func="sigmoid",
                routed_scaling_factor=cfg["moe_routed_scaling_factor"],
                first_k_dense_replace=sum(d for _, d, _ in kinds(cfg)))


def param_spec(cfg: dict) -> dict:
    h, v, d = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * d
    f, held = cfg["moe_intermediate_size"], cfg["num_experts_held"]
    spec = {"tok_emb/embedding": ((v, h), "embedding"),
            "final_norm/scale": ((h,), "scale"),
            "lm_head/kernel": ((h, v), "matrix")}

    def gated(path, width):
        spec[f"{path}/gate/kernel"] = ((h, width), "matrix")
        spec[f"{path}/up/kernel"] = ((h, width), "matrix")
        spec[f"{path}/down/kernel"] = ((width, h), "matrix")

    for n, (_, dense, heads) in enumerate(kinds(cfg)):
        b, a = f"block_{n}", f"block_{n}/attention"
        spec[f"{b}/attn_norm/scale"] = ((h,), "scale")
        spec[f"{b}/ffn_norm/scale"] = ((h,), "scale")
        spec[f"{a}/q_proj/kernel"] = ((h, heads * d), "matrix")
        spec[f"{a}/k_proj/kernel"] = ((h, kv), "matrix")
        spec[f"{a}/v_proj/kernel"] = ((h, kv), "matrix")
        spec[f"{a}/g_proj/kernel"] = ((h, heads), "matrix")
        spec[f"{a}/o_proj/kernel"] = ((heads * d, h), "matrix")
        if dense:
            gated(f"{b}/ffn", cfg["intermediate_size"])
            continue
        spec[f"{b}/router/kernel"] = ((h, cfg["num_experts"]), "matrix")
        spec[f"{b}/router/e_score_correction_bias"] = (
            (cfg["num_experts"],), "bias")
        spec[f"{b}/experts/gate"] = ((held, h, f), "matrix")
        spec[f"{b}/experts/up"] = ((held, h, f), "matrix")
        spec[f"{b}/experts/down"] = ((held, f, h), "matrix")
        gated(f"{b}/shared", cfg["shared_expert_intermediate_size"])
    return spec


totals = D.totals


def yarn(p: dict, head_dim: int) -> "tuple[np.ndarray, float, int]":
    """``(inv_freq, attention_factor, rotary lanes)`` of a kind's
    ``rope_parameters``: the default frequencies ``theta ** (-2i / R)``,
    or YaRN's (Hugging Face ``_compute_yarn_parameters``): each
    frequency blends the original (extrapolation) and the original over
    ``factor`` (interpolation), by a ramp from 1 to 0 between the
    floor and the ceiling of the dims at which ``beta_fast`` and
    ``beta_slow`` rotations fit in ``original_max_position_embeddings``."""
    rotary = int(head_dim * p.get("partial_rotary_factor", 1.0))
    theta = float(p["rope_theta"])
    i = np.arange(rotary // 2, dtype=np.float64)
    original = theta ** (-2.0 * i / rotary)
    if p.get("rope_type", "default") == "default":
        return original.astype(np.float32), 1.0, rotary
    factor = float(p["factor"])
    span = float(p["original_max_position_embeddings"])

    def dim_of(rotations: float) -> float:
        return (rotary * math.log(span / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(p["beta_fast"])), 0)
    high = min(math.ceil(dim_of(p["beta_slow"])), rotary - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = original * (1.0 - ramp) + original / factor * ramp
    scale = p.get("attention_factor", 0.1 * math.log(factor) + 1.0)
    return inv_freq.astype(np.float32), float(scale), rotary


def rotate(x, inv_freq, scale: float, rotary: int):
    """``(B, L, H, D)``: rotate-half over the first ``rotary`` lanes by
    position, cos and sin times ``scale``; the rest pass."""
    half = rotary // 2
    angles = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
              * jnp.asarray(inv_freq))
    cos = scale * jnp.cos(angles)[None, :, None]
    sin = scale * jnp.sin(angles)[None, :, None]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., rotary:]], axis=-1)


def _grouped(x, kv_heads: int, modulo: bool = False):
    """``(B, L, H, D)`` as ``(B, L, Hkv, H / Hkv, D)``: q head ``h`` in
    group ``h // (H / Hkv)``, or with ``modulo`` (the planted fault) in
    group ``h % Hkv``."""
    b, l, h, d = x.shape
    if modulo:
        return x.reshape(b, l, h // kv_heads, kv_heads, d).swapaxes(2, 3)
    return x.reshape(b, l, kv_heads, h // kv_heads, d)


def _ungrouped(x, modulo: bool = False):
    """:func:`_grouped` undone."""
    b, l, kv_heads, g, d = x.shape
    return (x.swapaxes(2, 3) if modulo else x).reshape(b, l, kv_heads * g, d)


def window_attention(q_, k_, v_, window: int, q=C.identity,
                     modulo: bool = False):
    """``softmax(Q K^T / sqrt(d)) V`` over ``(B, L, H, D)`` queries and
    ``(B, L, Hkv, D)`` keys and values, q head ``h`` against KV head
    ``h // (H / Hkv)``, query ``i`` against keys ``i - W < j <= i``: a
    block of queries at a time against the ``W`` keys before it and its
    own, each block recomputed in the backward pass."""
    b, l, h, d = q_.shape
    kv_heads = k_.shape[2]
    block = min(WINDOW_BLOCK, l)
    n = -(-l // block)
    q_ = jnp.pad(q_, ((0, 0), (0, n * block - l), (0, 0), (0, 0)))
    # ``window`` zero keys before the row (masked), ``n block - l`` after
    k_, v_ = (jnp.pad(t, ((0, 0), (window, n * block - l), (0, 0), (0, 0)))
              for t in (k_, v_))

    @jax.checkpoint
    def one(i):
        start = i * block
        qs = _grouped(jax.lax.dynamic_slice_in_dim(q_, start, block, axis=1),
                      kv_heads, modulo)
        ks, vs = (jax.lax.dynamic_slice_in_dim(t, start, window + block,
                                               axis=1) for t in (k_, v_))
        s = jnp.einsum("bqkgd,bskd->bkgqs", q(qs), q(ks)) / jnp.sqrt(
            jnp.float32(d))
        qpos = start + jnp.arange(block)[:, None]
        kpos = start - window + jnp.arange(window + block)[None, :]
        keep = (qpos >= kpos) & (qpos - kpos < window) & (kpos >= 0)
        s = jnp.where(keep, s, -jnp.inf)
        o = jnp.einsum("bkgqs,bskd->bqkgd", q(jax.nn.softmax(s, axis=-1)),
                       q(vs))
        return _ungrouped(o, modulo)

    out = jax.lax.map(one, jnp.arange(n))            # (n, B, block, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, h, d)[:, :l]


def causal_attention(q_, k_, v_, q=C.identity, modulo: bool = False):
    """Causal ``softmax(Q K^T / sqrt(d)) V`` over ``(B, L, H, D)``
    queries and ``(B, L, Hkv, D)`` keys and values, q head ``h`` against
    KV head ``h // (H / Hkv)``: a KV head's group at a time, one q head
    of it at a time, so that only one head's ``(L, L)`` scores exist,
    again in the backward pass."""
    b, l, h, d = q_.shape
    kv_heads = k_.shape[2]

    @jax.checkpoint
    def head(qh, kh, vh):                                 # (B, L, d)
        s = jnp.einsum("bqd,bkd->bqk", q(qh), q(kh)) / jnp.sqrt(
            jnp.float32(d))
        s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", q(jax.nn.softmax(s, axis=-1)),
                          q(vh))

    def group(qkv):
        qg, kh, vh = qkv                                  # (G, B, L, d)
        return jax.lax.map(lambda qh: head(qh, kh, vh), qg)

    out = jax.lax.map(group, (
        jnp.moveaxis(_grouped(q_, kv_heads, modulo), (2, 3), (0, 1)),
        jnp.moveaxis(k_, 2, 0), jnp.moveaxis(v_, 2, 0)))  # (Hkv, G, B, L, d)
    return _ungrouped(jnp.moveaxis(out, (0, 1), (2, 3)), modulo)


def attention(x, p, cfg: dict, sliding: bool, heads: int, q=C.identity):
    kv_heads, d = cfg["num_key_value_heads"], cfg["head_dim"]
    planted = cfg.get("planted")
    b, l = x.shape[:2]

    def project(name, n):
        return C.dense(x, p[name], q).reshape(b, l, n, d)

    q_, k_, v_ = (project("q_proj", heads), project("k_proj", kv_heads),
                  project("v_proj", kv_heads))
    kind = dict(cfg["rope_parameters"]["sliding_attention" if sliding
                                       else "full_attention"])
    if not sliding and planted == "no_yarn":
        kind = {"rope_type": "default", "rope_theta": kind["rope_theta"],
                "partial_rotary_factor": kind["partial_rotary_factor"]}
    if not sliding and planted == "full_rotary":
        kind["partial_rotary_factor"] = 1.0
    table = yarn(kind, d)
    q_, k_ = rotate(q_, *table), rotate(k_, *table)
    # q head h reads KV head h // (H / Hkv), or h % Hkv under the fault
    modulo = planted == "kv_group_modulo"
    if sliding and planted != "no_window":
        a = window_attention(q_, k_, v_, cfg["sliding_window"], q, modulo)
    else:
        a = causal_attention(q_, k_, v_, q, modulo)
    if planted != "no_gate":
        a = a * jax.nn.sigmoid(C.dense(x, p["g_proj"], q))[..., None]
    return C.dense(a.reshape(b, l, -1), p["o_proj"], q)


def layer_and_choice(x, p, sliding: bool, heads: int, cfg: dict,
                     q=C.identity):
    """The layer's output and the experts its router chose ``(B, L, k)``,
    ``None`` for a dense layer."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(D.rms_norm(x, p["attn_norm"], eps), p["attention"],
                      cfg, sliding, heads, q)
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
        [lambda x, p, s=s, h=h: layer_and_choice(x, p, s, h, cfg, q)
         for s, _, h in kinds(cfg)],
        params["tok_emb"]["embedding"][ids], params, cfg["num_experts"])
    x = D.rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    return C.dense(x, params["lm_head"], q), counts


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
    return D.step_state(as_deepseek(cfg), traffic,
                        loss=block_loss_and_counts)


def chosen_experts(params, ids, cfg: dict, q=C.identity):
    """The experts every expert layer's router chose, ``(layers, B, L,
    k)``: forward only (``calibrate_faults.py`` counts the pairs that
    bfloat16 operands move)."""
    cfg = as_deepseek(cfg)
    x = params["tok_emb"]["embedding"][ids]
    chosen = []
    for n, (s, _, h) in enumerate(kinds(cfg)):
        x, experts = layer_and_choice(x, params[f"block_{n}"], s, h, cfg, q)
        if experts is not None:
            chosen.append(experts)
    return jnp.stack(chosen)
