"""A Laguna-shaped decoder: sliding-window attention beside full layers
with scaled partial rotary, q head counts by layer, a per-head output
gate, sigmoid-routed experts beside a shared one.

The published modelling code (``model_type`` ``laguna``; Laguna-XS.2 is
the configuration the benchmark runs) as this framework's pieces.  Every
layer is ``h = x + attn(rms_norm(x))``, then ``h + ffn(rms_norm(h))``,
with no biases anywhere; a layer's attention is the kind the published
``layer_types`` gives it and its q head count the one
``num_attention_heads_per_layer`` gives it:

- *Attention* (:class:`LagunaAttention`): ``H`` q heads over
  ``num_key_value_heads`` KV heads, every head ``head_dim`` lanes; q
  head ``h`` reads KV head ``h // (H / Hkv)``; scores at ``head_dim **
  -0.5``.  ``"sliding_attention"``: query ``i`` sees keys ``i - W < j <=
  i`` (``sliding_window``, the flash kernels' ``window``: they visit the
  band's key blocks only), the rotary over the whole head at the
  sliding kind's ``rope_parameters``.  ``"full_attention"``: causal, the
  rotary over the first ``partial_rotary_factor`` of each head's lanes
  at the full kind's YaRN frequencies, cos and sin times its
  ``attention_factor``; the kernels turn lane ``j`` with ``j + D/2``, so
  q and k enter them in :func:`~apex_tpu.ops.rope.to_rotary_lanes`'
  order (the same for both: no score moves) with tables that pass the
  other lanes (:func:`~apex_tpu.ops.rope.partial_rope_kernel_tables`).
  Both rotate inside the flash kernels.  The output gate is per head:
  ``o_h * sigmoid(z W_g)_h`` with ``z`` the normed input, then ``o_proj``.
- *Feed-forward* by ``mlp_layer_types``: ``"dense"`` a gated
  feed-forward of ``intermediate_size``; ``"sparse"`` the router over
  ``n_routed_experts`` (sigmoid scores, a correction bias that moves the
  choice only, renormalised weights times ``routed_scaling_factor``),
  the experts held here and one shared expert of
  ``moe_intermediate_size``: :func:`apex_tpu.models.deepseek_v3.feed_forward`.
- *Head*: a final RMSNorm, then an untied head.

One chip's share of a deployment holds the published layers
``first_layer`` to ``first_layer + num_layers - 1``, each with its kinds
and head count by its published index.  The windowed flash call opens
``swa_window`` (:data:`apex_tpu.utils.profiling.SWA_SCOPES`).  Under amp
O2 every norm gain stays float32 (their names carry ``norm``); the
router's product is float32; the gate's sigmoid is taken in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.layers import Dense
from apex_tpu.models.deepseek_v3 import DeepseekV3Config, feed_forward
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.rope import (partial_rope_kernel_tables, rope_inv_freq,
                               scaled_rope_tables, to_rotary_lanes)
from apex_tpu.utils.profiling import SWA_WINDOW

_INIT = nn.initializers.normal(0.02)

#: Laguna-XS.2's published lists, a period of four layers
LAYER_TYPES = tuple("full_attention" if n % 4 == 0 else "sliding_attention"
                    for n in range(40))
MLP_LAYER_TYPES = ("dense",) + ("sparse",) * 39
HEADS_PER_LAYER = tuple(48 if n % 4 == 0 else 64 for n in range(40))
FULL_ROPE = (("rope_type", "yarn"), ("rope_theta", 500000.0),
             ("factor", 64.0), ("original_max_position_embeddings", 4096),
             ("beta_fast", 64.0), ("beta_slow", 1.0),
             ("attention_factor", 1.4158883083359672),
             ("partial_rotary_factor", 0.5))
SLIDING_ROPE = (("rope_type", "default"), ("rope_theta", 10000.0),
                ("partial_rotary_factor", 1.0))


@dataclasses.dataclass(frozen=True)
class LagunaConfig(DeepseekV3Config):
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 8192
    moe_intermediate_size: int = 512
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    #: every published layer's attention, ``"full_attention"`` or
    #: ``"sliding_attention"``; its feed-forward, ``"dense"`` or
    #: ``"sparse"``; its q head count
    layer_types: Tuple[str, ...] = LAYER_TYPES
    mlp_layer_types: Tuple[str, ...] = MLP_LAYER_TYPES
    num_attention_heads_per_layer: Tuple[int, ...] = HEADS_PER_LAYER
    sliding_window: int = 512
    #: each kind's ``rope_parameters``, as ``(key, value)`` pairs
    full_rope: Tuple[Tuple[str, object], ...] = FULL_ROPE
    sliding_rope: Tuple[Tuple[str, object], ...] = SLIDING_ROPE
    #: the first published layer held here
    first_layer: int = 0

    @property
    def full_rotary(self) -> int:
        """Lanes of a head the full kind's rotary turns."""
        return int(self.head_dim
                   * dict(self.full_rope).get("partial_rotary_factor", 1.0))

    def held_layer(self, i: int) -> "tuple[bool, bool, int]":
        """Whether held layer ``i`` slides, whether its feed-forward is
        dense, and its q head count, by its published index."""
        n = self.first_layer + i
        return (self.layer_types[n] == "sliding_attention",
                self.mlp_layer_types[n] == "dense",
                self.num_attention_heads_per_layer[n])


def rope_for(c: LagunaConfig, b: int, l: int) -> dict:
    """Each kind's rotary tables for rows ``(b, l)``, computed once a step:
    the sliding kind's half-width ``(cos, sin)``; the full kind's kernel
    tables for q and k in :func:`~apex_tpu.ops.rope.to_rotary_lanes`'
    order where its rotary turns part of a head."""
    positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
    tables = {}
    for kind, params in (("sliding", c.sliding_rope), ("full", c.full_rope)):
        inv_freq, factor = rope_inv_freq(dict(params), c.head_dim)
        tables[kind] = scaled_rope_tables(positions, inv_freq, factor)
    if c.full_rotary != c.head_dim:
        tables["full"] = partial_rope_kernel_tables(*tables["full"],
                                                    c.head_dim)
    return tables


class LagunaAttention(nn.Module):
    cfg: LagunaConfig
    heads: int
    sliding: bool

    @nn.compact
    def __call__(self, x, rope):
        """``rope``: :func:`rope_for`'s tables."""
        c = self.cfg
        from apex_tpu.attention import attention
        b, l, d = x.shape[0], x.shape[1], c.head_dim

        def heads(name, n):
            return Dense(n * d, use_bias=False, name=name)(x).reshape(
                b, l, n, d)

        q = heads("q_proj", self.heads)
        k = heads("k_proj", c.num_key_value_heads)
        v = heads("v_proj", c.num_key_value_heads)
        gate = Dense(self.heads, use_bias=False, name="g_proj")(x)
        scale = float(d) ** -0.5
        if self.sliding:
            with jax.named_scope(SWA_WINDOW):
                out = attention(q, k, v, causal=True, scale=scale,
                                rope=rope["sliding"],
                                window=c.sliding_window)
        else:
            q, k = (to_rotary_lanes(t, c.full_rotary) for t in (q, k))
            out = attention(q, k, v, causal=True, scale=scale,
                            rope=rope["full"])
        out = (out.astype(jnp.float32)
               * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None]
               ).astype(out.dtype)
        return Dense(c.hidden_size, use_bias=False, name="o_proj")(
            out.reshape(b, l, self.heads * d))


class LagunaBlock(nn.Module):
    cfg: LagunaConfig
    heads: int
    sliding: bool
    dense: bool

    @nn.compact
    def __call__(self, x, rope):
        c = self.cfg
        h = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="attn_norm")(x)
        y = LagunaAttention(c, self.heads, self.sliding,
                            name="attention")(h, rope)
        return feed_forward(c, self.dense, x + y)


class LagunaModel(nn.Module):
    """``__call__(input_ids)`` returns logits ``(B, L, vocab)``, or with
    ``return_stats`` also the expert layers' routing counters, one entry
    a layer, as :class:`~apex_tpu.models.deepseek_v3.DeepseekV3Model`
    gives them."""

    cfg: LagunaConfig

    @nn.compact
    def __call__(self, input_ids, return_stats: bool = False):
        c = self.cfg
        b, l = input_ids.shape
        x = nn.Embed(c.vocab_size, c.hidden_size, embedding_init=_INIT,
                     name="tok_emb")(input_ids)
        rope = rope_for(c, b, l)
        block_cls = (nn.remat(LagunaBlock, prevent_cse=False)
                     if c.remat else LagunaBlock)
        stats = []
        for i in range(c.num_layers):
            sliding, dense, heads = c.held_layer(i)
            x, s = block_cls(c, heads, sliding, dense,
                             name=f"block_{i}")(x, rope)
            if s:
                stats.append(s)
        x = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="final_norm")(x)
        logits = Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        if not return_stats:
            return logits
        return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
