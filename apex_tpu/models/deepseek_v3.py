"""A DeepSeek-V3-shaped decoder: latent attention, sigmoid-routed experts
beside shared ones, RMSNorm, gated feed-forwards.

The family's published modelling code (``model_type`` ``deepseek_v3``;
kanana-2-30b-a3b is the configuration the benchmark runs) as this
framework's pieces:

- *Latent attention* (MLA without the q latent): ``q = x W_q`` with each
  head split into a part that passes and a part that turns;
  ``[c_kv | k_pe] = x W_kva``; ``[k_nope | v] = rms_norm(c_kv) W_kvb``.
  The rotary turns ``q_pe`` and the one ``k_pe`` all heads share, over
  interleaved pairs (:func:`apex_tpu.ops.rope.apply_rope_interleaved`),
  outside the flash kernel, whose in-kernel rope turns whole heads.  q
  and k score at ``qk_nope + qk_rope`` lanes, v is summed at its own
  width: :func:`apex_tpu.attention.attention` takes both.
- *The first* ``first_k_dense_replace`` *layers* have a dense gated
  feed-forward; the rest route each token to ``num_experts_per_tok`` of
  ``n_routed_experts`` (:func:`apex_tpu.parallel.moe.route`: sigmoid
  scores, a correction bias that moves the choice only and gets no
  gradient, renormalised weights, a scaling factor) and add the shared
  experts, one gated feed-forward every token passes.
- *Expert parallelism*: the model holds ``n_routed_experts_held``
  experts of each layer, ``range(first_expert, first_expert + held)``,
  and computes their part of each layer's output
  (:func:`apex_tpu.parallel.moe.moe_apply`).  On one chip of a
  deployment that partial sum goes on to the next layer, as it would
  before the exchange that this chip does not see.

``Dense``, the ``mlp`` scope, ``lm_loss`` and the remat wrapper are the
ones :mod:`apex_tpu.models.gpt` uses.  Under amp O2 the four RMSNorm
gains stay float32 (their names carry ``norm``); the router's matrix and
bias are cast like every other leaf and the router product raises both
operands to float32, as the published code does.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.layers import Dense
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops.rope import apply_rope_interleaved, rope_tables_interleaved
from apex_tpu.parallel import moe
from apex_tpu.utils.profiling import (MLA_PROJECT, MLP, MOE_ROUTE,
                                      MOE_SHARED)

_INIT = nn.initializers.normal(0.02)


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 128256
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    intermediate_size: int = 6144          #: the dense layers' width
    moe_intermediate_size: int = 768       #: one routed expert's width
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128            #: the router's width
    #: experts of each layer held here (None: all of them)
    n_routed_experts_held: Optional[int] = None
    first_expert: int = 0
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    #: latent attention without positions (``mla_use_nope``): no rotary,
    #: the "rope" lanes of q and the shared ``k_pe`` score as they are
    mla_use_nope: bool = False
    remat: bool = False

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.n_routed_experts_held is None
                else self.n_routed_experts_held)


def deepseek_v3_tiny() -> DeepseekV3Config:
    """Test-scale config: one dense and two expert layers, 8 experts of
    which 4 are held, 2 a token."""
    return DeepseekV3Config(
        vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
        intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, n_routed_experts_held=4, n_shared_experts=2,
        num_experts_per_tok=2, kv_lora_rank=128, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)


class GatedMLP(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no biases."""

    hidden_size: int
    width: int

    @nn.compact
    def __call__(self, x):
        gate = Dense(self.width, use_bias=False, name="gate")(x)
        up = Dense(self.width, use_bias=False, name="up")(x)
        return Dense(self.hidden_size, use_bias=False,
                     name="down")(nn.silu(gate) * up)


class LatentAttention(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x, rope_cs):
        """``rope_cs`` is the rotary's ``(cos, sin)``, or ``None`` where
        the configuration has no positions (``mla_use_nope``)."""
        c = self.cfg
        from apex_tpu.attention import attention
        b, l = x.shape[0], x.shape[1]
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim

        def turn(t):
            return t if rope_cs is None else apply_rope_interleaved(
                t, *rope_cs)

        with jax.named_scope(MLA_PROJECT):
            q = Dense(c.num_heads * qk, use_bias=False, name="q_proj")(x)
            q = turn(q.reshape(b, l, c.num_heads, qk))
            kva = Dense(c.kv_lora_rank + c.qk_rope_head_dim, use_bias=False,
                        name="kv_a_proj")(x)
            c_kv = FusedRMSNorm(c.kv_lora_rank, eps=c.rms_norm_eps,
                                name="kv_norm")(kva[..., :c.kv_lora_rank])
            k_pe = turn(kva[..., c.kv_lora_rank:][:, :, None, :])
            kv = Dense(c.num_heads * (c.qk_nope_head_dim + c.v_head_dim),
                       use_bias=False, name="kv_b_proj")(c_kv)
            kv = kv.reshape(b, l, c.num_heads, -1)
            k = jnp.concatenate(
                [kv[..., :c.qk_nope_head_dim],
                 jnp.broadcast_to(k_pe, (b, l, c.num_heads,
                                         c.qk_rope_head_dim))], axis=-1)
            v = kv[..., c.qk_nope_head_dim:]
        out = attention(q, k, v, causal=True, scale=float(qk) ** -0.5)
        with jax.named_scope(MLA_PROJECT):
            return Dense(c.hidden_size, use_bias=False, name="o_proj")(
                out.reshape(b, l, c.num_heads * c.v_head_dim))


class Router(nn.Module):
    """The router's matrix and its correction bias; the product runs in
    float32 whatever the parameters' and the activations' dtype."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x) -> moe.Routing:
        c = self.cfg
        kernel = self.param("kernel", _INIT,
                            (x.shape[-1], c.n_routed_experts), jnp.float32)
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (c.n_routed_experts,), jnp.float32)
        logits = jnp.matmul(x.astype(jnp.float32),
                            kernel.astype(jnp.float32), precision="highest")
        return moe.route(logits, c.num_experts_per_tok,
                         scoring=c.scoring_func, bias=bias,
                         renormalize=c.norm_topk_prob,
                         scale=c.routed_scaling_factor)


class RoutedExperts(nn.Module):
    """The routed experts held here, as three stacked leaves."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x, routing):
        c = self.cfg
        h, f = c.hidden_size, c.moe_intermediate_size
        params = {"gate": self.param("gate", _INIT, (c.held, h, f)),
                  "up": self.param("up", _INIT, (c.held, h, f)),
                  "down": self.param("down", _INIT, (c.held, f, h))}
        return moe.moe_apply(moe.gated_ffn, params, x, routing,
                             n_experts=c.n_routed_experts,
                             first=c.first_expert)


def feed_forward(c: DeepseekV3Config, dense: bool, x):
    """A block's second half, for the block whose ``nn.compact`` method
    calls it: ``ffn_norm``, then the dense gated feed-forward or the
    router, the routed experts held here and the shared ones, all under
    the ``mlp`` scope; returns the residual sum and the expert layer's
    counters (none for a dense layer)."""
    h = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps, name="ffn_norm")(x)
    stats = {}
    with jax.named_scope(MLP):
        if dense:
            y = GatedMLP(c.hidden_size, c.intermediate_size, name="ffn")(h)
        else:
            tokens = h.reshape(-1, c.hidden_size)
            with jax.named_scope(MOE_ROUTE):
                routing = Router(c, name="router")(tokens)
            y, stats = RoutedExperts(c, name="experts")(tokens, routing)
            with jax.named_scope(MOE_SHARED):
                y = y.reshape(h.shape) + GatedMLP(
                    c.hidden_size,
                    c.n_shared_experts * c.moe_intermediate_size,
                    name="shared")(h)
    return x + y, stats


class DeepseekV3Block(nn.Module):
    cfg: DeepseekV3Config
    dense: bool

    @nn.compact
    def __call__(self, x, rope_cs):
        c = self.cfg
        h = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="attn_norm")(x)
        x = x + LatentAttention(c, name="attention")(h, rope_cs)
        return feed_forward(c, self.dense, x)


class DeepseekV3Model(nn.Module):
    """``__call__(input_ids)`` returns logits ``(B, L, vocab)``, or with
    ``return_stats`` also the expert layers' routing counters, one entry
    a layer: ``pairs`` the (token, expert) pairs the experts held here
    served, ``load_peak`` the fullest held expert's load over their mean,
    ``windows`` the windows of the sorted buffer the layer ran (no pair is
    ever dropped)."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, input_ids, return_stats: bool = False):
        c = self.cfg
        b, l = input_ids.shape
        x = nn.Embed(c.vocab_size, c.hidden_size, embedding_init=_INIT,
                     name="tok_emb")(input_ids)
        rope_cs = None if c.mla_use_nope else rope_tables_interleaved(
            jnp.broadcast_to(jnp.arange(l)[None, :], (b, l)),
            c.qk_rope_head_dim, c.rope_theta)
        block_cls = (nn.remat(DeepseekV3Block, prevent_cse=False)
                     if c.remat else DeepseekV3Block)
        stats = []
        for i in range(c.num_layers):
            x, s = block_cls(c, i < c.first_k_dense_replace,
                             name=f"block_{i}")(x, rope_cs)
            if s:
                stats.append(s)
        x = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="final_norm")(x)
        logits = Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        if not return_stats:
            return logits
        return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
