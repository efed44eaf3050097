"""An LFM2-MoE-shaped decoder: gated short-convolution mixers on most
layers, grouped-KV attention on the rest, sigmoid-routed experts with
no shared expert.

The published modelling code (``model_type`` ``lfm2_moe``;
LFM2-8B-A1B is the configuration the benchmark runs) as this
framework's pieces.  Every layer is ``h = x + mixer(rms_norm(x))``,
then ``h + ffn(rms_norm(h))``, with no biases anywhere; a layer's mixer
is the kind the published ``layer_types`` gives it:

- *Gated short convolution* (:class:`ShortConvMixer`, ``"conv"``):
  ``[B | C | u] = x W_in`` (three hidden-wide parts, in that order),
  ``y = C * conv(B * u)`` with ``conv`` depthwise and causal over
  ``conv_L_cache`` taps (:func:`gate`: on the chip a pair of Mosaic
  kernels, else :func:`apex_tpu.layers.short_conv`, the taps Kimi Delta
  Attention's convolutions use), no activation, then ``W_out``.
- *Grouped-KV attention* (:class:`GroupedQueryAttention`,
  ``"full_attention"``): ``num_heads`` q heads over
  ``num_key_value_heads`` KV heads; q and k each through a per-head
  RMSNorm, then rotate-half rotary over the whole head inside the flash
  kernel; causal softmax; q head ``h`` reads KV head ``h // (H /
  Hkv)`` (:func:`apex_tpu.attention.attention` takes k and v at their
  own head count).
- *Feed-forward*: the first ``num_dense_layers`` published layers a
  gated feed-forward of ``intermediate_size``; the others route each
  token to ``num_experts_per_tok`` of ``n_routed_experts`` by sigmoid
  scores and a correction bias that moves the choice only, renormalised
  weights, and add nothing else (``n_shared_experts`` 0): the feed-forward
  half, the router, the experts held here (``n_routed_experts_held``
  from ``first_expert``) and every norm are
  :mod:`apex_tpu.models.deepseek_v3`'s own.
- *Head*: a final RMSNorm, then the head tied to the embedding
  (``nn.Embed.attend`` on the same table, under the ``lm_head`` scope).

One chip's share of a deployment holds the published layers
``first_layer`` to ``first_layer + num_layers - 1``: each takes its
mixer from ``layer_types`` and its feed-forward from
``num_dense_layers`` by its published index.

Under amp O2 every norm gain (``q_layernorm`` and ``k_layernorm`` too)
stays float32 (their names carry ``norm``); the router's product is
float32; the gate's products and taps are formed in float32 and rounded
once.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.layers import Dense, ShortConvTaps, short_conv
from apex_tpu.models.deepseek_v3 import DeepseekV3Config, feed_forward
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.ops import use_pallas
from apex_tpu.ops.pallas.shortconv_gate import (gate_bwd, gate_fwd,
                                                gate_geometry)
from apex_tpu.ops.rope import rope_tables
from apex_tpu.utils.profiling import SHORTCONV_GATE, SHORTCONV_PROJECT

_INIT = nn.initializers.normal(0.02)

#: LFM2-8B-A1B's published ``layer_types``
LAYER_TYPES = tuple("full_attention" if n in (2, 6, 10, 14, 18, 21)
                    else "conv" for n in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(DeepseekV3Config):
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_layers: int = 24
    num_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    n_routed_experts: int = 32
    n_shared_experts: int = 0
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    #: every published layer's mixer, ``"conv"`` or ``"full_attention"``
    layer_types: Tuple[str, ...] = LAYER_TYPES
    #: the published layers, counting from 0, whose feed-forward is dense
    num_dense_layers: int = 2
    #: the first published layer held here
    first_layer: int = 0
    conv_L_cache: int = 3

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    def held_layer(self, i: int) -> "tuple[str, bool]":
        """The mixer kind of held layer ``i`` and whether its
        feed-forward is dense, by its published index."""
        n = self.first_layer + i
        return self.layer_types[n], n < self.num_dense_layers


def _kernel_rows(bcu, taps) -> "int | None":
    """Rows a grid step of the gate's Mosaic kernels takes where the
    kernels run (``apex_tpu.ops.use_pallas``) and the shape is theirs
    (:func:`~apex_tpu.ops.pallas.shortconv_gate.gate_geometry`), else
    ``None``: the XLA gate."""
    _, length, three_h = bcu.shape
    if not use_pallas():
        return None
    return gate_geometry(length, three_h // 3, taps.shape[0], bcu.dtype)


@jax.checkpoint
def _xla_gate(bcu, taps):
    """``C * conv(B * u)`` as XLA ops; under ``jax.checkpoint`` the
    backward pass recomputes ``B * u`` and its convolution from ``bcu``
    and keeps none of the float32 products."""
    b, g, u = jnp.split(bcu.astype(jnp.float32), 3, axis=-1)
    return (g * short_conv(b * u, taps.astype(jnp.float32))
            ).astype(bcu.dtype)


@jax.custom_vjp
def _kernel_gate(bcu, taps):
    return gate_fwd(bcu, taps, rows=_kernel_rows(bcu, taps))


def _kernel_gate_fwd(bcu, taps):
    with jax.named_scope(SHORTCONV_GATE):
        return gate_fwd(bcu, taps, rows=_kernel_rows(bcu, taps)), (bcu, taps)


def _kernel_gate_bwd(kept, d_y):
    bcu, taps = kept
    with jax.named_scope(SHORTCONV_GATE):
        return gate_bwd(d_y, bcu, taps, rows=_kernel_rows(bcu, taps))


_kernel_gate.defvjp(_kernel_gate_fwd, _kernel_gate_bwd)


def gate(bcu, taps):
    """``C * conv(B * u)`` of ``bcu = [B | C | u]`` over the taps ``(n,
    h)``, formed in float32 and rounded once to ``bcu``'s dtype.  By the
    shape (:func:`_kernel_rows`) either two Mosaic kernels, forward and
    backward (:mod:`apex_tpu.ops.pallas.shortconv_gate`; the residuals
    are ``bcu`` and the taps), or :func:`_xla_gate`."""
    if _kernel_rows(bcu, taps) is None:
        return _xla_gate(bcu, taps)
    return _kernel_gate(bcu, taps)


class ShortConvMixer(nn.Module):
    """``W_out (C * conv(B * u))`` with ``[B | C | u] = x W_in``; the gate
    is :func:`gate`: on the chip, at a width of whole 128 lanes, a pair
    of Mosaic kernels that form ``B * u``, the taps and ``C * s`` in VMEM
    forward and again backward, where they also sum the taps' gradient;
    else XLA ops under ``jax.checkpoint``.  Either way the backward pass
    keeps only the in-projection's output and the taps."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        with jax.named_scope(SHORTCONV_PROJECT):
            bcu = Dense(3 * c.hidden_size, use_bias=False,
                        name="in_proj")(x)
        with jax.named_scope(SHORTCONV_GATE):
            taps = ShortConvTaps(c.conv_L_cache, name="conv")(c.hidden_size)
            y = gate(bcu, taps)
        with jax.named_scope(SHORTCONV_PROJECT):
            return Dense(c.hidden_size, use_bias=False, name="out_proj")(y)


class HeadRMSNorm(nn.Module):
    """RMSNorm over a head's lanes, the mean and the gain in float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        gain = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                          jnp.float32)
        x32 = x.astype(jnp.float32)
        return (x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1,
                                             keepdims=True) + self.eps)
                * gain).astype(x.dtype)


class GroupedQueryAttention(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, rope_cs):
        """``rope_cs`` is the rotary's half-width ``(cos, sin)``."""
        c = self.cfg
        from apex_tpu.attention import attention
        b, l, d = x.shape[0], x.shape[1], c.head_dim

        def heads(name, n):
            return Dense(n * d, use_bias=False, name=name)(x).reshape(
                b, l, n, d)

        q = HeadRMSNorm(c.rms_norm_eps, name="q_layernorm")(
            heads("q_proj", c.num_heads))
        k = HeadRMSNorm(c.rms_norm_eps, name="k_layernorm")(
            heads("k_proj", c.num_key_value_heads))
        v = heads("v_proj", c.num_key_value_heads)
        out = attention(q, k, v, causal=True, scale=float(d) ** -0.5,
                        rope=rope_cs)
        return Dense(c.hidden_size, use_bias=False, name="out_proj")(
            out.reshape(b, l, c.num_heads * d))


class Lfm2MoeBlock(nn.Module):
    cfg: Lfm2MoeConfig
    dense: bool
    conv: bool

    @nn.compact
    def __call__(self, x, rope_cs):
        c = self.cfg
        h = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="attn_norm")(x)
        if self.conv:
            y = ShortConvMixer(c, name="conv")(h)
        else:
            y = GroupedQueryAttention(c, name="attention")(h, rope_cs)
        return feed_forward(c, self.dense, x + y)


class Lfm2MoeModel(nn.Module):
    """``__call__(input_ids)`` returns logits ``(B, L, vocab)``, or with
    ``return_stats`` also the expert layers' routing counters, one entry
    a layer, as :class:`~apex_tpu.models.deepseek_v3.DeepseekV3Model`
    gives them."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, return_stats: bool = False):
        c = self.cfg
        b, l = input_ids.shape
        embed = nn.Embed(c.vocab_size, c.hidden_size, embedding_init=_INIT,
                         name="tok_emb")
        x = embed(input_ids)
        rope_cs = rope_tables(jnp.broadcast_to(jnp.arange(l)[None, :],
                                               (b, l)),
                              c.head_dim, c.rope_theta)
        block_cls = (nn.remat(Lfm2MoeBlock, prevent_cse=False)
                     if c.remat else Lfm2MoeBlock)
        stats = []
        for i in range(c.num_layers):
            kind, dense = c.held_layer(i)
            x, s = block_cls(c, dense, kind == "conv",
                             name=f"block_{i}")(x, rope_cs)
            if s:
                stats.append(s)
        x = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="final_norm")(x)
        with jax.named_scope("lm_head"):
            logits = embed.attend(x)
        if not return_stats:
            return logits
        return logits, jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
