"""A Kimi-Linear-shaped decoder: Kimi Delta Attention on most layers,
latent attention without positions on the rest, sigmoid-routed experts.

The published modelling code (``model_type`` ``kimi_linear``; the Kimi
Linear report, arXiv 2510.26692; Kimi-Linear-48B-A3B is the configuration
the benchmark runs) as this framework's pieces.  A layer's mixer is the
kind the published lists give it (``kda_layers`` / ``full_attn_layers``,
counting from 1):

- *Kimi Delta Attention* (:class:`KimiDeltaAttention`): q, k and v each
  through a projection, a depthwise causal convolution over
  ``conv_size`` tokens and SiLU; q and k L2-normalised per head; a
  log-decay per channel ``g = -exp(A_log) * softplus(f_b(f_a(x)) +
  dt_bias)`` and a write strength per head ``beta = sigmoid(b(x))``,
  both float32; the gated delta rule chunk by chunk
  (:func:`apex_tpu.attention.gated_delta.chunk_gated_delta_rule`); the
  output through a per-head RMSNorm gated by ``sigmoid(g_b(g_a(x)))``
  and ``o_proj`` (:class:`GatedHeadNorm`).
- *Latent attention* is :class:`~apex_tpu.models.deepseek_v3.LatentAttention`
  with ``mla_use_nope``: no rotary anywhere in the model.
- The feed-forward half, the router, the experts held here
  (``n_routed_experts_held``, ``first_expert``) and every norm are
  :mod:`apex_tpu.models.deepseek_v3`'s own.

Under amp O2 ``A_log``, ``dt_bias`` and every norm gain (``o_norm``
too) stay float32 (``amp.default_keep_fp32_filter`` goes by their
names); ``g``, ``beta`` and the rule's state are float32 whatever the
activations' dtype.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.attention.gated_delta import chunk_gated_delta_rule
from apex_tpu.layers import Dense
from apex_tpu.models.deepseek_v3 import (DeepseekV3Config, LatentAttention,
                                         feed_forward)
from apex_tpu.normalization import FusedRMSNorm
from apex_tpu.utils.profiling import KDA_CONV, KDA_PROJECT

_INIT = nn.initializers.normal(0.02)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(DeepseekV3Config):
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_layers: int = 27
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    mla_use_nope: bool = True
    #: the layers, counting from 1, whose mixer is KDA; the others' is
    #: latent attention
    kda_layers: Tuple[int, ...] = tuple(
        n for n in range(1, 28) if n % 4 and n != 27)
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    gate_rank: int = 128               #: of the decay's and the output gate
    chunk_size: int = 64
    l2_norm_eps: float = 1e-6
    dt_init_range: Tuple[float, float] = (1e-3, 1e-1)


def _symmetric_uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _log_uniform(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.log(jax.random.uniform(key, shape, dtype, lo, hi))
    return init


def _inverse_softplus_of_uniform(lo: float, hi: float):
    def init(key, shape, dtype=jnp.float32):
        dt = jax.random.uniform(key, shape, dtype, lo, hi)
        return jnp.log(jnp.expm1(dt))
    return init


class ShortConvTaps(nn.Module):
    """The taps ``(taps, channels)`` of a depthwise causal convolution."""

    taps: int

    @nn.compact
    def __call__(self, channels: int):
        return self.param("kernel", _symmetric_uniform(
            1.0 / math.sqrt(self.taps)), (self.taps, channels))


def _short_conv(x, taps):
    """``y_t = sum_j w_j x_(t - (taps - 1) + j)`` per channel, zeros
    before the row's start, as shifted products."""
    n, l = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + l] * taps[j] for j in range(n))


def _unit(x, eps: float):
    """``x / |x|`` over the last axis, the sum in float32."""
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(
        jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + eps)
    ).astype(x.dtype)


class GatedHeadNorm(nn.Module):
    """``rms_norm(o) * gain * sigmoid(gate)`` over a head's lanes, the
    mean and the gain in float32.  Plain ``jax.numpy`` under
    ``jax.checkpoint``: the backward pass recomputes it from ``o`` and
    ``gate``, so no float32 copy and no per-row statistic (a column that
    pads to 128 lanes, 128 MB a layer at 8192 tokens) is kept."""

    eps: float

    @nn.compact
    def __call__(self, o, gate):
        gain = self.param("scale", nn.initializers.ones, (o.shape[-1],),
                          jnp.float32)

        @jax.checkpoint
        def normed(o, gate, gain):
            o32 = o.astype(jnp.float32)
            rms = jax.lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1,
                                         keepdims=True) + self.eps)
            return (o32 * rms * gain.astype(jnp.float32)
                    * jax.nn.sigmoid(gate.astype(jnp.float32))
                    ).astype(o.dtype)

        return normed(o, gate, gain)


class KimiDeltaAttention(nn.Module):
    """``__call__(x)`` returns the mixed rows and the recurrence's
    counters (``log_decay_min``, ``state_absmax``).  The elementwise
    stretches (the decay gate; convolution, SiLU and L2 norm; the gated
    norm) are each under ``jax.checkpoint``: the backward pass recomputes
    them from the projections' bfloat16 outputs and keeps none of their
    float32 intermediates (a dozen 128 MB arrays a layer at 8192
    tokens)."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        b, l = x.shape[0], x.shape[1]
        heads, d = c.kda_num_heads, c.kda_head_dim
        wide = heads * d

        def low_rank(name):
            return Dense(wide, use_bias=False, name=f"{name}_b_proj")(
                Dense(c.gate_rank, use_bias=False, name=f"{name}_a_proj")(x))

        @jax.checkpoint
        def log_decay(f, a_log, dt_bias):
            return -jnp.exp(jnp.repeat(a_log.astype(jnp.float32), d)) \
                * jax.nn.softplus(f.astype(jnp.float32)
                                  + dt_bias.astype(jnp.float32))

        def mixed(t, taps, unit_scale=None):
            @jax.checkpoint
            def run(t, taps):
                t = nn.silu(_short_conv(t, taps)).reshape(b, l, heads, d)
                return t if unit_scale is None else _unit(
                    t, c.l2_norm_eps) * unit_scale
            return run(t, taps)

        with jax.named_scope(KDA_PROJECT):
            qkv = [Dense(wide, use_bias=False, name=f"{n}_proj")(x)
                   for n in "qkv"]
            a_log = self.param("A_log", _log_uniform(1.0, 16.0), (heads,),
                               jnp.float32)
            dt_bias = self.param(
                "dt_bias", _inverse_softplus_of_uniform(*c.dt_init_range),
                (wide,), jnp.float32)
            g = log_decay(low_rank("f"), a_log, dt_bias)
            beta = jax.nn.sigmoid(Dense(heads, use_bias=False,
                                        name="b_proj")(x)
                                  .astype(jnp.float32))
            gate = low_rank("g")
        with jax.named_scope(KDA_CONV):
            taps = [ShortConvTaps(c.conv_size, name=f"{n}_conv")(wide)
                    .astype(x.dtype) for n in "qkv"]
            q = mixed(qkv[0], taps[0], d ** -0.5)
            k = mixed(qkv[1], taps[1], 1.0)
            v = mixed(qkv[2], taps[2])
        o, stats = chunk_gated_delta_rule(
            q, k, v, g.reshape(b, l, heads, d), beta,
            chunk_size=c.chunk_size, return_stats=True)
        with jax.named_scope(KDA_PROJECT):
            o = GatedHeadNorm(c.rms_norm_eps, name="o_norm")(
                o, gate.reshape(b, l, heads, d))
            return Dense(c.hidden_size, use_bias=False, name="o_proj")(
                o.reshape(b, l, wide)), stats


class KimiLinearBlock(nn.Module):
    cfg: KimiLinearConfig
    dense: bool
    kda: bool

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        h = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="attn_norm")(x)
        if self.kda:
            y, mixer_stats = KimiDeltaAttention(c, name="attention")(h)
        else:
            y, mixer_stats = LatentAttention(c, name="attention")(h, None), {}
        x, expert_stats = feed_forward(c, self.dense, x + y)
        return x, (mixer_stats, expert_stats)


class KimiLinearModel(nn.Module):
    """``__call__(input_ids)`` returns logits ``(B, L, vocab)``, or with
    ``return_stats`` also ``{"kda": ..., "experts": ...}``, each one entry
    a layer of its kind: a KDA layer's ``log_decay_min`` (the most
    negative running log-decay of any chunk) and ``state_absmax``; an
    expert layer's ``pairs``, ``load_peak`` and ``windows`` as
    :class:`~apex_tpu.models.deepseek_v3.DeepseekV3Model` gives them."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, input_ids, return_stats: bool = False):
        c = self.cfg
        if not c.mla_use_nope:
            raise ValueError("the published model has no positions "
                             "(mla_use_nope); this one takes no rotary")
        x = nn.Embed(c.vocab_size, c.hidden_size, embedding_init=_INIT,
                     name="tok_emb")(input_ids)
        block_cls = (nn.remat(KimiLinearBlock, prevent_cse=False)
                     if c.remat else KimiLinearBlock)
        kda, experts = [], []
        for i in range(c.num_layers):
            x, (m, e) = block_cls(c, i < c.first_k_dense_replace,
                                  i + 1 in c.kda_layers,
                                  name=f"block_{i}")(x)
            if m:
                kda.append(m)
            if e:
                experts.append(e)
        x = FusedRMSNorm(c.hidden_size, eps=c.rms_norm_eps,
                         name="final_norm")(x)
        logits = Dense(c.vocab_size, use_bias=False, name="lm_head")(x)
        if not return_stats:
            return logits

        def stack(entries):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *entries) \
                if entries else {}

        return logits, {"kda": stack(kda), "experts": stack(experts)}
