"""BERT — the FusedLayerNorm + FusedLAMB pretraining workload.

Port of BASELINE config 4 ("BERT-large pretraining FusedLAMB +
FusedLayerNorm (v5e-16)").  The reference carries no BERT model (its role
there is played by downstream users pairing apex's FusedLayerNorm/LAMB
kernels with their own BERT); the model here is authored TPU-first:

- every LayerNorm is :class:`apex_tpu.normalization.FusedLayerNorm`
  (Pallas-fused on TPU, fp32 statistics);
- attention/FFN matmuls route through the policy-cast op layer, softmax in
  fp32 (``lists/functional_overrides.py:29-65`` puts softmax on the fp32
  list);
- shapes default to BERT-large (hidden 1024, 24 layers, 16 heads).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.amp import ops as amp_ops
from apex_tpu.layers import Dense
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.utils.profiling import MLP, PRETRAINING_LOSS


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    #: Stack the encoder as one ``nn.scan`` over a single compiled layer
    #: body — layer params carry a leading ``num_layers`` axis (shardable
    #: over an fsdp/pipeline mesh axis), and ``remat`` composes per layer.
    #: Measured on one chip: step time identical to the unrolled loop
    #: (XLA dedups the 24 copies), compile slightly slower at 24 layers,
    #: so the named ``layer_{i}`` loop stays the default; turn this on for
    #: remat, per-layer sharding, or very deep stacks.
    scan_layers: bool = False
    #: Rematerialize each layer's activations in the backward pass
    #: (``jax.checkpoint`` through ``nn.remat``) — trades recompute FLOPs
    #: for HBM, the lever for long sequences / big batches.  Effective on
    #: both the scanned and the unrolled encoder.
    remat: bool = False


def bert_large() -> BertConfig:
    return BertConfig()


def bert_large_tpu() -> BertConfig:
    """bert-large with TPU-native head geometry: 8 heads of 128 instead
    of 16 of 64 — head_dim 128 fills the MXU/VPU lane width in the flash
    kernels at identical parameter count and FLOPs (see
    :func:`apex_tpu.models.gpt.gpt_small_tpu` for the measured kernel
    speedup).  Prefer this shape for models pretrained from scratch on
    TPU; :func:`bert_large` keeps the conventional 16x64 for checkpoint
    parity."""
    return BertConfig(num_heads=8)


def bert_base() -> BertConfig:
    return BertConfig(hidden_size=768, num_layers=12, num_heads=12,
                      intermediate_size=3072)


def bert_tiny() -> BertConfig:
    """Test-scale config."""
    return BertConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                      num_heads=4, intermediate_size=256,
                      max_position_embeddings=64)


class SelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None):
        c = self.cfg
        head_dim = c.hidden_size // c.num_heads
        from apex_tpu.ops import use_pallas
        kv_mask = None if mask is None else mask.astype(bool)
        scale = 1.0 / float(head_dim) ** 0.5
        if use_pallas() and head_dim < 128:
            # Head-major fast path: projections emit/consume
            # (B, H, L, D) with the permutation inside their dots, and
            # the flash kernel runs layout="bhld" — no (B*H, L, D)
            # relayout copies (BERT has no rotary step in between, so
            # the path is pure).  Gated to narrow heads: measured +3.1%
            # at 16x64 (bert_large) but -1% at 8x128 (bert_large_tpu),
            # where XLA's relayouts are cheap and the head-major einsum
            # spelling costs slightly more than it saves (same-day v5e
            # A/B, round 3).
            from apex_tpu.layers import HeadMajorOutProj, HeadMajorQKVProj
            from apex_tpu.ops.pallas.flash_attention import flash_attention
            qkv = HeadMajorQKVProj(c.hidden_size, c.num_heads,
                                   name="qkv")(x)
            out = flash_attention(qkv[0], qkv[1], qkv[2], kv_mask=kv_mask,
                                  scale=scale, layout="bhld")
            return HeadMajorOutProj(c.hidden_size, c.num_heads,
                                    name="out")(out)

        qkv = Dense(3 * c.hidden_size, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(t.shape[0], t.shape[1], c.num_heads, head_dim)

        q, k, v = heads(q), heads(k), heads(v)
        if use_pallas():
            # wide heads (>= 128): split layout + the flash kernel — the
            # (L, L) scores never hit HBM and the relayout is cheap here
            from apex_tpu.ops.pallas.flash_attention import flash_attention
            out = flash_attention(q, k, v, kv_mask=kv_mask, scale=scale)
            out = out.reshape(x.shape[0], x.shape[1], c.hidden_size)
            return Dense(c.hidden_size, name="out")(out)
        scores = amp_ops.einsum("bqhd,bkhd->bhqk", q, k) \
            / jnp.sqrt(head_dim)
        if mask is not None:
            # mask: (B, L) 1 = attend; large negative in fp32
            bias = (1.0 - mask[:, None, None, :]
                    .astype(jnp.float32)) * -1e9
            scores = scores.astype(jnp.float32) + bias
        probs = amp_ops.softmax(scores, axis=-1).astype(v.dtype)
        if mask is not None:
            # all-padding rows emit zeros, matching the flash branch
            probs = jnp.where(mask[:, None, None, :].astype(bool),
                              probs, 0)
        out = amp_ops.einsum("bhqk,bkhd->bqhd", probs, v)
        out = out.reshape(x.shape[0], x.shape[1], c.hidden_size)
        return Dense(c.hidden_size, name="out")(out)


class TransformerLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask=None):
        c = self.cfg
        a = SelfAttention(c, name="attention")(x, mask)
        x = FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                           name="attention_ln")(x + a)
        with jax.named_scope(MLP):
            h = Dense(c.intermediate_size, name="ffn_in")(x)
            h = nn.gelu(h)
            h = Dense(c.hidden_size, name="ffn_out")(h)
        return FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                              name="ffn_ln")(x + h)


class _ScanBody(nn.Module):
    """Carry-shaped wrapper over :class:`TransformerLayer` for ``nn.scan``."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask):
        return TransformerLayer(self.cfg, name="layer")(x, mask), None


class BertModel(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None):
        c = self.cfg
        B, L = input_ids.shape
        tok = nn.Embed(c.vocab_size, c.hidden_size, name="tok_emb")(input_ids)
        pos = nn.Embed(c.max_position_embeddings, c.hidden_size,
                       name="pos_emb")(jnp.arange(L)[None, :])
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        seg = nn.Embed(c.type_vocab_size, c.hidden_size,
                       name="seg_emb")(token_type_ids)
        x = FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                           name="emb_ln")(tok + pos + seg)
        if c.scan_layers:
            # One compiled layer body scanned num_layers times; params get
            # a leading layer axis (shard it over a pipeline/fsdp mesh axis
            # if desired).  remat composes inside the scan: each layer's
            # activations recompute in backward instead of living in HBM.
            body = _ScanBody
            if c.remat:
                body = nn.remat(body, prevent_cse=False)
            x, _ = nn.scan(
                body,
                variable_axes={"params": 0},
                split_rngs={"params": True},
                in_axes=(nn.broadcast,),
                length=c.num_layers,
                metadata_params={nn.meta.PARTITION_NAME: "layers"},
            )(c, name="layers")(x, attention_mask)
        else:
            layer_cls = (nn.remat(TransformerLayer, prevent_cse=False)
                         if c.remat else TransformerLayer)
            for i in range(c.num_layers):
                x = layer_cls(c, name=f"layer_{i}")(x, attention_mask)
        return x


class BertForPreTraining(nn.Module):
    """MLM + NSP heads over the encoder (the pretraining objective LAMB was
    built for)."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None):
        c = self.cfg
        seq = BertModel(c, name="bert")(input_ids, token_type_ids,
                                        attention_mask)
        # MLM head: transform + LN + vocab projection.
        h = Dense(c.hidden_size, name="mlm_transform")(seq)
        h = nn.gelu(h)
        h = FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                           name="mlm_ln")(h)
        mlm_logits = Dense(c.vocab_size, name="mlm_decoder")(h)
        # NSP head over the [CLS] (first) token.
        pooled = jnp.tanh(Dense(c.hidden_size, name="pooler")(seq[:, 0]))
        nsp_logits = Dense(2, name="nsp")(pooled)
        return mlm_logits, nsp_logits


@jax.named_scope(PRETRAINING_LOSS)
def pretraining_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                     mlm_mask):
    """Masked-LM + NSP cross entropy in fp32; ``mlm_mask`` selects the
    masked positions (1.0 where a prediction is scored)."""
    # -logp[label] = logsumexp - logits[label]: identical math to
    # log_softmax + gather without materializing the (B, L, V) fp32
    # log-probability tensor (see models/gpt.py lm_loss) — the fp32
    # policy rides amp_ops.logsumexp, the gather reads the raw logits.
    lse = amp_ops.logsumexp(mlm_logits, axis=-1)
    picked = jnp.take_along_axis(mlm_logits, mlm_labels[..., None],
                                 axis=-1).squeeze(-1).astype(lse.dtype)
    denom = jnp.maximum(mlm_mask.sum(), 1.0)
    mlm_loss = ((lse - picked) * mlm_mask).sum() / denom
    nsp_logp = amp_ops.log_softmax(nsp_logits, axis=-1)
    nsp_loss = -jnp.mean(
        jnp.take_along_axis(nsp_logp, nsp_labels[:, None], axis=-1))
    return mlm_loss + nsp_loss
