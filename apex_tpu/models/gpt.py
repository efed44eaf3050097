"""GPT-style causal language model — the long-context training workload.

Beyond the reference (2019-era apex has no LM and no long-context story,
SURVEY.md section 5.7); this model exists so the framework's long-context
machinery trains a *real* architecture end-to-end:

- causal Pallas flash attention (``apex_tpu.ops.pallas.flash_attention``)
  with rotary position embeddings — no (L, L) tensor in HBM, no learned
  position table capping the context;
- ``seq_axis_name`` switches attention to
  :func:`~apex_tpu.attention.ring_attention` so the sequence dimension
  shards over a mesh axis (context parallelism) while everything else is
  untouched;
- ``scan_layers`` / ``remat`` as in :class:`~apex_tpu.models.bert.BertModel`
  (one compiled layer body; recompute-for-HBM);
- FusedLayerNorm everywhere, matmuls at amp compute precision.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.layers import Dense
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.utils.profiling import LM_LOSS, MLP
# Rope math lives in ops (the flash kernel applies it in-kernel); the
# historical spellings stay importable from here.
from apex_tpu.ops.rope import (  # noqa: F401  (re-exports)
    apply_rope,
    apply_rope_mxu,
    rope,
    rope_tables,
    _rope_rot_matrix,
)


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 32000
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    layer_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    #: shard the sequence over this mesh axis (ring attention); None = local
    seq_axis_name: Optional[str] = None
    scan_layers: bool = False
    remat: bool = False


def gpt_small() -> GPTConfig:
    return GPTConfig()


def gpt_small_tpu() -> GPTConfig:
    """gpt-small with TPU-native head geometry: 6 heads of 128 instead
    of 12 of 64.  head_dim 128 fills the MXU/VPU lane width, measured
    35-40% faster flash attention at identical FLOPs and parameter
    count (B8·L2048 on v5e: fwd 2.50 -> 1.63 ms/layer, fwd+bwd 6.51 ->
    3.89 ms/layer).  Prefer this shape for models trained from scratch
    on TPU; :func:`gpt_small` keeps the GPU-conventional 12x64 for
    checkpoint parity."""
    return GPTConfig(num_heads=6)


def gpt_medium_tpu() -> GPTConfig:
    """gpt-medium (~368M params) with TPU-native 8x128 heads.  The
    bigger matmuls lift single-chip MFU past the small model (measured
    53% at B8·L2048 amp O2 on v5e, 43.4K tok/s)."""
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=8,
                     intermediate_size=4096)


def gpt_tiny() -> GPTConfig:
    """Test-scale config."""
    return GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                     num_heads=4, intermediate_size=128)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, rope_cs):
        c = self.cfg
        head_dim = c.hidden_size // c.num_heads
        b, l = x.shape[0], x.shape[1]
        scale = 1.0 / float(head_dim) ** 0.5
        from apex_tpu.attention import attention
        from apex_tpu.ops.rope import KernelRopeTables

        qkv = Dense(3 * c.hidden_size, name="qkv")(x)
        q, k, v = (t.reshape(b, l, c.num_heads, head_dim)
                   for t in jnp.split(qkv, 3, axis=-1))

        if isinstance(rope_cs, KernelRopeTables):
            # Kernel-fused rope (GPTModel builds the kernel-format
            # tables once per step, outside the scanned/remat body):
            # q/k reach the flash kernel UNROTATED and the rotation
            # happens on VMEM blocks right before the score matmul —
            # the rotated tensors never exist in HBM and the four rope
            # elementwise passes (q/k fwd, dq/dk bwd) disappear from
            # the step.  Same-day v5e A/B (round 4, B8·L2048 O2 train
            # step): split+fused-rope beats the round-3 prerotated path
            # ~+2% at both 12x64 and 6x128, and beats a head-major
            # (HeadMajorQKVProj + layout="bhld" + fused rope) variant
            # by ~5% at 12x64 — unlike BERT, GPT loses more to the
            # head-major projection einsum than the reshape relayout
            # costs, so the split spelling stays.
            out = attention(q, k, v, causal=True, scale=scale,
                            rope=rope_cs)
        else:
            cos, sin = rope_cs
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            # with seq_axis_name: ring attention over the mesh axis
            out = attention(q, k, v, axis_name=c.seq_axis_name,
                            causal=True, scale=scale)
        out = out.reshape(b, l, c.hidden_size)
        return Dense(c.hidden_size, name="out")(out)


class GPTBlock(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, rope_cs):
        c = self.cfg
        h = FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                           name="ln1")(x)
        x = x + CausalSelfAttention(c, name="attention")(h, rope_cs)
        h = FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                           name="ln2")(x)
        with jax.named_scope(MLP):
            h = Dense(c.intermediate_size, name="ffn_in")(h)
            h = nn.gelu(h)
            h = Dense(c.hidden_size, name="ffn_out")(h)
        return x + h


class _ScanBody(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, rope_cs):
        return GPTBlock(self.cfg, name="block")(x, rope_cs), None


class GPTModel(nn.Module):
    """Decoder-only transformer; ``__call__(input_ids, positions=None)``
    returns logits ``(B, L, vocab)``.

    ``positions`` are *global* token indices ``(B, L)``; when the sequence
    is sharded over ``seq_axis_name``, pass each rank its own slice (see
    :func:`lm_loss` and the sp dryrun slice) — defaults to ``0..L-1``.
    """

    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, positions=None):
        c = self.cfg
        B, L = input_ids.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        x = nn.Embed(c.vocab_size, c.hidden_size, name="tok_emb")(input_ids)
        # rope tables depend only on positions: compute once, share across
        # q/k and every layer (kept out of the scanned/remat body)
        head_dim = c.hidden_size // c.num_heads
        rope_cs = rope_tables(positions, head_dim, c.rope_theta)
        from apex_tpu.ops import use_pallas
        if use_pallas() and c.seq_axis_name is None:
            # Local flash path: pre-build the KERNEL-format tables here
            # too (concat/sign-fold/cast), so under scan_layers/remat
            # the per-layer attention calls reuse them instead of
            # rebuilding (B, L, D) tables inside the compiled loop body.
            from apex_tpu.ops.rope import rope_kernel_tables
            table_dtype = (jnp.bfloat16 if x.dtype == jnp.bfloat16
                           else jnp.float32)
            rope_cs = rope_kernel_tables(
                rope_cs[0], rope_cs[1], B, input_ids.shape[1], head_dim,
                table_dtype)
        if c.scan_layers:
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
            )(c, name="layers")(x, rope_cs)
        else:
            block_cls = (nn.remat(GPTBlock, prevent_cse=False)
                         if c.remat else GPTBlock)
            for i in range(c.num_layers):
                x = block_cls(c, name=f"block_{i}")(x, rope_cs)
        x = FusedLayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                           name="ln_f")(x)
        return Dense(c.vocab_size, use_bias=False, name="lm_head")(x)


@jax.named_scope(LM_LOSS)
def lm_loss(logits: jax.Array, targets: jax.Array,
            mask: Optional[jax.Array] = None,
            seq_axis_name: Optional[str] = None) -> jax.Array:
    """Mean next-token cross entropy in fp32.  ``targets`` are the
    *shifted* labels (callers shift; under sequence sharding each rank
    shifts within its shard and masks the seam or supplies the neighbor's
    first token).

    With ``seq_axis_name`` (sequence-sharded training) the normalizer is
    the *global* token count (``psum`` of the mask over the axis), so each
    shard returns ``local_sum / global_count``.  SPMD autodiff sums the
    replicated params' grads across shards, which then reconstructs
    exactly the gradient of the global mean — normalizing per shard
    instead would silently scale gradients by the shard count.  Report
    the global loss as ``lax.psum(loss, axis)`` (not pmean).
    """
    # -logp[target] = logsumexp(logits) - logits[target]: same math as
    # log_softmax + gather, but the (B, L, V) fp32 log-probability tensor
    # is never materialized in HBM — the cast fuses into the reduction
    # and only the (B, L) lse/picked rows are written (the gather reads
    # the bf16 logits directly).  At (8, 2047, 32000) that saves a ~2 GB
    # fp32 round-trip per step.
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None],
                                 axis=-1)[..., 0].astype(jnp.float32)
    if mask is None:
        m = jnp.ones(picked.shape, jnp.float32)
    else:
        m = mask.astype(jnp.float32)
    total = jnp.sum((lse - picked) * m)
    count = jnp.sum(m)
    if seq_axis_name is not None:
        count = jax.lax.psum(count, seq_axis_name)
    return total / jnp.maximum(count, 1.0)


def train_toy_lm(cfg=None, steps: int = 50, period: int = 16):
    """``(cfg, params, ids)``: a gpt_tiny BRIEFLY TRAINED on a
    periodic token stream, in the bf16 O2 serving layout, plus the
    ``(8, 64)`` int32 training ids its prompts should come from.

    The shared fixture behind every test/tool that needs a
    model with REAL argmax margins (``tests/l0/test_serve_spec.py``,
    ``tests/l0/test_quant.py``'s tolerance checks,
    ``tools/serve_scenarios.py``): a
    random-init model's near-uniform logits put ulp/quantization
    noise above the margins — measuring tie-breaking, not the thing
    under test — and make speculative acceptance structurally
    ~1/vocab.  ONE recipe (seed 8, FusedAdam lr 3e-3, ``steps``
    steps on ``(arange * 7) % period``) keeps every consumer
    measuring the same model; imports are lazy so the models module
    stays importable without the amp/optimizer stack."""
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    cfg = cfg or gpt_tiny()
    model = GPTModel(cfg)
    ids = (jnp.arange(8 * 64).reshape(8, 64) * 7) % period
    params = model.init(jax.random.PRNGKey(8),
                        ids[:1, :8].astype(jnp.int32))["params"]
    a = amp.initialize(optimizer=FusedAdam(lr=3e-3), opt_level="O2",
                       verbosity=0)
    state = a.init(params)

    def loss_fn(p, xb):
        logits = model.apply({"params": p}, xb)
        return lm_loss(logits[:, :-1], xb[:, 1:])

    step = jax.jit(amp.make_train_step(a, loss_fn))
    for _ in range(steps):
        state, _m = step(state, ids.astype(jnp.int32))
    import numpy as np
    return cfg, a.model_params(state), np.asarray(ids, np.int32)
