"""Sequence/context-parallel attention over a mesh axis.

Long-context scaling has no counterpart in the reference (SURVEY.md §5.7 —
apex predates it); these are the TPU-native mechanisms that make sequence
length a shardable dimension, designed around ICI collectives:

- :func:`ring_attention` — blockwise attention with online softmax: K/V
  shards rotate around the ring axis via ``lax.ppermute`` while each device
  keeps its query shard resident; peak memory per device is O(L·L/W) for
  the running block only, and the per-step ppermute overlaps with the
  block matmuls (Liu et al., "Ring Attention with Blockwise Transformers",
  2023 — pattern, not code).
- :func:`ulysses_attention` — all-to-all sequence parallelism: swap the
  sequence sharding for a head sharding with ``lax.all_to_all``, run full
  -sequence attention on 1/W of the heads per device, swap back
  (Jacobs et al., "DeepSpeed Ulysses", 2023 — pattern, not code).

Both compute softmax statistics in fp32 regardless of input dtype (the amp
blacklist rule for softmax, reference ``functional_overrides.py:29-65``)
and are exact: outputs match single-device full attention to float
tolerance (asserted in ``tests/distributed/test_ring_attention.py``).

Shapes follow the ``(batch, seq, heads, head_dim)`` convention with the
sequence dimension sharded over ``axis_name``.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _use_pallas_blocks() -> bool:
    from apex_tpu.ops import use_pallas
    return use_pallas()


def _vary_like(reference_array, axis_name):
    """``pvary`` tagger matching the full varying-axes set of an operand:
    under a multi-dim mesh the inputs may vary over more axes than the
    ring axis (e.g. a batch axis), and loop carries / switch branches must
    type-match them exactly."""
    try:
        vma = tuple(set(jax.typeof(reference_array).vma) | {axis_name})
    except Exception:
        vma = (axis_name,)
    return lambda t: lax.pcast(t, vma, to="varying")


def _block_scores(q, k, scale, q_off, k_off, causal, kv_mask, window=None):
    """fp32 attention scores for one (local-q, rotating-k) block pair; a
    ``window`` (with ``causal``) hides keys ``window`` or more behind."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, NEG_INF)
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        qpos = q_off + jnp.arange(lq)
        kpos = k_off + jnp.arange(lk)
        s = jnp.where(qpos[None, None, :, None] >= kpos[None, None, None, :],
                      s, NEG_INF)
        if window is not None:
            s = jnp.where(qpos[None, None, :, None] - kpos[None, None, None, :]
                          < window, s, NEG_INF)
    return s


def _ring_attention_flash(q, k, v, axis_name, causal, kv_mask, scale):
    """Ring attention with the Pallas flash kernel as the per-step block
    engine: each hop computes an exact local attention (out, lse) pair and
    merges it into the carry by logsumexp weighting — no ``(L/W, L/W)``
    score tensor ever hits HBM.  The merge is differentiable because
    :func:`flash_attention` exposes a differentiable ``lse``."""
    from apex_tpu.ops.pallas.flash_attention import NEG_INF as FLASH_NEG
    from apex_tpu.ops.pallas.flash_attention import flash_attention

    world = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, l_local, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(i, (i + 1) % world) for i in range(world)]

    vary = _vary_like(q, axis_name)
    o = vary(jnp.zeros((b, l_local, h, d), jnp.float32))
    lse = vary(jnp.full((b, l_local, h), FLASH_NEG, jnp.float32))
    mask_c = (vary(jnp.ones((b, l_local), bool))
              if kv_mask is None else kv_mask)

    def step(t, carry):
        k_t, v_t, mask_t, o, lse = carry
        src = (rank - t) % world

        def full_block(_):
            ot, lt = flash_attention(q, k_t, v_t, causal=False,
                                     kv_mask=mask_t, scale=scale,
                                     return_lse=True)
            return ot.astype(jnp.float32), lt

        def diag_block(_):
            ot, lt = flash_attention(q, k_t, v_t, causal=True,
                                     kv_mask=mask_t, scale=scale,
                                     return_lse=True)
            return ot.astype(jnp.float32), lt

        def skip_block(_):
            # literal zeros must be tagged device-varying to type-match the
            # other switch branches under VMA checking
            return (vary(jnp.zeros((b, l_local, h, d), jnp.float32)),
                    vary(jnp.full((b, l_local, h), FLASH_NEG, jnp.float32)))

        if causal:
            # src < rank: fully visible; src == rank: local causal;
            # src > rank: entirely in the future.
            branch = jnp.where(src == rank, 1,
                               jnp.where(src < rank, 0, 2))
            o_t, lse_t = lax.switch(branch,
                                    [full_block, diag_block, skip_block],
                                    None)
        else:
            o_t, lse_t = full_block(None)

        # logsumexp-weighted merge of two normalized partial results.
        m = jnp.maximum(lse, lse_t)
        w1 = jnp.exp(lse - m)
        w2 = jnp.exp(lse_t - m)
        tot = w1 + w2
        o_new = (o * w1[:, :, :, None]
                 + o_t * w2[:, :, :, None]) / tot[:, :, :, None]
        lse_new = m + jnp.log(tot)

        k_n = lax.ppermute(k_t, axis_name, perm)
        v_n = lax.ppermute(v_t, axis_name, perm)
        mask_n = lax.ppermute(mask_t, axis_name, perm)
        return k_n, v_n, mask_n, o_new, lse_new

    _, _, _, o, lse = lax.fori_loop(0, world, step,
                                    (k, v, mask_c, o, lse))
    return o.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """Exact attention with the sequence dimension sharded over
    ``axis_name``; call inside ``shard_map``.

    q, k, v: ``(B, L/W, H, D)`` local shards (contiguous blocks in ring
    order).  ``kv_mask``: optional ``(B, L/W)`` bool key mask, sharded like
    k/v (True = attend).  Online-softmax state (running max ``m``, running
    normalizer ``l``, fp32 accumulator) is carried across the W ring steps;
    K/V (and the mask) advance one hop per step with ``ppermute``.

    On TPU the per-step block attention runs the Pallas flash kernel
    (``impl="flash"`` forces it, ``impl="jnp"`` forces the materializing
    path).
    """
    if impl not in (None, "flash", "jnp"):
        raise ValueError(f"unknown ring impl {impl!r}")
    if impl == "flash" or (impl is None and _use_pallas_blocks()):
        return _ring_attention_flash(q, k, v, axis_name, causal, kv_mask,
                                     scale)
    world = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    b, l_local, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    perm = [(i, (i + 1) % world) for i in range(world)]

    # literal-initialized carries must be tagged device-varying so the loop
    # carry type matches the (varying) step outputs under shard_map's VMA
    # checking
    vary = _vary_like(q, axis_name)
    m = vary(jnp.full((b, h, l_local), NEG_INF, jnp.float32))
    l = vary(jnp.zeros((b, h, l_local), jnp.float32))
    acc = vary(jnp.zeros((b, l_local, h, d), jnp.float32))
    if kv_mask is None:
        kv_mask_c = vary(jnp.ones((b, l_local), bool))
    else:
        kv_mask_c = kv_mask

    def step(t, carry):
        k_t, v_t, mask_t, m, l, acc = carry
        # device `rank` holds K/V block (rank - t) mod world at step t
        src = (rank - t) % world
        s = _block_scores(q, k_t, scale, rank * l_local, src * l_local,
                          causal, mask_t)                  # (b, h, lq, lk)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])                  # (b, h, lq, lk)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_t.astype(jnp.float32))
        acc = acc * corr.transpose(0, 2, 1)[..., None] + pv
        k_n = lax.ppermute(k_t, axis_name, perm)
        v_n = lax.ppermute(v_t, axis_name, perm)
        mask_n = lax.ppermute(mask_t, axis_name, perm)
        return k_n, v_n, mask_n, m_new, l, acc

    _, _, _, m, l, acc = lax.fori_loop(
        0, world, step, (k, v, kv_mask_c, m, l, acc))

    # rows with no attendable key (fully masked) produce l = 0; emit zeros
    # rather than NaN, matching masked-softmax conventions.
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = acc / safe_l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    impl: Optional[str] = None,
) -> jax.Array:
    """All-to-all sequence parallelism: trade the sequence sharding for a
    head sharding, attend over the full sequence locally, trade back.
    ``impl="flash"``/``"jnp"`` forces the local attention engine (auto:
    flash on TPU).

    Requires ``heads % world == 0``.  One fused all-to-all each way on ICI;
    preferable to the ring when heads are plentiful and the sequence fits
    once per device.
    """
    world = lax.axis_size(axis_name)
    b, l_local, h, d = q.shape
    if h % world != 0:
        raise ValueError(f"heads ({h}) must divide by the axis size "
                         f"({world}) for ulysses_attention")
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    def to_full_seq(t):
        # (B, L/W, H, D) -> (B, L, H/W, D): split heads, concat sequence
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qf, kf, vf = to_full_seq(q), to_full_seq(k), to_full_seq(v)
    mask_f = (lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
              if kv_mask is not None else None)

    if impl not in (None, "flash", "jnp"):
        raise ValueError(f"unknown ulysses impl {impl!r}")
    if impl == "flash" or (impl is None and _use_pallas_blocks()):
        from apex_tpu.ops.pallas.flash_attention import flash_attention
        out = flash_attention(qf, kf, vf, causal=causal, kv_mask=mask_f,
                              scale=scale)
    else:
        s = _block_scores(qf, kf, scale, 0, 0, causal, mask_f)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = jnp.einsum("bhqk,bkhd->bqhd", p / safe_l,
                         vf.astype(jnp.float32)).astype(q.dtype)

    # (B, L, H/W, D) -> (B, L/W, H, D)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str] = None,
    impl: str = "ring",
    **kwargs,
) -> jax.Array:
    """Dispatcher: full local attention when ``axis_name`` is None (the
    Pallas flash kernel on TPU, the jnp path elsewhere; force one with
    ``impl="flash"`` / ``impl="jnp"``), else the selected sequence-parallel
    implementation (``impl="flash"``/``"jnp"`` with an ``axis_name`` select
    the ring path's block engine).  ``k`` and ``v`` may hold fewer heads
    than ``q`` (grouped KV heads, locally only), and ``window=W`` keeps
    each query to its ``W`` latest keys (locally only): see
    :func:`~apex_tpu.ops.pallas.flash_attention.flash_attention`."""
    if impl not in ("ring", "ulysses", "flash", "jnp"):
        raise ValueError(f"unknown attention impl {impl!r}")
    layout = kwargs.pop("layout", "blhd")
    if layout == "bhld" and axis_name is not None:
        # Head-major fast path (see flash_attention): local only — the
        # sequence-parallel engines speak (B, L, H, D).
        raise ValueError("layout='bhld' requires axis_name=None")
    rope = kwargs.pop("rope", None)
    head_ax = 1 if layout == "bhld" else 2
    if k.shape[head_ax] != q.shape[head_ax] and axis_name is not None:
        # Grouped KV heads (fewer k/v heads than q's) run locally only.
        raise ValueError("grouped KV heads require axis_name=None")
    window = kwargs.pop("window", None)
    if window is not None and axis_name is not None:
        # A window runs locally only: the ring and Ulysses engines see
        # whole blocks of keys.
        raise ValueError("a window requires axis_name=None")
    if window is not None and not kwargs.get("causal", False):
        raise ValueError("a window requires causal attention")
    if rope is not None and axis_name is not None:
        # The sequence-parallel engines take pre-rotated q/k (positions
        # are global, each rank rotates its shard before the collective).
        raise ValueError("rope=(cos, sin) requires axis_name=None; "
                         "rotate q/k with apply_rope before a "
                         "sequence-parallel call")
    if axis_name is None:
        if impl == "flash" or (impl != "jnp" and _use_pallas_blocks()):
            from apex_tpu.ops.pallas.flash_attention import flash_attention
            return flash_attention(q, k, v, layout=layout,
                                   causal=kwargs.get("causal", False),
                                   kv_mask=kwargs.get("kv_mask"),
                                   scale=kwargs.get("scale"),
                                   block_q=kwargs.get("block_q"),
                                   block_k=kwargs.get("block_k"),
                                   return_lse=kwargs.get("return_lse",
                                                         False),
                                   rope=rope, window=window)
        if rope is not None:
            from apex_tpu.ops.rope import apply_rope_tables
            q, k = apply_rope_tables(q, k, rope, layout)
        if layout == "bhld":
            # jnp fallback speaks (B, L, H, D)
            out = attention(jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2),
                            jnp.moveaxis(v, 1, 2), axis_name=None,
                            impl=impl, window=window, **kwargs)
            if kwargs.get("return_lse", False):
                return jnp.moveaxis(out[0], 1, 2), out[1]
            return jnp.moveaxis(out, 1, 2)
        from apex_tpu.ops.pallas.flash_attention import repeat_kv_heads
        k, v = repeat_kv_heads(k, v, q.shape[2])
        s = _block_scores(q, k, kwargs.get("scale") or 1.0 / (q.shape[-1] ** 0.5),
                          0, 0, kwargs.get("causal", False),
                          kwargs.get("kv_mask"), window)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = jnp.einsum("bhqk,bkhd->bqhd", p / safe_l,
                         v.astype(jnp.float32)).astype(q.dtype)
        if kwargs.get("return_lse", False):
            # (B, L, H) fp32, NEG_INF for fully-masked rows — the flash
            # branch's convention, so the two backends interchange.
            lse = jnp.where(l[..., 0] == 0.0, NEG_INF,
                            m[..., 0] + jnp.log(safe_l[..., 0]))
            return out, jnp.moveaxis(lse, 1, 2)
        return out
    if impl == "ulysses":
        return ulysses_attention(q, k, v, axis_name, **kwargs)
    if impl in ("flash", "jnp"):
        return ring_attention(q, k, v, axis_name, impl=impl, **kwargs)
    return ring_attention(q, k, v, axis_name, **kwargs)
