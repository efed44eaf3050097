"""Rotary position embeddings — table building and reference application.

Lives under :mod:`apex_tpu.ops` (not in the GPT model) because the flash
attention kernel can apply the rotation *inside* the kernel
(``flash_attention(..., rope=(cos, sin))``): q/k blocks are rotated in
VMEM right before the score matmul, so the rotated tensors never hit HBM
and the head-major projection path stays a pure reshape end to end
(round-3 measured the out-of-kernel rotation re-materializing the layout,
net -3% on GPT — the motivation for the fused path).

The reference (2019-era apex) predates rotary embeddings entirely; this
is part of the long-context story (SURVEY.md §5.7).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class KernelRopeTables(NamedTuple):
    """Full-width kernel-format rope tables (see
    :func:`rope_kernel_tables`).  Passing this to
    ``flash_attention(rope=...)`` instead of the half-width ``(cos,
    sin)`` pair skips the per-call table build — callers with a
    scanned/remat layer body (GPT) construct it ONCE per step so the
    concat/sign-fold/cast stays out of the compiled layer loop."""

    cos_full: jax.Array   #: (B, L, D)
    sin_signed: jax.Array  #: (B, L, D) — low half negated


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> tuple:
    """(cos, sin) rotation tables ``(B, L, 1, head_dim//2)`` from *global*
    position indices — computed once per step and shared by q and k across
    every layer (they depend only on positions), so the transcendentals
    stay out of the scanned/remat layer body."""
    half = head_dim // 2
    freqs = jnp.exp(-jnp.log(theta)
                    * jnp.arange(0, half, dtype=jnp.float32) / half)
    return scaled_rope_tables(positions, freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate ``(B, L, H, D)`` by precomputed tables."""
    half = x.shape[-1] // 2
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                          axis=-1)
    return out.astype(x.dtype)


def _rope_rot_matrix(d: int) -> jax.Array:
    """Constant (D, D) matrix with ``x @ R == rotate_half(x)`` (i.e.
    ``concat(-x2, x1)``).  Entries are 0/±1, exact in bf16."""
    half = d // 2
    i = jnp.arange(half)
    r = jnp.zeros((d, d), jnp.float32)
    r = r.at[half + i, i].set(-1.0)
    r = r.at[i, half + i].set(1.0)
    return r


def apply_rope_mxu(x: jax.Array, cos_full: jax.Array,
                   sin_full: jax.Array) -> jax.Array:
    """Rotary embedding with the half-rotation as an MXU matmul.

    The concat-of-half-slices spelling (:func:`apply_rope`) creates
    minor-dim-32 lane slices whose fwd+bwd materialize as copies in the
    head-major layout (round-3 profile: 48 copies + fp32 backward
    copies per step).  ``x @ R`` with a constant 0/±1 matrix is the
    same permutation on the MXU — layout-neutral, exact, and its
    transpose is again a single matmul.  Tables are full-width:
    ``cos_full = concat(cos, cos)``, ``sin_full = concat(sin, sin)``.
    """
    r = _rope_rot_matrix(x.shape[-1]).astype(x.dtype)
    # precision="highest": with fp32 inputs the MXU's default bf16
    # passes would round what must be an exact permutation (0/±1 rows);
    # bf16 inputs are exact either way, and the matmul is tiny.
    xr = jnp.matmul(x, r, precision="highest")
    out = (x.astype(jnp.float32) * cos_full
           + xr.astype(jnp.float32) * sin_full)
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """One-shot rotary embedding (tables + apply); positions are global
    indices, so a sequence-sharded rank rotates its local shard
    correctly."""
    cos, sin = rope_tables(positions, x.shape[-1], theta)
    return apply_rope(x, cos, sin)


def _apply_full_tables(x: jax.Array, cos_full: jax.Array,
                       sin_signed: jax.Array) -> jax.Array:
    """Out-of-kernel application of the kernel-format tables (the same
    lane-rotation formula the flash kernels run in VMEM): ``x·cos_full +
    rot_half(x)·sin_signed`` where ``rot_half`` maps lane ``j`` to
    ``x[(j + D/2) mod D]``.  ``x``: (..., L, H-or-1-broadcastable, D)
    with tables broadcast over the head axis."""
    half = x.shape[-1] // 2
    xr = jnp.concatenate([x[..., half:], x[..., :half]], axis=-1)
    out = (x.astype(jnp.float32) * cos_full.astype(jnp.float32)
           + xr.astype(jnp.float32) * sin_signed.astype(jnp.float32))
    return out.astype(x.dtype)


def apply_rope_tables(q: jax.Array, k: jax.Array, rope,
                      layout: str = "blhd") -> tuple:
    """Rotate q and k out-of-kernel from a public ``rope`` argument —
    either a half-width ``(cos, sin)`` pair (``(B, L, 1, D/2)`` or
    ``(B, L, D/2)``) or prebuilt :class:`KernelRopeTables` — the shared
    fallback stanza for paths that cannot fuse the rotation (jnp
    attention, interpret-under-shard_map).  Keeps the table-shape
    convention in one place next to :func:`rope_kernel_tables`.  Raises
    the same self-attention requirement the kernel path enforces."""
    seq_ax = 2 if layout == "bhld" else 1
    l = q.shape[seq_ax]
    if k.shape[seq_ax] != l:
        raise ValueError("rope requires self-attention (Lq == Lk): q and "
                         "k share one position table")
    if isinstance(rope, KernelRopeTables):
        cos4 = rope.cos_full[:, :, None, :]   # (B, L, 1, D)
        sin4 = rope.sin_signed[:, :, None, :]
        if layout == "bhld":
            cos4, sin4 = (jnp.moveaxis(t, 1, 2) for t in (cos4, sin4))
        return (_apply_full_tables(q, cos4, sin4),
                _apply_full_tables(k, cos4, sin4))
    half = q.shape[-1] // 2
    cos4 = rope[0].reshape(rope[0].shape[0], l, 1, half)
    sin4 = rope[1].reshape(rope[1].shape[0], l, 1, half)
    if layout == "bhld":
        cos4, sin4 = (jnp.moveaxis(t, 1, 2) for t in (cos4, sin4))
    return apply_rope(q, cos4, sin4), apply_rope(k, cos4, sin4)


def rope_kernel_tables(cos: jax.Array, sin: jax.Array, b: int, l: int,
                       d: int, dtype) -> KernelRopeTables:
    """Public (cos, sin) half-width tables → the flash kernel's
    ``(B, L, D)`` full-width pair ``(cos_full, sin_signed)``.

    The in-kernel rotation is spelled lane-rotation-style —
    ``rot(x) = x * cos_full + rotate_lanes(x, D/2) * sin_signed`` where
    ``rotate_lanes`` maps lane ``j`` to ``x[(j + D/2) mod D]`` — so the
    sign of the classic ``x1·cos − x2·sin`` low half is folded into the
    table: ``cos_full = [cos, cos]``, ``sin_signed = [−sin, sin]``.
    Table dtype follows the activation dtype (bf16 activations take bf16
    tables: the extra rounding is the same class as the bf16 q/k storage
    itself, and it halves the kernel's table DMA)."""
    cos = cos.reshape(cos.shape[0], l, d // 2)
    sin = sin.reshape(sin.shape[0], l, d // 2)
    if cos.shape[0] != b:
        cos = jnp.broadcast_to(cos, (b, l, d // 2))
        sin = jnp.broadcast_to(sin, (b, l, d // 2))
    cos_full = jnp.concatenate([cos, cos], axis=-1)
    sin_signed = jnp.concatenate([-sin, sin], axis=-1)
    return KernelRopeTables(cos_full.astype(dtype),
                            sin_signed.astype(dtype))


def rope_tables_interleaved(positions: jax.Array, rotary_dim: int,
                            theta: float) -> tuple:
    """(cos, sin) ``(B, L, 1, rotary_dim)`` for :func:`apply_rope_interleaved`:
    frequency ``i`` of ``rotary_dim // 2`` sits on lanes ``2i`` and
    ``2i + 1``, the pair it turns (``rope_interleave`` of the DeepSeek-V3
    family, GPT-J's layout)."""
    cos, sin = rope_tables(positions, rotary_dim, theta)
    return jnp.repeat(cos, 2, axis=-1), jnp.repeat(sin, 2, axis=-1)


def _interleaved_rot_matrix(d: int, rotary_dim: int) -> jax.Array:
    """Constant (D, D) matrix: ``(x @ R)[2i] == -x[2i + 1]`` and
    ``(x @ R)[2i + 1] == x[2i]`` on the trailing ``rotary_dim`` lanes,
    nought on the leading ones."""
    even = d - rotary_dim + 2 * jnp.arange(rotary_dim // 2)
    r = jnp.zeros((d, d), jnp.float32)
    r = r.at[even + 1, even].set(-1.0)
    return r.at[even, even + 1].set(1.0)


def apply_rope_interleaved(x: jax.Array, cos: jax.Array,
                           sin: jax.Array) -> jax.Array:
    """Partial rotary over interleaved pairs: the trailing
    ``cos.shape[-1]`` lanes of ``x`` ``(B, L, H, D)`` turn, pair
    ``(2i, 2i + 1)`` by frequency ``i``; the leading lanes pass
    unchanged (latent attention turns 64 of a head's 192).  Tables from
    :func:`rope_tables_interleaved`.

    Spelled like :func:`apply_rope_mxu`: the pair swap is a product with
    a constant 0/±1 matrix, so the head is neither split nor
    re-assembled and the layout stays as the projection left it; the
    tables are padded with ones and noughts over the lanes that pass."""
    d, rot = x.shape[-1], cos.shape[-1]
    lead = [(0, 0)] * (cos.ndim - 1) + [(d - rot, 0)]
    cos = jnp.pad(cos, lead, constant_values=1.0)
    sin = jnp.pad(sin, lead)
    xr = jnp.matmul(x, _interleaved_rot_matrix(d, rot).astype(x.dtype),
                    precision="highest")
    out = x.astype(jnp.float32) * cos + xr.astype(jnp.float32) * sin
    return out.astype(x.dtype)


def rope_inv_freq(rope_parameters: dict, head_dim: int) -> tuple:
    """``(inv_freq, attention_factor)`` of one layer kind's published
    ``rope_parameters`` (Hugging Face's ``rope_type`` ``default`` or
    ``yarn``; ``_compute_yarn_parameters`` with ``truncate`` true).

    The rotary turns ``rotary_dim = head_dim * partial_rotary_factor``
    lanes, ``rotary_dim / 2`` frequencies ``theta ** (-2i / rotary_dim)``.
    YaRN blends, frequency by frequency, the original one
    (extrapolation) and the one slowed by ``factor`` (interpolation) by
    a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow`` rotations over ``original_max_position_embeddings``;
    ``attention_factor`` (``0.1 ln(factor) + 1`` where not given) scales
    cos and sin.  float32 numpy, as the published code computes them."""
    import math

    import numpy as np
    p = rope_parameters
    dim = int(head_dim * p.get("partial_rotary_factor", 1.0))
    base = float(p["rope_theta"])
    pos = base ** (np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim))
    kind = p.get("rope_type", "default")
    if kind == "default":
        return (1.0 / pos).astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r} has no tables here")
    factor = float(p["factor"])
    original = float(p["original_max_position_embeddings"])
    scale = p.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0

    def correction_dim(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(p.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(p.get("beta_slow", 1))), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low if high != low else 0.001), 0, 1)
    extrapolation = 1.0 - ramp
    inv_freq = ((1.0 / (factor * pos)) * (1.0 - extrapolation)
                + (1.0 / pos) * extrapolation)
    return inv_freq.astype(np.float32), float(scale)


def scaled_rope_tables(positions: jax.Array, inv_freq,
                       attention_factor: float = 1.0) -> tuple:
    """(cos, sin) ``(B, L, 1, len(inv_freq))`` of global positions at the
    given frequencies, both times ``attention_factor``: the half-width
    tables :func:`rope_tables` gives, for any frequencies."""
    angles = (positions[:, :, None].astype(jnp.float32)
              * jnp.asarray(inv_freq, jnp.float32))      # (B, L, half)
    cos, sin = jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]
    if attention_factor == 1.0:
        return cos, sin
    return attention_factor * cos, attention_factor * sin


def to_rotary_lanes(x: jax.Array, rotary_dim: int) -> jax.Array:
    """``x`` ``(..., head_dim)`` in the kernels' lane order, where the
    rotary turns only the first ``rotary_dim`` lanes of a head
    (rotate-half inside them: lanes ``i`` and ``i + rotary_dim / 2``).
    The kernels turn lane ``j`` with ``j + head_dim / 2``, so the pairs
    go to lanes ``i`` and ``i + head_dim / 2`` and the lanes that pass
    fill the rest in order: four runs of lanes, a concatenation of
    slices and no gather.  q and k take the same order, so their
    product, and every score, is unchanged."""
    d = x.shape[-1]
    if rotary_dim == d:
        return x
    half, rhalf = d // 2, rotary_dim // 2
    split = rotary_dim + half - rhalf
    return jnp.concatenate([x[..., :rhalf], x[..., rotary_dim:split],
                            x[..., rhalf:rotary_dim], x[..., split:]],
                           axis=-1)


def partial_rope_kernel_tables(cos: jax.Array, sin: jax.Array,
                               head_dim: int) -> KernelRopeTables:
    """The kernels' full-width tables for a head whose lanes are in
    :func:`to_rotary_lanes`' order: ``cos`` and ``sin`` ``(B, L, 1,
    rotary_dim / 2)`` on the turning lanes (sine folded as
    :func:`rope_kernel_tables` folds it), cos 1 and sin 0 on the lanes
    that pass, so those come out as they went in."""
    b, l, rhalf = cos.shape[0], cos.shape[1], cos.shape[-1]
    cos = cos.reshape(b, l, rhalf)
    sin = sin.reshape(b, l, rhalf)
    ones = jnp.ones((b, l, head_dim // 2 - rhalf), cos.dtype)
    zeros = jnp.zeros_like(ones)
    return KernelRopeTables(jnp.concatenate([cos, ones, cos, ones], -1),
                            jnp.concatenate([-sin, zeros, sin, zeros], -1))
