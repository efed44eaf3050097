"""Pallas TPU kernels for the two-stage LAMB update.

TPU-native equivalents of ``csrc/multi_tensor_lamb_stage_1.cu:17-121`` and
``csrc/multi_tensor_lamb_stage_2.cu:18-92``.  The CUDA kernels resolve
per-tensor arguments (weight decay, trust ratio) through the block→tensor
table packed into kernel argument space; here the tensor list is packed
chunk-*aligned* (:func:`apex_tpu.ops.packing.pack_aligned`) so chunks never
straddle tensors, and the per-chunk scalar table sits whole in SMEM —
the direct analog of ``TensorListMetadata``'s block→tensor map living in
kernel argument space.

Stage boundaries mirror the CUDA split: stage 1 is the gradient
descale/clip → Adam moment update → ``update = m̂/(√v̂+ε) + decay·p`` pass;
per-tensor ‖p‖/‖update‖ norms feed stage 2 (the role of
``multi_tensor_l2norm``'s per-tensor output); stage 2 applies
``p ← p − ratio·update`` with the per-tensor trust ratio (lr folded in, with
the plain-lr fallback when either norm is zero).  All arithmetic is fp32.

Memory movement (round 6 retune): one grid step streams
``chunks_per_block`` chunks (shared selector,
:mod:`apex_tpu.ops.pallas.geometry`) instead of a single (8, 128) tile
(unmeasured on this benchmark: no cell runs LAMB).  The
chunk sub-blocks are statically unrolled so each keeps its own SMEM
table scalars, and ragged chunk counts ride Mosaic's masked last block
(the scalar tables are padded to the grid so the dead tail indexes real
slots).  Stage 1 optionally FUSES the per-tensor norm reductions into
the streaming pass (``with_norms=True``): per-chunk ‖p‖²/‖update‖²
partials land in SMEM accumulator tables keyed by the existing
chunk→tensor map, saving the two extra full passes
(``per_tensor_sumsq_from_packed`` re-reading p and u, 8N bytes) the
driver paid between the stages.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds
from apex_tpu.ops.pallas import geometry
from apex_tpu.ops.pallas.multi_tensor_kernels import _LANES, _view2d

#: Base chunk size for aligned packing: one (8, 128) fp32 tile per chunk.
LAMB_CHUNK = 8 * 128

#: Upper bound on chunks per call — keeps the SMEM scalar tables (fp32 per
#: chunk) around 128 KiB against the ~1 MiB SMEM budget; drivers grow the
#: chunk size instead of the table (see fused_lamb._pallas_lamb_update).
MAX_CHUNKS = 32768

#: Upper bound on the grown chunk size: stage 1 streams 7 fp32 buffers per
#: grid step, so 64 Ki elements (256 KiB each) stays ~3.5 MiB double-buffered
#: against the ~16 MiB VMEM budget.  MAX_CHUNKS × LAMB_CHUNK_MAX ≈ 2.1 B
#: params is the Pallas path's capacity; beyond it drivers fall back to the
#: jnp path rather than fail Mosaic compilation.
LAMB_CHUNK_MAX = 64 * 1024


def grown_chunk(total: int) -> int:
    """Chunk size grown so at most MAX_CHUNKS chunks cover ``total``
    elements — THE formula shared by the LAMB driver's packer and its
    capacity predicate (they must agree or an over-budget tree reaches
    Mosaic and fails compilation)."""
    return LAMB_CHUNK * max(1, -(-total // (LAMB_CHUNK * MAX_CHUNKS)))


def tree_within_packed_capacity(ps) -> bool:
    """Shared capacity predicate for the whole-tree packed optimizer paths
    (LAMB stages, packed Adam — all stream 7-8 fp32 buffers per grid
    step): element total bounded by MAX_CHUNKS x LAMB_CHUNK_MAX (VMEM
    tiles) AND chunk count bounded by MAX_CHUNKS (SMEM per-chunk tables;
    aligned packing gives every leaf at least one chunk, so many tiny
    leaves can blow the table even at a small element total)."""
    from apex_tpu.ops.packing import aligned_chunk_count, leaf_sizes
    sizes = leaf_sizes(ps)
    total = sum(sizes)
    if total > MAX_CHUNKS * LAMB_CHUNK_MAX:
        return False
    return aligned_chunk_count(sizes, grown_chunk(total)) <= MAX_CHUNKS


def stage1_geometry(n: int, chunk_size: int,
                    chunks_per_block: "int | None" = None
                    ) -> geometry.StreamGeometry:
    """Stage-1 streaming geometry (7 fp32 streams: g+p+m+v in,
    u+m+v out) — shared by the kernel and its tests."""
    return geometry.chunked_geometry(n, chunk_size,
                                     row_bytes=_LANES * 4 * 7,
                                     lanes=_LANES,
                                     chunks_per_block=chunks_per_block)


def stage2_geometry(n: int, chunk_size: int, *, with_copy: bool,
                    chunks_per_block: "int | None" = None
                    ) -> geometry.StreamGeometry:
    """Stage-2 geometry (p+u in, p out, optional half writeback)."""
    return geometry.chunked_geometry(
        n, chunk_size,
        row_bytes=_LANES * (3 * 4 + (2 if with_copy else 0)),
        lanes=_LANES, chunks_per_block=chunks_per_block)


def _stage1_kernel(scalars_ref, decay_ref, bc1_ref, bc2_ref, g_ref, p_ref,
                   m_ref, v_ref, u_ref, out_m_ref, out_v_ref, *rest,
                   chunk_rows, chunks_per_block):
    beta1 = scalars_ref[0]
    beta2 = scalars_ref[1]
    eps = scalars_ref[2]
    inv_scale = scalars_ref[3]   # 1 / clip_factor (grads arrive descaled)
    i = pl.program_id(0)

    for j in range(chunks_per_block):
        # Per-tensor weight decay AND bias correction (1 - beta^step, or
        # 1.0) resolved through the chunk->tensor tables in SMEM — the
        # role of TensorListMetadata's block_to_tensor map
        # (multi_tensor_apply.cuh:17-24).  Bias correction is per tensor,
        # not a launch-wide scalar, because each param leaf carries its
        # own step count (reference fused_adam.py:119-125 state per
        # param).
        c = i * chunks_per_block + j
        decay = decay_ref[c]
        bc1 = bc1_ref[c]
        bc2 = bc2_ref[c]
        rows = slice(j * chunk_rows, (j + 1) * chunk_rows)

        g = g_ref[rows, :].astype(jnp.float32) * inv_scale
        p = p_ref[rows, :].astype(jnp.float32)
        m = beta1 * m_ref[rows, :].astype(jnp.float32) + (1.0 - beta1) * g
        v = beta2 * v_ref[rows, :].astype(jnp.float32) + (1.0 - beta2) * g * g
        update = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + decay * p
        u_ref[rows, :] = update
        out_m_ref[rows, :] = m
        out_v_ref[rows, :] = v
        if rest:  # fused ‖p‖²/‖update‖² per-chunk partials (with_norms)
            rest[0][c] = (p * p).sum()
            rest[1][c] = (update * update).sum()


@functools.partial(jax.jit, static_argnames=("chunk_size", "chunks_per_block",
                                             "with_norms"))
def packed_lamb_stage1(g: jax.Array, p: jax.Array, m: jax.Array,
                       v: jax.Array, per_chunk_decay: jax.Array, *,
                       beta1, beta2, eps, inv_scale, bc1, bc2,
                       chunk_size: int = LAMB_CHUNK,
                       chunks_per_block: "int | None" = None,
                       with_norms: bool = False):
    """Stage 1 over chunk-aligned flat fp32 buffers.

    ``per_chunk_decay``: fp32 ``(n_chunks,)`` — weight decay per chunk (i.e.
    per tensor, via ``AlignedMeta.chunk_ids``).  ``bc1``/``bc2`` may be
    scalars (all tensors at the same step) or ``(n_chunks,)`` arrays
    (per-tensor step counts).  Returns ``(update, new_m, new_v)`` flat
    fp32 buffers — plus ``(p_sumsq, u_sumsq)`` per-chunk ``(n_chunks,)``
    tables when ``with_norms`` (the fused inter-stage norm partials; a
    segment add over ``AlignedMeta.chunk_ids`` turns them into the
    per-tensor norms, identical partials to
    ``multi_tensor.per_tensor_sumsq_from_packed`` without re-reading the
    flat buffers).
    """
    n = g.shape[0]
    n_chunks = n // chunk_size
    chunk_rows = chunk_size // _LANES
    geom = stage1_geometry(n, chunk_size, chunks_per_block)
    slots = geom.grid * geom.chunks_per_block
    scalars = jnp.stack([
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(inv_scale, jnp.float32),
    ])
    decay = geometry.pad_table(per_chunk_decay.astype(jnp.float32), slots)
    bc1 = geometry.pad_table(
        jnp.broadcast_to(jnp.asarray(bc1, jnp.float32), (n_chunks,)), slots)
    bc2 = geometry.pad_table(
        jnp.broadcast_to(jnp.asarray(bc2, jnp.float32), (n_chunks,)), slots)

    def spec():
        return pl.BlockSpec((geom.block_rows, _LANES), lambda i: (i, 0))

    out_specs = [spec(), spec(), spec()]
    out_shape = [sds((n // _LANES, _LANES), jnp.float32, g, p, m, v)
                 for _ in range(3)]
    if with_norms:
        # SMEM partial tables are revisited whole each grid step — the
        # grid must stay sequential ("arbitrary"); without them every
        # step touches disjoint blocks and the grid pipelines as
        # "parallel".
        out_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        out_shape += [sds((slots,), jnp.float32, g, p, m, v)
                      for _ in range(2)]
        semantics = ("arbitrary",)
    else:
        semantics = ("parallel",)

    outs = pl.pallas_call(
        functools.partial(_stage1_kernel, chunk_rows=chunk_rows,
                          chunks_per_block=geom.chunks_per_block),
        grid=(geom.grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec(), spec(), spec(), spec(),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        name="lamb_stage1",
        interpret=not on_tpu(),
    )(scalars, decay, bc1, bc2, _view2d(g), _view2d(p), _view2d(m),
      _view2d(v))
    u, new_m, new_v = (o.reshape(-1) for o in outs[:3])
    if with_norms:
        return u, new_m, new_v, outs[3][:n_chunks], outs[4][:n_chunks]
    return u, new_m, new_v


def _stage2_kernel(ratio_ref, p_ref, u_ref, out_p_ref, *rest, chunk_rows,
                   chunks_per_block):
    i = pl.program_id(0)
    for j in range(chunks_per_block):
        # lr·trust ratio for this chunk's tensor
        ratio = ratio_ref[i * chunks_per_block + j]
        rows = slice(j * chunk_rows, (j + 1) * chunk_rows)
        p = p_ref[rows, :].astype(jnp.float32) - ratio * u_ref[rows, :]
        out_p_ref[rows, :] = p.astype(out_p_ref.dtype)
        if rest:  # optional half-precision param writeback
            rest[0][rows, :] = p.astype(rest[0].dtype)


@functools.partial(jax.jit, static_argnames=("chunk_size", "p_copy_dtype",
                                             "chunks_per_block"))
def packed_lamb_stage2(p: jax.Array, u: jax.Array,
                       per_chunk_ratio: jax.Array, *,
                       chunk_size: int = LAMB_CHUNK, p_copy_dtype=None,
                       chunks_per_block: "int | None" = None):
    """Stage 2: ``p ← p − ratio·update`` with the per-chunk (= per-tensor)
    trust ratio in SMEM.  Returns ``new_p`` (or ``(new_p, p_copy)``)."""
    n = p.shape[0]
    chunk_rows = chunk_size // _LANES
    geom = stage2_geometry(n, chunk_size, with_copy=p_copy_dtype is not None,
                           chunks_per_block=chunks_per_block)
    ratio = geometry.pad_table(per_chunk_ratio.astype(jnp.float32),
                       geom.grid * geom.chunks_per_block)

    def spec():
        return pl.BlockSpec((geom.block_rows, _LANES), lambda i: (i, 0))

    out_shape = [sds((n // _LANES, _LANES), p.dtype, p, u)]
    out_specs = [spec()]
    if p_copy_dtype is not None:
        out_shape.append(sds((n // _LANES, _LANES), p_copy_dtype, p, u))
        out_specs.append(spec())

    outs = pl.pallas_call(
        functools.partial(_stage2_kernel, chunk_rows=chunk_rows,
                          chunks_per_block=geom.chunks_per_block),
        grid=(geom.grid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            spec(), spec(),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="lamb_stage2",
        interpret=not on_tpu(),
    )(ratio, _view2d(p), _view2d(u))
    if p_copy_dtype is None:
        return outs[0].reshape(-1)
    return outs[0].reshape(-1), outs[1].reshape(-1)
