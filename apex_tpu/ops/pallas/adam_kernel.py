"""Pallas TPU kernel for the fused Adam update.

TPU-native equivalent of ``csrc/fused_adam_cuda_kernel.cu:20-56``: one pass
over packed flat (p, m, v, g) buffers doing descale → moment update →
(eps-in/out-sqrt) → weight update → half-precision param writeback.  The
CUDA kernel grid-strides with ILP=4; here the flat buffers are viewed as
``(rows, LANES)`` and a sequential grid walks row-blocks, each block one VMEM
tile per operand.  ``step_size`` (with bias correction precomputed outside,
as in ``fused_adam_cuda_kernel.cu:83-91``), ``scale``, and ``weight_decay``
arrive as SMEM scalars so a changing loss scale never triggers recompilation.

Memory movement (round 6 retune): the row-block geometry comes from the
shared selector (:mod:`apex_tpu.ops.pallas.geometry`) instead of the old
8/32-row special cases — the largest ladder block whose double-buffered
working set across all 8 operand/result streams fits the VMEM budget
(measured +23% for 8→32 rows on v5e; the selector typically lands on
128).  Ragged row counts no longer drop to the 8-row tile floor: Mosaic
masks the out-of-bounds tail of the last grid block, so the grid is a
plain ceiling division.  The grid is declared ``parallel`` (every step
touches disjoint blocks) so the pipeliner overlaps the next block's DMA
with this block's compute.  ``donate=True`` adds ``input_output_aliases``
on the (p, m, v) streams — in-place updates that halve the buffers XLA
must hold live — but it is OPT-IN: the production train step wraps the
optimizer in the loss-scale skip-``cond`` whose untaken branch returns
the old state, keeping p/m/v live across the update; XLA then inserts
full copies and the "win" inverts (measured on chip: BERT-large 105 →
54 seq/s with aliased LAMB kernels).  Donate only from drivers whose
inputs are genuinely dead at the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds
from apex_tpu.ops.packing import STREAM_LANES, STREAM_TILE_ROWS
from apex_tpu.ops.pallas import geometry
from apex_tpu.ops.pallas.multi_tensor_kernels import _LANES, _view2d

#: Lane width of the packed-Adam flat view (wider than the 128-lane chunk
#: view: the flat path has no per-chunk tables to respect) — THE packing
#: constants, so ``packing.streaming_pad`` and this kernel's alignment
#: assert can never desync.
_ADAM_LANES = STREAM_LANES

#: Flat buffers must be padded to a multiple of this: one (8, 1024) fp32
#: tile — the only alignment the retuned kernel still requires (ragged
#: row counts ride the masked last grid block).
ADAM_PAD = STREAM_TILE_ROWS * STREAM_LANES


def adam_geometry(n: int, *, with_copy: bool,
                  block_rows: "int | None" = None) -> geometry.StreamGeometry:
    """Resolved streaming geometry for :func:`packed_adam` at ``n``
    elements — THE function the kernel and its tests share."""
    rows = n // _ADAM_LANES
    # 4 fp32 reads (p, m, v, g) + 3 fp32 writes + optional half writeback
    row_bytes = _ADAM_LANES * (7 * 4 + (2 if with_copy else 0))
    br = block_rows or geometry.select_block_rows(rows, row_bytes)
    return geometry.StreamGeometry(block_rows=br, lanes=_ADAM_LANES,
                                   grid=-(-rows // br))


def _adam_kernel(scalars_ref, p_ref, m_ref, v_ref, g_ref,
                 out_p_ref, out_m_ref, out_v_ref, *rest, eps_mode):
    step_size = scalars_ref[0]
    beta1 = scalars_ref[1]
    beta2 = scalars_ref[2]
    eps = scalars_ref[3]
    inv_scale = scalars_ref[4]
    weight_decay = scalars_ref[5]

    p = p_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * inv_scale
    g = g + weight_decay * p
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    if eps_mode == 1:  # eps inside sqrt
        denom = jnp.sqrt(v + eps)
    else:
        denom = jnp.sqrt(v) + eps
    p = p - step_size * m / denom
    out_p_ref[...] = p.astype(out_p_ref.dtype)
    out_m_ref[...] = m.astype(out_m_ref.dtype)
    out_v_ref[...] = v.astype(out_v_ref.dtype)
    if rest:  # optional half p_copy (the fused fp16 writeback)
        rest[0][...] = p.astype(rest[0].dtype)


def _adam_tree_kernel(scalars_ref, step_ref, p_ref, m_ref, v_ref, g_ref,
                      out_p_ref, out_m_ref, out_v_ref, *, eps_mode,
                      with_decay, chunk_rows, chunks_per_block):
    """Whole-tree variant: per-TENSOR step size (bias correction differs per
    leaf under per-leaf step counts) resolved through the chunk->tensor
    table in SMEM, like the LAMB kernels' decay/bc tables.  One grid step
    streams ``chunks_per_block`` chunks (statically unrolled so every
    chunk keeps its own table scalar); the step table is padded to the
    grid outside, so the masked tail of a ragged last block reads a real
    (dead) slot instead of running off the table.

    ``1-beta`` arrives precomputed (not derived from the rounded f32 betas
    in-kernel) and the descale is a true division, so the element math is
    bit-identical to the jnp reference path — the L1 conformance contract.
    """
    beta1 = scalars_ref[0]
    beta2 = scalars_ref[1]
    om_beta1 = scalars_ref[2]    # 1 - beta1, rounded from the exact value
    om_beta2 = scalars_ref[3]
    eps = scalars_ref[4]
    scale = scalars_ref[5]
    weight_decay = scalars_ref[6]
    i = pl.program_id(0)

    for j in range(chunks_per_block):
        step_size = step_ref[i * chunks_per_block + j]
        rows = slice(j * chunk_rows, (j + 1) * chunk_rows)

        p = p_ref[rows, :].astype(jnp.float32)
        m = m_ref[rows, :].astype(jnp.float32)
        v = v_ref[rows, :].astype(jnp.float32)
        g = g_ref[rows, :].astype(jnp.float32) / scale
        if with_decay:  # trace-time guard, mirroring the jnp path's
            g = g + weight_decay * p  # `if weight_decay:` (keeps -0.0 grads)
        m = beta1 * m + om_beta1 * g
        v = beta2 * v + om_beta2 * g * g
        if eps_mode == 1:
            denom = jnp.sqrt(v + eps)
        else:
            denom = jnp.sqrt(v) + eps
        out_p_ref[rows, :] = p - step_size * m / denom
        out_m_ref[rows, :] = m
        out_v_ref[rows, :] = v


def adam_tree_geometry(n: int, chunk_size: int,
                       chunks_per_block: "int | None" = None
                       ) -> geometry.StreamGeometry:
    """Geometry for :func:`packed_adam_tree`: K aligned chunks per grid
    step (7 fp32 streams over the 128-lane chunk view)."""
    return geometry.chunked_geometry(n, chunk_size,
                                     row_bytes=_LANES * 4 * 7,
                                     lanes=_LANES,
                                     chunks_per_block=chunks_per_block)


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "weight_decay", "eps_mode",
                     "chunk_size", "chunks_per_block"))
def packed_adam_tree(p: jax.Array, m: jax.Array, v: jax.Array, g: jax.Array,
                     per_chunk_step_size: jax.Array, *, beta1: float,
                     beta2: float, eps: float, scale, weight_decay: float,
                     eps_mode: int, chunk_size: int,
                     chunks_per_block: "int | None" = None):
    """One fused Adam pass over a whole chunk-ALIGNED packed tree — the
    TPU analog of the reference driving ``fused_adam_cuda.adam`` through
    ``multi_tensor_apply`` (``apex/optimizers/fused_adam.py:126-147``):
    hundreds of param leaves, one kernel launch, per-tensor bias
    correction riding the chunk→tensor SMEM table.

    All four buffers fp32, aligned to ``chunk_size`` (zero padding is
    harmless: 0-grads leave 0-moments and 0-params at 0 up to
    weight-decay, and padded lanes are sliced away by unpack).  Returns
    ``(new_p, new_m, new_v)`` flat fp32 buffers.
    """
    n = p.shape[0]
    geom = adam_tree_geometry(n, chunk_size, chunks_per_block)
    chunk_rows = chunk_size // _LANES
    scalars = jnp.stack([
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(1.0 - beta1, jnp.float32),  # exact, then rounded once
        jnp.asarray(1.0 - beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        jnp.asarray(scale, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
    ])
    steps = geometry.pad_table(per_chunk_step_size.astype(jnp.float32),
                               geom.grid * geom.chunks_per_block)

    def spec():
        return pl.BlockSpec((geom.block_rows, _LANES), lambda i: (i, 0))

    outs = pl.pallas_call(
        functools.partial(_adam_tree_kernel, eps_mode=eps_mode,
                          with_decay=bool(weight_decay),
                          chunk_rows=chunk_rows,
                          chunks_per_block=geom.chunks_per_block),
        grid=(geom.grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec(), spec(), spec(), spec()],
        out_specs=[spec(), spec(), spec()],
        out_shape=[sds((n // _LANES, _LANES), jnp.float32, p, m, v, g)
                   for _ in range(3)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="adam_tree",
        interpret=not on_tpu(),
    )(scalars, steps, _view2d(p), _view2d(m), _view2d(v), _view2d(g))
    return tuple(o.reshape(-1) for o in outs)


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "weight_decay", "eps_mode",
                     "p_copy_dtype", "block_rows", "donate"))
def packed_adam(p: jax.Array, m: jax.Array, v: jax.Array, g: jax.Array,
                *, step_size, beta1: float, beta2: float, eps: float,
                scale, weight_decay: float, eps_mode: int,
                p_copy_dtype=None, block_rows: "int | None" = None,
                donate: bool = False):
    """Fused Adam over flat buffers padded to a multiple of ``ADAM_PAD``.

    ``block_rows`` overrides the selector's row-block (the autotune
    sweep axis); ``donate=True`` aliases (p, m, v) in-place — see the
    module docstring for the production caveat before enabling it.
    Returns ``(new_p, new_m, new_v)`` or ``(..., p_copy)`` when
    ``p_copy_dtype`` is set.
    """
    n = p.shape[0]
    assert n % ADAM_PAD == 0, f"pad flat buffers to {ADAM_PAD} (got {n})"
    geom = adam_geometry(n, with_copy=p_copy_dtype is not None,
                         block_rows=block_rows)
    lanes = geom.lanes
    rows = n // lanes

    scalars = jnp.stack([
        jnp.asarray(step_size, jnp.float32),
        jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32),
        jnp.asarray(eps, jnp.float32),
        1.0 / jnp.asarray(scale, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
    ])

    def spec():
        return pl.BlockSpec((geom.block_rows, lanes), lambda i: (i, 0))

    out_shape = [
        sds((rows, lanes), p.dtype, p, g, m, v),
        sds((rows, lanes), m.dtype, p, g, m, v),
        sds((rows, lanes), v.dtype, p, g, m, v),
    ]
    out_specs = [spec(), spec(), spec()]
    if p_copy_dtype is not None:
        out_shape.append(sds((rows, lanes), p_copy_dtype, p, g, m, v))
        out_specs.append(spec())

    outs = pl.pallas_call(
        functools.partial(_adam_kernel, eps_mode=eps_mode),
        grid=(geom.grid,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  spec(), spec(), spec(), spec()],
        out_specs=out_specs,
        out_shape=out_shape,
        # every grid step touches disjoint row blocks, so the in-place
        # aliasing (donate) is hazard-free under either semantics
        input_output_aliases={1: 0, 2: 1, 3: 2} if donate else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="adam",
        interpret=not on_tpu(),
    )(scalars, *(t.reshape(rows, lanes) for t in (p, m, v, g)))
    return tuple(o.reshape(-1) for o in outs)
