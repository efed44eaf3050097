"""Pallas TPU kernels for the packed multi-tensor ops.

TPU-native equivalents of ``csrc/multi_tensor_scale_kernel.cu`` and
``csrc/multi_tensor_axpby_kernel.cu``.  The CUDA kernels grid-stride over
(tensor, chunk) pairs packed into kernel argument space; here the tensor list
is pre-packed into one flat HBM buffer (see :mod:`apex_tpu.ops.packing`)
viewed as ``(padded/128, 128)``, and a sequential 1-D grid walks chunk-sized
row blocks.  Mosaic requires block dims divisible by (8, 128), so the chunk
size must be a multiple of 1024 (the caller falls back to the jnp path
otherwise — see :func:`chunk_supported`).

The overflow flag is a single SMEM cell accumulated across the (sequential)
TPU grid — the analog of the ``noop_flag`` the CUDA kernels set on any
non-finite input (``multi_tensor_scale_kernel.cu:57-76``).  All arithmetic
runs in fp32 regardless of storage dtype, matching the CUDA functors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds

_LANES = 128


def chunk_supported(chunk_size: int) -> bool:
    """Chunk must map to whole (8, 128) tiles."""
    return chunk_size % (8 * _LANES) == 0


def _view2d(flat: jax.Array):
    return flat.reshape(flat.shape[0] // _LANES, _LANES)


def _block(chunk_size: int):
    return (chunk_size // _LANES, _LANES)


def _scale_kernel(scale_ref, x_ref, out_ref, flag_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        flag_ref[0] = 0

    x = x_ref[...].astype(jnp.float32)
    out_ref[...] = (x * scale_ref[0]).astype(out_ref.dtype)
    nonfinite = jnp.logical_not(jnp.isfinite(x)).any()

    @pl.when(nonfinite)
    def _flag():
        flag_ref[0] = 1


@functools.partial(jax.jit, static_argnames=("chunk_size", "out_dtype"))
def packed_scale(flat: jax.Array, scale: jax.Array, chunk_size: int,
                 out_dtype) -> tuple[jax.Array, jax.Array]:
    """``out = flat * scale`` in one pass + non-finite flag.

    ``flat`` must be padded to a multiple of ``chunk_size`` (finite pad).
    Returns ``(out_flat, overflow_flag_int32)``.
    """
    n = flat.shape[0]
    n_chunks = n // chunk_size
    br = _block(chunk_size)
    out, flag = pl.pallas_call(
        _scale_kernel,
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(br, lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec(br, lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            sds((n // _LANES, _LANES), out_dtype, flat),
            sds((1,), jnp.int32, flat),
        ],
        # same-dtype scaling is in-place (reference semantics: the CUDA
        # multi-tensor ops write through their tensor lists) — each grid
        # step touches the same block index, so aliasing is hazard-free
        # and halves the HBM traffic; XLA copies if the input stays live
        input_output_aliases=(
            {1: 0} if jnp.dtype(out_dtype) == flat.dtype else {}),
        name="mt_scale",
        interpret=not on_tpu(),
    )(jnp.asarray(scale, jnp.float32).reshape(1), _view2d(flat))
    return out.reshape(-1), flag[0]


def _axpby_kernel(ab_ref, x_ref, y_ref, out_ref, flag_ref, *, arg_to_check):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        flag_ref[0] = 0

    x = x_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    out_ref[...] = (ab_ref[0] * x + ab_ref[1] * y).astype(out_ref.dtype)
    # arg_to_check policy from multi_tensor_axpby_kernel.cu:16-87:
    # -1 => check both, 0 => only x, 1 => only y.
    checks = []
    if arg_to_check in (-1, 0):
        checks.append(jnp.logical_not(jnp.isfinite(x)).any())
    if arg_to_check in (-1, 1):
        checks.append(jnp.logical_not(jnp.isfinite(y)).any())
    nonfinite = functools.reduce(jnp.logical_or, checks)

    @pl.when(nonfinite)
    def _flag():
        flag_ref[0] = 1


@functools.partial(jax.jit,
                   static_argnames=("chunk_size", "out_dtype", "arg_to_check"))
def packed_axpby(x_flat: jax.Array, y_flat: jax.Array, a: jax.Array,
                 b: jax.Array, chunk_size: int, out_dtype,
                 arg_to_check: int = -1) -> tuple[jax.Array, jax.Array]:
    """``out = a*x + b*y`` in one pass + non-finite flag on the selected arg."""
    n = x_flat.shape[0]
    n_chunks = n // chunk_size
    br = _block(chunk_size)
    ab = jnp.stack([jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)])
    out, flag = pl.pallas_call(
        functools.partial(_axpby_kernel, arg_to_check=arg_to_check),
        grid=(n_chunks,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(br, lambda i: (i, 0)),
            pl.BlockSpec(br, lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec(br, lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            sds((n // _LANES, _LANES), out_dtype, x_flat),
            sds((1,), jnp.int32, x_flat),
        ],
        # in-place onto x when dtypes match (see packed_scale)
        input_output_aliases=(
            {1: 0} if jnp.dtype(out_dtype) == x_flat.dtype else {}),
        name="mt_axpby",
        interpret=not on_tpu(),
    )(ab, _view2d(x_flat), _view2d(y_flat))
    return out.reshape(-1), flag[0]


def _sumsq_kernel(x_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[0] = 0.0

    x = x_ref[...].astype(jnp.float32)
    acc_ref[0] += (x * x).sum()


#: Bound on the per-chunk sumsq SMEM table (fp32 per chunk, 128 KiB against
#: the ~1 MiB SMEM budget); beyond it drivers fall back to per-leaf jnp
#: reductions rather than fail Mosaic compilation.
MAX_SUMSQ_CHUNKS = 32768


def _sumsq_per_chunk_kernel(x_ref, acc_ref):
    x = x_ref[...].astype(jnp.float32)
    acc_ref[pl.program_id(0)] = (x * x).sum()


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def packed_sumsq_per_chunk(flat: jax.Array, chunk_size: int) -> jax.Array:
    """Per-chunk sums of squares over a chunk-ALIGNED flat buffer — the
    per-tensor output half of ``multi_tensor_l2norm_kernel.cu:117-180``:
    with aligned packing every chunk belongs to one tensor, so a segment
    add over ``AlignedMeta.chunk_ids`` turns this ``(n_chunks,)`` table
    into per-tensor norms.  The table rides SMEM like the CUDA kernel's
    per-block ``output_per_tensor`` partials."""
    n = flat.shape[0]
    n_chunks = n // chunk_size
    br = _block(chunk_size)
    return pl.pallas_call(
        _sumsq_per_chunk_kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec(br, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=sds((n_chunks,), jnp.float32, flat),
        name="mt_sumsq_per_chunk",
        interpret=not on_tpu(),
    )(_view2d(flat))


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def packed_sumsq(flat: jax.Array, chunk_size: int) -> jax.Array:
    """Total sum of squares over the flat buffer — the two-kernel reduction
    of ``multi_tensor_l2norm_kernel.cu:16-180`` collapsed into one pass with
    an SMEM accumulator riding the sequential grid."""
    n = flat.shape[0]
    n_chunks = n // chunk_size
    br = _block(chunk_size)
    acc = pl.pallas_call(
        _sumsq_kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec(br, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=sds((1,), jnp.float32, flat),
        name="mt_sumsq",
        interpret=not on_tpu(),
    )(_view2d(flat))
    return acc[0]
