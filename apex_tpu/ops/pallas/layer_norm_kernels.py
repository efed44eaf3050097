"""Pallas TPU kernels for FusedLayerNorm forward/backward.

TPU-native equivalent of ``csrc/layer_norm_cuda_kernel.cu``:

- forward (``cuApplyLayerNorm``, ``:279-324``): per-row (μ, 1/σ) in fp32 —
  the Welford/Chan warp dance collapses to a VPU row reduction — then the
  elementwise normalize + affine, saving (mean, invvar) as residuals exactly
  like the CUDA host side (``layer_norm_cuda.cpp:132,154``).
- backward: the CUDA version splits γ/β grads into a two-stage reduction
  (``cuComputePartGradGammaBeta``/``cuComputeGradGammaBeta``, ``:404-522``)
  plus ``cuComputeGradInput`` (``:523-640``).  Here one kernel computes
  ``dx`` per row-block and *accumulates* ``dγ``/``dβ`` partials across the
  sequential TPU grid into a single output tile — the grid itself is the
  second reduction stage.

Forward geometry (round 6 retune) comes from the shared selector
(:mod:`apex_tpu.ops.pallas.geometry`): per-row statistics make the block
size numerics-free, so the forward streams the largest row block whose
double-buffered working set fits the VMEM budget, with ragged row counts
riding Mosaic's masked last block (no padding pass at all) and the grid
declared ``parallel`` so the pipeliner overlaps DMA with the row
reductions.  The BACKWARD keeps the fixed 128-row blocks: its dγ/dβ
partials accumulate across the sequential grid, so the block size sets
the summation ORDER — part of the bit-exact digest contract the L1
conformance tier pins — and its rows stay padded to the block multiple.
Feature dims not divisible by 128 — or wide enough that the backward's
fixed-row blocks no longer fit double-buffered in the VMEM budget —
fall back to the jnp path at the call site (`supported`).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds
from apex_tpu.ops.pallas import geometry

_BLOCK_ROWS = 128


def fwd_block_rows(n1: int, n2: int, x_dtype,
                   block_rows: "int | None" = None) -> int:
    """Forward row block from the shared selector: x in + y out + the
    8 B/row fp32 stats, 16-row multiples (the bf16 sublane floor)."""
    if block_rows:
        return block_rows
    xb = jnp.dtype(x_dtype).itemsize
    return geometry.select_block_rows(
        max(n1, 1), row_bytes=n2 * 2 * xb + 8, multiple_of=16)


def supported(n2: int, dtype=None) -> bool:
    """Whether the fused pallas path handles an ``n2``-wide feature dim.

    With a ``dtype`` the check is budget-aware: the BACKWARD streams
    x/dy/dx blocks of fixed ``_BLOCK_ROWS`` rows (the block size sets
    the dγ/dβ summation order — part of the bit-exact digest contract —
    so it cannot shrink with the feature dim), and a wide-enough row
    no longer fits double-buffered in VMEM.  Those shapes route to the
    jnp fallback instead of shipping a kernel the Pallas sanitizer
    rejects with ``pallas-vmem-overflow`` (fp32 caps near n2=5376 at
    the default budget, bf16 near n2=10752)."""
    if n2 % 128 != 0 or n2 > 16384:
        return False
    if dtype is None:
        return True
    streams = _bwd_stream_bytes(n2, jnp.dtype(dtype).itemsize)
    tables = 3 * 4 * n2 + 2 * 2 * 4 * _BLOCK_ROWS   # w/dw/db + mean/inv
    return streams + tables <= 2 * geometry.vmem_budget()


def _bwd_stream_bytes(n2: int, itemsize: int) -> int:
    return 2 * 3 * _BLOCK_ROWS * n2 * itemsize      # x, dy, dx x2 buffers


#: fp32 copies of one row block the backward body keeps live besides the
#: streamed blocks.  Mosaic counts them against the same scoped limit: it
#: refused the 4096-feature fp32 backward at 17.99 MiB, 12.4 MiB of
#: streams plus about 2.8 such blocks (v5e, PR 21).  Rounded up, with
#: room for the casts of half-precision inputs.
_BWD_BODY_BLOCKS = 6


def _bwd_vmem_limit(n2: int, itemsize: int) -> int:
    """Scoped-VMEM limit the backward asks Mosaic for: its streams plus
    the body's working set, never under the 16 MiB default."""
    body = _BWD_BODY_BLOCKS * _BLOCK_ROWS * n2 * 4
    return max(geometry.DEFAULT_SCOPED_VMEM,
               _bwd_stream_bytes(n2, itemsize) + body)


def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, inv_ref, *, eps,
                affine, rms=False):
    x = x_ref[...].astype(jnp.float32)
    if rms:
        # RMS mode (upstream FusedRMSNorm on the LayerNorm kernels): no
        # centring, no bias; the saved mean is nought, so the backward's
        # ``xhat`` is ``x * inv`` by the same expression.
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        xc = x
    else:
        mean = x.mean(axis=1, keepdims=True)
        xc = x - mean
    var = (xc * xc).mean(axis=1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = xc * inv
    if affine and rms:
        y = y * w_ref[...].astype(jnp.float32)
    elif affine:
        y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    inv_ref[...] = inv


def _bwd_kernel(dy_ref, x_ref, w_ref, mean_ref, inv_ref,
                dx_ref, dw_ref, db_ref, *, affine, rms=False):
    i = pl.program_id(0)
    dy = dy_ref[...].astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32)
    mean = mean_ref[...]
    inv = inv_ref[...]
    xhat = (x - mean) * inv
    if affine:
        wdy = dy * w_ref[...].astype(jnp.float32)
    else:
        wdy = dy
    # grad_input (cuComputeGradInput): dx = inv*(wdy - mean(wdy) - xhat*mean(wdy*xhat))
    if rms:
        # no mean was taken, so none comes back: dx = inv*(wdy - xhat*m2)
        m2 = (wdy * xhat).mean(axis=1, keepdims=True)
        dx_ref[...] = (inv * (wdy - xhat * m2)).astype(dx_ref.dtype)
    else:
        m1 = wdy.mean(axis=1, keepdims=True)
        m2 = (wdy * xhat).mean(axis=1, keepdims=True)
        dx_ref[...] = (inv * (wdy - m1 - xhat * m2)).astype(dx_ref.dtype)
    # γ/β partials accumulated across the sequential grid.
    part_dw = (dy * xhat).sum(axis=0, keepdims=True)
    part_db = dy.sum(axis=0, keepdims=True)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    dw_ref[...] += part_dw
    db_ref[...] += part_db


def _pad_rows(x: jax.Array, rows: int) -> jax.Array:
    pad = (-rows) % _BLOCK_ROWS
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x


@functools.partial(jax.jit,
                   static_argnames=("eps", "affine", "block_rows", "rms"))
def _forward(x2d, w, b, eps: float, affine: bool,
             block_rows: "int | None" = None, rms: bool = False):
    n1, n2 = x2d.shape
    br = fwd_block_rows(n1, n2, x2d.dtype, block_rows)
    grid = -(-n1 // br)   # ragged tail rides the masked last block
    w2 = (w if w is not None else jnp.ones((n2,), jnp.float32)).reshape(1, n2)
    b2 = (b if b is not None else jnp.zeros((n2,), jnp.float32)).reshape(1, n2)
    y, mean, inv = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, affine=affine, rms=rms),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((br, n2), lambda i: (i, 0)),
            pl.BlockSpec((1, n2), lambda i: (0, 0)),
            pl.BlockSpec((1, n2), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, n2), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
            pl.BlockSpec((br, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            sds((n1, n2), x2d.dtype, x2d),
            sds((n1, 1), jnp.float32, x2d),
            sds((n1, 1), jnp.float32, x2d),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="layer_norm_fwd",
        interpret=not on_tpu(),
    )(x2d, w2, b2)
    return y, mean, inv


@functools.partial(jax.jit, static_argnames=("affine", "rms"))
def _backward(dy, x2d, w, mean, inv, affine: bool, rms: bool = False):
    n1, n2 = x2d.shape
    dyp = _pad_rows(dy, n1)
    xp = _pad_rows(x2d, n1)
    meanp = _pad_rows(mean, n1)
    # Pad inv with ones (zeros are fine too: dy pad rows are zero so all
    # partials vanish; ones avoid 0*inf style surprises).
    invp = _pad_rows(inv, n1)
    rows = xp.shape[0]
    grid = rows // _BLOCK_ROWS
    w2 = (w if w is not None else jnp.ones((n2,), jnp.float32)).reshape(1, n2)
    dx, dw, db = pl.pallas_call(
        functools.partial(_bwd_kernel, affine=affine, rms=rms),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n2), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, n2), lambda i: (i, 0)),
            pl.BlockSpec((1, n2), lambda i: (0, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_BLOCK_ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BLOCK_ROWS, n2), lambda i: (i, 0)),
            pl.BlockSpec((1, n2), lambda i: (0, 0)),
            pl.BlockSpec((1, n2), lambda i: (0, 0)),
        ],
        out_shape=[
            sds((rows, n2), x2d.dtype, x2d, dy, w),
            sds((1, n2), jnp.float32, x2d, dy, w),
            sds((1, n2), jnp.float32, x2d, dy, w),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_vmem_limit(n2, x2d.dtype.itemsize)),
        name="layer_norm_bwd",
        interpret=not on_tpu(),
    )(dyp, xp, w2, meanp, invp)
    return dx[:n1], dw.reshape(n2), db.reshape(n2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln_affine(x2d, w, b, eps):
    y, _, _ = _forward(x2d, w, b, eps, affine=True)
    return y


def _ln_affine_fwd(x2d, w, b, eps):
    y, mean, inv = _forward(x2d, w, b, eps, affine=True)
    return y, (x2d, w, mean, inv)


def _ln_affine_bwd(eps, res, dy):
    x2d, w, mean, inv = res
    dx, dw, db = _backward(dy, x2d, w, mean, inv, affine=True)
    # Under shard_map a replicated weight meets rows that vary over mesh
    # axes (sequence parallelism): its cotangent is the sum over those
    # axes — what autodiff's transpose of the implicit broadcast does on
    # the jnp path, and what custom_vjp's type check demands here.
    rows_only = tuple(jax.typeof(dw).vma - jax.typeof(w).vma)
    if rows_only:
        dw, db = jax.lax.psum((dw, db), rows_only)
    return dx, dw.astype(w.dtype), db.astype(w.dtype)


_ln_affine.defvjp(_ln_affine_fwd, _ln_affine_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _ln_plain(x2d, eps):
    y, _, _ = _forward(x2d, None, None, eps, affine=False)
    return y


def _ln_plain_fwd(x2d, eps):
    y, mean, inv = _forward(x2d, None, None, eps, affine=False)
    return y, (x2d, mean, inv)


def _ln_plain_bwd(eps, res, dy):
    x2d, mean, inv = res
    dx, _, _ = _backward(dy, x2d, None, mean, inv, affine=False)
    return (dx,)


_ln_plain.defvjp(_ln_plain_fwd, _ln_plain_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_affine(x2d, w, eps):
    y, _, _ = _forward(x2d, w, None, eps, affine=True, rms=True)
    return y


def _rms_affine_fwd(x2d, w, eps):
    y, mean, inv = _forward(x2d, w, None, eps, affine=True, rms=True)
    return y, (x2d, w, mean, inv)


def _rms_affine_bwd(eps, res, dy):
    x2d, w, mean, inv = res
    dx, dw, _ = _backward(dy, x2d, w, mean, inv, affine=True, rms=True)
    rows_only = tuple(jax.typeof(dw).vma - jax.typeof(w).vma)
    if rows_only:
        dw = jax.lax.psum(dw, rows_only)
    return dx, dw.astype(w.dtype)


_rms_affine.defvjp(_rms_affine_fwd, _rms_affine_bwd)


def layer_norm_fwd_vjp(x2d: jax.Array, w: Optional[jax.Array],
                       b: Optional[jax.Array], eps: float) -> jax.Array:
    """Differentiable fused layer norm on a (n1, n2) view."""
    if w is not None:
        return _ln_affine(x2d, w, b, eps)
    return _ln_plain(x2d, eps)


def rms_norm_fwd_vjp(x2d: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Differentiable fused RMS norm on a (n1, n2) view: the LayerNorm
    kernels in their ``rms`` mode (no mean, no bias), as upstream keeps
    ``FusedRMSNorm`` beside ``FusedLayerNorm`` on one set of kernels."""
    return _rms_affine(x2d, w, eps)
