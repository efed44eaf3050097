"""Parked: flat-packed gradient finite check (measured-negative).

The idea: ``apex_tpu.amp.scaler.all_finite`` lowers to ~one
reduce-to-scalar fusion per gradient leaf (~50 for gpt-small), and a
profile of the d=64 train step shows an ``is-finite_reduce_fusion.*`` +
``cond`` bucket worth ~16% of device time (a round-5 decomposition).
This module packs the leaves per dtype into one flat buffer and checks
them with ONE Pallas pass (the read-only half of ``_scale_kernel``'s
in-pass overflow flag, reference ``multi_tensor_scale_kernel.cu:57-71``)
streaming at the ~500 GB/s of ``packed_sumsq``.

Why it loses (same-day v5e A/B, B8 L2048 amp-O2 train step):

- per-leaf baseline:      gpt-small 107.4K tok/s, tpu-heads 138.2K
- flat-packed (this):     gpt-small 105.5K (−1.8%), tpu-heads 133.3K
  (−3.5%)
- no check at all:        tpu-heads 141.1K (+2.1%)

The profiled 16% bucket is an attribution artifact: XLA **fuses the
per-leaf is-finite reduction into the gradient fusions that read the
grads anyway** (the fusion is *named* after its reduce root but carries
the unscale/cast traffic too), so the per-leaf checks' true marginal
cost is only ~2.1% — and the packed path's explicit concat copy
(one extra write+read of the full gradient set that fuses into nothing)
costs more than that.  The remaining ~2.1% could only be recovered by
folding the check into the optimizer's existing flat-pack (which lives
inside the skip-``cond`` whose predicate the check feeds — a chicken-
and-egg restructuring), not by a standalone pass.

Kept numerics-pinned per the experimental-namespace convention; nothing
imports this on a default path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds
from apex_tpu.ops.pallas.multi_tensor_kernels import _block, _view2d

#: large blocks keep the pass bandwidth-bound (the LAMB-size lesson)
FINITE_CHUNK = 2048 * 32


def _nonfinite_kernel(x_ref, flag_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        flag_ref[0] = 0

    nonfinite = jnp.logical_not(
        jnp.isfinite(x_ref[...].astype(jnp.float32))).any()

    @pl.when(nonfinite)
    def _flag():
        flag_ref[0] = 1


@functools.partial(jax.jit, static_argnames=("chunk_size",))
def packed_nonfinite(flat: jax.Array,
                     chunk_size: int = FINITE_CHUNK) -> jax.Array:
    """int32 flag: 1 iff ANY element of the flat buffer is inf/nan.
    ``flat`` must be padded to a multiple of ``chunk_size`` (finite
    pad) — a ragged tail would silently go unchecked."""
    n = flat.shape[0]
    assert n % chunk_size == 0, \
        f"pad flat buffers to {chunk_size} (got {n})"
    n_chunks = n // chunk_size
    br = _block(chunk_size)
    flag = pl.pallas_call(
        _nonfinite_kernel,
        grid=(n_chunks,),
        in_specs=[pl.BlockSpec(br, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=sds((1,), jnp.int32, flat),
        interpret=not on_tpu(),
    )(_view2d(flat))
    return flag[0]


def all_finite_packed(tree) -> jax.Array:
    """Drop-in for ``amp.scaler.all_finite`` over the packed kernel —
    the parked variant the A/B above measured against."""
    leaves = [jnp.asarray(leaf) for leaf in jax.tree.leaves(tree)
              if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)]
    if not leaves:
        return jnp.asarray(True)
    by_dtype: dict = {}
    for leaf in leaves:
        # Mosaic has no f16 vector type ("Unsupported type in mosaic
        # dialect: 'f16'", found on-chip); the f32 upcast is exact and
        # preserves inf/nan, so f16 leaves join the f32 group
        if leaf.dtype == jnp.float16:
            leaf = leaf.astype(jnp.float32)
        by_dtype.setdefault(leaf.dtype, []).append(leaf.ravel())
    flags = []
    for flats in by_dtype.values():
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        pad = -flat.shape[0] % FINITE_CHUNK
        if pad:
            flat = jnp.pad(flat, (0, pad))   # zero pad: finite
        flags.append(packed_nonfinite(flat, FINITE_CHUNK))
    nonfinite = flags[0] if len(flags) == 1 else jnp.stack(flags).max()
    return nonfinite == 0
