"""The expert walk's combine, as one Mosaic kernel: ``moe_combine``.

:mod:`apex_tpu.parallel.moe` runs the experts held here on a window of
``R`` rows of the sorted buffer, then adds to every token's float32 row
what its pairs in the window bring::

    acc[t] += sum_j inside[j, t] * w[j, t] * f32(src[at[j, t]])

over its ``k`` choices ``j`` (pairs numbered choice-major, ``j * T +
t``).  Forward ``src`` is the window's expert output and ``w`` the
routing weights; backward ``src`` is the window rows' cotangent and ``w``
is 1 (the cotangent's way home).  Written in XLA this gathers a ``(k T,
d)`` array, converts it to float32 and reduces it over ``k``, though only
the pairs held in the window carry anything.  Here only those rows are
read.

**The pairs held.**  Outside the kernel the ``(k, T)`` flags are read
token by token (``t * k + j``) and the pairs inside the window listed in
that order by one sort, which carries each pair's row of ``src`` and
weight along; each gets a code: its token within its block of tokens,
whether it is its token's first pair and whether its last.  Each block
of tokens owns a stretch of the list, whose start the kernel gets with
the list, in SMEM (scalar prefetch).

**The kernel** walks the blocks of ``block`` tokens in order (grid
``(T / block,)``, sequential): the block's float32 accumulator comes in
and goes out through the pipeline (aliased, so a token with no pair in
the window keeps its row), and the block's pairs are read one by one.  A
DMA moves whole sublane tiles of an HBM array, so a pair's row arrives
inside the tile of :data:`_GROUP` rows that holds it, into one slot of a
ring of :data:`_RING`; the copies run that many pairs ahead of the sums,
across blocks, and a slot is refilled once its row has been taken.  The
row is taken by a dynamic sublane index from a float32 copy of the tile
(Mosaic indexes packed bfloat16 sublanes only at whole tiles).

**Rounding** as the XLA form's: each token's terms ``w * f32(row)`` are
summed in float32 from 0 in the order ``j = 0 .. k - 1`` and the sum is
then added to the accumulator; a pair outside the window adds nothing.
(In interpret mode XLA:CPU may contract a multiply and the add after it
into one fused operation, so the CPU can differ from the XLA form by a
rounding of one term.)

Off the chip the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds as _sds

_LANES = 128
#: rows of one DMA: the sublane tile of a 32-bit HBM array, and of a
#: bfloat16 one (its rows packed in pairs)
_GROUP = 8
#: pairs whose rows are in flight at once
_RING = 16
#: tokens a grid step, the first that divides ``T``
_BLOCKS = (256, 128, 64, 32, 16, 8)
#: SMEM bytes the pair lists may take (the v5e has 1 MiB)
_LISTS_SMEM = 512 * 1024
_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def combine_geometry(tokens: int, width: int, rows: int, pairs: int,
                     dtype) -> "int | None":
    """Tokens a grid step of :func:`moe_combine` takes, for ``tokens``
    rows of ``width`` lanes summing ``pairs`` pairs over a window of
    ``rows`` rows of ``dtype``; ``None`` where the shape is not the
    kernel's: a width that does not fill the 128 lanes, a dtype other
    than float32 or bfloat16, a window of no whole number of tiles, more
    listed pairs (at most ``min(rows, pairs)``) than SMEM holds, or no
    block of tokens that divides ``tokens``."""
    if (jnp.dtype(dtype) not in _DTYPES or width % _LANES or rows % _GROUP
            or pairs % tokens or 3 * 4 * min(rows, pairs) > _LISTS_SMEM):
        return None
    return next((b for b in _BLOCKS if tokens % b == 0), None)


def _kernel(starts, rows, codes, weights, src_hbm, acc_ref, out_ref, stage,
            tile, sem, *, n_blocks, weighted):
    b = pl.program_id(0)
    total = starts[n_blocks]

    def copy(p):
        slot = p % _RING
        first = pl.multiple_of(rows[p] // _GROUP * _GROUP, _GROUP)
        return pltpu.make_async_copy(src_hbm.at[pl.ds(first, _GROUP)],
                                     stage.at[slot], sem.at[slot])

    @pl.when(b == 0)
    def _():
        for p in range(_RING):
            @pl.when(p < total)
            def _():
                copy(p).start()

    out_ref[...] = acc_ref[...]

    def pair(p, s):
        copy(p).wait()
        slot, at = p % _RING, rows[p] % _GROUP
        if stage.dtype == jnp.float32:
            row = stage[slot, pl.ds(at, 1), :]
        else:
            tile[...] = stage[slot].astype(jnp.float32)
            row = tile[pl.ds(at, 1), :]

        @pl.when(p + _RING < total)
        def _():
            copy(p + _RING).start()

        code = codes[p]
        term = weights[p] * row if weighted else row
        s = jnp.where(code & 2 != 0, 0.0, s) + term

        @pl.when(code & 1 != 0)
        def _():
            t = code >> 2
            out_ref[pl.ds(t, 1), :] = out_ref[pl.ds(t, 1), :] + s
        return s

    lax.fori_loop(starts[b], starts[b + 1], pair,
                  jnp.zeros((1, out_ref.shape[1]), jnp.float32))


def _pair_lists(at, inside, weights, t: int, block: int, cap: int):
    """The pairs inside the window, token by token: where each block's
    stretch starts, each pair's row of ``src``, its code (token within
    its block, first of its token, last of it) and its weight.  One sort
    of the ``k T`` pairs puts those inside first, in token order, and
    carries their rows and weights along."""
    n = at.shape[0]
    k = n // t

    def by_token(a):
        return a.reshape(k, t).T.reshape(-1)

    held = by_token(inside)
    flat = jnp.arange(n, dtype=jnp.int32)
    keys, rows, *weights = lax.sort(
        [jnp.where(held, flat, n + flat), by_token(at).astype(jnp.int32)]
        + ([] if weights is None else [by_token(weights)]), num_keys=1)
    token = keys[:cap] % n // k
    i = jnp.arange(cap, dtype=jnp.int32)
    count = jnp.sum(held, dtype=jnp.int32)
    first = (i == 0) | (token != jnp.roll(token, 1))
    last = (i == count - 1) | (token != jnp.roll(token, -1))
    codes = token % block * 4 + first * 2 + last
    per_block = jnp.sum(held.reshape(t // block, block * k), axis=1,
                        dtype=jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(per_block, dtype=jnp.int32)])
    return (starts, rows[:cap], codes.astype(jnp.int32),
            weights[0][:cap].astype(jnp.float32) if weights
            else jnp.zeros((1,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("block",))
def moe_combine(acc, src, at, inside, weights=None, *, block: int):
    """``acc + sum_j inside * w * f32(src[at])`` per token: ``acc`` ``(T,
    d)`` float32, ``src`` ``(R, d)`` float32 or bfloat16, ``at`` and
    ``inside`` ``(k T,)`` choice-major (each pair inside the window on a
    row of its own), ``weights`` ``(k T,)`` or ``None`` for 1; ``block``
    from :func:`combine_geometry`.  One trace serves every layer of a
    model (``jax.jit``)."""
    t, d = acc.shape
    cap = min(src.shape[0], at.shape[0])
    lists = _pair_lists(at, inside, weights, t, block, cap)
    n_blocks = t // block
    spec = pl.BlockSpec((block, d), lambda b, *_: (b, 0))
    return pl.pallas_call(
        functools.partial(_kernel, n_blocks=n_blocks,
                          weighted=weights is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(lists), grid=(n_blocks,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM), spec],
            out_specs=spec,
            scratch_shapes=[pltpu.VMEM((_RING, _GROUP, d), src.dtype),
                            pltpu.VMEM((_GROUP, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((_RING,))]),
        out_shape=_sds((t, d), jnp.float32, acc, src, at, inside,
                       *([] if weights is None else [weights])),
        input_output_aliases={len(lists) + 1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="moe_combine", interpret=not on_tpu(),
    )(*lists, src, acc)
