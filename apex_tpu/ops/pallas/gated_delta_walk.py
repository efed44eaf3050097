"""The gated delta rule's walk over the chunks, as two Mosaic kernels.

:mod:`apex_tpu.attention.gated_delta` computes what needs no state for
all chunks at once and then walks the state through them.  Here the walk
is one ``pallas_call`` forward and one backward, in place of a
``lax.scan`` of ``L / C`` trips each: grid ``(B x H / heads, N)``, the
head axis ``parallel``, the chunk axis sequential, and a
group of heads' state (forward) or its cotangent (backward) in a float32
VMEM scratch buffer from the first chunk to the last, so that between
two chunks nothing of it goes to HBM but the one copy a chunk the
backward pass keeps.  The backward kernel meets the chunks last to first
through its ``index_map``.

The operands arrive as the stateless part leaves them, ``(N, B, H, C,
d)``; ``B`` and ``H`` merge for nothing (the tiled last two axes do not
move), and a grid step's block is one chunk of ``heads`` heads, ``(heads,
C, d)``.

**The state is held transposed**, ``(d_v, d_k)``: the per-channel decay
``(d_k,)`` then runs along the lanes, as its block delivers it, and
multiplies the state as a row broadcast over sublanes; ``d_decay`` is a
sum over sublanes and leaves as a row.  Every product is the scan
route's, with the same operands, the same roundings (the state rounded
to the operand dtype as an operand, carried in float32; ``u`` rounded
before it is an operand) and float32 accumulation: ``S K`` products
contract the lanes of both operands, ``K^T u`` ones the rows of both.
The kept states therefore have the layout ``(N, B x H, d_v, d_k)``,
which only the two kernels see.

Off the chip both kernels run in interpret mode, so CPU tests execute
their bodies.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds as _sds
from apex_tpu.ops.pallas.geometry import DEFAULT_SCOPED_VMEM

_LANES = 128
#: heads a grid step, the first that divides ``B x H``: whole float32
#: sublane tiles, which the decay's ``(heads, d_k)`` block has to be.
#: Sixteen heads' chains of products interleave a little better than
#: eight's; more than one chunk a step gains nothing (PERF.md, PR 33,
#: has the table)
_HEADS = (16, 8)


def walk_geometry(bh: int, c: int, d_k: int, d_v: int,
                  dtype) -> "int | None":
    """Heads a grid step of the kernels takes, for chunks of ``c`` rows
    over ``bh`` heads of ``d_k`` / ``d_v`` channels in ``dtype``; ``None``
    where the shape is not theirs: widths that do not fill the 128 lanes
    (a narrower state would pad every tile), a chunk that is no whole
    number of the dtype's sublane tiles, or heads that come neither in
    groups of :data:`_HEADS` nor as one group under eight."""
    if d_k % _LANES or d_v % _LANES or c % (32 // jnp.dtype(dtype).itemsize):
        return None
    return next((heads for heads in _HEADS if bh % heads == 0),
                bh if bh < min(_HEADS) else None)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    """``a b``."""
    return _dot(a, b, (1, 0))


def _nt(a, b):
    """``a b^T``: the lanes of both."""
    return _dot(a, b, (1, 1))


def _tn(a, b):
    """``a^T b``: the rows of both."""
    return _dot(a, b, (0, 0))


def _fwd_kernel(w_v_ref, w_k_ref, q_ref, scores_ref, k_ref, decay_ref, o_ref,
                *rest, heads, keep_states):
    states_ref = rest[0] if keep_states else None
    tops_ref, state = rest[-2:]
    op = w_v_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    top = None
    for h in range(heads):
        s = state[h]
        if keep_states:
            states_ref[h] = s
        s_op = s.astype(op)
        u = (w_v_ref[h].astype(jnp.float32) - _nt(w_k_ref[h], s_op)).astype(op)
        o_ref[h] = (_nt(q_ref[h], s_op)
                    + _nn(scores_ref[h], u)).astype(o_ref.dtype)
        after = decay_ref[pl.ds(h, 1), :] * s + _tn(u, k_ref[h])
        state[h] = after
        after = jnp.abs(after)
        top = after if top is None else jnp.maximum(top, after)
    top = jnp.max(jnp.max(top, axis=0, keepdims=True), axis=1, keepdims=True)
    tops_ref[...] = jnp.broadcast_to(top, tops_ref.shape)


def _bwd_kernel(states_ref, d_o_ref, w_v_ref, w_k_ref, q_ref, scores_ref,
                k_ref, decay_ref, d_w_v_ref, d_w_k_ref, d_q_ref, d_scores_ref,
                d_k_ref, d_decay_ref, d_state, *, heads):
    op = w_v_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)

    for h in range(heads):
        s, d_after = states_ref[h], d_state[h]
        s_op, d_s = s.astype(op), d_after.astype(op)
        w_k, d_o = w_k_ref[h], d_o_ref[h]
        u = (w_v_ref[h].astype(jnp.float32) - _nt(w_k, s_op)).astype(op)
        d_u = (_tn(scores_ref[h], d_o) + _nt(k_ref[h], d_s)).astype(op)
        d_state[h] = (_tn(d_o, q_ref[h])
                      + decay_ref[pl.ds(h, 1), :] * d_after - _tn(d_u, w_k))
        d_w_v_ref[h] = d_u
        d_w_k_ref[h] = (-_nn(d_u, s_op)).astype(op)
        d_q_ref[h] = _nn(d_o, s_op).astype(op)
        d_scores_ref[h] = _nt(d_o, u).astype(op)
        d_k_ref[h] = _nn(u, d_s).astype(op)
        d_decay_ref[pl.ds(h, 1), :] = jnp.sum(d_after * s, axis=0,
                                              keepdims=True)


def _call(kernel, name: str, operands, out_shape, state, *, reverse: bool):
    """``kernel`` over the grid ``(groups of heads, N)`` of operands and
    results ``(N, B x H or groups, ...)``: a block is one chunk of a
    group's share of the second axis, the chunks met first to last or,
    with ``reverse``, last to first.  ``state`` is the shape of the
    float32 scratch buffer, ``(heads, d_v, d_k)``."""
    n, bh = operands[0].shape[:2]
    grid = (bh // state[0], n)

    def spec(x):
        zeros = (0,) * (x.ndim - 2)
        return pl.BlockSpec(
            (None, x.shape[1] // grid[0], *x.shape[2:]),
            lambda g, i: (n - 1 - i if reverse else i, g, *zeros))

    scratch = jax.ShapeDtypeStruct(state, jnp.float32)
    # double-buffered blocks, the scratch and a quarter: a scoped-VMEM
    # limit of its own where that passes three quarters of the default
    need = 5 * (2 * _nbytes([*operands, *out_shape]) // math.prod(grid)
                + _nbytes([scratch])) // 4
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[spec(x) for x in operands],
        out_specs=[spec(x) for x in out_shape], out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(scratch.shape, scratch.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=(need if need > 3 * DEFAULT_SCOPED_VMEM // 4
                              else None)),
        name=name, interpret=not on_tpu(),
    )(*operands)


def _nbytes(shapes) -> int:
    return sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in shapes)


def _flat(*xs):
    """``(N, B, H, ...)`` as ``(N, B x H, ...)``: the tiled axes stay."""
    return [x.reshape(x.shape[0], -1, *x.shape[3:]) for x in xs]


@functools.partial(jax.jit, static_argnames=("heads", "keep_states"))
def walk_fwd(w_v, w_k, q_in, scores, k_out, decay, *, heads: int,
             keep_states: bool):
    """The state through the chunks.  Operands ``(N, B, H, C, ...)`` as
    ``_without_state`` returns them; returns the outputs ``(N, B, H, C,
    d_v)``, per chunk the state it started from (``(N, B x H, d_v,
    d_k)`` float32, transposed; ``None`` without ``keep_states``) and per
    chunk the largest magnitude in the state it leaves.  One trace serves
    every layer of a model (``jax.jit``)."""
    operands = _flat(w_v, w_k, q_in, scores, k_out, decay)
    n, bh, _, d_v = operands[0].shape
    state = (heads, d_v, w_k.shape[-1])
    out_shape = [_sds(operands[0].shape, w_v.dtype, *operands)]
    if keep_states:
        out_shape.append(_sds((n, bh, *state[1:]), jnp.float32, *operands))
    # the largest magnitude a chunk and group of heads, over a row of lanes
    out_shape.append(_sds((n, bh // heads, 1, _LANES), jnp.float32,
                          *operands))
    outs = _call(
        functools.partial(_fwd_kernel, heads=heads, keep_states=keep_states),
        "kda_walk_fwd", operands, out_shape, state, reverse=False)
    return (outs[0].reshape(w_v.shape), outs[1] if keep_states else None,
            jnp.max(outs[-1][:, :, 0, 0], axis=1))


@functools.partial(jax.jit, static_argnames=("heads",))
def walk_bwd(states, d_outs, w_v, w_k, q_in, scores, k_out, decay, *,
             heads: int):
    """The cotangents of the walk's six operands from ``d_outs``, with
    the states :func:`walk_fwd` kept; ``U`` is computed again from them."""
    gradients_of = (w_v, w_k, q_in, scores, k_out, decay)
    operands = [states] + _flat(d_outs, *gradients_of)
    grads = _call(
        functools.partial(_bwd_kernel, heads=heads), "kda_walk_bwd", operands,
        [_sds(x.shape, x.dtype, *operands) for x in operands[2:]],
        (heads, *states.shape[2:]), reverse=True)
    return tuple(g.reshape(x.shape) for g, x in zip(grads, gradients_of))
