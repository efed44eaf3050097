"""What the gated delta rule's chunks need no state for, as two Mosaic
kernels.

:mod:`apex_tpu.attention.gated_delta` splits the chunkwise rule into what
needs no state and the walk of the state through the chunks
(:mod:`apex_tpu.ops.pallas.gated_delta_walk`).  Here the first part is
one ``pallas_call`` forward, ``kda_chunk_fwd``, and one backward,
``kda_chunk_bwd``: grid ``(N, B x H / heads)``, both axes ``parallel``
(the chunks do not depend on one another here), a block one chunk of
up to eight heads in the ``(N, B x H, C, d)`` layout the walk reads,
every product and elementwise operation batched over the block's heads,
so that their chains of small products run side by side.  Per chunk and
head, from ``q``, ``k``, ``v`` ``(C, d)`` in the operand dtype and ``g
(C, d)``, ``beta (C,)`` in float32, the forward kernel forms in VMEM,
with ``G`` the running sum of ``g``::

    A_rs  = sum_c k_rc k_sc exp(G_rc - G_sc)      s <  r
    Aq_rs = sum_c q_rc k_sc exp(G_rc - G_sc)      s <= r
    T     = (I + diag(beta) A)^-1
    W_v   = T diag(beta) V,   W_k = T diag(beta) (K * exp(G))

and writes ``W_v``, ``W_k``, ``Q * exp(G)``, ``Aq`` and ``K * exp(G_C -
G)`` in the operand dtype and ``exp(G_C)`` in float32, as
``gated_delta._without_state`` returns them.  ``A`` and ``Aq`` are built
by ``_without_state``'s halving levels (every exponent ``<= 0``), ``T``
bottom up by its block forward substitution.  A running sum is ``log2 C``
shifted adds of rows (``pltpu.roll``), in float32; a level's reference
row, the first row of the upper half of each ``2b`` block, is taken from
``G`` by selection, so ``G_r - G_ref`` is a difference of ``G``'s own
values.

**Precision** as ``_without_state``'s: the levels' products take
operands in the operand dtype and accumulate in float32; the inverse and
the products it enters are float32 in three bfloat16 passes, each
float32 operand split into a high and a low bfloat16 part (Mosaic has
one pass or six).

**Backward**, derived by hand; the residuals are the five inputs, and
the kernel forms ``G``, ``A`` and ``T`` again.  With ``V' = diag(beta)
V``, ``K' = diag(beta) (K * exp(G))`` and ``M = diag(beta) A``::

    dT  = dW_v V'^T + dW_k K'^T        dV' = T^T dW_v,   dK' = T^T dW_k
    dM  = -(T^T dT T^T), strictly lower;   dA = diag(beta) dM
    dbeta = rowsum(dM * A) + rowsum(dV' * V) + rowsum(dK' * K exp(G))

and through the scores, level by level with the masked cotangent
``[dA; dAq]`` in place of the product (in the operand dtype)::

    dq_r  += sum_s dAq_rs k_s E_rs         dk_r += sum_s dA_rs k_s E_rs
    dk_s  += sum_r (dAq_rs q_r + dA_rs k_r) E_rs
    dG    += q * (the first) + k * (the second) - k * (the third)

with ``E_rs = exp(G_r - G_s)`` split at the level's reference, so again
no exponent is positive; the diagonal of ``Aq`` adds ``dAq_rr k_r`` to
``dq`` and ``dAq_rr q_r`` to ``dk`` and nothing to ``dG``.  ``Q exp(G)``,
``K exp(G_C - G)``, ``K'`` and ``exp(G_C)`` add their elementwise parts;
``dg`` is the reverse running sum of ``dG``.

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

_F32 = jnp.float32
#: heads a grid step takes at most, all at once: every product and every
#: elementwise operation of a chunk is batched over them, so that their
#: chains of small products (the triangular inverse's ten, one after
#: another) run side by side
_HEADS = 8
#: float32 values of ``(C, d)`` the body holds a head, about: for the
#: scoped-VMEM estimate
_BODY = 40
_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)


def _dot(a, b, contract):
    """Per head ``a b`` (``_NN``), ``a b^T`` (``_NT``) or ``a^T b``
    (``_TN``) of ``(heads, ., .)`` operands, accumulated in float32."""
    return jax.lax.dot_general(
        a, b, (((contract[0] + 1,), (contract[1] + 1,)), ((0,), (0,))),
        preferred_element_type=_F32)


def _halves(x):
    """``x`` as a high and a low bfloat16 part; no low part where ``x``
    is bfloat16 already."""
    if x.dtype == jnp.bfloat16:
        return x, None
    high = x.astype(jnp.bfloat16)
    return high, (x - high.astype(_F32)).astype(jnp.bfloat16)


def _dot3(a, b, contract):
    """A float32 product in three bfloat16 passes, ``highs + high x low +
    low x high`` (about 2^-17 a term): ``Precision.HIGH``."""
    (a_hi, a_lo), (b_hi, b_lo) = _halves(a), _halves(b)
    out = _dot(a_hi, b_hi, contract)
    for x, y in ((a_hi, b_lo), (a_lo, b_hi)):
        if x is not None and y is not None:
            out = out + _dot(x, y, contract)
    return out


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _shift(x, k: int):
    """Row ``r`` of the result is row ``r - k`` of ``x`` (mod its rows)."""
    return pltpu.roll(x, k % x.shape[1], 1)


def _running_sum(x, reverse: bool = False):
    """Per row the sum of the rows up to it (``reverse``: from it on), in
    ``log2`` rows shifted adds."""
    c = x.shape[1]
    at = _iota(x.shape, 1)
    step = 1
    while step < c:
        if reverse:
            x = x + jnp.where(at < c - step, _shift(x, -step), 0.0)
        else:
            x = x + jnp.where(at >= step, _shift(x, step), 0.0)
        step *= 2
    return x


def _pairs(shape):
    """Row and column of every entry of ``(heads, C, C)``."""
    return _iota(shape, 1), _iota(shape, 2)


def _corners(r, s, b: int):
    """Which ``(r, s)`` pair a row of the upper half of a ``2b`` block
    with a row of its lower half."""
    return ((r ^ s) < 2 * b) & ((r & b) != 0) & ((s & b) == 0)


def _levels(big):
    """Per level ``b`` (1, 2, .. ``C / 2``) ``(corners (heads, C, C),
    decay (heads, C, d))``: rows of an upper half decay from the
    reference down to themselves, ``exp(G - G_ref)``, rows of a lower
    half from themselves down to it, ``exp(G_ref - G)``."""
    h, c, _ = big.shape
    at = _iota(big.shape, 1)
    r, s = _pairs((h, c, c))
    # per row, G at the first row of its block of b rows
    first, b = big, 1
    while b < c:
        upper = (at & b) != 0
        ref = jnp.where(upper, first, _shift(first, -b))
        yield _corners(r, s, b), jnp.exp(jnp.where(upper, big - ref,
                                                   ref - big))
        first = jnp.where(upper, _shift(first, b), first)
        b *= 2


def _inverse(m):
    """``(I + M)^-1`` for strictly lower-triangular ``M (heads, C, C)``:
    bottom up, ``T_2b = T_b - T_b M_b T_b``."""
    r, s = _pairs(m.shape)
    t = jnp.where(r == s, 1.0, 0.0) - jnp.where(_corners(r, s, 1), m, 0.0)
    b = 2
    while b < m.shape[1]:
        t = t - _dot3(t, _dot3(jnp.where(_corners(r, s, b), m, 0.0), t, _NN),
                      _NN)
        b *= 2
    return t


def _column(row):
    """A ``(heads, 1, C)`` row as a ``(heads, C, 1)`` column."""
    r, s = _pairs((row.shape[0], row.shape[2], row.shape[2]))
    return jnp.sum(jnp.where(r == s, row, 0.0), axis=2, keepdims=True)


def _row(column):
    """A ``(heads, C, 1)`` column as a ``(heads, 1, C)`` row."""
    r, s = _pairs((column.shape[0], column.shape[1], column.shape[1]))
    return jnp.sum(jnp.where(r == s, column, 0.0), axis=1, keepdims=True)


def _forward(q, k, v, g, beta, op):
    """One chunk of a group of heads, ``(heads, C, .)``, ``beta`` as
    ``(heads, 1, C)`` rows: ``W_v, W_k, Q exp(G), Aq, K exp(G_C - G)`` in
    ``op`` and ``exp(G_C)`` as ``(heads, 1, d)`` rows."""
    h, c, d_v = v.shape
    q32, k32 = q.astype(_F32), k.astype(_F32)
    big = _running_sum(g)
    r, s = _pairs((h, c, c))
    a = jnp.zeros((h, c, c), _F32)
    aq = jnp.where(r == s, jnp.sum(q32 * k32, axis=2, keepdims=True), 0.0)
    for corners, decay in _levels(big):
        keys = (k32 * decay).astype(op)
        both = _dot(jnp.concatenate([keys, (q32 * decay).astype(op)], axis=1),
                    keys, _NT)
        a = a + jnp.where(corners, both[:, :c], 0.0)
        aq = aq + jnp.where(corners, both[:, c:], 0.0)
    beta = _column(beta)
    t = _inverse(beta * a)
    e_big = jnp.exp(big)
    w = _dot3(t, jnp.concatenate([beta * v.astype(_F32), beta * k32 * e_big],
                                 axis=2), _NN)
    last = big[:, c - 1:c]
    return (w[..., :d_v].astype(op), w[..., d_v:].astype(op),
            (q32 * e_big).astype(op), aq.astype(op),
            (k32 * jnp.exp(last - big)).astype(op), jnp.exp(last))


def _backward(q, k, v, g, beta, d_w_v, d_w_k, d_q_in, d_scores, d_k_out,
              d_decay, op):
    """One chunk of a group of heads, ``beta`` and ``d_decay`` as rows:
    ``dq, dk, dv`` in ``op``, ``dg`` in float32 and ``dbeta`` as
    ``(heads, 1, C)`` rows."""
    h, c, d_v = v.shape
    q32, k32, v32 = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    big = _running_sum(g)
    e_big = jnp.exp(big)
    r, s = _pairs((h, c, c))
    levels = list(_levels(big))
    a = jnp.zeros((h, c, c), _F32)
    for corners, decay in levels:
        keys = (k32 * decay).astype(op)
        a = a + jnp.where(corners, _dot(keys, keys, _NT), 0.0)
    beta = _column(beta)
    t = _inverse(beta * a)
    k_b = beta * k32 * e_big
    # the inverse and what it is applied to
    d_w = jnp.concatenate([d_w_v, d_w_k], axis=2)
    d_t = _dot3(d_w, jnp.concatenate([beta * v32, k_b], axis=2), _NT)
    d_vk = _dot3(t, d_w, _TN)
    d_v_b, d_k_b = d_vk[..., :d_v], d_vk[..., d_v:]
    d_m = jnp.where(r > s, -_dot3(t, _dot3(d_t, t, _NT), _TN), 0.0)
    d_beta = (jnp.sum(d_m * a, axis=2, keepdims=True)
              + jnp.sum(d_v_b * v32, axis=2, keepdims=True)
              + jnp.sum(d_k_b * k32 * e_big, axis=2, keepdims=True))
    d_v = beta * d_v_b
    d_k = beta * e_big * d_k_b
    d_big = d_k_b * k_b
    # Q exp(G), K exp(G_C - G) and exp(G_C)
    d_q_in = d_q_in.astype(_F32)
    d_q = d_q_in * e_big
    d_big = d_big + d_q_in * (q32 * e_big)
    last = big[:, c - 1:c]
    to_last = jnp.exp(last - big)
    d_k_out = d_k_out.astype(_F32)
    d_k = d_k + d_k_out * to_last
    d_to_last = d_k_out * (k32 * to_last)
    d_big = d_big - d_to_last + jnp.where(
        _iota(big.shape, 1) == c - 1,
        jnp.sum(d_to_last, axis=1, keepdims=True) + d_decay * jnp.exp(last),
        0.0)
    # the scores: the diagonal of Aq, then level by level
    d_a = beta * d_m
    d_aq = d_scores.astype(_F32)
    diagonal = jnp.sum(jnp.where(r == s, d_aq, 0.0), axis=2, keepdims=True)
    d_q = d_q + diagonal * k32
    d_k = d_k + diagonal * q32
    d_q_s = d_k_r = d_k_s = jnp.zeros_like(big)
    for corners, decay in levels:
        keys = (k32 * decay).astype(op)
        d_a_b = jnp.where(corners, d_a, 0.0)
        d_both = jnp.concatenate([d_a_b, jnp.where(corners, d_aq, 0.0)],
                                 axis=1).astype(op)
        d_rows = _dot(d_both, keys, _NN)
        d_keys = _dot(d_both, jnp.concatenate(
            [keys, (q32 * decay).astype(op)], axis=1), _TN)
        if op != _F32:
            # dA is float32: its low part too (dAq comes in op)
            low = (d_a_b - d_both[:, :c].astype(_F32)).astype(op)
            d_rows = d_rows + jnp.concatenate([_dot(low, keys, _NN),
                                               jnp.zeros_like(big)], axis=1)
            d_keys = d_keys + _dot(low, keys, _TN)
        d_k_r = d_k_r + decay * d_rows[:, :c]
        d_q_s = d_q_s + decay * d_rows[:, c:]
        d_k_s = d_k_s + decay * d_keys
    d_big = d_big + q32 * d_q_s + k32 * (d_k_r - d_k_s)
    return ((d_q + d_q_s).astype(op), (d_k + d_k_r + d_k_s).astype(op),
            d_v.astype(op), _running_sum(d_big, reverse=True), _row(d_beta))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, w_v_ref, w_k_ref,
                q_in_ref, scores_ref, k_out_ref, decay_ref):
    *outs, decay = _forward(q_ref[...], k_ref[...], v_ref[...], g_ref[...],
                            beta_ref[...][:, None, :], q_ref.dtype)
    for ref, x in zip((w_v_ref, w_k_ref, q_in_ref, scores_ref, k_out_ref),
                      outs):
        ref[...] = x
    decay_ref[...] = decay[:, 0, :]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, d_w_v_ref, d_w_k_ref,
                d_q_in_ref, d_scores_ref, d_k_out_ref, d_decay_ref, d_q_ref,
                d_k_ref, d_v_ref, d_g_ref, d_beta_ref):
    *grads, d_beta = _backward(
        q_ref[...], k_ref[...], v_ref[...], g_ref[...],
        beta_ref[...][:, None, :], d_w_v_ref[...], d_w_k_ref[...],
        d_q_in_ref[...], d_scores_ref[...], d_k_out_ref[...],
        d_decay_ref[...][:, None, :], q_ref.dtype)
    for ref, x in zip((d_q_ref, d_k_ref, d_v_ref, d_g_ref), grads):
        ref[...] = x
    d_beta_ref[...] = d_beta[:, 0, :]


def _call(kernel, name: str, operands, out_shape, heads: int):
    """``kernel`` over the grid ``(N, B x H / heads)`` of operands and
    results ``(N, B x H, ...)``: a block is one chunk of a group of
    ``heads`` heads, ``gcd(heads, _HEADS)`` of them."""
    heads = math.gcd(heads, _HEADS)
    n, bh, c = operands[0].shape[:3]
    grid = (n, bh // heads)

    def spec(x):
        zeros = (0,) * (x.ndim - 2)
        return pl.BlockSpec((None, heads, *x.shape[2:]),
                            lambda i, j: (i, j, *zeros))

    # double-buffered blocks and the body's float32 values, and a quarter:
    # a scoped-VMEM limit of its own where that passes three quarters of
    # the default
    blocks = _nbytes([*operands, *out_shape]) // math.prod(grid)
    need = 5 * (2 * blocks + _BODY * heads * c * operands[0].shape[-1] * 4
                ) // 4
    return pl.pallas_call(
        kernel, grid=grid, in_specs=[spec(x) for x in operands],
        out_specs=[spec(x) for x in out_shape], out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
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


@functools.partial(jax.jit, static_argnames=("heads",))
def chunk_fwd(q, k, v, g, beta, *, heads: int):
    """``W_v, W_k, Q exp(G), Aq, K exp(G_C - G)`` ``(N, B, H, C, ...)`` in
    ``q.dtype`` and ``exp(G_C)`` ``(N, B, H, d_k)`` in float32 from ``q,
    k, v (N, B, H, C, d)`` in one dtype, ``g (N, B, H, C, d_k)`` and
    ``beta (N, B, H, C)`` in float32; a grid step takes the heads of a
    group of ``heads`` (the walk's), up to :data:`_HEADS` of them.  One
    trace serves every layer of a model (``jax.jit``)."""
    operands = _flat(q, k, v, g, beta)
    n, bh, c, d_k = operands[0].shape
    op = q.dtype
    out_shape = [_sds(x.shape, op, *operands)
                 for x in (operands[2], operands[0], operands[0])]
    out_shape += [_sds((n, bh, c, c), op, *operands), out_shape[1],
                  _sds((n, bh, d_k), _F32, *operands)]
    outs = _call(_fwd_kernel, "kda_chunk_fwd", operands, out_shape, heads)
    shapes = (v.shape, q.shape, q.shape, (*q.shape[:-1], c), q.shape,
              q.shape[:3] + (d_k,))
    return tuple(o.reshape(s) for o, s in zip(outs, shapes))


@functools.partial(jax.jit, static_argnames=("heads",))
def chunk_bwd(q, k, v, g, beta, d_w_v, d_w_k, d_q_in, d_scores, d_k_out,
              d_decay, *, heads: int):
    """The cotangents of :func:`chunk_fwd`'s five operands from those of
    its six results; the chunk is formed again from the operands."""
    gradients_of = (q, k, v, g, beta)
    operands = _flat(*gradients_of, d_w_v, d_w_k, d_q_in, d_scores, d_k_out,
                     d_decay)
    grads = _call(_bwd_kernel, "kda_chunk_bwd", operands,
                  [_sds(x.shape, x.dtype, *operands) for x in operands[:5]],
                  heads)
    return tuple(d.reshape(x.shape) for d, x in zip(grads, gradients_of))
