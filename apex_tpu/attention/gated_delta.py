"""The gated delta rule, chunk by chunk (Kimi Delta Attention's recurrence).

Per head, with a state ``S`` of ``(d_k, d_v)`` that starts at nought::

    S_t = (I - beta_t k_t k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t <= 0`` is a log-decay per key channel, ``beta_t`` a scalar.  With
``u_t = beta_t (v_t - (diag(exp(g_t)) S_{t-1})^T k_t)`` the rule is
``S_t = diag(exp(g_t)) S_{t-1} + k_t u_t^T``: gated linear attention over
pseudo-values that depend on the state.  Over a chunk of ``C`` tokens that
starts from ``S_0``, with ``G_r`` the chunk's running sum of ``g``::

    A_rs  = sum_c k_rc k_sc exp(G_rc - G_sc)      s <  r
    Aq_rs = sum_c q_rc k_sc exp(G_rc - G_sc)      s <= r
    T     = (I + diag(beta) A)^-1                 unit lower triangular
    U     = T diag(beta) V - T diag(beta) (K * exp(G)) S_0  =  W_v - W_k S_0
    O     = (Q * exp(G)) S_0 + Aq U
    S_C   = diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

(the WY / UT form of the Kimi Linear report, arXiv 2510.26692).  Two
parts: what needs no state (``A``, ``Aq``, ``T``, ``W_v``, ``W_k`` and
the decayed ``Q``, ``K``) is computed for all chunks at once; the state
then walks the chunks, four small products a chunk and head.  **Each
part has two routes, read from the operands' shape at trace time by one
rule** (:func:`_kernels`): heads that fill the 128 lanes, in chunks of
whole sublane tiles of the operand dtype, take Mosaic kernels, off the
chip in interpret mode.  What needs no state is then one kernel forward
and one backward over every chunk at once, a grid step a chunk of up to
eight heads, all of it formed in VMEM
(:mod:`apex_tpu.ops.pallas.gated_delta_chunk`); the walk is one kernel
forward and one backward that hold a group of heads' state in VMEM from
the first chunk to the last
(:mod:`apex_tpu.ops.pallas.gated_delta_walk`).  Any other shape (narrow
heads) computes what needs no state as XLA operations
(:func:`_without_state`, which is also the kernels' oracle) and walks
under one ``lax.scan`` of ``L / C`` trips a pass.  One algorithm, the
same products in the same roundings; only the tiling depends on the
widths.

**Decays are only ever exponentiated as differences** ``exp(G_r - G_s)``
with ``s <= r`` inside one chunk, so every exponent is ``<= 0`` and a
channel that forgets fast underflows to nought and never overflows;
``exp(-G)`` is never formed.  ``A`` and ``Aq`` keep that and stay
matmul-shaped by halving: at level ``b`` (1, 2, 4, .. ``C / 2``) the
rows of the upper half of each ``2b`` block meet the keys of its lower
half, both decayed to the first row of the upper half, which lies
between them; a level is one ``(2C, d) x (d, C)`` product a chunk and a
mask.  ``T`` is built bottom up over the same blocks: with ``T_b`` the
inverse of the blocks of ``b`` rows and ``M_b`` the level's corners of
``diag(beta) A``, ``T_2b = T_b - T_b M_b T_b`` (block forward
substitution in float32, three bfloat16 passes a product: it does not
lose digits when keys repeat, where a sum of powers would); its backward pass is the
inverse's own, ``-T^T dT T^T``, not autodiff through the levels.

**Precision.**  ``g``, its running sums (a product at ``highest``; in
the kernels shifted adds), the exponentials, ``T`` and the state are
float32.  The products' operands are ``q.dtype`` (bfloat16
under amp O2; the state is rounded as an operand and carried in float32)
and every product accumulates in float32.

**Backward.**  The state's walk has a hand-written backward
(``jax.custom_vjp``) that keeps one state a chunk (``L / C x B x H x
d_k x d_v`` float32; the kernels write it only when differentiated, the
primal call leaves it out) and recomputes ``U`` from it, walking the
chunks backwards with the state's cotangent, in VMEM scratch or as the
scan's carry.  What needs no state has, on the kernels' route, a
backward derived by hand (``jax.custom_vjp``; the derivation is
:mod:`apex_tpu.ops.pallas.gated_delta_chunk`'s): it keeps only its five
operands and forms ``G``, ``A`` and ``T`` again in VMEM.  On the XLA
route it is differentiated by autodiff under ``jax.checkpoint``, so
nothing of its levels but the triangular inverse is kept between the
passes, and in up to eight slices of the chunks one after another
(``lax.map``), so that a pass holds one slice's levels at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.ops.pallas.gated_delta_chunk import chunk_bwd, chunk_fwd
from apex_tpu.ops.pallas.gated_delta_walk import (walk_bwd, walk_fwd,
                                                  walk_geometry)
from apex_tpu.utils.profiling import KDA_RECURRENCE

_HIGHEST = jax.lax.Precision.HIGHEST
#: what needs no state is computed in at most this many slices of the
#: chunks, one after another: at 8192 tokens, 32 heads of 128 and chunk
#: 64 its halving levels hold about 3 GB at once in the backward pass
_GROUPS = 8
#: the one array of that part the backward pass keeps: the triangular
#: inverse (64 x 64 float32 a chunk and head, 67 MB a layer at 8192
#: tokens), ten float32 products to make again
_KEPT = "kda_triangular_inverse"


def _dot(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32)


def _dot32(spec: str, a, b, precision=_HIGHEST):
    return jnp.einsum(spec, a, b, precision=precision,
                      preferred_element_type=jnp.float32)


def _dot3(spec: str, a, b):
    """A float32 product in three bfloat16 passes (about 2^-17 a term):
    for the triangular inverse and what it is applied to, whose results
    meet bfloat16 operands next; half the MXU time of ``highest``."""
    return _dot32(spec, a, b, jax.lax.Precision.HIGH)


def _level_reference(big, b: int):
    """Per row of a chunk, ``G`` at the first row of the upper half of
    the row's ``2b`` block: ``(..., C, d)`` like ``big``."""
    blocks = big.reshape(*big.shape[:-2], big.shape[-2] // (2 * b), 2 * b,
                         big.shape[-1])
    return jnp.broadcast_to(blocks[..., b:b + 1, :],
                            blocks.shape).reshape(big.shape)


def _level_mask(c: int, b: int):
    """``(upper, corners)``: which rows of a chunk lie in the upper half
    of their ``2b`` block, and which ``(r, s)`` pair such a row with a row
    of the lower half of the same block."""
    at = jnp.arange(c)
    upper = (at // b) % 2 == 1
    return upper, ((at[:, None] // (2 * b) == at[None, :] // (2 * b))
                   & upper[:, None] & ~upper[None, :])


@jax.custom_vjp
def _unit_lower_inverse(m):
    """``(I + M)^-1`` for strictly lower-triangular ``M (..., C, C)`` in
    float32: bottom up, ``T_2b = T_b - T_b M_b T_b``."""
    c = m.shape[-1]
    # blocks of two rows: I - M_1, no product
    inverse = jnp.eye(c, dtype=jnp.float32) \
        - jnp.where(_level_mask(c, 1)[1], m, 0.0)
    b = 2
    while b < c:
        corners = jnp.where(_level_mask(c, b)[1], m, 0.0)
        inverse = inverse - _dot3(
            "...rs,...st->...rt", inverse,
            _dot3("...rs,...st->...rt", corners, inverse))
        b *= 2
    return inverse


def _unit_lower_inverse_fwd(m):
    inverse = _unit_lower_inverse(m)
    return inverse, inverse


def _unit_lower_inverse_bwd(inverse, d_inverse):
    # d(X^-1) = -X^-1 dX X^-1, transposed
    return (-_dot3("...sr,...st->...rt", inverse,
                   _dot3("...rs,...ts->...rt", d_inverse, inverse)),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _without_state(q, k, v, g, beta):
    """Everything of a chunk that needs no state.  All arguments are
    ``(N, B, H, C, ...)``; returns ``W_v, W_k, Q*exp(G), Aq, K*exp(G_C -
    G)`` in ``q.dtype`` and ``exp(G_C)`` in float32.

    Every array keeps a chunk's whole ``(C, C)`` or ``(C, d)`` as its
    last two axes (blocks of a level are a mask, never a reshape: an
    array that ends in ``(b, b)`` pads each block to a whole tile on the
    chip)."""
    op = q.dtype
    c = q.shape[-2]
    at = jnp.arange(c)
    # the running sum as a product with a triangle of ones
    big = _dot32("rs,...sc->...rc",
                 (at[:, None] >= at[None, :]).astype(jnp.float32), g)
    # [A; Aq], (..., 2C, C): the diagonal of Aq, then level by level
    both = jnp.concatenate([
        jnp.zeros((*q.shape[:-1], c), jnp.float32),
        _dot("...c,...c->...", q, k)[..., None]
        * jnp.eye(c, dtype=jnp.float32)], axis=-2)
    b = 1
    while b < c:
        upper, corners = _level_mask(c, b)
        # rows of an upper half decay from the reference down to
        # themselves, keys of a lower half from themselves down to it
        decay = jnp.exp(jnp.where(upper, 1.0, -1.0)[:, None]
                        * (big - _level_reference(big, b)))
        keys = (k * decay).astype(op)
        rows = jnp.concatenate([keys, (q * decay).astype(op)], axis=-2)
        both = both + jnp.where(jnp.tile(corners, (2, 1)),
                                _dot("...rc,...sc->...rs", rows, keys), 0.0)
        b *= 2
    inverse = checkpoint_name(
        _unit_lower_inverse(beta[..., None] * both[..., :c, :]), _KEPT)
    w_v = _dot3("...rs,...sv->...rv", inverse,
                beta[..., None] * v.astype(jnp.float32))
    w_k = _dot3("...rs,...sc->...rc", inverse,
                beta[..., None] * k * jnp.exp(big))
    last = big[..., -1:, :]
    return (w_v.astype(op), w_k.astype(op), (q * jnp.exp(big)).astype(op),
            both[..., c:, :].astype(op),
            (k * jnp.exp(last - big)).astype(op), jnp.exp(last[..., 0, :]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _stateless(q, k, v, g, beta, heads: int):
    """:func:`_without_state` by the Mosaic kernels
    (:mod:`apex_tpu.ops.pallas.gated_delta_chunk`) for the walk's groups
    of ``heads`` heads (:func:`_kernels`); the residuals are the five
    operands.  Called inside the scope; the backward opens it again."""
    return chunk_fwd(q, k, v, g, beta, heads=heads)


def _stateless_fwd(q, k, v, g, beta, heads):
    return _stateless(q, k, v, g, beta, heads), (q, k, v, g, beta)


def _stateless_bwd(heads, operands, cotangents):
    with jax.named_scope(KDA_RECURRENCE):
        return chunk_bwd(*operands, *cotangents, heads=heads)


_stateless.defvjp(_stateless_fwd, _stateless_bwd)


def _in_groups(fn, groups: int, *args):
    """``fn`` over the leading axis of ``args``, ``groups`` slices of it
    one after another (``lax.map``), so that what ``fn`` holds while it
    runs, forward or backward, is a slice's and not the whole row's."""
    if groups == 1:
        return fn(*args)
    split = [a.reshape(groups, a.shape[0] // groups, *a.shape[1:])
             for a in args]
    outs = jax.lax.map(lambda xs: fn(*xs), tuple(split))
    return tuple(o.reshape(-1, *o.shape[2:]) for o in outs)


def _chunk(state, w_v, w_k, q_in, scores, k_out, decay):
    """One chunk from ``state``: pseudo-values, outputs, the next state."""
    op = w_v.dtype
    s = state.astype(op)
    u = w_v.astype(jnp.float32) - _dot("bhck,bhkv->bhcv", w_k, s)
    out = _dot("bhck,bhkv->bhcv", q_in, s) \
        + _dot("bhcs,bhsv->bhcv", scores, u.astype(op))
    after = decay[..., None] * state \
        + _dot("bhck,bhcv->bhkv", k_out, u.astype(op))
    return u, out, after


def _kernels(w_v, w_k):
    """Heads a grid step of the walk's Mosaic kernels takes where the
    operands' shape is theirs (full 128-lane heads, a chunk of whole
    sublane tiles), else ``None``: the scan, and ``_without_state``.
    ``(N, B, H, C, d)`` operands of the walk's, or ``v`` and ``q`` cut
    into chunks."""
    _, b, h, c, d_v = w_v.shape
    return walk_geometry(b * h, c, w_k.shape[-1], d_v, w_v.dtype)


def _forward(operands, keep_states: bool):
    """``(outs, states, tops)`` of the walk; ``states``, one a chunk, is
    the backward pass's alone and in the route's own layout.  A scan's
    stacked output that nothing reads is never computed; a kernel's is
    written all the same, so the kernels leave it out (``None``) without
    ``keep_states``."""
    w_v, w_k = operands[:2]
    heads = _kernels(w_v, w_k)
    with jax.named_scope(KDA_RECURRENCE):
        if heads is not None:
            return walk_fwd(*operands, heads=heads, keep_states=keep_states)
        _, b, h, _, d_v = w_v.shape
        d_k = w_k.shape[-1]

        def body(state, xs):
            _, out, after = _chunk(state, *xs)
            return after, (out.astype(w_v.dtype), state,
                           jnp.max(jnp.abs(after)))

        _, (outs, states, tops) = jax.lax.scan(
            body, jnp.zeros((b, h, d_k, d_v), jnp.float32), operands)
    return outs, states, tops


@jax.custom_vjp
def _walk(w_v, w_k, q_in, scores, k_out, decay):
    """The state through the chunks.  Arguments lead with the chunk axis
    ``N``; returns the outputs ``(N, B, H, C, d_v)`` and, per chunk, the
    largest magnitude in the state it leaves.  By the operands' shape
    (:func:`_kernels`) either two Mosaic kernels that hold the state in
    VMEM (:mod:`apex_tpu.ops.pallas.gated_delta_walk`) or a ``lax.scan``
    over the chunks, forward and backward."""
    outs, _, tops = _forward((w_v, w_k, q_in, scores, k_out, decay),
                             keep_states=False)
    return outs, tops


def _walk_fwd(*operands):
    outs, states, tops = _forward(operands, keep_states=True)
    return (outs, tops), (*operands, states)


def _walk_bwd(kept, cotangents):
    w_v, w_k, q_in, scores, k_out, decay, states = kept
    d_outs, _ = cotangents
    heads = _kernels(w_v, w_k)
    with jax.named_scope(KDA_RECURRENCE):
        if heads is not None:
            return walk_bwd(states, d_outs, w_v, w_k, q_in, scores, k_out,
                            decay, heads=heads)
        op = w_v.dtype

        def body(d_after, xs):
            state, d_out, w_v, w_k, q_in, scores, k_out, decay = xs
            u = _chunk(state, w_v, w_k, q_in, scores, k_out, decay)[0]
            s, u_op, d_s = state.astype(op), u.astype(op), d_after.astype(op)
            d_u = _dot("bhcs,bhcv->bhsv", scores, d_out) \
                + _dot("bhck,bhkv->bhcv", k_out, d_s)
            d_u_op = d_u.astype(op)
            d_state = _dot("bhck,bhcv->bhkv", q_in, d_out) \
                + decay[..., None] * d_after \
                - _dot("bhck,bhcv->bhkv", w_k, d_u_op)
            return d_state, (
                d_u_op,
                (-_dot("bhcv,bhkv->bhck", d_u_op, s)).astype(op),
                _dot("bhcv,bhkv->bhck", d_out, s).astype(op),
                _dot("bhcv,bhsv->bhcs", d_out, u_op).astype(op),
                _dot("bhcv,bhkv->bhck", u_op, d_s).astype(op),
                jnp.sum(d_after * state, axis=-1))

        _, grads = jax.lax.scan(
            body, jnp.zeros_like(states[0]),
            (states, d_outs, w_v, w_k, q_in, scores, k_out, decay),
            reverse=True)
    return grads


_walk.defvjp(_walk_fwd, _walk_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk_size: int = 64,
                           return_stats: bool = False):
    """``o`` of the rule above for ``q, k (B, L, H, d_k)``, ``v (B, L, H,
    d_v)``, log-decays ``g (B, L, H, d_k)`` (float32, ``<= 0``) and ``beta
    (B, L, H)``; q and k come normalised and q scaled by the caller.  A
    length that is no multiple of ``chunk_size`` is padded with tokens
    that neither decay nor write (``g = 0``, ``beta = 0``).

    With ``return_stats`` also ``{"log_decay_min": the most negative
    running log-decay any chunk reaches, "state_absmax": the largest
    magnitude the state takes at a chunk's end}``.
    """
    if chunk_size < 2 or chunk_size & (chunk_size - 1):
        raise ValueError(f"chunk_size {chunk_size} is no power of two: the "
                         f"levels halve a chunk")
    b, l, h, _ = q.shape
    n = -(-l // chunk_size)

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, n * chunk_size - l))
                    + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk_size, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)   # (N, B, H, C, ..)

    with jax.named_scope(KDA_RECURRENCE):
        g = chunks(g.astype(jnp.float32))
        operands = (chunks(q), chunks(k.astype(q.dtype)),
                    chunks(v.astype(q.dtype)), g,
                    chunks(beta.astype(jnp.float32)))
        heads = _kernels(operands[2], operands[0])
        if heads is not None:
            parts = _stateless(*operands, heads)
        else:
            parts = _in_groups(
                jax.checkpoint(_without_state, policy=jax.checkpoint_policies
                               .save_only_these_names(_KEPT)),
                math.gcd(n, _GROUPS), *operands)
    outs, tops = _walk(*parts)
    with jax.named_scope(KDA_RECURRENCE):
        o = jnp.moveaxis(jnp.moveaxis(outs, 3, 2), 0, 1).reshape(
            b, n * chunk_size, h, -1)[:, :l]
        if not return_stats:
            return o
        return o, {"log_decay_min": jnp.min(jnp.sum(g, axis=-2)),
                   "state_absmax": jnp.max(tops)}
