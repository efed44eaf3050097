"""Pallas TPU flash attention (forward + hand-written backward).

The reference has no attention kernels (a 2019 CNN/RNN-era library), but
this framework treats long-context as first-class: the sequence-parallel
paths (:mod:`apex_tpu.attention.ring`) and the BERT family need an
attention primitive that never materializes the ``(L, L)`` score matrix in
HBM.  This is the classic blockwise online-softmax scheme (Dao et al.,
FlashAttention — pattern, not code) mapped onto the TPU:

- the grid walks ``(batch·heads, q_block, k_block)`` with the k dimension
  innermost; Mosaic's sequential grid makes the k-walk a legal accumulation
  over VMEM scratch (running max ``m``, normalizer ``l``, fp32 ``acc``) —
  the role CUDA shared-memory tiling plays for the GPU kernels;
- one algorithm at two geometries, chosen from the call's shape and the
  chip's VMEM by :func:`_geometry` (no knob): a causal call whose head
  fits VMEM keeps the head's rows *resident* — K and V are one block a
  head, fetched and rotated once, and each block of q rows meets all
  its visible keys, none above the diagonal, in one step (one row max
  and one row sum a row instead of one a block pair); the backward is
  then one grid step a head that forms its own row sums ``delta`` and
  writes dq, dk and dv once, in the storage dtype.  A head over
  Mosaic's default scoped-VMEM limit asks for a limit of its own; one
  longer than a span of keys (2048) keeps K, V and the fp32 dk/dv sums
  resident while its q rows walk the grid and meet their keys a span
  at a time.  Everything else (non-causal, key masks, heads over the
  chip's VMEM, explicit blocks) keeps the grid walk.  A
  ``jax.named_scope`` (``flash_resident`` / ``flash_grid``) around the
  kernel calls says which was chosen;
- score/softmax arithmetic is fp32 regardless of storage dtype (the amp
  blacklist rule for softmax), matmuls ride the MXU with
  ``preferred_element_type=float32``;
- the backward recomputes probability blocks from the saved logsumexp;
  on the grid walk one fused pass produces dq, dk and dv together (dk/dv
  accumulate in VMEM scratch, dq lands in per-k-block fp32 partial
  planes summed outside — see ``_fused_bwd_max_bytes``), falling back
  to the classic two-pass scheme (a ``dq`` pass with k innermost, a
  ``dk/dv`` pass with q innermost) when the partials buffer would
  exceed the budget.  No ``(L, L)`` tensor ever hits HBM either way.

Widths: q and k share the head width ``d`` they score at; v, the
output and their cotangents have v's own width (``vf.shape[2]``), which
only the second matmul of each pass sees.  Heads: k and v may hold
fewer (grouped KV heads); every kernel's K/V index map then reads the
KV head of its q head's group (:func:`_kv_head`), and the backward's
per-q-head dk/dv are summed over each group outside the kernels.

A sliding window (``window=W``, causal) has kernels of its own,
``flash_window_fwd`` / ``flash_window_bwd``: each block of q rows meets
the band of key blocks that holds its last ``W`` keys in one pair, and
no other (see the section ``sliding window`` below).

Masking: ``kv_mask`` (key padding) arrives as an additive fp32 bias row
``(B, L)`` (0 = attend, ``NEG_INF`` = ignore); causal masking is computed
from block offsets inside the kernel.  Fully-masked query rows produce
``l = 0`` and emit zeros (masked-softmax convention, matching
``apex_tpu.attention``).

Rotary embeddings (``rope=(cos, sin)``) are applied *inside* the kernel:
q/k blocks are rotated in VMEM right before the score matmul, the saved
residuals stay unrotated, and the backward kernels rotate again for the
probability recompute and inverse-rotate the dq/dk accumulators at emit
(the rotation is orthogonal, so ``d(unrotated) = R^T · d(rotated)`` is
the same lane-rotation with the sine negated).  The rotated q/k never
exist in HBM — this is what lets the head-major GPT path stay a pure
reshape end to end.  Tables arrive as full-width
``(B, L, D)`` pairs (see :func:`apex_tpu.ops.rope.rope_kernel_tables`)
and are held VMEM-resident per batch when they fit
(``_ROPE_RESIDENT_MAX_BYTES`` per side) or streamed per block above that.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds as _sds

_LANES = 128
#: Minor-dim width for the per-row stats tensors (lse, delta) in HBM.
#: Full lane width (128) is what jax's TPU flash kernel uses too: narrower
#: widths save HBM (the stats are per-row scalars) but force Mosaic
#: relayouts in the backward inner loop (widths 1 and 8 were both slower
#: on the round-3 chip, BERT-large L=512).  The footprint is BH·L·512
#: bytes a tensor: 1.6 GB of residuals a step in the benchmark's GPT
#: cell (PERF.md section 7), the next thing to narrow.
_STATS_W = _LANES
NEG_INF = -1e30

#: Per-side byte budget (cos + sin whole tables) under which the rope
#: tables ride a single (1, Lp, D) VMEM block per batch — the index map
#: is constant across the inner grid walk, so Mosaic fetches them once
#: per batch instead of re-DMAing a (block, D) pair every step (at
#: d=64/bf16 the per-step table traffic would otherwise double the
#: k-side stream).  Above the budget (long contexts) the tables stream
#: per block; those regimes run 1024-wide blocks where compute dominates
#: the extra DMA.
_ROPE_RESIDENT_MAX_BYTES = 1 << 20


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _kv_head(index, groups: int):
    """The index map of a K or V operand from its q-side ``index``: with
    grouped heads (``groups`` q heads a KV head) q head ``bh = b H + h``
    reads KV head ``b Hkv + h // groups``, which is ``bh // groups``;
    with one q head a KV head, ``index`` itself."""
    if groups == 1:
        return index
    return lambda g0, *rest: (g0 // groups,) + tuple(index(g0, *rest))[1:]


def _rot(x, cos, sin):
    """Rotate a ``(rows, D)`` block in fp32: ``x·cos + rot_half(x)·sin``
    where ``rot_half`` maps lane ``j`` to ``x[(j + D/2) mod D]`` and the
    tables arrive pre-signed (``sin = [-sin, sin]`` — see
    :func:`apex_tpu.ops.rope.rope_kernel_tables`); the inverse rotation
    is the same call with ``-sin``.  The lane rotation is spelled as a
    two-slice concat, VMEM-local in Mosaic."""
    half = x.shape[-1] // 2
    xr = jnp.concatenate([x[:, half:], x[:, :half]], axis=1)
    return (x.astype(jnp.float32) * cos.astype(jnp.float32)
            + xr.astype(jnp.float32) * sin.astype(jnp.float32))


def _rope_nrefs(rope_mode) -> int:
    """How many rope refs a kernel receives for this mode."""
    return {None: 0, "resident": 2, "stream": 4}[rope_mode]


def _rope_q(rope_refs, rope_mode, q_start, block_q):
    """(cos, sin) for the current q block.  Resident mode slices the
    whole-(Lp, D) tables held in VMEM (block starts are multiples of the
    8-sublane granularity, so the dynamic slice is layout-aligned);
    stream mode reads the per-block pipelined refs."""
    if rope_mode == "resident":
        cos_ref, sin_ref = rope_refs
        return (cos_ref[0, pl.ds(q_start, block_q), :],
                sin_ref[0, pl.ds(q_start, block_q), :])
    return rope_refs[0][0], rope_refs[1][0]


def _rope_k(rope_refs, rope_mode, k_start, block_k):
    if rope_mode == "resident":
        cos_ref, sin_ref = rope_refs
        return (cos_ref[0, pl.ds(k_start, block_k), :],
                sin_ref[0, pl.ds(k_start, block_k), :])
    return rope_refs[2][0], rope_refs[3][0]


def _causal_mask(bq, bk, q_start, k_start):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return qpos >= kpos


def _causal_dispatch(causal, live, straddle, update, dead=None):
    """Shared block-dispatch stanza of the four kernels: fully-visible
    live blocks skip the iota/compare/where mask work, only
    diagonal-straddling blocks pay it (~60% of live blocks skip at
    L=2048 with 512 blocks).  ``dead`` optionally runs on non-live
    blocks (the fused backward zeroes its dq partial plane there)."""
    if causal:
        pl.when(jnp.logical_and(live, straddle))(lambda: update(True))
        pl.when(jnp.logical_and(live, jnp.logical_not(straddle)))(
            lambda: update(False))
        if dead is not None:
            pl.when(jnp.logical_not(live))(dead)
    else:
        update(False)


def _fwd_update(q, k, v, bias_row, mask, m_scr, l_scr, acc_scr, *,
                has_bias):
    """One online-softmax step: fold the ``(bq, bk)`` scores of ``q``
    against ``k`` into the running max / normalizer / accumulator
    scratch.  ``q`` and ``k`` arrive rotated and ``q`` pre-scaled;
    ``mask`` is the causal mask of a diagonal-straddling pair or
    ``None``; ``bias_row`` is ``(1, bk)``, read only with ``has_bias``."""
    # Matmul operands keep their storage dtype: bf16 inputs ride the
    # MXU at full rate, fp32 inputs keep exact fp32 semantics.
    # Accumulation is always fp32 (preferred_element_type), and every
    # softmax/statistics op stays fp32 — the amp fp32-softmax policy
    # is about the *reduction* precision, not MXU operand storage.
    # The softmax scale is folded into q by the caller (one (L, d)
    # pass instead of an (L, L) one here).
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bq, bk)
    if has_bias:
        s = s + bias_row                      # (1, bk) broadcast
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                        # (bq, LANES) replicated
    m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    corr = jnp.exp(m_prev - m_new)             # (bq, LANES)
    p = jnp.exp(s - m_new[:, :1])              # (bq, bk)
    # Masked entries need no explicit zeroing here: s == NEG_INF and
    # a finite m_new make exp underflow to exactly 0 (causal rows
    # always see the diagonal, so m_new is finite in every live
    # block).  Only the bias path can produce fully-masked rows
    # (m_new == NEG_INF -> exp(0) == 1), so only it re-zeroes.
    if has_bias:
        p = jnp.where(bias_row > NEG_INF / 2, p, 0.0)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
    l_new = l_scr[...] * corr + jnp.broadcast_to(
        p.sum(axis=1, keepdims=True), m_prev.shape)
    # p rides the MXU in the storage dtype (the flash convention: the
    # probabilities are cast to the value dtype for the PV matmul;
    # the fp32 accumulator keeps the reduction exact).
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)    # (bq, d)
    acc_scr[...] = acc_scr[...] * corr[:, :1] + pv
    m_scr[...] = m_new
    l_scr[...] = l_new


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, *rest, causal, has_bias,
                rope_mode, block_q, block_k, nk, resident, span=None):
    """One q block against one K/V block.  Grid walk: the grid's
    innermost axis walks the k dimension a block at a time, dead and
    straddling blocks told apart by ``pl.when``.  Resident (causal, no
    bias, ``block_k`` the whole padded head, one grid step on that
    axis): the block holds a head's K and V, fetched once a head, K
    rotated once a head into scratch, and the q block meets all its
    visible keys — rows ``[0, q_start + block_q)``, nothing above the
    diagonal — in one update: one row max and one row sum a row where
    the walk pays them once a block pair (PERF.md, PR 26: on the v5e
    those cross-lane reductions, not the score tile, are what a pair
    costs).  The width is static per q block, so the q blocks of a head
    are branches of the body.  A long head (``span``) meets its keys a
    span at a time, online: a loop over the whole spans under the q
    block, then the keys that end at its diagonal, the only masked ones
    (PERF.md, PR 28: one pair of 4096 keys or more runs several times
    slower than its spans)."""
    nrope = _rope_nrefs(rope_mode)
    rope_refs = rest[:nrope]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[nrope:nrope + 5]
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def _update(masked):
        q = q_ref[0]                              # (bq, d)
        k = k_ref[0]                              # (bk, d)
        if rope_mode:
            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            ck, sk = _rope_k(rope_refs, rope_mode, k_start, block_k)
            q = _rot(q, cq, sq).astype(q_ref.dtype)
            k = _rot(k, ck, sk).astype(k_ref.dtype)
        mask = (_causal_mask(block_q, block_k, q_start, k_start)
                if masked else None)
        _fwd_update(q, k, v_ref[0], bias_ref[0], mask, m_scr, l_scr,
                    acc_scr, has_bias=has_bias)

    def _visible_keys_at_once():
        q = q_ref[0]
        load_k = lambda rows: k_ref[0, rows, :]
        if rope_mode:
            krot_scr, = rest[nrope + 5:]          # (Lp, d) rotated K
            load_k = lambda rows: krot_scr[rows, :]

            @pl.when(iq == 0)
            def _rotate_k():
                for j in range(block_k // block_q):
                    rows = pl.ds(j * block_q, block_q)
                    ck, sk = _rope_k(rope_refs, rope_mode, j * block_q,
                                     block_q)
                    krot_scr[rows, :] = _rot(k_ref[0, rows, :], ck,
                                             sk).astype(krot_scr.dtype)

            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            q = _rot(q, cq, sq).astype(q_ref.dtype)

        def visit(rows, diagonal=None):
            # A span that ends at the chunk's diagonal says where its
            # first row stands among the span's keys.
            _fwd_update(q, load_k(rows), v_ref[0, rows, :], None,
                        None if diagonal is None else _causal_mask(
                            block_q, rows.size, diagonal, 0),
                        m_scr, l_scr, acc_scr, has_bias=False)

        # Chunk iq = a * r + b meets a whole spans of keys, all visible
        # (a loop, one body), then (b + 1) * block_q keys that end at
        # its diagonal (static per b).  Without a span, r is the head:
        # no loop, one pair a chunk.
        r = (span or block_k) // block_q
        a, start = 0, 0
        if span:
            a = iq // r
            start = pl.multiple_of(a * span, span)

            @pl.loop(0, a)
            def _(j):
                visit(pl.ds(pl.multiple_of(j * span, span), span))

        for b in range(r):
            @pl.when((iq % r if span else iq) == b)
            def _(b=b):
                visit(pl.ds(start, (b + 1) * block_q),
                      diagonal=b * block_q)

    if resident:
        _visible_keys_at_once()
    else:
        # Whole block strictly above the diagonal contributes nothing.
        live = (not causal) or (k_start <= q_start + block_q - 1)
        # Only diagonal-straddling blocks need the iota/compare/where
        # mask work; fully-below-diagonal blocks are entirely visible.
        straddle = k_start + block_k - 1 > q_start
        _causal_dispatch(causal, live, straddle, _update)

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_scr[:, :_STATS_W]                     # (bq, W) replicated
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / safe_l[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, NEG_INF,
                               m_scr[:, :_STATS_W] + jnp.log(safe_l))


def _bwd_p(q, k, bias_row, lse_col, *, masked, has_bias, q_start, k_start,
           block_q, block_k):
    """Recompute the probability block from the saved logsumexp.
    ``q`` is pre-scaled by the caller; ``bias_row``: (1, bk);
    ``lse_col``: (bq, 1).  ``masked`` says this block straddles the
    causal diagonal (fully-visible blocks skip the mask work).  Without
    a bias, masked entries and NEG_INF rows cannot make exp misfire
    (s - lse underflows to 0 for s == NEG_INF, and lse is finite for
    every causal row), so the explicit zeroing wheres exist only on the
    bias path."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if has_bias:
        s = s + bias_row
    if masked and not has_bias:
        s = jnp.where(_causal_mask(block_q, block_k, q_start, k_start),
                      s, NEG_INF)
    p = jnp.exp(s - lse_col)
    if has_bias:
        if masked:
            p = jnp.where(_causal_mask(block_q, block_k, q_start, k_start),
                          p, 0.0)
        p = jnp.where(bias_row > NEG_INF / 2, p, 0.0)
        # lse == NEG_INF marks fully-masked rows: their p must be 0.
        p = jnp.where(lse_col > NEG_INF / 2, p, 0.0)
    return p


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
               *rest, causal, has_bias, rope_mode, block_q, block_k, nk):
    rope_refs = rest[:_rope_nrefs(rope_mode)]
    dq_ref, dq_scr = rest[_rope_nrefs(rope_mode):]
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)
    straddle = k_start + block_k - 1 > q_start

    def _update(masked):
        q = q_ref[0]
        k = k_ref[0]
        if rope_mode:
            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            ck, sk = _rope_k(rope_refs, rope_mode, k_start, block_k)
            q = _rot(q, cq, sq).astype(q_ref.dtype)
            k = _rot(k, ck, sk).astype(k_ref.dtype)
        p = _bwd_p(q, k, bias_ref[0], lse_ref[0][:, :1], masked=masked,
                   has_bias=has_bias, q_start=q_start, k_start=k_start,
                   block_q=block_q, block_k=block_k)
        do = do_ref[0]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bq, bk)
        # The softmax scale lives in the pre-scaled q (and is applied to
        # dq once, outside the kernel) — no (bq, bk) scale pass here.
        ds = p * (dp - delta_ref[0][:, :1])
        # ds is cast to the storage dtype for its MXU op (flash bwd
        # convention); the fp32 scratch accumulator carries the sum.
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, live, straddle, _update)

    @pl.when(ik == nk - 1)
    def _emit():
        dq = dq_scr[...]
        if rope_mode:
            # The accumulated dq is w.r.t. the ROTATED q; chain through
            # the orthogonal rotation: R^T = the same lane-rotation with
            # the sine negated.
            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            dq = _rot(dq, cq, -sq)
        dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
                *rest, causal, has_bias, rope_mode, block_q, block_k, nq):
    rope_refs = rest[:_rope_nrefs(rope_mode)]
    dk_ref, dv_ref, dk_scr, dv_scr = rest[_rope_nrefs(rope_mode):]
    iq = pl.program_id(2)
    ik = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)
    straddle = k_start + block_k - 1 > q_start

    def _update(masked):
        q = q_ref[0]
        k = k_ref[0]
        if rope_mode:
            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            ck, sk = _rope_k(rope_refs, rope_mode, k_start, block_k)
            q = _rot(q, cq, sq).astype(q_ref.dtype)
            k = _rot(k, ck, sk).astype(k_ref.dtype)
        p = _bwd_p(q, k, bias_ref[0], lse_ref[0][:, :1], masked=masked,
                   has_bias=has_bias, q_start=q_start, k_start=k_start,
                   block_q=block_q, block_k=block_k)
        do = do_ref[0]
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (bk, d)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dk = ds^T @ q_scaled is exact: d(s)/d(k) carries the scale via
        # the pre-scaled q, so no (bq, bk) scale pass is needed.
        ds = p * (dp - delta_ref[0][:, :1])              # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(causal, live, straddle, _update)

    @pl.when(iq == nq - 1)
    def _emit():
        dk = dk_scr[...]
        if rope_mode:
            ck, sk = _rope_k(rope_refs, rope_mode, k_start, block_k)
            dk = _rot(dk, ck, -sk)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd_pair(q, k, v, do, lse_col, delta_col, bias_row, *, masked,
              has_bias, q_start, k_start, block_q, block_k):
    """One (q rows, k rows) pair of the one-pass backward: p and dp are
    computed once and feed all three gradients.  Returns the pair's
    fp32 contributions ``(dq, dk, dv)``, dq and dk with respect to the
    rotated (and, for q, pre-scaled) operands."""
    p = _bwd_p(q, k, bias_row, lse_col, masked=masked, has_bias=has_bias,
               q_start=q_start, k_start=k_start, block_q=block_q,
               block_k=block_k)
    return _pair_grads(p, q, k, v, do, delta_col)


def _pair_grads(p, q, k, v, do, delta_col):
    """A pair's fp32 ``(dq, dk, dv)`` from its recomputed probabilities:
    p and dp feed all three gradients."""
    dv = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bk, d)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col)                        # (bq, bk)
    ds_c = ds.astype(q.dtype)
    dk = jax.lax.dot_general(
        ds_c, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(
        ds_c, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (bq, d) fp32
    return dq, dk, dv


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      bias_ref, *rest, causal, has_bias, rope_mode,
                      block_q, block_k, nq):
    """One-pass backward: p/dp are computed once per block pair and feed
    dq, dk and dv together (the two-pass kernels recompute them, costing
    an extra score matmul + exp per pair).  Grid (bh, ik, iq): dk/dv
    accumulate in VMEM scratch over the inner q walk; dq can't (it's
    indexed by iq), so each k block writes its dq contribution to its
    own fp32 partial plane, summed by XLA outside — O(nk) extra HBM, so
    the caller only picks this kernel when nk is small."""
    rope_refs = rest[:_rope_nrefs(rope_mode)]
    dqp_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest[_rope_nrefs(rope_mode):]
    iq = pl.program_id(2)
    ik = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    live = (not causal) or (k_start <= q_start + block_q - 1)
    straddle = k_start + block_k - 1 > q_start

    def _update(masked):
        q = q_ref[0]
        k = k_ref[0]
        if rope_mode:
            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            ck, sk = _rope_k(rope_refs, rope_mode, k_start, block_k)
            q = _rot(q, cq, sq).astype(q_ref.dtype)
            k = _rot(k, ck, sk).astype(k_ref.dtype)
        dqp, dk, dv = _bwd_pair(
            q, k, v_ref[0], do_ref[0], lse_ref[0][:, :1],
            delta_ref[0][:, :1], bias_ref[0], masked=masked,
            has_bias=has_bias, q_start=q_start, k_start=k_start,
            block_q=block_q, block_k=block_k)
        dk_scr[...] += dk
        dv_scr[...] += dv
        if rope_mode:
            # Rotation is linear, so inverse-rotating each partial plane
            # equals inverse-rotating their sum (done outside otherwise).
            cq, sq = _rope_q(rope_refs, rope_mode, q_start, block_q)
            dqp = _rot(dqp, cq, -sq)
        dqp_ref[0, 0] = dqp

    def _zero_dead():
        # Dead blocks still own their dq partial plane slot: zero it.
        dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    _causal_dispatch(causal, live, straddle, _update, dead=_zero_dead)

    @pl.when(iq == nq - 1)
    def _emit():
        dk = dk_scr[...]
        if rope_mode:
            ck, sk = _rope_k(rope_refs, rope_mode, k_start, block_k)
            dk = _rot(dk, ck, -sk)
        dk_ref[0] = dk.astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _resident_bwd_refs(rest, has_dlse, rope_mode):
    """What follows ``lse_ref`` in the resident backward kernels' refs:
    ``(dlse_ref, rope_refs, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
    krot_scr)``, ``dlse_ref`` and the rotated-K scratch ``None`` where
    the call has none."""
    ndl, nrope = int(has_dlse), _rope_nrefs(rope_mode)
    outs = rest[ndl + nrope:ndl + nrope + 5]
    return (rest[0] if has_dlse else None, rest[ndl:ndl + nrope], *outs,
            rest[ndl + nrope + 5] if rope_mode else None)


def _bwd_resident_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         *rest, has_dlse, rope_mode, chunk, n):
    """The one-pass backward of a causal head whose rows are all in
    VMEM: one grid step a head.  Why a body of its own beside
    ``_bwd_fused_kernel``: there the pair walk is the grid and only dk
    and dv outlive a step, so dq leaves as one fp32 plane per k block
    and the row sums arrive from outside.  Here each chunk of q rows
    meets all its visible keys — rows ``[0, (i + 1) * chunk)``, a width
    static per chunk, so the chunks are unrolled — in one pair, which is
    ``_bwd_pair`` as there: its dq is whole, inverse-rotated and written
    once in the storage dtype; dk and dv add up in fp32 scratch over the
    head and are written once; the row sums ``delta`` come from the
    resident ``o`` and ``do``; K is rotated once a head."""
    (dlse_ref, rope_refs, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
     krot_scr) = _resident_bwd_refs(rest, has_dlse, rope_mode)
    load_k = ((lambda rows: krot_scr[rows, :]) if rope_mode
              else (lambda rows: k_ref[0, rows, :]))
    spans = [pl.ds(i * chunk, chunk) for i in range(n)]

    for j, rows in enumerate(spans):
        if rope_mode:
            ck, sk = _rope_k(rope_refs, rope_mode, j * chunk, chunk)
            krot_scr[rows, :] = _rot(k_ref[0, rows, :], ck,
                                     sk).astype(krot_scr.dtype)
        dk_scr[rows, :] = jnp.zeros((chunk, dk_scr.shape[1]), jnp.float32)
        dv_scr[rows, :] = jnp.zeros((chunk, dv_scr.shape[1]), jnp.float32)

    for i, rows in enumerate(spans):
        w = (i + 1) * chunk
        q = q_ref[0, rows, :]
        if rope_mode:
            cq, sq = _rope_q(rope_refs, rope_mode, i * chunk, chunk)
            q = _rot(q, cq, sq).astype(q_ref.dtype)
        do = do_ref[0, rows, :]
        delta = jnp.sum(o_ref[0, rows, :].astype(jnp.float32)
                        * do.astype(jnp.float32), axis=1, keepdims=True)
        if has_dlse:
            # ds_ij = p_ij (dp_ij - delta_i + dlse_i): the logsumexp's
            # cotangent is an offset on the row sums.
            delta = delta - dlse_ref[0, rows, :][:, :1]
        dq, dk, dv = _bwd_pair(
            q, load_k(pl.ds(0, w)), v_ref[0, :w, :], do,
            lse_ref[0, rows, :][:, :1], delta, None, masked=True,
            has_bias=False, q_start=i * chunk, k_start=0, block_q=chunk,
            block_k=w)
        dk_scr[:w, :] += dk
        dv_scr[:w, :] += dv
        if rope_mode:
            # dq is w.r.t. the ROTATED q; chain through the orthogonal
            # rotation, R^T = the same lane-rotation with the sine negated.
            dq = _rot(dq, cq, -sq)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    for j, rows in enumerate(spans):
        dk = dk_scr[rows, :]
        if rope_mode:
            ck, sk = _rope_k(rope_refs, rope_mode, j * chunk, chunk)
            dk = _rot(dk, ck, -sk)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv_scr[rows, :].astype(dv_ref.dtype)


def _bwd_qwalk_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
                      has_dlse, rope_mode, chunk, n, span):
    """The one-pass backward of a causal head too long for one grid
    step (:func:`_bwd_resident_kernel`): K, V and the fp32 dk/dv sums
    stay in VMEM for the head while q, do, o, lse and dq move by chunk
    — grid ``(bh, n)``, the K/V and dk/dv block indices constant over
    the q walk.  Chunk ``iq = a * r + b`` (``r`` chunks a span) meets
    its visible keys in spans: ``a`` whole spans, all visible (a loop,
    one body), then the ``(b + 1) * chunk`` keys that end at its
    diagonal (static per ``b``); each is ``_bwd_pair``.  Its dq is
    whole when its spans end, inverse-rotated and written once in the
    storage dtype; dk and dv add up in fp32 scratch over the head and
    are written at the head's last chunk; the row sums ``delta`` come
    from the chunk's own ``o`` and ``do``; K is rotated once a head."""
    (dlse_ref, rope_refs, dq_ref, dk_ref, dv_ref, dk_scr, dv_scr,
     krot_scr) = _resident_bwd_refs(rest, has_dlse, rope_mode)
    load_k = ((lambda rows: krot_scr[rows, :]) if rope_mode
              else (lambda rows: k_ref[0, rows, :]))
    iq = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        @pl.loop(0, n)
        def _(j):
            start = pl.multiple_of(j * chunk, chunk)
            rows = pl.ds(start, chunk)
            if rope_mode:
                ck, sk = _rope_k(rope_refs, rope_mode, start, chunk)
                krot_scr[rows, :] = _rot(k_ref[0, rows, :], ck,
                                         sk).astype(krot_scr.dtype)
            dk_scr[rows, :] = jnp.zeros((chunk, dk_scr.shape[1]),
                                        jnp.float32)
            dv_scr[rows, :] = jnp.zeros((chunk, dv_scr.shape[1]),
                                        jnp.float32)

    q = q_ref[0]
    if rope_mode:
        cq, sq = _rope_q(rope_refs, rope_mode, iq * chunk, chunk)
        q = _rot(q, cq, sq).astype(q_ref.dtype)
    do = do_ref[0]
    delta = jnp.sum(o_ref[0].astype(jnp.float32) * do.astype(jnp.float32),
                    axis=1, keepdims=True)
    if has_dlse:
        # ds_ij = p_ij (dp_ij - delta_i + dlse_i): the logsumexp's
        # cotangent is an offset on the row sums.
        delta = delta - dlse_ref[0][:, :1]
    lse_col = lse_ref[0][:, :1]

    def visit(rows, dq, diagonal=None):
        # A span that ends at the chunk's diagonal says where its first
        # row stands among the span's keys; a whole span has no mask.
        dq_s, dk, dv = _bwd_pair(
            q, load_k(rows), v_ref[0, rows, :], do, lse_col, delta, None,
            masked=diagonal is not None, has_bias=False, q_start=diagonal,
            k_start=0, block_q=chunk, block_k=rows.size)
        dk_scr[rows, :] += dk
        dv_scr[rows, :] += dv
        return dq + dq_s

    r = span // chunk
    a = iq // r
    dq = jax.lax.fori_loop(
        0, a, lambda j, dq: visit(
            pl.ds(pl.multiple_of(j * span, span), span), dq),
        jnp.zeros(q.shape, jnp.float32))

    for b in range(r):
        @pl.when(iq % r == b)
        def _(b=b):
            dq_b = visit(pl.ds(pl.multiple_of(a * span, span),
                               (b + 1) * chunk), dq, diagonal=b * chunk)
            if rope_mode:
                # dq is w.r.t. the ROTATED q; chain through the
                # orthogonal rotation, R^T = the same lane-rotation with
                # the sine negated.
                dq_b = _rot(dq_b, cq, -sq)
            dq_ref[0] = dq_b.astype(dq_ref.dtype)

    @pl.when(iq == n - 1)
    def _emit():
        @pl.loop(0, n)
        def _(j):
            start = pl.multiple_of(j * chunk, chunk)
            rows = pl.ds(start, chunk)
            dk = dk_scr[rows, :]
            if rope_mode:
                ck, sk = _rope_k(rope_refs, rope_mode, start, chunk)
                dk = _rot(dk, ck, -sk)
            dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_scr[rows, :].astype(dv_ref.dtype)


def _rope_inputs(cos_t, sin_t, rope_mode, h, lp, d, block_q, block_k,
                 q_pos, k_pos):
    """(operands, in_specs) for the rope tables of one pallas_call.
    ``q_pos``/``k_pos`` say which grid axis (1 or 2) carries the q/k
    block index in the calling kernel's grid order.  Resident mode: one
    whole-``(Lp, D)`` block per table with a constant index map — Mosaic
    fetches it once per batch and the kernel slices per block.  Stream
    mode: per-block pipelined (cos_q, sin_q, cos_k, sin_k)."""
    if not rope_mode:
        return [], []
    if rope_mode == "resident":
        spec = pl.BlockSpec((1, lp, d), lambda g0, g1, g2: (g0 // h, 0, 0))
        return [cos_t, sin_t], [spec, spec]

    def _m(pos):
        if pos == 1:
            return lambda g0, g1, g2: (g0 // h, g1, 0)
        return lambda g0, g1, g2: (g0 // h, g2, 0)

    qspec = pl.BlockSpec((1, block_q, d), _m(q_pos))
    kspec = pl.BlockSpec((1, block_k, d), _m(k_pos))
    return [cos_t, sin_t, cos_t, sin_t], [qspec, qspec, kspec, kspec]


def _delta(of, do_f, dlse_f):
    """Per-row backward offset ``sum(o * do) - dlse`` in fp32, broadcast
    to the ``_STATS_W`` stats width: a cotangent on the logsumexp folds
    into the backward as ``ds_ij = p_ij (dp_ij - delta_i + dlse_i)``
    (since dlse_i/ds_ij = p_ij); ``dlse_f`` is ``None`` where the
    caller dropped the logsumexp.  Shared by the grid-walk backward
    implementations so the fold stays in one place (the resident kernel
    forms the same sums from its own ``o`` and ``do``)."""
    bh, lp = of.shape[0], of.shape[1]
    delta = jnp.sum(of.astype(jnp.float32) * do_f.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # (bh, lp, 1)
    if dlse_f is not None:
        delta = delta - dlse_f[..., None]
    return jnp.broadcast_to(delta, (bh, lp, _STATS_W))


@functools.partial(jax.jit,
                   static_argnames=("causal", "has_bias", "rope_mode",
                                    "block_q", "block_k", "num_heads",
                                    "groups"))
def _flash_bwd_fused(qf, kf, vf, of, do_f, lse, bias, cos_t, sin_t, dlse_f,
                     *, causal, has_bias, rope_mode, block_q, block_k,
                     num_heads, groups=1):
    bh, lp, d = qf.shape
    dv_ = vf.shape[2]                   # v, o and do keep v's own width
    nq, nk = lp // block_q, lp // block_k
    h = num_heads
    delta = _delta(of, do_f, dlse_f)
    rope_ops, rope_specs = _rope_inputs(cos_t, sin_t, rope_mode, h, lp, d,
                                        block_q, block_k, q_pos=2, k_pos=1)
    kv_at = _kv_head(lambda bh_, ik, iq: (bh_, ik, 0), groups)

    dq_part, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, causal=causal,
                          has_bias=has_bias, rope_mode=rope_mode,
                          block_q=block_q, block_k=block_k, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_at),
            pl.BlockSpec((1, block_k, dv_), kv_at),
            pl.BlockSpec((1, block_q, dv_), lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh_, ik, iq: (bh_ // h, 0, ik)),
        ] + rope_specs,
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bh_, ik, iq: (ik, bh_, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh_, ik, iq: (bh_, ik, 0)),
            pl.BlockSpec((1, block_k, dv_), lambda bh_, ik, iq: (bh_, ik, 0)),
        ],
        out_shape=[
            _sds((nk, bh, lp, d), jnp.float32, qf),
            _sds((bh, lp, d), qf.dtype, qf),
            _sds((bh, lp, dv_), qf.dtype, qf),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv_), jnp.float32)],
        name="flash_bwd_fused",
        interpret=not on_tpu(),
    )(qf, kf, vf, do_f, lse, delta, bias, *rope_ops)
    dq = dq_part.sum(axis=0).astype(qf.dtype)
    return dq, dk, dv


def _compiler_params(vmem_limit):
    """``compiler_params`` of a call: nothing for a call under Mosaic's
    default scoped-VMEM limit, its own limit for one above it."""
    if vmem_limit is None:
        return {}
    return dict(compiler_params=pltpu.CompilerParams(
        vmem_limit_bytes=int(vmem_limit)))


@functools.partial(jax.jit,
                   static_argnames=("rope_mode", "chunk", "num_heads",
                                    "span", "vmem_limit", "groups"))
def _flash_bwd_resident(qf, kf, vf, of, do_f, lse, cos_t, sin_t, dlse_f, *,
                        rope_mode, chunk, num_heads, span=None,
                        vmem_limit=None, groups=1):
    """Causal, bias-free backward with a head's K and V resident.
    Without a ``span`` all of the head's rows are: grid ``(bh,)``, every
    operand one ``(1, Lp, ·)`` block.  With one, q and what goes with
    its rows walk the grid ``chunk`` rows at a time: grid ``(bh, Lp /
    chunk)``.  ``dlse_f`` is ``None`` where the caller dropped the
    logsumexp; otherwise it rides in at the stats width, as ``lse``
    does."""
    bh, lp, d = qf.shape
    dv_ = vf.shape[2]                   # v, o and do keep v's own width
    h = num_heads
    static = dict(has_dlse=dlse_f is not None, rope_mode=rope_mode,
                  chunk=chunk, n=lp // chunk)
    if span is None:
        kernel = functools.partial(_bwd_resident_kernel, **static)
        grid, rows = (bh,), lp
        at = lambda index: lambda bh_: index(bh_, 0)
    else:
        kernel = functools.partial(_bwd_qwalk_kernel, span=span, **static)
        grid, rows = (bh, lp // chunk), chunk
        at = lambda index: index
    by_chunk = at(lambda bh_, iq: (bh_, iq, 0))
    by_head = at(lambda bh_, iq: (bh_, 0, 0))
    qrow = pl.BlockSpec((1, rows, d), by_chunk)
    qvrow = pl.BlockSpec((1, rows, dv_), by_chunk)
    stats = pl.BlockSpec((1, rows, _STATS_W), by_chunk)
    row = pl.BlockSpec((1, lp, d), by_head)
    vrow = pl.BlockSpec((1, lp, dv_), by_head)
    table = pl.BlockSpec((1, lp, d), at(lambda bh_, iq: (bh_ // h, 0, 0)))
    operands = [qf, kf, vf, do_f, of, lse]
    in_specs = [qrow, pl.BlockSpec((1, lp, d), _kv_head(by_head, groups)),
                pl.BlockSpec((1, lp, dv_), _kv_head(by_head, groups)),
                qvrow, qvrow, stats]
    if dlse_f is not None:
        operands.append(jnp.broadcast_to(dlse_f[..., None],
                                         (bh, lp, _STATS_W)))
        in_specs.append(stats)
    scratch = [pltpu.VMEM((lp, d), jnp.float32),            # dk
               pltpu.VMEM((lp, dv_), jnp.float32)]          # dv
    if rope_mode:
        operands += [cos_t, sin_t]
        in_specs += [table, table]
        scratch.append(pltpu.VMEM((lp, d), kf.dtype))       # rotated K
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[qrow, row, vrow],
        out_shape=[_sds((bh, lp, d), qf.dtype, qf)] * 2
        + [_sds((bh, lp, dv_), qf.dtype, qf)],
        scratch_shapes=scratch,
        name="flash_bwd_fused",
        interpret=not on_tpu(),
        **_compiler_params(vmem_limit),
    )(*operands)


def _fused_bwd_max_bytes() -> int:
    """HBM budget for the grid walk's fused backward's (groups, BH, L,
    d) fp32 dq-partials buffer; the gate is its size, not the block
    count — fused still wins at nk=16 when the buffer fits.  Above this
    budget the extra HBM outweighs the saved recompute and the two-pass
    kernels take over (extreme contexts / big batches).  A resident
    head (:func:`_geometry`) has no partials and never asks: since PR
    28 that is every causal, mask-free head that fits the chip's VMEM
    (16384 rows of 128 in bf16 do), so the grid walk's backwards serve
    non-causal calls, key masks, explicit blocks and longer heads.

    ``APEX_TPU_FLASH_FUSED_BWD_MAX_BYTES`` overrides (0 forces the
    two-pass path) so memory-tight configs can steer without
    monkeypatching."""
    import os
    env = os.environ.get("APEX_TPU_FLASH_FUSED_BWD_MAX_BYTES")
    if env is not None:
        try:
            return int(env)
        except ValueError:
            raise ValueError(
                f"APEX_TPU_FLASH_FUSED_BWD_MAX_BYTES must be a plain "
                f"integer byte count, got {env!r}") from None
    return 1 << 30


def _pad_bhld(t, lp, layout="blhd"):
    """(B, L, H, D) or (B, H, L, D) → (BH, Lp, D), zero sequence padding.

    The ``bhld`` layout is the transpose-free fast path: models that
    emit per-head-major q/k/v (the projection dot absorbs the transpose
    for free — measured round 3) reach the kernel with a pure reshape,
    skipping the materialized relayout the ``blhd`` view needs (~6
    copies of (B, L, E) per transformer layer, fwd+bwd)."""
    if layout == "bhld":
        b, h, l, d = t.shape
        t = t.reshape(b * h, l, d)
    else:
        b, l, h, d = t.shape
        t = jnp.moveaxis(t, 2, 1).reshape(b * h, l, d)
    if lp != l:
        t = jnp.pad(t, ((0, 0), (0, lp - l), (0, 0)))
    return t


def _prep(q, k, v, bias, block_q, block_k, layout="blhd"):
    """q/k/v (see ``_pad_bhld``) → padded (BH, Lp, D); pad the additive
    key bias with ``NEG_INF`` so padded keys never attend."""
    l = q.shape[2] if layout == "bhld" else q.shape[1]
    lp = _ceil_to(l, math.lcm(block_q, block_k))
    if bias is not None:
        if lp != l:
            bias = jnp.pad(bias, ((0, 0), (0, lp - l)),
                           constant_values=NEG_INF)
        bias = bias[:, None, :]        # (B, 1, Lp): Mosaic-legal row blocks
    return (_pad_bhld(q, lp, layout), _pad_bhld(k, lp, layout),
            _pad_bhld(v, lp, layout), bias, lp)


def _unprep(t, b, l, h, layout="blhd"):
    t = t.reshape(b, h, -1, t.shape[-1])[:, :, :l, :]
    return t if layout == "bhld" else jnp.moveaxis(t, 1, 2)


@functools.partial(jax.jit,
                   static_argnames=("causal", "has_bias", "rope_mode",
                                    "block_q", "block_k", "num_heads",
                                    "resident", "span", "vmem_limit",
                                    "groups"))
def _flash_fwd(qf, kf, vf, bias, cos_t, sin_t, *, causal, has_bias,
               rope_mode, block_q, block_k, num_heads, resident, span=None,
               vmem_limit=None, groups=1):
    bh, lp, d = qf.shape
    dv_ = vf.shape[2]                   # v and o keep v's own width
    # Resident: K and V are one block a head, its index constant over
    # the q walk, so fetched once a head; else the grid walks K.
    if resident:
        block_k = lp
    nq, nk = lp // block_q, lp // block_k
    grid = (bh, nq, nk)
    h = num_heads
    rope_ops, rope_specs = _rope_inputs(cos_t, sin_t, rope_mode, h, lp, d,
                                        block_q, block_k, q_pos=1, k_pos=2)
    kv_at = _kv_head(lambda bh_, iq, ik: (bh_, ik, 0), groups)
    scratch = [
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, _LANES), jnp.float32),
        pltpu.VMEM((block_q, dv_), jnp.float32),
    ]
    if resident and rope_mode:
        scratch.append(pltpu.VMEM((lp, d), kf.dtype))      # rotated K

    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, has_bias=has_bias,
                          rope_mode=rope_mode, block_q=block_q,
                          block_k=block_k, nk=nk, resident=resident,
                          span=span),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_at),
            pl.BlockSpec((1, block_k, dv_), kv_at),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh_, iq, ik: (bh_ // h, 0, ik)),
        ] + rope_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv_), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, iq, ik: (bh_, iq, 0)),
        ],
        out_shape=[
            _sds((bh, lp, dv_), qf.dtype, qf),
            # logsumexp replicated across the stats minor dim (see
            # _STATS_W).
            _sds((bh, lp, _STATS_W), jnp.float32, qf),
        ],
        scratch_shapes=scratch,
        name="flash_fwd",
        interpret=not on_tpu(),
        **_compiler_params(vmem_limit),
    )(qf, kf, vf, bias, *rope_ops)
    return o, lse


@functools.partial(jax.jit,
                   static_argnames=("causal", "has_bias", "rope_mode",
                                    "block_q", "block_k", "num_heads",
                                    "groups"))
def _flash_bwd(qf, kf, vf, of, do_f, lse, bias, cos_t, sin_t, dlse_f, *,
               causal, has_bias, rope_mode, block_q, block_k, num_heads,
               groups=1):
    bh, lp, d = qf.shape
    dv_ = vf.shape[2]                   # v, o and do keep v's own width
    nq, nk = lp // block_q, lp // block_k
    h = num_heads
    delta = _delta(of, do_f, dlse_f)

    common_in = [qf, kf, vf, do_f, lse, delta, bias]
    rope_ops_q, rope_specs_q = _rope_inputs(cos_t, sin_t, rope_mode, h, lp,
                                            d, block_q, block_k,
                                            q_pos=1, k_pos=2)
    rope_ops_k, rope_specs_k = _rope_inputs(cos_t, sin_t, rope_mode, h, lp,
                                            d, block_q, block_k,
                                            q_pos=2, k_pos=1)
    kv_by_q = _kv_head(lambda bh_, iq, ik: (bh_, ik, 0), groups)
    kv_by_k = _kv_head(lambda bh_, ik, iq: (bh_, ik, 0), groups)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, has_bias=has_bias,
                          rope_mode=rope_mode, block_q=block_q,
                          block_k=block_k, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_by_q),
            pl.BlockSpec((1, block_k, dv_), kv_by_q),
            pl.BlockSpec((1, block_q, dv_), lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, iq, ik: (bh_, iq, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh_, iq, ik: (bh_ // h, 0, ik)),
        ] + rope_specs_q,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh_, iq, ik: (bh_, iq, 0)),
        out_shape=_sds((bh, lp, d), qf.dtype, qf),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=not on_tpu(),
    )(*common_in, *rope_ops_q)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, has_bias=has_bias,
                          rope_mode=rope_mode, block_q=block_q,
                          block_k=block_k, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, block_k, d), kv_by_k),
            pl.BlockSpec((1, block_k, dv_), kv_by_k),
            pl.BlockSpec((1, block_q, dv_), lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, block_q, _STATS_W),
                         lambda bh_, ik, iq: (bh_, iq, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda bh_, ik, iq: (bh_ // h, 0, ik)),
        ] + rope_specs_k,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh_, ik, iq: (bh_, ik, 0)),
            pl.BlockSpec((1, block_k, dv_), lambda bh_, ik, iq: (bh_, ik, 0)),
        ],
        out_shape=[
            _sds((bh, lp, d), qf.dtype, qf),
            _sds((bh, lp, dv_), qf.dtype, qf),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv_), jnp.float32)],
        name="flash_bwd_dkv",
        interpret=not on_tpu(),
    )(*common_in, *rope_ops_k)
    return dq, dk, dv


#: ``jax.named_scope`` around a flash call's kernels, by the geometry
#: :func:`_geometry` chose at trace time: the counter that says whether
#: the resident walk engaged, read off any instruction's ``op_name``.
RESIDENT_SCOPE, GRID_SCOPE = "flash_resident", "flash_grid"


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10, 11, 12, 13, 14, 15,
                                    16))
def _flash(q, k, v, bias, cos_t, sin_t, scale, causal, block_q, block_k,
           has_bias, rope_mode, layout, resident, with_lse, span,
           vmem_limit):
    """``out``, or ``(out, lse)`` with ``with_lse``: a caller that drops
    the logsumexp says so here, and its backward carries no cotangent
    for it.  ``span`` and ``vmem_limit`` are a long resident head's
    (:class:`_Geometry`), ``None`` for every other call."""
    outs, _ = _flash_core(q, k, v, bias, cos_t, sin_t, scale, causal,
                          block_q, block_k, has_bias, rope_mode, layout,
                          resident, with_lse, span, vmem_limit)
    return outs


def _lse_public(lse, b, l, h):
    """Internal (BH, Lp, W) logsumexp → public (B, L, H) fp32."""
    return jnp.moveaxis(lse[:, :, 0].reshape(b, h, -1)[:, :, :l], 1, 2)


def _flash_core(q, k, v, bias, cos_t, sin_t, scale, causal, block_q,
                block_k, has_bias, rope_mode, layout, resident, with_lse,
                span, vmem_limit):
    if layout == "bhld":
        b, h, l, d = q.shape
    else:
        b, l, h, d = q.shape
    qf, kf, vf, bias_p, lp = _prep(q, k, v, bias, block_q, block_k, layout)
    groups = qf.shape[0] // kf.shape[0]
    # Softmax scale folded into q once ((L, d) elementwise, fused into
    # the prep reshuffle) instead of an (L, L) pass per score block.
    # Scaling commutes with the in-kernel rotation (both linear), so the
    # fold stays valid on the rope path.
    qf = qf * jnp.asarray(scale, qf.dtype)
    if rope_mode and cos_t.shape[1] != lp:
        # Zero-padded tables rotate the (already zero) padded rows to
        # zero; padded keys are excluded by causality or the pad bias
        # either way.
        pad = ((0, 0), (0, lp - cos_t.shape[1]), (0, 0))
        cos_t = jnp.pad(cos_t, pad)
        sin_t = jnp.pad(sin_t, pad)
    with jax.named_scope(RESIDENT_SCOPE if resident else GRID_SCOPE):
        of, lse = _flash_fwd(qf, kf, vf, bias_p, cos_t, sin_t,
                             causal=causal, has_bias=has_bias,
                             rope_mode=rope_mode, block_q=block_q,
                             block_k=block_k, num_heads=h,
                             resident=resident, span=span,
                             vmem_limit=vmem_limit, groups=groups)
    out = _unprep(of, b, l, h, layout)
    return ((out, _lse_public(lse, b, l, h)) if with_lse else out,
            (qf, kf, vf, of, lse, bias_p, cos_t, sin_t))


def _flash_fwd_rule(q, k, v, bias, cos_t, sin_t, scale, causal, block_q,
                    block_k, has_bias, rope_mode, layout, resident,
                    with_lse, span, vmem_limit):
    outs, res = _flash_core(q, k, v, bias, cos_t, sin_t, scale, causal,
                            block_q, block_k, has_bias, rope_mode, layout,
                            resident, with_lse, span, vmem_limit)
    # The saved tables are padded to Lp; the cotangents must match the
    # caller's (unpadded) table shape, so remember it.
    return outs, (res, q.shape, cos_t.shape)


def _flash_bwd_rule(scale, causal, block_q, block_k, has_bias, rope_mode,
                    layout, resident, with_lse, span, vmem_limit, saved,
                    cotangents):
    dout, dlse = cotangents if with_lse else (cotangents, None)
    (qf, kf, vf, of, lse, bias_p, cos_t, sin_t), shape, table_shape = saved
    if layout == "bhld":
        b, h, l, d = shape
    else:
        b, l, h, d = shape
    lp = qf.shape[1]
    groups = qf.shape[0] // kf.shape[0]
    do_f = _pad_bhld(dout, lp, layout)
    # A cotangent on the logsumexp folds into the backward as an offset on
    # delta: ds_ij = p_ij (dp_ij - delta_i + dlse_i), since dlse_i/ds_ij =
    # p_ij.  Callers that dropped the logsumexp (plain attention) pay
    # nothing.
    dlse_f = None
    if with_lse:
        dlse_f = jnp.moveaxis(dlse.astype(jnp.float32), 1, 2).reshape(
            b * h, l)
        if lp != l:
            dlse_f = jnp.pad(dlse_f, ((0, 0), (0, lp - l)))
    with jax.named_scope(RESIDENT_SCOPE if resident else GRID_SCOPE):
        if resident:
            dqf, dkf, dvf = _flash_bwd_resident(
                qf, kf, vf, of, do_f, lse, cos_t, sin_t, dlse_f,
                rope_mode=rope_mode, chunk=block_k, num_heads=h, span=span,
                vmem_limit=vmem_limit, groups=groups)
        else:
            partials_bytes = (lp // block_k) * qf.shape[0] * lp * d * 4
            bwd = (_flash_bwd_fused
                   if partials_bytes <= _fused_bwd_max_bytes()
                   else _flash_bwd)
            dqf, dkf, dvf = bwd(qf, kf, vf, of, do_f, lse, bias_p, cos_t,
                                sin_t, dlse_f, causal=causal,
                                has_bias=has_bias, rope_mode=rope_mode,
                                block_q=block_q, block_k=block_k,
                                num_heads=h, groups=groups)
    if groups > 1:
        # Each q head's share of its KV head's dk and dv, summed over
        # the group in fp32: q head b H + h is row b Hkv + h // G, part
        # h % G, of a (B Hkv, G, Lp, D) view.
        dkf, dvf = (t.reshape(-1, groups, *t.shape[1:]).astype(
            jnp.float32).sum(axis=1).astype(t.dtype) for t in (dkf, dvf))
    # The kernels differentiate w.r.t. the pre-scaled q: dk comes out
    # exact (ds^T @ q_scaled), dq needs the one deferred scale.  On the
    # rope path the kernels already inverse-rotated at emit, so dq/dk
    # are w.r.t. the unrotated inputs here.
    dq = _unprep(dqf, b, l, h, layout) * jnp.asarray(scale, dqf.dtype)
    dk = _unprep(dkf, b, l, h // groups, layout)
    dv = _unprep(dvf, b, l, h // groups, layout)
    # The rope tables are position functions (int positions carry no
    # gradient); their zero cotangents DCE under jit.
    return (dq, dk, dv, _zeros_typed_like((b, l), bias_p),
            _zeros_typed_like(table_shape, cos_t),
            _zeros_typed_like(table_shape, sin_t))


def _zeros_typed_like(shape, like):
    """Zero cotangent of ``shape`` with ``like``'s dtype and varying-axes
    type: under ``shard_map`` custom_vjp holds a cotangent to the type of
    its primal, and fresh zeros vary over nothing."""
    zeros = jnp.zeros(shape, like.dtype)
    vma = tuple(jax.typeof(like).vma)
    return jax.lax.pcast(zeros, vma, to="varying") if vma else zeros


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# -- sliding window --------------------------------------------------------
#
# ``window=W``: query ``i`` sees keys ``i - W < j <= i``.  The head's K and
# V are one block a KV head (fetched once for its group of q heads, whose
# grid steps are consecutive), q walks the grid ``block`` rows at a time,
# and each q block meets the band of ``n`` key blocks that ends at its own,
# ``n = ceil((W - 1) / block) + 1`` (:func:`_band`), in one pair: a score
# tile ``(block, n block)``, one row max and one row sum.  No key block
# outside the band is fetched, scored or masked; the pair's band mask
# covers both of its edges.  The backward walks q the same way: dq is
# whole when its pair ends; dk and dv add up in fp32 scratch over the
# KV head's group and leave the kernel once per KV head, summed.


#: Rows of q a windowed call scores at a time; its key blocks are as tall.
_WINDOW_BLOCK = 256


def _band(lp: int, block: int, window: int) -> int:
    """Key blocks of ``block`` rows that the band of a q block of as many
    rows meets under a window of ``window`` keys: ``ceil((W - 1) /
    block) + 1``, and no more than the head's ``lp / block``."""
    return min(-(-(window - 1) // block) + 1, lp // block)


def _band_start(iq, n: int, nk: int):
    """The first key block of q block ``iq``'s band of ``n`` blocks: the
    band ends at the q block's own, and a band that would start before
    the head starts at its first block (its later keys are then masked
    as the diagonal's are)."""
    return jnp.minimum(jnp.maximum(iq - (n - 1), 0), nk - n)


def _window_mask(rows, width, q_start, k_start, window):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    return jnp.logical_and(qpos >= kpos, qpos - kpos < window)


def _window_refs(rest, n_in, rope):
    """``(rope_refs, outs_and_scratch, krot_scr)`` of a window kernel."""
    nrope = 2 if rope else 0
    return (rest[:nrope], rest[nrope:nrope + n_in],
            rest[nrope + n_in] if rope else None)


def _rotate_k_once(k_ref, krot_scr, rope_refs, nk, block):
    """K rotated into scratch a block at a time (once per KV head)."""
    @pl.loop(0, nk)
    def _(j):
        start = pl.multiple_of(j * block, block)
        rows = pl.ds(start, block)
        ck, sk = _rope_k(rope_refs, "resident", start, block)
        krot_scr[rows, :] = _rot(k_ref[0, rows, :], ck, sk).astype(
            krot_scr.dtype)


def _window_fwd_kernel(q_ref, k_ref, v_ref, *rest, rope, block, n, nk,
                       window, groups):
    rope_refs, (o_ref, lse_ref), krot_scr = _window_refs(rest, 2, rope)
    bh, iq = pl.program_id(0), pl.program_id(1)
    q_start = iq * block
    k_start = pl.multiple_of(_band_start(iq, n, nk) * block, block)
    band = pl.ds(k_start, n * block)
    q = q_ref[0]
    if rope:
        @pl.when(jnp.logical_and(bh % groups == 0, iq == 0))
        def _():
            _rotate_k_once(k_ref, krot_scr, rope_refs, nk, block)

        cq, sq = _rope_q(rope_refs, "resident", q_start, block)
        q = _rot(q, cq, sq).astype(q_ref.dtype)
        k = krot_scr[band, :]
    else:
        k = k_ref[0, band, :]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(_window_mask(block, n * block, q_start, k_start, window),
                  s, NEG_INF)
    # every row sees its own key: m is finite and masked entries give 0
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, band, :],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[0] = (pv / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (block, _STATS_W))


def _window_bwd_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
                       rope, block, n, nk, window, groups):
    rope_refs, (dq_ref, dk_ref, dv_ref, dk_scr, dv_scr), krot_scr = \
        _window_refs(rest, 5, rope)
    bh, iq = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(bh % groups == 0, iq == 0))
    def _init():
        if rope:
            _rotate_k_once(k_ref, krot_scr, rope_refs, nk, block)

        @pl.loop(0, nk)
        def _(j):
            rows = pl.ds(pl.multiple_of(j * block, block), block)
            dk_scr[rows, :] = jnp.zeros((block, dk_scr.shape[1]),
                                        jnp.float32)
            dv_scr[rows, :] = jnp.zeros((block, dv_scr.shape[1]),
                                        jnp.float32)

    q_start = iq * block
    k_start = pl.multiple_of(_band_start(iq, n, nk) * block, block)
    band = pl.ds(k_start, n * block)
    q = q_ref[0]
    if rope:
        cq, sq = _rope_q(rope_refs, "resident", q_start, block)
        q = _rot(q, cq, sq).astype(q_ref.dtype)
        k = krot_scr[band, :]
    else:
        k = k_ref[0, band, :]
    do = do_ref[0]
    delta = jnp.sum(o_ref[0].astype(jnp.float32) * do.astype(jnp.float32),
                    axis=1, keepdims=True)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(_window_mask(block, n * block, q_start, k_start, window),
                  s, NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, :1])
    dq, dk, dv = _pair_grads(p, q, k, v_ref[0, band, :], do, delta)
    dk_scr[band, :] += dk
    dv_scr[band, :] += dv
    if rope:
        # dq is w.r.t. the ROTATED q: R^T is the rotation with -sin
        dq = _rot(dq, cq, -sq)
    dq_ref[0] = dq.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(bh % groups == groups - 1,
                             iq == pl.num_programs(1) - 1))
    def _emit():
        @pl.loop(0, nk)
        def _(j):
            start = pl.multiple_of(j * block, block)
            rows = pl.ds(start, block)
            dk_j = dk_scr[rows, :]
            if rope:
                ck, sk = _rope_k(rope_refs, "resident", start, block)
                dk_j = _rot(dk_j, ck, -sk)
            dk_ref[0, rows, :] = dk_j.astype(dk_ref.dtype)
            dv_ref[0, rows, :] = dv_scr[rows, :].astype(dv_ref.dtype)


def _window_bytes(lp: int, d: int, d_v: int, itemsize: int, rope: bool,
                  block: int, n: int) -> int:
    """VMEM the windowed backward, the larger of the two kernels, needs:
    K, V, dk and dv whole and double-buffered, the fp32 dk/dv sums (and
    the rotated K) as scratch, the tables whole, the q walk's streams,
    and four fp32 tiles of the band's pair."""
    row, vrow = lp * _ceil_to(d, _LANES), lp * _ceil_to(d_v, _LANES)
    held = 2 * 2 * (row + vrow) * itemsize
    scratch = (row + vrow) * 4 + (row * itemsize if rope else 0)
    tables = 2 * 2 * row * itemsize if rope else 0
    streams = 2 * block * (2 * (_ceil_to(d, _LANES) + _ceil_to(d_v, _LANES))
                           * itemsize + _STATS_W * 4)
    return held + scratch + tables + streams + 4 * block * n * block * 4


def _window_params(need: int):
    """The window kernels' compiler parameters: the grid's two axes are
    sequential (scratch carries over a KV head's q heads and q blocks),
    and a scoped-VMEM limit of the estimate and a quarter."""
    limit = max(5 * need // 4, 16 << 20)
    if limit > 7 * _vmem_capacity() // 8:
        raise ValueError(
            f"flash_attention: a window over a head of this length needs "
            f"{need} bytes of VMEM, more than the chip holds")
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=int(limit))


def _window_specs(lp, d, block, h, groups, rope, cos_t, sin_t):
    """Shared in_specs pieces: q-walk rows, the KV head whole, tables."""
    by_q = lambda w: pl.BlockSpec((1, block, w), lambda bh, iq: (bh, iq, 0))
    kv = lambda w: pl.BlockSpec((1, lp, w),
                                lambda bh, iq: (bh // groups, 0, 0))
    table = pl.BlockSpec((1, lp, d), lambda bh, iq: (bh // h, 0, 0))
    return by_q, kv, ([cos_t, sin_t], [table, table]) if rope else ([], [])


@functools.partial(jax.jit, static_argnames=("rope", "block", "window",
                                             "num_heads", "groups"))
def _flash_window_fwd(qf, kf, vf, cos_t, sin_t, *, rope, block, window,
                      num_heads, groups):
    bh, lp, d = qf.shape
    d_v = vf.shape[2]
    nk, n = lp // block, _band(lp, block, window)
    by_q, kv, (tables, table_specs) = _window_specs(
        lp, d, block, num_heads, groups, rope, cos_t, sin_t)
    scratch = [pltpu.VMEM((lp, d), kf.dtype)] if rope else []
    need = _window_bytes(lp, d, d_v, qf.dtype.itemsize, rope, block, n)
    return pl.pallas_call(
        functools.partial(_window_fwd_kernel, rope=rope, block=block, n=n,
                          nk=nk, window=window, groups=groups),
        grid=(bh, nk),
        in_specs=[by_q(d), kv(d), kv(d_v)] + table_specs,
        out_specs=[by_q(d_v), by_q(_STATS_W)],
        out_shape=[_sds((bh, lp, d_v), qf.dtype, qf),
                   _sds((bh, lp, _STATS_W), jnp.float32, qf)],
        scratch_shapes=scratch,
        name="flash_window_fwd",
        interpret=not on_tpu(),
        compiler_params=_window_params(need),
    )(qf, kf, vf, *tables)


@functools.partial(jax.jit, static_argnames=("rope", "block", "window",
                                             "num_heads", "groups"))
def _flash_window_bwd(qf, kf, vf, of, do_f, lse, cos_t, sin_t, *, rope,
                      block, window, num_heads, groups):
    bh, lp, d = qf.shape
    d_v = vf.shape[2]
    nk, n = lp // block, _band(lp, block, window)
    by_q, kv, (tables, table_specs) = _window_specs(
        lp, d, block, num_heads, groups, rope, cos_t, sin_t)
    scratch = [pltpu.VMEM((lp, d), jnp.float32),
               pltpu.VMEM((lp, d_v), jnp.float32)]
    if rope:
        scratch.append(pltpu.VMEM((lp, d), kf.dtype))
    need = _window_bytes(lp, d, d_v, qf.dtype.itemsize, rope, block, n)
    bkv = kf.shape[0]
    return pl.pallas_call(
        functools.partial(_window_bwd_kernel, rope=rope, block=block, n=n,
                          nk=nk, window=window, groups=groups),
        grid=(bh, nk),
        in_specs=[by_q(d), kv(d), kv(d_v), by_q(d_v), by_q(d_v),
                  by_q(_STATS_W)] + table_specs,
        out_specs=[by_q(d), kv(d), kv(d_v)],
        out_shape=[_sds((bh, lp, d), qf.dtype, qf),
                   _sds((bkv, lp, d), qf.dtype, qf),
                   _sds((bkv, lp, d_v), qf.dtype, qf)],
        scratch_shapes=scratch,
        name="flash_window_bwd",
        interpret=not on_tpu(),
        compiler_params=_window_params(need),
    )(qf, kf, vf, do_f, of, lse, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_window(q, k, v, cos_t, sin_t, scale, window, block, rope,
                  layout):
    return _window_core(q, k, v, cos_t, sin_t, scale, window, block, rope,
                        layout)[0]


def _window_core(q, k, v, cos_t, sin_t, scale, window, block, rope, layout):
    b, h = q.shape[0], q.shape[1 if layout == "bhld" else 2]
    l = q.shape[2 if layout == "bhld" else 1]
    qf, kf, vf, _, lp = _prep(q, k, v, None, block, block, layout)
    groups = qf.shape[0] // kf.shape[0]
    qf = qf * jnp.asarray(scale, qf.dtype)
    if rope and cos_t.shape[1] != lp:
        pad = ((0, 0), (0, lp - cos_t.shape[1]), (0, 0))
        cos_t, sin_t = jnp.pad(cos_t, pad), jnp.pad(sin_t, pad)
    of, lse = _flash_window_fwd(qf, kf, vf, cos_t, sin_t, rope=rope,
                                block=block, window=window, num_heads=h,
                                groups=groups)
    return (_unprep(of, b, l, h, layout),
            (qf, kf, vf, of, lse, cos_t, sin_t))


def _window_fwd_rule(q, k, v, cos_t, sin_t, scale, window, block, rope,
                     layout):
    out, res = _window_core(q, k, v, cos_t, sin_t, scale, window, block,
                            rope, layout)
    return out, (res, q.shape, cos_t.shape)


def _window_bwd_rule(scale, window, block, rope, layout, saved, dout):
    (qf, kf, vf, of, lse, cos_t, sin_t), shape, table_shape = saved
    b, h = shape[0], shape[1 if layout == "bhld" else 2]
    l = shape[2 if layout == "bhld" else 1]
    groups = qf.shape[0] // kf.shape[0]
    do_f = _pad_bhld(dout, qf.shape[1], layout)
    dqf, dkf, dvf = _flash_window_bwd(
        qf, kf, vf, of, do_f, lse, cos_t, sin_t, rope=rope, block=block,
        window=window, num_heads=h, groups=groups)
    # the kernels differentiate w.r.t. the pre-scaled q and sum dk, dv
    # over each KV head's group
    return (_unprep(dqf, b, l, h, layout) * jnp.asarray(scale, dqf.dtype),
            _unprep(dkf, b, l, h // groups, layout),
            _unprep(dvf, b, l, h // groups, layout),
            _zeros_typed_like(table_shape, cos_t),
            _zeros_typed_like(table_shape, sin_t))


_flash_window.defvjp(_window_fwd_rule, _window_bwd_rule)


def repeat_kv_heads(k, v, heads: int):
    """``(B, L, Hkv, D)`` ``k`` and ``v`` with each KV head repeated for
    the ``heads // Hkv`` q heads that read it (q head ``h`` reads KV head
    ``h // (heads // Hkv)``): the materializing paths' way to grouped
    heads, whose gradient sums each group's shares.  The kernels read
    the shared KV head in place instead."""
    groups = heads // k.shape[2]
    if groups == 1:
        return k, v
    return jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)


def _jnp_attention(q, k, v, *, causal, kv_mask, scale, return_lse=False,
                   window=None):
    """Materializing jnp path with the kernel's exact conventions (fp32
    softmax, masked rows emit zeros) — the cross-attention fallback and
    the interpret-mode stand-in under ``shard_map`` (see
    :func:`flash_attention`).  Grouped KV heads are repeated to q's; a
    ``window`` (with ``causal``) hides keys ``window`` or more behind."""
    k, v = repeat_kv_heads(k, v, q.shape[2])
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    visible = jnp.ones((q.shape[0], 1, q.shape[1], k.shape[1]), bool)
    if kv_mask is not None:
        visible = visible & kv_mask[:, None, None, :]
    if causal:
        qpos = jnp.arange(q.shape[1])[:, None]
        kpos = jnp.arange(k.shape[1])[None, :]
        visible = visible & (qpos >= kpos)[None, None]
        if window is not None:
            visible = visible & (qpos - kpos < window)[None, None]
    s = jnp.where(visible, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.where(visible, jnp.exp(s - m), 0.0)
    l = p.sum(axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / safe_l, v.astype(jnp.float32))
    out = out.astype(q.dtype)
    if not return_lse:
        return out
    lse = jnp.where(l[..., 0] == 0.0, NEG_INF,
                    m[..., 0] + jnp.log(safe_l[..., 0]))   # (b, h, lq)
    return out, jnp.moveaxis(lse, 1, 2)


def _default_block(l: int) -> int:
    """Default q/k block edge of the grid walk by sequence length: 512,
    growing to 1024 at L >= 2048, where fewer, larger grid steps
    amortize the per-step overhead and the online-softmax stats updates
    (2048 blocks fail to compile with the fp32 score tile) — but only
    when the larger block adds no padding: for L not near a multiple of
    1024 the padded sequence would grow, and the quadratic extra
    attention work erases the per-step win.  The choice dates from the
    round-3 chip, at B8·H12·L2048·d64, causal.  Causal mask-free heads
    of that length are resident since PR 28 (:func:`_geometry`); what
    still walks the grid at L >= 2048 is non-causal, masked or longer
    than the chip's VMEM holds, and no cell of ``BENCHMARK.json`` runs
    such a call (PERF.md section 7)."""
    if l >= 2048 and _ceil_to(l, 1024) == _ceil_to(l, 512):
        return 1024
    return 512


#: Rows of q a resident head scores at a time (against all their visible
#: keys), widest first.  On the v5e a row of a pair costs about as much
#: whatever the pair's width, and every q block loads its keys into the
#: MXU anew, so fewer, taller blocks win as long as they fit: at the
#: gpt2 cells' shape 512 rows beat 256 and 128 in the forward and tie in
#: the backward (PERF.md, PR 26); at 8192 rows of 192/128 likewise, and
#: 1024 rows lose in the backward (PERF.md, PR 28).
_RESIDENT_CHUNKS = (512, 256)

#: What a resident head may plan to hold in VMEM with no word to the
#: compiler: three quarters of Mosaic's default 16 MiB scoped limit.  A
#: head under it compiles with the parameters it always had; one over
#: it asks for its own limit (:func:`_geometry`).
_RESIDENT_VMEM_BYTES = 3 * (16 << 20) // 4

#: Keys a chunk of a resident head meets at a time.  A head no longer
#: than this keeps all its rows in VMEM and meets a chunk's visible keys
#: in one pair; a longer one walks q on the grid and meets them in spans
#: of this many.  On the v5e a pair's cost per key is flat to 2048 keys
#: and several times that from 4096 (PERF.md, PR 28, has the table).
_LONG_HEAD_SPAN = 2048

#: VMEM of a TPU v5e core, for where no chip answers: CPU tests and a
#: compile for a described chip see one deterministic geometry.
_FALLBACK_VMEM_CAPACITY = 128 << 20


def _vmem_capacity() -> int:
    """VMEM of the chip this call is traced for, read from the device;
    :data:`_FALLBACK_VMEM_CAPACITY` where the device is no TPU."""
    try:
        return int(pltpu.get_tpu_info().vmem_capacity_bytes)
    except ValueError:
        return _FALLBACK_VMEM_CAPACITY


class _Geometry(NamedTuple):
    """How a call walks its head: ``resident`` (K/V one block a head,
    each ``block`` rows of q against all their visible keys in one
    step) or the grid walk with ``block`` as the edge of the grid's q
    and k blocks.  A resident head over Mosaic's default scoped-VMEM
    limit also says what it asks the compiler for (``vmem_limit``,
    bytes) and, where a chunk's visible keys are too many for one pair,
    how many it meets at a time (``span``)."""
    resident: bool
    block: int
    span: "int | None" = None
    vmem_limit: "int | None" = None


def _resident_bytes(lp: int, d: int, itemsize: int, rope: bool,
                    chunk: int, d_v: "int | None" = None) -> int:
    """VMEM the resident backward, the larger of the two kernels, needs
    for a head of ``lp`` rows scored ``chunk`` rows at a time: the
    head's operands, results and scratch once each (VMEM tiles are 128
    lanes wide whatever ``d`` is) and one and a half fp32 score tiles
    of the widest pair.  Fitted to the compiler's own accounting: for
    heads too long to fit, Mosaic's refusals name 16.4 to 22.8 MB at
    3072 rows in bf16 where this gives 16.4 to 22.7 (PERF.md, PR 26).
    ``d_v`` is the width of v, o and their cotangents where it is not
    ``d``."""
    row = lp * _ceil_to(d, _LANES)
    vrow = lp * _ceil_to(d_v or d, _LANES)
    rows = (4 * row + 4 * vrow) * itemsize     # q k dq dk; v do o dv
    stats = 2 * lp * _STATS_W * 4              # lse and its cotangent
    tables = 2 * row * (2 if itemsize == 2 else 4) if rope else 0
    scratch = (row + vrow) * 4 + (row * itemsize if rope else 0)
    return rows + stats + tables + scratch + 3 * chunk * lp * 4 // 2


def _long_head_bytes(lp: int, d: int, itemsize: int, rope: bool,
                     chunk: int, span: int,
                     d_v: "int | None" = None) -> int:
    """VMEM the backward of a long resident head needs, q walked
    ``chunk`` rows at a time on the grid: K, V, dk and dv as the
    pipeline's double-buffered blocks, the fp32 dk/dv sums (and the
    rotated K) as scratch, the chunk's streams, and three fp32 score
    tiles of the widest pair.  Fitted to the compiler's own accounting:
    at 8192 rows of 192/128 in bf16, 512-row chunks and 2048-key spans
    Mosaic names 48.9 MB where this gives 50 (PERF.md, PR 28)."""
    wide, vwide = _ceil_to(d, _LANES), _ceil_to(d_v or d, _LANES)
    row, vrow = lp * wide, lp * vwide
    held = 2 * 2 * (row + vrow) * itemsize     # k v dk dv, two buffers
    scratch = (row + vrow) * 4 + (row * itemsize if rope else 0)
    tables = 2 * 2 * row * (2 if itemsize == 2 else 4) if rope else 0
    streams = 2 * 2 * chunk * ((wide + vwide) * itemsize   # q dq; do o
                               + _STATS_W * 4)             # lse, dlse
    tiles = 3 * chunk * span * 4
    return held + scratch + tables + streams + tiles


def _grid_block(l: int, itemsize: int) -> int:
    """The grid walk's default block edge: :func:`_default_block`, capped
    at 512 for fp32 activations — the fused backward at 1024-blocks
    sits at the 16 MB scoped-VMEM cliff in fp32: Mosaic refuses it
    without rope tables, and the table blocks push it further over
    (16.93 MB on the O0 L2048 train step, round 4)."""
    block = _default_block(l)
    return min(block, 512) if itemsize == 4 else block


def _geometry(l: int, d: int, itemsize: int, causal: bool, rope: bool,
              has_bias: bool, d_v: "int | None" = None) -> _Geometry:
    """The geometry of a call with no explicit blocks, from what the
    call can see and the VMEM its chip has.  Resident when the mask is
    causal (nothing above the diagonal is then fetched, scored or
    masked), there is no key bias (its ``(1, block_k)`` lane blocks
    ride the grid), and the head fits at one of
    :data:`_RESIDENT_CHUNKS` — the one that pads the sequence least,
    the taller on a tie: under :data:`_RESIDENT_VMEM_BYTES` with the
    compiler's defaults, else under seven eighths of the chip's VMEM
    with a scoped limit of its own, the estimate and a quarter — all
    its rows if they are no more than :data:`_LONG_HEAD_SPAN`, else K,
    V and the dk/dv sums, with q walking the grid and meeting its keys
    a span at a time.  Else the grid walk with the blocks it always
    had: heads over that, any key mask, and every non-causal call."""
    if causal and not has_bias:
        chunks = sorted({min(c, _ceil_to(l, _LANES))
                         for c in _RESIDENT_CHUNKS},
                        key=lambda c: (_ceil_to(l, c), -c))
        for c in chunks:
            if _resident_bytes(_ceil_to(l, c), d, itemsize, rope, c,
                               d_v) <= _RESIDENT_VMEM_BYTES:
                return _Geometry(True, c)
        for c in chunks:
            lp = _ceil_to(l, c)
            if lp <= _LONG_HEAD_SPAN:
                span = None
                need = _resident_bytes(lp, d, itemsize, rope, c, d_v)
            else:
                span = _LONG_HEAD_SPAN
                need = _long_head_bytes(lp, d, itemsize, rope, c, span, d_v)
            if 5 * need // 4 <= 7 * _vmem_capacity() // 8:
                return _Geometry(True, c, span, 5 * need // 4)
    return _Geometry(False, _grid_block(l, itemsize))


@functools.lru_cache(maxsize=None)
def _warn_block_override(name: str, asked: int, got: int) -> None:
    """Once per distinct (name, asked, got): explicit block sizes are
    silently clamped/rounded to Mosaic tile granularity, which changes
    the blocking a tuner asked for — surface it (ADVICE r2)."""
    import warnings
    warnings.warn(
        f"flash_attention: {name}={asked} adjusted to {got} "
        f"(clamped to the padded sequence length and rounded to Mosaic "
        f"tile granularity: block_q to a multiple of 8, block_k to a "
        f"multiple of 128 — sub-128 k blocks miscompile on TPU)",
        stacklevel=3)


def flash_attention(q, k, v, *, causal=False, kv_mask=None, scale=None,
                    block_q=None, block_k=None, return_lse=False,
                    layout="blhd", rope=None, window=None):
    """Blockwise exact attention, ``(B, L, H, D)`` convention.

    ``v`` may have a head width of its own, ``(B, L, H, Dv)`` (latent
    attention scores at 192 and sums values at 128): the output and
    ``dv`` are then ``Dv`` wide, and every kernel streams v, o and do
    at that width, with no padding to ``D``.

    ``k`` and ``v`` may have fewer heads than ``q`` (grouped KV heads,
    ``Hkv`` dividing ``H``): q head ``h`` reads KV head ``h // (H /
    Hkv)``.  The kernels fetch the shared head through their K/V index
    maps, and ``dk``/``dv`` come back at ``Hkv`` heads, each q head's
    share summed over its group in fp32 outside the kernels.

    ``layout="bhld"`` instead takes/returns ``(B, H, L, D)`` — the
    transpose-free fast path for models whose projections emit
    head-major tensors (the relayout to the kernel's row view becomes a
    pure reshape; output and gradients likewise).  The logsumexp stays
    ``(B, L, H)`` in either layout.

    ``rope=(cos, sin)`` (tables from
    :func:`apex_tpu.ops.rope.rope_tables`, ``(B, L, 1, D/2)`` or
    ``(B, L, D/2)``) applies the rotary embedding to q and k *inside*
    the kernel: pass q/k unrotated, the rotation happens on VMEM blocks
    and the rotated tensors never exist in HBM (gradients are returned
    w.r.t. the unrotated inputs).  The tables themselves are treated as
    **non-differentiable position constants**: their cotangents are
    zero, so a learned-rotary variant differentiating through cos/sin
    would silently get zero table gradients — rotate outside the kernel
    for that case.  Requires self-attention (``Lq == Lk``).  With bf16
    activations the tables are cast to bf16 — the extra table rounding
    is the same class as the bf16 q/k storage itself (the fallback
    paths rotate in fp32 either way).

    Equivalent to the jnp reference path in :mod:`apex_tpu.attention`
    (scores never materialized; fp32 softmax; masked rows emit zeros).
    ``kv_mask``: optional ``(B, Lk)`` bool key mask (True = attend).
    With ``block_q``/``block_k`` left ``None`` the geometry comes from
    the call's own shape (:func:`_geometry`): a causal call without a
    key mask whose head fits VMEM keeps the head's rows resident and
    scores each block of q rows against all its visible keys at once;
    every other call walks the grid with blocks by sequence length —
    512, growing to 1024 at L >= 2048 (:func:`_default_block`).  Explicit ``block_q``/``block_k``
    are the caller's grid blocks: clamped to the (padded) length, then
    rounded up to Mosaic tile granularity (``block_q`` to a multiple of
    8, ``block_k`` to a multiple of 128 — narrower k blocks miscompile
    on hardware).
    Cross-attention (``Lq != Lk``) routes to an equivalent jnp path — the
    blockwise kernel packs q and k/v with one shared sequence length.

    With ``return_lse`` also returns the per-row logsumexp ``(B, L, H)``
    fp32 (``NEG_INF`` for fully-masked rows) — differentiable, so partial
    results can be merged online (ring attention's carry).

    ``window=W`` (with ``causal``) lets query ``i`` see keys ``i - W < j
    <= i`` only.  Its kernels (``flash_window_fwd`` / ``flash_window_bwd``)
    visit the band's key blocks and no other: each block of q rows meets
    the ``ceil((W - 1) / block) + 1`` key blocks that end at its own in
    one pair, K and V held whole a KV head and dk, dv summed over its
    group inside the backward.  ``block_q`` / ``block_k`` (the larger)
    set the block; there is no key mask and no logsumexp on this path.
    ``W >= L`` is the causal call.
    """
    if layout not in ("blhd", "bhld"):
        raise ValueError(f"unknown layout {layout!r}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    seq_ax = 2 if layout == "bhld" else 1
    head_ax = 3 - seq_ax
    b, l = q.shape[0], q.shape[seq_ax]
    d_head = q.shape[-1]
    if k.shape[-1] != d_head:
        raise ValueError(f"q and k score against each other and share "
                         f"one head width, got {d_head} and {k.shape[-1]}")
    heads, kv_heads = q.shape[head_ax], k.shape[head_ax]
    if v.shape[head_ax] != kv_heads or heads % kv_heads:
        raise ValueError(f"k and v share one count of KV heads that "
                         f"divides q's {heads}, got {kv_heads} and "
                         f"{v.shape[head_ax]}")
    if rope is not None and k.shape[seq_ax] != l:
        raise ValueError("rope requires self-attention (Lq == Lk): q and "
                         "k share one position table")
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError(f"a window (got {window}) is a positive count "
                             f"of keys and requires causal attention")
        if kv_mask is not None or return_lse:
            raise ValueError("a window takes no key mask and returns no "
                             "logsumexp")
        window = None if int(window) >= k.shape[seq_ax] else int(window)
    if k.shape[seq_ax] != l or (not on_tpu() and jax.typeof(q).vma):
        # Cross-attention (blockwise packing needs one shared length) and
        # interpret-mode-under-shard_map (a VMA propagation limitation in
        # jax's pallas interpreter; compiled Mosaic is unaffected) route
        # to the equivalent jnp math, which speaks (B, L, H, D).
        if k.shape[seq_ax] != l and return_lse:
            raise ValueError("return_lse requires Lq == Lk (kernel path)")
        if layout == "bhld":
            qb, kb, vb = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
        else:
            qb, kb, vb = q, k, v
        if rope is not None:
            from apex_tpu.ops.rope import apply_rope_tables
            qb, kb = apply_rope_tables(qb, kb, rope)
        out = _jnp_attention(qb, kb, vb, causal=causal, kv_mask=kv_mask,
                             scale=float(scale), return_lse=return_lse,
                             window=window)
        if layout == "bhld":
            if return_lse:
                return jnp.moveaxis(out[0], 1, 2), out[1]
            return jnp.moveaxis(out, 1, 2)
        return out
    if window is not None:
        return _windowed(q, k, v, scale, window, block_q, block_k, layout,
                         rope)
    explicit = (block_q, block_k)
    itemsize = jnp.dtype(q.dtype).itemsize
    geo = _geometry(l, d_head, itemsize, bool(causal), rope is not None,
                    kv_mask is not None, v.shape[-1])
    # Explicit blocks are the caller's blocks: the grid walks them.
    resident = geo.resident and explicit == (None, None)
    default = (geo.block if resident
               else _grid_block(l, itemsize))
    if block_q is None:
        block_q = default
    if block_k is None:
        block_k = default
    block_q = min(block_q, _ceil_to(l, 128))
    block_k = min(block_k, _ceil_to(l, 128))
    # Mosaic tile granularity: the score tile is (block_q, block_k), so
    # block_q rides the 8-sublane dim and block_k the 128-lane dim.
    # Sub-lane-width k blocks (block_k < 128) compile but produce wrong
    # numerics on hardware (interpret mode hides it) and would waste the
    # VPU anyway — round both up to legal sizes.
    block_q = max(8, _ceil_to(int(block_q), 8))
    block_k = max(_LANES, _ceil_to(int(block_k), _LANES))
    for name, asked, got in (("block_q", explicit[0], block_q),
                             ("block_k", explicit[1], block_k)):
        if asked is not None and int(asked) != got:
            _warn_block_override(name, int(asked), got)
    if kv_mask is not None:
        bias = jnp.where(kv_mask, 0.0, NEG_INF).astype(jnp.float32)
    else:
        # Placeholder keeping the kernel input list static; with
        # has_bias=False the kernels never read it (no bias add, no
        # zeroing wheres).
        bias = jnp.zeros((b, l), jnp.float32)
    # _prep pads keys with a NEG_INF bias column; that only reaches the
    # kernels on the bias path, so non-causal padded lengths must take
    # it even without a user mask (else zero-padded keys attend and
    # inflate the normalizer).  Causal is safe bias-free: every padded
    # key sits at kpos >= l > qpos for every real row.
    padded = l % math.lcm(int(block_q), int(block_k)) != 0
    has_bias = kv_mask is not None or (padded and not causal)
    rope_mode = None
    cos_t = sin_t = jnp.zeros((), jnp.float32)   # unused placeholder
    if rope is not None:
        cos_t, sin_t = _kernel_tables(rope, q, b, l, d_head)
        lp = _ceil_to(l, math.lcm(int(block_q), int(block_k)))
        per_side = 2 * lp * d_head * cos_t.dtype.itemsize
        # A resident head holds its tables whole (_resident_bytes counts
        # them); the budget is the grid walk's.
        rope_mode = ("resident" if resident
                     or per_side <= _ROPE_RESIDENT_MAX_BYTES else "stream")
    return _flash(q, k, v, bias, cos_t, sin_t, float(scale), bool(causal),
                  int(block_q), int(block_k), has_bias, rope_mode, layout,
                  resident, bool(return_lse),
                  geo.span if resident else None,
                  geo.vmem_limit if resident else None)


def _kernel_tables(rope, q, b, l, d_head):
    """The kernels' ``(cos_full, sin_signed)`` tables ``(B, L, D)`` from a
    public ``rope`` argument, in q's dtype where that is bf16."""
    from apex_tpu.ops.rope import KernelRopeTables, rope_kernel_tables
    table_dtype = (jnp.bfloat16 if q.dtype == jnp.bfloat16
                   else jnp.float32)
    if isinstance(rope, KernelRopeTables):
        # Prebuilt kernel-format tables: callers with scanned/remat
        # layer bodies construct them once per step so the
        # concat/sign-fold/cast stays out of the compiled layer loop.
        cos_t = rope.cos_full.astype(table_dtype)
        sin_t = rope.sin_signed.astype(table_dtype)
        if cos_t.shape[0] != b:
            cos_t = jnp.broadcast_to(cos_t, (b,) + cos_t.shape[1:])
            sin_t = jnp.broadcast_to(sin_t, (b,) + sin_t.shape[1:])
        return cos_t, sin_t
    return rope_kernel_tables(rope[0], rope[1], b, l, d_head, table_dtype)


def _windowed(q, k, v, scale, window, block_q, block_k, layout, rope):
    """:func:`flash_attention`'s windowed kernel path (see there)."""
    seq_ax = 2 if layout == "bhld" else 1
    b, l = q.shape[0], q.shape[seq_ax]
    asked = max(block_q or 0, block_k or 0) or _WINDOW_BLOCK
    block = min(_ceil_to(int(asked), _LANES), _ceil_to(l, _LANES))
    cos_t = sin_t = jnp.zeros((), jnp.float32)   # unused placeholder
    if rope is not None:
        cos_t, sin_t = _kernel_tables(rope, q, b, l, q.shape[-1])
    return _flash_window(q, k, v, cos_t, sin_t, float(scale), window, block,
                         rope is not None, layout)
