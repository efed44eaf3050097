"""Mixture of experts: one router, and a layer that is told which experts
it holds.

Beyond the reference (apex predates MoE — SURVEY.md section 2 "NOT
present"), but part of the parallelism surface (dp/tp/pp/sp/ep) this
framework validates, and the feed-forward of the DeepSeek-V3-shaped
decoder (:mod:`apex_tpu.models.deepseek_v3`).

- :func:`route` turns ``(T, E)`` router logits into each token's ``k``
  experts and their weights.  Its options cover the published routers:
  softmax or sigmoid scores, a correction bias that moves the choice
  and not the weight, weights renormalised over the chosen or not, a
  scaling factor.  ``k = 1`` with softmax scores is the switch router.
- :func:`moe_apply` runs the experts *held here* — ``range(first, first
  + held)`` of ``n_experts`` — on the (token, expert) pairs that fall on
  them.  There is no ``(T, E, C)`` one-hot, no capacity and no dropped
  token: the pairs are sorted by expert, the experts run as grouped
  matrix products over the sorted rows (:func:`grouped_matmul`), and
  each token sums its weighted results.  What the experts held
  elsewhere would add is left out: on one chip of an expert-parallel
  deployment that partial sum is the layer's output, and nothing stands
  in for the absent chips.  With an ``axis_name`` (inside ``shard_map``,
  tokens and experts both sharded over it) the rows travel to the ranks
  that hold their experts and back by ``lax.all_to_all``, and every
  expert is held somewhere.

Shapes are static whatever the routing: a buffer has room for every
pair that could fall on it and for aligning each expert's run to the
grouped product's row tile, and the product skips the tiles no run
covers.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.generate import greedy_argmax
from apex_tpu.ops import on_tpu, use_pallas
from apex_tpu.utils.profiling import MOE_DISPATCH, MOE_EXPERTS


class Routing(NamedTuple):
    """What :func:`route` decided for ``T`` tokens."""

    weights: jax.Array   #: (T, k) float32, what each chosen expert counts
    experts: jax.Array   #: (T, k) int32, ids in ``range(n_experts)``
    scores: jax.Array    #: (T, E) float32, the router's scores of all


def _top_k(choice: jax.Array, k: int) -> jax.Array:
    """Ids of the ``k`` largest of each row, largest first, the lowest
    index first among equals: ``k`` rounds of
    :func:`~apex_tpu.models.generate.greedy_argmax`, whose tie-break no
    refusion can move (a floating ``top_k`` is the ``det-tie-argmax``
    hazard; a router tie that flips between two consumers would send a
    token to one expert and weigh it as another)."""
    lanes = lax.broadcasted_iota(jnp.int32, choice.shape, choice.ndim - 1)
    picks = []
    for _ in range(k):
        e = greedy_argmax(choice)
        picks.append(e)
        choice = jnp.where(lanes == e[..., None], -jnp.inf, choice)
    return jnp.stack(picks, axis=-1)


def route(logits: jax.Array, k: int = 1, *, scoring: str = "softmax",
          bias: Optional[jax.Array] = None, renormalize: bool = False,
          scale: float = 1.0) -> Routing:
    """Route ``(T, E)`` router logits.

    ``scoring``: ``"softmax"`` over the experts or ``"sigmoid"`` of each
    logit, in float32.  ``bias`` ``(E,)`` is added to the scores for the
    *choice* of the ``k`` experts only (DeepSeek-V3's ``noaux_tc``
    correction bias); the weights are the chosen scores without it, and
    no gradient reaches it.  ``renormalize`` divides the chosen weights
    by their sum (``norm_topk_prob``); ``scale`` multiplies them
    (``routed_scaling_factor``).  The switch router is ``k=1``,
    softmax, neither option."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown scoring {scoring!r}")
    logits = logits.astype(jnp.float32)
    scores = (jax.nn.softmax(logits, axis=-1) if scoring == "softmax"
              else jax.nn.sigmoid(logits))
    choice = lax.stop_gradient(scores)
    if bias is not None:
        choice = choice + lax.stop_gradient(bias.astype(jnp.float32))
    experts = _top_k(choice, k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalize:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return Routing(weights * scale, experts, scores)


def load_balance_loss(routing: Routing) -> jax.Array:
    """The switch load-balancing loss (Switch Transformer eq. 4-6): the
    share of (token, expert) pairs each expert got times its mean score,
    summed and scaled by ``E``; 1 when both are even."""
    n_experts = routing.scores.shape[-1]
    onehot = jax.nn.one_hot(routing.experts, n_experts, dtype=jnp.float32)
    frac_routed = jnp.mean(jnp.sum(onehot, axis=1), axis=0) \
        / routing.experts.shape[-1]
    frac_score = jnp.mean(routing.scores, axis=0)
    return n_experts * jnp.sum(frac_routed * frac_score)


# ---------------------------------------------------------------------------
# the grouped matrix product
# ---------------------------------------------------------------------------

def _tile(n: int, cap: int) -> int:
    """The largest multiple of 128 up to ``cap`` that divides ``n``, or
    ``n`` itself."""
    for t in range(cap - cap % 128, 0, -128):
        if n % t == 0:
            return t
    return n


def _gmm_tiles(m: int, k: int, n: int) -> Tuple[int, int, int]:
    """Tiles of one megablox call.  On the v5e, at 49152 x 2048 x 768
    with an eighth of the rows in groups, 512 x 1024 x 768 ran the
    gated feed-forward's forward and backward in 3.8 ms against 4.1 at
    512 x 512 and 19.6 at the kernel's default 128 cubed; 2048-deep
    tiles overflow VMEM in the transposed product (PERF.md, PR 27)."""
    return _tile(m, 512), _tile(k, 1024), _tile(n, 1024)


def _megablox():
    """jax's grouped-product kernels (the package rebinds the name ``gmm``
    to its own differentiable wrapper, so the module is asked for)."""
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm_call(lhs, rhs, group_sizes, *, transpose_rhs=False):
    mb = _megablox()
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    out = mb.gmm(lhs, rhs, group_sizes, lhs.dtype,
                 _gmm_tiles(lhs.shape[0], lhs.shape[1], n),
                 transpose_rhs=transpose_rhs, interpret=not on_tpu())
    # the kernel never visits the rows past the last group: what it
    # leaves there is not a number to be carried on, forward or backward
    covered = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes)
    return jnp.where(covered, out, jnp.zeros((), out.dtype))


@jax.custom_vjp
def _gmm(lhs, rhs, group_sizes):
    return _gmm_call(lhs, rhs, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes):
    return _gmm_call(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(saved, grad):
    mb = _megablox()
    lhs, rhs, group_sizes = saved
    m, k = lhs.shape
    grad = grad.astype(lhs.dtype)
    d_lhs = _gmm_call(grad, rhs, group_sizes, transpose_rhs=True)
    d_rhs = mb.tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                    _gmm_tiles(m, k, rhs.shape[2]),
                    num_actual_groups=rhs.shape[0], interpret=not on_tpu())
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for ``lhs`` ``(M, K)`` whose rows
    are sorted by group, ``rhs`` ``(G, K, N)`` and ``group_sizes`` ``(G,)``
    int32 summing to at most ``M``.  Rows past the last group belong to
    no expert: the caller masks what comes out there.

    On the chip this is jax's own megablox kernels (``gmm`` forward and
    for ``d_lhs``, ``tgmm`` for ``d_rhs``), which visit only the row
    tiles a group covers; elsewhere ``jax.lax.ragged_dot``.  On the v5e
    megablox ran the cell's gated feed-forward a quarter faster than
    ``ragged_dot`` (PERF.md, PR 27)."""
    if not use_pallas():
        return lax.ragged_dot(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    pad = (-m) % 128
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32))[:m]


def gated_ffn(params: Any, rows: jax.Array,
              group_sizes: jax.Array) -> jax.Array:
    """The grouped gated feed-forward ``down(silu(gate(x)) * up(x))``:
    ``params`` holds ``gate`` and ``up`` ``(held, d, f)`` and ``down``
    ``(held, f, d)``, stacked over the experts held."""
    gate = grouped_matmul(rows, params["gate"].astype(rows.dtype),
                          group_sizes)
    up = grouped_matmul(rows, params["up"].astype(rows.dtype), group_sizes)
    return grouped_matmul(jax.nn.silu(gate) * up,
                          params["down"].astype(rows.dtype), group_sizes)


# ---------------------------------------------------------------------------
# sorting rows to their experts and back
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _move_rows(x, take, taken, back, came_back):
    """``where(taken, x[take % len(x)], 0)``: rows of ``x`` moved to where
    ``take`` wants them, nought where nothing is wanted.  ``back`` and
    ``came_back`` say the same of the way home (for every row of the
    result's cotangent that a row of ``x`` is owed, where it lies), so
    the cotangent returns by a gather and a sum over the ``len(back) /
    len(x)`` places a row went to, not by a scatter-add (five times
    slower on the v5e at 49152 rows of 2048, PERF.md PR 27)."""
    rows = jnp.take(x, take % x.shape[0], axis=0)
    return jnp.where(taken[:, None], rows, jnp.zeros((), x.dtype))


def _move_rows_fwd(x, take, taken, back, came_back):
    return _move_rows(x, take, taken, back, came_back), \
        (x.shape[0], back, came_back)


def _move_rows_bwd(saved, grad):
    n, back, came_back = saved
    home = jnp.where(came_back[:, None], jnp.take(grad, back, axis=0),
                     jnp.zeros((), grad.dtype))
    home = home.reshape(-1, n, *grad.shape[1:])
    if home.shape[0] > 1:
        home = jnp.sum(home.astype(jnp.float32), axis=0).astype(grad.dtype)
    return home.reshape(n, *grad.shape[1:]), None, None, None, None


_move_rows.defvjp(_move_rows_fwd, _move_rows_bwd)


def _row_tile(pairs: int) -> int:
    """Rows each expert's run is aligned to in the sorted buffer: the
    grouped product's row tile at the cell's size (a run then starts on
    a tile and an expert of up to 512 rows is one visit, whatever the
    routing: the step's time does not move with it), small where the
    buffers are."""
    return 512 if pairs >= 8192 else 128 if pairs >= 1024 else 8


def _grouped_apply(expert_fn, expert_params, x, ids, held: int):
    """Run the experts held here on their rows.  Pair ``i`` is row
    ``x[i % len(x)]`` for local expert ``ids[i]``, or for none of them
    where ``ids[i] == held``.  The pairs are sorted by expert into a
    buffer in which every expert's run starts on a row tile (the gaps
    are zero rows of that expert, which add nothing).  Returns the
    pairs' results in the order they came, zero rows for the pairs of no
    expert here, and the held experts' loads."""
    n = ids.shape[0]
    tile = _row_tile(n)
    room = n + held * tile
    with jax.named_scope(MOE_DISPATCH):
        order = jnp.argsort(ids, stable=True).astype(jnp.int32)
        experts = jnp.arange(held, dtype=ids.dtype)
        loads = jnp.sum(ids[:, None] == experts, axis=0, dtype=jnp.int32)
        sizes = -(-loads // tile) * tile               # aligned runs
        starts = jnp.cumsum(loads) - loads             # in sorted order
        slots = jnp.cumsum(sizes) - sizes              # in the buffer
        # where each pair lies in the buffer (pairs of no expert: nowhere)
        here = ids < held
        mine = jnp.minimum(ids, held - 1)
        lies = jnp.clip(slots[mine] + jnp.argsort(order).astype(jnp.int32)
                        - starts[mine], 0, room - 1)
        # which pair each row of the buffer holds (the gaps: none)
        row = jnp.arange(room, dtype=jnp.int32)
        run = jnp.minimum(jnp.searchsorted(slots + sizes, row, side="right"),
                          held - 1).astype(jnp.int32)
        filled = row - slots[run] < loads[run]
        holds = order[jnp.clip(starts[run] + row - slots[run], 0, n - 1)]
        rows = _move_rows(x, holds, filled, lies, here)
    with jax.named_scope(MOE_EXPERTS):
        out = expert_fn(expert_params, rows, sizes)
    with jax.named_scope(MOE_DISPATCH):
        return _move_rows(out, lies, here, holds, filled), loads


def _pairs(per_token):
    """``(T, k)`` of a routing as the choice-major ``(k * T,)`` the
    buffers are numbered by."""
    return per_token.T.reshape(-1)


def _combine(results, weights, dtype):
    """Each token's weighted sum over its ``k`` pairs, in float32."""
    t, k = weights.shape
    y = jnp.sum(results.reshape(k, t, -1).astype(jnp.float32)
                * weights.T[..., None], axis=0)
    return y.astype(dtype)


def moe_apply(
    expert_fn: Callable[[Any, jax.Array, jax.Array], jax.Array],
    expert_params: Any,
    x: jax.Array,
    routing: Routing,
    *,
    n_experts: int,
    first: int = 0,
    axis_name: Optional[str] = None,
) -> Tuple[jax.Array, dict]:
    """The routed experts' part of a mixture-of-experts layer.

    Args:
      expert_fn: ``(expert_params, rows (N, d), group_sizes (held,)) ->
        (N, d_out)``, grouped: the first ``group_sizes[0]`` rows belong
        to the first expert held, and so on (:func:`gated_ffn`).
      expert_params: the experts held here, stacked on a leading axis of
        length ``held``.
      x: ``(T, d)`` tokens.
      routing: :func:`route` over all ``n_experts`` for these tokens.
      first: without ``axis_name``, the experts held are ``range(first,
        first + held)``; pairs routed elsewhere add nothing.
      axis_name: inside ``shard_map``, with tokens and experts sharded
        over this axis (rank ``r`` holds ``range(r * held, (r + 1) *
        held)``, ``n_experts == ranks * held``): rows are exchanged with
        ``lax.all_to_all`` and every pair is served.

    Returns ``(y, stats)``: ``y`` ``(T, d_out)`` in ``x``'s dtype, to be
    added to the residual (and to the shared experts) outside, and
    ``stats`` — ``pairs`` served by the experts held here, ``load_peak``
    the fullest held expert's load over their mean.  No pair is dropped.
    """
    held = jax.tree.leaves(expert_params)[0].shape[0]
    t, k = routing.experts.shape
    flat = _pairs(routing.experts)
    if axis_name is None:
        if not 0 <= first <= n_experts - held:
            raise ValueError(f"experts {first}..{first + held} are not "
                             f"among {n_experts}")
        with jax.named_scope(MOE_DISPATCH):
            here = (flat >= first) & (flat < first + held)
            ids = jnp.where(here, flat - first, held)
        results, loads = _grouped_apply(expert_fn, expert_params, x, ids,
                                        held)
    else:
        results, loads = _exchanged(expert_fn, expert_params, x, flat, k,
                                    held, n_experts, axis_name)
    with jax.named_scope(MOE_DISPATCH):
        y = _combine(results, routing.weights, x.dtype)
    pairs = jnp.sum(loads)
    stats = {"pairs": pairs,
             "load_peak": jnp.max(loads) * held
             / jnp.maximum(pairs, 1).astype(jnp.float32)}
    return y, lax.stop_gradient(stats)


def _exchanged(expert_fn, expert_params, x, flat, k, held, n_experts,
               axis_name):
    """The pairs' results with the experts spread over ``axis_name``:
    sort the local pairs by expert (so by the rank that holds it), pack
    each rank's run into its slot of a ``(ranks, room, d)`` buffer,
    exchange, run the experts held here on what arrived, exchange back
    and unpack.  ``room`` is the most pairs these tokens can send one
    rank, ``T * min(k, held)``."""
    ranks = lax.axis_size(axis_name)
    if ranks * held != n_experts:
        raise ValueError(f"{ranks} ranks of {held} experts are not the "
                         f"router's {n_experts}")
    n, t = flat.shape[0], x.shape[0]
    room = t * min(k, held)
    with jax.named_scope(MOE_DISPATCH):
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        experts = flat[order]
        rank = experts // held                                  # (n,)
        counts = jnp.sum(rank[:, None] == jnp.arange(ranks), axis=0)
        starts = jnp.cumsum(counts) - counts
        place = jnp.arange(n) - starts[rank]       # within its rank's slot
        slot = starts[:, None] + jnp.arange(room)[None, :]  # (ranks, room)
        taken = jnp.arange(room)[None, :] < counts[:, None]
        pair = jnp.minimum(slot, n - 1)
        send = jnp.where(taken[..., None], x[order[pair] % t],
                         jnp.zeros((), x.dtype))
        send_ids = jnp.where(taken, experts[pair] % held, held)
        got = lax.all_to_all(send, axis_name, 0, 0)
        got_ids = lax.all_to_all(send_ids, axis_name, 0, 0)
    out, loads = _grouped_apply(expert_fn, expert_params,
                                got.reshape(ranks * room, -1),
                                got_ids.reshape(ranks * room), held)
    with jax.named_scope(MOE_DISPATCH):
        back = lax.all_to_all(out.reshape(ranks, room, -1), axis_name, 0, 0)
        return back[rank, place][jnp.argsort(order)], loads
