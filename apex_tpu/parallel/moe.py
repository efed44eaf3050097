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

Shapes are static whatever the routing.  The sorted buffer has room for
every pair that could fall on the experts held and for aligning each
expert's run to the grouped product's row tile, but it is never formed
whole: it is walked in *windows* of ``2 x expected + held x tile`` rows
(``expected`` the pairs the held experts get when routing is even;
:func:`_window_rows`), one traced body under a ``lax.while_loop`` that
runs the first window and ends with the last one a run reaches.  Even
routing fills the first window only (16 of 128 experts and 49152 pairs:
one window of 20480 rows where the buffer has 57344), and so does any
load up to twice the expected one: the step's time is then the same; a
load of any skew runs as many windows as it fills, so nothing is
dropped and nothing approximated.  A layer that holds every expert, or
whose buffer is no longer than a window, has one window: the same body
once, with no loop.  ``stats["windows"]`` counts the windows run.
A window's combine into the tokens' rows, and the way home of its
cotangent, read on the chip only the rows of the pairs held in the
window (:mod:`apex_tpu.ops.pallas.moe_combine`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.generate import greedy_argmax
from apex_tpu.ops import on_tpu, use_pallas
from apex_tpu.ops.pallas.moe_combine import combine_geometry, moe_combine
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

def _taken_rows(x, take, taken):
    """``where(taken, x[take % len(x)], 0)``: rows of ``x`` moved to where
    ``take`` wants them, nought where nothing is wanted."""
    rows = jnp.take(x, take % x.shape[0], axis=0)
    return jnp.where(taken[:, None], rows, jnp.zeros((), x.dtype))


def _row_tile(pairs: int) -> int:
    """Rows each expert's run is aligned to in the sorted buffer: the
    grouped product's row tile at the cell's size (a run then starts on
    a tile and an expert of up to 512 rows is one visit, so within a
    window the step's time does not move with the routing; it moves by
    a window's time when the held load passes a window's end), small
    where the buffers are."""
    return 512 if pairs >= 8192 else 128 if pairs >= 1024 else 8


def _window_rows(pairs: int, held: int, expected: int) -> int:
    """Rows of one window of the sorted buffer: twice the pairs the held
    experts get when routing is even, and a tile of alignment for each
    of them; the whole buffer where that is no less."""
    tile = _row_tile(pairs)
    return min(pairs + held * tile,
               -(-2 * expected // tile) * tile + held * tile)


class _Windows(NamedTuple):
    """The sorted buffer, window by window (``w`` windows of ``r`` rows,
    ``n`` pairs)."""

    holds: jax.Array    #: (w, r) which pair each row holds
    filled: jax.Array   #: (w, r) whether it holds one (the gaps: none)
    sizes: jax.Array    #: (w, held) rows of each expert's run in the window
    reached: jax.Array  #: () windows to run: the first, and to the last
                        #: that holds a row
    lies: jax.Array     #: (n,) the buffer row each pair lies in
    here: jax.Array     #: (n,) whether it lies in any

    def pairs_of(self, first):
        """For every pair, its row in the window that starts at buffer
        row ``first``, and whether it lies in that window."""
        at = self.lies - first
        return at, self.here & (at >= 0) & (at < self.holds.shape[1])


def _zeros(shape, dtype, *likes):
    """Zeros that vary over the mesh axes the ``likes`` vary over: under
    ``shard_map`` ``lax.while_loop`` holds its carry and ``custom_vjp``
    its cotangents to one type, and fresh zeros vary over nothing."""
    zeros = jnp.zeros(shape, dtype)
    vma = tuple(frozenset().union(*(
        jax.typeof(a).vma for a in jax.tree.leaves(likes))))
    return lax.pcast(zeros, vma, to="varying") if vma else zeros


def _each_window(body, carry, windows: _Windows):
    """``carry = body(carry, first row, holds, filled, sizes)`` for the
    first window and every further one a run reaches: the runs are
    packed from row 0, so those are the first ``reached`` windows and
    the loop ends with them.  A window past them costs nothing; a
    buffer of one window has no loop."""
    n_windows, rows = windows.holds.shape
    if n_windows == 1:
        return body(carry, 0, windows.holds[0], windows.filled[0],
                    windows.sizes[0])

    def step(state):
        w, carry = state
        with jax.named_scope(MOE_DISPATCH):
            window = (w * rows, windows.holds[w], windows.filled[w],
                      windows.sizes[w])
        return w + 1, body(carry, *window)
    return lax.while_loop(lambda state: state[0] < windows.reached, step,
                          (jnp.zeros((), jnp.int32), carry))[1]


def _window_vjp(window_fn, rows, sizes, closed):
    """What the experts make of a window's rows, and its transpose."""
    return jax.vjp(lambda rows, *closed: window_fn(rows, sizes, *closed),
                   rows, *closed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk(window_fn, closed, x, weights, windows: _Windows):
    """Each row of ``x``'s weighted sum over its pairs' results, the
    buffer walked window by window: a window gathers its rows of ``x``,
    runs the experts on them (``window_fn(rows, sizes, *closed)``) and
    adds to every row of ``x`` the weighted results of its pairs that
    lie in the window (:func:`_combine_block` says by which route)."""
    return _walk_fwd(window_fn, closed, x, weights, windows)[0]


def _walk_fwd(window_fn, closed, x, weights, windows):
    t, kept = x.shape[0], []

    def body(y, first, holds, filled, sizes):
        with jax.named_scope(MOE_DISPATCH):
            rows = _taken_rows(x, holds, filled)
        if windows.holds.shape[0] == 1:
            # outside any loop a window keeps what autodiff keeps of it
            out, vjp = _window_vjp(window_fn, rows, sizes, closed)
            kept.append((out, vjp))
        else:
            out = window_fn(rows, sizes, *closed)
        with jax.named_scope(MOE_DISPATCH):
            at, inside = windows.pairs_of(first)
            block = _combine_block(y, out, at)
            if block:
                return moe_combine(y, out, at, inside, weights, block=block)
            results = _taken_rows(out, at, inside)
            return y + _combine(results, weights, t, jnp.float32)

    with jax.named_scope(MOE_DISPATCH):
        made = jax.eval_shape(
            window_fn, _taken_rows(x, windows.holds[0], windows.filled[0]),
            windows.sizes[0], *closed)
        y = _zeros((t,) + made.shape[1:], jnp.float32, closed, x, weights)
    y = _each_window(body, y, windows)
    return y.astype(x.dtype), \
        (closed, x, weights, windows, kept.pop() if kept else None)


# inline: a trace that layers of one shape share, and the scopes of each
@functools.partial(jax.jit, static_argnums=0, inline=True)
def _walk_bwd(window_fn, saved, grad):
    """The walk again.  Under the loop nothing of a window was kept but
    what it was made from: kept, every window's residuals would be held
    at once (by ``lax.scan`` the experts' weights among them, once a
    window, and the step of the kanana cell then asks for more than the
    chip's memory).  So each window gathers its rows of ``grad`` and of
    ``x``, runs the experts on them once more, and adds what it owes the
    rows of ``x``, the pairs' weights and the experts' weights to three
    sums.  What a row of ``x`` is owed comes home by a gather and a sum
    over the places it went to, not by a scatter-add (five times slower
    on the v5e at 49152 rows of 2048, PERF.md PR 27): on the chip the
    kernel ``moe_combine``, which reads the rows of the pairs held in the
    window only (:func:`_combine_block`).  The first two
    sums are float32.  The experts' weights' is in their own dtype, each
    addition made in float32: the grouped product has rounded a window's
    part to that dtype before it is seen here, so a float32 sum would
    differ only where one expert's run spans three windows or more (a
    run of more rows than a window; two parts add to the same number
    either way), and in the kanana cell it costs 2.1 ms a layer for its
    zeros, its traffic and its cast (PERF.md PR 31)."""
    closed, x, weights, windows, kept = saved
    t = x.shape[0]

    def add(sum_, part):
        wide = jnp.promote_types(sum_.dtype, jnp.float32)
        return (sum_.astype(wide) + part.astype(wide)).astype(sum_.dtype)

    def body(owed, first, holds, filled, sizes):
        with jax.named_scope(MOE_DISPATCH):
            d_y = jnp.take(grad, holds % t, axis=0).astype(jnp.float32)
            d_out = d_y * jnp.where(filled, weights[holds], 0.0)[:, None]
            rows = None if kept else _taken_rows(x, holds, filled)
        out, vjp = kept or _window_vjp(window_fn, rows, sizes, closed)
        d_rows, *d_closed = vjp(d_out.astype(out.dtype))
        with jax.named_scope(MOE_DISPATCH):
            at, inside = windows.pairs_of(first)
            counted = jnp.sum(out.astype(jnp.float32) * d_y, axis=-1)
            block = _combine_block(owed[1], d_rows, at)
            # the kernel adds what comes home to the sum itself
            home = moe_combine(owed[1], d_rows, at, inside, block=block) \
                if block else jnp.sum(_taken_rows(d_rows, at, inside).reshape(
                    -1, t, *x.shape[1:]).astype(jnp.float32), axis=0)
            d_weights = jnp.where(inside, counted[at % counted.shape[0]], 0.0)
            if not block:
                return jax.tree.map(add, owed, (tuple(d_closed), home,
                                                d_weights))
            d_closed, d_weights = jax.tree.map(
                add, (owed[0], owed[2]), (tuple(d_closed), d_weights))
            return d_closed, home, d_weights

    with jax.named_scope(MOE_DISPATCH):
        likes = (closed, x, weights, grad)
        owed = (tuple(_zeros(a.shape, a.dtype, *likes) for a in closed),
                _zeros(x.shape, jnp.float32, *likes),
                _zeros(weights.shape, jnp.float32, *likes))
    d_closed, d_x, d_weights = _each_window(body, owed, windows)
    return d_closed, d_x.astype(x.dtype), d_weights.astype(weights.dtype), \
        None


_walk.defvjp(_walk_fwd, _walk_bwd)


def _window(expert_fn, expert_params, rows, sizes):
    with jax.named_scope(MOE_EXPERTS):
        return expert_fn(expert_params, rows, sizes)


# inline, as _walk_bwd: the expert layers of a model are one trace
@functools.partial(jax.jit, static_argnums=(0, 5, 6), inline=True)
def _grouped_apply(expert_fn, expert_params, x, ids, weights, held: int,
                   expected: int):
    """Run the experts held here on their rows.  Pair ``i`` is row
    ``x[i % len(x)]`` for local expert ``ids[i]``, or for none of them
    where ``ids[i] == held``, and counts ``weights[i]``; ``expected`` of
    the pairs fall on a held expert when routing is even.  The pairs are
    sorted by expert into a buffer in which every expert's run starts on
    a row tile (the gaps are zero rows of that expert, which add
    nothing), and the buffer is walked in windows of
    :func:`_window_rows`: the first window and every further one the
    runs reach is the same body on its rows and the overlaps of the runs
    with it, and the others are not visited.  Returns each row's
    weighted sum over its pairs' results (the pairs of no expert here
    add nothing), the held experts' loads and the windows that ran."""
    n = ids.shape[0]
    tile = _row_tile(n)
    window = _window_rows(n, held, expected)
    n_windows = -(-(n + held * tile) // window)
    room = n_windows * window
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
        first = jnp.arange(n_windows, dtype=jnp.int32) * window
        # a run that straddles two windows splits on a tile: both are
        # multiples of it, and so is every overlap
        overlap = jnp.clip(
            jnp.minimum((slots + sizes)[None, :], first[:, None] + window)
            - jnp.maximum(slots[None, :], first[:, None]), 0, window)
        windows = _Windows(
            holds.reshape(n_windows, window),
            filled.reshape(n_windows, window), overlap,
            jnp.maximum(-(-jnp.sum(sizes) // window), 1), lies, here)
        rows = _taken_rows(x, windows.holds[0], windows.filled[0])
    # the walk and its backward pass differentiate with respect to the
    # weights and whatever else expert_fn closed over: both get them as
    # arguments
    window_fn, closed = jax.closure_convert(
        lambda rows, sizes: _window(expert_fn, expert_params, rows, sizes),
        rows, windows.sizes[0])
    return _walk(window_fn, tuple(closed), x, weights, windows), loads, \
        windows.reached


def _pairs(per_token):
    """``(T, k)`` of a routing as the choice-major ``(k * T,)`` the
    buffers are numbered by."""
    return per_token.T.reshape(-1)


def _combine(results, weights, t: int, dtype):
    """Each of ``t`` rows' weighted sum over its pairs, in float32;
    ``results`` and ``weights`` choice-major, as :func:`_pairs` numbers
    them."""
    y = jnp.sum(results.reshape(-1, t, results.shape[-1]).astype(jnp.float32)
                * weights.reshape(-1, t, 1), axis=0)
    return y.astype(dtype)


def _combine_block(acc, src, at):
    """Tokens a grid step of the kernel ``moe_combine`` takes in a
    window's combine of ``src``'s rows into ``acc`` (or the way home of
    its cotangent), or ``None``: where ``use_pallas()`` and
    :func:`~apex_tpu.ops.pallas.moe_combine.combine_geometry` allow, the
    kernel reads the rows of the pairs held in the window and nothing
    else; every other shape, and the CPU, takes the XLA form, a row for
    every (token, choice) pair summed over the choices (the kernel's
    oracle).  Both sum a token's terms in float32 from 0 in the order of
    its choices and then add that sum to ``acc``."""
    return combine_geometry(acc.shape[0], src.shape[-1], src.shape[0],
                            at.shape[0], src.dtype) if use_pallas() else None


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
    the fullest held expert's load over their mean, ``windows`` the
    windows of the sorted buffer that were run (1 unless the held load
    passes twice its expectation).  No pair is dropped.  Where the
    buffer is more than one window, ``expert_fn`` runs once more on each
    window in the backward pass.
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
        y, loads, windows = _grouped_apply(
            expert_fn, expert_params, x, ids, _pairs(routing.weights), held,
            -(-t * k * held // n_experts))
    else:
        results, loads, windows = _exchanged(
            expert_fn, expert_params, x, flat, k, held, n_experts, axis_name)
        with jax.named_scope(MOE_DISPATCH):
            y = _combine(results, _pairs(routing.weights), t, x.dtype)
    pairs = jnp.sum(loads)
    stats = {"pairs": pairs,
             "load_peak": jnp.max(loads) * held
             / jnp.maximum(pairs, 1).astype(jnp.float32),
             "windows": windows}
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
        once = _zeros((ranks * room,), jnp.float32, got_ids) + 1.0
    # of the ranks * room slots that arrive, t * k hold a pair when
    # routing is even (every rank sends its share); a slot is one row
    # and one pair, weighed where it came from
    out, loads, windows = _grouped_apply(
        expert_fn, expert_params, got.reshape(ranks * room, -1),
        got_ids.reshape(ranks * room), once, held, t * k)
    with jax.named_scope(MOE_DISPATCH):
        back = lax.all_to_all(out.reshape(ranks, room, -1), axis_name, 0, 0)
        return back[rank, place][jnp.argsort(order)], loads, windows
