"""Expert parallelism: switch-style top-1 MoE over a mesh axis.

Beyond the reference (apex predates MoE — SURVEY.md section 2 "NOT
present"), but part of the full parallelism surface (dp/tp/pp/sp/ep) this
framework validates.  The design is the standard TPU dispatch/combine:
capacity-bounded one-hot dispatch tensors turn routing into dense einsums
(MXU work, static shapes — no scatter), and ``lax.all_to_all`` moves token
slots to the ranks that host their experts and back over ICI.

Call :func:`moe_apply` inside ``shard_map``: tokens are sharded over
``axis_name`` (data-parallel shard), experts are sharded over the same axis
(``n_experts = n_ranks * experts_per_rank``).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.generate import greedy_argmax


def top1_routing(logits: jax.Array, capacity: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Switch top-1 router on ``(T, E)`` logits.

    Returns ``(dispatch, combine, aux_loss)``: ``dispatch`` is a bool
    ``(T, E, C)`` one-hot (token t occupies slot c of expert e), ``combine``
    is the same mask scaled by the router probability, and ``aux_loss`` is
    the switch load-balancing loss (mean fraction-routed times mean router
    prob per expert, scaled by E).  Tokens beyond an expert's capacity are
    dropped (standard switch semantics): their combine weights are zero, so
    they pass through the residual path untouched.
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    # reassociation-proof routing: jnp.argmax's tie-break can differ
    # between the dispatch and combine consumers under refusion, and a
    # router tie that flips experts between the two poisons the
    # capacity bookkeeping (det-tie-argmax)
    expert = greedy_argmax(probs)                            # (T,)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)    # (T, E)
    # position of each token within its expert's queue (zero on the E-1
    # non-selected columns so the row-sum is exactly the queue index)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot        # (T, E)
    slot = jnp.sum(pos, axis=-1).astype(jnp.int32)           # (T,)
    keep = slot < capacity
    dispatch = (jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
                [:, None, :] * onehot[:, :, None]
                * keep[:, None, None].astype(jnp.float32))   # (T, E, C)
    gate = jnp.sum(probs * onehot, axis=-1)                  # (T,)
    combine = dispatch * gate[:, None, None]
    # load-balancing aux loss (Switch Transformer eq. 4-6)
    frac_routed = jnp.mean(onehot, axis=0)
    frac_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_routed * frac_prob)
    return dispatch, combine, aux


def moe_apply(
    expert_fn: Callable[[Any, jax.Array], jax.Array],
    expert_params: Any,
    router_w: jax.Array,
    x: jax.Array,
    axis_name: str = "expert",
    capacity_factor: float = 2.0,
) -> Tuple[jax.Array, jax.Array]:
    """Top-1 MoE layer with experts sharded over ``axis_name``.

    Call inside ``shard_map``.  Args:
      expert_fn: ``(one_expert_params, (tokens, d)) -> (tokens, d)``.
      expert_params: this rank's experts — leading axis ``E_local``.
      router_w: ``(d, E_global)`` router weights (replicated).
      x: local token shard ``(T_local, d)``.
      capacity_factor: per-expert slots = ``ceil(cf * T_local / E_global)``
        per rank's token shard.

    Returns ``(y, aux_loss)`` with ``y`` shaped like ``x`` (dropped tokens
    produce zeros — add the residual outside), ``aux_loss`` a scalar
    (psum-averaged over ranks).
    """
    import math
    n_ranks = lax.axis_size(axis_name)
    t_local, d = x.shape
    e_local = jax.tree.leaves(expert_params)[0].shape[0]
    e_global = n_ranks * e_local
    capacity = max(1, math.ceil(capacity_factor * t_local / e_global))

    logits = x @ router_w.astype(x.dtype)                    # (T, E_global)
    dispatch, combine, aux = top1_routing(logits, capacity)

    # (T,E,C) x (T,d) -> (E, C, d): dense dispatch, MXU-friendly
    sent = jnp.einsum("tec,td->ecd", dispatch.astype(jnp.float32),
                      x.astype(jnp.float32))
    # split expert axis across ranks: (E_global, C, d) ->
    # (n_ranks, E_local, C, d) -all_to_all-> (E_local, n_ranks*C, d)
    sent = sent.reshape(n_ranks, e_local, capacity, d)
    recv = lax.all_to_all(sent, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                        # (n*E_l, C, d)
    recv = recv.reshape(n_ranks, e_local, capacity, d).transpose(1, 0, 2, 3)
    recv = recv.reshape(e_local, n_ranks * capacity, d)

    out = jax.vmap(expert_fn)(expert_params, recv.astype(x.dtype))
    out = out.astype(jnp.float32)

    # return path mirrors the dispatch
    out = out.reshape(e_local, n_ranks, capacity, d).transpose(1, 0, 2, 3)
    out = out.reshape(n_ranks * e_local, capacity, d)
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)                        # (E_global,C,d)
    y = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32), back)
    aux = lax.pmean(aux, axis_name)
    return y.astype(x.dtype), aux
