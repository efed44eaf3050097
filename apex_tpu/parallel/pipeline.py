"""Pipeline parallelism over a mesh axis.

Beyond the reference (2019-era apex has no pipeline parallelism — SURVEY.md
section 2 "NOT present"), but required of a complete TPU framework: stage
params live on their pipeline rank, microbatch activations flow stage to
stage over ICI with ``lax.ppermute``, and the backward pipeline falls out of
autodiff (the transpose of ``ppermute`` is the reverse permutation), giving
a GPipe-style schedule: all microbatches forward, then all backward.

Design notes (TPU-first):

- SPMD: every rank runs the same compiled program; "which stage am I" is
  ``lax.axis_index``, so there is no per-stage program or coordinator —
  XLA overlaps the ``ppermute`` transfers with the next tick's compute.
- The schedule is expressed as one ``lax.scan`` over ``M + S - 1`` ticks
  (M microbatches, S stages) — compiler-friendly control flow, no Python
  loop over devices.
- Stage functions must be shape-preserving on the activation
  ``(microbatch, ...) -> (microbatch, ...)`` so the rotating buffer has a
  static shape; width changes belong inside a stage.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def stack_stage_params(params_list: Sequence[Any]) -> Any:
    """Stack per-stage param pytrees along a new leading "stage" axis, the
    layout expected by :func:`pipeline_apply` (shard it ``P("pipe", ...)``
    so each rank holds exactly its stage's slice)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    axis_name: str = "pipe",
    n_microbatches: Optional[int] = None,
    stacked: bool = True,
) -> jax.Array:
    """Run ``x`` through ``S = axis_size(axis_name)`` pipeline stages.

    Call **inside** ``shard_map`` over a mesh with ``axis_name``.

    Args:
      stage_fn: ``(one_stage_params, activation) -> activation``,
        shape-preserving.
      stage_params: this rank's stage params — the per-rank slice of a
        :func:`stack_stage_params` tree sharded over ``axis_name``, i.e.
        every leaf carries a leading stage axis of local size 1, which is
        squeezed (checked).  Pass ``stacked=False`` for a tree already at
        per-stage shape.
      x: the full batch ``(batch, ...)``, identical on every rank
        (replicated in_spec).  Split into ``n_microbatches`` equal
        microbatches along axis 0.
      n_microbatches: defaults to ``S``.

    Returns:
      ``(batch, ...)`` outputs of the final stage, identical on every rank
      (so an ``out_specs=P()`` works directly).
    """
    S = lax.axis_size(axis_name)
    s = lax.axis_index(axis_name)
    M = n_microbatches or S
    batch = x.shape[0]
    if batch % M:
        raise ValueError(f"batch {batch} not divisible into {M} microbatches")

    if stacked:
        # squeeze the local slice of the stacked stage axis (always present
        # and of size 1 in a stack_stage_params tree sharded over the axis)
        def _squeeze(leaf):
            if not leaf.ndim or leaf.shape[0] != 1:
                raise ValueError(
                    f"stacked stage param has local leading dim "
                    f"{leaf.shape}; expected size 1 — shard the "
                    f"stack_stage_params tree over {axis_name!r}, or pass "
                    "stacked=False for per-stage-shaped params")
            return leaf[0]
        params = jax.tree.map(_squeeze, stage_params)
    else:
        params = stage_params

    mb = batch // M
    micro = x.reshape((M, mb) + x.shape[1:])
    # the rotating buffer and the fed microbatches are device-varying over
    # the pipe axis (each rank holds different activations); type them so
    # (replicated x comes in unvarying and the scan carry stays stable)
    micro = lax.pcast(micro, (axis_name,), to="varying")
    zero = lax.pcast(jnp.zeros((mb,) + x.shape[1:], x.dtype),
                     (axis_name,), to="varying")
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        buf = carry
        # stage 0 ingests microbatch t (while t < M); later stages consume
        # what the previous tick's ppermute delivered.
        feed = lax.dynamic_index_in_dim(micro, jnp.minimum(t, M - 1), 0,
                                        keepdims=False)
        inp = jnp.where(s == 0, feed, buf)
        out = stage_fn(params, inp)
        nxt = lax.ppermute(out, axis_name, fwd_perm)
        # the last stage's output at tick t is microbatch t - (S-1)
        return nxt, out

    _, outs = lax.scan(tick, zero, jnp.arange(M + S - 1))
    # Valid final-stage outputs live at ticks S-1 .. S-1+M-1 on rank S-1.
    tail = lax.dynamic_slice_in_dim(outs, S - 1, M, axis=0)
    y_last = tail.reshape((batch,) + x.shape[1:])
    # Broadcast the last stage's result to every rank so callers can use
    # replicated out_specs; ranks contribute zero except S-1.
    y = jnp.where(s == S - 1, y_last, jnp.zeros_like(y_last))
    return lax.psum(y, axis_name)
