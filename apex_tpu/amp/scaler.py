"""Jit-safe dynamic loss scaling.

TPU-native port of the reference's ``apex/amp/scaler.py``.  The reference
keeps a device-side overflow buffer and performs exactly one D2H sync per
iteration (``scaler.py:192-193`` reads ``_overflow_buf.item()`` in
``update_scale``).  On TPU we go further: the scale, the good-step counter,
and the overflow flag are all device-side pytree state, the scale update is
pure ``jnp`` arithmetic, and step skipping is a ``lax.cond`` inside the
compiled step — there is **no** host sync anywhere in the hot loop.

Semantics matched to the reference:

- dynamic scale starts at ``2**16``, doubles after ``scale_window`` (2000)
  consecutive overflow-free steps, halves on overflow, clamped to
  ``[min_loss_scale, max_loss_scale]`` with ``max_loss_scale=2**24``
  (``scaler.py:39-72,190-210``).
- a *static* scale never changes, but overflow still skips the step
  (``scaler.py:190-198`` adjusts only when ``dynamic``).
- unscaling fuses the fp16→fp32 copy, the multiply by ``1/scale``, and the
  non-finite check into one pass (``scaler.py:113-116`` via
  ``amp_C.multi_tensor_scale``); here that is
  :func:`apex_tpu.multi_tensor_apply.multi_tensor_scale`, and on top XLA
  fuses it into neighbouring ops.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import DYNAMIC
from apex_tpu.utils.profiling import AMP_SCALER_UPDATE, AMP_UNSCALE


class LossScaleState(NamedTuple):
    """Device-side scaler state (a pytree; carry it through your step fn)."""

    loss_scale: jax.Array  # f32 scalar
    unskipped: jax.Array   # i32 scalar: consecutive overflow-free steps


def all_finite(tree: Any) -> jax.Array:
    """Single boolean: every element of every leaf is finite.

    Reference analog: the ``noop_flag`` set by ``multi_tensor_scale_kernel.cu:71``
    (any non-finite value flips a shared flag), or the Python fallback's
    per-tensor ``sum()`` check (``scaler.py:6-17``).
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.asarray(True)
    flags = [jnp.all(jnp.isfinite(leaf)) for leaf in leaves
             if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)]
    if not flags:
        return jnp.asarray(True)
    return jnp.stack(flags).all()


@dataclasses.dataclass(frozen=True)
class LossScaler:
    """Configuration + pure state-transition functions (``scaler.py:39-210``).

    ``loss_scale="dynamic"`` selects dynamic scaling; a number selects a
    static scale.
    """

    loss_scale: Union[float, str] = DYNAMIC
    init_scale: float = 2.0 ** 16
    scale_factor: float = 2.0
    scale_window: int = 2000
    min_loss_scale: Optional[float] = None
    max_loss_scale: float = 2.0 ** 24

    @property
    def dynamic(self) -> bool:
        return self.loss_scale == DYNAMIC

    def init_state(self) -> LossScaleState:
        scale = self.init_scale if self.dynamic else float(self.loss_scale)
        return LossScaleState(
            loss_scale=jnp.asarray(scale, jnp.float32),
            unskipped=jnp.asarray(0, jnp.int32),
        )

    @property
    def floor(self) -> float:
        """The effective minimum scale of the dynamic transition —
        ``min_loss_scale`` or the 1.0 default :meth:`update` clamps to."""
        return self.min_loss_scale if self.min_loss_scale is not None else 1.0

    @jax.named_scope(AMP_SCALER_UPDATE)
    def pinned_at_floor(self, state: LossScaleState) -> jax.Array:
        """Device-side flag: the dynamic scale sits at its floor, i.e. the
        next overflow CANNOT shrink it further.  ``overflow AND pinned``
        sustained for K steps is the divergence sentinel's signal that
        the run is in an overflow *storm*, not a normal transient skip
        (:mod:`apex_tpu.resilience.loop`).  Always False for a static
        scale (it never moves, so "pinned" carries no information)."""
        if not self.dynamic:
            return jnp.asarray(False)
        return state.loss_scale <= jnp.asarray(self.floor, jnp.float32)

    # -- hot-loop ops (all traceable) ------------------------------------

    def scale_loss(self, loss: jax.Array, state: LossScaleState) -> jax.Array:
        """``loss.float() * loss_scale`` (``handle.py:116``)."""
        return loss.astype(jnp.float32) * state.loss_scale

    def unscale(self, grads: Any, state: LossScaleState,
                out_dtype=jnp.float32) -> Tuple[Any, jax.Array]:
        """Fused unscale: grads * (1/scale) cast to ``out_dtype``, plus a
        single finite flag (``scaler.py:95-123``).

        Returns ``(unscaled_grads, grads_finite)``.  The finite check runs on
        the *incoming* (still-scaled) grads so that an overflow that saturates
        to inf is always seen, matching the fused kernel which checks the
        input values it reads (``multi_tensor_scale_kernel.cu:57-71``).
        """
        with jax.named_scope(AMP_UNSCALE):
            inv = (1.0 / state.loss_scale).astype(jnp.float32)
            finite = all_finite(grads)
            unscaled = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * inv).astype(out_dtype),
                grads)
            return unscaled, finite

    def unscale_with_stashed(self, new_grads: Any, stashed: Any,
                             state: LossScaleState,
                             out_dtype=jnp.float32) -> Tuple[Any, jax.Array]:
        """Gradient-accumulation path: ``out = (1/scale)·new + 1.0·stashed``
        with the inf-check restricted to the *new* grads
        (``scaler.py:149-182``, ``multi_tensor_axpby`` with arg_to_check=0).
        """
        with jax.named_scope(AMP_UNSCALE):
            inv = (1.0 / state.loss_scale).astype(jnp.float32)
            finite = all_finite(new_grads)
            out = jax.tree.map(
                lambda n, s: (n.astype(jnp.float32) * inv
                              + s.astype(jnp.float32)).astype(out_dtype),
                new_grads, stashed)
            return out, finite

    def update(self, state: LossScaleState,
               grads_finite: jax.Array) -> Tuple[LossScaleState, jax.Array]:
        """State transition of ``update_scale`` (``scaler.py:190-210``).

        Returns ``(new_state, should_skip)``; ``should_skip`` is the overflow
        flag (step skipping itself belongs to the optimizer wrapper so the
        whole thing stays one compiled graph).
        """
        overflow = jnp.logical_not(grads_finite)
        if not self.dynamic:
            return state, overflow

        shrunk = jnp.maximum(state.loss_scale / self.scale_factor,
                             jnp.asarray(self.floor, jnp.float32))
        unskipped = jnp.where(overflow, 0, state.unskipped + 1)
        window_hit = unskipped >= self.scale_window
        grown = jnp.minimum(state.loss_scale * self.scale_factor,
                            jnp.asarray(self.max_loss_scale, jnp.float32))
        new_scale = jnp.where(overflow, shrunk,
                              jnp.where(window_hit, grown, state.loss_scale))
        unskipped = jnp.where(window_hit, 0, unskipped)
        return LossScaleState(loss_scale=new_scale, unskipped=unskipped), overflow
