"""amp frontend: ``initialize`` + the mixed-precision train-step machinery.

TPU-native port of the reference frontend/initialization/optimizer-surgery
stack (``apex/amp/frontend.py:194-353``, ``_initialize.py:150-268``,
``_process_optimizer.py``, ``handle.py:15-154``).  The reference mutates the
user's model and optimizer in place (monkey-patched ``step``/``zero_grad``,
fp32 master clones swapped into param groups, grad hooks).  Here the same
observable semantics are a pure state machine:

- fp32 master params are a pytree in :class:`AmpState` (reference
  ``_process_optimizer.py:29-36`` master clones);
- the half-precision *compute* params are derived by :meth:`Amp.model_params`
  each step (reference ``_master_params_to_model_params`` copy-back,
  ``_process_optimizer.py:242-253`` — under jit, XLA keeps the cast fused
  into the consumers, so the "copy" costs one pass at most);
- loss scaling / unscaling / overflow-skip are the
  :class:`~apex_tpu.amp.scaler.LossScaler` transitions wired into
  :meth:`Amp.apply_gradients` with ``lax.cond`` skip (reference
  ``handle.py:110-150`` scale_loss enter/exit + skip_step patching);
- the whole iteration compiles to one XLA program with **zero** host syncs
  (the reference needed one ``.item()`` per step, ``scaler.py:192-193``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import optax

from apex_tpu.amp import ops as amp_ops
from apex_tpu.amp import policy as policy_lib
from apex_tpu.amp import scaler as scaler_lib
from apex_tpu.amp.policy import Properties
from apex_tpu.amp.scaler import LossScaler, LossScaleState
from apex_tpu.utils.profiling import (
    AMP_CAST, AMP_OPTIMIZER_STEP, AMP_REDUCE, AMP_SCALER_UPDATE, AMP_UNSCALE)

# Default name fragments identifying normalization params kept in fp32 under
# keep_batchnorm_fp32 (reference skips _BatchNorm modules during the O2 cast,
# fp16util.py:44-70). Matches flax's BatchNorm_*/LayerNorm_*/GroupNorm_* and
# common hand-rolled names.
_NORM_NAME_FRAGMENTS = ("batchnorm", "layernorm", "groupnorm", "norm", "bn")
# A recurrence's decay parameters (Mamba's and Kimi Delta Attention's
# ``A_log`` and ``dt_bias``) go through exp and softplus into a product
# over thousands of tokens: the published code keeps them float32 too.
_DECAY_NAMES = ("a_log", "dt_bias")


def default_keep_fp32_filter(path: Tuple[Any, ...]) -> bool:
    """True for param paths that look like normalization-layer params or
    a recurrence's decay parameters."""
    for entry in path:
        name = str(getattr(entry, "key", getattr(entry, "name", entry))).lower()
        if name in _DECAY_NAMES or any(
                frag in name for frag in _NORM_NAME_FRAGMENTS):
            return True
    return False


class AmpState(NamedTuple):
    """Carried training state for one (model, optimizer) pair.

    ``master_params`` is fp32 when master weights are on; otherwise it holds
    the params at model dtype (O0/O1/O3 semantics — the optimizer runs
    directly on them, ``_process_optimizer.py:165-239``).

    ``fp8_state`` is the delayed-scaling state of the O4 fp8 regime
    (:class:`apex_tpu.quant.fp8.Fp8TrainState`: one amax-history +
    scale per tensor class) and ``None`` below O4.  It sits next to
    the loss-scaler states on purpose: both are "how far can this
    step's values stretch" estimators carried as pure pytree state, so
    ``apply_gradients``, the resilience rewind path, and
    ``DurableCheckpointManager`` handle it with no special cases —
    it's just more leaves.
    """

    master_params: Any
    opt_state: Any
    scaler_states: Tuple[LossScaleState, ...]
    step: jax.Array
    fp8_state: Any = None


@dataclasses.dataclass(frozen=True)
class Amp:
    """Bound mixed-precision configuration (the return of :func:`initialize`)."""

    properties: Properties
    scaler: LossScaler
    tx: optax.GradientTransformation
    apply_fn: Optional[Callable] = None
    num_losses: int = 1
    keep_fp32_filter: Callable[[Tuple[Any, ...]], bool] = default_keep_fp32_filter

    # ------------------------------------------------------------------
    # setup
    # ------------------------------------------------------------------
    def init(self, params: Any) -> AmpState:
        """Build the initial state from user fp32 params (reference
        ``_initialize.py:176-177`` requires incoming fp32; we cast to be safe,
        mirroring ``allow_incoming_model_not_fp32`` leniency)."""
        master = self._master_from(params)
        fp8_state = None
        if self.properties.enabled and self.properties.fp8:
            from apex_tpu.quant import fp8 as fp8_lib
            fp8_state = fp8_lib.init_train_state(
                self.properties.fp8_amax_history_len)
        return AmpState(
            master_params=master,
            opt_state=self.tx.init(master),
            scaler_states=tuple(self.scaler.init_state()
                                for _ in range(self.num_losses)),
            step=jnp.zeros((), jnp.int32),
            fp8_state=fp8_state,
        )

    def _master_from(self, params: Any) -> Any:
        """Derive the carried ("master") representation of a param subtree
        — fp32 clones under master weights, compute-precision otherwise.
        Shared by :meth:`init` and :meth:`add_params` so the policy cannot
        diverge between original and later-added subtrees.

        Every leaf is a genuine CLONE (reference ``_initialize.py``
        ``.clone()`` semantics): ``astype`` to an unchanged dtype is an
        aliasing no-op in JAX, and an aliased master means a
        ``donate_argnums`` train step silently deletes the CALLER'S
        params — a later ``a.init(params)`` then builds a state of dead
        buffers (surfaces as an opaque INVALID_ARGUMENT on TPU)."""
        p = self.properties

        def clone(x, dtype=None):
            if dtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                return jnp.array(x, dtype=dtype, copy=True)
            return jnp.array(x, copy=True)

        if p.enabled and self._use_master_weights():
            return jax.tree.map(lambda x: clone(x, jnp.float32), params)
        # single pass: clone() with the policy's cast dtype materializes
        # copy and cast together (model_params_from-then-clone would
        # copy changed-dtype leaves twice)
        return jax.tree_util.tree_map_with_path(
            lambda path, x: clone(x, self._cast_leaf_dtype(path)), params)

    def _use_master_weights(self) -> bool:
        return self.properties.use_master_weights

    def _cast_leaf_dtype(self, path) -> Any:
        p = self.properties
        if not p.enabled or p.cast_model_dtype is None:
            return None  # leave as-is
        if p.keep_batchnorm_fp32 and self.keep_fp32_filter(path):
            return jnp.float32
        return p.cast_model_dtype

    def model_params_from(self, params: Any) -> Any:
        """Cast a param pytree to compute precision per the policy
        (reference ``_initialize.py:183-189`` model cast, batchnorm-safe via
        ``convert_network``)."""
        def cast(path, x):
            dt = self._cast_leaf_dtype(path)
            if dt is None or not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            return x.astype(dt)
        return jax.tree_util.tree_map_with_path(cast, params)

    def model_params(self, state: AmpState) -> Any:
        """Compute-precision view of the masters — the per-step equivalent of
        the reference's master→model fused copy
        (``_process_optimizer.py:242-253``)."""
        return self.model_params_from(state.master_params)

    def add_params(self, state: AmpState, new_params: Any) -> AmpState:
        """Grow the carried state with a new top-level param subtree — the
        functional analog of the reference's patched
        ``optimizer.add_param_group`` (``_process_optimizer.py:331-407``),
        which extends the master/fp16 group lists consistently.

        Both ``state.master_params`` and ``new_params`` must be dicts at
        the top level, with disjoint keys.  Optimizer state for existing
        params (moments, step counters) is preserved: the new union state
        is initialized fresh and every leaf whose tree path already
        existed (same shape/dtype) is grafted back from the old state.

        FusedAdam/FusedLAMB carry a per-leaf ``leaf_step`` pytree (the
        reference's per-param ``state['step']``, ``fused_adam.py:119-125``),
        so grafting preserves existing leaves' counts while new leaves
        start at step 0 — bias correction treats the new subtree as
        freshly initialized, exactly like the reference's
        ``add_param_group``.  Only the global schedule counter
        ``state.step`` is shared.
        """
        master = state.master_params
        if not isinstance(master, dict) or not isinstance(new_params, dict):
            raise TypeError("add_params requires dict param trees")
        overlap = set(master) & set(new_params)
        if overlap:
            raise ValueError(f"params already present: {sorted(overlap)}")

        merged = {**master, **self._master_from(new_params)}

        old_leaves = {
            jax.tree_util.keystr(path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(
                state.opt_state)
        }

        def graft(path, fresh_leaf):
            old = old_leaves.get(jax.tree_util.keystr(path))
            if old is not None and hasattr(old, "shape") and \
                    getattr(old, "shape", None) == fresh_leaf.shape and \
                    getattr(old, "dtype", None) == fresh_leaf.dtype:
                return old
            return fresh_leaf

        fresh = self.tx.init(merged)
        opt_state = jax.tree_util.tree_map_with_path(graft, fresh)
        return AmpState(merged, opt_state, state.scaler_states, state.step,
                        state.fp8_state)

    # ------------------------------------------------------------------
    # model application (reference _initialize.py:197-208 forward patch)
    # ------------------------------------------------------------------
    def apply(self, params: Any, *args, **kwargs):
        """Run the bound model with policy-correct input/output casting and,
        under O1, the cast-ops context active."""
        if self.apply_fn is None:
            raise ValueError("This Amp was initialized without a model apply_fn.")
        return self.run(self.apply_fn, params, *args, **kwargs)

    def run(self, fn: Callable, params: Any, *args, **kwargs):
        """Like :meth:`apply` for an arbitrary function taking ``params``."""
        p = self.properties
        if not p.enabled:
            return fn(params, *args, **kwargs)
        if p.cast_model_dtype is not None and p.cast_model_dtype != jnp.float32:
            args, kwargs = amp_ops._cast_tree((args, kwargs), p.cast_model_dtype)
        if p.cast_ops:
            with amp_ops.cast_context(p):
                out = fn(params, *args, **kwargs)
        else:
            out = fn(params, *args, **kwargs)
        out_dtype = (p.cast_model_outputs if p.cast_model_outputs is not None
                     else jnp.float32)
        if p.cast_model_dtype is not None and p.cast_model_dtype != jnp.float32:
            out = amp_ops._cast_tree(out, out_dtype)
        return out

    # ------------------------------------------------------------------
    # loss scaling (reference handle.py scale_loss)
    # ------------------------------------------------------------------
    def scale_loss(self, loss: jax.Array, state: AmpState,
                   loss_id: int = 0) -> jax.Array:
        """``loss * loss_scale`` for the selected scaler
        (``handle.py:96,116``)."""
        if not self.properties.enabled:
            return loss
        return self.scaler.scale_loss(loss, state.scaler_states[loss_id])

    # ------------------------------------------------------------------
    # gradient application (reference handle.py exit + patched step)
    # ------------------------------------------------------------------
    def apply_gradients(
        self,
        state: AmpState,
        grads: Any,
        loss_id: int = 0,
        stashed_grads: Optional[Any] = None,
        reduce_fn: Optional[Callable[[Any], Any]] = None,
        finite_axes: Optional[Sequence[str]] = None,
    ) -> Tuple[AmpState, dict]:
        """Unscale → finite-check → scaler update → conditionally step.

        ``grads`` are w.r.t. the *compute* params (still loss-scaled, at
        compute dtype — exactly what materializes from the backward pass in
        the reference).  ``reduce_fn`` (e.g. a data-parallel psum from
        :mod:`apex_tpu.parallel`) runs on the scaled grads, matching the
        reference DDP which allreduces scaled fp16 grads before unscaling.
        ``stashed_grads`` selects the gradient-accumulation path
        (``unscale_with_stashed``, ``_process_optimizer.py:125-129``).
        On that path the finite check covers the *combined* unscaled
        grads, not just the new micro-batch: an inf from any earlier
        micro-batch persists through the stashed adds, so checking the
        combination reproduces the reference's shared overflow buffer
        (which accumulates across every unscale of the iteration) with no
        caller cooperation.

        ``finite_axes`` names mesh axes over which params (and so grads)
        are *sharded* — pipeline stages over "pipe", experts over
        "expert", tensor-parallel shards.  The finite flag is AND-reduced
        over them so an overflow on any rank skips the step on every
        rank, keeping the skip decision (and the scaler trajectory)
        globally consistent.  DDP's replicated params don't need this:
        the reduced grads are identical everywhere.

        Returns ``(new_state, info)`` with ``info = {"overflow", "loss_scale"}``
        — both device arrays; nothing here syncs to the host.
        """
        if reduce_fn is not None:
            with jax.named_scope(AMP_REDUCE):
                grads = reduce_fn(grads)

        if not self.properties.enabled:
            with jax.named_scope(AMP_OPTIMIZER_STEP):
                updates, opt_state = self.tx.update(grads, state.opt_state,
                                                    state.master_params)
                master = optax.apply_updates(state.master_params, updates)
            return (AmpState(master, opt_state, state.scaler_states,
                             state.step + 1, state.fp8_state),
                    {"overflow": jnp.asarray(False),
                     "loss_scale": jnp.asarray(1.0, jnp.float32),
                     "pinned_at_floor": jnp.asarray(False)})

        sstate = state.scaler_states[loss_id]
        if stashed_grads is not None:
            grads_unscaled, _ = self.scaler.unscale_with_stashed(
                grads, stashed_grads, sstate)
            # Stale non-finites from earlier micro-batches survive the
            # adds (inf+x = inf / nan), so checking the combination
            # subsumes the reference's arg-0 check with no caller
            # cooperation (see unscale_gradients for the strict arg-0
            # per-loss policy).
            with jax.named_scope(AMP_UNSCALE):
                finite = scaler_lib.all_finite(grads_unscaled)
        else:
            grads_unscaled, finite = self.scaler.unscale(grads, sstate)
        finite = self._all_ranks_finite(finite, finite_axes)
        state, overflow = self.update_scaler(state, loss_id, finite)
        new_state = self.step_if(state, grads_unscaled, overflow)
        new_sstate = new_state.scaler_states[loss_id]
        return new_state, {
            "overflow": overflow,
            "loss_scale": new_sstate.loss_scale,
            # device-side storm signal for the resilience sentinel: this
            # overflow found the scale already at (or shrank it to) the
            # min_loss_scale floor (scaler.pinned_at_floor)
            "pinned_at_floor": self.scaler.pinned_at_floor(new_sstate)}

    @staticmethod
    @jax.named_scope(AMP_UNSCALE)
    def _all_ranks_finite(finite: jax.Array,
                          finite_axes: Optional[Sequence[str]]) -> jax.Array:
        """AND of the finite flag across the ranks that share the step
        decision (min of {0,1}); part of the finite check's scope."""
        for ax in (finite_axes or ()):
            finite = jax.lax.pmin(finite.astype(jnp.int32), ax).astype(bool)
        return finite

    # ------------------------------------------------------------------
    # composable pieces for multi-loss / multi-optimizer topologies
    # (reference: one `with amp.scale_loss(loss_i, opts_j, loss_id=k)` per
    # backward, each exit unscaling into the shared master grads, updating
    # scaler k, and arming skip_step on every optimizer it was passed —
    # handle.py:110-150, tests/L0/run_amp/test_multiple_models_optimizers_losses.py)
    # ------------------------------------------------------------------
    def unscale_gradients(
        self, state: AmpState, grads: Any, loss_id: int = 0,
        stashed_grads: Optional[Any] = None,
    ) -> Tuple[Any, jax.Array]:
        """Unscale one backward's grads with scaler ``loss_id``; returns
        ``(unscaled, finite)``.  The finite check follows the reference's
        arg-0 policy on the stashed path (``scaler.py:167-172``): only the
        *new* grads are checked, so a stale inf in ``stashed_grads`` (from
        another loss's backward) is never attributed to this scaler."""
        sstate = state.scaler_states[loss_id]
        if stashed_grads is not None:
            return self.scaler.unscale_with_stashed(grads, stashed_grads,
                                                    sstate)
        return self.scaler.unscale(grads, sstate)

    def update_scaler(self, state: AmpState, loss_id: int,
                      grads_finite: jax.Array) -> Tuple[AmpState, jax.Array]:
        """Run scaler ``loss_id``'s post-backward transition
        (``update_scale``, ``scaler.py:190-210``) without stepping.
        Returns ``(state_with_new_scaler, overflow)``."""
        with jax.named_scope(AMP_SCALER_UPDATE):
            new_sstate, overflow = self.scaler.update(
                state.scaler_states[loss_id], grads_finite)
        scaler_states = tuple(
            new_sstate if i == loss_id else s
            for i, s in enumerate(state.scaler_states))
        return state._replace(scaler_states=scaler_states), overflow

    def step_if(self, state: AmpState, grads_unscaled: Any,
                skip: jax.Array) -> AmpState:
        """Conditionally apply the optimizer step on already-unscaled grads
        — the ``lax.cond`` core of :meth:`apply_gradients`, split out so
        multi-loss/multi-optimizer drivers can route overflow flags across
        optimizers (the reference arms ``skip_step`` on every optimizer a
        ``scale_loss`` context was passed, ``handle.py:131-150``)."""
        def do_step(operand):
            master, opt_state = operand
            updates, new_opt_state = self.tx.update(grads_unscaled, opt_state,
                                                    master)
            return optax.apply_updates(master, updates), new_opt_state

        with jax.named_scope(AMP_OPTIMIZER_STEP):
            grads_unscaled = jax.tree.map(
                lambda g, p: g.astype(p.dtype) if hasattr(p, "dtype") else g,
                grads_unscaled, state.master_params)
            master, opt_state = jax.lax.cond(
                skip, lambda op: op, do_step,
                (state.master_params, state.opt_state))
        return AmpState(master, opt_state, state.scaler_states,
                        state.step + 1, state.fp8_state)

    def apply_gradients_multi(
        self,
        state: AmpState,
        grads_list: Sequence[Any],
        loss_ids: Optional[Sequence[int]] = None,
        reduce_fn: Optional[Callable[[Any], Any]] = None,
        finite_axes: Optional[Sequence[str]] = None,
    ) -> Tuple[AmpState, dict]:
        """One optimizer fed by several backward passes, each scaled by its
        own (or a shared) loss scaler — the reference's ``num_losses`` /
        ``loss_id`` machinery driven to completion in one call.

        ``grads_list[i]`` is the (still-scaled) grad pytree of loss ``i``;
        zeros where a loss does not touch a param (what ``.backward()``
        accumulation leaves untouched in the reference).  Per backward:
        unscale with scaler ``loss_ids[i]``, per-backward finite check,
        per-scaler ``update_scale``; the unscaled grads sum into the master
        grads and the step is skipped iff **any** backward overflowed
        (each exit arms ``skip_step`` on the shared optimizer,
        ``handle.py:131-150``).

        With a shared scaler (repeated loss_id) all backwards here unscale
        at the iteration-entry scale, while the reference re-scales later
        losses after an earlier overflow halved the shared scaler
        mid-iteration.  Scale and unscale cancel per backward, so master
        grads — and every observable outcome — are identical.

        ``finite_axes``: as in :meth:`apply_gradients` — each backward's
        finite flag is AND-reduced over the param-sharding mesh axes so
        skip decisions and per-loss scaler trajectories stay globally
        consistent.
        """
        if loss_ids is None:
            loss_ids = list(range(len(grads_list)))
        if len(loss_ids) != len(grads_list):
            raise ValueError("loss_ids and grads_list length mismatch")

        if not self.properties.enabled:
            total = jax.tree.map(lambda *gs: sum(gs), *grads_list)
            new_state, info = self.apply_gradients(state, total,
                                                   reduce_fn=reduce_fn)
            # Same metrics pytree shape as the enabled path below.
            return new_state, {
                "overflow": info["overflow"],
                "loss_scale": tuple(jnp.asarray(1.0, jnp.float32)
                                    for _ in new_state.scaler_states),
                "pinned_at_floor": tuple(jnp.asarray(False)
                                         for _ in new_state.scaler_states)}

        # Callers scale every loss at iteration entry, so unscale against the
        # entry-time scaler states even as the per-loss updates land below
        # (scale/unscale must use the same value to cancel).
        entry_state = state
        total = None
        any_overflow = None
        for grads, lid in zip(grads_list, loss_ids):
            if reduce_fn is not None:
                with jax.named_scope(AMP_REDUCE):
                    grads = reduce_fn(grads)
            unscaled, finite = self.unscale_gradients(entry_state, grads,
                                                      loss_id=lid)
            finite = self._all_ranks_finite(finite, finite_axes)
            state, overflow = self.update_scaler(state, lid, finite)
            total = unscaled if total is None else jax.tree.map(
                jnp.add, total, unscaled)
            any_overflow = overflow if any_overflow is None else \
                jnp.logical_or(any_overflow, overflow)

        new_state = self.step_if(state, total, any_overflow)
        return new_state, {
            "overflow": any_overflow,
            "loss_scale": tuple(s.loss_scale
                                for s in new_state.scaler_states),
            "pinned_at_floor": tuple(self.scaler.pinned_at_floor(s)
                                     for s in new_state.scaler_states),
        }


def initialize(
    apply_fn: Optional[Callable] = None,
    optimizer: Optional[optax.GradientTransformation] = None,
    opt_level: str = "O1",
    enabled: bool = True,
    half_dtype=jnp.bfloat16,
    cast_model_dtype=None,
    cast_ops: Optional[bool] = None,
    keep_batchnorm_fp32: Union[None, bool, str] = None,
    master_weights: Optional[bool] = None,
    loss_scale: Union[None, float, str] = None,
    cast_model_outputs=None,
    num_losses: int = 1,
    min_loss_scale: Optional[float] = None,
    max_loss_scale: float = 2.0 ** 24,
    keep_fp32_filter: Callable = default_keep_fp32_filter,
    verbosity: int = 1,
) -> Amp:
    """Resolve an opt level + overrides into a bound :class:`Amp`
    (reference ``amp.initialize``, ``frontend.py:194-353``).

    Unlike the reference this does not mutate a model/optimizer — it returns
    the pure state machine; pair it with :func:`make_train_step` or drive
    ``init`` / ``model_params`` / ``scale_loss`` / ``apply_gradients``
    yourself (the explicit analog of the ``with amp.scale_loss(...)`` loop).
    """
    props = policy_lib.resolve(
        opt_level=opt_level, half_dtype=half_dtype, enabled=enabled,
        cast_model_dtype=cast_model_dtype, cast_ops=cast_ops,
        keep_batchnorm_fp32=keep_batchnorm_fp32, master_weights=master_weights,
        loss_scale=loss_scale, cast_model_outputs=cast_model_outputs)
    scaler = LossScaler(
        loss_scale=props.loss_scale,
        min_loss_scale=min_loss_scale,
        max_loss_scale=max_loss_scale)
    if optimizer is None:
        optimizer = optax.identity()
    if verbosity > 0:
        from apex_tpu.utils.logging import maybe_print
        maybe_print(f"apex_tpu.amp configured: {props}")
    amp = Amp(properties=props, scaler=scaler, tx=optimizer,
              apply_fn=apply_fn, num_losses=num_losses,
              keep_fp32_filter=keep_fp32_filter)
    # Record for module-level amp.scale_loss (the reference's _amp_state
    # global, apex/amp/_amp_state.py).
    from apex_tpu.amp import handle as handle_lib
    handle_lib._set_active_amp(amp)
    return amp


def make_train_step(
    amp: Amp,
    loss_fn: Callable,
    axis_name: Optional[str] = None,
    reduce_fn: Optional[Callable[[Any], Any]] = None,
    has_aux: bool = False,
    finite_axes: Optional[Sequence[str]] = None,
    accum_steps: Optional[int] = None,
    aot_cache: Optional[str] = None,
):
    """Build a jittable single-loss train step.

    ``loss_fn(model_params, *batch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux``) is evaluated at compute precision; the returned
    ``step(state, *batch) -> (state, metrics)`` does forward, backward,
    unscale, scaler update, and the conditional optimizer step in one
    compiled graph (the whole of reference §3.2's hot loop).

    ``axis_name`` marks the compute params device-varying (so grads
    materialize per-rank, exactly like the reference's backward hooks) and
    applies a mean-``psum`` (plain DP); for the full knob set (predivide,
    fp32 wire, compression) also pass ``reduce_fn`` from
    ``DistributedDataParallel(...).reduce``.  When running under shard_map
    with a ``reduce_fn``, ``axis_name`` must be given — without it, SPMD
    autodiff auto-sums grads of replicated params and an explicit reduce
    would double-count.

    ``finite_axes``: mesh axes the *params* are sharded over (pipeline /
    expert / tensor shards) — the overflow-skip decision is AND-reduced
    across them (see :meth:`Amp.apply_gradients`).

    ``accum_steps``: gradient accumulation over N micro-batches — the
    reference's stashed-grad iteration (``_process_optimizer.py:125-129``)
    and the ``Reducer``'s every-N cadence, as one compiled ``lax.scan``:
    every batch argument's leading dim splits into ``(N, batch/N)``,
    scaled grads accumulate across micro-steps, and ONE
    unscale/scaler-update/conditional-step runs at the end.  Grads
    accumulate in fp32 (like the reference's fp32 master grads) and,
    with the reported loss, are averaged over micro-steps, so the step
    is numerically the large-batch mean-loss step (an inf in ANY
    micro-batch skips it — the accumulated sum stays non-finite, the
    reference's shared overflow buffer).  ``reduce_fn``/``axis_name``
    reduction applies once to the accumulated grads, the
    ``delay_allreduce=True`` economics.  Every batch argument must carry
    the leading batch dim; with ``has_aux`` the aux comes back stacked
    per micro-step (leading ``(N,)`` dim).

    ``aot_cache``: directory of the content-addressed AOT executable
    cache (:mod:`apex_tpu.analysis.export`).  When set, the returned
    step is self-jitting (state donated) and its FIRST call probes the
    cache: a verified key hit — same program, same mesh, same resolved
    policy, same jax — loads the serialized executable instead of
    paying XLA compilation (the cold-start cost of every new training
    replica today); a miss compiles, relints under the export gate,
    and populates the cache for the next replica.  The resolved
    provenance is exposed as ``step.aot_info``.  Without it the step
    is the plain jittable (jit and donate it yourself).
    """
    if axis_name is None and reduce_fn is not None:
        axis_name = getattr(reduce_fn, "__self__", None) and \
            getattr(reduce_fn.__self__, "axis_name", None)
    if axis_name is not None and reduce_fn is None:
        def reduce_fn(grads):
            return jax.lax.pmean(grads, axis_name)

    def step(state: AmpState, *batch):
        from apex_tpu.parallel.distributed import pvary_params
        with jax.named_scope(AMP_CAST):
            params_c = amp.model_params(state)
        if axis_name is not None:
            params_c = pvary_params(params_c, axis_name)
        fp8_on = amp.properties.enabled and amp.properties.fp8 \
            and state.fp8_state is not None

        def scaled_loss(p, micro):
            if fp8_on:
                # O4: the delayed scales enter (and the per-callsite
                # forward amaxes leave) through the trace-local fp8
                # context — all values of THIS trace, so the state
                # stays purely functional and the collected amaxes
                # ride the loss aux back out.  The e5m2 cotangent
                # scale is grad.scale/loss_scale: the rounding point
                # sees loss-scaled cotangents while the grad history
                # records unscaled units (stable across scaler moves)
                eff_gs = state.fp8_state.grad.scale \
                    / state.scaler_states[0].loss_scale
                with amp_ops.fp8_trace(state.fp8_state,
                                       grad_scale=eff_gs) as tr:
                    out = amp.run(loss_fn, p, *micro)
                    amaxes = amp_ops.collected_fp8_amaxes(tr)
            else:
                out = amp.run(loss_fn, p, *micro)
                amaxes = None
            loss, aux = out if has_aux else (out, None)
            return amp.scale_loss(loss, state), (loss, aux, amaxes)

        if accum_steps is None or accum_steps == 1:
            grads, (loss, aux, fp8_amaxes) = jax.grad(
                lambda p: scaled_loss(p, batch), has_aux=True)(params_c)
        else:
            def split(t):
                t = jnp.asarray(t)
                if t.ndim == 0 or t.shape[0] % accum_steps:
                    raise ValueError(
                        f"accum_steps={accum_steps}: every batch argument "
                        f"leaf must have a leading dim divisible by it; "
                        f"got shape {t.shape} (broadcast non-batched "
                        "extras inside loss_fn instead of passing them "
                        "as batch args)")
                return t.reshape((accum_steps, t.shape[0] // accum_steps)
                                 + t.shape[1:])

            micro_batches = jax.tree.map(split, batch)

            def body(acc, micro):
                g, (loss, aux, amaxes) = jax.grad(
                    lambda p: scaled_loss(p, micro),
                    has_aux=True)(params_c)
                # accumulate in fp32 regardless of compute dtype: summing
                # in bf16 would absorb small micro-contributions (the
                # reference accumulates into fp32 master grads)
                acc = jax.tree.map(
                    lambda a, gi: a + gi.astype(a.dtype), acc, g)
                return acc, (loss, aux, amaxes)

            zero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params_c)
            if axis_name is not None:
                # under shard_map the per-rank grads are device-varying;
                # fresh zeros are not — mark them varying so the scan
                # carry types agree (grads stay per-rank until reduce_fn)
                zero = pvary_params(zero, axis_name)
            grads, (losses, auxes, fp8_amaxes) = jax.lax.scan(
                body, zero, micro_batches)
            # mean-loss semantics: the accumulated step equals the
            # large-batch mean-loss step (grads scaled by 1/N; an inf in
            # any micro-batch survives the sum and skips the step)
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            loss = jnp.mean(losses)
            if fp8_on:
                # per-micro amaxes stacked (accum_steps,): the history
                # entry is the iteration's max, like every other class
                fp8_amaxes = jax.tree.map(jnp.max, fp8_amaxes)
            # per-micro aux stacked with a leading (accum_steps,) dim —
            # documented; reduce it yourself (e.g. take aux[-1] for
            # carried stats)
            aux = auxes if has_aux else None

        fp8_metrics = {}
        if fp8_on:
            # end-of-step history roll (quant.fp8): forward amaxes from
            # the op layer's collector, grad amax from THIS step's
            # still-scaled grads (the e5m2 rounding point sees scaled
            # cotangents, so the delayed grad scale tracks the scaled
            # magnitude) — everything stays on device, and
            # apply_gradients below threads the new state through with
            # no special case (it's just more pytree leaves)
            from apex_tpu.quant import fp8 as fp8_lib
            amax_in, amax_w = fp8_amaxes
            # grads are still loss-scaled here: record the UNSCALED
            # amax (divide the scale back out) so the grad history is
            # unit-stable across loss-scale moves — and so the
            # precision lint's scale-placement dataflow can prove the
            # returned state carries no scaled value
            amax_g = fp8_lib.tree_amax(grads) \
                * (1.0 / state.scaler_states[0].loss_scale)
            margin = amp.properties.fp8_margin
            new_fp8 = fp8_lib.update_train_state(
                state.fp8_state, amax_in, amax_w, amax_g, margin)
            fp8_metrics = {
                "fp8_amax_saturation": fp8_lib.step_saturation(
                    state.fp8_state, amax_in, amax_w, amax_g, margin),
                "fp8_rescales": fp8_lib.rescale_events(
                    state.fp8_state, new_fp8),
            }
            state = state._replace(fp8_state=new_fp8)

        new_state, info = amp.apply_gradients(state, grads,
                                              reduce_fn=reduce_fn,
                                              finite_axes=finite_axes)
        metrics = {"loss": loss, **info, **fp8_metrics}
        if has_aux:
            metrics["aux"] = aux
        return new_state, metrics

    if aot_cache is None:
        return step
    return _aot_cached_step(step, amp, aot_cache)


def _aot_cached_step(step: Callable, amp: Amp, cache_dir: str):
    """Wrap a train step so its first call resolves the executable
    through the AOT cache (:func:`apex_tpu.analysis.export.probe`):
    load on a verified key hit, compile + relint + export on a miss.
    Later calls dispatch straight to the resolved executable — the
    wrapper adds one dict lookup to the hot path, nothing else."""
    import functools

    jitted = jax.jit(step, donate_argnums=0)
    box: dict = {}

    @functools.wraps(step)
    def cached_step(state, *batch):
        if "compiled" not in box:
            from apex_tpu.analysis import export as aot
            compiled, info = aot.probe(
                jitted, state, *batch, cache_dir=cache_dir,
                policy=amp.properties, lane="train_step",
                export_on_miss=True)
            box["compiled"] = compiled
            cached_step.aot_info = info
        return box["compiled"](state, *batch)

    cached_step.aot_info = None
    return cached_step
