"""Data-parallel gradient reduction.

Port of ``apex/parallel/distributed.py``.  The reference's 511 lines are
mostly machinery to overlap NCCL allreduce with backward compute: grad-hook
bucketing by ``message_size``, a dedicated reduction stream, rank-0 bucket
structure broadcast, out-of-order bucket draining.  Under jit-compiled JAX
**all of that is the compiler's job**: gradients reduced with
``jax.lax.psum`` inside the step function are scheduled asynchronously by XLA
and overlapped with remaining backward compute (SURVEY.md §2 "TPU mapping
note").  What must be ported is the *semantics knob set* (``distributed.py:
134-177``):

- ``gradient_average`` — divide by world size after the sum;
- ``gradient_predivide_factor`` — pre-divide by ``f``, post-multiply by
  ``f / world_size`` for dynamic-range management at large world sizes
  (``distributed.py:379-398``; the post-scale applies only when
  ``gradient_average`` is on — with averaging off, grads deliver at
  ``sum/f``, matching the reference exactly);
- ``allreduce_always_fp32`` — upcast half grads to fp32 for the wire;
- ``compression="sign"`` — optional 1-bit sign compression of buckets before
  the collective.  This is the *intent* of the fork's broken
  ``param_signsgd`` hack (``distributed.py:41-43``, SURVEY.md §0); correct
  uncompressed reduction is the default and sign compression is opt-in.

Collectives ride mesh axes: use these reducers inside ``shard_map`` /
``pmap`` with the mesh from :mod:`apex_tpu.parallel.mesh`.  Under pure
``pjit`` auto-sharding you don't need a reducer at all — XLA inserts the
collective from the sharding specs; ``DistributedDataParallel`` here is for
the explicit-SPMD style that matches apex's semantics exactly.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax


class ReduceOp(enum.Enum):
    """Reference re-exports torch.distributed.ReduceOp
    (``apex/parallel/__init__.py:3-8``)."""
    SUM = "sum"
    PRODUCT = "product"
    MIN = "min"
    MAX = "max"


def all_reduce(x: Any, axis_name: str, op: ReduceOp = ReduceOp.SUM) -> Any:
    """``torch.distributed.all_reduce`` → mesh-axis collective."""
    fn = {ReduceOp.SUM: lax.psum, ReduceOp.MAX: lax.pmax,
          ReduceOp.MIN: lax.pmin}.get(op)
    if fn is None:
        raise NotImplementedError(f"ReduceOp {op} not supported on TPU mesh")
    return jax.tree.map(lambda t: fn(t, axis_name), x)


def all_gather(x: Any, axis_name: str) -> Any:
    return jax.tree.map(lambda t: lax.all_gather(t, axis_name), x)


def broadcast(x: Any, axis_name: str, root: int = 0) -> Any:
    """Rank-``root``'s value to everyone (the reference's param-init
    broadcast, ``distributed.py:242``).  Under SPMD with replicated init this
    is usually unnecessary; provided for parity."""
    def bc(t):
        masked = jnp.where(lax.axis_index(axis_name) == root, t,
                           jnp.zeros_like(t))
        return lax.psum(masked, axis_name)
    return jax.tree.map(bc, x)


@dataclasses.dataclass(frozen=True)
class ReduceConfig:
    """The DDP knob set (``distributed.py:134-177`` constructor args that
    still have meaning under XLA; ``message_size``/``delay_allreduce``/
    ``num_allreduce_streams`` are scheduling hints the XLA latency-hiding
    scheduler subsumes)."""

    gradient_average: bool = True
    gradient_predivide_factor: float = 1.0
    allreduce_always_fp32: bool = False
    compression: Optional[str] = None  # None | "sign"


def _fold_reduce_config(self) -> None:
    """Shared constructor logic for the wrappers that accept the reference
    knob spellings: fold them into ``config`` when none is given, reject
    conflicting specifications.  Knob fields default to ``None`` ("not
    passed") so an explicit knob equal to the ReduceConfig default still
    conflicts detectably with an explicit ``config``."""
    knobs = {k: getattr(self, k)
             for k in ("gradient_average", "gradient_predivide_factor",
                       "allreduce_always_fp32", "compression")}
    passed = {k: v for k, v in knobs.items() if v is not None}
    if self.config is None:
        object.__setattr__(self, "config", ReduceConfig(**passed))
        return
    if passed:
        raise ValueError(
            f"pass the reduction knobs either via config= or directly, "
            f"not both (got config={self.config} and {passed})")


def pvary_params(params: Any, axis_name: str) -> Any:
    """Mark replicated params as device-varying so gradients materialize
    *per-rank* instead of being auto-``psum``'d by shard_map's autodiff.

    Under modern SPMD autodiff, the cotangent of a replicated value is summed
    across the mesh automatically (the transpose of broadcast).  That is
    correct but leaves no per-rank gradient to apply apex's wire-format knobs
    (predivide, fp32 upcast, sign compression) to.  Calling this on the
    params before ``jax.grad`` restores the reference's model: per-rank grads
    (``allreduce_hook`` inputs) that the caller then reduces explicitly with
    :func:`reduce_gradients`.  No data movement — it only tags the values;
    a leaf that already varies over the axis (an expert or pipeline shard)
    is left as it is.
    """
    def tag(p):
        if axis_name in jax.typeof(p).vma:
            return p
        return lax.pcast(p, (axis_name,), to="varying")
    return jax.tree.map(tag, params)


def reduce_gradients(grads: Any, axis_name: str,
                     config: ReduceConfig = ReduceConfig()) -> Any:
    """Flat-semantics allreduce of a *per-rank* grad pytree
    (``allreduce_bucket``, ``distributed.py:379-398``).

    Expects unreduced (device-varying) grads — i.e. grads of params passed
    through :func:`pvary_params`; reducing already-summed grads would
    multiply them by the world size.
    """
    world = lax.axis_size(axis_name)

    @jax.named_scope("ddp_allreduce")
    def reduce_leaf(g):
        orig_dtype = g.dtype
        if config.allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if config.compression == "sign":
            g = jnp.sign(g)
        if config.gradient_predivide_factor != 1.0:
            g = g / config.gradient_predivide_factor
        g = lax.psum(g, axis_name)
        # Reference parity (``distributed.py:387-393``): the post-scale
        # runs ONLY under gradient_average; with averaging off the grads
        # stay at sum/f — the predivide is part of the delivered scale,
        # not cancelled.
        if config.gradient_average:
            post = config.gradient_predivide_factor / world
            if post != 1.0:
                g = g * post
        return g.astype(orig_dtype)

    return jax.tree.map(reduce_leaf, grads)


@dataclasses.dataclass(frozen=True)
class DistributedDataParallel:
    """Gradient-reducing wrapper (``distributed.py:134``), usable two ways:

    - ``ddp.reduce(grads)`` inside your step function (the steady-state hook
      path, fired automatically by ``amp.make_train_step(reduce_fn=ddp.reduce)``);
    - ``ddp.reduce_fn`` passed to :func:`apex_tpu.amp.make_train_step`.

    With ``delay_allreduce`` semantics (grad accumulation every N steps),
    simply don't call ``reduce`` on non-boundary steps — the reference's
    ``Reducer`` manual-trigger pattern (``distributed.py:94-131``).

    ``message_size`` is kept for the reference's bucketing knob
    (``distributed.py:167``): XLA schedules collective overlap itself, but
    :meth:`plan_buckets` exposes the same greedy assignment (native-backed)
    for callers that reduce in explicit groups — e.g. ``Reducer`` cadences
    that want one collective per ~message_size elements.
    """

    axis_name: str = "data"
    config: Optional[ReduceConfig] = None
    message_size: int = 10_000_000
    # Reference-constructor spellings (distributed.py:167-177); folded into
    # ``config`` when one isn't given explicitly.
    gradient_average: Optional[bool] = None
    gradient_predivide_factor: Optional[float] = None
    allreduce_always_fp32: Optional[bool] = None
    compression: Optional[str] = None

    def __post_init__(self):
        _fold_reduce_config(self)

    def reduce(self, grads: Any) -> Any:
        return reduce_gradients(grads, self.axis_name, self.config)

    def plan_buckets(self, grads: Any,
                     triggers: Optional[Any] = None):
        """Greedy in-order bucket ids for the leaves of ``grads``
        (first-iteration bucket construction,
        ``apex/parallel/distributed.py:339-362``; planning runs in the
        native host library when built, ``csrc/apex_tpu_C.cpp``)."""
        from apex_tpu import _native
        leaves = jax.tree.leaves(grads)
        numels = [int(l.size) for l in leaves]
        trig = jax.tree.leaves(triggers) if triggers is not None else None
        return _native.plan_buckets(numels, self.message_size, trig)

    @property
    def reduce_fn(self) -> Callable[[Any], Any]:
        return self.reduce

    def pvary(self, params: Any) -> Any:
        """See :func:`pvary_params` — apply to params before ``jax.grad`` so
        grads arrive per-rank for :meth:`reduce`."""
        return pvary_params(params, self.axis_name)

    def broadcast_params(self, params: Any, root: int = 0) -> Any:
        """Initial param sync (``distributed.py:242``)."""
        return broadcast(params, self.axis_name, root)


@dataclasses.dataclass(frozen=True)
class Reducer:
    """Manual-trigger variant (``distributed.py:94-131``): user decides when
    to reduce (e.g. every N accumulation steps)."""

    axis_name: str = "data"
    config: Optional[ReduceConfig] = None
    gradient_average: Optional[bool] = None
    gradient_predivide_factor: Optional[float] = None
    allreduce_always_fp32: Optional[bool] = None
    compression: Optional[str] = None

    def __post_init__(self):
        _fold_reduce_config(self)

    def reduce(self, grads: Any) -> Any:
        return reduce_gradients(grads, self.axis_name, self.config)
