"""Synchronized BatchNorm over mesh axes.

Port of the reference SyncBatchNorm family (``apex/parallel/
optimized_sync_batchnorm*.py`` + ``csrc/welford.cu``, with the Python
fallback ``sync_batchnorm*.py`` semantics — including returning the output,
which the fork's Python path failed to do, SURVEY.md §0.2).

Statistics pipeline, matching the optimized path (§3.5 call stack):

1. local per-channel (count, mean, biased var) — single-pass Welford on
   device (``welford.cu:257-293``; on TPU a fused XLA reduction in fp32);
2. ``all_gather`` of per-device stats over the mesh axis, honoring
   ``process_group`` sub-grouping via ``axis_index_groups``
   (``optimized_sync_batchnorm_kernel.py:33-38``);
3. Chan's generalized merge → global (mean, biased var, invstd)
   (``welford_kernel_parallel``, ``welford.cu:557-585``);
4. running stats EMA with the unbiased ``m/(m-1)`` correction, written in the
   running-buffer dtype (fp16 running buffers honored,
   ``optimized_sync_batchnorm_kernel.py:48-51``);
5. elementwise normalize in fp32, cast back to input dtype.

The backward defaults to the reference's hand-written two-stage split:
train-mode normalization goes through :func:`_bn_train_apply`, a
``custom_vjp`` whose backward runs ``reduce_bn → allreduce →
batchnorm_backward`` (``welford.cu:323-411``).  Plain autodiff of the fp32
stats graph would save fp32 activation-sized residuals (double the HBM
traffic of a bf16 model); the custom VJP saves only the input at its own
dtype plus per-channel fp32 vectors, measured ~3-4% faster ResNet-50
steps on one chip.  Trade-offs: like the reference, the fused backward
supports reverse-mode AD only (``jax.jvp``/``jacfwd`` through a training
graph raises; eval mode is unaffected) — ``fused_backward=False``
switches to plain autodiff (same total derivative, forward-mode capable,
not available with BN ``process_group`` sub-groups whose gathered stats
cannot be transposed under shard_map VMA checking).

TPU note: channels-last is the native layout (the reference needed separate
``_c_last`` CUDA kernels; here any ``channel_axis`` compiles equally well).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax import lax


def local_mean_var(x: jax.Array, reduce_axes: Sequence[int]):
    """Local per-channel (mean, biased var, count) in fp32.

    Computed as the one-pass ``E[x^2] - E[x]^2`` pair — NOT Welford's
    update: both reductions read ``x`` once and XLA fuses them into a
    single pass (often into the producing conv's epilogue).  The
    two-pass centered formulation (``x.var()``) re-reads the full
    activation to square the residuals — measured +7% on the whole RN50
    b256 step (round 3).

    Numerics regime: single-pass cancellation loses ``~2*log2(|mean|/
    std)`` bits of the variance.  fp32 accumulation (24 mantissa bits)
    over BN-scale activations (|mean|/std of order 1-10^2, as produced
    by normalized nets) keeps that loss ≤ ~14 bits — far above the
    1e-5 tolerance SyncBN guarantees (BASELINE.md); the same trade
    cuDNN and flax make.  A pathological |mean|/std ≳ 10^3 regime would
    bite, but can't arise between BN layers that themselves normalize.
    The *cross-device* merge stays Chan's algorithm
    (:func:`welford_parallel`), which is where single-pass numerics
    would actually bite (large disjoint populations)."""
    x32 = x.astype(jnp.float32)
    count = 1
    for a in reduce_axes:
        count *= x.shape[a]
    mean = x32.mean(axis=tuple(reduce_axes))
    mean_sq = jnp.square(x32).mean(axis=tuple(reduce_axes))
    var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)  # biased
    return mean, var, count


#: Reference-parity export spelling (``syncbn.welford_mean_var``,
#: SURVEY §2.1 #19).  The NAME is historical — the reference's local
#: stats kernel is Welford (`welford.cu`); this implementation is the
#: one-pass pair documented in :func:`local_mean_var` (ADVICE r3: keep
#: the parity spelling, name the real algorithm honestly).
welford_mean_var = local_mean_var


def welford_parallel(means: jax.Array, vars_: jax.Array,
                     counts: jax.Array):
    """Chan's generalized merge of per-device (mean, biased var, count)
    stacked on axis 0 (``syncbn.welford_parallel``, ``welford.cu:557-585``).

    Returns (mean, biased var) per channel.
    """
    counts = counts.astype(jnp.float32)
    if counts.ndim == 1:
        counts = counts[:, None]
    total = counts.sum(axis=0)
    mean = (counts * means).sum(axis=0) / total
    m2 = (counts * vars_).sum(axis=0) \
        + (counts * jnp.square(means - mean[None, :])).sum(axis=0)
    return mean, m2 / total


def batchnorm_forward(x: jax.Array, mean: jax.Array, invstd: jax.Array,
                      weight: Optional[jax.Array],
                      bias: Optional[jax.Array],
                      channel_axis: int) -> jax.Array:
    """Elementwise normalize (``syncbn.batchnorm_forward[_c_last]``)."""
    shape = [1] * x.ndim
    shape[channel_axis] = x.shape[channel_axis]
    y = (x.astype(jnp.float32) - mean.reshape(shape)) * invstd.reshape(shape)
    if weight is not None:
        y = y * weight.reshape(shape).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(shape).astype(jnp.float32)
    return y.astype(x.dtype)


def reduce_bn(grad_out: jax.Array, x: jax.Array, mean: jax.Array,
              invstd: jax.Array, weight: Optional[jax.Array],
              channel_axis: int):
    """Local backward reductions (``syncbn.reduce_bn[_c_last]``,
    ``welford.cu:323-384``): per-channel ``(mean_dy, mean_dy_xmu,
    grad_weight, grad_bias)`` from local data.  The reference allreduces the
    two means between this and :func:`batchnorm_backward`; under autodiff the
    same split falls out of the traced forward, but the pieces are exported
    for manual composition and conformance tests."""
    ch = channel_axis % x.ndim
    reduce_axes = tuple(a for a in range(x.ndim) if a != ch)
    count = 1
    for a in reduce_axes:
        count *= x.shape[a]
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    dy = grad_out.astype(jnp.float32)
    xmu = x.astype(jnp.float32) - mean.reshape(shape)
    sum_dy = dy.sum(axis=reduce_axes)
    sum_dy_xmu = (dy * xmu).sum(axis=reduce_axes)
    # grad_weight/grad_bias are computed unconditionally from the same sums
    # (the reference kernel always produces them; welford.cu:323-384) — a
    # bias-only BN still needs grad_bias.
    grad_weight = sum_dy_xmu * invstd
    grad_bias = sum_dy
    return sum_dy / count, sum_dy_xmu / count, grad_weight, grad_bias


def batchnorm_backward(grad_out: jax.Array, x: jax.Array, mean: jax.Array,
                       invstd: jax.Array, weight: Optional[jax.Array],
                       mean_dy: jax.Array, mean_dy_xmu: jax.Array,
                       channel_axis: int) -> jax.Array:
    """Elementwise grad_input from globally-reduced means
    (``syncbn.batchnorm_backward[_c_last]``, ``welford.cu:385-411``)."""
    ch = channel_axis % x.ndim
    shape = [1] * x.ndim
    shape[ch] = x.shape[ch]
    dy = grad_out.astype(jnp.float32)
    xmu = x.astype(jnp.float32) - mean.reshape(shape)
    iv = invstd.reshape(shape)
    gi = (dy - mean_dy.reshape(shape)
          - xmu * jnp.square(iv) * mean_dy_xmu.reshape(shape)) * iv
    if weight is not None:
        gi = gi * weight.reshape(shape).astype(jnp.float32)
    return gi.astype(x.dtype)


# _c_last spellings: NHWC is TPU's native layout, so the reference's separate
# channels-last kernels (welford.cu:586-829) collapse to channel_axis=-1 —
# same code, exported under the reference names for inventory parity.
def welford_mean_var_c_last(x: jax.Array):
    return welford_mean_var(x, tuple(range(x.ndim - 1)))


def batchnorm_forward_c_last(x, mean, invstd, weight, bias):
    return batchnorm_forward(x, mean, invstd, weight, bias, channel_axis=-1)


def reduce_bn_c_last(grad_out, x, mean, invstd, weight):
    return reduce_bn(grad_out, x, mean, invstd, weight, channel_axis=-1)


def batchnorm_backward_c_last(grad_out, x, mean, invstd, weight,
                              mean_dy, mean_dy_xmu):
    return batchnorm_backward(grad_out, x, mean, invstd, weight,
                              mean_dy, mean_dy_xmu, channel_axis=-1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bn_train_apply(channel_axis, axis_name, process_group,
                    x, mean, invstd, weight, bias):
    """Normalize with batch statistics, with the reference's hand-written
    backward (``reduce_bn → allreduce → batchnorm_backward``,
    ``optimized_sync_batchnorm_kernel.py:83-101``) as a ``custom_vjp``.

    The backward formula is the *total* derivative through the batch
    statistics (mean/invstd are functions of x over the global batch), so
    the saved-for-backward residuals are just the input at its own dtype
    plus per-channel fp32 vectors — plain autodiff of the fp32 stats graph
    instead saves fp32 activation-sized intermediates, doubling HBM traffic
    for bf16 models.  Cotangents for ``mean``/``invstd`` are defined zero:
    their dependence on ``x`` is folded into ``grad_input`` analytically.
    """
    return batchnorm_forward(x, mean, invstd, weight, bias, channel_axis)


def _bn_train_fwd(channel_axis, axis_name, process_group,
                  x, mean, invstd, weight, bias):
    y = batchnorm_forward(x, mean, invstd, weight, bias, channel_axis)
    return y, (x, mean, invstd, weight, bias)


def _bn_train_bwd(channel_axis, axis_name, process_group, res, dy):
    x, mean, invstd, weight, bias = res
    mean_dy, mean_dy_xmu, gw, gb = reduce_bn(dy, x, mean, invstd, weight,
                                             channel_axis)
    if axis_name is not None:
        # Global means of dy / dy·(x-µ): allreduce + divide by world size
        # (kernel.py:91-97); equal per-rank counts assumed, as the
        # reference does.  Grouped reductions ride all_gather + local mean,
        # the same recipe (and VMA-compatibility reason) as the forward.
        if process_group is not None:
            # already a tuple-of-tuples (normalized by the caller; must be
            # hashable as a nondiff arg)
            mean_dy = lax.all_gather(
                mean_dy, axis_name,
                axis_index_groups=process_group).mean(axis=0)
            mean_dy_xmu = lax.all_gather(
                mean_dy_xmu, axis_name,
                axis_index_groups=process_group).mean(axis=0)
        else:
            mean_dy = lax.pmean(mean_dy, axis_name)
            mean_dy_xmu = lax.pmean(mean_dy_xmu, axis_name)
    gi = batchnorm_backward(dy, x, mean, invstd, weight,
                            mean_dy, mean_dy_xmu, channel_axis)
    if axis_name is not None:
        # weight/bias are replicated across the whole axis (even with BN
        # sub-groups), so their cotangent is the full-axis sum — what
        # autodiff's transpose-of-broadcast inserts implicitly.
        if weight is not None:
            gw = lax.psum(gw, axis_name)
        if bias is not None:
            gb = lax.psum(gb, axis_name)
    return (gi,
            jnp.zeros_like(mean),
            jnp.zeros_like(invstd),
            gw.astype(weight.dtype) if weight is not None else None,
            gb.astype(bias.dtype) if bias is not None else None)


_bn_train_apply.defvjp(_bn_train_fwd, _bn_train_bwd)


class SyncBatchNorm(nn.Module):
    """Cross-device BatchNorm (``apex.parallel.SyncBatchNorm``).

    Attributes mirror the reference module (``optimized_sync_batchnorm.py:
    9-84``) adapted to flax conventions:

    - ``axis_name``: mesh axis to synchronize over; ``None`` degrades to
      ordinary (local) BatchNorm — the single-process fallback the reference
      has (``sync_batchnorm.py:86-91``).
    - ``process_group``: ``axis_index_groups`` — the
      ``create_syncbn_process_group`` capability (sub-pod BN groups).
    - ``channel_axis``: -1 (NHWC, TPU-native) by default; the reference's
      ``channel_last=True`` path.  Any axis works.
    - running stats live in the ``batch_stats`` collection; ``momentum``
      follows torch semantics: ``new = (1-momentum)·old + momentum·batch``.
    """

    use_running_average: Optional[bool] = None
    momentum: float = 0.1
    epsilon: float = 1e-5
    affine: bool = True
    axis_name: Optional[str] = None
    process_group: Optional[Sequence[Sequence[int]]] = None
    channel_axis: int = -1
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    running_dtype: Any = jnp.float32
    #: Use the hand-written two-stage backward (``reduce_bn`` →
    #: allreduce → ``batchnorm_backward``) instead of plain autodiff
    #: through the stats graph.  Both produce the same total derivative;
    #: back-to-back A/B on one chip measures the fused backward ~3-4%
    #: faster on ResNet-50 steps (smaller residuals: x at its own dtype +
    #: per-channel fp32 vectors vs the autodiff-saved fp32 stats graph),
    #: so it is the default.  ``False`` enables forward-mode AD; invalid
    #: with ``process_group`` (grouped gathered stats cannot be
    #: transposed under shard_map VMA checking).
    fused_backward: bool = True

    @nn.compact
    def __call__(self, x: jax.Array,
                 use_running_average: Optional[bool] = None) -> jax.Array:
        use_ra = nn.merge_param("use_running_average",
                                self.use_running_average, use_running_average)
        ch_axis = self.channel_axis % x.ndim
        num_features = x.shape[ch_axis]
        reduce_axes = [a for a in range(x.ndim) if a != ch_axis]

        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((num_features,),
                                                  self.running_dtype))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((num_features,),
                                                self.running_dtype))
        if self.affine:
            weight = self.param("scale", nn.initializers.ones,
                                (num_features,), self.param_dtype)
            bias = self.param("bias", nn.initializers.zeros,
                              (num_features,), self.param_dtype)
        else:
            weight = bias = None

        if use_ra:
            # Eval: normalize with running stats (reference falls back to
            # F.batch_norm, sync_batchnorm_kernel.py:82-85).
            mean = ra_mean.value.astype(jnp.float32)
            var = ra_var.value.astype(jnp.float32)
            invstd = lax.rsqrt(var + self.epsilon)
            return batchnorm_forward(x, mean, invstd, weight, bias, ch_axis)

        with jax.named_scope("sync_bn_welford"):  # reference nvtx range
            local_mean, local_var, local_count = welford_mean_var(
                x, reduce_axes)

        # During init there is no bound mesh axis to reduce over; local stats
        # are fine (flax's BatchNorm does the same).
        sync = self.axis_name is not None and not self.is_initializing()
        if sync and self.process_group is None:
            # Whole-axis sync: Chan's merge expressed as two psum rounds —
            # the same math as gathering per-rank stats and merging
            # (welford.cu:557-585), but psum outputs are replication-typed,
            # which shard_map's VMA checker can verify, so running stats stay
            # provably replicated.
            c = lax.pcast(jnp.asarray(float(local_count), jnp.float32),
                          (self.axis_name,), to="varying")
            total_count = lax.psum(c, self.axis_name)
            mean = lax.psum(local_mean * c, self.axis_name) / total_count
            m2 = lax.psum(c * local_var + c * jnp.square(local_mean - mean),
                          self.axis_name)
            var = m2 / total_count
        elif sync:
            # Grouped sync: grouped psum is unsupported under VMA checking,
            # so use the reference's own recipe — all_gather per-group stats
            # then Chan-merge locally (optimized_sync_batchnorm_kernel.py:
            # 33-39).  Results (and running stats) genuinely differ across
            # groups, i.e. they are device-varying by construction.
            groups = self.process_group
            counts = jnp.full((1,), float(local_count), jnp.float32)
            g_mean = lax.all_gather(local_mean, self.axis_name,
                                    axis_index_groups=groups)
            g_var = lax.all_gather(local_var, self.axis_name,
                                   axis_index_groups=groups)
            g_count = lax.all_gather(counts, self.axis_name,
                                     axis_index_groups=groups)
            mean, var = welford_parallel(g_mean, g_var, g_count)
            total_count = g_count.sum()
        else:
            mean, var = local_mean, local_var
            total_count = jnp.asarray(float(local_count), jnp.float32)

        invstd = lax.rsqrt(var + self.epsilon)

        if not self.is_initializing():
            # Unbiased correction m/(m-1) for the running var
            # (sync_batchnorm.py:92-128).
            unbiased = var * total_count / jnp.maximum(total_count - 1.0, 1.0)
            m = self.momentum
            ra_mean.value = ((1.0 - m) * ra_mean.value.astype(jnp.float32)
                             + m * lax.stop_gradient(mean)
                             ).astype(self.running_dtype)
            ra_var.value = ((1.0 - m) * ra_var.value.astype(jnp.float32)
                            + m * lax.stop_gradient(unbiased)
                            ).astype(self.running_dtype)

        # Train-mode normalize with the hand-written backward: residuals are
        # x (own dtype) + per-channel fp32 vectors, not the fp32 stats graph.
        if not self.fused_backward:
            if sync and self.process_group is not None:
                raise ValueError(
                    "fused_backward=False is unsupported with a BN "
                    "process_group: autodiff would transpose the grouped "
                    "all_gather of stats into a grouped reduction, which "
                    "shard_map VMA checking rejects (see the grouped-sync "
                    "forward comment)")
            # Plain autodiff through the stats graph — same total
            # derivative, and forward-mode capable.
            return batchnorm_forward(x, mean, invstd, weight, bias, ch_axis)
        groups = (tuple(map(tuple, self.process_group))
                  if sync and self.process_group is not None else None)
        return _bn_train_apply(ch_axis, self.axis_name if sync else None,
                               groups, x, lax.stop_gradient(mean),
                               lax.stop_gradient(invstd), weight, bias)


# Local BatchNorm is the axis_name=None degenerate case; exported under the
# familiar name for model code.
BatchNorm = SyncBatchNorm
