"""FusedLayerNorm.

Port of ``apex/normalization/fused_layer_norm.py`` +
``csrc/layer_norm_cuda_kernel.cu``.  The CUDA implementation computes (μ, σ²)
with warp-level Welford + Chan merging in fp32 even for fp16 inputs
(``layer_norm_cuda.cpp:132,154``), applies the normalization elementwise, and
has a two-stage backward for (γ, β).  The TPU equivalent keeps the same
numerics contract — statistics in fp32, output in input dtype — as a Pallas
kernel with a custom VJP (:mod:`apex_tpu.ops.pallas.layer_norm_kernels`),
with this jnp path as the always-available reference
(the analog of the reference's CPU ``F.layer_norm`` fallback,
``fused_layer_norm.py:148-150``).

Input is reshaped to ``(n1, n2)`` around ``normalized_shape`` exactly like
the C++ host side (``layer_norm_cuda.cpp:6-98``).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import flax.linen as nn

from apex_tpu.ops import use_pallas


def _normalized_shape(shape: Union[int, Sequence[int]]) -> Tuple[int, ...]:
    if isinstance(shape, int):
        return (shape,)
    return tuple(shape)


def fused_layer_norm(x: jax.Array,
                     normalized_shape: Union[int, Sequence[int]],
                     eps: float = 1e-5) -> jax.Array:
    """Non-affine layer norm (``fused_layer_norm_cuda.forward``,
    ``layer_norm_cuda.cpp:234-239``)."""
    return fused_layer_norm_affine(x, None, None, normalized_shape, eps)


def fused_layer_norm_affine(x: jax.Array,
                            weight: Optional[jax.Array],
                            bias: Optional[jax.Array],
                            normalized_shape: Union[int, Sequence[int]],
                            eps: float = 1e-5) -> jax.Array:
    """Affine layer norm (``fused_layer_norm_cuda.forward_affine``).

    Statistics are computed in fp32 regardless of input dtype; the affine
    transform runs in fp32 and the result is cast back to the input dtype.
    """
    nshape = _normalized_shape(normalized_shape)
    assert x.shape[len(x.shape) - len(nshape):] == nshape, (
        f"trailing dims of {x.shape} must equal normalized_shape {nshape}")
    n2 = 1
    for d in nshape:
        n2 *= d
    n1 = x.size // n2

    from apex_tpu.ops.pallas import layer_norm_kernels as lnk
    if use_pallas() and lnk.supported(n2, x.dtype):
        x2d = x.reshape(n1, n2)
        w = None if weight is None else weight.reshape(n2)
        b = None if bias is None else bias.reshape(n2)
        return lnk.layer_norm_fwd_vjp(x2d, w, b, eps).reshape(x.shape)

    x32 = x.reshape(n1, n2).astype(jnp.float32)
    mean = x32.mean(axis=1, keepdims=True)
    var = x32.var(axis=1, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    y = (x32 - mean) * invvar
    if weight is not None:
        y = y * weight.reshape(1, n2).astype(jnp.float32)
    if bias is not None:
        y = y + bias.reshape(1, n2).astype(jnp.float32)
    return y.astype(x.dtype).reshape(x.shape)


def fused_rms_norm_affine(x: jax.Array, weight: jax.Array,
                          normalized_shape: Union[int, Sequence[int]],
                          eps: float = 1e-5) -> jax.Array:
    """Affine RMS norm (upstream ``fused_rms_norm_affine``):
    ``x * rsqrt(mean(x^2) + eps) * weight``, the mean square in fp32
    whatever the input's dtype, the result in the input's dtype.  On the
    Pallas path it is the LayerNorm kernels' ``rms`` mode."""
    nshape = _normalized_shape(normalized_shape)
    assert x.shape[len(x.shape) - len(nshape):] == nshape, (
        f"trailing dims of {x.shape} must equal normalized_shape {nshape}")
    n2 = 1
    for d in nshape:
        n2 *= d
    n1 = x.size // n2

    from apex_tpu.ops.pallas import layer_norm_kernels as lnk
    if use_pallas() and lnk.supported(n2, x.dtype):
        return lnk.rms_norm_fwd_vjp(x.reshape(n1, n2), weight.reshape(n2),
                                    eps).reshape(x.shape)

    x32 = x.reshape(n1, n2).astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=1, keepdims=True) + eps)
    y = x32 * inv * weight.reshape(1, n2).astype(jnp.float32)
    return y.astype(x.dtype).reshape(x.shape)


class FusedLayerNorm(nn.Module):
    """Module mirroring ``torch.nn.LayerNorm`` semantics
    (``fused_layer_norm.py:64-160``): ``normalized_shape``, ``eps``,
    ``elementwise_affine``; params initialized to γ=1, β=0."""

    normalized_shape: Union[int, Sequence[int]]
    eps: float = 1e-5
    elementwise_affine: bool = True
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        nshape = _normalized_shape(self.normalized_shape)
        if self.elementwise_affine:
            weight = self.param("scale", nn.initializers.ones, nshape,
                                self.param_dtype)
            bias = self.param("bias", nn.initializers.zeros, nshape,
                              self.param_dtype)
        else:
            weight = bias = None
        return fused_layer_norm_affine(x, weight, bias, nshape, self.eps)


class FusedRMSNorm(nn.Module):
    """Upstream ``apex.normalization.FusedRMSNorm``: one gain ``scale``
    (initialised to 1), no bias, no centring."""

    normalized_shape: Union[int, Sequence[int]]
    eps: float = 1e-5
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        nshape = _normalized_shape(self.normalized_shape)
        weight = self.param("scale", nn.initializers.ones, nshape,
                            self.param_dtype)
        return fused_rms_norm_affine(x, weight, nshape, self.eps)
