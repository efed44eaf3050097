"""Building blocks shared by the reference architectures.

Every matmul takes its operands through ``q``, the identity in the
reference.  The control of ``correct`` passes :func:`fp8_operands`
instead: the same mathematics one precision step below bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def identity(x):
    return x


def _rounded(x, dtype, top: float):
    """``x`` rounded to the float8 ``dtype`` under a per-tensor absmax
    scale (``top`` is the type's largest finite value) and back."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_operands(x):
    """What a float8 matmul sees of an operand, as delayed-scaling fp8
    training has it (and the program's own O4 level): the operand in
    e4m3 going forward, its gradient in e5m2 coming back, each under a
    per-tensor scale."""
    return _rounded(x, jnp.float8_e4m3fn, 448.0)


fp8_operands.defvjp(
    lambda x: (fp8_operands(x), None),
    lambda _, g: (_rounded(g, jnp.float8_e5m2, 57344.0),))


def dense(x, p, q=identity):
    y = jnp.matmul(q(x), q(p["kernel"]))
    return y + p["bias"] if "bias" in p else y


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def attention(q_, k_, v_, causal: bool, q=identity):
    """``softmax(Q K^T / sqrt(d)) V`` over ``(B, L, H, D)`` tensors."""
    d = q_.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q(q_), q(k_)) / jnp.sqrt(
        jnp.float32(d))
    if causal:
        n = scores.shape[-1]
        keep = jnp.tril(jnp.ones((n, n), bool))
        scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(v_))


def cross_entropy(logits, labels):
    """``-log softmax(logits)[label]`` per position, float32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked


def stack_layers(params: dict, prefix: str, n: int):
    """The ``n`` per-layer subtrees ``prefix0 .. prefix{n-1}`` as one tree
    with a leading layer axis, for ``lax.scan``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[params[f"{prefix}{i}"] for i in range(n)])


def scan_layers(layer_fn, x, stacked):
    """``x`` through every layer of ``stacked``; each layer's activations
    are recomputed in the backward pass, so the reference fits."""
    body = jax.checkpoint(lambda h, lp: (layer_fn(h, lp), None))
    return jax.lax.scan(body, x, stacked)[0]
