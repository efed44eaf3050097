"""The optimizers as published, on float32 trees, one leaf at a time.

``adam``: Kingma & Ba (2015) with bias correction, epsilon outside the
root, and (where ``weight_decay`` is set) L2 decay added to the
gradient; with ``lr_warmup_steps`` the rate rises linearly to ``lr``
over that many steps (:func:`warmup`).  ``lamb``: You et al. (2020) as
NVIDIA's apex computes it:
the gradient is first divided by ``max(global_norm / max_grad_norm, 1)``,
then Adam's moments with bias correction, the decay added to the
update, and each leaf's step scaled by ``|p| / |update|`` (1 where
either norm is zero).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(params):
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": jnp.float32(0.0)}


def warmup(lr, t, lr_warmup_steps: int = 0):
    """The rate at step ``t`` (1-based): ``lr * min(1, t /
    lr_warmup_steps)``, float32; ``lr`` itself where there is no warm-up
    (DeepSeek-V3, arXiv:2412.19437 section 4.2: linear from 0 over the
    first 2K steps)."""
    if not lr_warmup_steps:
        return lr
    return jnp.float32(lr) * jnp.minimum(
        jnp.float32(1.0), t / jnp.float32(lr_warmup_steps))


def adam(params, grads, state, *, lr, betas=(0.9, 0.999), eps=1e-8,
         weight_decay=0.0, lr_warmup_steps=0):
    b1, b2 = betas
    t = state["t"] + 1.0
    lr = warmup(lr, t, lr_warmup_steps)
    if weight_decay:
        grads = jax.tree.map(lambda g, p: g + weight_decay * p, grads, params)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    step = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    params = jax.tree.map(
        lambda p, m, v: p - step * m / (jnp.sqrt(v) + eps), params, m, v)
    return params, {"m": m, "v": v, "t": t}


def lamb(params, grads, state, *, lr, betas=(0.9, 0.999), eps=1e-6,
         weight_decay=0.01, max_grad_norm=1.0):
    b1, b2 = betas
    t = state["t"] + 1.0
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    clip = jnp.maximum(gnorm / max_grad_norm, 1.0) if max_grad_norm else 1.0
    grads = jax.tree.map(lambda g: g / clip, grads)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)

    def leaf(p, m, v):
        update = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) \
            + weight_decay * p
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        u_norm = jnp.sqrt(jnp.sum(jnp.square(update)))
        ratio = jnp.where((p_norm > 0) & (u_norm > 0),
                          p_norm / jnp.maximum(u_norm, 1e-38), 1.0)
        return p - lr * ratio * update

    return jax.tree.map(leaf, params, m, v), {"m": m, "v": v, "t": t}


OPTIMIZERS = {"adam": adam, "lamb": lamb}
