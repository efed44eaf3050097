"""Weights from ``--seed``, made on the device in one jitted call.

A family states its parameter tree as ``{path: (shape, kind)}``; this
file turns it into arrays.  Both the program and the plain reference
are handed what this makes: neither makes weights for the other.

Kinds: ``matrix`` and ``embedding`` are N(0, 0.02); ``bias`` is
N(0, 0.02) too, so that no leaf starts at a point where its gradient
vanishes by symmetry; ``scale`` (a LayerNorm gain) is 1 + N(0, 0.1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

STD = {"matrix": 0.02, "embedding": 0.02, "bias": 0.02, "scale": 0.1}


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (the driver's seeds
    pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf(key, index: int, shape, kind: str, dtype=jnp.float32):
    n = jax.random.normal(jax.random.fold_in(key, index), shape,
                          jnp.float32) * STD[kind]
    if kind == "scale":
        n = n + 1.0
    return n.astype(dtype)


def nest(flat: dict) -> dict:
    """``{"a/b/c": x}`` -> ``{"a": {"b": {"c": x}}}``."""
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = value
    return tree


def flatten(tree: dict, prefix: str = "") -> dict:
    flat = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            flat.update(flatten(v, path))
        else:
            flat[path] = v
    return flat


def make(spec: dict, key) -> dict:
    """The nested parameter tree of ``spec`` (traceable: call it under
    ``jit`` with the key as the argument)."""
    return nest({path: leaf(key, i, shape, kind)
                 for i, (path, (shape, kind)) in enumerate(sorted(spec.items()))})
