"""Where JAX's persistent compilation cache lives.

Every entry point that compiles for the chip calls :func:`enable`
before its first trace.  The directory is part of the cache key, so it
must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads the variable itself, so nothing is set
here), else ``.jax_cache`` in the checkout.  No other code sets a
compile-cache directory.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` (gitignored)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> Optional[str]:
    """Point JAX's persistent compilation cache at its fixed place and
    return the directory in use.  On the CPU platform nothing is set and
    ``None`` is returned: the cache is for chip compiles, which take
    minutes; an XLA:CPU entry is tied to the host it was built on.

    The key keeps the instructions' metadata
    (``jax_compilation_cache_include_metadata_in_key``; JAX strips it by
    default): per-layer metrics read ``op_name`` from the compiled
    program, and an executable cached from a tree with other scope names
    must not answer for this one.  Of the Python call stack an operation
    was traced under, that metadata then holds the innermost frame only
    (``jax_traceback_in_locations_limit``): with the callers in the key,
    one function lowered from two call sites is two entries, and a
    program's key depends on what the process traced before it (a jitted
    helper keeps the stack of its first caller)."""
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
