"""Structured trace spans over the profiling shims.

:func:`apex_tpu.utils.profiling.nvtx_range` already names a region in
both worlds — ``jax.named_scope`` (the name rides the HLO op metadata
into compiled programs and captured xplanes) and
``jax.profiler.TraceAnnotation`` (the host-side section shows on the
capture's python line).  This module layers *structure* on that shim:

- spans **nest** and the emitted name is the slash-joined path
  (``serve/step/decode``), so a capture groups by subsystem instead of
  scattering flat labels — :func:`current_path` returns the live path;
- spans are **timed into the metrics registry**: leaving a span
  observes its wall duration in the ``span_seconds__<path>`` histogram
  (dots and slashes sanitized to ``_``), giving every named region
  p50/p99 through the same :class:`~apex_tpu.obs.metrics.Histogram`
  quantile math the serve engine uses;
- under an **active trace** (calling a span inside ``jit`` tracing) the
  timing is suppressed — trace-time wall clock is compile cost, not
  runtime — while the named scope still lands in the HLO metadata.
  That is the whole contract: inside traced code a span contributes
  *metadata only*, so instrumentation can never add a host callback or
  a retrace hazard to the step (the graph-lint syncs pass on the
  instrumented serve/train lanes pins it).

Span naming convention (the catalog in
``docs/source/observability.rst``): ``<subsystem>/<region>`` with
lowercase snake segments — ``serve/decode_step``, ``serve/prefill``,
``train/step``.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, List, Optional

import jax

from apex_tpu.obs import metrics as metrics_mod
from apex_tpu.utils.profiling import nvtx_range

__all__ = ["span", "current_path", "traced_span"]

_state = threading.local()


def _stack() -> List[str]:
    if not hasattr(_state, "stack"):
        _state.stack = []
    return _state.stack


def current_path() -> str:
    """Slash-joined path of the live span stack (``""`` outside any)."""
    return "/".join(_stack())


def _tracing() -> bool:
    """True while jax is tracing (span timings suppressed there)."""
    return not jax.core.trace_ctx.is_top_level()


def metric_name(path: str) -> str:
    """``serve/decode_step`` -> ``span_seconds__serve_decode_step``."""
    safe = "".join(c if c.isalnum() else "_" for c in path)
    return f"span_seconds__{safe}"


@contextlib.contextmanager
def span(name: str, registry: Optional[metrics_mod.Registry] = None,
         record: bool = True):
    """Named region: HLO metadata + host trace annotation + (outside
    tracing) a wall-duration observation into the registry histogram
    for the span's full path."""
    stack = _stack()
    stack.append(name)
    path = "/".join(stack)
    tracing = _tracing()
    t0 = time.perf_counter()
    try:
        with nvtx_range(path):
            yield
    finally:
        stack.pop()
        if record and not tracing:
            reg = registry or metrics_mod.DEFAULT
            reg.histogram(metric_name(path),
                          f"wall seconds inside span {path!r}"
                          ).observe(time.perf_counter() - t0)


def traced_span(name: Optional[str] = None,
                registry: Optional[metrics_mod.Registry] = None
                ) -> Callable:
    """Decorator form (the :func:`apex_tpu.utils.annotate` shape, with
    span structure and timing)."""
    def deco(fn):
        label = name or fn.__name__

        def wrapped(*args, **kwargs):
            with span(label, registry=registry):
                return fn(*args, **kwargs)

        wrapped.__name__ = fn.__name__
        wrapped.__doc__ = fn.__doc__
        return wrapped
    return deco
