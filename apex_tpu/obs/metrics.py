"""Process-local metrics registry: counters, gauges, fixed-bucket
histograms — with **1-step-lagged** resolution of device values.

The design constraint comes from the step path: a serving or training
loop that fetches a metric scalar the step it was produced inserts a
host sync exactly where the paper's speed lives.  The resilience loop
(PR 3) solved this privately — dispatch steps back-to-back, resolve
each step's metrics one step behind, by which point they are already
computed on an accelerator.  This module makes that the *registry's*
contract so every subsystem shares one implementation:

- instruments accept plain host numbers (applied immediately, ~dict-op
  cost) **or concrete ``jax.Array`` values** (appended to a pending
  queue, *no* ``device_get``);
- :meth:`Registry.tick` marks a step boundary; groups older than
  ``lag`` steps (default 1) become resolvable, and are fetched in
  **batches** of ``resolve_every`` groups (default 8) with a single
  ``device_get`` — so a deferred metric is at least ``lag`` and at
  most ``lag + resolve_every - 1`` steps stale, and the step path
  pays one amortized fetch of already-computed values instead of one
  sync point per step (even a lagged per-step ``device_get`` is a
  measurable pipeline serialization on a fast step);
- :meth:`Registry.flush` drains everything (end of run / incident
  snapshot time).

Passing a **tracer** (calling an instrument *inside* a jitted
function) is a hard error: it would leak the tracer and silently
record nothing.  Inside traced code use :mod:`apex_tpu.obs.spans`
(named scopes land in the HLO metadata instead); record metrics on the
step's *outputs*.

Histograms are fixed-bucket (device-friendly: an ``observe`` is a
``searchsorted``, never a growing reservoir) and quantiles are
interpolated from the cumulated bucket counts the way Prometheus's
``histogram_quantile`` does — the serve engine and the serving tools
read p50/p99 through :meth:`Histogram.quantile` so they can never
disagree on percentile math.

Exports: :meth:`Registry.snapshot` (JSON document — the ``export``
section of the committed ``OBS_r01.json``) and
:meth:`Registry.to_prometheus` (text exposition format).

This module itself imports no jax at module level — jax is touched
lazily, only to classify deferred values and to resolve them.  (The
``apex_tpu.obs`` package init does import jax via :mod:`.spans`, like
every other ``apex_tpu`` subpackage; the lazy imports here keep the
jax dependency confined to the two deferred-value code paths, not a
backend-isolation guarantee.)
"""

from __future__ import annotations

import bisect
import math
import threading
import time
from collections import deque
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "DEFAULT", "get_registry", "counter", "gauge", "histogram",
    "instrument_step", "LATENCY_BUCKETS",
]

#: default histogram bucket upper bounds for step/span latencies in
#: SECONDS: geometric ladder 100 us .. ~26 s (factor 2), wide enough
#: for a 2.7 ms chip decode step and a CPU-smoke step alike; the +inf
#: overflow bucket is implicit.
LATENCY_BUCKETS = tuple(1e-4 * 2.0 ** i for i in range(19))


def _classify(value: Any) -> str:
    """``"host"`` | ``"deferred"``; raises on a tracer (recording a
    metric inside a traced function is a bug, not a deferral)."""
    if isinstance(value, (int, float, bool, np.generic, np.ndarray)):
        return "host"
    try:
        import jax
    except ImportError:          # jax-free process: everything is host
        return "host"
    if isinstance(value, jax.core.Tracer):
        raise TypeError(
            "metrics must be recorded on step OUTPUTS (concrete "
            "jax.Array values resolve with 1-step lag), never inside "
            "a traced function — use apex_tpu.obs.spans for named "
            "regions inside jit")
    if isinstance(value, jax.Array):
        return "deferred"
    return "host"


class _Instrument:
    """Base: a named instrument owned by one :class:`Registry`."""

    kind = "untyped"

    def __init__(self, registry: "Registry", name: str, help: str = ""):
        self._registry = registry
        self.name = name
        self.help = help

    def _record(self, value: Any) -> None:
        # fast path: plain host numbers are the per-step hot case (a
        # few of these per serving/training step — they must cost
        # microseconds, not numpy dispatch)
        if type(value) in (int, float, bool):
            with self._registry._lock:
                self._apply_scalar(float(value))
        elif _classify(value) == "deferred":
            self._registry._defer(self, value)
        else:
            with self._registry._lock:
                self._apply(value)

    def _apply_scalar(self, value: float) -> None:
        self._apply(value)

    def _apply(self, value: Any) -> None:  # pragma: no cover - abstract
        raise NotImplementedError


class Counter(_Instrument):
    """Monotonic accumulator.  ``inc(v)`` adds ``v`` (default 1); a
    deferred array adds ``sum(asarray(v))`` once resolved — so
    ``inc(overflow_flag)`` counts a boolean step output and a
    per-scaler tuple stacked into one array counts every firing."""

    kind = "counter"

    def __init__(self, registry, name, help=""):
        super().__init__(registry, name, help)
        self.value = 0.0

    def inc(self, value: Any = 1.0) -> None:
        self._record(value)

    def _apply_scalar(self, value: float) -> None:
        self.value += value

    def _apply(self, value: Any) -> None:
        self.value += float(np.sum(np.asarray(value, dtype=np.float64)))


class Gauge(_Instrument):
    """Last-write-wins scalar.  A deferred array resolves to its mean
    (a scalar stays itself)."""

    kind = "gauge"

    def __init__(self, registry, name, help=""):
        super().__init__(registry, name, help)
        self.value = 0.0

    def set(self, value: Any) -> None:
        self._record(value)

    def _apply_scalar(self, value: float) -> None:
        self.value = value

    def _apply(self, value: Any) -> None:
        self.value = float(np.mean(np.asarray(value, dtype=np.float64)))


class Histogram(_Instrument):
    """Fixed-bucket histogram: ``buckets`` are sorted finite upper
    bounds; an implicit +inf bucket catches the overflow.  ``observe``
    accepts a scalar or an array (every element observed)."""

    kind = "histogram"

    def __init__(self, registry, name, help="",
                 buckets: Sequence[float] = LATENCY_BUCKETS):
        super().__init__(registry, name, help)
        bounds = tuple(float(b) for b in buckets)
        if not bounds or list(bounds) != sorted(set(bounds)) or \
                not all(math.isfinite(b) for b in bounds):
            raise ValueError(
                f"histogram {name!r}: buckets must be strictly "
                f"increasing finite upper bounds, got {buckets!r}")
        self.bounds = bounds
        self.counts = np.zeros(len(bounds) + 1, np.int64)
        self.sum = 0.0
        self.count = 0
        self._max = -math.inf

    def observe(self, value: Any) -> None:
        self._record(value)

    def _apply_scalar(self, value: float) -> None:
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.sum += value
        self.count += 1
        if value > self._max:
            self._max = value

    def _apply(self, value: Any) -> None:
        arr = np.asarray(value, dtype=np.float64).ravel()
        if arr.size == 0:
            return
        idx = np.searchsorted(self.bounds, arr, side="left")
        np.add.at(self.counts, idx, 1)
        self.sum += float(arr.sum())
        self.count += arr.size
        self._max = max(self._max, float(arr.max()))

    # -- read side ----------------------------------------------------

    def state(self) -> Tuple[np.ndarray, float, int, float]:
        """Opaque snapshot for windowed reads (``quantile(q,
        since=state)`` — how a caller isolates one offered-load
        level on a long-lived engine)."""
        return (self.counts.copy(), self.sum, self.count, self._max)

    def quantile(self, q: float, since=None) -> float:
        """Prometheus-style ``histogram_quantile``: rank-interpolated
        within the owning bucket (lower edge 0 for the first bucket);
        observations in the +inf bucket interpolate toward the largest
        value seen.  ``nan`` when (the window holds) no observations."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile {q} outside [0, 1]")
        counts, _, total, hi_max = self.counts, self.sum, self.count, \
            self._max
        if since is not None:
            counts = counts - since[0]
            total = self.count - since[2]
            # the window's max is only known when it SET the running
            # max; otherwise a stale pre-window max (e.g. an excluded
            # compile step) must not stretch the overflow bucket —
            # fall back to the last finite bound
            if not self._max > since[3]:
                hi_max = -math.inf
        if total <= 0:
            return math.nan
        rank = q * total
        cum = np.cumsum(counts)
        i = int(np.searchsorted(cum, rank, side="left"))
        i = min(i, len(counts) - 1)
        lo = 0.0 if i == 0 else self.bounds[i - 1]
        hi = self.bounds[i] if i < len(self.bounds) else \
            (hi_max if math.isfinite(hi_max) else lo)
        in_bucket = counts[i]
        if in_bucket <= 0 or hi <= lo:
            return float(hi)
        prev = cum[i - 1] if i else 0
        frac = (rank - prev) / in_bucket
        return float(lo + (hi - lo) * min(max(frac, 0.0), 1.0))


class Registry:
    """A process-local instrument registry with lagged resolution (see
    the module docstring).  ``counter``/``gauge``/``histogram`` are
    get-or-create: asking twice for one name returns the same
    instrument; asking for it as a different kind is an error."""

    def __init__(self, lag: int = 1, resolve_every: int = 8):
        if lag < 0:
            raise ValueError(f"lag={lag}")
        if resolve_every < 1:
            raise ValueError(f"resolve_every={resolve_every}")
        self.lag = lag
        self.resolve_every = resolve_every
        self._lock = threading.RLock()
        self._resolve_lock = threading.Lock()
        self._instruments: Dict[str, _Instrument] = {}
        # sealed groups of (instrument, deferred value), oldest first
        self._pending: Deque[List[Tuple[_Instrument, Any]]] = deque()
        self._current: List[Tuple[_Instrument, Any]] = []

    # -- instrument creation ------------------------------------------

    def _get(self, cls, name: str, help: str, **kwargs) -> _Instrument:
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(self, name, help, **kwargs)
                self._instruments[name] = inst
            elif not isinstance(inst, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{inst.kind}, not {cls.kind}")
            return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "",
                  buckets: Sequence[float] = LATENCY_BUCKETS
                  ) -> Histogram:
        return self._get(Histogram, name, help, buckets=buckets)

    # -- lagged resolution --------------------------------------------

    def _defer(self, instrument: _Instrument, value: Any) -> None:
        with self._lock:
            self._current.append((instrument, value))

    @property
    def pending_groups(self) -> int:
        """Sealed-but-unresolved groups (tests pin the lag contract)."""
        with self._lock:
            return len(self._pending) + (1 if self._current else 0)

    def tick(self) -> None:
        """Step boundary: seal the current deferred group; once
        ``resolve_every`` groups have aged past ``lag``, fetch them
        with one batched ``device_get`` (values at least one step
        behind dispatch are already computed on an accelerator, so
        the amortized fetch never stalls the pipeline)."""
        with self._lock:
            if self._current:
                self._pending.append(self._current)
                self._current = []
        self._drain(keep=self.lag, min_batch=self.resolve_every)

    def flush(self) -> None:
        """Resolve everything pending (end of run, incident capture)."""
        with self._lock:
            if self._current:
                self._pending.append(self._current)
                self._current = []
        self._drain(keep=0, min_batch=1)

    def discard_pending(self) -> None:
        """Drop unresolved deferred values (a rewind re-dispatches the
        steps whose metrics these were — resolving them would count the
        abandoned timeline)."""
        with self._lock:
            self._pending.clear()
            self._current = []

    def _drain(self, keep: int, min_batch: int) -> None:
        """Pop every group past the newest ``keep``, fetch, apply.
        ``_resolve_lock`` is held across pop-and-apply so concurrent
        resolvers (a loop's ``tick`` racing an exporter's ``flush``)
        apply batches in queue order — a stale loss must never
        overwrite a newer one.  The ``device_get`` happens OUTSIDE
        ``_lock`` (a fetch waiting on a wedged device must not block
        :meth:`snapshot` — the watchdog's incident capture reads the
        resolved state through that lock, and only that lock)."""
        with self._resolve_lock:
            with self._lock:
                ripe = len(self._pending) - keep
                if ripe < min_batch:
                    return
                entries = [e for _ in range(ripe)
                           for e in self._pending.popleft()]
            if not entries:
                return
            import jax
            values = jax.device_get([v for _, v in entries])
            with self._lock:
                for (inst, _), host in zip(entries, values):
                    inst._apply(host)

    # -- export --------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-serializable export of every instrument's *resolved*
        state (call :meth:`flush` first to include the lag window)."""
        out = []
        with self._lock:
            for name in sorted(self._instruments):
                inst = self._instruments[name]
                rec: dict = {"name": name, "type": inst.kind,
                             "help": inst.help}
                if isinstance(inst, Histogram):
                    rec["buckets"] = {
                        _fmt_le(b): int(c) for b, c in
                        zip(inst.bounds + (math.inf,),
                            np.cumsum(inst.counts).tolist())}
                    rec["sum"] = round(float(inst.sum), 9)
                    rec["count"] = int(inst.count)
                else:
                    rec["value"] = float(inst.value)
                out.append(rec)
        return {"metrics": out}

    def to_prometheus(self) -> str:
        """Prometheus text exposition format (histograms as cumulative
        ``_bucket{le=...}`` series plus ``_sum``/``_count``)."""
        lines: List[str] = []
        with self._lock:
            for name in sorted(self._instruments):
                inst = self._instruments[name]
                if inst.help:
                    lines.append(f"# HELP {name} {inst.help}")
                lines.append(f"# TYPE {name} {inst.kind}")
                if isinstance(inst, Histogram):
                    cum = np.cumsum(inst.counts)
                    for b, c in zip(inst.bounds + (math.inf,), cum):
                        lines.append(
                            f'{name}_bucket{{le="{_fmt_le(b)}"}} '
                            f"{int(c)}")
                    lines.append(f"{name}_sum {_fmt_val(inst.sum)}")
                    lines.append(f"{name}_count {inst.count}")
                else:
                    lines.append(f"{name} {_fmt_val(inst.value)}")
        return "\n".join(lines) + "\n"

    def reset(self) -> None:
        """Drop every instrument and all pending values (tests)."""
        with self._lock:
            self._instruments.clear()
            self._pending.clear()
            self._current = []


def _fmt_le(bound: float) -> str:
    return "+Inf" if math.isinf(bound) else repr(round(bound, 12))


def _fmt_val(v: float) -> str:
    return repr(int(v)) if float(v).is_integer() else repr(v)


#: the process-default registry every subsystem shares unless handed a
#: private one (tests isolate by constructing their own)
DEFAULT = Registry(lag=1)


def get_registry() -> Registry:
    return DEFAULT


def counter(name: str, help: str = "") -> Counter:
    return DEFAULT.counter(name, help)


def gauge(name: str, help: str = "") -> Gauge:
    return DEFAULT.gauge(name, help)


def histogram(name: str, help: str = "",
              buckets: Sequence[float] = LATENCY_BUCKETS) -> Histogram:
    return DEFAULT.histogram(name, help, buckets=buckets)


def instrument_step(step_fn: Callable, registry: Optional[Registry] = None,
                    name: str = "train") -> Callable:
    """Wrap a jitted ``step_fn(state, *args) -> (state, metrics)`` with
    zero-sync telemetry: per-call dispatch-latency histogram and step
    counter (host numbers, immediate), plus — when the returned
    ``metrics`` dict carries them — ``loss`` (gauge) and ``overflow``
    (counter) recorded as **deferred device values** and resolved with
    the registry's lag at each :meth:`Registry.tick`.

    The wrapper is strictly host-side: the traced program is untouched
    (the graph-lint syncs pass on an instrumented lane proves the
    point), and nothing in it forces a device fetch.
    ``run_resilient`` instruments itself — do not double-wrap a step
    you hand to the resilience loop.
    """
    reg = registry or DEFAULT
    hist = reg.histogram(f"{name}_step_dispatch_seconds",
                         "wall time to dispatch one step (host side; "
                         "not device latency)")
    steps = reg.counter(f"{name}_steps_total", "steps dispatched")
    loss_g = reg.gauge(f"{name}_loss", "last resolved loss (1-step lag)")
    over_c = reg.counter(f"{name}_overflows_total",
                         "loss-scale overflow skips (1-step lag)")
    # O4 fp8 regime telemetry (present only when the step's metrics
    # carry them — make_train_step under an fp8 policy): both are
    # step OUTPUTS recorded as deferred device values at the existing
    # lag-resolved point, so the instrumentation adds zero host syncs
    # (the graph-lint syncs pass on the O4 lane pins the program side)
    fp8_sat = reg.gauge(
        f"{name}_fp8_amax_saturation",
        "fp8 dynamic-range utilization of the worst tensor class "
        "(amax * delayed scale / fp8_max; >1 = clipped, 1-step lag)")
    fp8_resc = reg.counter(
        f"{name}_fp8_rescales_total",
        "fp8 overflow-to-rescale events: tensor classes whose delayed "
        "scale shrank after the step's amax roll (1-step lag)")

    def wrapped(state, *args, **kwargs):
        t0 = time.perf_counter()
        out = step_fn(state, *args, **kwargs)
        hist.observe(time.perf_counter() - t0)
        steps.inc()
        if isinstance(out, tuple) and len(out) == 2 \
                and isinstance(out[1], dict):
            m = out[1]
            if "loss" in m:
                loss_g.set(m["loss"])
            if "overflow" in m:
                over_c.inc(m["overflow"])
            if "fp8_amax_saturation" in m:
                fp8_sat.set(m["fp8_amax_saturation"])
            if "fp8_rescales" in m:
                fp8_resc.inc(m["fp8_rescales"])
        reg.tick()
        return out

    wrapped.__name__ = getattr(step_fn, "__name__", "step")
    wrapped.__wrapped__ = step_fn
    return wrapped
