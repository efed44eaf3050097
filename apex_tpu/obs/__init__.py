"""apex_tpu.obs — unified runtime telemetry.

The paper's value proposition is *measured* mixed-precision speed;
this package is the measuring instrument, shared by every subsystem
instead of re-implemented inside each:

- :mod:`apex_tpu.obs.metrics` — process-local counters / gauges /
  fixed-bucket histograms whose device-valued updates resolve with
  **1-step lag** (zero host syncs on the step path — the resilience
  loop's trick promoted to the registry contract), with Prometheus-text
  and JSON export (the committed ``OBS_r01.json`` artifact);
- :mod:`apex_tpu.obs.spans` — structured, nesting trace spans layered
  on the :mod:`apex_tpu.utils.profiling` shims: named regions land in
  the HLO metadata *and* captured xplanes, and span wall-durations
  feed the registry's histograms;
- :mod:`apex_tpu.obs.xplane` — the xplane / chrome-trace parsing
  library (all profile tools import it), with device-time aggregation, step markers, and
  named-bucket attribution for ``tools/profile_decode.py``;
- :mod:`apex_tpu.obs.reqtrace` — per-request lifecycle traces across
  the serving fleet (request ids minted at router admission, a closed
  host-side event vocabulary recorded at the existing step
  boundaries, chrome-trace export, and the committed ``TRACE_r*.json``
  artifact behind ``apex_tpu/analysis/trace.py``);
- :mod:`apex_tpu.obs.flight` — the incident flight recorder (a
  bounded ring of recent events + resolved metric snapshots that
  incident records ship as their validated ``flight`` field);
- :mod:`apex_tpu.obs.fleet` — fleet-level registry merging (counter
  sums, bucket-union histogram quantiles, per-replica gauge tables) —
  the ONE implementation the serving tools share;
- :mod:`apex_tpu.obs.stepclass` — the shared compiled-HLO op
  classifiers (decode / serve-decode seven-bucket vocabulary, the
  pinned fwd/bwd/optimizer/collectives/host_gap train vocabulary) the
  offline profile tools AND the continuous profiler bucket through —
  one copy, so online and offline attribution can never disagree;
- :mod:`apex_tpu.obs.contprof` — the always-on continuous profiler
  (bounded sampled capture windows inside the serve/training loops,
  profiled steps excluded from the gated latency histograms) and the
  online :class:`~apex_tpu.obs.contprof.DriftSentinel` (K-consecutive
  out-of-band confirmation against a baseline under the PR-13 band
  rule; incident + flight note + ``serve_profile_drift`` gauge on
  confirmation) — the committed ``PROFILE_DRIFT_r*.json`` artifact
  behind ``apex_tpu/analysis/profile_drift.py``;
- :mod:`apex_tpu.obs.exposition` — the stdlib HTTP scrape target
  (``/metrics`` Prometheus text, ``/fleet`` merged view);
- :mod:`apex_tpu.obs.slo` — declarative SLO objectives over the live
  registry (decode p99, spec acceptance, block utilization) with
  windowed burn-rate evaluation riding the lag-resolved boundary —
  zero new host syncs; consumed by
  :class:`apex_tpu.serve.DisaggRouter` admission (a violating replica
  loses eligibility) and recorded into the SCENARIO / chaos-incident
  artifacts.

See ``docs/source/observability.rst`` for the metric catalog, the
lag-resolution contract, and the span naming convention.
"""

from apex_tpu.obs import contprof, exposition, fleet, slo, stepclass, xplane
from apex_tpu.obs.contprof import (
    ContinuousProfiler,
    ContProfConfig,
    DriftSentinel,
    serve_profiler,
    train_profiler,
)
from apex_tpu.obs.exposition import MetricsServer
from apex_tpu.obs.flight import FlightRecorder
from apex_tpu.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    LATENCY_BUCKETS,
    Registry,
    counter,
    gauge,
    get_registry,
    histogram,
    instrument_step,
)
from apex_tpu.obs.reqtrace import EVENT_KINDS, RequestTracer
from apex_tpu.obs.slo import SLObjective, SLOEvaluator, serve_objectives
from apex_tpu.obs.spans import current_path, span, traced_span

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "LATENCY_BUCKETS",
    "counter", "gauge", "histogram", "get_registry", "instrument_step",
    "span", "current_path", "traced_span",
    "EVENT_KINDS", "FlightRecorder", "RequestTracer",
    "SLObjective", "SLOEvaluator", "serve_objectives",
    "fleet", "slo", "xplane",
]
