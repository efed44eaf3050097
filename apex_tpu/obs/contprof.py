"""Always-on continuous profiler + online op-level drift sentinel.

Every profiling surface before this module was OFFLINE:
``tools/profile_decode.py`` judges a capture after the fact.  The live fleet's only online signals were
scalar metrics and SLO burn rates — an op-level regression (a new
materialized copy, a fusion break, a collective gone sync) stayed
invisible until the next offline round.  This module is the runtime
half: bounded sampled captures in the serving/training loop itself,
bucketed through the SAME shared classifiers the offline tools use
(:mod:`apex_tpu.obs.stepclass`), compared online against a baseline
under the PR-13 statistical band rule, raising an incident the moment
a bucket drifts for ``k`` consecutive windows.

Two cooperating pieces:

- :class:`ContinuousProfiler` — every ``capture_every`` steps, wraps
  ``capture_steps`` consecutive step dispatches in one
  ``jax.profiler`` trace, parses the capture through the one shared
  :mod:`apex_tpu.obs.xplane` API (the XLA:CPU ``tf_XLA*`` fallback
  makes the whole pipeline tier-1-testable), buckets the step ops
  with the lane's classifier, and hands the window to the sentinel.
  Integration contract (the serve engine and ``run_resilient`` both
  follow it): the host loop calls :meth:`~ContinuousProfiler.
  step_begin` before a step dispatch and :meth:`~ContinuousProfiler.
  step_end` after — a ``True`` from ``step_begin`` means the step is
  inside a capture window and its latency must be EXCLUDED from the
  gated latency histogram (``serve_decode_step_seconds``), so SLO and
  latency gates never judge a profiled step.  Only ONE window can be
  open per process (``jax.profiler`` is process-global): a second
  profiler's due window is skipped and counted, never queued.  The
  compiled programs are untouched — everything here is host-side
  work at the existing step boundaries, and the window cost is gated
  (≤ :data:`~apex_tpu.analysis.obs.CONTPROF_BUDGET_PCT`% of the
  inter-capture step wall, the OBS_r03 ``contprof`` lane) with an
  auto-throttle that widens ``capture_every`` when a window runs
  over budget;

- :class:`DriftSentinel` — compares each window's bucket fractions
  and step wall against the baseline using the ONE sentinel rule in
  :mod:`apex_tpu.analysis.profile_drift` (band = variance-derived
  width when recorded, else the 0.03 default; out-of-band = a
  fraction moved more than ``band`` absolute, or the wall above
  ``baseline × (1 + band)``).  A drift is CONFIRMED only after ``k``
  consecutive out-of-band windows — never a single noisy one — and
  on confirmation the sentinel notes the flight recorder, writes a
  schema-valid incident naming the drifting bucket and the top
  offending ops, and flips the ``{name}_profile_drift`` gauge the
  SLO evaluator and the router's admission control consume.  The
  rule functions are imported from the stdlib schema module, so the
  live sentinel and the committed artifact's validator can never
  disagree.

Baselines: :func:`baseline_from_profile` builds one from the newest
committed ``DECODE_PROFILE_r*.json`` (the on-chip deployment story —
a stable device makes committed fractions directly comparable);
``baseline=None`` seeds from the session's own first clean window
(recorded as ``"first-window"`` — the CPU thread-summed captures'
cross-host spread makes a foreign-host baseline meaningless, which
``tools/continuous_profile.py`` documents in the artifact).
"""

from __future__ import annotations

import dataclasses
import math
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from apex_tpu.analysis.profile_drift import (
    DEFAULT_BAND,
    confirm_bucket,
    out_of_band,
)
from apex_tpu.obs import metrics as obs_metrics
from apex_tpu.obs import xplane
from apex_tpu.obs.stepclass import (
    DECODE_BUCKETS,
    TRAIN_BUCKETS,
    ServeStepClassifier,
    TrainStepClassifier,
)

__all__ = ["ContProfConfig", "ContinuousProfiler", "DriftSentinel",
           "serve_profiler", "train_profiler", "baseline_from_profile",
           "drift_objective"]

#: one ``jax.profiler`` trace per process — a profiler whose window
#: comes due while another holds the capture SKIPS it (counted),
#: never queues behind it.
_capture_lock = threading.Lock()


@dataclasses.dataclass(frozen=True)
class ContProfConfig:
    """Cadence and bounds of the continuous profiler.

    ``capture_every`` steps between window STARTS (the auto-throttle
    can only widen it); ``capture_steps`` dispatches per window;
    ``warmup_steps`` skipped before the cadence counter starts (the
    compile step must never seed a baseline); ``phase`` offsets the
    cadence (per-replica staggering so fleet windows don't collide on
    the process-global tracer); ``max_overhead_pct`` is the
    auto-throttle budget (window cost as a percentage of the
    inter-capture step wall; ``None`` pins the cadence);
    ``max_windows`` stops capturing after N windows (scripted
    sessions/tests)."""

    capture_every: int = 256
    capture_steps: int = 2
    warmup_steps: int = 1
    phase: int = 0
    logdir: Optional[str] = None
    keep_top_ops: int = 5
    max_overhead_pct: Optional[float] = 1.0
    max_windows: Optional[int] = None

    def __post_init__(self):
        if self.capture_steps < 1:
            raise ValueError(f"capture_steps={self.capture_steps}")
        if self.capture_every <= self.capture_steps:
            raise ValueError(
                f"capture_every={self.capture_every} must exceed "
                f"capture_steps={self.capture_steps} — a window may "
                f"not overlap the next window's start")
        if self.phase < 0:
            raise ValueError(f"phase={self.phase}")


class DriftSentinel:
    """Online drift confirmation over profile windows (see the module
    docstring).  The observation machine is EXACTLY
    :func:`apex_tpu.analysis.profile_drift.replay_sentinel` run
    incrementally — the committed artifact's validator replays it
    over the recorded windows and must derive the same verdicts."""

    def __init__(self, baseline: Optional[dict] = None,
                 band: float = DEFAULT_BAND,
                 band_source: str = "default",
                 k: int = 2,
                 name: str = "serve",
                 registry: Optional[obs_metrics.Registry] = None,
                 flight: Optional[Any] = None,
                 incident_path: Optional[str] = None):
        if k < 2:
            raise ValueError(
                f"k={k}: a sentinel confirming on a single window "
                f"alarms on every noisy capture — k >= 2")
        if not 0.0 < band < 1.0:
            raise ValueError(f"band={band} outside (0, 1)")
        self.baseline = baseline
        self.band = float(band)
        self.band_source = band_source
        self.k = k
        self.name = name
        self.flight = flight
        self.incident_path = incident_path
        self.drifts: List[dict] = []
        self.incidents: List[dict] = []
        self._run: List[List[dict]] = []
        self._active = False
        self._gauge = None
        if registry is not None:
            self._gauge = registry.gauge(
                f"{name}_profile_drift",
                "1 = the continuous profiler confirmed an op-level "
                "drift (k consecutive out-of-band windows) that has "
                "not yet recovered; consumed by SLO objectives and "
                "router admission")
            self._gauge.set(0.0)

    @property
    def drifting(self) -> bool:
        """A confirmed drift that has not yet recovered (no fully
        in-band window since) — what router admission de-ranks on."""
        return self._active

    def observe(self, window: dict) -> dict:
        """Judge one window; annotates it with ``out_of_band`` and
        returns it.  On the ``k``-th consecutive out-of-band window,
        confirms the drift (incident + flight note + gauge)."""
        if self.baseline is None:
            # first clean window seeds the baseline: in-band by
            # construction, recorded so the artifact's replay agrees
            self.baseline = {"source": "first-window",
                             "fractions": dict(window["fractions"]),
                             "step_wall_s": window.get("step_wall_s")}
            window["out_of_band"] = []
            return window
        exc = out_of_band(window["fractions"],
                          window.get("step_wall_s"),
                          self.baseline, self.band)
        window["out_of_band"] = exc
        if not exc:
            self._run = []
            if self._active and self._gauge is not None:
                self._gauge.set(0.0)
            self._active = False
            return window
        self._run.append(exc)
        if not self._active and len(self._run) >= self.k:
            self._confirm(window)
        return window

    def _confirm(self, window: dict) -> None:
        bucket = confirm_bucket(self._run[-self.k:])
        top = [op for op in window.get("top_ops", ())
               if op.get("bucket") == bucket] or \
            list(window.get("top_ops", ()))[:3]
        drift = {"window": window["index"], "bucket": bucket,
                 "windows_out": len(self._run),
                 "band": self.band, "top_ops": top}
        self.drifts.append(drift)
        self._active = True
        if self._gauge is not None:
            self._gauge.set(1.0)
        if self.flight is not None:
            self.flight.note("profile_drift", name=self.name,
                             bucket=bucket, window=window["index"],
                             windows_out=len(self._run))
        self._write_incident(drift, window)

    def _write_incident(self, drift: dict, window: dict) -> None:
        # lazy import: resilience.loop imports apex_tpu.obs — a
        # module-level import here would be the cycle back
        from apex_tpu.resilience import incidents as incidents_lib
        summary = (
            f"continuous profiler confirmed an op-level drift on "
            f"{self.name!r}: bucket {drift['bucket']!r} out of band "
            f"({self.band} {self.band_source}) for "
            f"{drift['windows_out']} consecutive window(s)")
        evidence: List[Any] = [
            f"bucket {drift['bucket']} drifted at window "
            f"{drift['window']} (k={self.k})",
            {"excursions": self._run[-1],
             "baseline": self.baseline,
             "top_ops": drift["top_ops"]}]
        extra: Dict[str, Any] = {"drift": drift}
        if self.flight is not None:
            extra["flight"] = self.flight.dump()
        try:
            if self.incident_path:
                rec = incidents_lib.write_incident(
                    self.incident_path, "profile-drift", summary,
                    evidence, **extra)
            else:
                rec = incidents_lib.make_incident(
                    "profile-drift", summary, evidence, **extra)
            self.incidents.append(rec)
        except Exception:   # forensics must not kill the serving loop
            import traceback
            traceback.print_exc()


class ContinuousProfiler:
    """Sampled capture windows around a host loop's step dispatches
    (see the module docstring for the ``step_begin``/``step_end``
    integration contract)."""

    def __init__(self, buckets=DECODE_BUCKETS,
                 classifier_builder: Optional[Callable[[], Any]] = None,
                 config: Optional[ContProfConfig] = None,
                 sentinel: Optional[DriftSentinel] = None,
                 registry: Optional[obs_metrics.Registry] = None,
                 name: str = "serve"):
        self.config = config or ContProfConfig()
        self.buckets = tuple(buckets)
        self.sentinel = sentinel
        self.name = name
        self._builder = classifier_builder
        self._clf = None
        self._clf_error: Optional[str] = None
        self.classifier_build_s = 0.0
        #: clean windows, in capture order (what the sentinel judged)
        self.windows: List[dict] = []
        #: windows discarded before the sentinel (a prefill/admission
        #: dispatch contaminated the capture — its identically-named
        #: ops would misattribute time)
        self.discarded: List[dict] = []
        self.skipped_windows = 0
        self._step = 0
        self._in_window = False
        self._owns_capture = False
        self._win_walls: List[float] = []
        self._win_start_step = 0
        self._open_marker = None
        self._capture_t0 = 0.0
        self._logdir = None
        self.effective_every = self.config.capture_every
        #: the step index the next window may open at, RELATIVE to
        #: the last window start/skip/suppression — never an absolute
        #: cadence grid, so a throttle-widened interval (or a skipped
        #: or suppressed window) always buys the FULL new interval
        #: before the next capture
        self._next_start = self.config.warmup_steps + 1 \
            + self.config.phase
        self._m_windows = None
        self._m_skipped = None
        if registry is not None:
            self._m_windows = registry.counter(
                f"{name}_profile_windows_total",
                "continuous-profiler capture windows parsed")
            self._m_skipped = registry.counter(
                f"{name}_profile_windows_skipped_total",
                "due windows skipped because another profiler held "
                "the process-global capture")

    # -- classifier ----------------------------------------------------

    @property
    def has_classifier_builder(self) -> bool:
        """True when a classifier source exists — a builder still
        pending, a classifier already built, or a build that failed
        and was recorded.  The loop integrations use this to supply a
        builder exactly once (the builder reference is dropped after
        the one build, so its closure never outlives the window that
        consumed it)."""
        return (self._builder is not None or self._clf is not None
                or self._clf_error is not None)

    def set_classifier_builder(self, builder: Callable[[], Any]) -> None:
        self._builder = builder

    def _classifier(self):
        if self._clf is None and self._clf_error is None \
                and self._builder is not None:
            t0 = time.perf_counter()
            try:
                self._clf = self._builder()
            except Exception as e:  # noqa: BLE001 — profiling must
                # degrade, not kill the loop it watches
                self._clf_error = f"{type(e).__name__}: {e}"[:200]
            finally:
                # one build per profiler: drop the closure so
                # anything it captured is released
                self._builder = None
            self.classifier_build_s = round(
                time.perf_counter() - t0, 4)
        return self._clf

    # -- the step hooks ------------------------------------------------

    @property
    def in_window(self) -> bool:
        return self._in_window

    def _window_due(self) -> bool:
        cfg = self.config
        if cfg.max_windows is not None and \
                len(self.windows) + len(self.discarded) >= \
                cfg.max_windows:
            return False
        return self._step >= self._next_start

    def step_begin(self, marker: Any = None) -> bool:
        """Called before a step dispatch; True = this step is inside
        a capture window (EXCLUDE its latency from gated histograms).
        ``marker`` is an opaque contamination cursor (the engine's
        admission-dispatch count): the window is discarded when it
        moved between open and close."""
        self._step += 1
        if self._in_window:
            return True
        if self._step <= self.config.warmup_steps or \
                not self._window_due():
            return False
        if not _capture_lock.acquire(blocking=False):
            self.skipped_windows += 1
            if self._m_skipped is not None:
                self._m_skipped.inc()
            # a full interval before the next attempt — skipped,
            # never queued behind the holder
            self._next_start = self._step + self.effective_every
            return False
        self._owns_capture = True
        if self.config.logdir is not None:
            # a FIXED logdir must be cleared of the previous window's
            # capture before the trace writes the next one
            self._logdir = self.config.logdir
            shutil.rmtree(self._logdir, ignore_errors=True)
        else:
            self._logdir = tempfile.mkdtemp(
                prefix="apex_tpu_contprof_")
        self._capture_t0 = time.perf_counter()
        import jax
        jax.profiler.start_trace(self._logdir)
        self._in_window = True
        self._win_walls = []
        self._win_start_step = self._step
        # ``capture_every`` steps between window STARTS (the throttle
        # pushes this further out when the window runs over budget)
        self._next_start = self._step + self.effective_every
        self._open_marker = marker
        return True

    def step_end(self, wall_s: float, marker: Any = None,
                 block_on: Any = None) -> Optional[dict]:
        """Called after a step dispatch with its wall seconds; closes
        the window (stop trace → parse → bucket → sentinel) on the
        ``capture_steps``-th step and returns the window record."""
        if not self._in_window:
            return None
        self._win_walls.append(float(wall_s))
        if len(self._win_walls) < self.config.capture_steps:
            return None
        return self._close_window(marker, block_on)

    def abort_window(self) -> None:
        """Abort an open capture window without judging it (the loop
        drained or stopped mid-window): stop the process-global
        trace, release ownership, discard the partial capture.  The
        engines' ``run()`` and ``run_resilient``'s exit path call
        this so a half-open window can never leak the tracer into
        the next loop."""
        if not self._in_window:
            return
        import jax
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
        self._release()
        self._in_window = False
        if self._logdir:
            shutil.rmtree(self._logdir, ignore_errors=True)

    def suppress(self) -> None:
        """Abort any open window and restart the cadence from here —
        the rewind path: a loop re-dispatching an abandoned timeline
        must not feed the sentinel a half-rewound capture.  A full
        interval must elapse before the next window opens."""
        self.abort_window()
        self._next_start = self._step + self.effective_every

    def _release(self) -> None:
        if self._owns_capture:
            self._owns_capture = False
            _capture_lock.release()

    def _close_window(self, marker: Any, block_on: Any) -> dict:
        # profiling must degrade, not kill the loop it watches: a
        # failing stop/parse becomes a discarded window — and the
        # process-global lock is ALWAYS released, or every later
        # step would be misrouted into the profiled histogram
        import jax
        stop_err = None
        try:
            if block_on is not None:
                jax.block_until_ready(block_on)
        except Exception as e:  # noqa: BLE001
            stop_err = e
        try:
            # ALWAYS attempted, even after a failed block: a trace
            # left open would poison the process-global tracer
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            stop_err = stop_err or e
        if stop_err is not None:
            self._release()
            self._in_window = False
            if self._logdir and self.config.logdir is None:
                shutil.rmtree(self._logdir, ignore_errors=True)
            window = {"index": len(self.windows) + len(self.discarded),
                      "start_step": self._win_start_step,
                      "steps": len(self._win_walls),
                      "discarded": f"capture stop failed: "
                                   f"{type(stop_err).__name__}: "
                                   f"{stop_err}"[:200]}
            self.discarded.append(window)
            return window
        self._release()
        self._in_window = False
        capture_s = time.perf_counter() - self._capture_t0
        t1 = time.perf_counter()
        try:
            window = self._parse_window()
        except Exception as e:  # noqa: BLE001 — a corrupt/empty
            # capture dir must not propagate into the hot loop
            if self._logdir and self.config.logdir is None:
                shutil.rmtree(self._logdir, ignore_errors=True)
            window = {"index": len(self.windows) + len(self.discarded),
                      "start_step": self._win_start_step,
                      "steps": len(self._win_walls),
                      "discarded": f"capture parse failed: "
                                   f"{type(e).__name__}: {e}"[:200]}
            self.discarded.append(window)
            return window
        window["capture_s"] = round(capture_s, 6)
        parse_s = time.perf_counter() - t1
        window["parse_s"] = round(parse_s, 6)
        if self._logdir and self.config.logdir is None:
            shutil.rmtree(self._logdir, ignore_errors=True)
        clean = marker == self._open_marker
        if not clean:
            window["discarded"] = "admission/prefill dispatch inside " \
                "the capture window (identically-named ops would " \
                "misattribute time)"
            self.discarded.append(window)
        else:
            t2 = time.perf_counter()
            if self.sentinel is not None:
                self.sentinel.observe(window)
            window["sentinel_s"] = round(time.perf_counter() - t2, 6)
            self.windows.append(window)
            if self._m_windows is not None:
                self._m_windows.inc()
        self._throttle(window)
        return window

    def _parse_window(self) -> dict:
        times = xplane.op_times(self._logdir)
        clf = self._classifier()
        walls = self._win_walls
        step_wall = sum(walls) / max(len(walls), 1)
        window: dict = {
            "index": len(self.windows) + len(self.discarded),
            "start_step": self._win_start_step,
            "steps": len(walls),
            "step_wall_s": round(step_wall, 6),
            "total_ps": int(times.total_ps),
            "source": times.source,
        }
        if clf is None:
            # degraded mode (no classifier): everything lands in
            # "other"; the sentinel still watches the step wall
            window["fractions"] = {b: 0.0 for b in self.buckets}
            window["fractions"]["other"] = 1.0 if times.total_ps else 0.0
            window["matched_frac"] = 0.0
            window["top_ops"] = []
            if self._clf_error:
                window["classifier_error"] = self._clf_error
            return window
        step_ops = clf.step_ops()
        step_times = {n: ps for n, ps in times.by_op.items()
                      if n in step_ops}
        step_times = self._seed(step_times, clf)
        named = [b for b in self.buckets if b not in ("other",
                                                      "host_gap")]
        table = xplane.bucket_op_times(step_times, clf, buckets=named)
        bucket_ps = dict(table["bucket_ps"])
        total = table["total_ps"]
        if "host_gap" in self.buckets:
            # the derived residual: measured wall not attributed to
            # any device op (thread-summed CPU captures can exceed
            # wall — clamp at zero)
            gap = max(0, int(sum(walls) * 1e12) - total)
            bucket_ps["host_gap"] = gap
            total += gap
        window["fractions"] = {
            b: round(bucket_ps.get(b, 0) / total, 4) if total else 0.0
            for b in self.buckets}
        window["matched_frac"] = round(
            table["matched_ps"] / max(table["total_ps"], 1), 4)
        top = sorted(step_times.items(), key=lambda kv: -kv[1])
        window["top_ops"] = [
            {"op": n, "ps": int(ps), "bucket": clf(n) or "other"}
            for n, ps in top[:self.config.keep_top_ops]]
        return window

    def _seed(self, step_times: dict, clf) -> dict:
        """Hook for the scripted seeded-regression session
        (``tools/continuous_profile.py`` overrides it to inflate one
        bucket's measured op times); identity in production."""
        return step_times

    def _throttle(self, window: dict) -> None:
        budget = self.config.max_overhead_pct
        if budget is None:
            return
        cost = window.get("capture_s", 0.0) + \
            window.get("parse_s", 0.0) + window.get("sentinel_s", 0.0)
        wall = window.get("step_wall_s") or 0.0
        if wall <= 0 or cost <= 0:
            return
        needed = int(math.ceil(cost / (budget / 100.0 * wall)))
        if needed > self.effective_every:
            self.effective_every = needed
            # re-anchor off the window that just proved the wider
            # interval is needed — the next start must sit the FULL
            # new interval after this window's start, not at the next
            # multiple of an absolute grid
            self._next_start = max(self._next_start,
                                   self._win_start_step + needed)
            window["throttled_to"] = needed


# ---------------------------------------------------------------------------
# integration factories
# ---------------------------------------------------------------------------

def serve_classifier_builder(engine) -> Callable[[], Any]:
    """A lazy :class:`~apex_tpu.obs.stepclass.ServeStepClassifier`
    builder over one engine's OWN compiled step: the jit is lowered
    with the live carry's shapes via the engine's
    ``decode_step_args()`` — same program, same instruction names as
    the executed capture (the lowering never executes, so the donated
    carry is untouched).  A speculative engine classifies against its
    VERIFY program instead (the target model's per-round work — the
    plain decode step is compiled but never dispatched there); draft
    ops land in ``other``."""
    def build():
        args = engine.decode_step_args()
        step = engine._decode_step
        if hasattr(engine, "_verify_step"):
            import jax.numpy as jnp
            proposals = jnp.zeros(
                (engine.scfg.num_slots, engine.spec.k), jnp.int32)
            args = args[:3] + (proposals,) + args[3:]
            step = engine._verify_step
        txt = step.lower(*args).compile().as_text()
        return ServeStepClassifier(txt, engine.cfg, engine.scfg)

    return build


def serve_profiler(engine,
                   config: Optional[ContProfConfig] = None,
                   sentinel: Optional[DriftSentinel] = None,
                   attach: bool = True) -> ContinuousProfiler:
    """A decode-vocabulary profiler for one
    :class:`~apex_tpu.serve.engine.ServeEngine`
    (:func:`serve_classifier_builder` supplies the classifier).
    ``attach=True`` sets ``engine.profiler`` so the engine's
    ``step()`` drives the hooks and excludes profiled steps from
    ``serve_decode_step_seconds``."""
    prof = ContinuousProfiler(
        buckets=DECODE_BUCKETS,
        classifier_builder=serve_classifier_builder(engine),
        config=config, sentinel=sentinel, registry=engine.metrics,
        name="serve")
    if attach:
        engine.profiler = prof
    return prof


def train_profiler(config: Optional[ContProfConfig] = None,
                   sentinel: Optional[DriftSentinel] = None,
                   registry: Optional[obs_metrics.Registry] = None,
                   ) -> ContinuousProfiler:
    """A train-vocabulary profiler for :func:`apex_tpu.resilience.
    run_resilient` (pass it as ``profiler=``): the loop supplies the
    classifier builder from its own jitted step on first dispatch
    (:func:`train_classifier_builder`), captures are suppressed
    across rewinds, and the sentinel (when given) gates on the
    fwd/bwd/optimizer/collectives/host_gap vocabulary."""
    return ContinuousProfiler(
        buckets=TRAIN_BUCKETS, classifier_builder=None, config=config,
        sentinel=sentinel, registry=registry, name="train")


def train_classifier_builder(step_fn, state, batch) -> Callable[[], Any]:
    """A lazy :class:`~apex_tpu.obs.stepclass.TrainStepClassifier`
    builder over a jitted step's compiled HLO.  Only
    ``jax.ShapeDtypeStruct`` avals of the given state/batch are
    captured (``lower()`` needs shapes alone, and the build may run
    hundreds of steps later — closing over the live arrays would pin
    a full copy of params + optimizer state until then).  A step that
    cannot be lowered (not a jit) degrades to the all-``other``
    window."""
    import jax

    def _aval(x):
        if not (hasattr(x, "shape") and hasattr(x, "dtype")):
            import jax.numpy as jnp
            x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    avals = jax.tree_util.tree_map(_aval, (state, tuple(batch)))

    def build():
        state_av, batch_av = avals
        txt = step_fn.lower(state_av, *batch_av).compile().as_text()
        return TrainStepClassifier(txt)
    return build


def baseline_from_profile(doc: dict) -> dict:
    """A sentinel baseline from a committed ``DECODE_PROFILE_r*.json``
    document: the on-chip story, where a stable device makes the
    committed fractions directly comparable window-to-window.  (On
    CPU the thread-summed fractions spread ~10 percentage points
    ACROSS hosts — ``tools/continuous_profile.py`` self-baselines and
    records the committed document as a cross-reference instead.)"""
    return {"source": "DECODE_PROFILE",
            "fractions": dict(doc.get("device_time_fractions") or {}),
            "step_wall_s": None}


def drift_objective(name: str = "serve"):
    """An :class:`apex_tpu.obs.slo.SLObjective` over the sentinel's
    ``{name}_profile_drift`` gauge — wire it into
    ``RouterConfig.slo`` and a drift-confirmed replica loses
    admission eligibility until its windows recover."""
    from apex_tpu.obs.slo import SLObjective
    return SLObjective(
        name=f"{name}_no_profile_drift", kind="gauge",
        metric=f"{name}_profile_drift", threshold=0.5, op="le",
        window=4, min_count=1)
