"""Self-healing training loop: watchdog, IO retry, divergence rewind.

The r02 incident (a chip-lease wedge) is the design brief: a
hung device call wedged a session for 6+ hours with no watchdog, no
incident artifact, and no resumable state.  :func:`run_resilient` wraps
a jitted train step so that the failure modes a production run actually
hits become *handled inputs*:

- **step watchdog** — a monitor thread tracks wall-clock per step; a
  step that neither dispatches nor resolves within the budget produces
  an incident artifact (with the main thread's stack as evidence) and a
  graceful :class:`WatchdogTimeout` instead of a silent wedge.  The
  monitor can only interrupt Python-level waits (``interrupt_main``); a
  truly wedged C call still gets its incident written within the budget
  — the artifact, not the unstick, is the contract (r02's gap).
- **IO retry** — checkpoint save/restore runs through
  :func:`retry_io` (bounded attempts, exponential backoff), so a flaky
  filesystem is absorbed instead of killing the run.
- **divergence sentinel** — distinguishes amp's *normal* overflow-skip
  (scale halves, training continues) from pathological states: ``K``
  consecutive overflows with the loss scale pinned at its floor
  (``metrics["pinned_at_floor"]``), or a non-finite loss that is NOT an
  overflow skip.  Response: rewind to the last good checkpoint with a
  re-initialized scaler; after ``max_rewinds`` rewinds, hard-fail with a
  structured incident instead of looping forever.

Normal-path cost: the loop adds **no host sync on the step path** — it
dispatches steps back-to-back and resolves each step's metrics one step
behind (``sentinel_lag``), by which point they are (on an accelerator)
already computed; the watchdog is a sleeping daemon thread and the
in-flight table is two dict ops per step.  Measured overhead on the CPU
bench smoke is recorded by ``tools/chaos_run.py --overhead`` (< 2%; see
``docs/source/checkpoint.rst``).
"""

from __future__ import annotations

import dataclasses
import math
import os
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from apex_tpu.obs import metrics as obs_metrics
from apex_tpu.obs.flight import FlightRecorder
from apex_tpu.resilience import incidents as incidents_lib
from apex_tpu.resilience.faults import FaultInjector, SimulatedPreemption


class WatchdogTimeout(RuntimeError):
    """A step exceeded the wall-clock budget; an incident was recorded."""


class DivergenceError(RuntimeError):
    """Pathological state persisted past the rewind budget (or there was
    nothing to rewind to); an incident was recorded."""


def retry_io(fn: Callable[[], Any], retries: int = 3,
             backoff_s: float = 0.05,
             on_retry: Optional[Callable[[int, BaseException], None]] = None
             ) -> Any:
    """Run ``fn`` with bounded retries and exponential backoff on
    ``OSError`` (the checkpoint-IO failure class; anything else is a bug
    and propagates immediately)."""
    attempt = 0
    while True:
        try:
            return fn()
        except OSError as e:
            attempt += 1
            if attempt > retries:
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            time.sleep(backoff_s * (2.0 ** (attempt - 1)))


@dataclasses.dataclass
class ResilienceConfig:
    watchdog_timeout_s: float = 300.0
    watchdog_poll_s: float = 0.05
    checkpoint_every: int = 0          # 0 = no checkpointing
    io_retries: int = 3
    io_backoff_s: float = 0.05
    max_rewinds: int = 2
    overflow_patience: int = 4         # K pinned-at-floor overflows
    sentinel_lag: int = 1              # steps to lag metric resolution
    incident_path: Optional[str] = None  # where watchdog/divergence artifacts go
    #: opt-in SPMD preflight re-run after every rewind/reshape: called
    #: as ``preflight(restored_state)`` before the loop resumes stepping
    #: (wire it to :func:`apex_tpu.parallel.multiproc.spmd_preflight`
    #: over the step's fresh lowering).  A fleet whose post-restore step
    #: compiles a divergent collective schedule — the elastic shrink/
    #: regrow hazard — aborts here with a named diff and an incident
    #: artifact, instead of deadlocking on the first resumed step.
    preflight: Optional[Callable[[Any], Any]] = None


@dataclasses.dataclass
class RunResult:
    state: Any
    steps_completed: int
    losses: List[Tuple[int, float]]
    rewinds: int
    events: List[dict]
    incidents: List[dict]
    #: the loop's flight recorder (ring of step/overflow/fault/rewind
    #: events) — callers writing their own post-run incident records
    #: embed ``flight.dump()`` the way the loop's in-flight incidents do
    flight: Optional[FlightRecorder] = None


def run_resilient(
    step_fn: Callable,
    state: Any,
    batches: Union[Sequence[Any], Callable[[int], Any]],
    num_steps: int,
    amp_obj: Any = None,
    manager: Any = None,
    config: Optional[ResilienceConfig] = None,
    injector: Optional[FaultInjector] = None,
    registry: Optional[obs_metrics.Registry] = None,
    flight: Optional[FlightRecorder] = None,
    profiler: Optional[Any] = None,
    fleet_metrics: Optional[Any] = None,
) -> RunResult:
    """Drive ``step_fn(state, *batch) -> (state, metrics)`` for
    ``num_steps`` with the protections in the module docstring.

    ``batches`` is a sequence or a ``step -> batch`` callable (batch may
    be a tuple of step-fn args or a single array).  ``amp_obj`` (the
    bound :class:`~apex_tpu.amp.frontend.Amp`) enables scaler re-init on
    rewind; ``manager`` (a
    :class:`~apex_tpu.resilience.durable.DurableCheckpointManager`)
    enables on-disk checkpointing and checksum-verified rewind — without
    one, an in-memory host snapshot at the same cadence backs rewind.

    The loop records its runtime telemetry into ``registry`` (default:
    the shared :data:`apex_tpu.obs.metrics.DEFAULT`): ``train_steps/
    overflows/rewinds/checkpoints_total`` counters, the ``train_loss``
    gauge, and ``train_watchdog_margin_s`` (budget minus the observed
    step wall at resolve time — how close the run sails to the
    watchdog).  Every update happens at the existing lag-resolved
    points where the scalars are already host values, so the shared
    registry adds **zero** host syncs; incident records embed a
    ``metrics`` snapshot of the resolved state (never a device fetch —
    a watchdog incident must not block on the very device that hung)
    and the ``flight`` tail of the loop's
    :class:`~apex_tpu.obs.flight.FlightRecorder` (``flight=`` to share
    one across restarts; default a fresh 256-event ring) — the
    step/overflow/checkpoint/fault/rewind history that LED to the
    incident, returned on :attr:`RunResult.flight` either way.
    Steps you hand here should NOT also be wrapped with
    :func:`apex_tpu.obs.metrics.instrument_step` (double counting).

    ``profiler`` (an :class:`apex_tpu.obs.contprof.ContinuousProfiler`,
    usually from :func:`apex_tpu.obs.contprof.train_profiler`) turns
    on continuous profiling: every ``capture_every`` dispatches a
    short window is captured around the step boundary and bucketed
    into the pinned train vocabulary (fwd/bwd/optimizer/collectives/
    host_gap) — the classifier is built lazily from THIS loop's
    jitted step.  Capture is SUPPRESSED across a rewind (an open
    window is aborted and the cadence restarts — the sentinel must
    never judge a half-rewound capture), and any window still open
    when the loop exits is aborted.

    On a :class:`~apex_tpu.resilience.faults.SimulatedPreemption` (or a
    real ``KeyboardInterrupt`` that is not the watchdog), in-flight saves
    are flushed and an incident recorded (status ``preempted`` /
    ``interrupted``) before re-raising — the next process's
    ``manager.restore`` lands on the last good snapshot.

    ``fleet_metrics`` (an
    :class:`apex_tpu.resilience.fleet.FleetMetrics`) hooks the elastic
    fleet's ``train_fleet_*`` family into the same lag-resolved
    boundaries: ``on_resolve()`` fires where the loop's own counters
    update (re-asserting the active-ranks gauge from a host int) and
    ``on_rewind()`` where a divergence rewind lands — both host-side
    only, so the instrumented step's lowering stays syncs-clean.
    """
    cfg = config or ResilienceConfig()
    from apex_tpu import checkpoint as ckpt
    from apex_tpu.amp.scaler import all_finite

    if callable(batches):
        batch_fn = batches
    else:
        batch_fn = lambda i: batches[i]  # noqa: E731

    events: List[dict] = []
    written_incidents: List[dict] = []
    losses: List[Tuple[int, float]] = []

    # the black box: every step/overflow/checkpoint/fault/rewind notes
    # into the bounded ring, and every incident written below ships the
    # ring's tail — the last-N-events history, not just final gauges
    fr = flight if flight is not None else FlightRecorder()
    seen_inj = len(injector.events) if injector is not None else 0

    reg = registry if registry is not None else obs_metrics.DEFAULT
    m_steps = reg.counter("train_steps_total",
                          "train steps resolved (1-step lag)")
    m_over = reg.counter("train_overflows_total",
                         "loss-scale overflow skips")
    m_rewinds = reg.counter("train_rewinds_total",
                            "divergence rewinds executed")
    m_ckpts = reg.counter("train_checkpoints_total",
                          "checkpoints committed (or snapshotted)")
    m_loss = reg.gauge("train_loss", "last resolved loss (1-step lag)")
    m_margin = reg.gauge(
        "train_watchdog_margin_s",
        "watchdog budget minus observed step wall at resolve")

    # -- watchdog ---------------------------------------------------------
    inflight: Dict[int, float] = {}
    lock = threading.Lock()
    abort = threading.Event()
    stop = threading.Event()
    # the thread driving this loop: its stack is the hang evidence, and
    # interrupt_main only helps when it IS the main thread
    entry_thread = threading.current_thread()

    def _note_new_faults() -> None:
        """Mirror freshly fired injector events into the flight ring
        (called after each dispatch and before every incident write —
        a Preempt raises out of the dispatch before the loop's own
        diff point)."""
        nonlocal seen_inj
        if injector is None:
            return
        # under the loop lock: the watchdog thread mirrors through
        # _write_incident concurrently with the main loop's per-step
        # call, and an unguarded cursor would duplicate fault events
        # in the forensic record
        with lock:
            fresh = injector.events[seen_inj:]
            seen_inj = len(injector.events)
        for ev in fresh:
            # injector payload keys may collide with the ring's own
            # fields (CorruptCheckpoint records kind="truncate") —
            # prefix those instead of exploding note()'s signature
            fr.note("fault", **{
                ("fault_" + k if k in ("kind", "ts") else k): v
                for k, v in ev.items() if k != "utc"})

    def _write_incident(status: str, summary: str,
                        evidence: List[Any], **extra: Any) -> None:
        try:
            # embed the RESOLVED metrics state (no flush: a watchdog
            # incident fires while the device may be wedged — snapshot
            # must never device_get) and the flight recorder's tail
            # (the event history that LED here, not just end gauges)
            _note_new_faults()
            extra.setdefault("metrics", reg.snapshot())
            extra.setdefault("flight", fr.dump())
            if cfg.incident_path:
                rec = incidents_lib.write_incident(
                    cfg.incident_path, status, summary, evidence, **extra)
            else:
                rec = incidents_lib.make_incident(status, summary, evidence,
                                                  **extra)
            written_incidents.append(rec)
        except Exception:  # incident writing must never mask the failure
            traceback.print_exc()

    def _monitor() -> None:
        while not stop.wait(cfg.watchdog_poll_s):
            with lock:
                if not inflight:
                    continue
                step_i, t0 = min(inflight.items(), key=lambda kv: kv[1])
            elapsed = time.monotonic() - t0
            if elapsed <= cfg.watchdog_timeout_s:
                continue
            frames = None
            try:
                import sys
                frame = sys._current_frames().get(entry_thread.ident)
                if frame is not None:
                    frames = traceback.format_stack(frame)
            except Exception:
                pass
            fr.note("watchdog", step=step_i,
                    elapsed_s=round(elapsed, 3),
                    budget_s=cfg.watchdog_timeout_s)
            _write_incident(
                "watchdog-timeout",
                f"step {step_i} exceeded the {cfg.watchdog_timeout_s}s "
                "wall-clock budget; aborting instead of wedging (r02 "
                "mitigation)",
                [f"step {step_i} in flight {elapsed:.3f}s > budget "
                 f"{cfg.watchdog_timeout_s}s"]
                + ([{"main_thread_stack": frames[-6:]}] if frames else []),
            )
            abort.set()
            if entry_thread is threading.main_thread():
                try:        # break a Python-level wait; a loop driven
                    import _thread      # from a worker thread relies on
                    _thread.interrupt_main()  # the abort flag instead
                except Exception:
                    pass
            return

    monitor = threading.Thread(target=_monitor, daemon=True,
                               name="apex-tpu-watchdog")
    monitor.start()

    # -- rewind machinery -------------------------------------------------
    rewinds = 0
    consecutive_pinned = 0
    # (step, ("amp", ckpt state_dict) | ("tree", host leaf copies))
    mem_snapshot: Optional[Tuple[int, Any]] = None

    def _reinit_scaler(st: Any) -> Any:
        if amp_obj is None or not hasattr(st, "scaler_states"):
            return st
        return st._replace(scaler_states=tuple(
            amp_obj.scaler.init_state() for _ in st.scaler_states))

    def _save(step_i: int, st: Any) -> None:
        if not bool(all_finite(st.master_params
                               if hasattr(st, "master_params") else st)):
            events.append({"event": "checkpoint_skipped_nonfinite",
                           "step": step_i})
            fr.note("checkpoint_skipped_nonfinite", step=step_i)
            return
        nonlocal mem_snapshot
        if manager is not None:
            retry_io(lambda: manager.save(step_i, st),
                     retries=cfg.io_retries, backoff_s=cfg.io_backoff_s,
                     on_retry=lambda a, e: events.append(
                         {"event": "save_retry", "step": step_i,
                          "attempt": a, "error": repr(e)}))
        else:   # managerless runs rewind from a host snapshot instead
            if hasattr(st, "master_params"):
                mem_snapshot = (step_i, ("amp", ckpt.state_dict(st)))
            else:
                # run_resilient never required AmpState — a generic
                # pytree state snapshots as a plain host copy of its
                # leaves (ckpt.state_dict reads AmpState fields and
                # would crash here)
                import jax
                mem_snapshot = (step_i,
                                ("tree", jax.tree.map(np.asarray, st)))
        events.append({"event": "checkpoint", "step": step_i})
        m_ckpts.inc()
        fr.note("checkpoint", step=step_i)
        # the periodic resolved-metrics snapshot riding the checkpoint
        # cadence — the "what did the gauges say then" half of the ring
        fr.note_metrics(reg)

    def _rewind(st: Any, reason: str) -> Tuple[Any, int]:
        nonlocal rewinds, consecutive_pinned
        rewinds += 1
        consecutive_pinned = 0
        if rewinds > cfg.max_rewinds:
            _write_incident(
                "diverged",
                f"pathological state persisted past max_rewinds="
                f"{cfg.max_rewinds}: {reason}",
                [reason] + events[-8:],
                rewinds=rewinds - 1)
            raise DivergenceError(
                f"exceeded max_rewinds={cfg.max_rewinds}: {reason}")
        restored = None
        if manager is not None:
            try:        # flush in-flight async saves before deciding
                manager.wait()   # whether there is anything to rewind to
            except RuntimeError as e:
                events.append({"event": "rewind_flush_error",
                               "error": repr(e)})
        if manager is not None and manager.all_steps():
            new_state, _ = retry_io(
                lambda: manager.restore(st),
                retries=cfg.io_retries, backoff_s=cfg.io_backoff_s)
            restored = manager.last_restore["step"]
        elif mem_snapshot is not None:
            snap_step, (kind, payload) = mem_snapshot
            if kind == "amp":
                new_state, _ = ckpt.load_state_dict(st, payload)
            else:       # generic-pytree snapshot: host leaves back to jax
                import jax
                new_state = jax.tree.map(
                    lambda s, _r: jax.numpy.asarray(s), payload, st)
            restored = snap_step
        else:
            _write_incident(
                "diverged", f"{reason} — and no checkpoint to rewind to",
                [reason], rewinds=rewinds)
            raise DivergenceError(f"{reason}; no checkpoint to rewind to")
        new_state = _reinit_scaler(new_state)
        if cfg.preflight is not None:
            try:
                cfg.preflight(new_state)
            except Exception as e:
                _write_incident(
                    "preflight-failed",
                    f"post-rewind SPMD preflight rejected the restored "
                    f"step (rewind to step {restored}): {e}",
                    [reason, repr(e)] + events[-8:],
                    rewinds=rewinds)
                raise
            events.append({"event": "preflight", "to_step": restored})
            fr.note("preflight", to_step=restored)
        events.append({"event": "rewind", "to_step": restored,
                       "reason": reason, "rewind_count": rewinds})
        m_rewinds.inc()
        if fleet_metrics is not None:
            fleet_metrics.on_rewind()
        fr.note("rewind", to_step=restored, reason=reason,
                rewind_count=rewinds)
        return new_state, restored + 1

    # -- main loop --------------------------------------------------------
    pending: deque = deque()   # (step, metrics) awaiting resolution
    i = 0
    steps_completed = 0

    def _resolve(entry: Tuple[int, dict], st: Any) -> Tuple[Any, Optional[int]]:
        """Consume one lagged metrics record; returns (state, jump)."""
        nonlocal consecutive_pinned, steps_completed
        j, m = entry
        # one host fetch for the three sentinel scalars (by now — one
        # step behind dispatch — they are already computed, so this does
        # not stall the device pipeline)
        import jax
        loss, overflow, pinned = jax.device_get(
            (m["loss"], m.get("overflow", False),
             m.get("pinned_at_floor", False)))
        loss = float(np.asarray(loss))
        # multi-loss metrics carry per-scaler tuples: any scaler counts
        overflow = bool(np.any(np.asarray(overflow)))
        pinned = bool(np.any(np.asarray(pinned)))
        with lock:
            t0 = inflight.pop(j, None)
        losses.append((j, loss))
        steps_completed = max(steps_completed, j + 1)
        # shared-registry telemetry: every value here is already a host
        # scalar at this (lag-resolved) point — zero added syncs
        m_steps.inc()
        m_loss.set(loss)
        if fleet_metrics is not None:
            fleet_metrics.on_resolve()
        if overflow:
            m_over.inc()
        if t0 is not None:
            m_margin.set(cfg.watchdog_timeout_s
                         - (time.monotonic() - t0))
        fr.note("step", step=j, loss=round(loss, 6),
                overflow=overflow)
        if overflow:
            fr.note("overflow", step=j, pinned_at_floor=pinned)
        if overflow and pinned:
            consecutive_pinned += 1
        else:
            consecutive_pinned = 0
        if consecutive_pinned >= cfg.overflow_patience:
            return _rewind(st, f"{consecutive_pinned} consecutive overflows "
                               "with loss scale pinned at min_loss_scale")
        if not math.isfinite(loss) and not overflow:
            return _rewind(st, f"non-finite loss {loss} at step {j} outside "
                               "an overflow skip")
        return st, None

    try:
        try:
            while i < num_steps or pending:
                if abort.is_set():
                    raise WatchdogTimeout(
                        "watchdog aborted the run; see incident record")
                if i < num_steps:
                    batch = batch_fn(i)
                    if not isinstance(batch, tuple):
                        batch = (batch,)
                    with lock:
                        inflight[i] = time.monotonic()
                    if injector is not None:
                        injector.on_step_start(i)
                        batch = injector.poison_batch(i, batch)
                        _note_new_faults()
                    if profiler is not None:
                        if not profiler.has_classifier_builder:
                            # the classifier comes from THIS loop's
                            # own jitted step (lowered lazily at the
                            # first window close, never executed)
                            from apex_tpu.obs.contprof import (
                                train_classifier_builder)
                            profiler.set_classifier_builder(
                                train_classifier_builder(
                                    step_fn, state, batch))
                        profiler.step_begin()
                        t_disp = time.perf_counter()
                    state, metrics = step_fn(state, *batch)
                    if profiler is not None:
                        # window close blocks on the step's loss (the
                        # capture must hold the device work it wraps);
                        # non-window steps record wall only
                        profiler.step_end(
                            time.perf_counter() - t_disp,
                            block_on=metrics.get("loss")
                            if isinstance(metrics, dict) else None)
                    pending.append((i, metrics))
                # resolve lagged metrics (all of them once dispatch is done)
                lag = cfg.sentinel_lag if i < num_steps else 0
                jump = None
                while len(pending) > lag and jump is None:
                    state, jump = _resolve(pending.popleft(), state)
                if jump is not None:
                    pending.clear()
                    with lock:
                        inflight.clear()
                    if profiler is not None:
                        # capture suppressed while rewinding: abort
                        # any open window and restart the cadence —
                        # the re-dispatched timeline must not feed
                        # the sentinel a half-rewound capture
                        profiler.suppress()
                    i = jump
                    continue
                if i < num_steps and cfg.checkpoint_every \
                        and (i + 1) % cfg.checkpoint_every == 0:
                    _save(i, state)
                i += 1
        except KeyboardInterrupt:
            if abort.is_set():
                raise WatchdogTimeout(
                    "watchdog aborted the run; see incident record") from None
            raise
    except (SimulatedPreemption, KeyboardInterrupt) as e:
        if manager is not None:
            try:
                manager.wait()
            except Exception:
                pass
        if isinstance(e, SimulatedPreemption):
            _write_incident(
                "preempted",
                f"SIGTERM at step {e.step}; in-flight checkpoints flushed — "
                "restart restores the last good snapshot",
                [str(e)] + ([{"injector_events": injector.events[-6:]}]
                            if injector else []))
        else:   # a real operator interrupt still leaves an artifact
            _write_incident(
                "interrupted",
                f"KeyboardInterrupt around step {i}; in-flight checkpoints "
                "flushed — restart restores the last good snapshot",
                [f"interrupted at step {i} of {num_steps}"])
        raise
    finally:
        stop.set()
        monitor.join(timeout=1.0)
        if profiler is not None:
            # a window still open on any exit path (preemption,
            # watchdog, normal drain mid-window) must not leak the
            # process-global tracer
            profiler.abort_window()
        if manager is not None:
            try:
                manager.wait()
            except Exception as e:
                # surface a tail async-save failure unless it would mask
                # the exception already propagating
                events.append({"event": "final_wait_error", "error": repr(e)})
                import sys as _sys
                if _sys.exc_info()[0] is None:
                    raise
        # a fault firing on an ASYNC commit (checkpoint corruption)
        # can land after the loop's last dispatch-side diff — sweep
        # the stragglers so the returned ring is complete
        _note_new_faults()

    return RunResult(state=state, steps_completed=steps_completed,
                     losses=losses, rewinds=rewinds, events=events,
                     incidents=written_incidents, flight=fr)
