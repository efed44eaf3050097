"""Chaos harness: a small amp-O2 train loop driven under a fault schedule.

The resilience layer's claims (``apex_tpu/resilience/``) are only worth
what survives injection, so this tool runs a tiny MLP + FusedAdam amp-O2
loop through :func:`apex_tpu.resilience.run_resilient` with a
command-line fault schedule and emits an ``INCIDENT_r*.json``-schema
artifact (validated by the same :mod:`apex_tpu.resilience.incidents`
schema ``tools/gate_hygiene.py`` enforces on committed incidents).

Fault specs (``--faults``, repeatable):

- ``nan_storm@S[:D]``    — poison the batch for D firings from step S
  (default D=6: long enough to pin the scale at its floor and trip the
  divergence sentinel, i.e. a *storm*, not a normal transient overflow);
- ``ckpt_truncate@S`` / ``ckpt_corrupt@S`` — damage the first checkpoint
  committed at/after step S (restore must fall back to the last good one);
- ``preempt@S``          — SIGTERM mid-step: the harness then simulates a
  scheduler restart (fresh process state, restore from disk, resume);
- ``hang@S[:SEC]``       — host hang at step S (watchdog prey);
- ``flaky_io[:N]``       — first N checkpoint saves raise OSError;
- ``slow_io[:SEC]``      — every save sleeps SEC first;
- ``rank_kill@S[:RANK]`` — SIGKILL a real training process at step S
  (the ``--fleet`` lane only: the single-process lane has no peer to
  survive the kill).

``--fleet`` switches the harness from the in-process loop to the REAL
multi-process elastic-fleet drill (``tools/train_fleet.py``): the one
scheduled ``rank_kill`` fault is executed as an actual ``SIGKILL`` on a
live ``jax.distributed`` rank, the survivor shrinks, the returned rank
regrows, and the emitted ``TRAINFLEET_r*.json`` artifact is validated
by ``apex_tpu/analysis/trainfleet.py``.  Both lanes share one fault
vocabulary (:func:`apex_tpu.resilience.faults.parse_fault`).

``--overhead`` additionally measures the resilience wrapper's normal-path
cost (bare jitted loop vs ``run_resilient`` with no faults and no
checkpointing) and records it in the artifact — the "< 2% step time"
budget documented in ``docs/source/checkpoint.rst``.

The emitted incident embeds the loop's **flight-recorder tail**
(:class:`apex_tpu.obs.flight.FlightRecorder` — the bounded ring of
step/overflow/fault/rewind events), and the harness ASSERTS that tail
is schema-valid and actually contains the injected faults' events (a
scheduled nan storm must appear as ``fault`` firings, an executed
rewind as a ``rewind`` event): a black box that missed the crash it
flew through fails the run, not just the review.

Usage::

    python tools/chaos_run.py --steps 24 \
        --faults nan_storm@6 ckpt_truncate@11 --checkpoint-every 4 \
        --out INCIDENT_chaos_run.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def parse_fault(spec: str):
    """``name@step[:arg]`` / ``name[:arg]`` → fault dataclass.  The
    vocabulary lives in :func:`apex_tpu.resilience.faults.parse_fault`
    (one grammar for this harness AND the fleet drill); this shim just
    turns its ``ValueError`` into a CLI usage error."""
    from apex_tpu.resilience.faults import parse_fault as _parse
    try:
        return _parse(spec)
    except ValueError as e:
        raise SystemExit(str(e))


def _run_fleet_lane(args) -> int:
    """The ``--fleet`` chaos lane: delegate to the elastic-fleet drill
    harness with the ``rank_kill`` fault translated from the shared
    injector vocabulary.  Exactly one ``rank_kill@S[:RANK]`` must be
    scheduled; the other fault kinds belong to the in-process lane."""
    from apex_tpu.resilience.faults import RankKill

    faults = [parse_fault(s) for s in args.faults]
    kills = [f for f in faults if isinstance(f, RankKill)]
    if len(kills) != 1 or len(faults) != len(kills):
        raise SystemExit(
            "--fleet takes exactly one rank_kill@STEP[:RANK] fault and "
            f"no others (got --faults {args.faults or 'none'}); the "
            "in-process fault kinds run without --fleet")
    kill = kills[0]
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_apex_train_fleet", str(REPO / "tools" / "train_fleet.py"))
    train_fleet = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(train_fleet)
    return train_fleet.main([
        "--steps", str(args.steps),
        "--checkpoint-every", str(args.checkpoint_every),
        "--kill-step", str(kill.step),
        "--kill-rank", str(kill.rank if kill.rank is not None else 1),
        "--seed", str(args.seed),
        "--out", args.out])


def build_workload(seed: int = 0, min_loss_scale: float = 2.0 ** 14,
                   features=(32,), batch: int = 32, d_in: int = 16):
    """MLP + FusedAdam amp-O2 training step with fixed batches.

    ``min_loss_scale`` sits high so an injected storm pins the scale in a
    couple of overflows — the sentinel's storm signal fires within a
    handful of steps instead of after 16 halvings.  The default shape is
    tiny (fast chaos loops); :func:`measure_overhead` uses a bench-smoke
    sized one.
    """
    from apex_tpu import amp
    from apex_tpu.models.mlp import MLP, cross_entropy_loss
    from apex_tpu.optimizers import FusedAdam

    model = MLP(features=features)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, d_in)))["params"]
    amp_obj = amp.initialize(optimizer=FusedAdam(lr=1e-2), opt_level="O2",
                             min_loss_scale=min_loss_scale, verbosity=0)
    step_fn = jax.jit(amp.make_train_step(
        amp_obj, lambda p, x, y: cross_entropy_loss(
            model.apply({"params": p}, x), y)))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (batch, d_in))
    y = jax.random.randint(jax.random.PRNGKey(seed + 2), (batch,), 0, 10)
    state = amp_obj.init(params)
    return amp_obj, step_fn, state, lambda i: (x, y)


def measure_overhead(steps: int = 40, reps: int = 5, seed: int = 0) -> dict:
    """Wall time of a bare jitted loop vs run_resilient with no faults /
    no checkpointing — the normal-path cost of the wrapper, at the CPU
    bench-smoke scale (a ~dozens-of-ms step; on a microscopic sub-ms
    step the fixed ~0.1 ms/step Python bookkeeping dominates and the percentage is meaningless).  Reps are
    interleaved bare/wrapped and compared min-to-min: on a shared/noisy
    host the run-to-run spread (±30% observed) dwarfs the effect, and
    the minimum is the standard noise-robust wall-clock estimator."""
    from apex_tpu.resilience import ResilienceConfig, run_resilient

    amp_obj, step_fn, state0, batch_fn = build_workload(
        seed, features=(256, 256), batch=256, d_in=256)
    batch = batch_fn(0)

    def bare():
        st = state0
        t0 = time.perf_counter()
        for _ in range(steps):
            st, m = step_fn(st, *batch)
        jax.block_until_ready(m["loss"])
        return time.perf_counter() - t0

    def wrapped():
        cfg = ResilienceConfig(watchdog_timeout_s=300.0, checkpoint_every=0)
        t0 = time.perf_counter()
        run_resilient(step_fn, state0, batch_fn, steps, amp_obj=amp_obj,
                      config=cfg)
        return time.perf_counter() - t0

    bare(); wrapped()      # compile outside the timed region
    bare_ts, wrap_ts = [], []
    for _ in range(reps):
        bare_ts.append(bare())
        wrap_ts.append(wrapped())
    bare_t, wrap_t = min(bare_ts), min(wrap_ts)
    return {"steps": steps, "reps": reps,
            "bare_s": round(bare_t, 4), "resilient_s": round(wrap_t, 4),
            "bare_ms_per_step": round(bare_t / steps * 1e3, 3),
            "resilient_ms_per_step": round(wrap_t / steps * 1e3, 3),
            "normal_path_overhead_pct":
                round(100.0 * (wrap_t - bare_t) / bare_t, 2)}


def check_flight(rec: dict, fault_specs, rewinds) -> list:
    """Problems with the incident's flight tail as a black box of this
    run (``[]`` = covered): the ``flight`` field must be present and
    schema-valid (``validate_incident`` already enforces the shape —
    this re-checks so the verdict is usable standalone), every
    scheduled nan-storm must appear among its ``fault`` events, and an
    executed rewind must appear as a ``rewind`` event."""
    from apex_tpu.resilience.incidents import _validate_flight

    flight = rec.get("flight")
    if flight is None:
        return ["incident carries no 'flight' field — the loop's ring "
                "was not dumped"]
    problems = [f"flight: {p}" for p in _validate_flight(flight)]
    events = flight.get("events") if isinstance(flight, dict) else []
    if not isinstance(events, list):
        events = []
    kinds = [e.get("kind") for e in events if isinstance(e, dict)]
    fired_faults = {e.get("fault") for e in events
                    if isinstance(e, dict) and e.get("kind") == "fault"}
    for spec in fault_specs:
        name = spec.partition("@")[0].partition(":")[0]
        if name == "nan_storm" and "nan_storm" not in fired_faults:
            problems.append(
                f"flight tail never recorded the scheduled {spec!r} "
                f"firing (fault kinds seen: {sorted(fired_faults)})")
    if rewinds and "rewind" not in kinds:
        problems.append(
            f"loop rewound {rewinds}x but the flight tail has no "
            f"'rewind' event (kinds seen: {sorted(set(kinds))})")
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--checkpoint-every", type=int, default=4)
    ap.add_argument("--watchdog", type=float, default=60.0)
    ap.add_argument("--patience", type=int, default=3,
                    help="K consecutive pinned-at-floor overflows → rewind")
    ap.add_argument("--max-rewinds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--out", default=None,
                    help="artifact path (default INCIDENT_chaos_run.json,"
                         " or TRAINFLEET_r01.json under --fleet)")
    ap.add_argument("--overhead", action="store_true",
                    help="also measure the wrapper's normal-path overhead")
    ap.add_argument("--fleet", action="store_true",
                    help="run the multi-process elastic-fleet drill "
                         "(tools/train_fleet.py) instead of the "
                         "in-process loop; requires one rank_kill fault")
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = "TRAINFLEET_r01.json" if args.fleet \
            else "INCIDENT_chaos_run.json"

    if args.fleet:
        return _run_fleet_lane(args)

    from apex_tpu.resilience import (DivergenceError, DurableCheckpointManager,
                                     FaultInjector, ResilienceConfig,
                                     SimulatedPreemption, WatchdogTimeout,
                                     run_resilient)

    faults = [parse_fault(s) for s in args.faults]
    injector = FaultInjector(faults, seed=args.seed)
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="apex_tpu_chaos_")
    cfg = ResilienceConfig(
        watchdog_timeout_s=args.watchdog,
        checkpoint_every=args.checkpoint_every,
        overflow_patience=args.patience,
        max_rewinds=args.max_rewinds,
        incident_path=args.out)

    def make_manager():
        return DurableCheckpointManager(ckpt_dir, max_to_keep=3,
                                        io_hook=injector.io_hook,
                                        on_commit=injector.on_commit)

    from apex_tpu.obs.flight import FlightRecorder
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.obs.slo import SLObjective, SLOEvaluator

    amp_obj, step_fn, state, batch_fn = build_workload(args.seed)
    # SLO verdicts over the loop's own registry (apex_tpu.obs.slo):
    # the overflow-rate objective judges the storm's damage (a clean
    # run overflows ~never; a nan storm burns the budget), the
    # watchdog-margin gauge the proximity to a wedge.  The evaluator
    # reads resolved host state only; the first evaluate() seeds the
    # window base at zero.
    registry = Registry()
    registry.counter("train_steps_total")
    registry.counter("train_overflows_total")
    registry.gauge("train_watchdog_margin_s").set(args.watchdog)
    slo_ev = SLOEvaluator(registry, (
        SLObjective(name="overflow_rate", kind="ratio",
                    ratio_num="train_overflows_total",
                    ratio_den="train_steps_total", op="le",
                    threshold=0.25, window=1,
                    min_count=min(8, args.steps)),
        SLObjective(name="watchdog_margin", kind="gauge",
                    metric="train_watchdog_margin_s", op="ge",
                    threshold=0.0, window=1, min_count=1),
    ))
    slo_ev.evaluate()
    restarts = 0
    status, summary = "completed", "chaos run completed"
    result = None
    evidence = []
    # ONE flight recorder across restarts: the final incident's tail
    # must span the whole chaos run, preemption restarts included.
    # Capacity is sized to the run (the loop notes up to ~4 events per
    # step): check_flight below DEMANDS the injected faults' events in
    # the tail, so a long run must not evict an early fault's firing
    # out of the black box it is later judged by.
    flight = FlightRecorder(capacity=max(256, args.steps * 4 + 64))
    with injector:
        remaining = True
        while remaining:
            remaining = False
            manager = make_manager()
            try:
                result = run_resilient(
                    step_fn, state, batch_fn, args.steps, amp_obj=amp_obj,
                    manager=manager, config=cfg, injector=injector,
                    registry=registry, flight=flight)
            except SimulatedPreemption as e:
                # scheduler restart: fresh process state, restore from the
                # last GOOD (checksum-verified) snapshot, resume
                restarts += 1
                amp_obj, step_fn, state, batch_fn = build_workload(args.seed)
                manager = make_manager()
                try:
                    state, _ = manager.restore(state)
                    evidence.append(
                        f"preempted at step {e.step}; restart restored "
                        f"checkpoint step {manager.last_restore['step']} "
                        f"(skipped: {manager.last_restore['skipped']})")
                except FileNotFoundError:
                    # preempted before the first commit: a real restart
                    # starts over from initialization
                    evidence.append(
                        f"preempted at step {e.step} before any checkpoint "
                        "committed; restarted from scratch")
                remaining = True
            except (WatchdogTimeout, DivergenceError) as e:
                status, summary = "aborted", f"{type(e).__name__}: {e}"
                evidence.append(str(e))

    final_loss = None
    if result is not None and result.losses:
        final_loss = result.losses[-1][1]
        if result.rewinds or restarts:
            status, summary = "recovered", (
                f"run completed after {result.rewinds} rewind(s) and "
                f"{restarts} restart(s); final loss {final_loss:.4f}")
    evidence += [f"faults scheduled: {args.faults or 'none'}",
                 {"injector_events": injector.events}]
    if result is not None:
        evidence.append({"loop_events": result.events,
                         "loop_incidents": [r.get("summary")
                                            for r in result.incidents],
                         "final_loss": final_loss,
                         "steps_completed": result.steps_completed,
                         "rewinds": result.rewinds})

    # the run's SLO verdict: one end-of-run evaluation over the whole
    # window (base = the pre-run snapshot) — recorded into the
    # incident so the chaos artifact carries an objective-level story
    # next to the event-level flight tail
    registry.flush()
    slo_verdict = None
    try:
        slo_ev.evaluate()
        slo_verdict = slo_ev.summary()
    except Exception as e:  # noqa: BLE001 - forensics must not die
        slo_verdict = {"error": f"{type(e).__name__}: {e}"[:200]}

    extra = {"artifact": "chaos-run fault-injection record",
             "harness": "tools/chaos_run.py -> apex_tpu.resilience",
             "faults": list(args.faults), "restarts": restarts,
             "checkpoint_dir": ckpt_dir,
             "slo": slo_verdict,
             "flight": flight.dump()}
    if args.overhead:
        extra["overhead"] = measure_overhead(seed=args.seed)

    from apex_tpu.resilience import write_incident
    rec = write_incident(args.out, status, summary, evidence, **extra)
    # the black-box bar: the dumped tail must be schema-valid AND
    # contain the injected faults' events — a completed chaos run whose
    # flight recorder missed the injected crash fails here
    flight_problems = check_flight(rec, args.faults,
                                   getattr(result, "rewinds", 0))
    if flight_problems:
        print(f"chaos_run: flight-recorder tail incomplete: "
              f"{flight_problems}", file=sys.stderr)
    print(json.dumps({"status": rec["status"], "out": args.out,
                      "slo_ok": (slo_verdict or {}).get("ok"),
                      "restarts": restarts,
                      "rewinds": getattr(result, "rewinds", None),
                      "final_loss": final_loss,
                      "flight_events": len(rec["flight"]["events"]),
                      **({"overhead": extra["overhead"]}
                         if args.overhead else {})}))
    ok = status in ("completed", "recovered") and final_loss is not None \
        and np.isfinite(final_loss) and not flight_problems
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
