"""Scripted continuous-profiling session → PROFILE_DRIFT_r*.json.

Runs the always-on profiler (:mod:`apex_tpu.obs.contprof`) against a
real serve engine in TWO lanes and commits the evidence:

- **clean** — a steady decode stream, capture windows every
  ``capture_every`` steps, sentinel self-baselined on the first
  window.  The sentinel must stay QUIET: zero confirmed drifts across
  the whole session (single noisy windows are allowed — the
  K-consecutive rule exists exactly for them);
- **seeded** — the same stream, with a DOCUMENTED synthetic
  regression seeded into the measured op-time table from window
  ``seed_from`` onward: every op the compiled-HLO classifier assigns
  to the seeded bucket has its measured time multiplied by
  ``seed_factor`` — as if the kv reads grew a materialized copy.
  The seeding happens at the op-times level, BEFORE bucketing, so the
  entire pipeline under test (bucket fold → band rule → K-consecutive
  confirmation → incident/gauge) runs on the seeded data exactly as
  it would on a real regression.  The sentinel must CATCH it — first
  confirmed drift at window ``seed_from + k − 1``, naming the seeded
  bucket.

Baseline note: the committed ``DECODE_PROFILE_r*.json`` fractions are
thread-summed XLA:CPU host-executor times and spread ~10 percentage
points ACROSS hosts (measured), so a foreign-host committed baseline
would alarm on every window here; each session self-baselines on its
own first window and the newest committed DECODE_PROFILE is recorded
as ``baseline_ref`` (cross-reference, not the gate).  On a TPU the
same tool runs with ``--baseline committed``
(:func:`apex_tpu.obs.contprof.baseline_from_profile`) — a stable
device makes committed fractions directly comparable.

The emitted document is validated against
``apex_tpu/analysis/profile_drift.py`` (stdlib-only; gate_hygiene
enforces it on committed copies, replaying the sentinel rule over the
recorded windows) and the tool refuses to write an invalid one.

Usage:
    python tools/continuous_profile.py [--windows 5] [--k 2]
        [--band 0.12] [--capture-every 12] [--capture-steps 8]
        [--seed-bucket kv_read] [--seed-factor 2.0] [--quick]
        [--baseline first-window|committed]
        [--emit PROFILE_DRIFT_rN.json]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

os.environ.setdefault("APEX_TPU_KERNELS", "jnp")
import jax  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu"))

import numpy as np  # noqa: E402

from apex_tpu.analysis import profile_drift as schema  # noqa: E402
from apex_tpu.obs import contprof  # noqa: E402
from apex_tpu.obs import metrics as obs_metrics  # noqa: E402
from apex_tpu.serve import Request  # noqa: E402


class SeededProfiler(contprof.ContinuousProfiler):
    """The seeded-regression lane: inflate the measured op times of
    one classified bucket from window ``seed_from`` onward, BEFORE
    bucketing — the only difference from production is the synthetic
    regression itself."""

    def __init__(self, *args, seed_bucket=None, seed_factor=2.0,
                 seed_from=1, **kwargs):
        super().__init__(*args, **kwargs)
        self.seed_bucket = seed_bucket
        self.seed_factor = float(seed_factor)
        self.seed_from = int(seed_from)

    def _seed(self, step_times, clf):
        if self.seed_bucket is None:
            return step_times
        idx = len(self.windows) + len(self.discarded)
        if idx < self.seed_from:
            return step_times
        return {n: (int(ps * self.seed_factor)
                    if clf(n) == self.seed_bucket else ps)
                for n, ps in step_times.items()}


def build_engine(num_slots: int, registry):
    """The ONE shared serve-engine construction
    (``graph_lint.build_serve_engine``) at the profile geometry —
    obs_report's contprof overhead lane measures the same engine."""
    import graph_lint

    eng, _ = graph_lint.build_serve_engine(
        num_slots=num_slots, block_size=16,
        num_blocks=num_slots * 8 + 1, max_blocks_per_slot=8,
        prefill_chunk=16, registry=registry)
    return eng, eng.cfg, eng.scfg


def run_session(opts, seed_bucket=None, baseline=None) -> dict:
    """One scripted lane: admit a full batch, decode for exactly the
    steps ``--windows`` windows need, return the session record."""
    reg = obs_metrics.Registry()
    eng, cfg, scfg = build_engine(opts.slots, reg)
    sent = contprof.DriftSentinel(
        baseline=baseline, band=opts.band,
        band_source=opts.band_source, k=opts.k, registry=reg)
    pcfg = contprof.ContProfConfig(
        capture_every=opts.capture_every,
        capture_steps=opts.capture_steps,
        warmup_steps=opts.warmup, max_overhead_pct=None,
        max_windows=opts.windows)
    prof = SeededProfiler(
        buckets=contprof.DECODE_BUCKETS,
        classifier_builder=contprof.serve_classifier_builder(eng),
        config=pcfg, sentinel=sent, registry=reg,
        seed_bucket=seed_bucket, seed_factor=opts.seed_factor,
        seed_from=opts.seed_from)
    eng.profiler = prof

    total_steps = opts.warmup + opts.windows * opts.capture_every \
        + opts.capture_steps + 2
    rng = np.random.RandomState(0)
    for i in range(opts.slots):
        eng.submit(Request(
            uid=f"s{i}", prompt=rng.randint(0, cfg.vocab_size, (8,)),
            max_new_tokens=total_steps + 8))
    for _ in range(total_steps):
        eng.step()
        if len(prof.windows) + len(prof.discarded) >= opts.windows \
                and not prof.in_window:
            break
    prof.abort_window()

    session = {
        "baseline": sent.baseline,
        "windows": prof.windows,
        "drifts": sent.drifts,
        "quiet": len(sent.drifts) == 0,
        "discarded_windows": len(prof.discarded),
        "skipped_windows": prof.skipped_windows,
        "classifier_build_s": prof.classifier_build_s,
    }
    if seed_bucket is not None:
        session["seed"] = {"bucket": seed_bucket,
                           "factor": opts.seed_factor,
                           "from_window": opts.seed_from}
    return session


def committed_profile_ref():
    """The newest committed DECODE_PROFILE document (cross-reference
    for the self-baselined CPU sessions; the gating baseline under
    ``--baseline committed`` on a stable device)."""
    path = max(REPO.glob("DECODE_PROFILE_r*.json"), default=None)
    if path is None:
        return None, None
    try:
        with open(path) as f:
            return path.name, json.load(f)
    except (OSError, ValueError):
        return None, None


def build_doc(opts) -> dict:
    ref_name, ref_doc = committed_profile_ref()
    committed_baseline = None
    if opts.baseline == "committed":
        if ref_doc is None:
            raise SystemExit("--baseline committed: no committed "
                             "DECODE_PROFILE_r*.json found")
        committed_baseline = contprof.baseline_from_profile(ref_doc)

    clean = run_session(opts, seed_bucket=None,
                        baseline=dict(committed_baseline)
                        if committed_baseline else None)
    seeded = run_session(opts, seed_bucket=opts.seed_bucket,
                         baseline=dict(committed_baseline)
                         if committed_baseline else None)

    caught = [d for d in seeded["drifts"]]
    doc = {
        "round": 1,
        "platform": jax.devices()[0].platform,
        "kind": "serve-decode",
        "config": {
            "model": "gpt_tiny", "num_slots": opts.slots,
            "capture_every": opts.capture_every,
            "capture_steps": opts.capture_steps,
            "warmup_steps": opts.warmup, "windows": opts.windows,
            "baseline_mode": opts.baseline,
        },
        "band": {"value": opts.band, "source": opts.band_source},
        "k": opts.k,
        "sessions": {"clean": clean, "seeded": seeded},
        "gate": {
            "clean_quiet": clean["quiet"],
            "seeded_caught": bool(caught),
            "ok": clean["quiet"] and bool(caught),
        },
        "note": (
            "Continuous-profiler drift evidence: a clean serve-decode "
            "session the sentinel stays quiet on, and a seeded "
            "synthetic regression (documented op-time inflation of "
            "one classified bucket, applied before bucketing) it must "
            "catch in exactly k consecutive windows, naming the "
            "bucket.  Windows are jax.profiler captures of the LIVE "
            "engine's decode dispatches parsed through obs.xplane "
            "(XLA:CPU host-executor fallback on this platform — "
            "thread-summed times, no HBM claim) and bucketed by the "
            "shared compiled-HLO classifier "
            "(apex_tpu.obs.stepclass.ServeStepClassifier).  Sessions "
            "self-baseline on their first window; the committed "
            "DECODE_PROFILE fractions are recorded as baseline_ref "
            "(cross-host CPU thread-sum spread ~10pp makes them a "
            "cross-reference here; on a TPU run --baseline "
            "committed).  Profiled steps are excluded from "
            "serve_decode_step_seconds (gate-exclusion contract, "
            "tested in tests/l0/test_contprof.py)."),
    }
    if caught:
        first = caught[0]
        doc["gate"]["caught_in_windows"] = \
            first["window"] - opts.seed_from + 1
    if ref_name is not None:
        doc["baseline_ref"] = {
            "file": ref_name,
            "device_time_fractions":
                (ref_doc or {}).get("device_time_fractions"),
        }
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--windows", type=int, default=5,
                    help="capture windows per session")
    ap.add_argument("--k", type=int, default=2,
                    help="consecutive out-of-band windows to confirm")
    ap.add_argument("--band", type=float, default=0.12)
    ap.add_argument("--band-source", default=None,
                    help="recorded provenance of the band width "
                         "(default: a text derived from --band)")
    ap.add_argument("--capture-every", type=int, default=12)
    ap.add_argument("--capture-steps", type=int, default=8)
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--seed-bucket", default="kv_read",
                    choices=[b for b in schema.DECODE_BUCKETS
                             if b != "other"])
    ap.add_argument("--seed-factor", type=float, default=2.0)
    ap.add_argument("--seed-from", type=int, default=1,
                    help="first seeded window index")
    ap.add_argument("--baseline", default="first-window",
                    choices=("first-window", "committed"))
    ap.add_argument("--quick", action="store_true",
                    help="smaller everything (tests); not for "
                         "committed artifacts")
    ap.add_argument("--emit", default=None,
                    metavar="PROFILE_DRIFT_rN.json")
    opts = ap.parse_args(argv)
    if opts.quick:
        opts.windows = min(opts.windows, 3)
        opts.capture_every = 6
        opts.capture_steps = 4
        opts.warmup = 2
    if opts.band_source is None:
        opts.band_source = (
            "measured same-host window spread of thread-summed "
            "XLA:CPU captures (no recorded variance covers the decode "
            "profile; the 0.03 chip-day default is a TPU "
            "number)" if opts.band != schema.DEFAULT_BAND
            else "default")

    doc = build_doc(opts)
    if opts.emit:
        m = re.search(r"_r(\d+)\.json$", os.path.basename(opts.emit))
        if m:
            doc["round"] = int(m.group(1))
        problems = schema.validate_profile_drift(doc)
        if problems:
            print(f"refusing to write {opts.emit}: {problems}",
                  file=sys.stderr)
            return 1
        with open(opts.emit, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"profile-drift artifact written: {opts.emit}",
              file=sys.stderr)
    else:
        print(json.dumps(doc, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
