"""Gate-artifact hygiene check: the gate's memory must be committed.

VERDICT r5 weak #7: ``SCALING_SWEEP.json`` was left
modified-but-uncommitted at round end.  An uncommitted gate baseline is
a gate that can drift silently: the next round compares against
whatever happens to be on disk, not against what review saw.

This check fails (exit 1) when

- a REQUIRED gate-baseline artifact is missing or untracked, or
- ANY gate-baseline artifact (required or optional, e.g. the
  round-numbered ``MEMLINT_r*.json`` lint artifacts) is modified,
  staged-but-uncommitted, or — for round-numbered artifacts — present
  but never added, or
- a committed ``INCIDENT_r*.json`` does not validate against the
  incident schema (``apex_tpu/resilience/incidents.py``: status, utc or
  date, non-empty evidence) — chaos-run artifacts must not rot into
  prose nobody can machine-check, or
- a committed ``MEMLINT_r*.json`` does not validate against the
  memory-lint schema (``apex_tpu/analysis/memlint.py``: round,
  platform, non-empty lanes each carrying ``peak_hbm_bytes`` / the
  donation-aliasing table / cost-model numbers) — the static HBM
  story of every lane is gate memory, or
- a committed ``PRECLINT_r*.json`` does not validate against the
  precision-lint schema (``apex_tpu/analysis/preclint.py``: round,
  platform, half_dtype, non-empty lanes each carrying the verdict,
  finding counts, and the pass's evidence counters) — the
  mixed-precision contract verdict of every O0–O3 lane is gate
  memory too, or
- a committed ``DECODE_DECOMPOSE_r*.json`` does not validate against
  the decode-decomposition schema
  (``apex_tpu/analysis/decode_decompose.py``: config, complete bucket
  table, >= 90% named-bucket coverage) — the explanation of the b8
  decode gap must stay machine-checked, not prose, or
- a committed ``OBS_r*.json`` does not validate against the
  observability schema (``apex_tpu/analysis/obs.py``: instrumentation
  overhead under the 1% budget, a clean syncs table over the
  instrumented lanes, a non-empty metric-catalog export) — the
  telemetry layer's own cost is gate memory too, or
- a committed ``DECODE_PROFILE_r*.json`` does not validate against the
  decode-profile schema (``apex_tpu/analysis/decode_profile.py``:
  capture provenance, the DECODE_DECOMPOSE bucket vocabulary, a
  stated verdict) — the measured half of the decode decomposition
  stays machine-checked like the static half, or
- a committed ``CONVERGENCE_r*.json`` does not validate against the
  convergence schema (``apex_tpu/analysis/convergence.py``: platform,
  ``all_ok`` consistent with every lane's ``ok`` — legacy
  single-record round-2 shape accepted) — the loss-curve /
  decode-fidelity evidence is gate memory like everything else, or
- a committed ``EXPORT_r*.json`` does not validate against the
  AOT-export schema (``apex_tpu/analysis/export_schema.py``: per-lane
  cache keys, gating lint verdicts consistent with ``export_ok`` —
  an exported lane with a failing lint report, or without a passing
  bitwise round trip, is a CONTRADICTORY verdict and schema-invalid —
  refused lanes naming the documented finding id, and a ``cold_start``
  block whose ``ok`` agrees with its own load-vs-compile numbers) —
  the executable cache's build evidence is gate memory too, or
- a committed ``SCENARIO_r*.json`` does not validate against the
  serve scenario-matrix schema (``apex_tpu/analysis/scenario.py``:
  >= 10 cells each carrying config/percentiles and a gate verdict
  that AGREES with its own numbers, a spec-vs-baseline A/B whose
  ``spec_wins`` rows agree with the tokens-per-step numbers they
  cite) — "handles many scenarios" and the speculative-decoding
  latency win are gate memory, not prose, or
- a committed ``TRACE_r*.json`` does not validate against the
  request-trace schema (``apex_tpu/analysis/trace.py``: per-request
  lifecycles whose span trees NEST, token accounting that equals the
  engines' own ``serve_tokens_total`` deltas, every reroute naming a
  killed replica, and a gate agreeing with its own numbers — a
  contradictory trace is schema-invalid) — the fleet's request-level
  forensic record is gate memory like every other artifact.  The
  incident schema's grown optional ``flight`` field (the
  flight-recorder tail) is validated through the same committed
  ``INCIDENT_r*.json`` check above, or
- a committed ``PROFILE_DRIFT_r*.json`` does not validate against
  the continuous-profile drift schema
  (``apex_tpu/analysis/profile_drift.py``: band + k, a clean session
  and a seeded-regression session whose recorded windows REPLAY to
  the stated verdicts under the one sentinel rule — a quiet verdict
  over a recorded out-of-band window run, an invented drift, or a
  first drift not naming the seeded bucket is CONTRADICTORY and
  schema-invalid) — the live drift tripwire's evidence is gate
  memory like the offline profiles, or
- a committed ``FLEETLINT_r*.json`` does not validate against the
  cross-rank SPMD lint schema (``apex_tpu/analysis/fleetlint.py``:
  per-rank collective-schedule hashes, a ``consistent`` verdict that
  RE-DERIVES from those hashes, mismatch rows naming the first
  diverging op in both spellings, and a gate agreeing with its own
  lanes — a contradictory fleet verdict is schema-invalid) — "every
  rank compiles the same collective schedule" is gate memory, not
  prose, or
- a committed ``TRAINFLEET_r*.json`` does not validate against the
  elastic-training-fleet schema (``apex_tpu/analysis/trainfleet.py``:
  generation chain whose member sets strictly shrink/regrow, recovery
  rows whose ``steps_lost`` re-derive from the kill/restore steps and
  stay within one checkpoint interval, bitwise verdicts that re-derive
  from the recorded state digests, and a ``gate`` agreeing with its
  own bitwise table — a typed-in "survived the kill" is CONTRADICTORY
  and schema-invalid) — the chaos drill's shrink/regrow evidence is
  gate memory like every other floor.

It is wired into tier-1 (``tests/l0/test_gate_hygiene.py``), so a round
cannot go green with dirty gate memory.  Best-effort on the VCS side:
outside a git checkout (a tarball export, a read-only mirror) the check
records that and passes — hygiene of a repo is meaningless without one.

Usage: python tools/gate_hygiene.py [--repo DIR]
"""

from __future__ import annotations

import argparse
import fnmatch
import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

#: Artifacts that MUST exist and be tracked: the scaling-law baseline.
REQUIRED = ("SCALING_SWEEP.json",)

#: All gate-baseline patterns whose working-tree copies must match HEAD
#: (round-numbered artifacts included: a fresh MEMLINT_rN.json is
#: gate memory the moment it exists; incident records are round
#: evidence the same way).
PATTERNS = ("SCALING_SWEEP.json", "INCIDENT_r*.json", "MEMLINT_r*.json",
            "PRECLINT_r*.json", "DECODE_DECOMPOSE_r*.json",
            "OBS_r*.json", "DECODE_PROFILE_r*.json",
            "CONVERGENCE_r*.json", "EXPORT_r*.json",
            "SCENARIO_r*.json", "TRACE_r*.json",
            "PROFILE_DRIFT_r*.json", "FLEETLINT_r*.json",
            "TRAINFLEET_r*.json", "KERNLINT_r*.json", "DETLINT_r*.json")

#: Round-numbered incident artifacts additionally get schema-validated.
INCIDENT_PATTERN = "INCIDENT_r*.json"

#: ... and so do the memory-lint artifacts (graph_lint --emit-json) ...
MEMLINT_PATTERN = "MEMLINT_r*.json"

#: ... and the precision-lint artifacts ...
PRECLINT_PATTERN = "PRECLINT_r*.json"

#: ... and the decode-decomposition artifacts ...
DECOMPOSE_PATTERN = "DECODE_DECOMPOSE_r*.json"

#: ... and the observability artifacts ...
OBS_PATTERN = "OBS_r*.json"

#: ... and the measured decode-profile artifacts ...
PROFILE_PATTERN = "DECODE_PROFILE_r*.json"

#: ... and the convergence-evidence artifacts ...
CONVERGENCE_PATTERN = "CONVERGENCE_r*.json"

#: ... and the AOT-export artifacts ...
EXPORT_PATTERN = "EXPORT_r*.json"

#: ... and the serve scenario-matrix gate artifacts ...
SCENARIO_PATTERN = "SCENARIO_r*.json"

#: ... and the fleet request-trace artifacts ...
TRACE_PATTERN = "TRACE_r*.json"

#: ... and the continuous-profile drift artifacts ...
PROFILE_DRIFT_PATTERN = "PROFILE_DRIFT_r*.json"

#: ... and the cross-rank SPMD consistency artifacts ...
FLEETLINT_PATTERN = "FLEETLINT_r*.json"

#: ... and the elastic-training-fleet chaos-drill artifacts ...
TRAINFLEET_PATTERN = "TRAINFLEET_r*.json"

#: ... and the Pallas kernel-sanitizer sweep artifacts ...
KERNLINT_PATTERN = "KERNLINT_r*.json"

#: ... and the bitwise-determinism lint artifacts.
DETLINT_PATTERN = "DETLINT_r*.json"


def _load_by_path(repo: str, *rel: str):
    """Load a stdlib-only schema module directly by file path so this
    tool never imports jax; ``None`` outside a full checkout."""
    import importlib.util
    mod_path = Path(repo).joinpath(*rel)
    if not mod_path.exists():  # best-effort outside a full checkout
        return None
    spec = importlib.util.spec_from_file_location(
        "_apex_" + mod_path.stem, mod_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _validate_incidents(repo: str) -> "list[str]":
    """Schema problems over every present INCIDENT_r*.json, as
    ``path: problem`` strings."""
    incidents = _load_by_path(repo, "apex_tpu", "resilience",
                              "incidents.py")
    if incidents is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(INCIDENT_PATTERN)):
        for msg in incidents.validate_incident_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_memlints(repo: str) -> "list[str]":
    """Schema problems over every present MEMLINT_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/memlint.py``)."""
    memlint = _load_by_path(repo, "apex_tpu", "analysis", "memlint.py")
    if memlint is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(MEMLINT_PATTERN)):
        for msg in memlint.validate_memlint_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_preclints(repo: str) -> "list[str]":
    """Schema problems over every present PRECLINT_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/preclint.py``)."""
    preclint = _load_by_path(repo, "apex_tpu", "analysis", "preclint.py")
    if preclint is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(PRECLINT_PATTERN)):
        for msg in preclint.validate_preclint_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_decomposes(repo: str) -> "list[str]":
    """Schema problems over every present DECODE_DECOMPOSE_r*.json, as
    ``path: problem`` strings
    (``apex_tpu/analysis/decode_decompose.py`` — which also enforces
    the >= 90% named-bucket coverage acceptance bar)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis",
                           "decode_decompose.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(DECOMPOSE_PATTERN)):
        for msg in schema.validate_decompose_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_obs(repo: str) -> "list[str]":
    """Schema problems over every present OBS_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/obs.py`` — which
    also enforces the <1% overhead budget and the clean-syncs bar)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis", "obs.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(OBS_PATTERN)):
        for msg in schema.validate_obs_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_profiles(repo: str) -> "list[str]":
    """Schema problems over every present DECODE_PROFILE_r*.json, as
    ``path: problem`` strings
    (``apex_tpu/analysis/decode_profile.py``)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis",
                           "decode_profile.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(PROFILE_PATTERN)):
        for msg in schema.validate_profile_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_convergences(repo: str) -> "list[str]":
    """Schema problems over every present CONVERGENCE_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/convergence.py``)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis",
                           "convergence.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(CONVERGENCE_PATTERN)):
        for msg in schema.validate_convergence_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_exports(repo: str) -> "list[str]":
    """Schema problems over every present EXPORT_r*.json, as
    ``path: problem`` strings
    (``apex_tpu/analysis/export_schema.py``)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis",
                           "export_schema.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(EXPORT_PATTERN)):
        for msg in schema.validate_export_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_scenarios(repo: str) -> "list[str]":
    """Schema problems over every present SCENARIO_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/scenario.py``)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis", "scenario.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(SCENARIO_PATTERN)):
        for msg in schema.validate_scenario_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_traces(repo: str) -> "list[str]":
    """Schema problems over every present TRACE_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/trace.py`` — which
    also enforces the span-nesting / token-accounting / reroute
    contradiction rejections)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis", "trace.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(TRACE_PATTERN)):
        for msg in schema.validate_trace_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_profile_drifts(repo: str) -> "list[str]":
    """Schema problems over every present PROFILE_DRIFT_r*.json, as
    ``path: problem`` strings
    (``apex_tpu/analysis/profile_drift.py`` — which also replays the
    sentinel rule over the recorded windows)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis",
                           "profile_drift.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(PROFILE_DRIFT_PATTERN)):
        for msg in schema.validate_profile_drift_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_fleetlints(repo: str) -> "list[str]":
    """Schema problems over every present FLEETLINT_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/fleetlint.py`` —
    which also re-derives every ``consistent`` verdict from the
    recorded per-rank schedule hashes)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis", "fleetlint.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(FLEETLINT_PATTERN)):
        for msg in schema.validate_fleetlint_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_trainfleets(repo: str) -> "list[str]":
    """Schema problems over every present TRAINFLEET_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/trainfleet.py`` —
    which also re-derives the bitwise verdicts, the generation chain,
    and the steps-lost bound from the recorded events and digests)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis",
                           "trainfleet.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(TRAINFLEET_PATTERN)):
        for msg in schema.validate_trainfleet_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_kernlints(repo: str) -> "list[str]":
    """Schema problems over every present KERNLINT_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/kernlint.py`` —
    which also re-derives every per-kernel ``ok`` verdict from the
    recorded per-rule finding counts and waivers, and ``gate.ok``
    from the verdicts)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis", "kernlint.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(KERNLINT_PATTERN)):
        for msg in schema.validate_kernlint_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _validate_detlints(repo: str) -> "list[str]":
    """Schema problems over every present DETLINT_r*.json, as
    ``path: problem`` strings (``apex_tpu/analysis/detlint.py`` —
    which also re-derives every per-lane ``ok`` verdict from the
    recorded finding counts and waivers, every comparator verdict
    from the recorded signature streams, and ``gate.ok`` from
    both)."""
    schema = _load_by_path(repo, "apex_tpu", "analysis", "detlint.py")
    if schema is None:
        return []
    problems = []
    for p in sorted(Path(repo).glob(DETLINT_PATTERN)):
        for msg in schema.validate_detlint_file(str(p)):
            problems.append(f"{p.name}: {msg}")
    return problems


def _git(repo: str, *args: str) -> "str | None":
    """stdout of a git command, or None when git/The repo is unavailable
    (the best-effort contract)."""
    try:
        out = subprocess.run(
            ["git", "-C", repo, *args], capture_output=True, text=True,
            timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if out.returncode != 0:
        return None
    return out.stdout


def check(repo: str = str(REPO)) -> dict:
    """``{"ok": bool, "missing": [...], "untracked": [...],
    "dirty": [...], "invalid_incidents": [...],
    "invalid_memlints": [...], "invalid_preclints": [...]}`` — see the
    module docstring for the rules."""
    tracked_raw = _git(repo, "ls-files", "--", *PATTERNS)
    if tracked_raw is None:
        return {"ok": True, "skipped": "not a git checkout (or no git): "
                                       "hygiene unverifiable", "missing": [],
                "untracked": [], "dirty": [], "invalid_incidents": [],
                "invalid_memlints": [], "invalid_preclints": [],
                "invalid_decomposes": [], "invalid_obs": [],
                "invalid_profiles": [], "invalid_convergences": [],
                "invalid_exports": [], "invalid_scenarios": [],
                "invalid_traces": [], "invalid_profile_drifts": [],
                "invalid_fleetlints": [], "invalid_trainfleets": [],
                "invalid_kernlints": [], "invalid_detlints": []}
    tracked = set(tracked_raw.split())
    missing = [f for f in REQUIRED
               if not (Path(repo) / f).exists() or f not in tracked]

    # -uall: surface untracked round artifacts too (a new
    # MEMLINT_rN.json must be committed, not parked)
    status_raw = _git(repo, "status", "--porcelain", "-uall", "--",
                      *PATTERNS) or ""
    untracked, dirty = [], []
    for line in status_raw.splitlines():
        if len(line) < 4:
            continue
        code, path = line[:2], line[3:].strip()
        if not any(fnmatch.fnmatch(Path(path).name, p) for p in PATTERNS):
            continue
        if code == "??":
            untracked.append(path)
        else:
            dirty.append(path)
    invalid = _validate_incidents(repo)
    invalid_mem = _validate_memlints(repo)
    invalid_prec = _validate_preclints(repo)
    invalid_dec = _validate_decomposes(repo)
    invalid_obs = _validate_obs(repo)
    invalid_prof = _validate_profiles(repo)
    invalid_conv = _validate_convergences(repo)
    invalid_exp = _validate_exports(repo)
    invalid_scen = _validate_scenarios(repo)
    invalid_trace = _validate_traces(repo)
    invalid_pd = _validate_profile_drifts(repo)
    invalid_fl = _validate_fleetlints(repo)
    invalid_tf = _validate_trainfleets(repo)
    invalid_kl = _validate_kernlints(repo)
    invalid_dl = _validate_detlints(repo)
    return {"ok": not (missing or untracked or dirty or invalid
                       or invalid_mem or invalid_prec or invalid_dec
                       or invalid_obs or invalid_prof or invalid_conv
                       or invalid_exp or invalid_scen or invalid_trace
                       or invalid_pd or invalid_fl
                       or invalid_tf or invalid_kl or invalid_dl),
            "missing": missing, "untracked": untracked, "dirty": dirty,
            "invalid_incidents": invalid,
            "invalid_memlints": invalid_mem,
            "invalid_preclints": invalid_prec,
            "invalid_decomposes": invalid_dec,
            "invalid_obs": invalid_obs,
            "invalid_profiles": invalid_prof,
            "invalid_convergences": invalid_conv,
            "invalid_exports": invalid_exp,
            "invalid_scenarios": invalid_scen,
            "invalid_traces": invalid_trace,
            "invalid_profile_drifts": invalid_pd,
            "invalid_fleetlints": invalid_fl,
            "invalid_trainfleets": invalid_tf,
            "invalid_kernlints": invalid_kl,
            "invalid_detlints": invalid_dl}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--repo", default=str(REPO))
    args = ap.parse_args(argv)
    verdict = check(args.repo)
    print(json.dumps(verdict))
    if not verdict["ok"]:
        print("gate_hygiene: gate-baseline artifacts must be committed — "
              f"missing/untracked {verdict['missing'] + verdict['untracked']},"
              f" modified {verdict['dirty']}; invalid incident records "
              f"{verdict.get('invalid_incidents', [])}; invalid memlint "
              f"records {verdict.get('invalid_memlints', [])}; invalid "
              f"preclint records {verdict.get('invalid_preclints', [])}; "
              f"invalid decode-decompose records "
              f"{verdict.get('invalid_decomposes', [])}; invalid obs "
              f"records {verdict.get('invalid_obs', [])}; invalid "
              f"decode-profile records "
              f"{verdict.get('invalid_profiles', [])}; invalid "
              f"convergence records "
              f"{verdict.get('invalid_convergences', [])}; invalid "
              f"export records {verdict.get('invalid_exports', [])}; "
              f"invalid scenario records "
              f"{verdict.get('invalid_scenarios', [])}; "
              f"invalid trace records "
              f"{verdict.get('invalid_traces', [])}; invalid "
              f"profile-drift records "
              f"{verdict.get('invalid_profile_drifts', [])}; invalid "
              f"fleetlint records "
              f"{verdict.get('invalid_fleetlints', [])}; invalid "
              f"train-fleet records "
              f"{verdict.get('invalid_trainfleets', [])}; invalid "
              f"kernlint records "
              f"{verdict.get('invalid_kernlints', [])}; invalid "
              f"detlint records "
              f"{verdict.get('invalid_detlints', [])}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
