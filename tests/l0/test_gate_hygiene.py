"""tools/gate_hygiene.py — the gate's memory must be committed.

The repo-level test IS the tier-1 wiring (VERDICT r5 weak #7): a round
whose gate-baseline artifacts are modified-but-uncommitted fails the
suite, so the gate memory can never drift silently past a green tier-1.  The unit tests pin the verdict classes on throwaway git
repos.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))

import gate_hygiene  # noqa: E402


def _git(repo, *args):
    subprocess.run(["git", "-C", str(repo), "-c", "user.email=t@t",
                    "-c", "user.name=t", *args], check=True,
                   capture_output=True)


@pytest.fixture
def tmp_repo(tmp_path):
    try:
        _git(tmp_path, "init", "-q")
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("git unavailable")
    for name in gate_hygiene.REQUIRED:
        (tmp_path / name).write_text("{}")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "seed")
    return tmp_path


def test_clean_repo_passes(tmp_repo):
    verdict = gate_hygiene.check(str(tmp_repo))
    assert verdict["ok"], verdict


def test_modified_baseline_fails(tmp_repo):
    (tmp_repo / "SCALING_SWEEP.json").write_text('{"drift": 1}')
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["dirty"] == ["SCALING_SWEEP.json"]
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_untracked_round_artifact_fails(tmp_repo):
    (tmp_repo / "DETLINT_r06.json").write_text("{}")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["DETLINT_r06.json"]
    # ...and committing it restores green
    _git(tmp_repo, "add", "DETLINT_r06.json")
    _git(tmp_repo, "commit", "-q", "-m", "r06 artifact")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_missing_required_fails(tmp_repo):
    _git(tmp_repo, "rm", "-q", "SCALING_SWEEP.json")
    _git(tmp_repo, "commit", "-q", "-m", "drop")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["missing"] == ["SCALING_SWEEP.json"]


def test_non_repo_records_skip(tmp_path):
    verdict = gate_hygiene.check(str(tmp_path))
    assert verdict["ok"] and "skipped" in verdict


def test_non_gate_files_ignored(tmp_repo):
    (tmp_repo / "scratch.json").write_text("{}")
    (tmp_repo / "DETLINT.json").write_text("{}")  # un-numbered out
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def _incidents_module(repo):
    """The schema validator the tmp repo's check will load — copy the
    real one in, like a real checkout has."""
    src = REPO / "apex_tpu" / "resilience" / "incidents.py"
    dst = repo / "apex_tpu" / "resilience"
    dst.mkdir(parents=True, exist_ok=True)
    (dst / "incidents.py").write_text(src.read_text())


def test_committed_incident_validated_against_schema(tmp_repo):
    """ISSUE 3 satellite: a committed INCIDENT_r*.json that does not
    validate (here: no evidence list, no timestamp) fails hygiene."""
    _incidents_module(tmp_repo)
    (tmp_repo / "INCIDENT_r07_bad.json").write_text(
        '{"status": "partial"}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad incident")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("INCIDENT_r07_bad.json" in p
               for p in verdict["invalid_incidents"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_valid_incident_passes_schema(tmp_repo):
    _incidents_module(tmp_repo)
    (tmp_repo / "INCIDENT_r07_ok.json").write_text(json.dumps({
        "status": "recovered", "utc": "2026-08-03T00:00:00Z",
        "summary": "chaos run", "evidence": ["rewound at step 8"]}))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good incident")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_uncommitted_incident_artifact_fails(tmp_repo):
    """A fresh INCIDENT_rN.json is round evidence the moment it exists —
    parked-but-untracked must fail like every round artifact."""
    _incidents_module(tmp_repo)
    (tmp_repo / "INCIDENT_r08_new.json").write_text(json.dumps({
        "status": "recovered", "utc": "2026-08-03T00:00:00Z",
        "evidence": ["x"]}))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["INCIDENT_r08_new.json"]


def test_truncated_incident_json_is_invalid(tmp_repo):
    _incidents_module(tmp_repo)
    (tmp_repo / "INCIDENT_r09_trunc.json").write_text('{"status": "par')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "truncated")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("unreadable incident JSON" in p
               for p in verdict["invalid_incidents"])


def test_repo_r02_incident_validates():
    """The pre-existing wedge record is the schema's reference instance;
    it must stay valid."""
    assert gate_hygiene._validate_incidents(str(REPO)) == []


# ---------------------------------------------------------------------------
# ISSUE 4: MEMLINT_r*.json is gate memory too
# ---------------------------------------------------------------------------

def _memlint_module(repo):
    """The schema validator the tmp repo's check will load — copy the
    real one in, like a real checkout has."""
    src = REPO / "apex_tpu" / "analysis" / "memlint.py"
    dst = repo / "apex_tpu" / "analysis"
    dst.mkdir(parents=True, exist_ok=True)
    (dst / "memlint.py").write_text(src.read_text())


def _valid_memlint():
    return {"round": 4, "platform": "cpu", "lanes": {
        "mlp_o1_train": {"ok": True, "peak_hbm_bytes": 10,
                         "donation": [], "cost": {}, "findings": {}}}}


def test_committed_memlint_validated_against_schema(tmp_repo):
    """A committed MEMLINT_r*.json that does not validate (here: no
    lanes at all) fails hygiene like a bad incident record."""
    _memlint_module(tmp_repo)
    (tmp_repo / "MEMLINT_r04_bad.json").write_text('{"round": 4}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad memlint")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("MEMLINT_r04_bad.json" in p
               for p in verdict["invalid_memlints"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_valid_memlint_passes_schema(tmp_repo):
    _memlint_module(tmp_repo)
    (tmp_repo / "MEMLINT_r04_ok.json").write_text(
        json.dumps(_valid_memlint()))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good memlint")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_uncommitted_memlint_artifact_fails(tmp_repo):
    """A fresh MEMLINT_rN.json is gate memory the moment it exists —
    parked-but-untracked must fail like every round artifact."""
    _memlint_module(tmp_repo)
    (tmp_repo / "MEMLINT_r05_new.json").write_text(
        json.dumps(_valid_memlint()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["MEMLINT_r05_new.json"]


def test_repo_memlint_validates():
    """The committed MEMLINT artifact is the schema's reference
    instance; it must stay valid."""
    assert gate_hygiene._validate_memlints(str(REPO)) == []


# ---------------------------------------------------------------------------
# ISSUE 6: DECODE_DECOMPOSE_r*.json is gate memory too
# ---------------------------------------------------------------------------

def _decompose_module(repo):
    src = REPO / "apex_tpu" / "analysis" / "decode_decompose.py"
    dst = repo / "apex_tpu" / "analysis"
    dst.mkdir(parents=True, exist_ok=True)
    (dst / "decode_decompose.py").write_text(src.read_text())


def _valid_decompose(other_frac=0.01):
    named = (1.0 - other_frac) / 6
    fr = {k: round(named, 4) for k in
          ("param_read", "kv_read", "kv_write", "attention",
           "sampling", "host_sync")}
    fr["other"] = other_frac
    total = 1_000_000
    buckets = {k: int(v * total) for k, v in fr.items()}
    return {"round": 1, "platform": "cpu",
            "config": {"batch": 8, "prefill": 2048, "new_tokens": 256},
            "step_bytes": {"total": sum(buckets.values()),
                           "buckets": buckets},
            "device_time_fractions": fr,
            "coverage": round(1.0 - other_frac, 4)}


def test_committed_decompose_validated_against_schema(tmp_repo):
    _decompose_module(tmp_repo)
    (tmp_repo / "DECODE_DECOMPOSE_r07_bad.json").write_text(
        '{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad decompose")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("DECODE_DECOMPOSE_r07_bad.json" in p
               for p in verdict["invalid_decomposes"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_decompose_coverage_bar_enforced(tmp_repo):
    """The >= 90% named-bucket coverage ACCEPTANCE bar is schema-level:
    a committed decomposition whose 'explanation' is 20% unexplained
    remainder fails hygiene."""
    _decompose_module(tmp_repo)
    (tmp_repo / "DECODE_DECOMPOSE_r08_thin.json").write_text(
        json.dumps(_valid_decompose(other_frac=0.2)))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "thin decompose")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("coverage" in p for p in verdict["invalid_decomposes"])


def test_valid_decompose_passes_schema(tmp_repo):
    _decompose_module(tmp_repo)
    (tmp_repo / "DECODE_DECOMPOSE_r09_ok.json").write_text(
        json.dumps(_valid_decompose()))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good decompose")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_uncommitted_decompose_artifact_fails(tmp_repo):
    _decompose_module(tmp_repo)
    (tmp_repo / "DECODE_DECOMPOSE_r10_new.json").write_text(
        json.dumps(_valid_decompose()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["DECODE_DECOMPOSE_r10_new.json"]


def test_repo_decompose_validates():
    """The committed DECODE_DECOMPOSE artifact is the schema's
    reference instance; it must stay valid (and over the coverage
    bar)."""
    assert gate_hygiene._validate_decomposes(str(REPO)) == []


# ---------------------------------------------------------------------------
# ISSUE 7: OBS_r*.json and DECODE_PROFILE_r*.json are gate memory too
# ---------------------------------------------------------------------------

def _analysis_module(repo, stem):
    src = REPO / "apex_tpu" / "analysis" / f"{stem}.py"
    dst = repo / "apex_tpu" / "analysis"
    dst.mkdir(parents=True, exist_ok=True)
    (dst / f"{stem}.py").write_text(src.read_text())


def _valid_obs(overhead_pct=0.4):
    return {"round": 1, "platform": "cpu",
            "overhead": {"steps": 40, "bare_s": 0.5,
                         "instrumented_s": 0.5,
                         "overhead_pct": overhead_pct},
            "syncs": {"clean": True,
                      "lanes": {"serve_step": {"host_callbacks": 0,
                                               "static_scalars": 0,
                                               "errors": 0}}},
            "export": {"metrics": [{"name": "x", "type": "counter"}]}}


def test_committed_obs_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "obs")
    (tmp_repo / "OBS_r07_bad.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad obs")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("OBS_r07_bad.json" in p for p in verdict["invalid_obs"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_obs_overhead_budget_bar_enforced(tmp_repo):
    """The <1% instrumentation-overhead ACCEPTANCE bar is
    schema-level: a committed OBS record over budget fails hygiene."""
    _analysis_module(tmp_repo, "obs")
    (tmp_repo / "OBS_r08_slow.json").write_text(
        json.dumps(_valid_obs(overhead_pct=1.8)))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "slow obs")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("budget" in p for p in verdict["invalid_obs"])


def test_valid_obs_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "obs")
    (tmp_repo / "OBS_r09_ok.json").write_text(json.dumps(_valid_obs()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["OBS_r09_ok.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good obs")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_committed_profile_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "decode_profile")
    (tmp_repo / "DECODE_PROFILE_r07_bad.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad profile")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("DECODE_PROFILE_r07_bad.json" in p
               for p in verdict["invalid_profiles"])


def test_repo_obs_and_profile_validate():
    """The committed OBS_r01 / DECODE_PROFILE_r01 artifacts are the
    schemas' reference instances; they must stay valid."""
    assert gate_hygiene._validate_obs(str(REPO)) == []
    assert gate_hygiene._validate_profiles(str(REPO)) == []


# ---------------------------------------------------------------------------
# ISSUE 9: CONVERGENCE_r*.json schema validation
# ---------------------------------------------------------------------------

def _valid_convergence():
    return {"platform": "cpu", "all_ok": True,
            "o4_mnist": {"name": "o4_mnist", "ok": True},
            "int8_kv_decode": {"name": "int8_kv_decode", "ok": True},
            "anchors": {"ngram1_nats_per_byte": 3.15}}


def test_committed_convergence_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "convergence")
    (tmp_repo / "CONVERGENCE_r07_bad.json").write_text('{"x": 1}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad convergence")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("CONVERGENCE_r07_bad.json" in p
               for p in verdict["invalid_convergences"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_convergence_all_ok_must_match_lanes(tmp_repo):
    """all_ok contradicting the lanes' ok flags is schema-invalid (the
    verdict must be derivable from the document alone); a consistent
    document — and the legacy round-2 single-record shape — pass."""
    _analysis_module(tmp_repo, "convergence")
    bad = _valid_convergence()
    bad["o4_mnist"]["ok"] = False           # all_ok still True
    (tmp_repo / "CONVERGENCE_r08_lie.json").write_text(json.dumps(bad))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "contradictory convergence")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("contradicts" in p
               for p in verdict["invalid_convergences"])

    good = _valid_convergence()
    legacy = {"platform": "tpu", "ok": True, "epochs": 3}
    (tmp_repo / "CONVERGENCE_r08_lie.json").write_text(json.dumps(good))
    (tmp_repo / "CONVERGENCE_r02_legacy.json").write_text(
        json.dumps(legacy))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good convergence")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


# ---------------------------------------------------------------------------
# ISSUE 10: EXPORT_r*.json is gate memory too
# ---------------------------------------------------------------------------

def _valid_export():
    return {"round": 1, "platform": "cpu",
            "versions": {"jax": "0.4.37"},
            "lanes": {
                "mlp_o1_train": {
                    "export_ok": True, "cache_key": "a" * 64,
                    "module_sha256": "b" * 64,
                    "lint": {"ok": True, "counts": {}},
                    "compile_s": 0.3, "load_s": 0.01,
                    "bitwise_equal": True}},
            "cold_start": {"lane": "mlp_o1_train", "compile_s": 0.3,
                           "load_s": 0.01, "load_ratio": 0.03,
                           "budget": 0.5, "ok": True}}


def test_committed_export_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "export_schema")
    (tmp_repo / "EXPORT_r07_bad.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad export")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("EXPORT_r07_bad.json" in p
               for p in verdict["invalid_exports"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_export_contradictory_verdict_fails_hygiene(tmp_repo):
    """The an-executable-only-enters-clean invariant is schema-level:
    a committed record claiming export_ok over a FAILING lint report
    fails hygiene."""
    _analysis_module(tmp_repo, "export_schema")
    doc = _valid_export()
    doc["lanes"]["mlp_o1_train"]["lint"]["ok"] = False
    (tmp_repo / "EXPORT_r08_lie.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "contradictory export")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("contradictory" in p for p in verdict["invalid_exports"])


def test_valid_export_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "export_schema")
    (tmp_repo / "EXPORT_r09_ok.json").write_text(
        json.dumps(_valid_export()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["EXPORT_r09_ok.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good export")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_export_validates():
    """The committed EXPORT artifact is the schema's reference
    instance; it must stay valid."""
    assert gate_hygiene._validate_exports(str(REPO)) == []


def test_real_committed_convergence_artifacts_validate():
    """Every CONVERGENCE_r*.json in the real repo, through the r06
    quant lanes, validates."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_conv_schema", REPO / "apex_tpu" / "analysis" / "convergence.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    arts = sorted(REPO.glob("CONVERGENCE_r*.json"))
    assert len(arts) >= 4
    for p in arts:
        assert mod.validate_convergence_file(str(p)) == [], p.name


# ---------------------------------------------------------------------------
# ISSUE 12: SCENARIO_r*.json — the serve scenario matrix is gate memory
# ---------------------------------------------------------------------------

def _valid_scenario():
    def cell(spec, tps):
        # decode_steps chosen so tokens_per_step IS tokens/steps (the
        # schema re-derives it — a free-floating number is rejected)
        c = {"config": {"context": 128, "new_tokens": 16,
                        "num_slots": 2, "arrival": "steady",
                        "sampling": "greedy", "kv8": False,
                        "spec": spec, "churn": False},
             "tok_s": 800.0, "p50_ms": 2.0, "p99_ms": 4.0,
             "decode_steps": int(round(60 / tps)), "decode_tokens": 60,
             "tokens_per_step": tps, "retraces": 1, "preemptions": 0,
             "gate": {"tail_ok": True, "retrace_ok": True, "ok": True}}
        if spec:
            c["acceptance_rate"] = 0.8
        return c

    cells = {}
    for i in range(5):
        cells[f"c{i}"] = cell(False, 2.0)
        cells[f"c{i}_spec"] = cell(True, 6.0)
    return {
        "round": 1, "platform": "cpu", "model": "gpt_tiny",
        "gate_k": 20.0, "cells": cells,
        "ab": [{"on": f"c{i}_spec", "off": f"c{i}",
                "tokens_per_step_on": 6.0, "tokens_per_step_off": 2.0,
                "spec_wins": True, "gated": i == 0}
               for i in range(5)],
        "gate": {"cells_ok": True, "ab_ok": True, "ok": True},
    }


def test_committed_scenario_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "scenario")
    (tmp_repo / "SCENARIO_r07_bad.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad scenario")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("SCENARIO_r07_bad.json" in p
               for p in verdict["invalid_scenarios"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_scenario_contradictory_cell_gate_fails_hygiene(tmp_repo):
    """A cell's tail verdict must be derivable from its own numbers:
    tail_ok over a p99 beyond K x p50 is a lie the schema rejects."""
    _analysis_module(tmp_repo, "scenario")
    doc = _valid_scenario()
    doc["cells"]["c0"]["p99_ms"] = 999.0   # >> 20 x p50, gate says ok
    (tmp_repo / "SCENARIO_r08_lie.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "contradictory cell")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("CONTRADICTORY" in p and "tail_ok" in p
               for p in verdict["invalid_scenarios"])


def test_scenario_ab_must_cite_real_numbers(tmp_repo):
    """An A/B row's tokens-per-step must MATCH the cells it cites and
    its spec_wins must derive from them — a won A/B over a lost pair
    is schema-invalid either way."""
    _analysis_module(tmp_repo, "scenario")
    doc = _valid_scenario()
    doc["ab"][0]["tokens_per_step_on"] = 1.0   # real cell says 6.0
    (tmp_repo / "SCENARIO_r09_cite.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "mismatched ab citation")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("does not match" in p
               for p in verdict["invalid_scenarios"])
    doc = _valid_scenario()
    doc["ab"][0].update(tokens_per_step_on=1.0,
                        tokens_per_step_off=2.0)
    doc["cells"]["c0_spec"]["tokens_per_step"] = 1.0  # spec LOST
    doc["cells"]["c0_spec"]["decode_steps"] = 60     # 60/60 = 1.0
    (tmp_repo / "SCENARIO_r09_cite.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "lost ab claims a win")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("spec_wins" in p for p in verdict["invalid_scenarios"])


def test_scenario_tokens_per_step_must_derive_from_counts(tmp_repo):
    """The A/B chain's anchor: a cell's tokens_per_step must BE its
    decode_tokens/decode_steps — a fabricated spec win that edited
    only the headline number (and the ab row citing it) is rejected
    by re-derivation, not trusted for matching itself."""
    _analysis_module(tmp_repo, "scenario")
    doc = _valid_scenario()
    doc["cells"]["c0_spec"]["tokens_per_step"] = 9.0
    doc["ab"][0]["tokens_per_step_on"] = 9.0     # cites "the cell"
    (tmp_repo / "SCENARIO_r13_fab.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "fabricated tokens_per_step")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("CONTRADICTORY record" in p and "tokens_per_step" in p
               for p in verdict["invalid_scenarios"])


def test_scenario_too_few_cells_fails_hygiene(tmp_repo):
    """The coverage bar: a committed scenario round under MIN_CELLS
    cells is not a matrix."""
    _analysis_module(tmp_repo, "scenario")
    doc = _valid_scenario()
    doc["cells"] = {k: doc["cells"][k] for k in ("c0", "c0_spec")}
    doc["ab"] = doc["ab"][:1]
    (tmp_repo / "SCENARIO_r10_thin.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "thin scenario round")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("MATRIX" in p or "matrix" in p
               for p in verdict["invalid_scenarios"])


def test_scenario_churn_cell_must_preempt(tmp_repo):
    _analysis_module(tmp_repo, "scenario")
    doc = _valid_scenario()
    doc["cells"]["c1"]["config"]["churn"] = True   # preemptions stays 0
    (tmp_repo / "SCENARIO_r11_churn.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "churnless churn cell")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert any("churned nothing" in p
               for p in verdict["invalid_scenarios"])


def test_valid_scenario_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "scenario")
    (tmp_repo / "SCENARIO_r12_ok.json").write_text(
        json.dumps(_valid_scenario()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["SCENARIO_r12_ok.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good scenario")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_scenario_validates():
    """The committed SCENARIO artifact is the schema's reference
    instance; it must stay valid — and its gate must HOLD (>= 10
    cells, every cell gate green, every gated spec-vs-baseline A/B
    won: the 'handles many scenarios' + speculative-latency-win
    acceptance bars ride this assertion)."""
    assert gate_hygiene._validate_scenarios(str(REPO)) == []
    arts = sorted(REPO.glob("SCENARIO_r*.json"))
    assert arts, "the scenario gate artifact must be committed"
    doc = json.loads(arts[-1].read_text())
    assert len(doc["cells"]) >= 10
    assert doc["gate"]["ok"] is True


# ---------------------------------------------------------------------------
# TRACE_r*.json — the request-trace artifacts (ISSUE 13)
# ---------------------------------------------------------------------------

def _resilience_module(repo, stem):
    src = REPO / "apex_tpu" / "resilience" / f"{stem}.py"
    dst = repo / "apex_tpu" / "resilience"
    dst.mkdir(parents=True, exist_ok=True)
    (dst / f"{stem}.py").write_text(src.read_text())


def _valid_trace():
    return {
        "round": 1, "platform": "cpu", "config": {"model": "gpt_tiny"},
        "requests": {
            "a": {
                "trace_id": "t00001",
                "events": [
                    {"seq": 1, "ts": 0.0, "kind": "enqueue",
                     "where": "router"},
                    {"seq": 2, "ts": 0.1, "kind": "admit",
                     "where": "prefill", "tokens": 1},
                    {"seq": 3, "ts": 0.2, "kind": "decode_step",
                     "where": "replica0", "tokens": 1},
                    {"seq": 4, "ts": 0.3, "kind": "retire",
                     "where": "replica0", "tokens_out": 2},
                ],
                "spans": [
                    {"name": "request", "where": "*", "t0": 0.0,
                     "t1": 0.3, "parent": -1},
                    {"name": "replica0", "where": "replica0",
                     "t0": 0.2, "t1": 0.3, "parent": 0},
                ],
                "tokens": 2,
            },
        },
        "engine": {"serve_tokens_total": {"prefill": 1, "replica0": 1},
                   "delta_total": 2},
        "chaos": {"killed": [], "rerouted": []},
        "gate": {"bitwise_ok": True, "tokens_ok": True, "ok": True},
    }


def test_committed_trace_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "trace")
    (tmp_repo / "TRACE_r09_bad.json").write_text(
        json.dumps({"round": 9}))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad trace")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("TRACE_r09_bad.json" in p
               for p in verdict["invalid_traces"])


def test_trace_token_contradiction_fails_hygiene(tmp_repo):
    """A trace whose token accounting disagrees with the engines' own
    counters is CONTRADICTORY and schema-invalid."""
    _analysis_module(tmp_repo, "trace")
    doc = _valid_trace()
    doc["engine"]["delta_total"] = 9
    doc["engine"]["serve_tokens_total"] = {"replica0": 9}
    (tmp_repo / "TRACE_r09_contra.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "contradictory trace")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("CONTRADICTION" in p for p in verdict["invalid_traces"])


def test_trace_nonnesting_spans_fail_hygiene(tmp_repo):
    _analysis_module(tmp_repo, "trace")
    doc = _valid_trace()
    doc["requests"]["a"]["spans"][1]["t1"] = 99.0
    (tmp_repo / "TRACE_r09_spans.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "non-nesting trace")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("nest" in p for p in verdict["invalid_traces"])


def test_valid_trace_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "trace")
    (tmp_repo / "TRACE_r09_ok.json").write_text(
        json.dumps(_valid_trace()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert verdict["untracked"] == ["TRACE_r09_ok.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "good trace")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_incident_flight_field_validated_by_hygiene(tmp_repo):
    """The INCIDENT schema's grown optional ``flight`` field rides
    the same committed-incident validation: an unordered or
    over-capacity ring tail fails tier-1."""
    _resilience_module(tmp_repo, "incidents")
    rec = {"status": "recovered", "utc": "2026-08-04T00:00:00Z",
           "evidence": ["e"],
           "flight": {"capacity": 4, "dropped": 0,
                      "events": [{"ts": 1.0, "kind": "step"},
                                 {"ts": 0.2, "kind": "rewind"}]}}
    (tmp_repo / "INCIDENT_r09_flight.json").write_text(json.dumps(rec))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "incident w/ bad flight")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("ordered" in p for p in verdict["invalid_incidents"])
    # fixed ordering -> valid
    rec["flight"]["events"][1]["ts"] = 1.5
    (tmp_repo / "INCIDENT_r09_flight.json").write_text(json.dumps(rec))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "incident w/ good flight")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_trace_validates():
    """The committed TRACE artifact is the schema's reference
    instance; it must stay valid — and its gate must HOLD (the
    killed request's lifecycle reconstructed, token accounting
    closed against the engines: the ISSUE-13 acceptance bar rides
    tests/l0/test_reqtrace.py's deeper assertion; this is the
    hygiene wiring)."""
    assert gate_hygiene._validate_traces(str(REPO)) == []
    arts = sorted(REPO.glob("TRACE_r*.json"))
    assert arts, "the trace gate artifact must be committed"
    doc = json.loads(arts[-1].read_text())
    assert doc["gate"]["ok"] is True
    assert doc["chaos"]["killed"] and doc["chaos"]["rerouted"]
    assert doc["config"]["topology"]["n_devices"] >= 16


# ---------------------------------------------------------------------------
# PROFILE_DRIFT_r*.json — the continuous-profile drift artifacts
# ---------------------------------------------------------------------------

def _valid_profile_drift():
    base = {"source": "first-window", "step_wall_s": 0.003,
            "fractions": {"param_read": 0.1, "kv_read": 0.6,
                          "kv_write": 0.05, "attention": 0.02,
                          "sampling": 0.15, "host_sync": 0.0,
                          "other": 0.08}}
    drifted = dict(base["fractions"], kv_read=0.8, sampling=0.0)
    clean_w = [{"index": 0, "fractions": dict(base["fractions"]),
                "step_wall_s": 0.003, "out_of_band": []},
               {"index": 1, "fractions": dict(base["fractions"]),
                "step_wall_s": 0.0031, "out_of_band": []}]
    exc = [{"metric": "kv_read", "value": 0.8, "baseline": 0.6,
            "delta": 0.2},
           {"metric": "sampling", "value": 0.0, "baseline": 0.15,
            "delta": -0.15}]
    seeded_w = [{"index": 0, "fractions": dict(base["fractions"]),
                 "step_wall_s": 0.003, "out_of_band": []},
                {"index": 1, "fractions": drifted,
                 "step_wall_s": 0.003, "out_of_band": exc},
                {"index": 2, "fractions": drifted,
                 "step_wall_s": 0.003, "out_of_band": exc}]
    return {"round": 1, "platform": "cpu", "kind": "serve-decode",
            "config": {}, "band": {"value": 0.05, "source": "test"},
            "k": 2,
            "sessions": {
                "clean": {"baseline": base, "windows": clean_w,
                          "drifts": [], "quiet": True},
                "seeded": {"baseline": base, "windows": seeded_w,
                           "seed": {"bucket": "kv_read",
                                    "factor": 2.0, "from_window": 1},
                           "drifts": [{"window": 2,
                                       "bucket": "kv_read",
                                       "windows_out": 2}],
                           "quiet": False}},
            "gate": {"clean_quiet": True, "seeded_caught": True,
                     "ok": True},
            "note": "test"}


def test_committed_profile_drift_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "profile_drift")
    (tmp_repo / "PROFILE_DRIFT_r07.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad drift record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("PROFILE_DRIFT_r07.json" in p
               for p in verdict["invalid_profile_drifts"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_profile_drift_quiet_contradiction_fails_hygiene(tmp_repo):
    """A quiet verdict over recorded out-of-band windows that replay
    to a confirmed drift is the lie the schema exists to reject."""
    _analysis_module(tmp_repo, "profile_drift")
    doc = _valid_profile_drift()
    doc["sessions"]["seeded"]["drifts"] = []
    doc["sessions"]["seeded"]["quiet"] = True
    doc["gate"]["seeded_caught"] = False
    doc["gate"]["ok"] = False
    (tmp_repo / "PROFILE_DRIFT_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "suppressed drift")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("CONTRADICTORY" in p
               for p in verdict["invalid_profile_drifts"])


def test_valid_profile_drift_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "profile_drift")
    (tmp_repo / "PROFILE_DRIFT_r09.json").write_text(
        json.dumps(_valid_profile_drift()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]            # parked-but-untracked
    assert verdict["untracked"] == ["PROFILE_DRIFT_r09.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "drift round")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_profile_drift_validates():
    """The committed PROFILE_DRIFT_r01 is the schema's reference
    instance, and the committed OBS round carries the contprof lane
    (both ride the repo-level hygiene check in tier-1)."""
    assert gate_hygiene._validate_profile_drifts(str(REPO)) == []
    assert sorted(REPO.glob("PROFILE_DRIFT_r*.json")), \
        "the profile-drift gate artifact must be committed"


# ---------------------------------------------------------------------------
# FLEETLINT_r*.json — the cross-rank SPMD consistency artifacts
# ---------------------------------------------------------------------------

def _valid_fleetlint():
    rank = {"schedule_hash": "a" * 64, "opcode_hash": "b" * 64,
            "n_collectives": 3}
    return {"round": 1, "platform": "cpu", "n_ranks": 8,
            "lanes": {"ddp_o1_train": {"compare": "schedule",
                                       "consistent": True,
                                       "ranks": {"0": dict(rank),
                                                 "1": dict(rank)},
                                       "mismatches": []}},
            "gate": {"ok": True, "inconsistent_lanes": 0}}


def test_committed_fleetlint_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "fleetlint")
    (tmp_repo / "FLEETLINT_r07.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad fleet record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("FLEETLINT_r07.json" in p
               for p in verdict["invalid_fleetlints"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_fleetlint_contradictory_verdict_fails_hygiene(tmp_repo):
    """A ``consistent`` lane verdict over disagreeing recorded per-rank
    schedule hashes is the lie the schema exists to reject — "every rank
    compiles the same collective schedule" must re-derive from the
    recorded hashes, not be asserted."""
    _analysis_module(tmp_repo, "fleetlint")
    doc = _valid_fleetlint()
    doc["lanes"]["ddp_o1_train"]["ranks"]["1"]["schedule_hash"] = "d" * 64
    doc["lanes"]["ddp_o1_train"]["mismatches"] = [
        {"ranks": ["0", "1"], "index": 0,
         "a": "all-reduce(bf16)", "b": "all-reduce(f32)"}]
    (tmp_repo / "FLEETLINT_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "asserted fleet consistency")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("contradicts" in p for p in verdict["invalid_fleetlints"])


def test_valid_fleetlint_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "fleetlint")
    (tmp_repo / "FLEETLINT_r09.json").write_text(
        json.dumps(_valid_fleetlint()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]            # parked-but-untracked
    assert verdict["untracked"] == ["FLEETLINT_r09.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "fleet round")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_fleetlint_validates():
    """The committed FLEETLINT_r01 is the schema's reference instance
    (it rides the repo-level hygiene check in tier-1)."""
    assert gate_hygiene._validate_fleetlints(str(REPO)) == []
    assert sorted(REPO.glob("FLEETLINT_r*.json")), \
        "the fleet SPMD gate artifact must be committed"


# ---------------------------------------------------------------------------
# ISSUE 18: TRAINFLEET_r*.json — the elastic-fleet chaos drill is gate memory
# ---------------------------------------------------------------------------

def _trainfleet_modules(repo):
    """The trainfleet schema loads the incident sub-schema by relative
    path, so the tmp checkout needs both modules in place."""
    _analysis_module(repo, "trainfleet")
    _incidents_module(repo)


def _trainfleet_doc():
    """The committed drill artifact is the schema's reference instance —
    contradiction tests mutate a copy of the real thing, so they can
    never drift from what the drill actually emits."""
    return json.loads((REPO / "TRAINFLEET_r01.json").read_text())


def test_committed_trainfleet_validated_against_schema(tmp_repo):
    _trainfleet_modules(tmp_repo)
    (tmp_repo / "TRAINFLEET_r07.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad fleet drill record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("TRAINFLEET_r07.json" in p
               for p in verdict["invalid_trainfleets"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_trainfleet_typed_in_steps_lost_rejected(tmp_repo):
    """``steps_lost`` must equal ``interrupted_step - restore_step`` —
    a typed-in smaller loss is the lie the schema exists to reject."""
    _trainfleet_modules(tmp_repo)
    doc = _trainfleet_doc()
    shrink = next(r for r in doc["recoveries"] if r["reason"] == "shrink")
    shrink["steps_lost"] = 0
    (tmp_repo / "TRAINFLEET_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "optimistic fleet record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("steps_lost" in p and "contradicts" in p
               for p in verdict["invalid_trainfleets"])


def test_trainfleet_contradictory_bitwise_rejected(tmp_repo):
    """A ``bitwise`` verdict the recorded digests refute (here: a
    shrink-replay digest that no longer matches the drill snapshot,
    while the flag still says True) fails hygiene — and flipping
    ``gate.ok`` against its own bitwise table fails the same way."""
    _trainfleet_modules(tmp_repo)
    doc = _trainfleet_doc()
    rank0 = next(iter(doc["replays"]["shrink"]["finals"]))
    doc["replays"]["shrink"]["finals"][rank0]["digest"] = "f" * 64
    (tmp_repo / "TRAINFLEET_r08.json").write_text(json.dumps(doc))
    contradicted_gate = _trainfleet_doc()
    contradicted_gate["gate"]["ok"] = False
    (tmp_repo / "TRAINFLEET_r09.json").write_text(
        json.dumps(contradicted_gate))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "asserted fleet verdicts")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    probs = verdict["invalid_trainfleets"]
    assert any("TRAINFLEET_r08" in p and
               "bitwise.shrink_matches_uninterrupted" in p for p in probs)
    assert any("TRAINFLEET_r09" in p and "gate.ok" in p for p in probs)


def test_trainfleet_regrown_rank_must_load_from_aot_cache(tmp_repo):
    """The elastic claim the AOT cache backs: a regrown generation that
    COMPILED its step (``aot.source != "cache"``) is schema-invalid."""
    _trainfleet_modules(tmp_repo)
    doc = _trainfleet_doc()
    last_gen = doc["generations"][-1]["gen"]
    for e in doc["events"]:
        if e.get("kind") == "aot" and e.get("gen") == last_gen:
            e["source"] = "compile"
    (tmp_repo / "TRAINFLEET_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "cold fleet record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("must LOAD from the AOT cache" in p
               for p in verdict["invalid_trainfleets"])


def test_trainfleet_membership_must_chain(tmp_repo):
    """A 'shrink' generation whose members are not a strict subset of
    its predecessor's is an incoherent story, not a recovery."""
    _trainfleet_modules(tmp_repo)
    doc = _trainfleet_doc()
    shrink_gen = next(g for g in doc["generations"]
                      if g["reason"] == "shrink")
    shrink_gen["members"] = doc["generations"][0]["members"]
    (tmp_repo / "TRAINFLEET_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "unchained fleet record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("strict subset" in p
               for p in verdict["invalid_trainfleets"])


def test_valid_trainfleet_passes_and_untracked_fails(tmp_repo):
    _trainfleet_modules(tmp_repo)
    (tmp_repo / "TRAINFLEET_r08.json").write_text(
        json.dumps(_trainfleet_doc()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]            # parked-but-untracked
    assert verdict["untracked"] == ["TRAINFLEET_r08.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "fleet drill round")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_trainfleet_validates():
    """The committed TRAINFLEET artifact is the schema's reference
    instance, and its drill verdicts must HOLD: the kill was real, the
    recovery stayed within one checkpoint interval, every bitwise flag
    derived true (the ISSUE-18 acceptance bars ride this assertion)."""
    assert gate_hygiene._validate_trainfleets(str(REPO)) == []
    arts = sorted(REPO.glob("TRAINFLEET_r*.json"))
    assert arts, "the fleet chaos-drill artifact must be committed"
    doc = json.loads(arts[-1].read_text())
    assert doc["gate"]["ok"] is True
    assert all(doc["bitwise"].values())
    shrink = next(r for r in doc["recoveries"] if r["reason"] == "shrink")
    assert 0 <= shrink["steps_lost"] <= doc["config"]["checkpoint_every"]
    assert any(e["kind"] == "kill" for e in doc["events"])


# ---------------------------------------------------------------------------
# KERNLINT_r*.json — the Pallas kernel sanitizer sweep artifacts
# ---------------------------------------------------------------------------

def _valid_kernlint():
    rules = ["pallas-parallel-race", "pallas-alias-race",
             "pallas-oob-unmasked", "pallas-uncovered-output",
             "pallas-vmem-overflow", "pallas-seq-accum-parallel"]
    return {"round": 1, "platform": "cpu", "budget_mb": 16.0,
            "rules": rules,
            "kernels": {"fused_adam": {
                "ok": True, "configs": 2, "calls": 3,
                "findings": {r: 0 for r in rules}}},
            "gate": {"ok": True, "kernels_clean": 1,
                     "kernels_total": 1}}


def test_committed_kernlint_validated_against_schema(tmp_repo):
    _analysis_module(tmp_repo, "kernlint")
    (tmp_repo / "KERNLINT_r07.json").write_text('{"round": 7}')
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "bad kernel record")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("KERNLINT_r07.json" in p
               for p in verdict["invalid_kernlints"])
    assert gate_hygiene.main(["--repo", str(tmp_repo)]) == 1


def test_kernlint_contradictory_verdict_fails_hygiene(tmp_repo):
    """A clean kernel verdict sitting on recorded unwaived findings is
    the lie the schema exists to reject — "the kernels are race-free
    and under budget" must re-derive from the finding counts."""
    _analysis_module(tmp_repo, "kernlint")
    doc = _valid_kernlint()
    doc["kernels"]["fused_adam"]["findings"]["pallas-vmem-overflow"] = 2
    (tmp_repo / "KERNLINT_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "asserted kernel cleanliness")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("contradicts" in p for p in verdict["invalid_kernlints"])


def test_kernlint_stale_waiver_fails_hygiene(tmp_repo):
    """A waiver citing a rule that never fired is dead documentation —
    it would silently excuse a FUTURE regression of that rule."""
    _analysis_module(tmp_repo, "kernlint")
    doc = _valid_kernlint()
    doc["kernels"]["fused_adam"]["waivers"] = {
        "pallas-oob-unmasked": "masked tail, verified by hand"}
    (tmp_repo / "KERNLINT_r08.json").write_text(json.dumps(doc))
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "stale kernel waiver")
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]
    assert any("stale waiver" in p for p in verdict["invalid_kernlints"])


def test_valid_kernlint_passes_and_untracked_fails(tmp_repo):
    _analysis_module(tmp_repo, "kernlint")
    (tmp_repo / "KERNLINT_r09.json").write_text(
        json.dumps(_valid_kernlint()))
    verdict = gate_hygiene.check(str(tmp_repo))
    assert not verdict["ok"]            # parked-but-untracked
    assert verdict["untracked"] == ["KERNLINT_r09.json"]
    _git(tmp_repo, "add", "-A")
    _git(tmp_repo, "commit", "-q", "-m", "kernel lint round")
    assert gate_hygiene.check(str(tmp_repo))["ok"]


def test_repo_kernlint_validates():
    """The committed KERNLINT_r01 is the schema's reference instance
    (it rides the repo-level hygiene check in tier-1)."""
    assert gate_hygiene._validate_kernlints(str(REPO)) == []
    assert sorted(REPO.glob("KERNLINT_r*.json")), \
        "the kernel sanitizer gate artifact must be committed"
