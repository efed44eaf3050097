"""The longitudinal perf timeline (``apex_tpu/analysis/timeline.py`` +
``tools/perf_timeline.py``).

Contracts under test: (a) the adapter registry ingests every committed
artifact family and an unknown family is a LINT error, not a silent
coverage hole; (b) the statistical-band regression rule and its
attribution — a synthetic artifact set with a planted drop between
rounds yields exactly one regression row naming the planted round and
the commits between the two rounds' artifact commits; (c) the schema's
contradiction rejection (fabricated rows, suppressed rows, self-citing
gate verdicts, stale coverage); (d) the committed ``TIMELINE_r01.json``
is schema-valid against THIS checkout and mechanically rediscovers the
two known tpu-heads regressions (gpt / bert_lamb between r04 and r05,
VERDICT r5 weak #1) with the documented suspect commits in range.
"""

import copy
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu.analysis import timeline  # noqa: E402


# ---------------------------------------------------------------------------
# naming + ingestion
# ---------------------------------------------------------------------------

def test_parse_artifact_name():
    assert timeline.parse_artifact_name("BENCH_r05.json") == \
        ("BENCH", 5, "")
    assert timeline.parse_artifact_name("INCIDENT_r02_wedge.json") == \
        ("INCIDENT", 2, "_wedge")
    assert timeline.parse_artifact_name("ROOFLINE_RN50_r04.json") == \
        ("ROOFLINE_RN50", 4, "")
    assert timeline.parse_artifact_name("BASELINE.json") is None
    assert timeline.parse_artifact_name("SCALING_SWEEP.json") is None


def test_every_committed_family_has_an_adapter():
    """The staleness lint's premise: THIS checkout's committed
    round-numbered artifacts all have registered adapters, and the
    ingest covers them all with rows."""
    out = timeline.ingest_repo(str(REPO))
    assert out["unknown"] == [], out["unknown"]
    assert out["unreadable"] == [], out["unreadable"]
    fams = set(out["coverage"])
    for expect in ("BENCH", "KERNELBENCH", "MEMLINT", "PRECLINT",
                   "SCENARIO", "SERVE_DISAGG", "TRACE", "OBS",
                   "EXPORT", "CONVERGENCE", "DECODE_PROFILE",
                   "DECODE_DECOMPOSE", "BENCH_VARIANCE", "FLEETLINT",
                   "PREFIXCACHE", "TRAINFLEET", "KERNLINT"):
        assert expect in fams, f"{expect} not ingested ({fams})"
    assert all(rec["files"] for rec in out["coverage"].values())
    assert sum(rec["rows"] for rec in out["coverage"].values()) > 100


def test_fleetlint_adapter_rows():
    """FLEETLINT rounds chart per-lane consistency (1.0 = every rank
    compiled the same collective schedule), the lane's collective count,
    and the gate's inconsistent-lane total — a regression on any of them
    is a fleet-wide deadlock risk appearing in the timeline."""
    rank = {"schedule_hash": "a" * 64, "opcode_hash": "b" * 64,
            "n_collectives": 4}
    doc = {"round": 1, "platform": "cpu", "n_ranks": 8,
           "lanes": {"ddp_o1_train": {"compare": "schedule",
                                      "consistent": True,
                                      "ranks": {"0": dict(rank),
                                                "1": dict(
                                                    rank,
                                                    n_collectives=3)},
                                      "mismatches": []}},
           "gate": {"ok": True, "inconsistent_lanes": 0}}
    rows = timeline.ADAPTERS["FLEETLINT"](doc, {})
    assert ("ddp_o1_train", "consistent", 1.0) in rows
    assert ("ddp_o1_train", "n_collectives", 4.0) in rows
    assert ("gate", "inconsistent_lanes", 0.0) in rows


def test_kernlint_adapter_rows():
    """KERNLINT rounds chart each kernel's clean verdict as 1.0/0.0,
    its total finding count, and the gate's clean fraction — a kernel
    regressing into findings (or a waiver papering over them) drops a
    charted value, not just prose."""
    rules = ["pallas-parallel-race", "pallas-vmem-overflow"]
    doc = {"round": 1, "platform": "cpu", "budget_mb": 16.0,
           "rules": rules,
           "kernels": {
               "fused_adam": {"ok": True, "configs": 2, "calls": 3,
                              "findings": {r: 0 for r in rules}},
               "layer_norm": {"ok": False, "configs": 4, "calls": 6,
                              "findings": {"pallas-vmem-overflow": 2}}},
           "gate": {"ok": False, "kernels_clean": 1,
                    "kernels_total": 2}}
    rows = timeline.ADAPTERS["KERNLINT"](doc, {})
    assert ("kernel:fused_adam", "lint_clean", 1.0) in rows
    assert ("kernel:fused_adam", "rule_findings", 0.0) in rows
    assert ("kernel:layer_norm", "lint_clean", 0.0) in rows
    assert ("kernel:layer_norm", "rule_findings", 2.0) in rows
    assert ("gate", "kernels_clean_frac", 0.5) in rows


def test_prefixcache_adapter_rows():
    """PREFIXCACHE rounds chart both arms' deterministic counts plus
    the hit-rate headline — a round where sharing quietly dispatches
    MORE prefill tokens (or the hit rate collapses) shows up as a
    timeline regression, not a silent rot."""
    doc = {"round": 1, "platform": "cpu",
           "sharing": {"prefill_chunks": 5,
                       "prefill_tokens_dispatched": 33,
                       "peak_live_blocks": 10,
                       "admitted_requests_per_block": 0.4,
                       "p50_ms": 1.9, "p99_ms": 3.2, "retraces": 1,
                       "prefix": {"hit_rate": 0.75, "hit_tokens": 31,
                                  "cow_copies": 1,
                                  "shared_blocks_peak": 4}},
           "baseline": {"prefill_tokens_dispatched": 64,
                        "peak_live_blocks": 16,
                        "admitted_requests_per_block": 0.25}}
    rows = timeline.ADAPTERS["PREFIXCACHE"](doc, {})
    assert ("sharing", "prefill_tokens_dispatched", 33.0) in rows
    assert ("baseline", "prefill_tokens_dispatched", 64.0) in rows
    assert ("sharing", "admitted_requests_per_block", 0.4) in rows
    assert ("prefix", "hit_rate", 0.75) in rows
    assert ("prefix", "hit_tokens", 31.0) in rows


def test_trainfleet_adapter_rows():
    """TRAINFLEET rounds chart the chaos drill's wall clock, generation
    count, per-recovery steps-lost, and the bitwise verdicts as
    1.0/0.0 — a round where recovery quietly loses more steps (or a
    bitwise flag drops to 0) is a timeline regression, not prose."""
    doc = {"round": 1, "platform": "cpu", "wall_s": 51.0,
           "generations": [{"gen": 0}, {"gen": 1}, {"gen": 2}],
           "recoveries": [
               {"reason": "shrink", "steps_lost": 3},
               {"reason": "regrow", "steps_lost": 1}],
           "bitwise": {"shrink_matches_uninterrupted": True,
                       "regrow_matches_uninterrupted": True,
                       "final_cross_rank_identical": False},
           "gate": {"ok": False}}
    rows = timeline.ADAPTERS["TRAINFLEET"](doc, {})
    assert ("drill", "wall_s", 51.0) in rows
    assert ("drill", "generations", 3.0) in rows
    assert ("shrink", "steps_lost", 3.0) in rows
    assert ("regrow", "steps_lost", 1.0) in rows
    assert ("bitwise", "final_cross_rank_identical", 0.0) in rows
    assert ("bitwise", "shrink_matches_uninterrupted", 1.0) in rows
    assert ("gate", "ok", 0.0) in rows


def test_unknown_family_is_a_lint_error(tmp_path):
    """A committed family with no adapter must refuse the build — the
    mechanism that keeps the timeline from silently going stale."""
    (tmp_path / "NEWFAMILY_r01.json").write_text('{"x": 1}')
    out = timeline.ingest_repo(str(tmp_path))
    assert out["unknown"] == ["NEWFAMILY_r01.json"]
    import perf_timeline
    with pytest.raises(ValueError, match="NEWFAMILY"):
        perf_timeline.build_timeline(str(tmp_path), gated=[])


def test_unreadable_artifact_excluded_from_coverage(tmp_path):
    """A corrupt committed artifact must NOT be vouched for: it stays
    out of the coverage table (so the staleness lint flags the doc
    against the checkout) and the tool refuses to build over it."""
    (tmp_path / "KERNELBENCH_r01.json").write_text(_bench_artifact(
        {}))          # readable (empty kernels -> zero rows)
    (tmp_path / "KERNELBENCH_r02.json").write_text('{"trunc')
    out = timeline.ingest_repo(str(tmp_path))
    assert out["coverage"]["KERNELBENCH"]["files"] == \
        ["KERNELBENCH_r01.json"]
    assert any("KERNELBENCH_r02" in u for u in out["unreadable"])
    # a timeline claiming that coverage is STALE vs the checkout
    doc = {"round": 1, "bands": {"default": 0.03},
           "series": {"BENCH|c|tok_s": {
               "family": "BENCH", "config": "c", "metric": "tok_s",
               "points": [{"round": 1, "value": 1.0}]}},
           "regressions": [], "coverage": out["coverage"],
           "gate": {"regressions": 0, "ok": True}}
    problems = timeline.validate_timeline(doc, repo_dir=str(tmp_path))
    assert any("STALE" in p and "KERNELBENCH_r02" in p
               for p in problems)
    import perf_timeline
    with pytest.raises(ValueError, match="unreadable"):
        perf_timeline.build_timeline(str(tmp_path), gated=[])


def test_bench_adapter_reconstructs_truncated_round(tmp_path):
    """A round whose tail is truncated past its configs map (the
    driver keeps ~2000 chars): the adapter reconstructs each rate as
    prev x (1 + recorded delta) — the artifact's own regression deltas
    are the recoverable witness."""
    (tmp_path / "BENCH_r04.json").write_text(_bench_artifact(
        {"gpt_small_tpu_heads_o2": {"tok_s": 139660.56, "mfu": 0.55}}))
    (tmp_path / "BENCH_r05.json").write_text(json.dumps({
        "parsed": None,
        "tail": 'eam": {"img_s": 354.08}}, "regression_check": {"deltas": '
                '{"gpt_small_tpu_heads_o2": -0.0323}, "ok": true}}'}))
    rows = timeline.ingest_repo(str(tmp_path))["rows"]
    by = {(r["family"], r["round"], r["config"], r["metric"]):
          r["value"] for r in rows}
    r4 = by[("BENCH", 4, "gpt_small_tpu_heads_o2", "tok_s")]
    r5 = by[("BENCH", 5, "gpt_small_tpu_heads_o2", "tok_s")]
    assert r4 == 139660.56
    assert r5 == pytest.approx(r4 * (1 - 0.0323), rel=1e-6)


# ---------------------------------------------------------------------------
# the band rule
# ---------------------------------------------------------------------------

def _series(values, family="BENCH", config="c", metric="tok_s"):
    key = timeline.series_key(family, config, metric)
    return {key: {"family": family, "config": config, "metric": metric,
                  "points": [{"round": i + 1, "value": v,
                              "commit": None}
                             for i, v in enumerate(values)]}}


def test_detect_regressions_band_rule():
    s = _series([100.0, 104.0, 100.9])     # -3.0% vs best: inside band
    key = next(iter(s))
    assert timeline.detect_regressions(s, [key],
                                       default_band=0.03) == []
    s = _series([100.0, 104.0, 100.0])     # -3.8% vs best: crosses
    rows = timeline.detect_regressions(s, [key], default_band=0.03)
    assert len(rows) == 1
    row = rows[0]
    assert row["best_round"] == 2 and row["drop_round"] == 3
    assert row["from_round"] == 2
    assert row["drop_frac"] == pytest.approx(0.0385, abs=1e-3)
    # per-series band overrides the default
    assert timeline.detect_regressions(
        s, [key], bands={key: 0.05}, default_band=0.03) == []
    # a recovered series (newest back above band) never rows
    s = _series([100.0, 90.0, 99.0])
    assert timeline.detect_regressions(s, [key],
                                       default_band=0.03) == []
    # ungated series never row
    assert timeline.detect_regressions(s, [], default_band=0.03) == []


def test_first_drop_round_named():
    """The row names the FIRST round that fell below the band, not
    just the newest."""
    s = _series([100.0, 95.0, 94.0, 93.0])
    key = next(iter(s))
    rows = timeline.detect_regressions(s, [key], default_band=0.03)
    assert rows[0]["drop_round"] == 2       # 95 < 100*0.97
    assert rows[0]["from_round"] == 1
    assert rows[0]["newest_round"] == 4


# ---------------------------------------------------------------------------
# seeded-regression attribution (satellite: the planted-drop test)
# ---------------------------------------------------------------------------

def _git(repo, *args):
    subprocess.run(["git", "-C", str(repo), "-c", "user.email=t@t",
                    "-c", "user.name=t", *args], check=True,
                   capture_output=True)


def _bench_artifact(configs):
    return json.dumps({"parsed": {"metric": "m", "value": 1.0,
                                  "unit": "u", "configs": configs}})


def test_seeded_regression_attribution(tmp_path):
    """A synthetic artifact set with a planted drop between rounds
    yields EXACTLY ONE regression row naming the planted round and
    the commits between the two round tags."""
    try:
        _git(tmp_path, "init", "-q")
    except (OSError, subprocess.CalledProcessError):
        pytest.skip("git unavailable")
    import perf_timeline

    (tmp_path / "BENCH_r01.json").write_text(_bench_artifact(
        {"cfg_a": {"tok_s": 1000.0}, "cfg_b": {"tok_s": 500.0}}))
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "round 1 artifact")
    # the suspect: a code commit BETWEEN the two round tags
    (tmp_path / "kernel.py").write_text("# the perf-relevant change\n")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "the suspect change")
    suspect = subprocess.run(
        ["git", "-C", str(tmp_path), "rev-parse", "--short", "HEAD"],
        capture_output=True, text=True, check=True).stdout.strip()
    # round 2: cfg_a planted -10%, cfg_b steady
    (tmp_path / "BENCH_r02.json").write_text(_bench_artifact(
        {"cfg_a": {"tok_s": 900.0}, "cfg_b": {"tok_s": 501.0}}))
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-q", "-m", "round 2 artifact")

    gated = [timeline.series_key("BENCH", c, "tok_s")
             for c in ("cfg_a", "cfg_b")]
    doc = perf_timeline.build_timeline(str(tmp_path), gated=gated)
    assert len(doc["regressions"]) == 1
    row = doc["regressions"][0]
    assert row["series"] == timeline.series_key("BENCH", "cfg_a",
                                                "tok_s")
    assert row["drop_round"] == 2 and row["from_round"] == 1
    assert row["drop_frac"] == pytest.approx(0.10, abs=1e-4)
    suspects = [s["commit"] for s in row["suspects"]]
    assert suspect in suspects, (suspect, suspects)
    # ... and the emitted document validates against its own repo
    assert timeline.validate_timeline(doc,
                                      repo_dir=str(tmp_path)) == []


# ---------------------------------------------------------------------------
# schema contradiction classes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def committed_doc():
    # the NEWEST committed round: the one gate_hygiene holds to
    # coverage-completeness against this checkout
    newest = max(REPO.glob("TIMELINE_r*.json"))
    with open(newest) as f:
        return json.load(f)


def test_committed_timeline_validates(committed_doc):
    assert timeline.validate_timeline(committed_doc,
                                      repo_dir=str(REPO)) == []


def test_committed_timeline_rediscovers_known_regressions(
        committed_doc):
    """The acceptance bar: the committed round's regression table
    independently rediscovers the gpt/bert tpu-heads drops between
    r04 and r05, with VERDICT's suspects in the attributed range."""
    rows = {r["series"]: r for r in committed_doc["regressions"]}
    gpt = rows["BENCH|gpt_small_tpu_heads_o2|tok_s"]
    bert = rows["BENCH|bert_large_tpu_heads_lamb_o2|seq_s"]
    for row in (gpt, bert):
        assert row["drop_round"] == 5 and row["from_round"] == 4
        suspects = [s["commit"] for s in row["suspects"]]
        # the two suspects VERDICT r5 named by hand
        assert "90d60d2" in suspects      # prefill-flash
        assert "02a761d" in suspects      # mt-aliasing
    assert gpt["drop_frac"] == pytest.approx(0.0323, abs=1e-3)
    assert committed_doc["gate"] == {"regressions": 2, "ok": False}
    # the kv8 seed is reported as UNMEASURED, not passed off as a floor
    assert "gpt_small_tpu_decode_kv8" in \
        committed_doc["provisional_floors"]


def test_fabricated_regression_rejected(committed_doc):
    bad = copy.deepcopy(committed_doc)
    bad["regressions"][0]["series"] = "BENCH|resnet50_o2|img_s"
    problems = timeline.validate_timeline(bad)
    assert any("never cross" in p for p in problems)


def test_suppressed_regression_rejected(committed_doc):
    bad = copy.deepcopy(committed_doc)
    bad["regressions"] = []
    bad["gate"] = {"regressions": 0, "ok": True}
    problems = timeline.validate_timeline(bad)
    assert any("suppressed regression" in p for p in problems)


def test_self_citing_gate_rejected(committed_doc):
    bad = copy.deepcopy(committed_doc)
    bad["gate"]["ok"] = True
    problems = timeline.validate_timeline(bad)
    assert any("CONTRADICTORY verdict: gate.ok" in p
               for p in problems)
    bad2 = copy.deepcopy(committed_doc)
    bad2["gate"]["regressions"] = 99
    assert any("gate.regressions" in p
               for p in timeline.validate_timeline(bad2))


def test_tampered_values_rejected(committed_doc):
    """A regression row whose stated values disagree with the series
    it cites is contradictory."""
    bad = copy.deepcopy(committed_doc)
    bad["regressions"][0]["best_value"] += 10.0
    problems = timeline.validate_timeline(bad)
    assert any("CONTRADICTORY record" in p for p in problems)


def test_tampered_from_round_rejected(committed_doc):
    """from_round defines the suspect-commit attribution range; a row
    claiming a different range than the cited series derives is
    contradictory like every other field."""
    bad = copy.deepcopy(committed_doc)
    bad["regressions"][0]["from_round"] = 1
    problems = timeline.validate_timeline(bad)
    assert any("from_round" in p for p in problems)


def test_stale_coverage_rejected(tmp_path, committed_doc):
    """A committed artifact absent from the coverage table invalidates
    the timeline when judged against the checkout — a new family or
    round cannot land without refreshing the timeline."""
    # judged against a dir with one extra committed family file
    (tmp_path / "KERNELBENCH_r99.json").write_text("{}")
    problems = timeline.validate_timeline(committed_doc,
                                          repo_dir=str(tmp_path))
    assert any("STALE timeline" in p and "KERNELBENCH_r99" in p
               for p in problems)
    # internal-only validation of the same doc stays clean
    assert timeline.validate_timeline(committed_doc) == []
