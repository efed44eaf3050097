"""Cross-rank SPMD consistency lint (:mod:`apex_tpu.analysis.spmd`).

The fleet invariant is "every rank executes the same collective
schedule"; a violation is a hang, not an error message.  Each detector
class must (a) FIRE on a seeded divergence with its documented finding
id — ``spmd-schedule-mismatch`` (different op sequence: the static
deadlock), ``spmd-group-mismatch`` (same sequence, different channel
wiring), ``spmd-bytes-mismatch`` (the signSGD class: a sign-compressed
/ width-changed bucket on one rank), ``spmd-conditional-collective``
(a collective under a rank-divergent predicate) — and (b) stay QUIET on
rank-identical lowerings and the real DDP lanes.  The collective
schedule parser (both StableHLO and compiled-HLO spellings), the
fingerprint the runtime preflight exchanges, the FLEETLINT artifact
schema, and the graph_lint fleet lanes are pinned here too (ISSUE 16).
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu import analysis  # noqa: E402
from apex_tpu.analysis import spmd  # noqa: E402
from apex_tpu.analysis.collectives import (canon_groups,  # noqa: E402
                                           collective_attrs,
                                           collective_audit,
                                           collective_table)
from apex_tpu.analysis.fleetlint import (validate_fleetlint,  # noqa: E402
                                         validate_fleetlint_file)
from apex_tpu.parallel import multiproc  # noqa: E402
from apex_tpu.parallel.distributed import (ReduceConfig,  # noqa: E402
                                           reduce_gradients)
from jax import shard_map  # noqa: E402


def mesh(n=8):
    return Mesh(np.array(jax.devices()[:n]), ("data",))


def _text(fn, *args):
    return analysis.lower_quiet(jax.jit(fn), *args).as_text()


def _psum_text(extra=False, n=8):
    def f(x):
        g = jax.lax.psum(x, "data")
        if extra:
            g = g + jax.lax.pmax(x, "data")
        return g

    sm = shard_map(f, mesh=mesh(n), in_specs=P("data"), out_specs=P())
    return _text(sm, jnp.ones((n, 4), jnp.float32))


def _ops(findings):
    return [f.op for f in findings]


# ---------------------------------------------------------------------------
# the collective schedule: both lowering representations
# ---------------------------------------------------------------------------

def test_stablehlo_schedule_entries():
    sched = spmd.collective_schedule(_psum_text())
    assert len(sched) == 1
    e = sched[0]
    assert e["kind"] == "all-reduce" and e["variant"] == "sync"
    assert e["replica_groups"] == "{{0,1,2,3,4,5,6,7}}"
    assert e["dtypes"] == ["f32"] and e["bytes"] == 4 * 4  # f32[4] shard
    assert e["region"] is None


HLO_REGIONS = """
%body.1 (p: f32[4]) -> f32[4] {
  %ar.in = f32[4]{0} all-reduce(f32[4]{0} %p), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add
}
ENTRY %main.2 (q: f32[8]) -> f32[8] {
  %ag-start = (f32[1]{0}, f32[8]{0}) all-gather-start(f32[1]{0} %q), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, dimensions={0}
  %ag-done = f32[8]{0} all-gather-done((f32[1]{0}, f32[8]{0}) %ag-start)
}
"""


def test_compiled_hlo_schedule_regions_channels_async():
    sched = spmd.collective_schedule(HLO_REGIONS)
    assert [e["kind"] for e in sched] == ["all-reduce", "all-gather"]
    ar, ag = sched
    # the non-entry computation names the region; ENTRY is top level
    assert ar["region"] == "body.1" and ag["region"] is None
    assert ar["channel_id"] == 2
    assert ar["replica_groups"] == "{{0,1,2,3},{4,5,6,7}}"
    # the async pair yields ONE entry, result-buffer bytes, global ids
    assert ag["variant"] == "async" and ag["bytes"] == 8 * 4
    assert ag["use_global_device_ids"] is True


def test_fingerprint_ignores_text_layout_but_not_payload():
    text = _psum_text()
    sched = spmd.collective_schedule(text)
    shifted = spmd.collective_schedule("\n\n\n" + text)
    assert [e["lineno"] for e in sched] != [e["lineno"] for e in shifted]
    # lineno is layout, not semantics: fingerprints must agree
    assert spmd.schedule_fingerprint(sched) == \
        spmd.schedule_fingerprint(shifted)
    # ... and the opcode-only digest is a coarser hash than the full one
    assert spmd.schedule_fingerprint(sched, opcodes_only=True) != \
        spmd.schedule_fingerprint(sched)
    bumped = [dict(sched[0], bytes=sched[0]["bytes"] * 2)]
    assert spmd.schedule_fingerprint(bumped) != \
        spmd.schedule_fingerprint(sched)


def test_first_divergence_names_end_of_schedule():
    a = spmd.collective_schedule(_psum_text())
    assert spmd.first_divergence(a, list(a)) is None
    d = spmd.first_divergence(a, [])
    assert d is not None and d[0] == 0
    assert d[1].startswith("all-reduce(") and d[2] == "<end of schedule>"


# ---------------------------------------------------------------------------
# the four finding ids fire on seeded fixtures
# ---------------------------------------------------------------------------

def test_seeded_schedule_mismatch_fires():
    """One rank lowers an extra collective: the static deadlock."""
    findings = spmd.compare_lowerings(
        {"rank 0": _psum_text(), "rank 7": _psum_text(extra=True)})
    assert _ops(findings) == ["spmd-schedule-mismatch"]
    f = findings[0]
    assert f.severity == "error" and f.count == 1
    assert "deadlock" in f.message
    assert "<end of schedule>" in f.example


def test_identical_lowerings_are_quiet():
    assert spmd.compare_lowerings(
        {"rank 0": _psum_text(), "rank 1": _psum_text()}) == []


HLO_GROUPS_A = """
ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %ar = f32[4]{0} all-reduce(f32[4]{0} %p), channel_id=1, replica_groups={{0,1,2,3,4,5,6,7}}, use_global_device_ids=true, to_apply=%add
}
"""
HLO_GROUPS_B = HLO_GROUPS_A.replace("{{0,1,2,3,4,5,6,7}}",
                                    "{{0,1,2,3},{4,5,6,7}}")


def test_seeded_group_mismatch_fires():
    """Same op sequence, different replica_groups: ranks rendezvous on
    mismatched channels."""
    findings = spmd.diff_schedules(
        "rank 0", spmd.collective_schedule(HLO_GROUPS_A),
        "rank 5", spmd.collective_schedule(HLO_GROUPS_B))
    assert _ops(findings) == ["spmd-group-mismatch"]
    assert findings[0].severity == "error"
    assert "groups={{0,1,2,3,4,5,6,7}}" in findings[0].example
    assert "groups={{0,1,2,3},{4,5,6,7}}" in findings[0].example


def test_seeded_signsgd_bytes_mismatch_fires():
    """The fork's signSGD hack: one rank's gradient bucket travels
    sign-compressed at fp32 wire width while its peers send bf16 — the
    payload halves of the same all-reduce disagree."""
    def make(cfg):
        sm = shard_map(lambda g: reduce_gradients(g, "data", cfg),
                       mesh=mesh(), in_specs=P(), out_specs=P())
        return _text(sm, jnp.ones((16,), jnp.bfloat16))

    findings = spmd.diff_schedules(
        "rank 0", spmd.collective_schedule(make(ReduceConfig())),
        "rank 7", spmd.collective_schedule(make(ReduceConfig(
            allreduce_always_fp32=True, compression="sign"))))
    assert _ops(findings) == ["spmd-bytes-mismatch"]
    f = findings[0]
    assert f.severity == "error" and "signSGD" in f.message
    assert "bf16" in f.example and "f32" in f.example


def test_seeded_conditional_collective_fires():
    """A psum only some ranks reach: the enclosing branch predicate is
    derived from the rank index."""
    def f(x):
        return jax.lax.cond(jax.lax.axis_index("data") < 4,
                            lambda v: jax.lax.pcast(
                                jax.lax.psum(v, "data"), "data",
                                to="varying"),
                            lambda v: v, x)

    sm = shard_map(f, mesh=mesh(), in_specs=P("data"),
                   out_specs=P("data"))
    text = _text(sm, jnp.ones((8, 4), jnp.float32))
    findings = spmd.conditional_collective_findings(text)
    assert "spmd-conditional-collective" in _ops(findings)
    f0 = [x for x in findings if x.op == "spmd-conditional-collective"][0]
    assert f0.severity == "error" and f0.lineno
    assert "rank-divergent predicate" in f0.message


def test_unconditional_collective_is_quiet():
    assert spmd.conditional_collective_findings(_psum_text()) == []


# ---------------------------------------------------------------------------
# reshape pairs: opcode sequence must survive a mesh reshape
# ---------------------------------------------------------------------------

def test_reshape_pair_opcode_consistent_is_info():
    findings = spmd.reshape_pair_findings(
        "mesh8", _psum_text(n=8), "mesh4", _psum_text(n=4))
    assert _ops(findings) == ["reshape-pair"]
    assert findings[0].severity == "info"
    assert "opcode-consistent" in findings[0].message


def test_reshape_pair_changed_sequence_is_error():
    findings = spmd.reshape_pair_findings(
        "mesh8", _psum_text(n=8), "mesh4", _psum_text(extra=True, n=4))
    assert _ops(findings) == ["spmd-schedule-mismatch"]
    assert "deadlock" in findings[0].message


# ---------------------------------------------------------------------------
# the registered pass
# ---------------------------------------------------------------------------

def test_spmd_pass_registered_and_reports_schedule():
    def f(x):
        return jax.lax.psum(x, "data")

    sm = jax.jit(shard_map(f, mesh=mesh(), in_specs=P("data"),
                           out_specs=P()))
    rep = analysis.analyze(sm, jnp.ones((8, 4), jnp.float32),
                           passes=("spmd-consistency",), compile=False)
    assert rep.ok and rep.passes == ("spmd-consistency",)
    sched_info = [f_ for f_ in rep.findings if f_.op == "schedule"]
    assert len(sched_info) == 1 and sched_info[0].count == 1


def test_spmd_pass_peers_option_diffs_against_context():
    def f(x):
        return jax.lax.psum(x, "data")

    sm = jax.jit(shard_map(f, mesh=mesh(), in_specs=P("data"),
                           out_specs=P()))
    rep = analysis.analyze(
        sm, jnp.ones((8, 4), jnp.float32),
        passes=("spmd-consistency",), compile=False,
        options={"spmd-consistency":
                 {"peers": {"rank 7": _psum_text(extra=True)}}})
    assert not rep.ok
    assert "spmd-schedule-mismatch" in [f_.op for f_ in rep.findings]


# ---------------------------------------------------------------------------
# collective_table wiring attributes (satellite: parser pins)
# ---------------------------------------------------------------------------

def test_canon_groups_spellings():
    assert canon_groups("{{0,1},{2,3}}") == "{{0,1},{2,3}}"
    # StableHLO dense form (whitespace and 2D brackets normalized)
    assert canon_groups("[[0, 1], [2, 3]]") == "{{0,1},{2,3}}"
    # iota form survives verbatim (no literal groups to normalize)
    assert canon_groups("[2,4]<=[8]") == "[2,4]<=[8]"


def test_collective_attrs_absent_defaults():
    attrs = collective_attrs("  %ar = f32[4]{0} all-reduce(f32[4]{0} %p)")
    assert attrs == {"channel_id": None, "replica_groups": None,
                     "use_global_device_ids": False}


def test_collective_table_records_channel_wiring():
    table = collective_table(HLO_REGIONS)
    ar, ag = table["all-reduce"], table["all-gather"]
    assert ar["channels"] == [2] and ag["channels"] == [1]
    assert ar["replica_groups"] == ["{{0,1,2,3},{4,5,6,7}}"]
    assert ag["replica_groups"] == ["{{0,1,2,3,4,5,6,7}}"]
    assert ag["global_ids"] == 1 and ar["global_ids"] == 0
    # the dryrun-compat audit shape is unchanged: {count, bytes} only
    assert collective_audit(HLO_REGIONS)["all-gather"] == {
        "count": 1, "bytes": 8 * 4}


# ---------------------------------------------------------------------------
# the runtime preflight (single process — the degenerate barrier)
# ---------------------------------------------------------------------------

def test_spmd_preflight_single_process_records_hashes():
    text = _psum_text()
    rec = multiproc.spmd_preflight(text, label="unit")
    assert rec["ok"] and rec["label"] == "unit"
    assert rec["n_ranks"] == 1 and rec["n_collectives"] == 1
    assert rec["schedule_hash"] == spmd.schedule_fingerprint(
        spmd.collective_schedule(text))
    # a zero-arg callable (the initialize() deferred form) works too
    rec2 = multiproc.spmd_preflight(lambda: text, label="unit")
    assert rec2["schedule_hash"] == rec["schedule_hash"]


def test_spmd_preflight_rejects_garbage():
    with pytest.raises(TypeError, match="lowering"):
        multiproc.spmd_preflight(42)


# ---------------------------------------------------------------------------
# FLEETLINT schema: contradiction-rejecting
# ---------------------------------------------------------------------------

def _valid_fleetlint():
    rank = {"schedule_hash": "a" * 64, "opcode_hash": "b" * 64,
            "n_collectives": 3}
    return {
        "round": 1, "platform": "cpu", "n_ranks": 8,
        "lanes": {
            "ddp_o1_train": {"compare": "schedule", "consistent": True,
                             "ranks": {"0": dict(rank), "1": dict(rank)},
                             "findings": {"info": 1}, "mismatches": []},
            "reshape_8to4": {"compare": "opcodes", "consistent": True,
                             "ranks": {"mesh8": dict(rank),
                                       "mesh4": dict(
                                           rank, schedule_hash="c" * 64)},
                             "mismatches": []},
        },
        "gate": {"ok": True, "inconsistent_lanes": 0},
    }


def test_valid_fleetlint_passes():
    assert validate_fleetlint(_valid_fleetlint()) == []


def test_fleetlint_contradictory_lane_verdict_rejected():
    """consistent=true over disagreeing recorded hashes is the lie the
    schema exists to reject (and vice versa)."""
    doc = _valid_fleetlint()
    doc["lanes"]["ddp_o1_train"]["ranks"]["1"]["schedule_hash"] = "d" * 64
    doc["lanes"]["ddp_o1_train"]["mismatches"] = [
        {"ranks": ["0", "1"], "index": 0, "a": "x", "b": "y"}]
    probs = validate_fleetlint(doc)
    assert any("contradicts" in p for p in probs)
    doc2 = _valid_fleetlint()
    doc2["lanes"]["ddp_o1_train"]["consistent"] = False
    assert any("contradicts" in p for p in validate_fleetlint(doc2))


def test_fleetlint_mismatch_rows_must_name_the_diverging_op():
    doc = _valid_fleetlint()
    lane = doc["lanes"]["ddp_o1_train"]
    lane["consistent"] = False
    lane["ranks"]["1"]["schedule_hash"] = "d" * 64
    # hashes disagree but no mismatch row: rejected
    probs = validate_fleetlint(doc)
    assert any("no mismatch row" in p for p in probs)
    lane["mismatches"] = [{"ranks": ["0", "nope"], "index": -1, "a": ""}]
    doc["gate"] = {"ok": False, "inconsistent_lanes": 1}
    probs = validate_fleetlint(doc)
    assert any("two recorded rank labels" in p for p in probs)
    assert any("'index'" in p for p in probs)
    assert any("side 'b'" in p for p in probs)


def test_fleetlint_gate_must_agree_with_lanes():
    doc = _valid_fleetlint()
    doc["gate"]["inconsistent_lanes"] = 2
    assert any("contradicts the lanes" in p for p in validate_fleetlint(doc))
    doc["gate"] = {"ok": False, "inconsistent_lanes": 0}
    assert any("gate.ok=False contradicts" in p
               for p in validate_fleetlint(doc))


def test_fleetlint_needs_two_sides_per_lane():
    doc = _valid_fleetlint()
    lane = doc["lanes"]["ddp_o1_train"]
    lane["ranks"] = {"0": lane["ranks"]["0"]}
    assert any("proves nothing" in p for p in validate_fleetlint(doc))


def test_repo_fleetlint_artifact_validates():
    """The committed FLEETLINT round is the schema's reference
    instance."""
    paths = sorted(REPO.glob("FLEETLINT_r*.json"))
    assert paths, "the fleet SPMD gate artifact must be committed"
    for p in paths:
        assert validate_fleetlint_file(str(p)) == [], p


# ---------------------------------------------------------------------------
# graph_lint fleet lanes
# ---------------------------------------------------------------------------

def test_fleet_ddp_lane_consistent_at_two_ranks():
    import graph_lint
    findings, rec = graph_lint.fleet_lane_result("ddp_o1_train", n_ranks=2)
    assert rec["compare"] == "schedule" and rec["consistent"]
    assert set(rec["ranks"]) == {"0", "1"} and rec["mismatches"] == []
    assert all(f.severity != "error" for f in findings)
    assert rec["ranks"]["0"]["n_collectives"] >= 2  # grad reduce + pmean


def test_fleet_reshape_lane_opcode_consistent():
    import graph_lint
    findings, rec = graph_lint.fleet_lane_result("reshape_8to4")
    assert rec["compare"] == "opcodes" and rec["consistent"]
    assert set(rec["ranks"]) == {"mesh8", "mesh4"}
    # a reshape legally changes groups, so the FULL hashes differ ...
    hashes = {r["schedule_hash"] for r in rec["ranks"].values()}
    assert len(hashes) == 2
    # ... while the opcode hashes agree (that is the lane's verdict)
    assert len({r["opcode_hash"] for r in rec["ranks"].values()}) == 1


def test_lint_fleet_skips_unrequested_passes():
    import graph_lint
    assert graph_lint.lint_fleet("ddp_o1_train",
                                 passes=("memory",)).passes == ()


def test_cli_fleet_lane_dispatch(monkeypatch, capsys):
    import graph_lint
    orig = graph_lint.lint_fleet

    def two_rank(lane, passes=None, n_ranks=None, _collect=None):
        return orig(lane, passes=passes, n_ranks=2, _collect=_collect)

    monkeypatch.setattr(graph_lint, "lint_fleet", two_rank)
    assert graph_lint.main(["--lanes", "fleet",
                            "--passes", "spmd-consistency"]) == 0
    out = capsys.readouterr().out
    for lane in graph_lint.FLEET_LANES:
        assert f'"lane": "{lane}"' in out
    for line in out.splitlines():
        rec = json.loads(line)
        assert rec["ok"], rec


def test_cli_emit_fleetlint_refuses_partial_configs():
    import graph_lint
    # the committed artifact must always cover the full lane/pass matrix
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", "FLEETLINT_r99.json",
                         "--lanes", "o1"])
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", "FLEETLINT_r99.json",
                         "--passes", "memory"])
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", "FLEETLINT_r99.json",
                         "--families", "mlp"])


def test_emit_fleetlint_writes_schema_valid_doc(tmp_path, monkeypatch):
    """The emitter and the schema can never drift: a (canned) emit
    round-trips through the validator."""
    import graph_lint

    rank = {"schedule_hash": "a" * 64, "opcode_hash": "b" * 64,
            "n_collectives": 3}

    def canned(lane, n_ranks=8):
        return [], {"compare": "schedule", "consistent": True,
                    "ranks": {"0": dict(rank), "1": dict(rank)},
                    "mismatches": []}

    monkeypatch.setattr(graph_lint, "fleet_lane_result", canned)
    path = tmp_path / "FLEETLINT_r07.json"
    assert graph_lint.emit_fleetlint(str(path)) == 0
    assert validate_fleetlint_file(str(path)) == []
    doc = json.loads(path.read_text())
    assert doc["round"] == 7
    assert set(doc["lanes"]) == set(graph_lint.FLEET_LANES)
