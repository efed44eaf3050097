"""Units for bench.py's harness pieces (the benchmark itself runs on the
driver's chip): the no-chip error, the FLOP-count fallback and the gates."""

import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

import bench  # noqa: E402


def test_main_without_a_chip_is_an_error(capsys):
    """bench.py measures the chip and nothing else: on the CPU platform
    the tests run on it exits non-zero, says why, and prints no result."""
    with pytest.raises(SystemExit) as exc:
        bench.main([])
    assert "no TPU" in str(exc.value)
    assert capsys.readouterr().out == ""


def test_step_flops_fallback():
    class NoCost:
        def cost_analysis(self):
            raise NotImplementedError
    assert bench.step_flops(NoCost(), fallback=123.0) == 123.0

    class DictCost:
        def cost_analysis(self):
            return {"flops": 7.0}
    assert bench.step_flops(DictCost(), fallback=0.0) == 7.0

    class ZeroCost:  # some backends report 0 — fall back
        def cost_analysis(self):
            return {"flops": 0.0}
    assert bench.step_flops(ZeroCost(), fallback=5.0) == 5.0


def _write_bench(tmp_path, name, configs):
    import json
    p = tmp_path / name
    p.write_text(json.dumps({"configs": configs}))
    return str(p)


def test_compare_configs_flags_only_real_drops(tmp_path):
    prior = _write_bench(tmp_path, "BENCH_r03.json", {
        "resnet50_o2": {"img_s": 1000.0},
        "gpt_small_o2": {"tok_s": 50000.0},
        "bert_large_lamb_o2": {"seq_s": 100.0},
        "errored_before": {"error": "OOM"},
    })
    verdict = bench.compare_configs(prior, {
        "resnet50_o2": {"img_s": 960.0},        # -4%: within variance
        "gpt_small_o2": {"tok_s": 40000.0},     # -20%: regression
        "bert_large_lamb_o2": {"error": "OOM"},  # errored now: uncompared
        "errored_before": {"seq_s": 5.0},        # errored then: uncompared
        "brand_new_cfg": {"img_s": 1.0},         # no baseline: uncompared
    }, threshold=0.10)
    assert verdict["regressions"] == ["gpt_small_o2"]
    assert not verdict["ok"]
    assert verdict["deltas"]["resnet50_o2"] == -0.04
    assert set(verdict["uncompared"]) == {
        "bert_large_lamb_o2", "errored_before", "brand_new_cfg"}


def test_compare_configs_skips_batch_mismatch(tmp_path):
    """An OOM batch-ladder fallback (bench_gpt) changes the tok/s
    denominator; a config whose batch differs from the baseline's must
    be listed uncompared, not read as a 50% regression."""
    prior = _write_bench(tmp_path, "BENCH_r03.json", {
        "gpt_medium_tpu_o2": {"tok_s": 43500.0, "batch": 8},
        "gpt_small_o2": {"tok_s": 100000.0, "batch": 8},
    })
    verdict = bench.compare_configs(prior, {
        "gpt_medium_tpu_o2": {"tok_s": 25000.0, "batch": 4,
                              "oom_fallback_from_batch": 8},
        "gpt_small_o2": {"tok_s": 99000.0, "batch": 8},
    }, threshold=0.10)
    assert verdict["ok"] and not verdict["regressions"]
    assert "gpt_medium_tpu_o2" in verdict["uncompared"]
    assert verdict["deltas"].keys() == {"gpt_small_o2"}


def test_compare_configs_ok_within_threshold(tmp_path):
    prior = _write_bench(tmp_path, "BENCH_r03.json",
                         {"resnet50_o2": {"img_s": 1000.0}})
    verdict = bench.compare_configs(
        prior, {"resnet50_o2": {"img_s": 930.0}}, threshold=0.10)
    assert verdict["ok"] and not verdict["regressions"]


def test_compare_configs_unwraps_driver_artifact(tmp_path):
    import json
    p = tmp_path / "BENCH_r03.json"  # driver shape: payload under "parsed"
    p.write_text(json.dumps({
        "n": 3, "rc": 0, "tail": "...",
        "parsed": {"configs": {"resnet50_o2": {"img_s": 1000.0}}}}))
    verdict = bench.compare_configs(
        str(p), {"resnet50_o2": {"img_s": 800.0}}, threshold=0.10)
    assert verdict["regressions"] == ["resnet50_o2"]


def test_compare_configs_unreadable_baseline_never_fails(tmp_path):
    bad = tmp_path / "BENCH_r99.json"
    bad.write_text("{not json")
    verdict = bench.compare_configs(str(bad), {"a": {"img_s": 1.0}})
    assert verdict["ok"] and "error" in verdict


def test_find_prior_bench_picks_newest_round(tmp_path):
    for n in (1, 3, 2):
        _write_bench(tmp_path, f"BENCH_r{n:02d}.json", {})
    assert bench.find_prior_bench(str(tmp_path)).endswith("BENCH_r03.json")
    assert bench.find_prior_bench(str(tmp_path / "empty")) is None


def test_repo_has_prior_bench_artifact():
    # the real repo carries round artifacts; the default gate must find one
    assert bench.find_prior_bench(str(REPO)) is not None


def test_mfu_vs_hfu_pass_counts():
    # MFU books 6 analytic attention passes (PaLM model-FLOPs
    # convention); HFU books the 7 the fused backward actually runs.
    assert bench.ATTN_MODEL_PASSES == 6
    assert bench.ATTN_FUSED_EXEC_PASSES == 7


def test_pallas_attn_compiled_detection():
    class Hlo:
        def __init__(self, txt):
            self._txt = txt

        def as_text(self):
            return self._txt

    # detection must be attention-specific: a fused-optimizer or
    # layer-norm custom call in the step must NOT vouch for the
    # attention kernel path (it would re-introduce the double count)
    assert bench._pallas_attn_compiled(Hlo(
        '%c.1 = custom-call(...), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/jvp(jit(_flash_fwd))/flash_fwd/pallas_call"}'))
    assert bench._pallas_attn_compiled(Hlo(
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(f)/transpose(jvp(jit(_flash_bwd_fused)))/flash_bwd_fused/'
        'pallas_call"}'))
    assert not bench._pallas_attn_compiled(Hlo(
        '%c.3 = custom-call(...), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(f)/jit(packed_lamb_stage1)/lamb_stage1/'
        'pallas_call"}'))
    assert not bench._pallas_attn_compiled(Hlo("fusion(...) dot(...)"))

    class NoText:
        def as_text(self):
            raise NotImplementedError
    assert bench._pallas_attn_compiled(NoText()) is None


def test_compare_configs_lists_prior_only(tmp_path):
    prior = _write_bench(tmp_path, "BENCH_r03.json", {
        "gpt_small_o2": {"tok_s": 50000.0},
        "deleted_config": {"img_s": 9.0},
    })
    verdict = bench.compare_configs(prior, {
        "gpt_small_o2": {"tok_s": 49000.0},
    }, threshold=0.10)
    assert verdict["ok"]
    assert "deleted_config" in verdict["uncompared"]  # baseline-only


def test_compare_configs_wrong_shape_baselines_never_crash(tmp_path):
    import json
    for i, payload in enumerate(
            ('{"configs": null}', "[1, 2, 3]", '{"parsed": 7}', "3")):
        p = tmp_path / f"BENCH_r9{i}.json"
        p.write_text(payload)
        verdict = bench.compare_configs(str(p), {"a": {"img_s": 1.0}})
        assert verdict["ok"] and "error" in verdict, payload


def test_compare_configs_ladder_substitutes_same_batch(tmp_path):
    """A batch-mismatched config with a persisted same-batch ladder
    baseline is gated like-for-like instead of listed uncompared
    (VERDICT r4 next #4)."""
    prior = _write_bench(tmp_path, "BENCH_r04.json", {
        "gpt_medium_tpu_o2": {"tok_s": 43500.0, "batch": 8},
    })
    ladder = {"gpt_medium_tpu_o2": {
        "4": {"tok_s": 50000.0, "batch": 4, "recorded": "2026-08-01"}}}
    # like-for-like b4-vs-b4: -4% is fine
    verdict = bench.compare_configs(prior, {
        "gpt_medium_tpu_o2": {"tok_s": 48000.0, "batch": 4}},
        threshold=0.10, ladder=ladder)
    assert verdict["ok"]
    assert verdict["deltas"]["gpt_medium_tpu_o2"] == -0.04
    assert verdict["ladder_compared"]["gpt_medium_tpu_o2"]["batch"] == 4
    # a real 20% drop vs the same-batch ladder rung DOES trip the gate
    verdict = bench.compare_configs(prior, {
        "gpt_medium_tpu_o2": {"tok_s": 40000.0, "batch": 4}},
        threshold=0.10, ladder=ladder)
    assert verdict["regressions"] == ["gpt_medium_tpu_o2"]
    # no ladder entry for the batch -> still uncompared, never guessed
    verdict = bench.compare_configs(prior, {
        "gpt_medium_tpu_o2": {"tok_s": 40000.0, "batch": 6}},
        threshold=0.10, ladder=ladder)
    assert "gpt_medium_tpu_o2" in verdict["uncompared"]


def test_compare_configs_ladder_covers_errored_prior(tmp_path):
    """The OOM scenario the ladder exists for: the prior round's entry
    ERRORED (or is missing entirely) — the same-batch rung must still
    gate the config instead of leaving it uncompared."""
    prior = _write_bench(tmp_path, "BENCH_r04.json", {
        "gpt_medium_tpu_o2": {"error": "RESOURCE_EXHAUSTED ..."},
    })
    ladder = {"gpt_medium_tpu_o2": {
        "4": {"tok_s": 50000.0, "batch": 4, "recorded": "2026-08-01"}}}
    verdict = bench.compare_configs(prior, {
        "gpt_medium_tpu_o2": {"tok_s": 40000.0, "batch": 4}},
        threshold=0.10, ladder=ladder)
    assert verdict["regressions"] == ["gpt_medium_tpu_o2"]
    assert verdict["ladder_compared"]["gpt_medium_tpu_o2"]["batch"] == 4
    # prior missing the config entirely: same story
    prior2 = _write_bench(tmp_path, "BENCH_r05.json", {})
    verdict = bench.compare_configs(prior2, {
        "gpt_medium_tpu_o2": {"tok_s": 49500.0, "batch": 4}},
        threshold=0.10, ladder=ladder)
    assert verdict["ok"]
    assert verdict["deltas"]["gpt_medium_tpu_o2"] == -0.01


def test_ladder_baselines_roundtrip(tmp_path):
    configs = {
        "gpt_medium_tpu_o2": {"tok_s": 49000.0, "batch": 4, "mfu": 0.58},
        "errored": {"error": "OOM"},
        "no_batch": {"tok_s": 5.0},
    }
    bench.update_ladder_baselines(str(tmp_path), configs)
    doc = bench.load_ladder_baselines(str(tmp_path))
    assert doc["gpt_medium_tpu_o2"]["4"]["tok_s"] == 49000.0
    assert "recorded" in doc["gpt_medium_tpu_o2"]["4"]
    assert "errored" not in doc and "no_batch" not in doc
    # updating a new rung keeps the old one
    bench.update_ladder_baselines(
        str(tmp_path), {"gpt_medium_tpu_o2": {"tok_s": 44000.0,
                                              "batch": 8}})
    doc = bench.load_ladder_baselines(str(tmp_path))
    assert set(doc["gpt_medium_tpu_o2"]) == {"4", "8"}


def test_repo_ladder_has_medium_b4_baseline():
    # the gate must be able to compare a b4 OOM-ladder landing
    doc = bench.load_ladder_baselines(str(REPO))
    assert doc["gpt_medium_tpu_o2"]["4"]["tok_s"] > 0


def test_mfu_floor_gate():
    floors = bench.MFU_FLOORS
    assert "resnet50_o2" in floors and "gpt_medium_tpu_o2" in floors
    gate = floors["resnet50_o2"] * (1 - bench.MFU_VARIANCE_BAND)
    # r4's measured 0.2983 (0.6% under the prose floor, inside chip-day
    # variance) passes the banded gate — the VERDICT weak-#2 resolution
    check = bench.check_mfu_floors({"resnet50_o2": {"mfu": 0.2983}})
    assert check["ok"] and check["checked"]["resnet50_o2"]["ok"]
    assert check["checked"]["resnet50_o2"]["gate"] == round(gate, 4)
    # a real efficiency loss does not
    check = bench.check_mfu_floors({"resnet50_o2": {"mfu": 0.27}})
    assert not check["ok"] and check["violations"] == ["resnet50_o2"]
    # errored/skipped/missing configs are not judged
    check = bench.check_mfu_floors({"resnet50_o2": {"error": "OOM"},
                                    "gpt_small_o2": {"mfu": None}})
    assert check["ok"] and not check["checked"]


@pytest.mark.slow
def test_bench_generate_tiny_cpu():
    """The decode bench path runs end-to-end on CPU with the tiny
    config (the real config runs on the driver's chip)."""
    r = bench.bench_generate(batch=2, prefill=16, new_tokens=8,
                             warmup=0, iters=1, peak=None, tiny=True)
    assert r["tok_s"] > 0 and r["batch"] == 2
    assert r["hbm_tok_s_ceiling"] > 0 and r["prefill"] == 16


def test_ladder_baselines_never_ratchet_down(tmp_path):
    """A slow chip-day must not lower a stored rung: only a >= rate
    overwrites (a lowered bar would mask the next real regression)."""
    fast = {"gpt_medium_tpu_o2": {"tok_s": 49000.0, "batch": 4}}
    slow = {"gpt_medium_tpu_o2": {"tok_s": 43000.0, "batch": 4}}
    faster = {"gpt_medium_tpu_o2": {"tok_s": 50500.0, "batch": 4}}
    bench.update_ladder_baselines(str(tmp_path), fast)
    bench.update_ladder_baselines(str(tmp_path), slow)
    doc = bench.load_ladder_baselines(str(tmp_path))
    assert doc["gpt_medium_tpu_o2"]["4"]["tok_s"] == 49000.0
    bench.update_ladder_baselines(str(tmp_path), faster)
    doc = bench.load_ladder_baselines(str(tmp_path))
    assert doc["gpt_medium_tpu_o2"]["4"]["tok_s"] == 50500.0


def test_gate_exit_code_absolute_gates_fire_without_compare():
    """MFU-floor and A/B-sign gates are absolute: they fail the run even
    when no --compare baseline was given (CI without a BENCH_r*.json
    must not silently pass an efficiency regression)."""
    bad_mfu = {"ok": True, "mfu_floors": {"ok": False,
                                          "violations": ["resnet50_o2"]},
               "ab_failures": []}
    bad_ab = {"ok": True, "mfu_floors": {"ok": True},
              "ab_failures": ["gpt_small_tpu_serve_c8"]}
    clean = {"ok": True, "mfu_floors": {"ok": True}, "ab_failures": []}
    assert bench.gate_exit_code(bad_mfu, compare_given=False) == 2
    assert bench.gate_exit_code(bad_ab, compare_given=False) == 2
    assert bench.gate_exit_code(clean, compare_given=False) == 0
    # CPU rounds have no MFU record at all — never gated on it
    assert bench.gate_exit_code({"ok": True, "mfu_floors": None,
                                 "ab_failures": []},
                                compare_given=False) == 0


def test_gate_exit_code_delta_gate_stays_opt_in():
    """Throughput deltas fail the run only under --compare; the
    unreadable-baseline early-return shape (no regressions/deltas keys)
    must not crash the gate either way."""
    regressed = {"ok": False, "mfu_floors": {"ok": True},
                 "ab_failures": [], "regressions": ["gpt_small_o2"],
                 "deltas": {"gpt_small_o2": -0.2}}
    assert bench.gate_exit_code(regressed, compare_given=False) == 0
    assert bench.gate_exit_code(regressed, compare_given=True) == 2
    unreadable = {"baseline": "BENCH_r99.json", "ok": True,
                  "error": "baseline unreadable: no configs map",
                  "mfu_floors": {"ok": False, "violations": ["x"]},
                  "ab_failures": []}
    assert bench.gate_exit_code(unreadable, compare_given=True) == 2


# ---------------------------------------------------------------------------
# Round-6 floor hygiene: kernel floors surfaced in the gate, and the
# no-ratchet-down rule over every published floor table.

#: Frozen snapshots of the floor tables as committed in round 6.  The
#: erosion guard below compares the LIVE tables against these: raising a
#: floor updates the snapshot in the same commit (fine — gains ratchet
#: the bar up); LOWERING one without a BENCH_VARIANCE.json entry whose
#: recorded spread covers the drop fails this suite.  Deleting a floor
#: is erosion too.
MFU_FLOOR_SNAPSHOT_R06 = {
    "resnet50_o2": 0.30,
    "resnet50_o3": 0.30,
    "resnet50_s2d_o2": 0.32,
    "gpt_small_o2": 0.41,
    "bert_large_lamb_o2": 0.49,
    "gpt_small_tpu_heads_o2": 0.54,
    "bert_large_tpu_heads_lamb_o2": 0.59,
    "gpt_small_tpu_heads_L8192_o2": 0.55,
    "gpt_small_tpu_heads_L16384_o2": 0.51,
    "gpt_medium_tpu_o2": 0.58,
}
KERNEL_FLOOR_SNAPSHOT_R06 = {
    "fused_adam": 0.30,
    "lamb_stage1": 0.17,
    "lamb_stage2": 0.12,
    "mt_scale": 0.75,
    "mt_axpby": 0.80,
    "mt_sumsq": 0.63,
    "layernorm_fwd": 0.34,
    "layernorm_fwd_bwd": 0.51,
}


def _kernel_floors():
    sys.path.insert(0, str(REPO / "tools"))
    import kernel_bench
    return kernel_bench.KERNEL_FLOORS


def test_floors_never_erode_without_variance_evidence():
    """Every floor change must be accompanied by recorded variance
    (VERDICT r5 weak #1: floors lowered on soft days absorb real
    regressions; the band then does the load-bearing work the floor was
    supposed to do)."""
    variance = bench.load_variance(str(REPO))
    for name, old in MFU_FLOOR_SNAPSHOT_R06.items():
        new = bench.MFU_FLOORS.get(name)
        assert new is not None, f"floor for {name} deleted (erosion)"
        assert bench.floor_change_allowed(name, old, new, variance), (
            f"{name}: floor lowered {old} -> {new} without a "
            "BENCH_VARIANCE.json entry covering the drop — run "
            "tools/bench_variance.py on chip and commit the artifact")
    kfloors = _kernel_floors()
    for name, old in KERNEL_FLOOR_SNAPSHOT_R06.items():
        new = kfloors.get(name)
        assert new is not None, f"kernel floor for {name} deleted"
        assert bench.floor_change_allowed(name, old, new, variance,
                                          kind="kernel"), (
            f"{name}: kernel floor lowered {old} -> {new} without "
            "variance evidence")


def test_floor_change_allowed_rule():
    """The rule itself: raise always; lower only with a non-tiny
    variance entry whose rel_spread covers the drop."""
    assert bench.floor_change_allowed("x", 0.30, 0.31, None)
    assert not bench.floor_change_allowed("x", 0.30, 0.29, None)
    doc = {"entries": {"config:x": {"rel_spread": 0.05},
                       "kernel:k": {"rel_spread": 0.10}}}
    # -3.3% drop inside the recorded 5% spread: allowed
    assert bench.floor_change_allowed("x", 0.30, 0.29, doc)
    # -17% drop far beyond it: refused
    assert not bench.floor_change_allowed("x", 0.30, 0.25, doc)
    # kernel floors key the kernel: namespace
    assert bench.floor_change_allowed("k", 0.17, 0.16, doc, kind="kernel")
    assert not bench.floor_change_allowed("x", 0.30, 0.29, doc,
                                          kind="kernel")
    # the MFU sub-statistic wins for configs when recorded
    mfu_doc = {"entries": {"config:x": {"rel_spread": 0.20,
                                        "mfu": {"rel_spread": 0.01}}}}
    assert not bench.floor_change_allowed("x", 0.30, 0.29, mfu_doc)
    # tiny-smoke artifacts are not evidence
    assert not bench.floor_change_allowed(
        "x", 0.30, 0.29, {"tiny": True,
                          "entries": {"config:x": {"rel_spread": 0.9}}})


def test_gate_exit_code_kernel_floors_absolute():
    """A kernel-floor violation from the committed KERNELBENCH artifact
    fails the model bench too — the 2%-of-step kernel regression cannot
    hide behind a green model round."""
    bad = {"ok": True, "mfu_floors": {"ok": True},
           "kernel_floors": {"ok": False, "violations": ["fused_adam"]},
           "ab_failures": []}
    assert bench.gate_exit_code(bad, compare_given=False) == 2
    # no kernel artifact at all (fresh checkout): never gated on it
    assert bench.gate_exit_code({"ok": True, "mfu_floors": {"ok": True},
                                 "kernel_floors": None,
                                 "ab_failures": []},
                                compare_given=False) == 0


def test_check_kernel_floor_artifact_reads_committed_round():
    """The repo's newest committed KERNELBENCH_r*.json passes the
    published floors (floors sit at-or-below the measured values, the
    MFU_FLOORS convention) and unreadable artifacts never fail."""
    out = bench.check_kernel_floor_artifact(str(REPO))
    assert out is not None and out["ok"], out
    assert out["artifact"].startswith("KERNELBENCH_r")
    # unreadable artifact: recorded, never failing
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        (Path(d) / "KERNELBENCH_r07.json").write_text("{not json")
        broken = bench.check_kernel_floor_artifact(d)
        assert broken["ok"] and "error" in broken
        assert bench.check_kernel_floor_artifact(
            tempfile.gettempdir() + "/definitely_empty_dir_xyz") is None


def test_check_floor_calibration_fails_loud_on_unimportable_floors(
        monkeypatch):
    """An unimportable KERNEL_FLOORS table must fail the calibration
    gate, never silently run with the floor half of the check off
    (the fail-loud contract in the docstring)."""
    ok = bench.check_floor_calibration(str(REPO))
    assert ok["ok"], ok
    monkeypatch.setitem(sys.modules, "kernel_bench", None)
    broken = bench.check_floor_calibration(str(REPO))
    assert not broken["ok"]
    assert "KERNEL_FLOORS not audited" in broken["error"]


# ---------------------------------------------------------------------------
# Round-6 decode serving: the decode-bandwidth floors and the serve
# bench (ISSUE 6).

#: Frozen snapshot of the decode hbm_frac floors as committed in round
#: 6 (the r05 measured values, now that DECODE_DECOMPOSE_r01.json
#: explains the b8 number) — same erosion rule as every floor table.
DECODE_FLOOR_SNAPSHOT_R06 = {
    "gpt_small_tpu_decode_b1": 0.54,
    "gpt_small_tpu_decode_b8": 0.43,
}


def test_decode_floors_never_erode_without_variance_evidence():
    variance = bench.load_variance(str(REPO))
    for name, old in DECODE_FLOOR_SNAPSHOT_R06.items():
        new = bench.DECODE_FLOORS.get(name)
        assert new is not None, f"decode floor for {name} deleted"
        assert bench.floor_change_allowed(name, old, new, variance), (
            f"{name}: decode floor lowered {old} -> {new} without "
            "variance evidence")


def test_decode_floor_gate():
    """hbm_frac under floor*(1-band) trips; at/over passes; errored or
    absent configs are skipped (optional-config semantics); a floor
    above the roofline ceiling fails loudly."""
    ok = bench.check_decode_floors(
        {"gpt_small_tpu_decode_b8": {"hbm_frac": 0.43}})
    assert ok["ok"] and ok["checked"]["gpt_small_tpu_decode_b8"]["ok"]
    low = bench.check_decode_floors(
        {"gpt_small_tpu_decode_b8": {"hbm_frac": 0.39}})
    assert not low["ok"]
    assert low["violations"] == ["gpt_small_tpu_decode_b8"]
    skipped = bench.check_decode_floors(
        {"gpt_small_tpu_decode_b8": {"error": "OOM"}})
    assert skipped["ok"] and not skipped["checked"]
    # hbm_frac of exactly 0.0 is a catastrophic regression, not a
    # missing value — it must TRIP the gate, never falsy-skip it
    zero = bench.check_decode_floors(
        {"gpt_small_tpu_decode_b8": {"hbm_frac": 0.0}})
    assert not zero["ok"]
    assert zero["violations"] == ["gpt_small_tpu_decode_b8"]
    try:
        bench.DECODE_FLOORS["__impossible"] = 1.2
        bad = bench.check_decode_floors({})
        assert not bad["ok"] and "__impossible" in bad["violations"]
    finally:
        del bench.DECODE_FLOORS["__impossible"]


def test_gate_exit_code_includes_decode_floors():
    bad = {"ok": True, "mfu_floors": {"ok": True},
           "decode_floors": {"ok": False,
                             "violations": ["gpt_small_tpu_decode_b8"]},
           "ab_failures": []}
    assert bench.gate_exit_code(bad, compare_given=False) == 2
    # CPU rounds record no decode gate — never gated on it
    assert bench.gate_exit_code(
        {"ok": True, "mfu_floors": None, "decode_floors": None,
         "ab_failures": []}, compare_given=False) == 0


def test_bench_generate_reports_roofline_bound():
    """The decode ceiling now rides the shared roofline machinery:
    the record names the binding resource (bandwidth at decode
    intensity)."""
    r = bench.bench_generate(batch=2, prefill=16, new_tokens=8,
                             warmup=0, iters=1, peak=None, tiny=True)
    assert r["bound"] == "bandwidth"
    assert r["hbm_tok_s_ceiling"] > 0 and 0 <= r["hbm_frac"]


def test_bench_generate_kv8_byte_model_derives_higher_ceiling():
    """The int8-KV config's ceiling comes from the int8 byte model
    through the SAME roofline_expectation call — never hand-written:
    at equal shapes the kv8 ceiling strictly exceeds the dense one
    (cache term halves, plus 4 bytes/position/layer of scales), and
    the record carries the byte-model evidence."""
    dense = bench.bench_generate(batch=2, prefill=16, new_tokens=8,
                                 warmup=0, iters=1, peak=None, tiny=True)
    kv8 = bench.bench_generate(batch=2, prefill=16, new_tokens=8,
                               warmup=0, iters=1, peak=None, tiny=True,
                               kv_dtype="int8")
    assert kv8["kv_dtype"] == "int8"
    assert kv8["hbm_tok_s_ceiling"] > dense["hbm_tok_s_ceiling"]
    # byte model: 1 byte/elem per cache + 4-byte scales per position
    from apex_tpu.models.gpt import gpt_tiny
    cfg = gpt_tiny()
    m = 16 + 8
    want = (2 * cfg.num_layers * 2 * m * cfg.hidden_size * 1
            + 2 * cfg.num_layers * 2 * m * 4)
    assert kv8["cache_bytes_per_step"] == want
    assert kv8["bound"] == "bandwidth"


def test_decode_floors_carry_kv8_config():
    """The committed kv8 floor exists (CPU-smoke-seeded,
    catastrophic-regression guard; on-chip ratchet is the next driver
    round's job) and sits under the roofline like every floor."""
    assert 0 < bench.DECODE_FLOORS["gpt_small_tpu_decode_kv8"] <= 1.0


def test_bench_serve_tiny_cpu():
    """The serve bench path end-to-end on CPU: offered-load sweep
    c1 -> c_slots, decode-step p50/p99, the latency-tail ab gate, and
    exactly one decode trace across the whole stream."""
    r = bench.bench_serve(warmup=1, iters=1, peak=None, tiny=True)
    assert r["tok_s"] > 0 and r["ab_ok"] is True
    assert r["p99_ms"] >= r["p50_ms"] > 0
    levels = r["offered_load"]
    assert set(levels) == {"c1", "c2"}
    assert all(v["retraces"] == 1 for v in levels.values())


@pytest.mark.slow
def test_bench_serve_spec_tiny_cpu():
    """The speculative serve A/B end-to-end on CPU: the briefly
    trained model gives the layer-skip draft real margins, the same
    stream runs through both arms, and the gate — tokens per decode
    dispatch strictly greater with spec on, retraces == 1 both arms —
    holds (ab_ok rides gate_exit_code's absolute ab_failures lane
    like every other sign gate)."""
    r = bench.bench_serve_spec(warmup=1, iters=1, peak=None, tiny=True)
    assert r["ab_ok"] is True
    assert r["spec"]["tokens_per_step"] > r["baseline"]["tokens_per_step"]
    assert r["spec"]["retraces"] == 1 and r["baseline"]["retraces"] == 1
    assert r["spec"]["acceptance_rate"] > 0
    assert r["tok_s"] > 0 and r["p99_ms"] >= r["p50_ms"] > 0


def test_merged_decode_quantile_unions_replica_windows():
    """The fleet percentile is the union of the replicas' histogram
    windows through the SAME Histogram interpolation — two replicas
    with disjoint latency populations must merge to the population
    quantile, and pre-mark observations stay outside the window.
    (bench's private ``_merged_decode_quantile`` is GONE — this is
    the one public copy, ``apex_tpu.obs.fleet.merged_quantile``,
    which bench_serve_disagg now imports.)"""
    from apex_tpu.obs.fleet import merged_quantile
    from apex_tpu.obs.metrics import Histogram, Registry

    assert not hasattr(bench, "_merged_decode_quantile")
    reg = Registry()
    h1, h2 = Histogram(reg, "a"), Histogram(reg, "b")
    h1.observe(10.0)                    # pre-window (compile step)
    m1, m2 = h1.state(), h2.state()
    for _ in range(50):
        h1.observe(0.001)
        h2.observe(0.004)
    merged_p50 = merged_quantile([(h1, m1), (h2, m2)], 0.5)
    merged_p99 = merged_quantile([(h1, m1), (h2, m2)], 0.99)
    # half the union sits near 1 ms, the slow half near 4 ms: p50
    # lands between the two modes, p99 inside the slow replica's
    # bucket — and far under the excluded 10 s compile outlier
    assert 0.0005 < merged_p50 < 0.004
    assert 0.002 < merged_p99 < 0.01
    # stale-max guard: an overflow-bucket observation AFTER the mark
    # must interpolate toward the window's own max, never toward the
    # excluded pre-mark outlier — merged and single-histogram math
    # must agree exactly (h3's 100 s compile vs a 30 s window step)
    h3 = Histogram(reg, "c")
    h3.observe(100.0)
    m3 = h3.state()
    h3.observe(30.0)
    merged = merged_quantile([(h3, m3)], 0.99)
    assert merged == h3.quantile(0.99, since=m3)
    assert merged <= 30.0


@pytest.mark.slow
def test_bench_serve_disagg_tiny_cpu():
    """The disaggregated A/B path end-to-end on CPU: both arms serve
    the same stream, percentiles come from the engines' own
    histograms, the topology records disjoint slices, and every
    program keeps one trace.  (The committed SERVE_DISAGG artifact —
    generated by tools/serve_disagg.py at the full c16 shape — is the
    gated instance; this is the code-path smoke.)"""
    r = bench.bench_serve_disagg(warmup=1, iters=1, peak=None,
                                 n_replicas=2, slots_per_replica=2,
                                 prefill=16, new_tokens=8, tiny=True)
    assert "skipped" not in r, r
    assert r["mono"]["retraces"] == 1
    assert r["disagg"]["retraces"] == [1, 1]
    assert r["disagg"]["shipments"] == r["batch"] == 4
    assert r["disagg"]["kv_transfer_bytes"] > 0
    flat = r["topology"]["prefill"] + [
        d for rep in r["topology"]["decode"] for d in rep]
    assert len(flat) == len(set(flat))
    assert r["p99_ms"] >= r["p50_ms"] > 0


# ---------------------------------------------------------------------------
# ISSUE 14: statistical floor bands derived from recorded variance
# ---------------------------------------------------------------------------

def _variance_doc(entries, tiny=False):
    return {"platform": "tpu", "tiny": tiny, "entries": entries}


def test_derive_floor_bands_formula_and_ratchet():
    """floor = mean - k*std where evidence qualifies; hand floors are
    the frozen fallback; the no-ratchet-down rule applies to DERIVED
    candidates too (a candidate below the hand floor beyond the
    recorded spread is refused)."""
    hand = {"cfg": 0.40}
    # no artifact / tiny artifact / missing entry / OFF-CHIP artifact
    # (a full-size CPU run says nothing about TPU floors): hand stands
    cpu_doc = dict(_variance_doc({"config:cfg": {
        "mfu": {"n": 9, "mean": 0.10, "std": 0.01},
        "rel_spread": 0.9}}), platform="cpu")
    for doc in (None, _variance_doc({}, tiny=True), _variance_doc({}),
                cpu_doc):
        bands = bench.derive_floor_bands(hand, doc, kind="config",
                                         stat="mfu")
        assert bands["cfg"] == {"floor": 0.40, "source": "hand",
                                "provisional": False}
    # qualifying entry ABOVE the hand floor: derived, ratchets up
    doc = _variance_doc({"config:cfg": {
        "mfu": {"n": 5, "mean": 0.46, "std": 0.01},
        "rel_spread": 0.05}})
    rec = bench.derive_floor_bands(hand, doc, kind="config",
                                   stat="mfu")["cfg"]
    assert rec["source"] == "derived" and rec["floor"] == 0.44
    # candidate below the hand floor but INSIDE the recorded spread:
    # the statistical floor may honestly sit lower
    doc = _variance_doc({"config:cfg": {
        "mfu": {"n": 5, "mean": 0.40, "std": 0.005,
                "rel_spread": 0.06}, "rel_spread": 0.06}})
    rec = bench.derive_floor_bands(hand, doc, kind="config",
                                   stat="mfu")["cfg"]
    assert rec["source"] == "derived" and rec["floor"] == 0.39
    # candidate far below beyond the spread: REFUSED (no-ratchet-down)
    doc = _variance_doc({"config:cfg": {
        "mfu": {"n": 5, "mean": 0.30, "std": 0.01,
                "rel_spread": 0.02}, "rel_spread": 0.02}})
    rec = bench.derive_floor_bands(hand, doc, kind="config",
                                   stat="mfu")["cfg"]
    assert rec["source"] == "hand" and rec["floor"] == 0.40
    assert "no-ratchet-down" in rec["reason"]
    # insufficient samples: hand floor, reason recorded
    doc = _variance_doc({"config:cfg": {
        "mfu": {"n": 2, "mean": 0.46, "std": 0.01}}})
    rec = bench.derive_floor_bands(hand, doc, kind="config",
                                   stat="mfu")["cfg"]
    assert rec["source"] == "hand" and "insufficient" in rec["reason"]
    # the drop is judged by the spread of the SAME statistic the
    # floor gates: a wide spread on a DIFFERENT metric (here the
    # rate) is not evidence about hbm_frac — refused
    doc = _variance_doc({"config:cfg": {
        "rel_spread": 0.50,        # wide rate spread
        "hbm_frac": {"n": 5, "mean": 0.30, "std": 0.005,
                     "rel_spread": 0.02}}})
    rec = bench.derive_floor_bands(hand, doc, kind="config",
                                   stat="hbm_frac")["cfg"]
    assert rec["source"] == "hand" and "no-ratchet-down" in \
        rec["reason"]
    assert not bench.floor_change_allowed("cfg", 0.40, 0.30, doc,
                                          stat="hbm_frac")
    assert bench.floor_change_allowed("cfg", 0.40, 0.395, doc,
                                      stat="hbm_frac")


def test_frozen_fallback_no_floor_loosened_by_committed_artifact():
    """The acceptance bar: with the COMMITTED BENCH_VARIANCE_r*.json
    (a tiny CPU smoke until a chip round lands), every effective floor
    equals today's hand value exactly — consulting the artifact can
    never loosen a gate silently."""
    kfloors = _kernel_floors()
    for table, kind, stat in (
            (bench.MFU_FLOORS, "config", "mfu"),
            (bench.DECODE_FLOORS, "config", "hbm_frac"),
            (kfloors, "kernel", "roofline_frac")):
        eff, bands = bench.effective_floors(table, str(REPO),
                                            kind=kind, stat=stat)
        assert eff == dict(table), (kind, eff)
        assert all(b["source"] == "hand" for b in bands.values())


def test_gates_consult_derived_bands(monkeypatch, tmp_path):
    """check_mfu_floors/check_decode_floors with a search_dir apply
    the DERIVED floor (here: ratcheted up by synthetic evidence) and
    record its source — the 'demonstrably consult' bar."""
    import json as _json
    doc = _variance_doc({"config:gpt_small_o2": {
        "mfu": {"n": 6, "mean": 0.50, "std": 0.01},
        "rel_spread": 0.03}})
    (tmp_path / "BENCH_VARIANCE_r05.json").write_text(_json.dumps(doc))
    # measured 0.45: passes the hand floor 0.41, FAILS the derived
    # 0.48 gate (0.456) — the consultation is observable
    out = bench.check_mfu_floors({"gpt_small_o2": {"mfu": 0.45}},
                                 search_dir=str(tmp_path))
    assert out["checked"]["gpt_small_o2"]["source"] == "derived"
    assert out["checked"]["gpt_small_o2"]["floor"] == 0.48
    assert out["violations"] == ["gpt_small_o2"]
    # without the artifact the same measurement passes the hand floor
    ok = bench.check_mfu_floors({"gpt_small_o2": {"mfu": 0.45}})
    assert ok["ok"] and ok["checked"]["gpt_small_o2"]["source"] == "hand"


def test_kv8_floor_marked_provisional_in_gate_record():
    """The CPU-smoke-seeded kv8 entry is reported as UNMEASURED: the
    decode gate record and check_floor_calibration both name it
    provisional instead of passing it off as a floor."""
    assert "gpt_small_tpu_decode_kv8" in bench.PROVISIONAL_FLOORS
    out = bench.check_decode_floors(
        {"gpt_small_tpu_decode_kv8": {"hbm_frac": 0.002}})
    assert out["provisional"] == ["gpt_small_tpu_decode_kv8"]
    assert out["checked"]["gpt_small_tpu_decode_kv8"]["provisional"] \
        is True
    cal = bench.check_floor_calibration(str(REPO))
    assert cal["ok"], cal
    assert "gpt_small_tpu_decode_kv8" in cal["provisional_floors"]
    # measured floors are NOT provisional
    ok = bench.check_decode_floors(
        {"gpt_small_tpu_decode_b8": {"hbm_frac": 0.43}})
    assert "provisional" not in ok["checked"]["gpt_small_tpu_decode_b8"]
