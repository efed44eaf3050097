"""Units for tools/kernel_bench.py (the microbench itself runs on the
driver's chip): the per-kernel regression gate and the byte accounting.
"""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))

import kernel_bench as kb  # noqa: E402


def _write(tmp_path, kernels):
    p = tmp_path / "KERNELBENCH_r04.json"
    p.write_text(json.dumps({"kernels": kernels}))
    return str(p)


def test_compare_kernels_gates_slowdowns_only(tmp_path):
    prior = _write(tmp_path, {
        "fused_adam": {"ms_per_step": 1.0},
        "mt_scale": {"ms_per_step": 0.5},
        "lamb_stage1": {"ms_per_step": 2.0},
        "errored_before": {"error": "boom"},
    })
    verdict = kb.compare_kernels(prior, {
        "fused_adam": {"ms_per_step": 1.05},   # +5%: within variance
        "mt_scale": {"ms_per_step": 0.65},     # +30%: regression
        "lamb_stage1": {"ms_per_step": 1.2},   # faster: fine
        "errored_before": {"ms_per_step": 9.0},  # no prior time
        "brand_new": {"ms_per_step": 1.0},
    }, threshold=0.10)
    assert verdict["regressions"] == ["mt_scale"]
    assert not verdict["ok"]
    assert verdict["deltas"]["fused_adam"] == 0.05
    assert verdict["deltas"]["lamb_stage1"] == -0.4
    assert set(verdict["uncompared"]) == {"errored_before", "brand_new"}


def test_compare_kernels_unreadable_baseline_never_fails(tmp_path):
    bad = tmp_path / "KERNELBENCH_r99.json"
    bad.write_text("{not json")
    verdict = kb.compare_kernels(str(bad), {"a": {"ms_per_step": 1.0}})
    assert verdict["ok"] and "error" in verdict


def test_compare_kernels_refuses_mismatched_geometry(tmp_path):
    p = tmp_path / "KERNELBENCH_r04.json"
    p.write_text(json.dumps({
        "n_elements": 1 << 24, "ln_shape": [8192, 1024],
        "kernels": {"fused_adam": {"ms_per_step": 2.3}}}))
    # a 4x-larger current run must not read as a 4x regression
    verdict = kb.compare_kernels(
        str(p), {"fused_adam": {"ms_per_step": 9.8}}, 0.10,
        geometry={"n_elements": 1 << 26, "ln_shape": [1 << 17, 1024]})
    assert verdict["ok"] and "geometry mismatch" in verdict["error"]
    # matched geometry gates normally
    verdict = kb.compare_kernels(
        str(p), {"fused_adam": {"ms_per_step": 9.8}}, 0.10,
        geometry={"n_elements": 1 << 24, "ln_shape": [8192, 1024]})
    assert verdict["regressions"] == ["fused_adam"]


def test_byte_accounting_matches_docstring():
    n = 1 << 16
    assert kb.bench_fused_adam(n)[1] == 30.0 * n
    assert kb.bench_lamb_stage1(n)[1] == 28.0 * n
    assert kb.bench_lamb_stage2(n)[1] == 14.0 * n
    assert kb.bench_mt_scale(n)[1] == 8.0 * n
    assert kb.bench_mt_axpby(n)[1] == 12.0 * n
    assert kb.bench_mt_sumsq(n)[1] == 4.0 * n
    rows, hidden = 64, 512
    assert kb.bench_layernorm_fwd(rows, hidden)[1] == \
        4.0 * rows * hidden + 8.0 * rows


def test_tiny_suite_runs_everywhere():
    """End-to-end smoke at tiny shapes (interpret mode off-TPU): every
    kernel produces a timing record, none errors."""
    result = kb.run_suite(tiny=True)
    errs = {k: v["error"] for k, v in result["kernels"].items()
            if "error" in v}
    assert not errs, errs
    # tiny interpret-mode timings can degenerate to the clamp under
    # host contention (the difference quotient needs real device time);
    # 0.0 baselines are filtered by compare_kernels' truthiness check
    assert all(v["ms_per_step"] >= 0 for v in result["kernels"].values())


@pytest.mark.slow
def test_geometry_recorded_per_kernel():
    """Every timed record names the geometry it measured (device kind +
    n_elements are top-level; block shape per kernel — the ISSUE-2
    artifact contract)."""
    result = kb.run_suite(tiny=True)
    assert result["device_kind"] is not None and "n_elements" in result
    for name, rec in result["kernels"].items():
        assert "error" in rec or ("geometry" in rec and "iters" in rec), \
            (name, rec)
        if "geometry" in rec:
            g = rec["geometry"]
            assert g["block_rows"] >= 1 and g["grid"] >= 1, (name, g)


def test_autotune_sweeps_and_chooses(monkeypatch):
    """--autotune sweeps each retunable kernel's knob and the chosen
    value is the fastest swept candidate."""
    # deterministic fake timer: bigger blocks "faster", candidate 64 best
    def fake_time(build, iters, trials=3):
        return {8: 9.0, 32: 5.0, 64: 1.0, 128: 2.0, 256: 3.0,
                1: 9.0, 2: 5.0, 4: 3.0, 16: 2.5, 512: 4.0}.get(
                    fake_time.cand, 1.0) * 1e-3
    calls = {}
    real = {}

    def spy_fn(name, fn):
        def wrapped(*a, **kw):
            knob, _ = kb.AUTOTUNE_KNOBS[name]
            fake_time.cand = kw.get(knob) or 0
            calls.setdefault(name, []).append(kw.get(knob))
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(kb, "_time_scan", fake_time)
    for name in ("fused_adam", "lamb_stage1"):
        fn = getattr(kb, f"bench_{name}")
        real[name] = fn
        monkeypatch.setattr(kb, f"bench_{name}", spy_fn(name, fn))
    result = kb.run_suite(tiny=True, autotune=True)
    adam = result["kernels"]["fused_adam"]
    assert adam["autotune"]["chosen"] == {"block_rows": 64}
    assert set(adam["autotune"]["swept_ms"]) == \
        {str(c) for c in kb.AUTOTUNE_KNOBS["fused_adam"][1]}
    lamb = result["kernels"]["lamb_stage1"]
    assert lamb["autotune"]["chosen"] == {"chunks_per_block": 16}
    # final timing ran at the chosen knob (last call per kernel)
    assert calls["fused_adam"][-1] == 64


def test_autotune_refuses_lint_rejected_candidates(monkeypatch):
    """A knob candidate the Pallas sanitizer rejects is recorded as a
    ``lint_rejected`` dict entry — never timed, never chosen — even
    when it would have swept fastest (the export-gate treatment)."""
    # shrink the VMEM budget so block_rows=256 overflows the working
    # set while block_rows=8 still fits (budget read at call time)
    monkeypatch.setenv("APEX_TPU_VMEM_BUDGET_MB", "0.25")
    monkeypatch.setattr(kb, "AUTOTUNE_KNOBS",
                        {"fused_adam": ("block_rows", (8, 256))})
    # fake timer makes the REJECTED candidate look fastest: only the
    # lint gate can keep it out of the knob table
    def fake_time(build, iters, trials=3):
        return 1e-3
    monkeypatch.setattr(kb, "_time_scan", fake_time)
    result = kb.run_suite(tiny=True, autotune=True)
    auto = result["kernels"]["fused_adam"]["autotune"]
    assert auto["swept_ms"]["256"] ==         {"lint_rejected": ["pallas-vmem-overflow"]}
    assert isinstance(auto["swept_ms"]["8"], float)
    assert auto["chosen"] == {"block_rows": 8}


def test_kernel_floor_gate():
    floors = kb.KERNEL_FLOORS
    assert "fused_adam" in floors and "lamb_stage1" in floors
    # the r05 measured values pass their own floors (the gate fires on
    # future regressions, not retroactively)
    import json as _json
    r05 = _json.load(open(REPO / "KERNELBENCH_r05.json"))
    check = kb.check_kernel_floors(r05["kernels"])
    assert check["ok"], check
    # a real bandwidth loss fails
    check = kb.check_kernel_floors({"fused_adam": {"roofline_frac": 0.20}})
    assert not check["ok"] and check["violations"] == ["fused_adam"]
    # a gated kernel that ERRORED (stopped running at all) fails the
    # gate too — the floor must not fail open on the worst regression
    check = kb.check_kernel_floors({"fused_adam": {"error": "boom"}})
    assert not check["ok"] and check["errored"] == ["fused_adam"]
    # kernels absent from a partial map are merely not judged
    check = kb.check_kernel_floors({})
    assert check["ok"] and not check["checked"]


def test_assert_floors_exits_nonzero_on_violation(monkeypatch, tmp_path):
    """`--assert-floors` is a real gate: exit 2 on a violated kernel
    floor, 0 when clean, and never armed without the flag."""
    violating = {
        "platform": "tpu", "device_kind": "TPU v5 lite",
        "n_elements": 1 << 26, "ln_shape": [1 << 17, 1024],
        "hbm_gbps_peak": 819.0,
        "kernels": {"fused_adam": {"ms_per_step": 30.0, "gb_moved": 2.0,
                                   "gbps": 67.0, "roofline_frac": 0.08,
                                   "iters": 60}}}
    monkeypatch.setattr(kb, "run_suite",
                        lambda tiny=False, autotune=False: dict(violating))
    out = str(tmp_path / "KB.json")
    assert kb.main(["--out", out, "--assert-floors"]) == 2
    assert kb.main(["--out", out]) == 0   # unarmed: recorded only
    import json as _json
    assert not _json.load(open(out))["floors"]["ok"]
    # clean run passes the armed gate
    clean = dict(violating)
    clean["kernels"] = {"fused_adam": {"ms_per_step": 3.0, "gb_moved": 2.0,
                                       "gbps": 670.0,
                                       "roofline_frac": 0.82, "iters": 60}}
    monkeypatch.setattr(kb, "run_suite",
                        lambda tiny=False, autotune=False: dict(clean))
    assert kb.main(["--out", out, "--assert-floors"]) == 0


def test_floors_skip_off_tpu(monkeypatch, tmp_path):
    """Off-chip (CPU smoke) roofline fractions are meaningless: the
    floors block records skipped and --assert-floors never fires."""
    cpu = {"platform": "cpu", "device_kind": "", "n_elements": 1 << 16,
           "ln_shape": [64, 512], "hbm_gbps_peak": 819.0,
           "kernels": {"fused_adam": {"ms_per_step": 1.0,
                                      "roofline_frac": 0.0001}}}
    monkeypatch.setattr(kb, "run_suite",
                        lambda tiny=False, autotune=False: dict(cpu))
    out = str(tmp_path / "KB.json")
    assert kb.main(["--out", out, "--tiny", "--assert-floors"]) == 0
    import json as _json
    doc = _json.load(open(out))
    assert doc["floors"]["ok"] and "skipped" in doc["floors"]


def test_check_kernel_floors_accepts_derived_override():
    """bench.py and main() pass variance-derived effective floors;
    the override is applied verbatim — a ratcheted-up derived floor
    fails a measurement the hand floor would pass."""
    measured = {"fused_adam": {"roofline_frac": 0.32}}
    assert kb.check_kernel_floors(measured)["ok"]          # hand 0.30
    out = kb.check_kernel_floors(measured,
                                 floors={"fused_adam": 0.36})
    assert not out["ok"] and out["violations"] == ["fused_adam"]
    assert out["checked"]["fused_adam"]["floor"] == 0.36


def test_effective_kernel_floors_frozen_fallback():
    """With the committed (tiny) variance artifact, the effective
    kernel floors equal the published hand table — nothing loosened —
    and every source records 'hand'."""
    floors, bands = kb.effective_kernel_floors()
    assert floors == dict(kb.KERNEL_FLOORS)
    assert all(b["source"] == "hand" for b in bands.values())
