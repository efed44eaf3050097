"""Run the hardware-gated test selection on the chip and write a
machine-readable log of it.

Usage:  python tools/onchip_run.py [--out chiprun_out/onchip_pytest.json]

Selects every test that skips off-chip (Mosaic-compiled Pallas kernels,
pallas-under-shard_map, AOT layout regressions) plus the kernel fuzz
tiers, whose Pallas paths run in interpret mode everywhere else, and
runs them in one child process with ``APEX_TPU_TEST_PLATFORM=tpu``.  One
process holds the chip at a time: the child runs first, and this
process touches JAX only after it has exited.
"""

import argparse
import json
import os
import subprocess
import sys
import time
import xml.etree.ElementTree as ET
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

#: the on-chip selection: hardware-gated tests + the fuzz suites whose
#: pallas paths run interpret-mode everywhere else
SELECTION = [
    "tests/l0/test_fused_lamb.py",
    "tests/l0/test_flash_attention.py",
    # production head-major layout pins (bhld dispatch, rope MXU
    # spelling, head-major projections) — the experimental flash_mh /
    # conv1x1 kernels keep ONE numerics pin each (VERDICT r3 #8) so
    # drift is caught without spending chip minutes on shelf inventory
    "tests/l0/test_flash_mh.py::test_bhld_layout_matches_blhd",
    "tests/l0/test_flash_mh.py::test_attention_dispatcher_bhld_routes_and_falls_back",
    "tests/l0/test_flash_mh.py::test_bhld_cross_attention_falls_back",
    "tests/l0/test_flash_mh.py::test_rope_mxu_matches_concat_spelling",
    "tests/l0/test_flash_mh.py::test_head_major_projections_match_dense_split",
    "tests/l0/test_flash_mh.py::test_mh_forward_matches_reference[True]",
    # KV-cached generation vs the naive full-forward oracle (the two
    # cheapest cases: the naive oracle recompiles per length)
    "tests/l1/test_generate.py::test_single_token_decode",
    "tests/l1/test_generate.py::test_temperature_sampling_deterministic_and_varied",
    "tests/l0/test_conv1x1.py::test_bwd_matches_lax_transpose[2-8-64-256]",
    # parked flat-packed finite check: one Mosaic numerics pin
    "tests/l0/test_scaler.py::TestAllFinitePacked::test_mixed_dtype_groups",
    "tests/l0/test_multi_tensor.py",
    "tests/l0/test_fused_adam.py",
    # cross-commit numerical drift gate on the hardware platform
    # (VERDICT r2 item 4a: the stored-baseline axis of the reference's
    # tests/L1/common/compare.py, on the platform that matters)
    "tests/l1/test_golden_digests.py",
    "tests/distributed/test_ring_attention.py::test_ring_flash_kernel_on_tpu",
    "tests/distributed/test_onchip_pallas_shardmap.py",
    # what changed since the last on-chip round: the LayerNorm and
    # streaming-kernel geometry, and the serve engine
    "tests/l0/test_fused_layer_norm.py",
    "tests/l0/test_kernel_geometry.py",
    # the serve engine's platform-independent contracts.  Its four
    # bitwise-equal-to-solo tests are a CPU-tier contract and are not
    # here: on the chip solo generate() prefills through the flash
    # kernel and the engine through the paged einsum, and on their
    # random-init gpt_tiny every argmax is a near-tie (PR 21 chip run:
    # all four diverge at the first token).  The on-chip form of that
    # contract is chip_smoke.py's serve phase, on a trained model.
    "tests/l0/test_serve_engine.py::test_decode_step_has_no_host_sync_or_retrace_hazard",
    "tests/l0/test_serve_engine.py::test_submit_validation",
    "tests/l0/test_serve_engine.py::test_sample_tokens_greedy_and_topk1_agree",
    "tests/l0/test_serve_engine.py::test_sample_tokens_topk_topp_restrict_support",
    "tests/l0/test_serve_engine.py::test_sample_tokens_chains_keys",
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out",
                    default=str(REPO / "chiprun_out" / "onchip_pytest.json"))
    args = ap.parse_args(argv)
    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    xml_path = out_path.with_suffix(".junit.xml")
    env = dict(os.environ, APEX_TPU_TEST_PLATFORM="tpu")
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", *SELECTION, "-q",
         "-p", "no:cacheprovider", f"--junitxml={xml_path}"],
        cwd=str(REPO), env=env, capture_output=True, text=True,
        timeout=3600)
    wall = round(time.time() - t0, 1)

    tests = []
    counts = {"passed": 0, "failed": 0, "error": 0, "skipped": 0}
    if xml_path.exists():
        for case in ET.parse(xml_path).getroot().iter("testcase"):
            outcome, detail = "passed", None
            for tag in ("failure", "error", "skipped"):
                node = case.find(tag)
                if node is not None:
                    outcome = tag if tag != "failure" else "failed"
                    detail = (node.text or node.get("message")
                              or "")[-3000:]
                    break
            counts[outcome] += 1
            tests.append({
                "nodeid": f"{case.get('classname')}::{case.get('name')}",
                "outcome": outcome,
                "time_s": float(case.get("time", 0.0)),
                **({"detail": detail} if detail else {}),
            })

    # the child has exited and released the chip: say what it ran on
    import jax
    device = jax.devices()[0]
    out = {
        "artifact": "on-chip test run log",
        "platform": device.platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "jax": jax.__version__,
        "env": {"APEX_TPU_TEST_PLATFORM": "tpu"},
        "selection": SELECTION,
        "wall_s": wall,
        "rc": proc.returncode,
        "counts": counts,
        # skips count against ok: on hardware NOTHING in the selection
        # may skip — in particular the golden-digest drift gate
        # pytest.skip()s when no baseline exists for the reported
        # platform, and an all-skipped gate must not read as green
        "ok": proc.returncode == 0 and counts["failed"] == 0
              and counts["error"] == 0 and counts["skipped"] == 0
              and counts["passed"] > 0 and device.platform == "tpu",
        "date": time.strftime("%Y-%m-%d %H:%M:%S"),
        "tail": proc.stdout[-6000:],
        "tests": tests,
    }
    out_path.write_text(json.dumps(out, indent=1) + "\n")
    print(proc.stdout[-6000:])
    print(f"{out_path}: ok={out['ok']} {counts}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
