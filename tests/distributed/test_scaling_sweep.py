"""Pins the scaling-sweep law layer (``tools/scaling_sweep.py``).

Two tiers:

- Pure-unit: ``check_laws`` on canned audit records — the law table
  (which collective, which growth function, which tolerance) cannot
  drift without failing here.  A synthetic violation of each law class
  (const broken, linear broken) must be caught.
- Integration (slow): one real child at world 8 in-process is already
  covered by the dryrun tests; here a REAL subprocess child at world 16
  verifies the scaled topologies compile/execute and that the audits
  equal the world-8 dryrun values for every const-law collective — the
  empirical anchor for "per-device volume independent of world size".

The full 8-64 sweep (including ``gradient_predivide_factor`` parity at
world 64) runs via ``python tools/scaling_sweep.py`` and is recorded in
``SCALING_SWEEP.json`` each round.
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))

from scaling_sweep import (  # noqa: E402
    RECORD_TAG, check_laws, expert_alltoall_scale, sweep_topology,
)

#: the world-8 audits (== MULTICHIP_SLICES dryrun values for the shared
#: topologies), used as the canned baseline for the law-layer units
BASE = {
    "dp_syncbn": {"all-reduce": {"count": 28, "bytes": 26456}},
    "dp_sp_ring": {"collective-permute": {"count": 5, "bytes": 8208},
                   "all-reduce": {"count": 3, "bytes": 331020}},
    "dp_tp_pjit": {"all-reduce": {"count": 3, "bytes": 2310}},
    "pipeline": {"collective-permute": {"count": 2, "bytes": 256},
                 "all-reduce": {"count": 3, "bytes": 1032}},
    "expert": {"all-reduce": {"count": 4, "bytes": 528},
               "all-to-all": {"count": 4, "bytes": 12800}},
    "fsdp": {"all-gather": {"count": 1, "bytes": 1024},
             "all-reduce": {"count": 2, "bytes": 1026}},
    "dp_tp_sp_3d": {"collective-permute": {"count": 5, "bytes": 4112},
                    "all-reduce": {"count": 6, "bytes": 14348}},
}

CONST_KINDS = [
    ("dp_syncbn", "all-reduce"),
    ("dp_sp_ring", "collective-permute"),
    ("dp_sp_ring", "all-reduce"),
    ("dp_tp_pjit", "all-reduce"),
    ("pipeline", "collective-permute"),
    ("dp_tp_sp_3d", "collective-permute"),
    ("dp_tp_sp_3d", "all-reduce"),
]


def _records(n, *, mutate=None):
    recs = {}
    for name, coll in BASE.items():
        c = {k: dict(v) for k, v in coll.items()}
        # the statically-growing laws: fsdp's compute all-gather
        # (linear in params) and the expert all-to-all's slot of room
        # rows for every rank (linear in the ranks)
        if name == "fsdp":
            c["all-gather"]["bytes"] = 1024 * n // 8
            c["all-reduce"]["bytes"] = 1026 * n // 8
        if name == "expert":
            c["all-to-all"]["bytes"] = int(
                12800 * expert_alltoall_scale(n) / expert_alltoall_scale(8))
        recs[name] = {"name": name, "ok": True, "collectives": c, "n": n}
    if mutate:
        mutate(recs)
    return recs


def _by_n(ns=(8, 16, 32, 64), mutate_at=None, mutate=None):
    return {n: _records(n, mutate=mutate if n == mutate_at else None)
            for n in ns}


def test_all_laws_pass_on_lawful_series():
    laws = check_laws(_by_n())
    assert laws, "law table is empty"
    failed = [lw for lw in laws if not lw["ok"]]
    assert not failed, failed


def test_const_law_catches_growth():
    # a DP implementation whose per-device all-reduce grows with world
    # size is the classic non-scalable bug — the law must fire
    def grow(recs):
        recs["dp_syncbn"]["collectives"]["all-reduce"]["bytes"] *= 2

    laws = check_laws(_by_n(mutate_at=64, mutate=grow))
    bad = [lw for lw in laws
           if lw["slice"] == "dp_syncbn" and not lw["ok"]]
    assert bad, "doubled world-64 DP all-reduce not caught"


def test_linear_law_catches_flatline():
    # an fsdp whose all-gather STOPS growing would mean it no longer
    # reconstitutes the full parameter — also a bug
    def flat(recs):
        recs["fsdp"]["collectives"]["all-gather"]["bytes"] = 1024

    laws = check_laws(_by_n(mutate_at=64, mutate=flat))
    bad = [lw for lw in laws if lw["slice"] == "fsdp" and not lw["ok"]]
    assert bad, "flat world-64 fsdp all-gather not caught"


def test_failed_slice_fails_its_laws():
    def broke(recs):
        recs["expert"]["ok"] = False

    laws = check_laws(_by_n(mutate_at=32, mutate=broke))
    bad = [lw for lw in laws
           if lw["slice"] == "expert" and not lw["ok"]]
    assert bad, "failed slice record passed its law"


def test_expert_exchange_room_formula():
    # ranks * room, room = T_local * min(k, e_local) = 16: linear in n
    assert expert_alltoall_scale(8) == 128.0
    assert expert_alltoall_scale(16) == 256.0
    assert expert_alltoall_scale(32) == 512.0
    assert expert_alltoall_scale(64) == 1024.0
    # the sweep's own numbers at worlds 8 and 16: 12800 and 25600 bytes
    # (rows and their expert ids out, results back) — a layout that
    # silently doubled the volume at one world would fail the law
    def wrong(recs):
        recs["expert"]["collectives"]["all-to-all"]["bytes"] *= 2

    laws = check_laws(_by_n(mutate_at=16, mutate=wrong))
    bad = [lw for lw in laws
           if lw["slice"] == "expert" and not lw["ok"]]
    assert bad, "doubled world-16 expert all-to-all not caught"


def test_derived_executed_volumes_scale():
    laws = {(lw["slice"], lw["law"]): lw for lw in check_laws(_by_n())}
    ring = laws[("dp_sp_ring", "ring executed volume ~ sp")]
    # derived = static x sp: sp doubles 2->4->8->16 across the sweep
    s = ring["series"]
    assert s["16"]["bytes"] == 2 * s["8"]["bytes"]
    assert s["64"]["bytes"] == 8 * s["8"]["bytes"]
    pipe = laws[("pipeline", "pipe executed volume ~ 2S-1")]
    assert pipe["series"]["64"]["bytes"] == 256 * (2 * 64 - 1)


@pytest.mark.slow
def test_world16_child_matches_const_laws():
    """Real subprocess at world 16: scaled topologies (sp=4, tp=4,
    16-stage pipeline) compile, execute, and audit byte-identical to the
    world-8 baseline for every const-law collective."""
    p = subprocess.run(
        [sys.executable, str(REPO / "tools" / "scaling_sweep.py"),
         "--child", "16"],
        capture_output=True, text=True, timeout=900, cwd=str(REPO))
    recs = {json.loads(line[len(RECORD_TAG):])["name"]:
            json.loads(line[len(RECORD_TAG):])
            for line in p.stdout.splitlines()
            if line.startswith(RECORD_TAG)}
    assert recs, f"no records; stderr tail: {p.stderr[-500:]}"
    failed = [r["name"] for r in recs.values() if not r["ok"]]
    assert not failed, (failed, [recs[f].get("error") for f in failed])
    assert sweep_topology(16) == {"sp": 4, "tp": 4, "stages": 16}
    for name, kind in CONST_KINDS:
        got = recs[name]["collectives"][kind]["bytes"]
        want = BASE[name][kind]["bytes"]
        assert got == want, (name, kind, got, want)
    # and the linear anchors: fsdp all-gather and the expert exchange
    # exactly double
    assert recs["fsdp"]["collectives"]["all-gather"]["bytes"] == 2048
    assert recs["expert"]["collectives"]["all-to-all"]["bytes"] == 25600
