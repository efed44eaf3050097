"""EXPORT_r*.json — schema for the committed AOT-export artifact.

``tools/aot_export.py`` writes one of these per round: the export
pipeline's acceptance evidence — per-lane cache keys, the lint
verdicts that gated each executable into (or out of) the
content-addressed cache, load-vs-compile wall clock, and the
round-trip bitwise check.  Like MEMLINT/PRECLINT/OBS records the
artifact is gate memory: ``tools/gate_hygiene.py`` validates every
committed ``EXPORT_r*.json`` against this schema, and the schema
ENFORCES the export invariants — an exported lane must carry a clean
gating lint verdict and a passing bitwise round trip (a contradictory
verdict is schema-invalid, not just wrong), a refused lane must name
the documented finding id that refused it, and the serve cold-start
block's ``ok`` must agree with its own numbers against the
``load_ratio <= COLD_START_RATIO_MAX`` bar.

This module is deliberately **stdlib-only** (no jax import):
``gate_hygiene`` loads it directly by file path the same way it loads
``analysis/memlint.py``.

Document shape::

    {
      "round": 1,
      "platform": "cpu",
      "versions": {"jax": "0.4.37", ...},
      "cache": {"dir": ".aot_cache", "entries": 3},
      "lanes": {
        "mlp_o1_train": {
          "export_ok": true,
          "cache_key": "<64 hex>", "module_sha256": "<64 hex>",
          "lint": {"ok": true, "passes": [...], "counts": {...}},
          "compile_s": 0.31, "load_s": 0.01, "load_ratio": 0.04,
          "bitwise_equal": true},
        "seeded_io_callback": {
          "export_ok": false,
          "refused": "export-host-callback",
          "lint": {"ok": false, ...}},
        ...
      },
      "cold_start": {"lane": "serve_step", "compile_s": ..., "load_s": ...,
                     "load_ratio": ..., "budget": 0.5, "ok": true}
    }
"""

from __future__ import annotations

import json
import re
from typing import List

#: the absolute cold-start gate: loading the serve lane from the cache
#: must cost at most this fraction of compiling it on the same host —
#: otherwise the cache is decoration, not a cold-start fix.
COLD_START_RATIO_MAX = 0.5

_HEX64 = re.compile(r"^[0-9a-f]{64}$")


def _check_lint(lane: str, lint, problems: List[str]) -> "bool | None":
    """Validate a lane's embedded lint block; returns its ok flag."""
    if not isinstance(lint, dict) or not isinstance(lint.get("ok"), bool):
        problems.append(f"lane {lane!r}: missing/invalid 'lint' block "
                        f"with boolean 'ok'")
        return None
    if not isinstance(lint.get("counts"), dict):
        problems.append(f"lane {lane!r}: lint block missing 'counts'")
    return lint["ok"]


def validate_export(doc) -> List[str]:
    """Problems with one parsed EXPORT document (empty = valid)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    if not isinstance(doc.get("round"), int):
        problems.append("missing/invalid 'round' (int)")
    if not isinstance(doc.get("platform"), str):
        problems.append("missing/invalid 'platform' (str)")
    versions = doc.get("versions")
    if not isinstance(versions, dict) or \
            not isinstance(versions.get("jax"), str):
        problems.append("missing/invalid 'versions' (object with a "
                        "'jax' version string)")

    lanes = doc.get("lanes")
    if not isinstance(lanes, dict) or not lanes:
        problems.append("missing/empty 'lanes' object")
        lanes = {}
    for lane, rec in lanes.items():
        if not isinstance(rec, dict):
            problems.append(f"lane {lane!r}: not an object")
            continue
        export_ok = rec.get("export_ok")
        if not isinstance(export_ok, bool):
            problems.append(f"lane {lane!r}: missing boolean "
                            f"'export_ok'")
            continue
        lint_ok = _check_lint(lane, rec.get("lint"), problems)
        if export_ok:
            for k in ("cache_key", "module_sha256"):
                if not (isinstance(rec.get(k), str)
                        and _HEX64.match(rec[k])):
                    problems.append(f"lane {lane!r}: missing/invalid "
                                    f"{k!r} (64-char sha256 hex)")
            if lint_ok is False:
                problems.append(
                    f"lane {lane!r}: contradictory verdict — "
                    f"export_ok with a FAILING gating lint report (an "
                    f"executable can only enter the cache clean)")
            if not (isinstance(rec.get("compile_s"), (int, float))
                    and rec["compile_s"] > 0):
                problems.append(f"lane {lane!r}: missing positive "
                                f"'compile_s'")
            if not (isinstance(rec.get("load_s"), (int, float))
                    and rec["load_s"] >= 0):
                problems.append(f"lane {lane!r}: missing "
                                f"non-negative 'load_s'")
            if rec.get("bitwise_equal") is not True:
                problems.append(
                    f"lane {lane!r}: contradictory verdict — "
                    f"export_ok without a passing bitwise round trip "
                    f"(reloaded outputs must equal the fresh "
                    f"compile's, bit for bit)")
        else:
            if not (isinstance(rec.get("refused"), str)
                    and rec["refused"]):
                problems.append(
                    f"lane {lane!r}: refused lane must name the "
                    f"documented finding id in 'refused'")
            if lint_ok is True and rec.get("refused") not in (
                    "export-compat-not-run",):
                problems.append(
                    f"lane {lane!r}: contradictory verdict — refused "
                    f"with a CLEAN gating lint report")

    cs = doc.get("cold_start")
    if not isinstance(cs, dict):
        problems.append("missing/invalid 'cold_start' object (the "
                        "serve-lane compile-vs-load numbers)")
    else:
        lane = cs.get("lane")
        if not isinstance(lane, str) or not lane:
            problems.append("cold_start: missing 'lane'")
        elif lane not in lanes:
            problems.append(f"cold_start: lane {lane!r} not among the "
                            f"document's lanes")
        for k in ("compile_s", "load_s", "load_ratio", "budget"):
            if not isinstance(cs.get(k), (int, float)):
                problems.append(f"cold_start: missing numeric {k!r}")
        if not isinstance(cs.get("ok"), bool):
            problems.append("cold_start: missing boolean 'ok'")
        elif all(isinstance(cs.get(k), (int, float))
                 for k in ("load_ratio", "budget")):
            implied = cs["load_ratio"] <= cs["budget"]
            if cs["ok"] is not implied:
                problems.append(
                    "cold_start: contradictory verdict — 'ok' "
                    "disagrees with load_ratio vs budget")
    return problems


def validate_export_file(path: str) -> List[str]:
    """Schema problems of one EXPORT_r*.json file (empty = valid)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"unreadable export JSON: {e}"]
    return validate_export(doc)
