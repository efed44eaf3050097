"""Incident-record schema: the machine-readable artifact a failure leaves.

The r02 chip-lease wedge set the precedent:
when a run dies — or survives something that should have killed it — the
evidence goes into a JSON artifact with a fixed minimal shape, so the
next round (and ``tools/gate_hygiene.py``) can machine-check it instead
of re-reading prose.  This module is the single source of truth for that
shape; the resilience loop, the watchdog, and ``tools/chaos_run.py`` all
write through :func:`write_incident`, and gate hygiene validates every
committed ``INCIDENT_r*.json`` through :func:`validate_incident`.

Deliberately **stdlib-only** (no jax/numpy): ``tools/gate_hygiene.py``
loads this file directly via importlib so the hygiene CLI never pays the
jax import.

Schema (the r02 artifact is the reference instance):

- ``status``    (required, non-empty str) — e.g. ``"recovered"``,
  ``"preempted"``, ``"watchdog-timeout"``, ``"partial - ..."``;
- ``utc`` or ``date`` (required, non-empty str) — when it happened;
- evidence      (required) — a non-empty list of str/dict entries, either
  top-level ``"evidence"``, nested under ``"incident"``, or any key
  containing ``"evidence"`` (the r02 artifact uses both of the last two);
- ``metrics``   (optional) — a runtime-telemetry snapshot in the
  :meth:`apex_tpu.obs.metrics.Registry.snapshot` shape
  (``{"metrics": [{"name", "type", ...}, ...]}``): what the counters
  and gauges said when the incident fired.  The resilience loop embeds
  one automatically; records without it (the r02 wedge predates the
  obs layer) stay valid;
- ``flight``    (optional) — the flight-recorder tail in the
  :meth:`apex_tpu.obs.flight.FlightRecorder.dump` shape
  (``{"capacity": int, "dropped": int, "events": [{"ts": number,
  "kind": str, ...}, ...]}``): the last-N-events black box of what led
  to the incident, not just the end-state gauges.  The resilience loop
  and the disaggregated router's replica-death path embed one; records
  without it (the r02 wedge predates the recorder) stay valid;
- anything else is free-form context (``artifact``, ``summary``,
  ``harness``, ``mitigations_added``, ...).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Sequence

SCHEMA_DOC = "status:str, utc|date:str, *evidence*: non-empty list"


def utc_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def _evidence_lists(d: Dict[str, Any]) -> List[Any]:
    """Every value reachable under a key containing ``evidence`` —
    top-level or one dict level down (covers the r02 layout where the
    list lives at ``incident.evidence``)."""
    found = []
    for key, val in d.items():
        if "evidence" in str(key).lower():
            found.append(val)
        elif isinstance(val, dict):
            for k2, v2 in val.items():
                if "evidence" in str(k2).lower():
                    found.append(v2)
    return found


def validate_incident(obj: Any) -> List[str]:
    """Problems with ``obj`` as an incident record; ``[]`` when valid."""
    problems: List[str] = []
    if not isinstance(obj, dict):
        return [f"incident record must be a JSON object, got {type(obj).__name__}"]
    status = obj.get("status")
    if not (isinstance(status, str) and status.strip()):
        problems.append("missing/empty required field 'status' (str)")
    when = obj.get("utc") or obj.get("date")
    if not (isinstance(when, str) and when.strip()):
        problems.append("missing/empty required field 'utc' (or 'date')")
    ev_lists = _evidence_lists(obj)
    good = [e for e in ev_lists if isinstance(e, (list, tuple)) and len(e)]
    if not good:
        problems.append("no non-empty *evidence* list found (top-level or "
                        "nested one level, e.g. incident.evidence)")
    else:
        for lst in good:
            for i, entry in enumerate(lst):
                if not isinstance(entry, (str, dict)):
                    problems.append(
                        f"evidence[{i}] must be str or object, got "
                        f"{type(entry).__name__}")
    problems.extend(_validate_flight(obj.get("flight")))
    snap = obj.get("metrics")
    if snap is not None:
        rows = snap.get("metrics") if isinstance(snap, dict) else None
        if not isinstance(rows, list) or not all(
                isinstance(r, dict) and isinstance(r.get("name"), str)
                and isinstance(r.get("type"), str) for r in rows):
            problems.append(
                "'metrics' present but not a registry snapshot "
                "({'metrics': [{'name': ..., 'type': ...}, ...]})")
    return problems


def _validate_flight(flight: Any) -> List[str]:
    """Problems with an optional ``flight`` field (``[]`` when absent
    or valid): the :meth:`~apex_tpu.obs.flight.FlightRecorder.dump`
    shape — bounded ring metadata plus ordered event records each
    carrying a numeric ``ts`` and a non-empty ``kind``."""
    if flight is None:
        return []
    if not isinstance(flight, dict):
        return [f"'flight' must be an object, got "
                f"{type(flight).__name__}"]
    problems: List[str] = []
    cap = flight.get("capacity")
    if not (isinstance(cap, int) and not isinstance(cap, bool)
            and cap >= 1):
        problems.append("flight.capacity must be an int >= 1")
    dropped = flight.get("dropped")
    if not (isinstance(dropped, int) and not isinstance(dropped, bool)
            and dropped >= 0):
        problems.append("flight.dropped must be an int >= 0")
    events = flight.get("events")
    if not isinstance(events, list):
        problems.append("flight.events must be a list")
        return problems
    if isinstance(cap, int) and not isinstance(cap, bool) \
            and len(events) > cap:
        problems.append(
            f"flight holds {len(events)} events over its stated "
            f"capacity {cap} — a ring that overflows its own bound is "
            f"a contradiction")
    last_ts = None
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"flight.events[{i}] must be an object")
            continue
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or isinstance(ts, bool):
            problems.append(f"flight.events[{i}] missing numeric 'ts'")
        elif last_ts is not None and ts < last_ts:
            problems.append(
                f"flight.events[{i}] ts {ts} precedes its predecessor "
                f"{last_ts} — ring events must be ordered")
        else:
            last_ts = ts
        kind = ev.get("kind")
        if not (isinstance(kind, str) and kind.strip()):
            problems.append(
                f"flight.events[{i}] missing non-empty str 'kind'")
    return problems


def make_incident(status: str, summary: str,
                  evidence: Sequence[Any], **extra: Any) -> Dict[str, Any]:
    """Assemble a schema-valid incident dict (raises on an invalid one —
    a writer that emits records its own validator rejects is a bug)."""
    rec: Dict[str, Any] = {
        "artifact": extra.pop("artifact", "apex_tpu.resilience incident record"),
        "status": status,
        "utc": utc_now(),
        "summary": summary,
        "evidence": list(evidence),
    }
    rec.update(extra)
    problems = validate_incident(rec)
    if problems:
        raise ValueError(f"refusing to write invalid incident: {problems}")
    return rec


def write_incident(path: str, status: str, summary: str,
                   evidence: Sequence[Any], **extra: Any) -> Dict[str, Any]:
    """Write an incident artifact atomically (tmp + rename: a watchdog
    firing mid-crash must not leave a half-written record) and return it."""
    rec = make_incident(status, summary, evidence, **extra)
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    return rec


def validate_incident_file(path: str) -> List[str]:
    """Validate one on-disk artifact; parse failures are schema failures
    (a truncated incident file is exactly the rot this exists to catch)."""
    try:
        with open(path) as f:
            obj = json.load(f)
    except (OSError, ValueError) as e:
        return [f"unreadable incident JSON: {e}"]
    return validate_incident(obj)
