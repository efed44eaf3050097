"""xplane / chrome-trace attribution library.

One parser for every profile-reading tool in the repo: the xplane
protobuf walk behind a library API, so ``tools/conv_attrib.py``'s
per-layer attribution and ``tools/profile_decode.py``'s bucketed decode
attribution share one implementation whose behavior is pinned by a
fixture test.

Sources, in preference order:

1. **xplane protobuf** (``*.xplane.pb`` via the tensorflow/tsl proto):
   complete op-level events.  Device planes (``/device:...`` — TPU,
   GPU) aggregate the ``"XLA Ops"`` line; when a capture has *no*
   device plane (XLA:CPU), the host plane's ``tf_XLA*`` executor lines
   carry the per-HLO-instruction events instead and are harvested with
   the infrastructure events (``Thing::Method`` names) filtered out —
   that CPU path is what makes a tier-1 profile smoke possible at all.
2. **chrome-trace JSON** (``*.trace.json.gz``): lossy fallback when
   the proto is not importable — op-level events can be missing for
   large programs (ADVICE r2); same plane/line filter.

Durations are picoseconds throughout (the xplane unit; the JSON
fallback converts).

API:

- :func:`load_planes` — raw ``XPlane`` protos of a capture;
- :func:`op_times` / :func:`parse_xplane` — device time aggregated by
  op name and by ``hlo_category``;
- :func:`step_markers` — the device ``"Steps"`` line's spans (empty on
  hosts that don't emit step markers, e.g. XLA:CPU);
- :func:`bucket_op_times` — fold an op-time table into named buckets
  through a classifier (the DECODE_PROFILE bucketing).
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import sys
from typing import Callable, Counter as TCounter, Dict, List, Optional

__all__ = ["OpTimes", "load_planes", "op_times", "parse_xplane",
           "parse_trace_json", "step_markers", "bucket_op_times"]


@dataclasses.dataclass
class OpTimes:
    """Aggregated device time of one capture (picoseconds)."""

    by_op: TCounter[str]
    by_category: TCounter[str]
    total_ps: int
    source: str                 # xplane-device | xplane-host | trace-json


def _xplane_pb2():
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
        return xplane_pb2
    except ImportError:
        return None


def load_planes(logdir: str) -> List[object]:
    """Every ``XPlane`` proto under ``logdir`` (all ``*.xplane.pb``
    files); ``[]`` when the tsl proto is unavailable."""
    pb2 = _xplane_pb2()
    if pb2 is None:
        return []
    planes = []
    for path in glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True):
        xs = pb2.XSpace()
        with open(path, "rb") as f:
            xs.ParseFromString(f.read())
        planes.extend(xs.planes)
    return planes


def _short(name: str) -> str:
    """Strip an ``%op = type{layout} ...`` HLO dump down to the op name
    (device-plane event names are full dumps; host-line names are
    already short)."""
    return name.split(" = ")[0].lstrip("%")


def _hlo_category_id(plane):
    """The plane's ``hlo_category`` stat-metadata id, found ONCE per
    plane (scanning per event would be O(events x stat table))."""
    return next((k for k, v in plane.stat_metadata.items()
                 if v.name == "hlo_category"), None)


def _category_of(plane, ev, cat_id) -> str:
    if cat_id is None:
        return "?"
    smeta = plane.stat_metadata
    emeta = plane.event_metadata[ev.metadata_id]
    for st in list(ev.stats) + list(emeta.stats):
        if st.metadata_id != cat_id:
            continue
        which = st.WhichOneof("value")
        val = getattr(st, which)
        return smeta[val].name if which == "ref_value" else str(val)
    return "?"


def _host_xla_event(name: str) -> bool:
    """Keep HLO-instruction events on the host ``tf_XLA*`` lines;
    drop the executor infrastructure (``ThreadpoolListener::...``,
    ``ThunkExecutor::... (…)``)."""
    return "::" not in name and " " not in name and bool(name)


def op_times(logdir: str) -> OpTimes:
    """Aggregate one capture's XLA-op device time by op and category.
    Prefers device planes' ``"XLA Ops"`` lines; falls back to the host
    plane's ``tf_XLA*`` executor lines (XLA:CPU captures), then to the
    lossy chrome-trace JSON (no tsl proto)."""
    planes = load_planes(logdir)
    if not planes:
        if _xplane_pb2() is None:
            # the JSON export is LOSSY (op events can be missing for
            # large programs) — a silent fallback would print confident tables off an
            # incomplete capture
            print("warning: xplane proto unavailable; falling back to "
                  "the lossy chrome-trace JSON parser (install "
                  "tensorflow for the complete tsl xplane protobuf "
                  "path)", file=sys.stderr)
        by_op, by_cat, total = parse_trace_json(logdir)
        return OpTimes(by_op, by_cat, total, "trace-json")
    by_op: TCounter[str] = collections.Counter()
    by_cat: TCounter[str] = collections.Counter()
    total = 0
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        emeta = plane.event_metadata
        cat_id = _hlo_category_id(plane)
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                d = ev.duration_ps
                by_op[_short(emeta[ev.metadata_id].name)] += d
                by_cat[_category_of(plane, ev, cat_id)] += d
                total += d
    if total:
        return OpTimes(by_op, by_cat, total, "xplane-device")
    # XLA:CPU: no device plane exists — the per-instruction events live
    # on the host plane's executor threadpool lines
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        emeta = plane.event_metadata
        cat_id = _hlo_category_id(plane)
        for line in plane.lines:
            if not line.name.startswith("tf_XLA"):
                continue
            for ev in line.events:
                name = emeta[ev.metadata_id].name
                if not _host_xla_event(name):
                    continue
                d = ev.duration_ps
                by_op[_short(name)] += d
                by_cat[_category_of(plane, ev, cat_id)] += d
                total += d
    return OpTimes(by_op, by_cat, total, "xplane-host")


def parse_xplane(logdir: str):
    """Compatibility shape of :func:`op_times`:
    ``(by_name, by_category, total_ps)`` — the signature the three
    profile tools historically carried as private copies."""
    t = op_times(logdir)
    return t.by_op, t.by_category, t.total_ps


def parse_trace_json(logdir: str):
    """Lossy fallback: aggregate the chrome-trace JSON export
    (op-level events can be missing for large programs — prefer the
    xplane).  Filters to the device planes' ``"XLA Ops"`` line via the
    metadata events, falling back to host ``tf_XLA*`` threads when no
    device thread produced anything, mirroring :func:`op_times`."""
    by_name: TCounter[str] = collections.Counter()
    by_cat: TCounter[str] = collections.Counter()
    total = 0
    host_rows = []
    for path in glob.glob(f"{logdir}/**/*.trace.json.gz", recursive=True):
        with gzip.open(path, "rt") as f:
            trace = json.loads(f.read())
        events = trace.get("traceEvents", [])
        proc: Dict[object, str] = {}
        thread: Dict[tuple, str] = {}
        for ev in events:
            if ev.get("ph") != "M":
                continue
            name = ev.get("args", {}).get("name", "")
            if ev.get("name") == "process_name":
                proc[ev.get("pid")] = name
            elif ev.get("name") == "thread_name":
                thread[(ev.get("pid"), ev.get("tid"))] = name
        for ev in events:
            if ev.get("ph") != "X" or "dur" not in ev:
                continue
            pname = proc.get(ev.get("pid"), "")
            tname = thread.get((ev.get("pid"), ev.get("tid")), "")
            d = int(ev["dur"] * 1e6)            # us -> ps, match xplane
            name = _short(ev.get("name", "?"))
            cat = ev.get("args", {}).get("hlo_category", "?")
            if pname.startswith("/device:") and tname == "XLA Ops":
                by_name[name] += d
                by_cat[cat] += d
                total += d
            elif pname.startswith("/host:") and \
                    tname.startswith("tf_XLA") and _host_xla_event(name):
                host_rows.append((name, cat, d))
    if not total and host_rows:
        for name, cat, d in host_rows:
            by_name[name] += d
            by_cat[cat] += d
            total += d
    return by_name, by_cat, total


def step_markers(logdir: str) -> List[dict]:
    """The device plane's ``"Steps"`` line as
    ``[{"name", "start_ps", "duration_ps"}]`` (step-marker bucketing:
    slice an op-level analysis to one step's window).  Empty when the
    backend emits no step line (XLA:CPU) or no proto is available."""
    out = []
    for plane in load_planes(logdir):
        if not plane.name.startswith("/device:"):
            continue
        emeta = plane.event_metadata
        for line in plane.lines:
            if line.name != "Steps":
                continue
            for ev in line.events:
                out.append({"name": emeta[ev.metadata_id].name,
                            "start_ps": ev.offset_ps,
                            "duration_ps": ev.duration_ps})
    out.sort(key=lambda r: r["start_ps"])
    return out


def bucket_op_times(by_op: Dict[str, int],
                    classify: Callable[[str], Optional[str]],
                    buckets: Optional[List[str]] = None) -> dict:
    """Fold an op→ps table into named buckets: ``classify(op_name)``
    returns a bucket name or ``None`` (→ ``"other"``).  Returns
    ``{"bucket_ps": {...}, "total_ps": n, "matched_ps": n,
    "fractions": {...}}`` with every requested bucket present (zeros
    included) so a schema over the bucket table never sees a partial
    row."""
    bucket_ps: Dict[str, int] = {b: 0 for b in (buckets or [])}
    bucket_ps.setdefault("other", 0)
    total = 0
    matched = 0
    for name, ps in by_op.items():
        b = classify(name)
        total += ps
        if b is None or (buckets is not None and b not in bucket_ps):
            b = "other"
        else:
            matched += ps
        bucket_ps[b] = bucket_ps.get(b, 0) + ps
    fractions = {b: (round(v / total, 4) if total else 0.0)
                 for b, v in bucket_ps.items()}
    return {"bucket_ps": bucket_ps, "total_ps": int(total),
            "matched_ps": int(matched), "fractions": fractions}
