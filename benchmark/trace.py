"""From a profiler trace to intervals, sums and gaps.

``jax.profiler.ProfileData`` (JAX alone, no TensorFlow) reads the
``.xplane.pb`` a trace leaves.  On the v5e each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
HLO instruction, named by the instruction's whole text
(``%fusion.12 = bf16[...] fusion(...), kind=kLoop, ...``; a Mosaic
kernel is ``%flash_fwd.3 = ... custom-call(...)``), and whose line
``Async XLA Ops`` holds transfers in flight.  The host is the plane
``/host:CPU``; its line ``python`` carries the benchmark's own
``TraceAnnotation`` spans.  All on one clock, in nanoseconds.

The reduction works on plain :class:`Event` lists, so that the tests
run it on a recorded fixture with no profiler.
"""

from __future__ import annotations

import collections
import glob
import os
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, ASYNC_LINE, STEPS_LINE = "XLA Ops", "Async XLA Ops", "Steps"
HOST_PLANE, HOST_LINE = "/host:CPU", "python"

_INSTR = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_DEF = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")
#: instructions that only hold other instructions; their time is their
#: children's
CONTAINERS = ("conditional", "while", "call")


class Event(NamedTuple):
    name: str
    start: float      # ns
    duration: float   # ns

    @property
    def end(self) -> float:
        return self.start + self.duration


def instruction(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``; other names unchanged."""
    m = _INSTR.match(name)
    return m.group(1) if m else name


def _unnumbered(instr: str) -> str:
    return re.sub(r"(\.\d+)+$", "", instr)


def kernel(name: str) -> str:
    """The instruction's name without its numeric suffix:
    ``flash_bwd_fused.71`` -> ``flash_bwd_fused``."""
    return _unnumbered(instruction(name))


def opcode(name: str) -> str:
    m = _INSTR.match(name)
    if not m:
        return ""
    m = _OPCODE.search(name, m.end() - 1)
    return m.group(1) if m else ""


def is_collective(name: str) -> bool:
    return opcode(name).startswith(COLLECTIVES)


# ---------------------------------------------------------------- reading

def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: str) -> dict:
    """``{plane name: {line name: [Event]}}`` of the device planes and of
    the host's ``python`` line."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    planes: dict = {}
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE, STEPS_LINE):
                continue
            if not device and line.name != HOST_LINE:
                continue
            lines[line.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
    return planes


def device_planes(planes: dict) -> list:
    """Names of the device planes, by chip number."""
    return sorted((p for p in planes if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


# ------------------------------------------------------------ arithmetic

def union(intervals: Iterable) -> list:
    """Sorted, merged ``(start, end)`` pairs."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def length(intervals: Iterable) -> float:
    return sum(end - start for start, end in intervals)


def subtract(intervals: list, holes: list) -> list:
    """``intervals`` minus ``holes``; both merged and sorted."""
    out, h = [], 0
    for start, end in intervals:
        while h < len(holes) and holes[h][1] <= start:
            h += 1
        k, cursor = h, start
        while k < len(holes) and holes[k][0] < end:
            if holes[k][0] > cursor:
                out.append((cursor, holes[k][0]))
            cursor = max(cursor, holes[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def clip(intervals: Iterable, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


#: stamps are picoseconds rounded to nanoseconds: two operations that
#: follow each other may seem to overlap by this much
STAMP_SLACK_NS = 2.0


def self_times(events: list) -> list:
    """``[(Event, self ns)]``: each event's duration less the part its
    nested events cover (an ``XLA Ops`` line nests the instructions of a
    conditional's branch inside the conditional).  An event is nested in
    another only if it also ends inside it; one that merely starts a
    rounding error before the other ends follows it."""
    out, stack = [], []          # stack of [event, covered ns]

    def close():
        event, covered = stack.pop()
        out.append((event, max(event.duration - covered, 0.0)))
        if stack:
            stack[-1][1] += event.duration

    for event in sorted(events, key=lambda e: (e.start, -e.duration)):
        while stack and (
                stack[-1][0].end <= event.start + STAMP_SLACK_NS
                or event.end > stack[-1][0].end + STAMP_SLACK_NS):
            close()
        stack.append([event, 0.0])
    while stack:
        close()
    return out


def window(planes: dict, plane: str) -> "tuple[float, float]":
    """The traced window on one chip: from the first step's start to the
    last step's end, or of its operations where the line is missing."""
    events = planes[plane].get(STEPS_LINE) or planes[plane][OPS_LINE]
    return min(e.start for e in events), max(e.end for e in events)


def busy(planes: dict, plane: str) -> list:
    """Merged intervals in which an operation ran on this chip, inside
    its traced window."""
    lo, hi = window(planes, plane)
    return clip(union((e.start, e.end) for e in planes[plane][OPS_LINE]
                      if e.duration > 0), lo, hi)


def instruction_seconds(planes: dict, plane: str) -> dict:
    """Self seconds by instruction name, containers left out, inside the
    traced window."""
    lo, hi = window(planes, plane)
    sums: dict = collections.defaultdict(float)
    for event, own in self_times(planes[plane][OPS_LINE]):
        if lo <= event.start and event.end <= hi \
                and opcode(event.name) not in CONTAINERS:
            sums[instruction(event.name)] += own * 1e-9
    return dict(sums)


def kernel_seconds(by_instruction: dict) -> dict:
    """:func:`instruction_seconds` summed by kernel name (the numeric
    suffix dropped)."""
    sums: dict = collections.defaultdict(float)
    for instr, seconds in by_instruction.items():
        sums[_unnumbered(instr)] += seconds
    return dict(sums)


def exposed_collective_seconds(planes: dict, plane: str) -> float:
    """Time inside collective operations (in flight or executing) during
    which no other operation runs on this chip."""
    lo, hi = window(planes, plane)
    lines = planes[plane]
    inside = union((e.start, e.end)
                   for line in (OPS_LINE, ASYNC_LINE)
                   for e in lines.get(line, ()) if is_collective(e.name))
    others = union((e.start, e.end) for e in lines[OPS_LINE]
                   if e.duration > 0 and not is_collective(e.name)
                   and opcode(e.name) not in CONTAINERS)
    return length(clip(subtract(inside, others), lo, hi)) * 1e-9


def steps_traced(planes: dict, plane: str) -> int:
    return len(planes[plane].get(STEPS_LINE, ()))


def idle_gaps(planes: dict, plane: str, prefix: str = "bench/") -> list:
    """``[[what the host was doing, idle seconds]]``, longest first: each
    gap between operations goes to the benchmark's own host span that
    covers most of it."""
    lo, hi = window(planes, plane)
    gaps = subtract([(lo, hi)], busy(planes, plane))
    spans = [e for e in planes.get(HOST_PLANE, {}).get(HOST_LINE, ())
             if e.name.startswith(prefix)]
    sums: dict = collections.defaultdict(float)
    for start, end in gaps:
        best, share = "host:unattributed", 0.0
        for span in spans:
            overlap = min(end, span.end) - max(start, span.start)
            if overlap > share:
                best, share = "host:" + span.name[len(prefix):], overlap
        sums[best] += (end - start) * 1e-9
    return sorted(([k, v] for k, v in sums.items()), key=lambda kv: -kv[1])


def top_ops(by_kernel: dict, n: int = 10) -> list:
    """``[[kernel name, seconds]]`` of the operations that took most of
    the traced window."""
    return [[k, v] for k, v in sorted(by_kernel.items(),
                                      key=lambda kv: -kv[1])[:n]]


# ------------------------------------------------- the compiled program

def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name metadata}`` of a compiled program's
    HLO text.  A fusion's ``op_name`` is that of the instruction it was
    built around."""
    names = {}
    for line in hlo_text.splitlines():
        d = _HLO_DEF.match(line)
        if d:
            m = _OP_NAME.search(line)
            if m:
                names[d.group(1)] = m.group(1)
    return names


def mosaic_kernels(hlo_text: str) -> list:
    """Names of the Mosaic custom calls (compiled Pallas kernels) in a
    compiled program."""
    found = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            d = _HLO_DEF.match(line)
            if d:
                found.add(_unnumbered(d.group(1)))
    return sorted(found)


#: ``op_name`` scopes that mark the optimizer and loss-scaler update: a
#: copy of the rule of ``apex_tpu.obs.stepclass.TrainStepClassifier``
#: (the overflow-skip ``cond`` that wraps the update, the unscale, and
#: the named optimizer kernels)
OPTIMIZER_SCOPES = ("cond", "amp_unscale", "adam", "lamb", "sgd",
                    "apply_grad", "optimizer", "larc", "novograd")
OPTIMIZER_KERNELS = ("lamb_stage1", "lamb_stage2", "adam", "adam_tree")


def is_optimizer(name: str, op_name: "str | None") -> bool:
    """Whether an executed instruction belongs to the optimizer and
    scaler update.  ``name`` is the event's name or the instruction's.
    Collectives, and whatever autodiff stamped as forward (``jvp(``) or
    backward (``transpose(jvp(``, ``vjp(``), do not; the named optimizer
    kernels do; of the rest, those with a marker in a scope do."""
    if is_collective(name):
        return False
    base = kernel(name)
    if base in OPTIMIZER_KERNELS or base.startswith("mt_"):
        return True
    if not op_name or "jvp(" in op_name or "vjp(" in op_name:
        return False
    return any(marker in scope for scope in op_name.split("/")[1:]
               for marker in OPTIMIZER_SCOPES)
