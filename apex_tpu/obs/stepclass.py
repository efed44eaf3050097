"""Compiled-HLO op classifiers: ONE bucket vocabulary per loop kind.

Every profile consumer in this repo buckets measured op time through a
classifier built from the compiled HLO text — instruction name →
named bucket, shape/metadata markers deciding the bucket.  Until this
module the classifiers were private tool code: the decode shape
classifier lived inside ``tools/profile_decode.py`` and the train
step had no op-level vocabulary at all, only raw ``hlo_category``
tables.  The continuous profiler
(:mod:`apex_tpu.obs.contprof`) runs the SAME bucketing online, inside
the serving and training loops — so the classifiers move here, behind
a library API the offline tools now import (private copies deleted,
behavior pinned by fixture tests — the PR-7 xplane treatment), and
the online profiler and the offline tools can never disagree about
what "kv_read" or "bwd" means.

Three classifiers, two vocabularies:

- :class:`DecodeStepClassifier` — the DECODE_PROFILE seven buckets
  (``param_read / kv_read / kv_write / attention / sampling /
  host_sync / other``) over the monolithic decode program's
  while-body (``tools/profile_decode.py``'s classifier, moved);
- :class:`ServeStepClassifier` — the same seven buckets over the
  serve engine's compiled continuous-batching decode step (whole
  program = one step; paged-pool shape markers, scatter writes);
- :class:`TrainStepClassifier` — the pinned train-step vocabulary
  :data:`TRAIN_BUCKETS` (``fwd / bwd / optimizer / collectives /
  host_gap / other``) from the instructions' ``op_name`` metadata
  scopes: jax AD stamps forward ops ``jvp(...)`` and backward ops
  ``transpose(jvp(...))``; the optimizer/scaler update runs under the
  train step's own ``amp_optimizer_step``, ``amp_unscale`` and
  ``amp_scaler_update`` scopes; collectives classify by opcode.  ``host_gap`` is never returned by the
  classifier — it is the derived residual (measured step wall minus
  attributed op time) the profiler fills in.

Classifiers are plain callables (``clf(op_name) -> bucket | None``)
with a ``step_ops()`` set, exactly the contract
:func:`apex_tpu.obs.xplane.bucket_op_times` consumes.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Set

from apex_tpu.utils.profiling import (
    AMP_OPTIMIZER_STEP, AMP_SCALER_UPDATE, AMP_UNSCALE)

__all__ = [
    "TRAIN_BUCKETS", "DECODE_BUCKETS",
    "computations", "closure",
    "DecodeStepClassifier", "ServeStepClassifier",
    "TrainStepClassifier", "StepClassifier",
]

#: the decode bucket vocabulary — MUST equal
#: ``apex_tpu.analysis.decode_profile.BUCKETS`` (pinned by test; the
#: schema module stays stdlib-only and is loaded standalone by
#: gate_hygiene, so the tuple is duplicated, not imported).
DECODE_BUCKETS = ("param_read", "kv_read", "kv_write", "attention",
                  "sampling", "host_sync", "other")

#: the pinned train-step vocabulary — MUST equal
#: ``apex_tpu.analysis.profile_drift.TRAIN_BUCKETS`` (same
#: duplicated-and-pinned arrangement).  ``host_gap`` is the derived
#: wall-minus-ops residual, never a classification result.
TRAIN_BUCKETS = ("fwd", "bwd", "optimizer", "collectives", "host_gap",
                 "other")

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_CALLS_RE = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations)="
    r"[{(]?%?([\w.\-]+)")
_CALLBACKS = ("python_cpu_callback", "python_gpu_callback",
              "python_tpu_callback", "tpu_host_callback", "infeed",
              "outfeed")
_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all",
                   "collective-broadcast")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')

#: ``op_name`` metadata scopes that mark the optimizer/scaler update:
#: the scopes ``amp.make_train_step`` opens around the update with its
#: overflow skip, the unscale and the loss-scale transition, and the
#: named optimizer kernels.  A bare ``cond`` is no marker: any other
#: ``lax.cond`` in a step would count as the optimizer.
OPTIMIZER_SCOPES = (AMP_OPTIMIZER_STEP, AMP_UNSCALE, AMP_SCALER_UPDATE,
                    "adam", "lamb", "sgd", "apply_grad", "optimizer",
                    "larc", "novograd")


def computations(hlo: str) -> dict:
    """``{computation name: [body lines]}`` of an HLO text dump."""
    comps: dict = {}
    cur = None
    for raw in hlo.splitlines():
        s = raw.strip()
        if s.endswith("{") and " = " not in s and "(" in s:
            cur = s.split()[0].lstrip("%").split("(")[0]
            comps[cur] = []
        elif cur is not None:
            comps[cur].append(raw)
            if s == "}":
                cur = None
    return comps


def expand_refs(rest: str, comps: dict) -> str:
    """One instruction's classification text: the def line plus the
    body of every computation it references (``calls=`` fusions,
    ``to_apply=`` calls/reduces, conditional branches) — one level
    deep, which is where the op_name metadata and shape markers of a
    wrapped region live."""
    text = rest
    for m in _CALLS_RE.finditer(rest):
        body = comps.get(m.group(1))
        if body:
            text = text + "\n" + "\n".join(body)
    return text


def closure(comps: dict, roots) -> set:
    """Computation names reachable from ``roots`` through
    calls/body/condition/to_apply references."""
    seen = set()
    work = list(roots)
    while work:
        name = work.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for raw in comps[name]:
            for m in _CALLS_RE.finditer(raw):
                work.append(m.group(1))
    return seen


class _ShapeBucketer:
    """Shared decode-bucket decision over shape markers (set by the
    concrete classifier): ``cache_full`` (the whole pool's type
    string), ``cache_slices`` (materialized per-request cache reads),
    vocab and context-length marks.  ``_write_ops`` names the write
    opcodes — ``dynamic-update-slice`` for the monolithic in-place
    cache, plus ``scatter`` for the paged pools."""

    cache_full: str = ""
    cache_slices: tuple = ()
    vocab_marks: tuple = ()
    m_marks: tuple = ()
    _write_ops = ("dynamic-update-slice",)

    buckets: Dict[str, Optional[str]]
    slice_copy_ops: Set[str]

    def _classify_comps(self, comps: dict, names) -> None:
        self.buckets = {}
        self.slice_copy_ops = set()
        for cname in names:
            for raw in comps.get(cname, ()):
                m = _DEF_RE.match(raw)
                if not m:
                    continue
                name, rest = m.groups()
                self.buckets[name] = self._bucket(
                    name, rest, expand_refs(rest, comps))

    def _bucket(self, name: str, defline: str, text: str):
        if any(cb in text for cb in _CALLBACKS):
            return "host_sync"
        if self.cache_full in text and \
                any(w in text for w in self._write_ops):
            return "kv_write"
        cacheish = self.cache_full in text or \
            any(cs in text for cs in self.cache_slices)
        dot = re.search(r"\bdot\(", text) is not None
        if cacheish:
            result_type = defline.split(" ")[0]
            if not dot and any(cs in result_type
                               for cs in self.cache_slices):
                # a materialized cache-slice-shaped RESULT with no
                # consuming dot in the same fusion: the slice-copy
                # candidate the decompose residual points at
                self.slice_copy_ops.add(name)
            return "kv_read"
        if dot or "convolution(" in text:
            return "param_read"
        if any(vm in text for vm in self.vocab_marks):
            if "gather(" in text:
                return "param_read"          # embedding-row gather
            return "sampling"
        if any(mm in text for mm in self.m_marks):
            return "attention"
        return None                          # -> "other"

    def step_ops(self) -> set:
        return set(self.buckets)

    def __call__(self, name: str):
        return self.buckets.get(name)


class DecodeStepClassifier(_ShapeBucketer):
    """instruction name -> bucket, for the MONOLITHIC decode
    program's while-body instructions, built from the compiled HLO
    text (moved verbatim from ``tools/profile_decode.py``; behavior
    pinned by the tool's CPU smoke + the fixture test).

    Shape markers (HLO type strings like ``bf16[12,8,2304,4,64]``):
    the full cache pool ``(L,B,M,H,D)``, a cache-slice
    materialization ``(B,M,H,D)`` (the DECODE_DECOMPOSE residual
    candidate — tracked separately as ``slice_copy`` evidence), the
    vocab dimension, and the context length M.  Classification mirrors
    the static walk's conventions: ops reading the cache feed
    ``kv_read``; cache writes ``kv_write``; weight-operand dots and
    the embedding gather ``param_read``; vocab-shaped non-dot ops
    ``sampling``; M-length score-chain tensors ``attention``."""

    def __init__(self, hlo: str, cfg, batch: int, m_ctx: int):
        L, H = cfg.num_layers, cfg.num_heads
        D = cfg.hidden_size // cfg.num_heads
        V = cfg.vocab_size
        self.cache_full = f"[{L},{batch},{m_ctx},{H},{D}]"
        self.cache_slices = (f"[{batch},{m_ctx},{H},{D}]",
                             f"[1,{batch},{m_ctx},{H},{D}]")
        self.vocab_marks = (f",{V}]", f"[{V},")
        self.m_marks = (f",{m_ctx},", f",{m_ctx}]")
        comps = computations(hlo)
        # the decode loop = while bodies whose closure touches the
        # cache pool (prefill has no full-pool operand)
        bodies = []
        for lines in comps.values():
            for raw in lines:
                if " while(" not in raw:
                    continue
                bm = re.search(r"body=%?([\w.\-]+)", raw)
                if bm:
                    bodies.append(bm.group(1))
        step_comps = set()
        for body in bodies:
            cl = closure(comps, [body])
            if any(self.cache_full in raw
                   for c in cl for raw in comps.get(c, [])):
                step_comps |= cl
        if not step_comps:
            raise RuntimeError(
                "no while body touching the KV cache pool "
                f"{self.cache_full} found — the compiled layout "
                "changed; update DecodeStepClassifier")
        self._classify_comps(comps, step_comps)


#: backwards-compatible name ``tools/profile_decode.py`` imported the
#: classifier under before the extraction.
StepClassifier = DecodeStepClassifier


class ServeStepClassifier(_ShapeBucketer):
    """instruction name -> DECODE bucket for the SERVE engine's
    compiled continuous-batching decode step.  The whole program IS
    one step (the engine dispatches it per generated token), so every
    computation is in scope — no while-body selection.  Markers come
    from the paged layout: the ``(L, num_blocks, bs, H, D)`` pools
    (``cache_full``), the page-table-gathered per-slot caches
    ``(S, M, H, D)`` (``cache_slices`` — a materialized gather is the
    paged analog of the monolithic slice copy), vocab and per-slot
    context-length marks.  Cache writes are paged SCATTERS, not
    dynamic-update-slices."""

    _write_ops = ("dynamic-update-slice", "scatter")

    def __init__(self, hlo: str, cfg, serve_cfg):
        L, H = cfg.num_layers, cfg.num_heads
        D = cfg.hidden_size // cfg.num_heads
        V = cfg.vocab_size
        S = serve_cfg.num_slots
        bs = serve_cfg.block_size
        nb = serve_cfg.num_blocks
        m = serve_cfg.max_blocks_per_slot * bs
        self.cache_full = f"[{L},{nb},{bs},{H},{D}]"
        self.cache_slices = (f"[{S},{m},{H},{D}]",
                             f"[1,{S},{m},{H},{D}]",
                             f"[{nb},{bs},{H},{D}]")
        self.vocab_marks = (f",{V}]", f"[{V},")
        self.m_marks = (f",{m},", f",{m}]")
        comps = computations(hlo)
        self._classify_comps(comps, list(comps))


class TrainStepClassifier:
    """instruction name -> TRAIN bucket for a compiled train step,
    from each instruction's ``op_name`` metadata scope (jax stamps
    the Python trace path into the HLO metadata):

    - opcode is a collective (all-reduce / all-gather / reduce-scatter
      / collective-permute / all-to-all) → ``collectives`` (checked
      FIRST: a gradient all-reduce sits inside ``transpose(jvp(``
      scopes but its cost story is the wire, not the backward math);
    - scope contains ``transpose(jvp(`` or ``vjp(`` → ``bwd`` (the AD
      transpose pass);
    - scope hits an optimizer marker (:data:`OPTIMIZER_SCOPES`: the
      step's ``amp_optimizer_step``, ``amp_unscale`` and
      ``amp_scaler_update`` scopes, named optimizer kernels) →
      ``optimizer``;
    - scope contains ``jvp(`` → ``fwd``;
    - anything else → ``None`` (→ ``other``).

    Fusions classify by their JOINED text (def line + called fused
    computation), so a fusion mixing forward and backward ops lands in
    ``bwd`` — the precedence is part of the pinned contract (fixture
    test).  ``host_gap`` is never returned: it is the derived
    wall-minus-attributed residual the profiler computes."""

    def __init__(self, hlo: str,
                 optimizer_scopes=OPTIMIZER_SCOPES):
        self._opt_res = [re.compile(r"(?:^|/)[^/]*" + re.escape(s))
                         for s in optimizer_scopes]
        comps = computations(hlo)
        self.buckets: Dict[str, Optional[str]] = {}
        for cname, lines in comps.items():
            for raw in lines:
                m = _DEF_RE.match(raw)
                if not m:
                    continue
                name, rest = m.groups()
                self.buckets[name] = self._bucket(
                    rest, expand_refs(rest, comps))

    def _bucket(self, defline: str, text: str) -> Optional[str]:
        if any(f" {op}(" in text or f" {op}-" in text
               or f"= {op}(" in text for op in _COLLECTIVE_OPS):
            return "collectives"
        scopes = _OPNAME_RE.findall(text)
        joined = "\n".join(scopes)
        if "transpose(jvp" in joined or "vjp(" in joined:
            return "bwd"
        if any(r.search(s) for s in scopes for r in self._opt_res):
            return "optimizer"
        if "jvp(" in joined:
            return "fwd"
        return None

    def step_ops(self) -> set:
        return set(self.buckets)

    def __call__(self, name: str):
        return self.buckets.get(name)
