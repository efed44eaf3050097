"""Decompose the b8 decode step — explain the 0.43-of-ceiling number
(VERDICT r5 #6) with device-time buckets.

Decode is HBM-bandwidth-bound, so under the roofline model a byte
accounting IS a device-time accounting: bucket every byte of the decode
step's HBM traffic and you have bucketed the step.  This tool walks the
lowered StableHLO of the decode program
(``apex_tpu.models.generate._generate_impl`` at gpt_small_tpu b8,
prefill 2048, 256 new tokens — lowered from ShapeDtypeStructs, nothing
is initialized or run) and classifies every op of the per-token step
function (layer-loop trip counts applied, private calls expanded) into:

- ``param_read``   — weight reads: per-layer projection/FFN slices,
  lm_head, final LN, the embedding-row gather
- ``kv_read``      — the cache-slice operands of the attention dots
  (the K and V reads of every layer)
- ``kv_write``     — the two per-layer ``dynamic_update_slice`` token
  writes (in-place on the loop carry: update bytes ×2)
- ``attention``    — the score/output dots' non-cache traffic and the
  fp32 softmax chain
- ``sampling``     — the argmax/top-k epilogue over ``(B, V)`` logits
- ``host_sync``    — host callbacks on the token loop (count; must be
  0 bytes — the loop is a device-side ``lax.scan``)
- ``other``        — rope tables, layernorm stats, residual adds

Conventions (stated in the artifact): element-wise/reshape/convert ops
are counted FUSED (result bytes only, or zero for pure layout ops) —
the walk models the roofline-ideal step.  The ops XLA *could* fail to
fuse (the per-layer cache-slice copies, the bf16→f32 cache converts)
are recorded separately as **materialization candidates** with their
would-be volumes.  The committed r01 round also reconciled the
modeled step against a decode rate measured in round 5 (its
``measured`` and ``gap_attribution`` blocks); that measurement's source
is gone and nothing on the repo's benchmark serves, so a round written
now carries the static decomposition alone.

The committed ``DECODE_DECOMPOSE_r01.json`` is schema-validated by
``tools/gate_hygiene.py`` against
``apex_tpu/analysis/decode_decompose.py`` (stdlib-only), which
enforces the >= 90% named-bucket coverage bar.

Usage:
    python tools/decode_decompose.py [--batch 8] [--prefill 2048]
        [--new-tokens 256] [--tiny] [--no-compile]
        [--emit DECODE_DECOMPOSE_r01.json]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

os.environ.setdefault("APEX_TPU_KERNELS", "jnp")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu"))

from apex_tpu.analysis import dflow  # noqa: E402

_ELEM_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "i1": 1,
               "i8": 1, "ui8": 1, "i16": 2, "ui16": 2, "i32": 4,
               "ui32": 4, "i64": 8, "ui64": 8}

_CALLEE = re.compile(r"@([\w$.-]+)")

#: host-round-trip custom-call targets (the syncs-pass list)
_CALLBACK = ("python_cpu_callback", "python_gpu_callback",
             "python_tpu_callback", "tpu_host_callback")


def _nbytes(payload: str) -> int:
    dims = dflow.dims_of(payload)
    et = dflow.element_type(payload)
    return int(math.prod(dims)) * _ELEM_BYTES.get(et, 4) if dims \
        else _ELEM_BYTES.get(et, 4)


def lower_decode(batch: int, prefill: int, new_tokens: int,
                 tiny: bool = False):
    """AOT-lower the exact bench decode program from ShapeDtypeStructs
    (bf16 serving layout) — no params materialize, nothing runs.
    Returns ``(lowered, cfg)``."""
    from importlib import import_module

    gen = import_module("apex_tpu.models.generate")
    from apex_tpu.models.gpt import GPTModel, gpt_small_tpu, gpt_tiny

    cfg = gpt_tiny() if tiny else gpt_small_tpu()
    model = GPTModel(cfg)
    prompt = jax.ShapeDtypeStruct((batch, prefill), jnp.int32)
    params = jax.eval_shape(lambda k, p: model.init(k, p)["params"],
                            jax.random.PRNGKey(0), prompt)
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating)
            else s.dtype), params)
    blocks = [params[f"block_{i}"] for i in range(cfg.num_layers)]
    stacked = jax.tree.map(
        lambda *xs: jax.ShapeDtypeStruct((len(xs),) + xs[0].shape,
                                         xs[0].dtype), *blocks)
    top = {k: v for k, v in params.items() if not k.startswith("block_")}
    lowered = gen._generate_impl.lower(
        top, stacked, prompt, jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), cfg=cfg,
        max_new_tokens=new_tokens, sample=False)
    return lowered, cfg


def find_step_funcs(funcs, cache_dims):
    """``(step_fn_name, layer_fn_name)``: among the private functions
    carrying both full caches as args, the decode STEP is the one whose
    (layer-loop) body calls another cache-carrying function — that
    callee is the per-layer block.  Fails loudly rather than bucketing
    the wrong program."""
    carriers = [name for name, f in funcs.items()
                if sum(1 for _t, p in f.args
                       if dflow.dims_of(p) == cache_dims) >= 2]
    for name in carriers:
        for op in funcs[name].ops:
            if op.name != "call":
                continue
            m = _CALLEE.search(op.line)
            if m and m.group(1) in carriers and m.group(1) != name:
                return name, m.group(1)
    raise RuntimeError(
        f"could not identify the decode step function among cache "
        f"carriers {carriers} — the lowering layout changed; update "
        f"find_step_funcs")


class Walk:
    """Bucketed byte accounting of the per-token decode step (see the
    module docstring for the conventions)."""

    def __init__(self, funcs, cfg, batch, m_ctx, vocab):
        self.funcs = funcs
        self.L = cfg.num_layers
        self.cache_dims = (cfg.num_layers, batch, m_ctx, cfg.num_heads,
                           cfg.hidden_size // cfg.num_heads)
        self.m_ctx = m_ctx
        self.vocab = vocab
        self.slice_elems = int(math.prod(self.cache_dims[1:]))
        self.buckets = {k: 0.0 for k in
                        ("param_read", "kv_read", "kv_write",
                         "attention", "sampling", "host_sync", "other")}
        self.host_sync_count = 0
        self.candidates = []      # (label, would_be_bytes, count)

    def _is_cache(self, payload):
        return dflow.dims_of(payload) == self.cache_dims

    def _is_cache_slice(self, payload):
        dims = dflow.dims_of(payload)
        return (self.m_ctx in dims
                and int(math.prod(dims)) >= self.slice_elems)

    def _has_vocab(self, op):
        return any(self.vocab in dflow.dims_of(t) for t in op.types)

    def _add(self, bucket, nbytes, mult):
        self.buckets[bucket] += nbytes * mult

    def _candidate(self, label, nbytes, mult):
        self.candidates.append((label, int(nbytes * mult)))

    def run(self, step_fn, layer_fn):
        self._walk(step_fn, mult=1, layer_mult=self.L,
                   layer_fn=layer_fn)

    def _walk(self, fname, mult, layer_mult=1, layer_fn=None,
              depth_guard=0):
        if depth_guard > 6 or fname not in self.funcs:
            return
        for op in self.funcs[fname].ops:
            m = mult * (layer_mult if op.depth >= 1 else 1)
            if op.name == "while":
                continue                      # body ops counted below
            if op.name == "call":
                cm = _CALLEE.search(op.line)
                if cm:
                    self._walk(cm.group(1), m, 1, None, depth_guard + 1)
                continue
            self._classify(op, m)

    def _classify(self, op, m):
        name, types = op.name, op.types
        res = types[-1] if types else None
        res_b = _nbytes(res) if res else 0
        if name == "custom_call" and any(t in op.line
                                         for t in _CALLBACK):
            self.host_sync_count += int(m)
            self._add("host_sync", 0, m)
            return
        if name == "dynamic_update_slice" and res and \
                self._is_cache(res):
            upd = _nbytes(types[1]) if len(types) >= 2 else 0
            self._add("kv_write", 2 * upd, m)
            return
        if name == "dynamic_slice" and types and \
                self._is_cache(types[0]):
            # the slice READ itself is charged to the consuming dot
            # (kv_read); a copy that fails to fuse would add this much:
            self._candidate("kv-slice-copy-write", res_b, m)
            return
        if name == "convert" and types and \
                self._is_cache_slice(types[0]):
            op_b = _nbytes(types[0])
            self._candidate("kv-f32-convert-roundtrip", op_b + res_b, m)
            return
        if name in ("reshape", "broadcast_in_dim"):
            return          # layout/expansion: fused, no HBM traffic
        if name == "dot_general":
            cache_ops = [t for t in types[:-1]
                         if self._is_cache_slice(t)]
            if cache_ops:
                for t in cache_ops:
                    self._add("kv_read", _nbytes(t), m)
                rest = sum(_nbytes(t) for t in types[:-1]
                           if not self._is_cache_slice(t))
                self._add("attention", rest + res_b, m)
                return
            # projection/FFN/logits matmul: dominated by the weight
            # operand — the whole op is a parameter read
            self._add("param_read",
                      sum(_nbytes(t) for t in types), m)
            return
        if name == "dynamic_slice" and types and \
                dflow.dims_of(types[0])[:1] == (self.L,):
            # per-layer slice of the stacked params: one read
            self._add("param_read", res_b, m)
            return
        if name == "gather" and types and \
                self.vocab in dflow.dims_of(types[0])[:1]:
            # embedding rows: read + result write + indices
            self._add("param_read", 2 * res_b, m)
            return
        if self._has_vocab(op):
            self._add("sampling", res_b, m)
            return
        if res and self.m_ctx in dflow.dims_of(res):
            # score-chain tensors (B, H, 1, M): softmax/where/compare
            self._add("attention", res_b, m)
            return
        self._add("other", res_b, m)


def decompose(batch: int, prefill: int, new_tokens: int,
              tiny: bool = False, compile: bool = True) -> dict:
    lowered, cfg = lower_decode(batch, prefill, new_tokens, tiny=tiny)
    funcs = dflow.parse_module(lowered.as_text())
    m_ctx = prefill + new_tokens
    cache_dims = (cfg.num_layers, batch, m_ctx, cfg.num_heads,
                  cfg.hidden_size // cfg.num_heads)
    step_fn, layer_fn = find_step_funcs(funcs, cache_dims)
    walk = Walk(funcs, cfg, batch, m_ctx, cfg.vocab_size)
    walk.run(step_fn, layer_fn)

    total = sum(walk.buckets.values())
    fractions = {k: round(v / total, 4) for k, v in walk.buckets.items()}
    coverage = round(1.0 - fractions["other"], 4)

    # rank the materialization candidates (merged by label)
    cand: dict = {}
    for label, b in walk.candidates:
        cand[label] = cand.get(label, 0) + b
    cand = dict(sorted(cand.items(), key=lambda kv: -kv[1]))

    doc = {
        "round": 1,
        "platform": jax.devices()[0].platform,
        "config": {"batch": batch, "prefill": prefill,
                   "new_tokens": new_tokens,
                   "model": "gpt_tiny" if tiny else "gpt_small_tpu"},
        "method": "stablehlo-walk",
        "step_fn": {"step": step_fn, "layer_body": layer_fn,
                    "layer_trips": cfg.num_layers},
        "step_bytes": {"total": int(total),
                       "buckets": {k: int(v)
                                   for k, v in walk.buckets.items()}},
        "device_time_fractions": fractions,
        "coverage": coverage,
        "host_sync_count": walk.host_sync_count,
        "static_candidates_ranked": cand,
        "note": (
            "Bytes conventions: elementwise/layout ops fused (result "
            "bytes only / zero); cache DUS in-place (2x update); cache "
            "reads charged at the consuming dot; per-layer ops x "
            "num_layers via the layer-loop walk.  Fractions model the "
            "roofline-IDEAL step: on a bandwidth-bound program they "
            "are device-time fractions.  The candidates are "
            "the statically-visible buffers XLA may materialize on "
            "top of the ideal."),
    }
    if compile:
        try:
            from apex_tpu.analysis import cost as cost_mod
            ct = cost_mod.cost_table(lowered.compile())
            if ct:
                ct["caveat"] = ("XLA:CPU cost model counts loop bodies "
                                "once, not per trip — reference only")
                doc["xla_cost_model"] = ct
        except Exception as e:  # noqa: BLE001 - reference info only
            doc["xla_cost_model"] = {"error": str(e)[:200]}
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prefill", type=int, default=2048)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--tiny", action="store_true",
                    help="gpt_tiny config (tests)")
    ap.add_argument("--no-compile", action="store_true",
                    help="skip the XLA cost-model reference read")
    ap.add_argument("--emit", default=None,
                    metavar="DECODE_DECOMPOSE_rN.json",
                    help="write the committed artifact (validated "
                         "against apex_tpu/analysis/decode_decompose.py "
                         "before writing; refuses an invalid document)")
    opts = ap.parse_args(argv)

    doc = decompose(opts.batch, opts.prefill, opts.new_tokens,
                    tiny=opts.tiny, compile=not opts.no_compile)
    if opts.emit:
        m = re.search(r"_r(\d+)\.json$", os.path.basename(opts.emit))
        if m:
            doc["round"] = int(m.group(1))
        from apex_tpu.analysis import decode_decompose as schema
        problems = schema.validate_decompose(doc)
        if problems:
            print(f"refusing to write {opts.emit}: {problems}",
                  file=sys.stderr)
            return 1
        with open(opts.emit, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"decode decomposition written: {opts.emit}",
              file=sys.stderr)
    else:
        print(json.dumps(doc, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
