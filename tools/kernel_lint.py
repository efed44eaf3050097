#!/usr/bin/env python
"""Sweep every hand-written Pallas kernel through the sanitizer.

Traces each kernel — adam, lamb stage-1/2, layer_norm fwd/bwd,
multi_tensor, flash_attention, and the three ``experimental/`` kernels
— across the geometry ladder (explicit row-block / chunks-per-block
overrides at the ladder's extremes plus the selector's own pick) and
adversarial ragged shapes, runs all six
:mod:`apex_tpu.analysis.pallas_lint` rules over every ``pallas_call``
found, and writes the per-kernel verdict as ``KERNLINT_r*.json``
(schema: :mod:`apex_tpu.analysis.kernlint`, validated by
``tools/gate_hygiene.py`` in tier-1).

Tracing only — nothing is compiled or executed, so the sweep is cheap
enough for CI and runs identically on CPU and TPU (the jaxpr-level
``pallas_call`` carries the same grid/BlockSpec metadata either way).

Usage::

    python tools/kernel_lint.py --out KERNLINT_r01.json
    python tools/kernel_lint.py            # print verdicts, no file

Exit code 1 when any kernel records an unwaived finding (or a config
fails to trace), so the sweep can gate CI directly.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the kernels under test must BE pallas (graph_lint's jnp default would
# trace fallback einsums instead of kernels), and the experimental
# kernels only route when opted in
os.environ["APEX_TPU_KERNELS"] = "pallas"
os.environ["APEX_TPU_EXPERIMENTAL"] = "1"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from apex_tpu.analysis import pallas_lint               # noqa: E402
from apex_tpu.analysis.kernlint import (                # noqa: E402
    RULES, validate_kernlint)

#: documented waivers: kernel -> {rule id -> reason}.  A waiver only
#: validates when the rule actually fired (the schema rejects stale
#: ones), so this table is empty while the sweep is clean.
WAIVERS: dict = {}


# ---------------------------------------------------------------------------
# the config table: kernel -> [(config label, fn, args)]
# ---------------------------------------------------------------------------

def _adam_configs():
    from apex_tpu.ops.pallas import adam_kernel as ak
    f32 = jnp.float32
    cfgs = []
    for n, br, donate in [
            (ak.ADAM_PAD, None, False),        # selector's own pick
            (ak.ADAM_PAD * 3, 256, True),      # donated, autotune max
            (ak.ADAM_PAD * 3, 8, False),       # ladder bottom, ragged
    ]:
        p = jnp.zeros((n,), f32)
        args = (p, jnp.zeros_like(p), jnp.zeros_like(p),
                jnp.ones_like(p))

        def fn(p, m, v, g, _br=br, _d=donate):
            return ak.packed_adam(
                p, m, v, g, step_size=1e-3, beta1=0.9, beta2=0.999,
                eps=1e-8, scale=1.0, weight_decay=0.01, eps_mode=0,
                p_copy_dtype=jnp.bfloat16, block_rows=_br, donate=_d)

        cfgs.append((f"n={n} block_rows={br} donate={donate}", fn, args))
    return cfgs


def _lamb_configs():
    from apex_tpu.ops.pallas import lamb_kernels as lk
    f32 = jnp.float32
    cfgs = []
    for n_chunks, cpb, with_norms in [(8, None, False), (8, 1, True),
                                      (16, 4, True)]:
        n = lk.LAMB_CHUNK * n_chunks
        g = jnp.ones((n,), f32)
        args = (g, jnp.ones_like(g), jnp.zeros_like(g),
                jnp.zeros_like(g), jnp.full((n_chunks,), 0.01, f32))

        def fn(g, p, m, v, d, _cpb=cpb, _wn=with_norms):
            return lk.packed_lamb_stage1(
                g, p, m, v, d, beta1=0.9, beta2=0.999, eps=1e-6,
                inv_scale=1.0, bc1=1.0, bc2=1.0,
                chunks_per_block=_cpb, with_norms=_wn)

        cfgs.append((f"stage1 n_chunks={n_chunks} cpb={cpb} "
                     f"norms={with_norms}", fn, args))
    for n_chunks, cpb in [(8, None), (16, 4)]:
        n = lk.LAMB_CHUNK * n_chunks
        p = jnp.ones((n,), f32)
        args = (p, jnp.ones_like(p), jnp.ones((n_chunks,), f32))

        def fn(p, u, r, _cpb=cpb):
            return lk.packed_lamb_stage2(
                p, u, r, p_copy_dtype=jnp.bfloat16,
                chunks_per_block=_cpb)

        cfgs.append((f"stage2 n_chunks={n_chunks} cpb={cpb}", fn, args))
    return cfgs


def _layer_norm_configs():
    from apex_tpu.ops.pallas import layer_norm_kernels as lnk
    cfgs = []
    # forward across the row ladder + ragged rows; fwd+bwd via vjp at
    # the widest shapes supported() admits per dtype — the sanitizer is
    # exactly why wider ones route to the jnp fallback
    shapes = [(256, 1024, jnp.float32), (100, 512, jnp.bfloat16),
              (256, 5376, jnp.float32),      # fp32 backward boundary
              (256, 10752, jnp.bfloat16)]    # bf16 backward boundary
    for n1, n2, dt in shapes:
        assert lnk.supported(n2, dt), (n2, dt)
        x = jnp.ones((n1, n2), dt)
        w = jnp.ones((n2,), dt)
        b = jnp.zeros((n2,), dt)

        def fwd(x, w, b):
            return lnk._forward(x, w, b, 1e-5, affine=True)

        def fwd_bwd(x, w, b):
            y, vjp = jax.vjp(
                lambda x, w, b: lnk.layer_norm_fwd_vjp(x, w, b, 1e-5),
                x, w, b)
            return vjp(y)

        name = jnp.dtype(dt).name
        cfgs.append((f"fwd {n1}x{n2} {name}", fwd, (x, w, b)))
        cfgs.append((f"fwd+bwd {n1}x{n2} {name}", fwd_bwd, (x, w, b)))
    return cfgs


def _multi_tensor_configs():
    from apex_tpu.ops.pallas import multi_tensor_kernels as mtk
    f32 = jnp.float32
    ch = 2048
    flat = jnp.ones((ch * 7,), f32)    # prime chunk count: ragged grid
    s = jnp.float32(2.0)
    return [
        ("scale", lambda f, s: mtk.packed_scale(f, s, ch, f32),
         (flat, s)),
        ("axpby", lambda x, y, a, b: mtk.packed_axpby(
            x, y, a, b, ch, f32, arg_to_check=0), (flat, flat, s, s)),
        ("sumsq", lambda f: mtk.packed_sumsq(f, ch), (flat,)),
        ("sumsq_per_chunk",
         lambda f: mtk.packed_sumsq_per_chunk(f, ch), (flat,)),
    ]


def _flash_configs():
    from apex_tpu.ops.pallas.flash_attention import flash_attention
    bf16 = jnp.bfloat16
    cfgs = []
    for b, l, h, d, causal in [(2, 384, 2, 64, True),   # ragged L
                               (1, 512, 4, 128, False)]:
        q = jnp.ones((b, l, h, d), bf16)
        mask = jnp.ones((b, l), jnp.bool_)

        def fwd(q, k, v, m, _c=causal):
            return flash_attention(q, k, v, causal=_c, kv_mask=m)

        def fwd_bwd(q, k, v, m, _c=causal):
            y, vjp = jax.vjp(
                lambda q, k, v: flash_attention(q, k, v, causal=_c,
                                                kv_mask=m), q, k, v)
            return vjp(y)

        tag = f"b{b} l{l} h{h} d{d} causal={causal}"
        cfgs.append((f"fwd {tag}", fwd, (q, q, q, mask)))
        cfgs.append((f"fwd+bwd {tag}", fwd_bwd, (q, q, q, mask)))

    # the benchmark's call: causal, rope in the kernel, no key mask, so
    # the head's rows stay resident in VMEM (whole-head K/V blocks, the
    # dq scratch, one grid step a head in the backward)
    from apex_tpu.ops.rope import rope_tables
    b, l, h, d = 2, 1024, 2, 64
    q = jnp.ones((b, l, h, d), bf16)
    cos, sin = rope_tables(jnp.broadcast_to(jnp.arange(l)[None, :], (b, l)),
                           d, 10000.0)

    def resident_fwd_bwd(q, k, v, cos, sin):
        y, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            rope=(cos, sin)), q, k, v)
        return vjp(y)

    cfgs.append((f"fwd+bwd b{b} l{l} h{h} d{d} causal rope resident",
                 resident_fwd_bwd, (q, q, q, cos, sin)))

    # a long head (the kanana cell's call: 8192 rows, q and k at 192, v
    # at 128): resident under a scoped-VMEM limit of its own, which is
    # the ceiling its working set is held to
    q = jnp.ones((1, 8192, 2, 192), bf16)
    v = jnp.ones((1, 8192, 2, 128), bf16)

    def long_head_fwd_bwd(q, k, v):
        y, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return vjp(y)

    cfgs.append(("fwd+bwd b1 l8192 h2 d192/128 causal long head",
                 long_head_fwd_bwd, (q, q, v)))
    return cfgs


def _conv1x1_configs():
    from apex_tpu.ops.pallas.experimental import conv1x1 as cv
    bf16 = jnp.bfloat16

    def fwd_bwd(x, w):
        y, vjp = jax.vjp(cv.conv1x1, x, w)
        return vjp(y)

    cfgs = []
    for b, hw, cin, cout in [(2, 16, 64, 128), (1, 32, 128, 256)]:
        x = jnp.ones((b, hw, hw, cin), bf16)
        w = jnp.ones((1, 1, cin, cout), bf16)
        cfgs.append((f"bwd b{b} {hw}x{hw} {cin}->{cout}", fwd_bwd,
                     (x, w)))
    return cfgs


def _finite_pack_configs():
    from apex_tpu.ops.pallas.experimental import finite_pack as fp
    flat = jnp.ones((fp.FINITE_CHUNK * 3,), jnp.float32)
    return [("nonfinite", lambda f: fp.packed_nonfinite(f), (flat,))]


def _flash_mh_configs():
    from apex_tpu.ops.pallas.experimental.flash_mh import \
        flash_attention_mh
    bf16 = jnp.bfloat16
    cfgs = []
    for b, l, h, d in [(1, 256, 2, 64), (1, 384, 12, 64)]:
        q = jnp.ones((b, l, h, d), bf16)

        def fwd_bwd(q, k, v):
            y, vjp = jax.vjp(
                lambda q, k, v: flash_attention_mh(q, k, v,
                                                   causal=True),
                q, k, v)
            return vjp(y)

        cfgs.append((f"fwd+bwd b{b} l{l} h{h} d{d}", fwd_bwd,
                     (q, q, q)))
    return cfgs


KERNELS = {
    "fused_adam": _adam_configs,
    "fused_lamb": _lamb_configs,
    "layer_norm": _layer_norm_configs,
    "multi_tensor": _multi_tensor_configs,
    "flash_attention": _flash_configs,
    "conv1x1": _conv1x1_configs,
    "finite_pack": _finite_pack_configs,
    "flash_mh": _flash_mh_configs,
}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def sweep_kernel(name: str, configs, verbose: bool = False) -> dict:
    """One kernel's KERNLINT record: per-rule error counts over every
    config, the number of pallas_calls actually linted, the verdict."""
    findings = {rule: 0 for rule in RULES}
    calls = 0
    error = None
    waivers = dict(WAIVERS.get(name, {}))
    for label, fn, args in configs:
        try:
            report = pallas_lint.lint_fn(fn, *args)
        except Exception as e:  # noqa: BLE001 - record, don't crash sweep
            error = f"{label}: {type(e).__name__}: {e}"
            break
        for f in report.findings:
            if f.op == "pallas-call" and f.count != 0:
                calls += 1
            if f.severity == "error" and f.op in findings:
                findings[f.op] += 1
                if verbose:
                    print(f"  [{name}] {label}: {f.op}: {f.message}",
                          file=sys.stderr)
    unwaived = sum(c for rule, c in findings.items()
                   if rule not in waivers)
    rec = {"ok": unwaived == 0 and error is None,
           "configs": len(configs), "calls": calls,
           "findings": findings}
    if waivers:
        rec["waivers"] = waivers
    if error is not None:
        rec["error"] = error
    return rec


def run_sweep(verbose: bool = False) -> dict:
    kernels = {}
    for name, build in KERNELS.items():
        try:
            configs = build()
        except Exception as e:  # noqa: BLE001 - config build counts too
            kernels[name] = {"ok": False, "configs": 0, "calls": 0,
                             "findings": {rule: 0 for rule in RULES},
                             "error": f"config build: "
                                      f"{type(e).__name__}: {e}"}
            continue
        kernels[name] = sweep_kernel(name, configs, verbose=verbose)
    clean = sum(1 for rec in kernels.values() if rec["ok"])
    return {
        "round": None,           # filled from --out / --round in main
        "platform": jax.default_backend(),
        "budget_mb": round(pallas_lint.vmem_ceiling() / (1 << 20), 2),
        "rules": list(RULES),
        "kernels": kernels,
        "gate": {"ok": clean == len(kernels), "kernels_clean": clean,
                 "kernels_total": len(kernels)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Pallas kernel sanitizer sweep -> KERNLINT_r*.json")
    ap.add_argument("--out", default=None,
                    help="write the KERNLINT JSON here (round parsed "
                         "from a KERNLINT_rNN.json name)")
    ap.add_argument("--round", type=int, default=None,
                    help="round number (default: parsed from --out, "
                         "else 1)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print every error finding as it is counted")
    opts = ap.parse_args(argv)

    rnd = opts.round
    if rnd is None and opts.out:
        m = re.search(r"KERNLINT_r(\d+)", os.path.basename(opts.out))
        rnd = int(m.group(1)) if m else None
    doc = run_sweep(verbose=opts.verbose)
    doc["round"] = rnd if rnd is not None else 1

    problems = validate_kernlint(doc)
    for name, rec in doc["kernels"].items():
        bad = {rule: c for rule, c in rec["findings"].items() if c}
        status = "ok" if rec["ok"] else "FAIL"
        extra = f" findings={bad}" if bad else ""
        extra += f" error={rec['error']!r}" if "error" in rec else ""
        print(f"{name:16s} {status}  configs={rec['configs']} "
              f"calls={rec['calls']}{extra}")
    gate = doc["gate"]
    print(f"gate: ok={gate['ok']} "
          f"({gate['kernels_clean']}/{gate['kernels_total']} clean)")
    if problems:      # a self-emitted doc failing its own schema is a bug
        for p in problems:
            print(f"schema: {p}", file=sys.stderr)
        return 2
    if opts.out:
        with open(opts.out, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {opts.out}")
    return 0 if gate["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
