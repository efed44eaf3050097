"""Per-kernel microbenchmarks: fused optimizers, multi-tensor ops,
fused LayerNorm — step time + achieved HBM bandwidth vs roofline.

The model-level bench (``bench.py``) folds optimizer cost into full
train steps, where a 2%-of-step kernel regression hides inside chip-day
variance (VERDICT r4 missing #3).  This tool isolates each Pallas
kernel on HBM-resident flat buffers and records per-step time, analytic
bytes moved, achieved GB/s, and the fraction of the chip's HBM roofline
— all these kernels are elementwise/reduction passes, so bandwidth IS
their roofline (BASELINE.md: "FusedAdam step time — tracked per chip").

Method: each kernel runs inside a jitted ``lax.scan`` of K chained
steps — the kernel's outputs feed the next iteration's inputs, so the
loop body cannot be hoisted — timed by a scalar fetch around the whole
scan.  The per-step time is a **difference quotient**: best-of-trials
at K and at 6K, ``(t_6K - t_K) / 5K`` — the constant per-call overhead
(dispatch + fetch) cancels exactly.

Gate: ``--compare KERNELBENCH_rN.json`` fails (exit 2) when any
kernel's per-step time worsens by more than ``--threshold`` (default
10%, calibrated like bench.py's: chip-day variance is ±2-4%).

CAVEAT on reading the optimizer numbers: the chained scans here leave
every input dead after its call, so ``input_output_aliases`` donation
would measure ~2x — but the PRODUCTION train step wraps the optimizer
in the loss-scale skip-``cond``, whose untaken branch returns the old
state, keeping p/m/v live across the update; XLA then materializes
full copies and the "win" inverts (measured on chip: BERT-large
105 -> 54 seq/s with aliased LAMB kernels, and chunk-32768 packing
OOM'd the b16 step outright).  The multi-tensor scale/axpby kernels DO
alias in production — their callers run before the skip decision — and
their numbers here reflect it.

Bytes accounting per kernel (N = elements, fp32 flats unless noted):

- ``fused_adam``    R p+m+v+g (16N)  W p+m+v (12N) + bf16 copy (2N)
- ``lamb_stage1``   R g+p+m+v (16N)  W u+m+v (12N) + the fused per-chunk
  norm tables (with_norms — the production driver config; ~N/chunk·8 B,
  accounted as 0)
- ``lamb_stage2``   R p+u (8N)       W p (4N) + bf16 copy (2N)
- ``mt_scale``      R 4N             W 4N
- ``mt_axpby``      R 8N             W 4N
- ``mt_sumsq``      R 4N             W ~0
- ``layernorm_fwd`` (B,H) bf16: R 2S  W 2S + 8B/row stats (S = B*H)
- ``layernorm_fwd_bwd`` adds R dy+x+stats, W dx (+ the dw/db partial
  reduction XLA appends) — accounted as 6S + fwd

Geometry: every record carries the resolved block geometry (the shared
selector's choice, ``apex_tpu.ops.pallas.geometry``) so the artifact
states the shape it measured; ``--autotune`` sweeps each retunable
kernel's geometry knob over its candidate ladder (short timings), picks
the fastest, and records the sweep alongside the final full-length
timing.

Floors: ``KERNEL_FLOORS`` publishes a per-kernel roofline-fraction
floor (the KERNELBENCH_r05 measured values, MFU_FLOORS convention:
gate = floor × (1 − band); floors only move with BENCH_VARIANCE.json
evidence — tests/l1/test_bench_units.py pins the no-ratchet-down rule).
The ``floors`` block is always recorded; ``--assert-floors`` makes a
violation exit 2 (the ``gate_exit_code`` pattern bench.py's absolute
gates use).  Roofline fractions are only meaningful on TPU — off-chip
the floors block records ``skipped`` and never gates.

Usage: python tools/kernel_bench.py [--out KERNELBENCH.json]
       [--compare KERNELBENCH_rN.json] [--threshold 0.10] [--tiny]
       [--autotune] [--assert-floors]
"""

import argparse
import json
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

CHUNK = 2048 * 32   # the multi-tensor chunk (reference semantics const)


def _hbm_peak(tiny: bool = False) -> float:
    """HBM bytes/s of this chip (an unknown chip is an error).  The
    ``tiny`` CPU smoke, whose numbers mean nothing, divides by the v5e
    entry so its records keep their shape."""
    from apex_tpu.utils.chip_peaks import CHIP_PEAKS, chip_peak
    return (CHIP_PEAKS["TPU v5 lite"] if tiny
            else chip_peak()).hbm_bytes_per_s


def _sync(out) -> float:
    """Drain the pipeline via a scalar fetch: slice one element ON
    DEVICE, transfer 4 bytes (``np.asarray(out)`` would copy the whole
    256 MB result to the host inside the timed region)."""
    leaf = jax.tree.leaves(out)[0]
    return float(leaf.ravel()[0].astype(jnp.float32))


def _time_scan_at(build, k: int, trials: int) -> float:
    """Best-of-``trials`` wall seconds for one compiled scan(k) call,
    synced by a scalar fetch."""
    run, args = build(k)
    compiled = jax.jit(run).lower(*args).compile()
    _sync(compiled(*args))
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        _sync(compiled(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _time_scan(build, iters: int, trials: int = 3) -> float:
    """Per-step seconds as the difference quotient between scan(iters)
    and scan(6*iters): the constant per-call overhead (dispatch +
    fetch) cancels; only the 5*iters extra steps remain."""
    t_short = _time_scan_at(build, iters, trials)
    t_long = _time_scan_at(build, 6 * iters, trials)
    return max(t_long - t_short, 1e-9) / (5 * iters)


def _lint_candidate(build) -> list:
    """Rule ids the Pallas sanitizer rejects a candidate geometry for.

    Traces one tiny ``scan(2)`` step through
    :mod:`apex_tpu.analysis.pallas_lint` — trace only, no compile, no
    execution — and returns the sorted error-severity rule ids (empty
    = clean).  ``--autotune`` refuses to time or record a knob entry
    the sanitizer rejects: an over-budget or racy geometry must never
    win a sweep on a lucky interpret-mode timing and land in the knob
    table (the export-gate treatment, applied to autotune)."""
    from apex_tpu.analysis import pallas_lint
    run, args = build(2)
    report = pallas_lint.lint_fn(run, *args)
    return sorted({f.op for f in report.findings
                   if f.severity == "error" and f.op})


def bench_fused_adam(n: int, block_rows: "int | None" = None):
    from apex_tpu.ops.pallas.adam_kernel import adam_geometry, packed_adam

    geom = adam_geometry(n, with_copy=True, block_rows=block_rows)

    def build(k):
        key = jax.random.PRNGKey(0)
        p = jax.random.normal(key, (n,), jnp.float32)
        g = jax.random.normal(jax.random.PRNGKey(1), (n,), jnp.float32)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)

        def run(p, m, v, g):
            def body(carry, _):
                p, m, v = carry
                p, m, v, _copy = packed_adam(
                    p, m, v, g, step_size=1e-3, beta1=0.9, beta2=0.999,
                    eps=1e-8, scale=1.0, weight_decay=0.0, eps_mode=1,
                    p_copy_dtype=jnp.bfloat16, block_rows=block_rows)
                return (p, m, v), None
            (p, m, v), _ = jax.lax.scan(body, (p, m, v), None, length=k)
            return p
        return run, (p, m, v, g)

    return build, 30.0 * n, geom.asdict()


def bench_lamb_stage1(n: int, chunks_per_block: "int | None" = None):
    from apex_tpu.ops.pallas.lamb_kernels import (grown_chunk,
                                                  packed_lamb_stage1,
                                                  stage1_geometry)

    chunk = grown_chunk(n)   # the chunk the production driver packs at n
    geom = stage1_geometry(n, chunk, chunks_per_block)

    def build(k):
        g = jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32)
        p = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
        m = jnp.zeros((n,), jnp.float32)
        v = jnp.zeros((n,), jnp.float32)
        decay = jnp.zeros((n // chunk,), jnp.float32)

        def run(g, p, m, v):
            def body(carry, _):
                g, m, v = carry
                # with_norms: the production driver config — the fused
                # per-chunk ‖p‖²/‖u‖² tables ride along
                u, m, v, _psq, _usq = packed_lamb_stage1(
                    g, p, m, v, decay, beta1=0.9, beta2=0.999, eps=1e-6,
                    inv_scale=1.0, bc1=1.0, bc2=1.0, chunk_size=chunk,
                    chunks_per_block=chunks_per_block, with_norms=True)
                return (u, m, v), None   # update feeds the next "grad"
            (u, m, v), _ = jax.lax.scan(body, (g, m, v), None, length=k)
            return u
        return run, (g, p, m, v)

    return build, 28.0 * n, geom.asdict()


def bench_lamb_stage2(n: int, chunks_per_block: "int | None" = None):
    from apex_tpu.ops.pallas.lamb_kernels import (grown_chunk,
                                                  packed_lamb_stage2,
                                                  stage2_geometry)

    chunk = grown_chunk(n)
    geom = stage2_geometry(n, chunk, with_copy=True,
                           chunks_per_block=chunks_per_block)

    def build(k):
        p = jax.random.normal(jax.random.PRNGKey(4), (n,), jnp.float32)
        u = jax.random.normal(jax.random.PRNGKey(5), (n,), jnp.float32)
        ratio = jnp.full((n // chunk,), 1e-3, jnp.float32)

        def run(p, u):
            def body(carry, _):
                p2, _copy = packed_lamb_stage2(
                    carry, u, ratio, chunk_size=chunk,
                    p_copy_dtype=jnp.bfloat16,
                    chunks_per_block=chunks_per_block)
                return p2, None
            p, _ = jax.lax.scan(body, p, None, length=k)
            return p
        return run, (p, u)

    return build, 14.0 * n, geom.asdict()


def _chunk_geometry(n: int) -> dict:
    """Geometry of the fixed-chunk multi-tensor kernels (one CHUNK-sized
    block per grid step, 128-lane view)."""
    from apex_tpu.ops.pallas.geometry import StreamGeometry
    return StreamGeometry(block_rows=CHUNK // 128, lanes=128,
                          grid=n // CHUNK).asdict()


def bench_mt_scale(n: int):
    from apex_tpu.ops.pallas.multi_tensor_kernels import packed_scale

    def build(k):
        x = jax.random.normal(jax.random.PRNGKey(6), (n,), jnp.float32)

        def run(x):
            def body(carry, _):
                out, _flag = packed_scale(carry, 1.0000001, CHUNK,
                                          jnp.float32)
                return out, None
            x, _ = jax.lax.scan(body, x, None, length=k)
            return x
        return run, (x,)

    return build, 8.0 * n, _chunk_geometry(n)


def bench_mt_axpby(n: int):
    from apex_tpu.ops.pallas.multi_tensor_kernels import packed_axpby

    def build(k):
        x = jax.random.normal(jax.random.PRNGKey(7), (n,), jnp.float32)
        y = jax.random.normal(jax.random.PRNGKey(8), (n,), jnp.float32)

        def run(x, y):
            def body(carry, _):
                out, _flag = packed_axpby(carry, y, 0.999, 0.001, CHUNK,
                                          jnp.float32)
                return out, None
            x, _ = jax.lax.scan(body, x, None, length=k)
            return x
        return run, (x, y)

    return build, 12.0 * n, _chunk_geometry(n)


def bench_mt_sumsq(n: int):
    from apex_tpu.ops.pallas.multi_tensor_kernels import packed_sumsq

    def build(k):
        x = jax.random.normal(jax.random.PRNGKey(9), (n,), jnp.float32)

        def run(x):
            def body(carry, _):
                x, s = carry
                # O(1)-traffic dependence: the result feeds one element
                # back (scaled so the write is non-trivial but the value
                # drift is ~1e-13) — a literal *0.0 constant-folds away
                # and lets XLA hoist the whole kernel out of the loop
                # (measured: "1.3x roofline")
                r = packed_sumsq(x, CHUNK)
                x = x.at[0].add(r * 1e-20)
                return (x, s + r), None
            (x, s), _ = jax.lax.scan(body, (x, jnp.float32(0.0)), None,
                                     length=k)
            return s
        return run, (x,)

    return build, 4.0 * n, _chunk_geometry(n)


def _ln_geometry(rows: int, hidden: int,
                 block_rows: "int | None" = None) -> dict:
    from apex_tpu.ops.pallas.geometry import StreamGeometry
    from apex_tpu.ops.pallas.layer_norm_kernels import fwd_block_rows
    br = fwd_block_rows(rows, hidden, jnp.bfloat16, block_rows)
    return StreamGeometry(block_rows=br, lanes=hidden,
                          grid=-(-rows // br)).asdict()


def bench_layernorm_fwd(rows: int, hidden: int,
                        block_rows: "int | None" = None):
    from apex_tpu.ops.pallas import layer_norm_kernels as lnk

    def build(k):
        x = jax.random.normal(jax.random.PRNGKey(10), (rows, hidden),
                              jnp.bfloat16)
        w = jnp.ones((hidden,), jnp.float32)
        b = jnp.zeros((hidden,), jnp.float32)

        def run(x):
            def body(carry, _):
                # the kernel itself (the wrapper's reshape is free) so the
                # autotune sweep can pass the block override through
                y, _mean, _inv = lnk._forward(carry, w, b, 1e-5, True,
                                              block_rows=block_rows)
                return y, None
            x, _ = jax.lax.scan(body, x, None, length=k)
            return x
        return run, (x,)

    s = rows * hidden
    return build, 4.0 * s + 8.0 * rows, _ln_geometry(rows, hidden,
                                                     block_rows)


def bench_layernorm_fwd_bwd(rows: int, hidden: int):
    from apex_tpu.normalization.fused_layer_norm import (
        fused_layer_norm_affine)

    def build(k):
        x = jax.random.normal(jax.random.PRNGKey(11), (rows, hidden),
                              jnp.bfloat16)
        w = jnp.ones((hidden,), jnp.float32)
        b = jnp.zeros((hidden,), jnp.float32)

        def run(x):
            def body(carry, _):
                y, f_vjp = jax.vjp(
                    lambda t: fused_layer_norm_affine(t, w, b, hidden),
                    carry)
                (dx,) = f_vjp(y)   # dx feeds the next iteration
                return dx, None
            x, _ = jax.lax.scan(body, x, None, length=k)
            return x
        return run, (x,)

    s = rows * hidden
    # fwd geometry selected; bwd pinned at 128 rows (dγ/dβ accumulation
    # order is part of the digest contract)
    geom = _ln_geometry(rows, hidden)
    geom["bwd_block_rows"] = 128
    return build, 10.0 * s + 16.0 * rows, geom


#: Per-kernel autotune knob + candidate ladder (the geometry axis each
#: retuned kernel exposes as a static kwarg).  Fixed-chunk kernels have
#: no knob and are never swept.
AUTOTUNE_KNOBS = {
    "fused_adam": ("block_rows", (8, 32, 64, 128, 256)),
    "lamb_stage1": ("chunks_per_block", (1, 2, 4, 8, 16)),
    "lamb_stage2": ("chunks_per_block", (1, 2, 4, 8, 16)),
    "layernorm_fwd": ("block_rows", (64, 128, 256, 512)),
}


def suite_specs(tiny: bool = False) -> dict:
    """``{name: (bench_fn, args, iters)}`` — THE kernel suite table,
    shared with ``tools/bench_variance.py`` so a kernel added here (and
    to ``KERNEL_FLOORS``) is automatically variance-measurable.

    Buffers must EXCEED VMEM (~128 MB) or XLA keeps the scan carry
    resident and the measurement reads VMEM bandwidth, not HBM
    (observed: a 16 MB layer-norm carry "achieved" 18.7 TB/s).
    difference-quotient span: 5*iters extra device-seconds must dwarf
    the per-call overhead's jitter; cheap kernels need more steps,
    the ~20 ms LAMB stage-1 pass far fewer."""
    n = (1 << 16) if tiny else (1 << 26)            # 256 MB fp32 flats
    rows, hidden = (64, 512) if tiny else (1 << 17, 1024)  # 256 MB bf16

    def it(fast):
        return 4 if tiny else fast
    return {
        "fused_adam": (bench_fused_adam, (n,), it(60)),
        "lamb_stage1": (bench_lamb_stage1, (n,), it(30)),
        "lamb_stage2": (bench_lamb_stage2, (n,), it(40)),
        "mt_scale": (bench_mt_scale, (n,), it(150)),
        "mt_axpby": (bench_mt_axpby, (n,), it(150)),
        "mt_sumsq": (bench_mt_sumsq, (n,), it(300)),
        "layernorm_fwd": (bench_layernorm_fwd, (rows, hidden), it(150)),
        "layernorm_fwd_bwd": (bench_layernorm_fwd_bwd, (rows, hidden),
                              it(80)),
    }


def run_suite(tiny: bool = False, autotune: bool = False) -> dict:
    n = (1 << 16) if tiny else (1 << 26)
    rows, hidden = (64, 512) if tiny else (1 << 17, 1024)
    suite = suite_specs(tiny)
    bw = _hbm_peak(tiny)
    kernels = {}
    for name, (fn, args, iters) in suite.items():
        try:
            kw, sweep = {}, None
            if autotune and name in AUTOTUNE_KNOBS:
                knob, cands = AUTOTUNE_KNOBS[name]
                sweep = {}
                for cand in cands:
                    # per-candidate isolation: one over-budget geometry
                    # (e.g. a block whose double-buffered streams blow
                    # VMEM and fail Mosaic) must cost only its sweep
                    # entry, never the kernel's default-geometry record
                    # or its floor-gate coverage
                    try:
                        build, _, _ = fn(*args, **{knob: cand})
                        rejected = _lint_candidate(build)
                        if rejected:
                            # sanitizer-rejected geometry: recorded as
                            # a dict entry, so it is excluded from the
                            # timed table and can never be chosen
                            sweep[str(cand)] = \
                                {"lint_rejected": rejected}
                            continue
                        # short sweep timings (fewer steps, 2 trials):
                        # the knob's effect is way above the quotient's
                        # noise
                        sec = _time_scan(build, max(iters // 3, 2),
                                         trials=2)
                        sweep[str(cand)] = round(sec * 1e3, 4)
                    except Exception as e:  # noqa: BLE001
                        sweep[str(cand)] = \
                            {"error": f"{type(e).__name__}: {e}"[:120]}
                timed = {c: ms for c, ms in sweep.items()
                         if not isinstance(ms, dict)}
                if timed:  # all-failed sweep -> selector's default
                    kw = {knob: int(min(timed, key=timed.get))}
            build, nbytes, geom = fn(*args, **kw)
            sec = _time_scan(build, iters)
            gbps = nbytes / sec / 1e9
            kernels[name] = {
                "ms_per_step": round(sec * 1e3, 4),
                "gb_moved": round(nbytes / 1e9, 4),
                "gbps": round(gbps, 1),
                "roofline_frac": round(gbps * 1e9 / bw, 4),
                "iters": iters,
                "geometry": geom,
            }
            if sweep is not None:
                kernels[name]["autotune"] = {"swept_ms": sweep,
                                             "chosen": kw}
        except Exception as e:  # noqa: BLE001 - per-kernel isolation
            kernels[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
    return {"platform": jax.devices()[0].platform,
            "device_kind": getattr(jax.devices()[0], "device_kind", ""),
            "n_elements": n, "ln_shape": [rows, hidden],
            "hbm_gbps_peak": bw / 1e9, "kernels": kernels}


#: Published per-kernel roofline-fraction floors — the KERNELBENCH_r05
#: measured values rounded to two places (MFU_FLOORS convention: the
#: floor is the bar, the band absorbs chip-day variance; the gate trips
#: at floor × (1 − band)).  Floors RATCHET UP when a retune lands a
#: measured gain and may only move DOWN with a BENCH_VARIANCE.json entry
#: justifying the band (tests/l1/test_bench_units.py pins the rule).
KERNEL_FLOOR_BAND = 0.05
KERNEL_FLOORS = {
    "fused_adam": 0.30,
    "lamb_stage1": 0.17,
    "lamb_stage2": 0.12,
    "mt_scale": 0.75,
    "mt_axpby": 0.80,
    "mt_sumsq": 0.63,
    "layernorm_fwd": 0.34,
    "layernorm_fwd_bwd": 0.51,
}


def effective_kernel_floors(
        search_dir: "str | None" = None) -> "tuple[dict, dict]":
    """``({kernel: floor}, bands)`` — KERNEL_FLOORS after consulting
    the committed ``BENCH_VARIANCE_r*.json`` in ``search_dir``
    (default: this checkout) through ``bench.derive_floor_bands``
    (statistical floors where a qualifying ``kernel:<name>`` entry
    carries a ``roofline_frac`` stats block; the hand table as the
    frozen fallback, protected by the no-ratchet-down rule).  Falls
    back to the hand table when bench is unimportable — the gate must
    never silently disarm."""
    try:
        # bench.py may BE the running __main__ (python bench.py):
        # `import bench` would then re-execute its whole module —
        # resolve the already-loaded instance first
        bench = sys.modules.get("bench")
        if bench is None or not hasattr(bench, "effective_floors"):
            main_mod = sys.modules.get("__main__")
            if main_mod is not None and \
                    hasattr(main_mod, "effective_floors") and \
                    hasattr(main_mod, "derive_floor_bands"):
                bench = main_mod
            else:
                if str(REPO) not in sys.path:
                    sys.path.insert(0, str(REPO))
                import bench
        floors, bands = bench.effective_floors(
            KERNEL_FLOORS, search_dir or str(REPO), kind="kernel",
            stat="roofline_frac")
        return floors, bands
    except Exception:  # noqa: BLE001 - hand floors always stand
        return dict(KERNEL_FLOORS), {
            n: {"floor": f, "source": "hand", "provisional": False}
            for n, f in KERNEL_FLOORS.items()}


def check_kernel_floors(kernels: dict,
                        floors: "dict | None" = None) -> dict:
    """Absolute per-kernel efficiency gate: every measured kernel with a
    published floor must hold ``roofline_frac >= floor * (1 - band)``.
    ``floors`` overrides the hand table (``bench.py`` and ``main``
    pass the variance-derived effective floors; ``None`` = the
    published hand values).

    A gated kernel PRESENT in the map but errored (no roofline_frac —
    e.g. a geometry change that fails Mosaic compilation) fails the gate
    too, listed under ``errored``: a kernel that stops running entirely
    is the worst regression, and a gate that skips it fails open.
    Kernels absent from the map (partial runs) are merely not judged."""
    checked, violations, errored = {}, [], []
    for name, floor in (floors if floors is not None
                        else KERNEL_FLOORS).items():
        cur = kernels.get(name)
        if cur is None:
            continue
        if not isinstance(cur, dict) or not cur.get("roofline_frac"):
            errored.append(name)
            continue
        gate = floor * (1.0 - KERNEL_FLOOR_BAND)
        ok = cur["roofline_frac"] >= gate
        checked[name] = {"roofline_frac": cur["roofline_frac"],
                         "floor": floor, "gate": round(gate, 4), "ok": ok}
        if not ok:
            violations.append(name)
    return {"band": KERNEL_FLOOR_BAND, "checked": checked,
            "violations": violations, "errored": errored,
            "ok": not (violations or errored)}


def compare_kernels(prior_path: str, kernels: dict,
                    threshold: float = 0.10,
                    geometry: "dict | None" = None) -> dict:
    """Per-kernel step-time gate: worsening >threshold fails; faster is
    fine; kernels present on only one side are listed, never gated.

    ``geometry`` (``{"n_elements": ..., "ln_shape": ...}`` of the
    CURRENT run) must match the baseline's, or every delta would just
    measure the size change — mismatched baselines are recorded and
    never gated."""
    try:
        with open(prior_path) as f:
            doc = json.load(f)
        prior = doc.get("kernels")
        if not isinstance(prior, dict):
            raise ValueError("no kernels map")
    except (OSError, ValueError, TypeError) as e:
        return {"baseline": prior_path, "ok": True,
                "error": f"baseline unreadable: {e}"}
    if geometry is not None:
        prior_geom = {k: doc.get(k) for k in geometry}
        if prior_geom != geometry:
            return {"baseline": Path(prior_path).name, "ok": True,
                    "error": f"geometry mismatch: baseline {prior_geom}"
                             f" vs current {geometry} — not comparable"}
    deltas, regressions, uncompared = {}, [], []
    for name, cur in kernels.items():
        old = prior.get(name)
        if not (isinstance(old, dict) and old.get("ms_per_step")
                and isinstance(cur, dict) and cur.get("ms_per_step")):
            uncompared.append(name)
            continue
        delta = cur["ms_per_step"] / old["ms_per_step"] - 1.0
        deltas[name] = round(delta, 4)
        if delta > threshold:
            regressions.append(name)
    uncompared += [k for k in prior if k not in kernels]
    return {"baseline": Path(prior_path).name, "threshold": threshold,
            "deltas": deltas, "regressions": regressions,
            "uncompared": uncompared, "ok": not regressions}


def gate_exit_code(result: dict, compare_given: bool,
                   assert_floors: bool) -> int:
    """2 when the run must fail, else 0 — the bench.py pattern: the
    floor gate is ABSOLUTE (needs no baseline) once armed via
    ``--assert-floors``; the step-time delta gate stays opt-in via
    ``--compare``."""
    floors = result.get("floors") or {}
    if assert_floors and not floors.get("ok", True):
        return 2
    if compare_given and not result.get("compare", {}).get("ok", True):
        return 2
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(REPO / "KERNELBENCH.json"))
    ap.add_argument("--compare", default=None)
    ap.add_argument("--threshold", type=float, default=0.10)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes (CPU smoke; numbers meaningless)")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep each retunable kernel's geometry knob "
                         "and record the sweep alongside the winner")
    ap.add_argument("--assert-floors", action="store_true",
                    help="exit 2 when any kernel sits under its "
                         "published roofline-fraction floor (on-chip "
                         "gate; off-TPU the floors block is skipped)")
    args = ap.parse_args(argv)

    # A determinism-lint round name on a kernel-bench document is the
    # armed-gate-asserts-nothing failure: gate_hygiene would validate
    # the file against the DETLINT schema (and reject it), but until
    # then a DETLINT_rN.json full of microbenchmark timings asserts
    # nothing about tie-breaks or reduction shapes.  Refuse the name;
    # the sweep lives in tools/det_lint.py.
    if re.match(r"DETLINT_r\d+\.json$", Path(args.out).name):
        ap.error(f"--out {args.out}: DETLINT_rN.json is the "
                 "bitwise-determinism lint artifact family (emitted by "
                 "tools/det_lint.py or graph_lint --emit-json); a "
                 "kernel-bench document under that name would be "
                 "schema-rejected by gate_hygiene and, until then, "
                 "assert nothing the name promises")

    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    result = run_suite(tiny=args.tiny, autotune=args.autotune)
    # The floors block is ALWAYS recorded; roofline fractions are only
    # meaningful against a real HBM (off-chip the interpret-mode timings
    # measure the host), so off-TPU it records skipped and never gates.
    if result["platform"] == "tpu":
        # the gate consults the committed variance artifact: derived
        # statistical floors where evidence qualifies, the published
        # hand table otherwise (never looser without evidence)
        eff, bands = effective_kernel_floors()
        result["floors"] = check_kernel_floors(result["kernels"],
                                               floors=eff)
        result["floors"]["floor_sources"] = {
            n: b["source"] for n, b in bands.items()}
    else:
        result["floors"] = {
            "ok": True,
            "skipped": f"platform {result['platform']!r}: roofline "
                       "fractions only meaningful on TPU"}
    if args.compare:
        result["compare"] = compare_kernels(
            args.compare, result["kernels"], args.threshold,
            geometry={"n_elements": result["n_elements"],
                      "ln_shape": result["ln_shape"]})
    Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result))
    rc = gate_exit_code(result, bool(args.compare), args.assert_floors)
    if rc:
        print("kernel_bench: gate failed: step-time regressions "
              f"{result.get('compare', {}).get('regressions', [])}, "
              "floor violations "
              f"{result['floors'].get('violations', [])}",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
