"""Repeated-timing variance recorder — the statistics under the floors.

VERDICT r5 weak #6: every gate width in the repo (the 5% MFU band, each
floor, the kernel-bench 10% threshold) was calibrated from anecdote — a
same-day spread measured informally once, for one config, cited in a
commit message.  This tool records the statistic: N repeated timings per
config, written to ``BENCH_VARIANCE.json`` with mean/min/max and the
relative spread, so floor and band widths are DERIVED from recorded
variance — and so lowering a floor requires pointing at an entry (the
no-ratchet-down rule ``tests/l1/test_bench_units.py`` enforces over
``bench.py``'s floor tables).

Two entry kinds:

- ``kernel:<name>`` — repeats of ``tools/kernel_bench.py``'s per-kernel
  difference-quotient timing (ms_per_step).  Cheap enough for N≥5 on
  chip; the CPU-tiny smoke keeps the tool runnable in tier-1.
- ``config:<name>`` — repeats of a ``bench.py`` model config's rate
  metric (img_s / tok_s / seq_s) and MFU.  Chip-expensive; run a small
  set across the round's days.

The artifact is a gate baseline: ``tools/gate_hygiene.py`` fails tier-1
when it is modified-but-uncommitted, and round-numbered artifacts
(``--round N`` → ``BENCH_VARIANCE_rNN.json``) are additionally
schema-validated (``apex_tpu/analysis/variance.py``: recorded
mean/min/max/std/rel_spread must agree with the recorded samples — a
spread that excuses a floor drop must be derivable, not typed in).

Each entry records ``std`` (sample standard deviation) next to the
spread, plus the gate statistics the floors actually ride: kernels
carry a ``roofline_frac`` sub-stat block, configs an ``mfu`` and (for
decode configs) an ``hbm_frac`` block — so
``bench.derive_floor_bands()`` computes ``floor = mean − k·std`` on
exactly the gated statistic, and ``tools/perf_timeline.py`` reads
per-series band widths from the same entries.

Usage: python tools/bench_variance.py [--out BENCH_VARIANCE.json]
       [--round N] [--n 5] [--kernels fused_adam,mt_scale,...]
       [--configs resnet50_o2,gpt_small_o2] [--tiny]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

import jax  # noqa: E402


def _stats(values):
    # summarize the ROUNDED samples the record actually stores, so the
    # schema validator (apex_tpu/analysis/variance.py) can re-derive
    # every summary statistic from the recorded values exactly.
    # SIGNIFICANT digits, not fixed decimals: a sub-microsecond tiny-
    # smoke timing must not round to 0.0 and destroy the stats block
    values = [float(f"{v:.6g}") for v in values]
    mean = sum(values) / len(values)
    # sample standard deviation: the "spread" in the derived-floor
    # formula floor = mean - k*std (0.0 for a single sample — which
    # derive_floor_bands refuses anyway via its min-samples rule)
    std = (sum((v - mean) ** 2 for v in values)
           / (len(values) - 1)) ** 0.5 if len(values) > 1 else 0.0
    return {
        "n": len(values),
        "values": values,
        "mean": float(f"{mean:.6g}"),
        "min": min(values),
        "max": max(values),
        "std": float(f"{std:.6g}"),
        # the band-width statistic: worst-case same-artifact swing
        "rel_spread": round((max(values) - min(values)) / mean, 4)
        if mean else None,
    }


def measure_kernels(names, n: int, tiny: bool) -> dict:
    """N independent difference-quotient timings per kernel (each repeat
    re-times both scan lengths, so the spread includes the quotient's
    own noise — the statistic the kernel floor band must cover).  The
    suite table is ``kernel_bench.suite_specs`` itself, so every gated
    kernel is variance-measurable by construction."""
    import kernel_bench as kb

    specs = kb.suite_specs(tiny)
    entries = {}
    for name in names:
        if name not in specs:
            entries[f"kernel:{name}"] = {"error": "unknown kernel"}
            continue
        try:
            fn, args, iters = specs[name]
            build, nbytes, geom = fn(*args)
            vals = [kb._time_scan(build, iters) * 1e3 for _ in range(n)]
            entry = {"metric": "ms_per_step", "geometry": geom,
                     **_stats(vals)}
            # the GATED statistic: per-repeat roofline fraction (the
            # KERNEL_FLOORS unit), so derive_floor_bands computes
            # mean - k*std on exactly what the floor gates.  A repeat
            # whose difference quotient collapsed to <= 0 (tiny-smoke
            # noise) has no meaningful fraction — skip the block
            # rather than divide by it
            if all(ms > 0 for ms in vals):
                bw = kb._hbm_peak(tiny)
                entry["roofline_frac"] = _stats(
                    [nbytes / (ms * 1e-3) / bw for ms in vals])
            entries[f"kernel:{name}"] = entry
        except Exception as e:  # noqa: BLE001 - per-entry isolation
            entries[f"kernel:{name}"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}
    return entries


def measure_configs(names, n: int, tiny: bool) -> dict:
    """N repeats of a bench.py model config's rate + MFU (the model-gate
    statistic).  Uses the same bench functions and argument sets as
    ``bench.py main`` so the variance is measured on exactly the gated
    config."""
    import bench

    from apex_tpu.utils.chip_peaks import chip_peak

    # full size measures the chip (an unknown chip is an error); --tiny
    # is the explicit CPU smoke, marked as such in the artifact
    on_tpu = not tiny
    peak = chip_peak().bf16_flops_per_s if on_tpu else None
    if on_tpu:
        rn = dict(batch=256, size=224, warmup=4, iters=20)
        gpt = dict(batch=8, seq=2048, warmup=3, iters=12, tiny=False)
        bert = dict(batch=16, seq=512, warmup=3, iters=10, tiny=False)
    else:
        rn = dict(batch=8, size=64, warmup=1, iters=3)
        gpt = dict(batch=2, seq=64, warmup=1, iters=3, tiny=True)
        bert = dict(batch=2, seq=64, warmup=1, iters=3, tiny=True)
    # every MFU_FLOORS config is measurable here (the no-ratchet-down
    # rule requires an entry to lower any floor), args mirroring
    # bench.py main's
    fns = {
        "resnet50_o2": lambda: bench.bench_resnet(opt_level="O2",
                                                  peak=peak, **rn),
        "resnet50_o3": lambda: bench.bench_resnet(opt_level="O3",
                                                  peak=peak, **rn),
        "resnet50_s2d_o2": lambda: bench.bench_resnet(
            opt_level="O2", s2d=True, peak=peak, **rn),
        "gpt_small_o2": lambda: bench.bench_gpt(peak=peak, **gpt),
        "gpt_small_tpu_heads_o2": lambda: bench.bench_gpt(
            tpu_heads=True, peak=peak, **gpt),
        "gpt_small_tpu_heads_L8192_o2": lambda: bench.bench_gpt(
            tpu_heads=True, remat=True, peak=peak,
            **dict(gpt, batch=2 if on_tpu else gpt["batch"],
                   seq=8192 if on_tpu else gpt["seq"])),
        "gpt_small_tpu_heads_L16384_o2": lambda: bench.bench_gpt(
            tpu_heads=True, remat=True, peak=peak,
            **dict(gpt, batch=1 if on_tpu else gpt["batch"],
                   seq=16384 if on_tpu else gpt["seq"])),
        "gpt_medium_tpu_o2": lambda: bench.bench_gpt(
            tpu_heads="medium" if on_tpu else True, peak=peak, **gpt),
        "bert_large_lamb_o2": lambda: bench.bench_bert(peak=peak, **bert),
        "bert_large_tpu_heads_lamb_o2": lambda: bench.bench_bert(
            tpu_heads=True, peak=peak, **bert),
    }
    # the DECODE_FLOORS configs: hbm_frac is their gated statistic, so
    # a chip round can justify (or refuse) a decode-floor move with
    # the same recorded-variance rule the MFU floors ride — including
    # the kv8 config whose CPU-seeded placeholder floor stays
    # provisional until an entry lands here
    if on_tpu:
        dec = dict(batch=8, prefill=2048, new_tokens=256, warmup=1,
                   iters=4, tiny=False)
    else:
        dec = dict(batch=2, prefill=16, new_tokens=8, warmup=0,
                   iters=1, tiny=True)
    fns.update({
        "gpt_small_tpu_decode_b1": lambda: bench.bench_generate(
            peak=peak, **dict(dec, batch=1)),
        "gpt_small_tpu_decode_b8": lambda: bench.bench_generate(
            peak=peak, **dec),
        "gpt_small_tpu_decode_kv8": lambda: bench.bench_generate(
            peak=peak, kv_dtype="int8", **dec),
    })
    entries = {}
    for name in names:
        if name not in fns:
            entries[f"config:{name}"] = {"error": "unknown config"}
            continue
        try:
            rates, mfus, fracs, key = [], [], [], None
            for _ in range(n):
                res = fns[name]()
                key = next(k for k in bench.RATE_KEYS if res.get(k))
                rates.append(float(res[key]))
                if res.get("mfu"):
                    mfus.append(float(res["mfu"]))
                if isinstance(res.get("hbm_frac"), (int, float)):
                    fracs.append(float(res["hbm_frac"]))
            entries[f"config:{name}"] = {"metric": key, **_stats(rates)}
            if mfus:
                entries[f"config:{name}"]["mfu"] = _stats(mfus)
            if fracs:
                entries[f"config:{name}"]["hbm_frac"] = _stats(fracs)
        except Exception as e:  # noqa: BLE001 - per-entry isolation
            entries[f"config:{name}"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}
    return entries


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="output path (default BENCH_VARIANCE.json, or "
                         "BENCH_VARIANCE_rNN.json with --round)")
    ap.add_argument("--round", type=int, default=None,
                    help="emit the round-numbered, schema-validated "
                         "gate artifact BENCH_VARIANCE_rNN.json")
    ap.add_argument("--n", type=int, default=5)
    ap.add_argument("--kernels", default="fused_adam,lamb_stage1,mt_scale")
    ap.add_argument("--configs", default="",
                    help="comma-separated bench.py configs (chip-"
                         "expensive; empty = none)")
    ap.add_argument("--tiny", action="store_true",
                    help="tiny shapes (CPU smoke; spreads meaningless)")
    args = ap.parse_args(argv)
    if args.out is None:
        args.out = str(REPO / (f"BENCH_VARIANCE_r{args.round:02d}.json"
                               if args.round is not None
                               else "BENCH_VARIANCE.json"))

    entries = {}
    if args.kernels:
        entries.update(measure_kernels(
            [k for k in args.kernels.split(",") if k], args.n, args.tiny))
    if args.configs:
        entries.update(measure_configs(
            [c for c in args.configs.split(",") if c], args.n, args.tiny))
    result = {
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind", ""),
        "tiny": args.tiny,
        "entries": entries,
    }
    if args.round is not None:
        result["round"] = args.round
        # a round-numbered artifact is gate memory: refuse to write an
        # invalid one (the same pre-flight serve_scenarios runs)
        from apex_tpu.analysis.variance import validate_variance
        problems = validate_variance(result)
        if problems:
            print(f"bench_variance: REFUSING schema-invalid artifact: "
                  f"{problems}", file=sys.stderr)
            return 1
    Path(args.out).write_text(json.dumps(result, indent=1) + "\n")
    print(json.dumps(result))
    # errors are per-entry records, not exit failures: partial variance
    # evidence beats none after the chip time is spent
    return 0


if __name__ == "__main__":
    sys.exit(main())
