"""Profile one training-step config and print the top device-time ops.

Usage: python tools/profile_step.py [resnet50|gpt|bert] [opt_level]
       python tools/profile_step.py --train-buckets [mlp|gpt|bert|resnet50]
           [--opt-level O1] [--iters 4]

Captures an XProf trace of a few steps, parses the xplane protobuf
directly (tensorflow's tsl proto is in the image; no tensorboard UI
needed) and aggregates device time by HLO category and by op on the
TPU plane — the "profile one step and act on the top hotspot" loop of
VERDICT r1 item 3.  The chrome-trace JSON export is lossy here (op-level
events are missing for large programs); the xplane is complete.

``--train-buckets`` is the op-level lane: it lowers the EXACT amp
train step graph_lint lints (``graph_lint.build_train_step``),
captures its dispatches, and folds the measured op times into the
pinned train-step vocabulary — fwd / bwd / optimizer / collectives /
host_gap — through the SHARED classifier
(:class:`apex_tpu.obs.stepclass.TrainStepClassifier`, built from the
compiled HLO's ``op_name`` metadata scopes).  The continuous profiler
(:mod:`apex_tpu.obs.contprof`) buckets its online training windows
through the same class, so this offline table and the live sentinel
can never disagree about what "bwd" means; the classifier's behavior
is pinned by the fixture test in ``tests/l0/test_contprof.py``.
"""

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

import jax

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

# the xplane/chrome-trace walk lives in the obs library now (one
# parser for every profile tool; behavior pinned by the obs fixture
# tests) — this tool only drives the capture and prints the table
from apex_tpu.obs.xplane import (  # noqa: E402
    bucket_op_times,
    op_times,
    parse_xplane,
)


def build(model_name: str, opt_level: str):
    import bench
    from apex_tpu.utils.chip_peaks import chip_peak

    peak = chip_peak().bf16_flops_per_s
    if model_name == "gpt":
        # same config as bench.py's headline GPT entry (keep in sync)
        fn = lambda: bench.bench_gpt(batch=8, seq=2048, warmup=2, iters=8,
                                     peak=peak, tiny=False)
    elif model_name == "bert":
        fn = lambda: bench.bench_bert(batch=16, seq=512, warmup=2, iters=8,
                                      peak=peak, tiny=False)
    else:
        fn = lambda: bench.bench_resnet(opt_level, batch=256, size=224,
                                        warmup=2, iters=8, peak=peak)
    return fn


def category_profile(model_name: str, opt_level: str) -> None:
    """The historical lane: capture a bench config, print device time
    by hlo_category and the top ops."""
    fn = build(model_name, opt_level)
    fn()  # warm compile outside the trace
    logdir = f"/tmp/apex_tpu_prof_{model_name}_{opt_level}"
    shutil.rmtree(logdir, ignore_errors=True)  # stale xplanes would
    # double-count: the parser aggregates every file under the logdir
    with jax.profiler.trace(logdir):
        out = fn()
    time.sleep(1)
    print(json.dumps(out))
    by_name, by_cat, total = parse_xplane(logdir)
    print(f"device XLA-op time by category, total {total / 1e12:.3f}s:")
    for cat, dur in by_cat.most_common():
        print(f"  {dur / 1e9:10.1f}ms {100 * dur / max(total, 1):5.1f}%  "
              f"{cat}")
    print("top ops:")
    for name, dur in by_name.most_common(25):
        print(f"  {dur / 1e9:10.1f}ms {100 * dur / max(total, 1):5.1f}%  "
              f"{name[:100]}")


def train_bucket_profile(family: str, opt_level: str,
                         iters: int = 4) -> dict:
    """The op-level lane: capture the exact graph_lint train step and
    fold measured op time into the pinned train vocabulary through
    the SHARED classifier (the one the continuous profiler uses)."""
    import graph_lint

    from apex_tpu.obs.stepclass import TRAIN_BUCKETS, TrainStepClassifier

    step, args, _props = graph_lint.build_train_step(
        family, opt_level=opt_level)
    state, *batch = args
    compiled_txt = step.lower(state, *batch).compile().as_text()
    clf = TrainStepClassifier(compiled_txt)

    state, metrics = step(state, *batch)       # compile outside trace
    jax.block_until_ready(metrics["loss"])
    logdir = f"/tmp/apex_tpu_prof_train_{family}_{opt_level}"
    shutil.rmtree(logdir, ignore_errors=True)
    with jax.profiler.trace(logdir):
        # wall of the STEPS only — trace start/stop is capture
        # overhead (the contprof OBS lane gates it), not step time
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = step(state, *batch)
        jax.block_until_ready(metrics["loss"])
        wall_s = time.perf_counter() - t0
    time.sleep(0.5)

    times = op_times(logdir)
    step_ops = clf.step_ops()
    step_times = {n: ps for n, ps in times.by_op.items()
                  if n in step_ops}
    named = [b for b in TRAIN_BUCKETS if b not in ("other",
                                                   "host_gap")]
    table = bucket_op_times(step_times, clf, buckets=named)
    bucket_ps = dict(table["bucket_ps"])
    total = table["total_ps"]
    # host_gap = the wall the capture held that no attributed device
    # op explains (thread-summed CPU captures can exceed wall: 0)
    gap = max(0, int(wall_s * 1e12) - total)
    bucket_ps["host_gap"] = gap
    total += gap
    return {
        "family": family, "opt_level": opt_level, "iters": iters,
        "source": times.source,
        "wall_s": round(wall_s, 4),
        "bucket_ps": {b: int(bucket_ps.get(b, 0))
                      for b in TRAIN_BUCKETS},
        "fractions": {b: (round(bucket_ps.get(b, 0) / total, 4)
                          if total else 0.0) for b in TRAIN_BUCKETS},
        "matched_frac": round(table["matched_ps"]
                              / max(table["total_ps"], 1), 4),
        "step_ops_profiled": len(step_times),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("model", nargs="?", default="resnet50")
    ap.add_argument("opt_level", nargs="?", default="O2")
    ap.add_argument("--train-buckets", metavar="FAMILY", default=None,
                    help="bucket the FAMILY amp train step's measured "
                         "op time into the pinned fwd/bwd/optimizer/"
                         "collectives/host_gap vocabulary (shared "
                         "classifier) instead of the category table")
    ap.add_argument("--opt-level", dest="opt_flag", default=None)
    ap.add_argument("--iters", type=int, default=4)
    opts = ap.parse_args(argv)
    if opts.train_buckets:
        doc = train_bucket_profile(
            opts.train_buckets, opts.opt_flag or opts.opt_level,
            iters=opts.iters)
        print(json.dumps(doc, indent=1, sort_keys=True))
        return 0
    category_profile(opts.model, opts.opt_level)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
