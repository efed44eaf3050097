"""Read the two ends every limit of ``correct`` is set between.

Run by hand on the chip, at the cell's own size; a benchmark run never
calls it.  For ``--seeds`` seeds it takes the program's readings (the
compiled step driven through its first steps, as a run does) and the
plain reference's, and prints their gaps: the largest over the seeds is
a limit's lower reading.  For the first ``--controls`` seeds it also
puts stand-ins in the program's place and prints the same gaps:

- ``control``: the reference with its matmul operands in float8 (e4m3
  forward, e5m2 for the gradients coming back, per-tensor scales), the
  precision step below the bfloat16 that the configurations state;
- ``half_batch``: the reference on the first half of every batch's
  rows, the mean taken over those;
- ``no_exchange`` (a cell on several chips): the reference on the first
  chip's rows alone, which is what that chip's weights follow when the
  gradient exchange is left out.

(A step that returns its state unchanged needs no run: its first moment
and its parameters' change are nought, which reads 1 in ``grad_gap``
and ``delta_gap``.)  The smallest of a stand-in's readings over the
seeds is an upper reading.  The gaps are written to ``--out`` as JSON and
every reading behind them, leaf by leaf, to ``<out>.leaves``.
"""

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_033)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run as bench_run
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")

    import jax
    import numpy as np
    from apex_tpu.data import prefetch_to_device
    from apex_tpu.utils import compile_cache

    from benchmark import weights
    from benchmark.drivers import train
    from benchmark.reference import common, train as ref

    _, cell, cfg, family, _ = bench_run.resolve(args.workload, args.rehearse)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.rehearse:
        sys.exit("no accelerator")
    devices = devices[:cell["chips"]]
    compile_cache.enable()
    traffic = cell["parameters"]
    rows = traffic["rows_per_chip"] * cell["chips"]
    n_steps = traffic["reference_steps"]
    seeds = [args.first_seed + 7_919 * i for i in range(args.seeds)]

    # the program, one compiled step for all the seeds
    made = train.make_step(cell, cfg, family, devices)
    init = jax.jit(lambda k: made["a"].init(weights.make(made["spec"], k)),
                   out_shardings=made["replicated"])
    compiled, got, batches = None, {}, {}
    for seed in seeds:
        rng = np.random.default_rng(seed)
        kept = [family.make_batch(rng, rows, cfg, traffic)
                for _ in range(n_steps + 2)]
        batches[seed] = kept[:n_steps]
        b = dict(state=init(weights.seed_key(seed)), spec=made["spec"],
                 key=weights.seed_key(seed), beta1=made["beta1"],
                 feed=prefetch_to_device(iter(kept), lookahead=2,
                                         sharding=made["by_rows"]))
        b["first"] = next(b["feed"])
        if compiled is None:
            compiled = jax.jit(made["step_fn"], donate_argnums=(0,)).lower(
                b["state"], *b["first"]).compile()
        b["compiled"] = compiled
        got[seed], early = train.first_steps(b, n_steps)
        skipped = sum(bool(m["overflow"]) for m in early)
        print(f"program seed {seed}: losses {got[seed]['losses']}, "
              f"overflow-skipped {skipped}", flush=True)
        del b
    del compiled, made, init

    kw = train.reference_kwargs(cfg, traffic, devices)

    def follow(seed, **extra):
        return ref.follow(family.reference, cfg, family.reference.param_spec(
            cfg), seed, batches[seed], **dict(kw, **extra))

    stand_ins = {"control": dict(q=common.fp8_operands),
                 "half_batch": dict(rows=rows // 2,
                                    block_rows=max(1, kw["block_rows"] // 2))}
    if cell["chips"] > 1:
        stand_ins["no_exchange"] = dict(
            rows=rows // cell["chips"],
            block_rows=max(1, kw["block_rows"] // cell["chips"]),
            devices=devices[:1])
    record = {"workload": args.workload, "seeds": seeds, "program": {},
              **{name: {} for name in stand_ins}}
    leaves = {}        # every reading, leaf by leaf, for a closer look
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        want = follow(seed)
        leaves[seed] = {"program": got[seed], "reference": want}
        record["program"][seed] = ref.gaps(got[seed], want)
        print(f"seed {seed}: program {record['program'][seed]} "
              f"(reference {time.perf_counter() - t:.1f} s)", flush=True)
        if n < args.controls:
            for name, extra in stand_ins.items():
                leaves[seed][name] = follow(seed, **extra)
                record[name][seed] = ref.gaps(leaves[seed][name], want)
                print(f"seed {seed}: {name} {record[name][seed]}",
                      flush=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)
        with open(args.out + ".leaves", "w") as f:
            json.dump(leaves, f)

    for number in ("loss_gap", "grad_gap", "delta_gap"):
        lower = max(r[number] for r in record["program"].values())
        uppers = {name: min(r[number] for r in record[name].values())
                  for name in stand_ins if record[name]}
        print(f"{number}: lower reading {lower:.6g}; upper readings "
              + ", ".join(f"{k} {v:.6g}" for k, v in uppers.items()),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
