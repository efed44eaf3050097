"""``benchmark/calibrate.py`` for a family that plants faults of its own.

Run by hand on the chip, at the cell's own size; a benchmark run never
calls it.  As ``calibrate.py`` it takes, for ``--seeds`` seeds, the
program's readings and the plain reference's and prints their gaps (the
largest is a limit's lower reading), and for the first ``--controls``
seeds puts stand-ins in the program's place (the smallest of a
stand-in's readings is an upper reading): the float8 ``control``, and
each fault of ``family.planted_faults(cfg, traffic)`` — the reference
under a changed configuration, on a part of each batch or (a fault's
third entry) told something else of the traffic: ``no_warmup`` and
``no_balance`` leave out a part of the cell's recipe.  (A batch of
one row has no half for ``calibrate.py``'s ``half_batch``; a family's
own ``half_tokens`` takes its place.)

With ``family.reference.chosen_experts`` it also counts, on the first
seed's first batch, the share of (token, expert) pairs that differ
between the float32 reference and the same reference with its matmul
operands rounded to bfloat16: what a bfloat16 program's routing can
differ from the reference's by rounding alone.
"""

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--controls", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=2_200_000_033)
    ap.add_argument("--out", required=True)
    ap.add_argument("--only", default="",
                    help="stand-ins to read, by name and comma (all)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from benchmark import run as bench_run
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
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

    made = train.make_step(cell, cfg, family, devices)
    init = jax.jit(lambda k: made["a"].init(weights.make(made["spec"], k)))
    compiled, got, batches = None, {}, {}
    for seed in seeds:
        rng = np.random.default_rng(seed)
        kept = [family.make_batch(rng, rows, cfg, traffic)
                for _ in range(n_steps + 2)]
        batches[seed] = kept[:n_steps]
        b = dict(state=init(weights.seed_key(seed)), spec=made["spec"],
                 key=weights.seed_key(seed), beta1=made["beta1"],
                 feed=prefetch_to_device(iter(kept), lookahead=2))
        b["first"] = next(b["feed"])
        if compiled is None:
            compiled = jax.jit(made["step_fn"], donate_argnums=(0,)).lower(
                b["state"], *b["first"]).compile()
        b["compiled"] = compiled
        got[seed], early = train.first_steps(b, n_steps)
        print(f"program seed {seed}: losses {got[seed]['losses']}, "
              f"overflow-skipped {sum(bool(m['overflow']) for m in early)}",
              flush=True)
        del b
    del compiled, made, init

    kw = train.reference_kwargs(cfg, traffic, devices)
    spec = family.reference.param_spec(cfg)

    def follow(seed, cfg_=cfg, part=None, told=None, **extra):
        kept = batches[seed] if part is None else [part(b)
                                                   for b in batches[seed]]
        kw_ = kw if told is None else train.reference_kwargs(
            cfg_, dict(traffic, **told), devices)
        return ref.follow(family.reference, cfg_, spec, seed, kept,
                          **dict(kw_, **extra))

    stand_ins = {"control": lambda seed: follow(seed,
                                                q=common.fp8_operands)}
    for name, (cfg_, part, *told) in family.planted_faults(
            cfg, traffic).items():
        stand_ins[name] = (lambda seed, cfg_=cfg_, part=part, told=told:
                           follow(seed, cfg_, part, *told))
    if args.only:
        stand_ins = {name: stand_ins[name] for name in args.only.split(",")}
    record = {"workload": args.workload, "seeds": seeds, "program": {},
              **{name: {} for name in stand_ins}}
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        want = follow(seed)
        record["program"][seed] = ref.gaps(got[seed], want)
        print(f"seed {seed}: program {record['program'][seed]} "
              f"(reference {time.perf_counter() - t:.1f} s)", flush=True)
        if n < args.controls:
            for name, stand_in in stand_ins.items():
                record[name][seed] = ref.gaps(stand_in(seed), want)
                print(f"seed {seed}: {name} {record[name][seed]}",
                      flush=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)

    if hasattr(family.reference, "chosen_experts"):
        def bf16_operands(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)

        with jax.default_matmul_precision("highest"):
            params = jax.jit(lambda k: weights.make(spec, k))(
                weights.seed_key(seeds[0]))
            ids = jnp.asarray(batches[seeds[0]][0][0])
            chosen = [np.asarray(jax.jit(
                lambda p, i, q=q: family.reference.chosen_experts(
                    p, i, cfg, q))(params, ids))
                for q in (common.identity, bf16_operands)]
        same = (chosen[0][..., :, None] == chosen[1][..., None, :]).any(-1)
        record["pairs_moved_by_bfloat16"] = {
            "share": float(1.0 - same.mean()),
            "by_layer": [float(1.0 - s.mean()) for s in same]}
        print(f"pairs moved by bfloat16 operands: "
              f"{record['pairs_moved_by_bfloat16']}", flush=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)

    for number in [k for k, v in record["program"][seeds[0]].items()
                   if isinstance(v, float)]:
        lower = max(r[number] for r in record["program"].values())
        uppers = {name: min(r[number] for r in record[name].values())
                  for name in stand_ins if record[name]}
        print(f"{number}: lower reading {lower:.6g}; upper readings "
              + ", ".join(f"{k} {v:.6g}" for k, v in uppers.items()),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
