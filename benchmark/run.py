"""Run one cell once and print its result line.

``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of the checkout.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: every number ``correct`` rests on
beside its limit.  The lines before it carry what those keys have no
room for.

Nothing here names a cell, a configuration, a family, a driver or a
metric: each is a file found by the name ``BENCHMARK.json`` or another
file gives (``workloads/<cell>.json``, the configuration's ``file``,
``families/<family>.py``, ``drivers/<driver>.py``,
``metrics/<metric>.json`` and ``readers/<reader>.py``).

``--rehearse`` runs the same control flow at the files' ``rehearse``
sizes on the CPU (four virtual devices): it reports ``"platform":
"cpu"``, ``"rehearsal": true`` and no metric, so no check takes its
line for a chip run.  Without it a machine with no chip is an error.
"""

import time
_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def entry(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"BENCHMARK.json has no {what} named {name!r}; it has "
                     f"{[e['name'] for e in entries]}")


def resolve(workload: str, rehearse: bool, root: str = ROOT):
    """The cell, its configuration, family and driver, by name."""
    bench = load(os.path.join(root, "BENCHMARK.json"))
    listed = entry(bench["workloads"], workload, "workload")
    cell = load(os.path.join(root, "benchmark", "workloads",
                             workload + ".json"))
    for key in ("config", "traffic", "chips"):
        if cell[key] != listed[key]:
            raise SystemExit(f"{workload}: {key} is {listed[key]!r} in "
                             f"BENCHMARK.json and {cell[key]!r} in its file")
    cfg = load(os.path.join(root, entry(bench["configs"], cell["config"],
                                        "configuration")["file"]))
    if rehearse:
        # tiny sizes, and the limits read at them on the CPU: they let the
        # tests see ``correct`` turn with a planted fault, nothing more
        cfg.update(cfg.get("rehearse", {}))
        cell["parameters"].update(cell.get("rehearse", {}))
        cell["limits"] = cell.get("rehearse_limits", cell["limits"])
    family = importlib.import_module("benchmark.families." + cfg["family"])
    driver = importlib.import_module("benchmark.drivers." + cell["driver"])
    return bench, cell, cfg, family, driver


def metric_names(bench: dict, cell: dict, group: str) -> list:
    """The metrics of ``group`` this cell reports."""
    return [m["name"] for m in bench[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def read_per_layer(bench: dict, cell: dict, run, root: str = ROOT) -> dict:
    out = {}
    for name in metric_names(bench, cell, "per_layer"):
        spec = load(os.path.join(root, "benchmark", "metrics",
                                 name + ".json"))
        value = importlib.import_module(
            "benchmark.readers." + spec["reader"]).read(run)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out


def judge(compared: dict, limits: dict) -> "tuple[bool, dict]":
    """Each number beside its limit.  A number whose limit is null is
    shown and not judged; at least one has to be judged."""
    table = {k: {"value": v, "limit": limits.get(k)}
             for k, v in compared.items()}
    judged = [row for row in table.values() if row["limit"] is not None]
    return bool(judged) and all(r["value"] <= r["limit"] for r in judged), \
        table


def main(argv=None, step_wrapper=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes, no metric: control flow only")
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the trace under .bench_trace/")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)

    bench, cell, cfg, family, driver = resolve(args.workload, args.rehearse)
    out = driver.run(cell, cfg, family, args, _T_START, ROOT,
                     step_wrapper=step_wrapper)

    correct, table = judge(out["compared"], cell["limits"])
    devices = out["devices"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": out["run"].memory_peak_bytes}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
    if args.rehearse:
        metrics = {}
    elif args.trace:
        metrics = read_per_layer(bench, cell, out["run"])
        device.update(out["busy"])
    else:
        metrics = {name: {"value": out["end_to_end"][name],
                          "unit": units[name]}
                   for name in metric_names(bench, cell, "end_to_end")}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    if args.rehearse:
        line["rehearsal"] = True
    if args.trace and not args.rehearse:
        line["breakdown"] = out["breakdown"]
        for k, v in sorted(out["run"].notes.items()):
            print(f"note: {k} = {v}", flush=True)
    line["compared"] = table
    sys.stdout.flush()
    for k, row in table.items():
        print(f"compared {k}: {row['value']:.6g} (limit {row['limit']})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
