"""Virtual-mesh scaling sweep: the seven dryrun slices at 8-64 devices.

BASELINE.md names a "Scaling sweep 8 -> 64 chips" metric; real multi-chip
hardware is not reachable from this rig, so the sweep runs every slice of
``__graft_entry__.dryrun_multichip`` on virtual CPU meshes of n in
{8, 16, 32, 64} devices and **asserts the analytic collective-volume
scaling laws** a correct sharding implies.  Each world size runs in a
fresh subprocess (``--xla_force_host_platform_device_count`` must be set
before backend init), compiles + executes one step, and reports the
per-device HLO collective audit.

The slices scale the axis under test with the world size while holding
every per-device shard constant, so the per-device *static* collective
volumes obey exact laws:

- ``dp_syncbn`` (data axis = n): gradient + BatchNorm-stat all-reduce
  bytes are **constant** — per-device volume independent of world size
  is exactly what makes data parallelism scale.
- ``dp_sp_ring`` (ring sp = n/4, fixed L/sp shard): per-iteration
  ``collective-permute`` bytes constant; the ring loop runs ``sp`` trips
  (`lax.fori_loop``), so the **executed** ring volume derived as
  ``static x sp`` grows linearly — the ring law.  DP grad all-reduce
  stays constant.
- ``dp_tp_pjit`` (model axis = n/4, hidden = 16*tp): activation
  partial-sum + grad all-reduce bytes constant (Megatron sharding keeps
  both activations and weight shards per-device constant).
- ``pipeline`` (depth = n, constant microbatch): per-tick permute bytes
  constant; executed volume derived as ``static x (M + S - 1)`` per the
  GPipe schedule (M = S microbatches).
- ``expert`` (experts = 2n, constant per-device tokens): ``all-to-all``
  bytes follow the exchange buffer ``ranks * room * d`` with ``room =
  T_local * min(k, e_local)``, the most pairs one rank's tokens can send
  another (no capacity, no dropped token): **linear in the number of
  ranks** from the start — what a static-shaped exchange that drops
  nothing pays, and the reason production MoE bounds how many ranks a
  token may reach.  The sweep asserts the formula.
- ``fsdp`` (hidden = 16n, constant shard): the compute all-gather
  reconstitutes the FULL parameter, so its bytes grow **linearly with
  n** — ZeRO-3's bandwidth cost — while grad reduction stays constant
  per device.
- ``dp_tp_sp_3d``: permute + all-reduce constant (composition preserves
  the per-axis laws).

At world 64 the sweep additionally runs ``dp_syncbn`` with
``gradient_predivide_factor=64`` (pre-divide by f, post-divide by
world/f — the large-world overflow-headroom knob, reference
``apex/parallel/distributed.py:387-393``) and asserts the updated master
params match the default reduction to fp32 round-off.

Usage:
  python tools/scaling_sweep.py              # full sweep 8..64 + laws
  python tools/scaling_sweep.py --ns 8 16    # subset (tests use this)
  python tools/scaling_sweep.py --child 16   # one world size (internal)

Writes ``SCALING_SWEEP.json`` at the repo root and exits nonzero if any
slice fails or any law is violated.
"""

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

RECORD_TAG = "SWEEP_RECORD "
DEFAULT_NS = (8, 16, 32, 64)
PREDIVIDE_WORLD = 64
#: const-law tolerance: per-device programs are shape-identical across n,
#: so audits should match to the byte; a small band absorbs incidental
#: scalar bookkeeping (loss counters) XLA may fold differently.
CONST_RTOL = 0.02
#: linear-law tolerance (fsdp all-gather, derived executed volumes)
LINEAR_RTOL = 0.05


def sweep_topology(n: int) -> dict:
    """Axis sizes under test per slice at world n (doc table above)."""
    return {"sp": max(2, n // 4), "tp": max(2, n // 4), "stages": n}


def expert_alltoall_scale(n: int) -> float:
    """Analytic per-device all-to-all buffer volume of the expert slice,
    up to a constant factor: ``ranks * room`` with the slice's
    ``T_local=16, e_local=2, k=1`` (``apex_tpu/parallel/moe.py``
    ``_exchanged``: a slot of ``room = T_local * min(k, e_local)`` rows
    for every rank)."""
    t_local, e_local, k = 16, 2, 1
    return float(n * t_local * min(k, e_local))


def child_main(n: int) -> None:
    """Run the scaled slices on an n-device virtual CPU mesh; print one
    JSON record per slice (``SWEEP_RECORD`` lines; parent parses)."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        .replace("--xla_force_host_platform_device_count=8", "").strip()
        + f" --xla_force_host_platform_device_count={n}").strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ["APEX_TPU_KERNELS"] = "jnp"  # see dryrun_multichip

    import numpy as np

    import __graft_entry__ as graft

    devices = jax.devices("cpu")[:n]
    if len(devices) < n:
        raise RuntimeError(f"need {n} CPU devices, have {len(devices)}")

    topo = sweep_topology(n)
    sp, tp, stages = topo["sp"], topo["tp"], topo["stages"]
    slices = [
        ("dp_syncbn", lambda d: graft._build_dp_syncbn(d)),
        ("dp_sp_ring", lambda d: graft._build_dp_sp(d, sp=sp)),
        ("dp_tp_pjit", lambda d: graft._build_dp_tp(d, tp=tp)),
        ("pipeline", lambda d: graft._build_pp(d, n_stages=stages)),
        ("expert", lambda d: graft._build_ep(d)),
        ("fsdp", lambda d: graft._build_fsdp(d)),
        ("dp_tp_sp_3d", lambda d: graft._build_dp_tp_sp(d, sp=sp)),
    ]
    for name, build in slices:
        rec = graft._run_slice(name, build, devices)
        rec["n"] = n
        rec["topology"] = topo
        print(RECORD_TAG + json.dumps(rec), flush=True)

    if n >= PREDIVIDE_WORLD:
        rec = {"name": "predivide_parity", "n": n, "ok": False}
        try:
            step_a, args_a, _ = graft._build_dp_syncbn(devices)
            out_a = step_a(*args_a)
            jax.block_until_ready(out_a)
            step_b, args_b, _ = graft._build_dp_syncbn(
                devices, predivide=float(n))
            out_b = step_b(*args_b)
            jax.block_until_ready(out_b)
            # out = (state, stats, loss, scale); master params fp32
            diffs = [
                float(np.max(np.abs(np.asarray(la) - np.asarray(lb))))
                for la, lb in zip(
                    jax.tree.leaves(out_a[0].master_params),
                    jax.tree.leaves(out_b[0].master_params))
            ]
            rec["max_abs_param_diff"] = max(diffs)
            rec["loss_a"] = float(out_a[2])
            rec["loss_b"] = float(out_b[2])
            rec["gradient_predivide_factor"] = float(n)
            # predivide only reassociates the mean (g/f summed, then
            # x f/world) — parity is fp32 round-off away from exact;
            # Adam-normalized updates bound any drift by ~2*lr
            rec["ok"] = bool(rec["max_abs_param_diff"] < 2.5e-3
                             and abs(rec["loss_a"] - rec["loss_b"]) < 1e-5)
        except Exception as e:  # noqa: BLE001 - recorded, parent fails
            rec["error"] = f"{type(e).__name__}: {e}"
        print(RECORD_TAG + json.dumps(rec), flush=True)


def run_child(n: int, timeout: int = 1200):
    """-> (records, error|None) from a fresh-process child at world n."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    p = subprocess.run(
        [sys.executable, str(REPO / "tools" / "scaling_sweep.py"),
         "--child", str(n)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=str(REPO))
    records = [json.loads(line[len(RECORD_TAG):])
               for line in p.stdout.splitlines()
               if line.startswith(RECORD_TAG)]
    if p.returncode != 0 and not records:
        tail = (p.stderr or p.stdout or "").strip().splitlines()
        return [], f"child n={n} rc={p.returncode}: " + \
            "; ".join(tail[-3:])
    return records, None


def _get(rec, kind, field="bytes"):
    return ((rec.get("collectives") or {}).get(kind) or {}).get(field, 0)


def _ratio_ok(actual, expected, rtol):
    if expected == 0:
        return actual == 0
    return abs(actual / expected - 1.0) <= rtol


def check_laws(by_n: dict) -> list:
    """Assert the per-slice scaling laws over {n: {slice: record}}.

    Returns a list of law records ``{law, slice, ok, detail}`` — one per
    (slice, law) pair — computed against the smallest world size as the
    reference point.
    """
    ns = sorted(by_n)
    n0 = ns[0]
    laws = []

    def law(name, slice_name, kind, expected_fn, rtol, derived_fn=None):
        base = _get(by_n[n0].get(slice_name, {}), kind)
        series = {}
        ok = base > 0
        for n in ns:
            rec = by_n[n].get(slice_name)
            if rec is None or not rec.get("ok"):
                ok = False
                continue
            actual = _get(rec, kind)
            if derived_fn is not None:
                actual = derived_fn(n, actual)
                expected = derived_fn(n0, base) * expected_fn(n) \
                    / expected_fn(n0)
            else:
                expected = base * expected_fn(n) / expected_fn(n0)
            series[str(n)] = {"bytes": actual,
                              "expected": round(expected, 1)}
            if not _ratio_ok(actual, expected, rtol):
                ok = False
        laws.append({"law": name, "slice": slice_name, "kind": kind,
                     "ok": bool(ok), "series": series})

    const = (lambda n: 1.0)
    # data parallelism: per-device reduction volume independent of world
    law("dp allreduce const/device", "dp_syncbn", "all-reduce",
        const, CONST_RTOL)
    # ring attention: per-iteration permute const; executed volume
    # (static x sp trips of the fori_loop ring) grows with the ring
    law("ring permute const/iteration", "dp_sp_ring",
        "collective-permute", const, CONST_RTOL)
    law("ring executed volume ~ sp", "dp_sp_ring", "collective-permute",
        lambda n: sweep_topology(n)["sp"], LINEAR_RTOL,
        derived_fn=lambda n, b: b * sweep_topology(n)["sp"])
    law("ring dp-grad allreduce const", "dp_sp_ring", "all-reduce",
        const, CONST_RTOL)
    # tensor parallelism: Megatron sharding keeps per-device volumes flat
    law("tp allreduce const/device", "dp_tp_pjit", "all-reduce",
        const, CONST_RTOL)
    # pipeline: per-tick permute const; executed = static x (M + S - 1)
    law("pipe permute const/tick", "pipeline", "collective-permute",
        const, CONST_RTOL)
    law("pipe executed volume ~ 2S-1", "pipeline", "collective-permute",
        lambda n: 2 * n - 1, LINEAR_RTOL,
        derived_fn=lambda n, b: b * (2 * n - 1))
    # expert parallelism: a slot of room rows for every rank, so the
    # exchange grows with the ranks (module docstring)
    law("expert all-to-all ~ ranks * room", "expert",
        "all-to-all", expert_alltoall_scale, LINEAR_RTOL)
    # fsdp: the compute all-gather reconstitutes the FULL (growing)
    # parameter — the one law that is linear in the static audit itself
    law("fsdp all-gather ~ params", "fsdp", "all-gather",
        lambda n: n, LINEAR_RTOL)
    # 3-D composition preserves the per-axis laws
    law("3d permute const/iteration", "dp_tp_sp_3d",
        "collective-permute", const, CONST_RTOL)
    law("3d allreduce const/device", "dp_tp_sp_3d", "all-reduce",
        const, CONST_RTOL)
    return laws


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", type=int, default=None)
    ap.add_argument("--ns", type=int, nargs="*", default=None)
    ap.add_argument("--out", default=str(REPO / "SCALING_SWEEP.json"))
    args = ap.parse_args(argv)

    if args.child is not None:
        child_main(args.child)
        return 0

    ns = tuple(args.ns) if args.ns else DEFAULT_NS
    by_n = {}
    errors = []
    for n in ns:
        print(f"--- world {n} ---", flush=True)
        records, err = run_child(n)
        if err:
            errors.append(err)
            print(err, flush=True)
        by_n[n] = {r["name"]: r for r in records}
        for r in records:
            print(json.dumps(r), flush=True)

    laws = check_laws(by_n)
    failed_slices = [f"n={n}:{name}" for n, recs in by_n.items()
                     for name, r in recs.items() if not r.get("ok")]
    failed_laws = [f"{lw['slice']}: {lw['law']}" for lw in laws
                   if not lw["ok"]]
    parity = next((r for recs in by_n.values()
                   for r in recs.values()
                   if r.get("name") == "predivide_parity"), None)
    verdict = {
        "ns": list(ns),
        "slices": {str(n): recs for n, recs in by_n.items()},
        "laws": laws,
        "predivide_parity": parity,
        "failed_slices": failed_slices,
        "failed_laws": failed_laws,
        "errors": errors,
        "ok": not (failed_slices or failed_laws or errors
                   or (max(ns) >= PREDIVIDE_WORLD
                       and not (parity or {}).get("ok"))),
    }
    Path(args.out).write_text(json.dumps(verdict, indent=1))
    summary = {"scaling_sweep": {
        "ns": list(ns), "ok": verdict["ok"],
        "laws_ok": sum(1 for lw in laws if lw["ok"]),
        "laws_total": len(laws),
        "failed_laws": failed_laws, "failed_slices": failed_slices,
        "predivide_parity_ok": (parity or {}).get("ok"),
    }}
    print(json.dumps(summary), flush=True)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
