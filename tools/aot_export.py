"""Lint-gated AOT export of the laned entry points.

The build step of the content-addressed executable cache
(:mod:`apex_tpu.analysis.export`): every selected lane is lowered
once, compiled once (timed — the cold-start cost a serving replica
pays today), run through the full gate matrix including the
``export-compat`` pass, and — only when the gate is clean — the
compiled executable is AOT-serialized into the cache with a manifest
embedding its sha256 and the gating lint Report.  Each exported lane
is then RELOADED from the cache (timed — the cold-start cost a
replica pays with the cache) and its outputs checked BITWISE against
the freshly compiled executable's on identical inputs.

Default lanes: the mlp O1/O2 train steps and the serve engine's
decode step (``tools/graph_lint.py``'s builders — the export pipeline
and the lint share one definition of "lane"), plus
``seeded_io_callback``: a deliberately non-exportable program (an
injected ``io_callback``) that must be REFUSED from the cache with
the documented ``export-host-callback`` finding id — the refusal
path is round evidence, not just a test.

``--emit-json EXPORT_rN.json`` writes the committed artifact
(schema: ``apex_tpu/analysis/export_schema.py``, validated by
``tools/gate_hygiene.py``): per-lane cache keys, gating verdicts,
compile-vs-load wall clock, the bitwise round-trip verdict, and the
``cold_start`` block (load must cost <= 0.5x compile on this host).

``--verify-reload KEY --io FILE.pkl`` is the fresh-process check: it
loads ONLY the cache entry (no model build, no trace), calls it on
the pickled inputs, and compares bitwise against the pickled expected
outputs — run it in a subprocess to prove the round trip across a
process boundary (tests/l0/test_aot_export.py does).

Usage:
    python tools/aot_export.py [--cache-dir DIR]
                               [--lanes mlp_o1,mlp_o2,serve,seeded]
                               [--emit-json EXPORT_r01.json] [-v]
    python tools/aot_export.py --verify-reload KEY --io IO.pkl
                               [--cache-dir DIR]
"""

import argparse
import json
import os
import pickle
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import graph_lint  # noqa: E402  (sets platform/env before jax init)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from apex_tpu.analysis import export as aot  # noqa: E402
from apex_tpu.analysis.core import (  # noqa: E402
    PassContext,
    _args_info,
    _out_info,
    _static_scalars,
    run_passes,
)
from apex_tpu.analysis.export_schema import COLD_START_RATIO_MAX  # noqa: E402

#: CLI lane name -> artifact lane name
LANE_NAMES = {"mlp_o1": "mlp_o1_train", "mlp_o2": "mlp_o2_train",
              "serve": "serve_step", "seeded": "seeded_io_callback"}
DEFAULT_LANES = ("mlp_o1", "mlp_o2", "serve", "seeded")

#: the serve lane is the cold-start story's lane: a scale-out replica
#: pays exactly this compile before serving its first token
COLD_START_LANE = "serve_step"


def default_cache_dir() -> str:
    return os.environ.get(aot.CACHE_ENV) or str(REPO / ".aot_cache")


def build_seeded_io_callback():
    """A lane with an injected host callback — compiles fine, must be
    refused from the cache (the acceptance path for the
    ``export-host-callback`` finding)."""
    from jax.experimental import io_callback

    def step(x):
        y = x * 2.0
        io_callback(lambda v: None, None, y.sum(), ordered=True)
        return y.sum()

    return jax.jit(step), (jnp.ones((16, 16), jnp.float32),), None


def build_lane(cli_name: str):
    """(jitted, args, lint_policy, key_policy) for one CLI lane name.

    ``key_policy`` is what enters the cache key; for the serve lane it
    is ``None`` — the engine's startup probe has no resolved amp
    policy in hand (the params are already cast), so the tool must key
    the entry the way the engine will look it up, or a replica could
    never hit the entry this tool built.  The LINT still runs with the
    real O2 serving policy."""
    if cli_name == "mlp_o1":
        step, args, props = graph_lint.build_train_step(
            "mlp", opt_level="O1")
        return step, args, props, props
    if cli_name == "mlp_o2":
        step, args, props = graph_lint.build_train_step(
            "mlp", opt_level="O2")
        return step, args, props, props
    if cli_name == "serve":
        fn, args, props = graph_lint.build_serve_step(
            *graph_lint.SERVE_LANES["serve_step"])
        return fn, args, props, None
    if cli_name == "seeded":
        jitted, args, props = build_seeded_io_callback()
        return jitted, args, props, props
    raise KeyError(f"unknown lane {cli_name!r}; have {DEFAULT_LANES}")


def _copy_args(tree):
    """Deep-copy the array leaves so a donated executable can be
    called repeatedly on identical inputs (donation consumes the
    originals)."""
    return jax.tree.map(
        lambda x: jnp.asarray(np.asarray(x)) if hasattr(x, "shape")
        else x, tree)


def _bitwise_equal(a, b) -> bool:
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(fa) != len(fb):
        return False
    for x, y in zip(fa, fb):
        xa, ya = np.asarray(x), np.asarray(y)
        if xa.dtype != ya.dtype or xa.shape != ya.shape \
                or xa.tobytes() != ya.tobytes():
            return False
    return True


def export_lane(name: str, jitted, args, policy, cache_dir,
                key_policy=None, verbose: bool = False) -> dict:
    """One lane through the pipeline: lower, compile (timed), gate,
    export-or-refuse, reload (timed), bitwise round trip.  Returns
    the artifact lane record."""
    lowered = aot.lower_quiet(jitted, *args)
    text = lowered.as_text()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    ctx = PassContext(
        stablehlo_text=text, hlo_text=compiled.as_text(),
        args=_args_info(lowered), outputs=_out_info(lowered),
        compiled=compiled, policy=policy,
        static_scalars=_static_scalars(args, {}, lowered.args_info))
    # single-chip lanes: any collective is a regression (the
    # graph_lint budget), so the gate matrix here matches the lint's
    report = run_passes(ctx, passes=aot.gate_passes_for(policy),
                        options={"collectives": {"budget": {"total": 0}}})
    parts = aot.key_parts(text, mesh=aot.mesh_descriptor(lowered),
                          policy=key_policy)
    key = aot.cache_key(parts)
    counts = report.to_dict()["counts"]
    rec = {"lint": {"ok": report.ok, "passes": list(report.passes),
                    "counts": counts}}
    if verbose or not report.ok:
        print(f"--- {name} ---\n{report.format()}", file=sys.stderr)
    try:
        manifest = aot.write_entry(cache_dir, key, parts, compiled,
                                   report, lane=name)
    except aot.ExportRefused as e:
        rec.update(export_ok=False, refused=e.finding_id)
        print(f"{name}: REFUSED from the cache ({e.finding_id})",
              file=sys.stderr)
        return rec

    t0 = time.perf_counter()
    hit = aot.load_entry(cache_dir, key)
    load_s = time.perf_counter() - t0
    if hit is None:   # just-written entry must verify — else our bug
        raise RuntimeError(f"{name}: freshly written cache entry "
                           f"{key[:16]}… failed verification")
    loaded, _ = hit
    out_fresh = compiled(*_copy_args(args))
    out_cache = loaded(*_copy_args(args))
    bitwise = _bitwise_equal(out_fresh, out_cache)
    rec.update(export_ok=True, cache_key=key,
               module_sha256=parts["module_sha256"],
               sha256=manifest["sha256"],
               compile_s=round(compile_s, 4), load_s=round(load_s, 4),
               load_ratio=round(load_s / compile_s, 4)
               if compile_s else 0.0,
               bitwise_equal=bool(bitwise))
    print(f"{name}: exported {key[:16]}… compile {compile_s:.3f}s "
          f"load {load_s:.3f}s bitwise={bitwise}", file=sys.stderr)
    return rec


def run_lanes(cli_lanes, cache_dir, verbose: bool = False) -> dict:
    lanes = {}
    for cli_name in cli_lanes:
        jitted, args, policy, key_policy = build_lane(cli_name)
        lanes[LANE_NAMES[cli_name]] = export_lane(
            LANE_NAMES[cli_name], jitted, args, policy, cache_dir,
            key_policy=key_policy, verbose=verbose)
    return lanes


def cold_start_block(lanes: dict) -> "dict | None":
    rec = lanes.get(COLD_START_LANE)
    if not isinstance(rec, dict) or not rec.get("export_ok"):
        return None
    ratio = rec["load_ratio"]
    return {"lane": COLD_START_LANE, "compile_s": rec["compile_s"],
            "load_s": rec["load_s"], "load_ratio": ratio,
            "budget": COLD_START_RATIO_MAX,
            "ok": ratio <= COLD_START_RATIO_MAX}


def emit_export(path: str, lanes: dict, cache_dir) -> int:
    """Write the committed EXPORT artifact; returns the number of
    problems (a lane that should have exported but didn't, a missing
    cold-start block, a failed bitwise check)."""
    cs = cold_start_block(lanes)
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    doc = {
        "round": int(m.group(1)) if m else 0,
        "platform": jax.devices()[0].platform,
        "versions": aot.runtime_versions(),
        "cache": {"dir": os.path.relpath(str(cache_dir), str(REPO))
                  if str(cache_dir).startswith(str(REPO))
                  else str(cache_dir),
                  "entries": len(aot.list_entries(cache_dir))},
        "lanes": lanes,
        "cold_start": cs,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"export artifact written: {path} ({len(lanes)} lanes)",
          file=sys.stderr)
    problems = 0
    for name, rec in lanes.items():
        if name == LANE_NAMES["seeded"]:
            if rec.get("export_ok") is not False:
                print(f"{name}: the seeded violation EXPORTED — the "
                      f"gate is broken", file=sys.stderr)
                problems += 1
        elif not (rec.get("export_ok") and rec.get("bitwise_equal")):
            print(f"{name}: export/round-trip failed — see record",
                  file=sys.stderr)
            problems += 1
    if cs is None or not cs["ok"]:
        print(f"cold_start gate failed: {cs}", file=sys.stderr)
        problems += 1
    return problems


def verify_reload(cache_dir, key: str, io_path: str) -> int:
    """Fresh-process half of the round trip: load ONLY the cache entry
    (no build, no trace), run it on the pickled inputs, compare
    bitwise with the pickled expected outputs."""
    hit = aot.load_entry(cache_dir, key)
    if hit is None:
        print(json.dumps({"hit": False}))
        print(f"verify-reload: no verified entry for {key[:16]}…",
              file=sys.stderr)
        return 1
    compiled, manifest = hit
    with open(io_path, "rb") as f:
        io = pickle.load(f)
    treedef = jax.tree_util.tree_structure(compiled.args_info)
    args, kwargs = jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(x) for x in io["inputs"]])
    out = compiled(*args, **kwargs)
    got = [np.asarray(x) for x in jax.tree.leaves(out)]
    exp = [np.asarray(x) for x in io["expected"]]
    ok = len(got) == len(exp) and all(
        g.dtype == e.dtype and g.shape == e.shape
        and g.tobytes() == e.tobytes() for g, e in zip(got, exp))
    print(json.dumps({"hit": True, "bitwise_equal": bool(ok),
                      "lane": manifest.get("lane")}))
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cache-dir", default=None,
                    help=f"cache root (default ${aot.CACHE_ENV} or "
                         f"<repo>/.aot_cache)")
    ap.add_argument("--lanes", default=",".join(DEFAULT_LANES),
                    help=f"comma list from {DEFAULT_LANES}")
    ap.add_argument("--emit-json", default=None,
                    metavar="EXPORT_rN.json",
                    help="write the committed export artifact (always "
                         "the full default lane set)")
    ap.add_argument("--verify-reload", default=None, metavar="KEY",
                    help="load the entry KEY from the cache and check "
                         "it bitwise against --io (fresh-process mode: "
                         "no model build, no trace)")
    ap.add_argument("--io", default=None, metavar="IO.pkl",
                    help="pickled {'inputs': [...], 'expected': [...]} "
                         "for --verify-reload")
    ap.add_argument("-v", "--verbose", action="store_true")
    opts = ap.parse_args(argv)

    cache_dir = opts.cache_dir or default_cache_dir()
    if opts.verify_reload:
        if not opts.io:
            ap.error("--verify-reload needs --io")
        return verify_reload(cache_dir, opts.verify_reload, opts.io)

    cli_lanes = [x.strip() for x in opts.lanes.split(",") if x.strip()]
    unknown = [x for x in cli_lanes if x not in LANE_NAMES]
    if unknown or not cli_lanes:
        ap.error(f"unknown lanes {unknown or opts.lanes!r}; have "
                 f"{DEFAULT_LANES}")
    if opts.emit_json and tuple(cli_lanes) != DEFAULT_LANES:
        # the committed artifact's contract is the full lane set —
        # the refusal lane included (the gate's negative evidence)
        ap.error("--emit-json always writes the full default lane "
                 "set; drop --lanes")
    os.makedirs(cache_dir, exist_ok=True)
    lanes = run_lanes(cli_lanes, cache_dir, verbose=opts.verbose)
    if opts.emit_json:
        return 1 if emit_export(opts.emit_json, lanes, cache_dir) \
            else 0
    bad = [n for n, r in lanes.items()
           if n != LANE_NAMES["seeded"]
           and not (r.get("export_ok") and r.get("bitwise_equal"))]
    bad += [n for n, r in lanes.items()
            if n == LANE_NAMES["seeded"] and r.get("export_ok")]
    if bad:
        print(f"aot export FAILED for: {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
