"""Run the disaggregated chaos drill with request tracing on and emit
the committed ``TRACE_r*.json`` lifecycle artifact.

The drill is PR 10's replica-kill scenario at the c16 fleet topology
(1 prefill slice + 2 decode replicas x 8 slots on the virtual
16-device CPU platform — the tool forces
``--xla_force_host_platform_device_count=16``), with :class:`apex_tpu.obs.RequestTracer`
and :class:`apex_tpu.obs.FlightRecorder` attached: a request stream is
admitted, the busiest decode replica is killed mid-stream, the router
rebuilds its in-flight requests from the streamed-token log and
re-prefills them elsewhere, and every output is checked BITWISE
against solo ``generate()``.

The emitted document (schema ``apex_tpu/analysis/trace.py``, enforced
on committed copies by ``tools/gate_hygiene.py``) reconstructs each
request's FULL lifecycle — enqueue at the router, chunked prefill, the
KV shipment, decode steps with per-slot token attribution, the
reroute naming the killed replica, the re-prefill on the surviving
replica, retirement — and is contradiction-rejecting: span trees must
nest, the trace's token accounting must equal the engines' own
``serve_tokens_total`` deltas, and every reroute must name a killed
replica.  ``--chrome PATH`` additionally writes the same lifecycles as
chrome-trace JSON for ``chrome://tracing`` / Perfetto.

Usage:
    python tools/trace_report.py --emit-json TRACE_r01.json \
        [--chrome trace.json] [--n-replicas 2] [--slots 8]
        [--prefill 24] [--new-tokens 12] [--requests 16]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

# 16 virtual host devices BEFORE any jax backend initialization: the
# c16 fleet topology, CPU-testable end to end.
os.environ.setdefault("APEX_TPU_KERNELS", "jnp")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=16").strip()
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu"))
jax.config.update("jax_threefry_partitionable", True)


def run_traced_drill(n_replicas: int = 2, slots: int = 8,
                     prefill: int = 24, new_tokens: int = 12,
                     n_requests: int = 16, kill_after: int = 3,
                     incident_path=None) -> dict:
    """The traced c16 chaos drill; returns the full TRACE document
    (un-rounded — the caller stamps ``round`` from the emit path) plus
    the tracer under ``"_tracer"`` for the chrome export."""
    from apex_tpu import amp
    from apex_tpu.models import GPTModel, gpt_tiny
    from apex_tpu.models.generate import generate
    from apex_tpu.obs import FlightRecorder, RequestTracer, fleet
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import (DisaggRouter, Request, RouterConfig,
                                ServeConfig)

    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    params = amp.initialize(
        opt_level="O2", verbosity=0).model_params_from(params)
    block = 4
    mb = -(-(prefill + new_tokens) // block)
    scfg = ServeConfig(num_slots=slots, block_size=block,
                       num_blocks=slots * mb + 1,
                       max_blocks_per_slot=mb, prefill_chunk=8)
    tracer = RequestTracer()
    flight = FlightRecorder()
    router = DisaggRouter(
        params, cfg, scfg,
        RouterConfig(n_decode_replicas=n_replicas, transfer="ship",
                     incident_path=incident_path),
        registry=Registry(), tracer=tracer, flight=flight)

    labels = ["prefill"] + [f"replica{i}" for i in range(n_replicas)]
    regs = [router.prefill.eng.metrics] + [r.eng.metrics
                                           for r in router.replicas]
    tok0 = [r.counter("serve_tokens_total").value for r in regs]

    rng = np.random.RandomState(3)
    reqs = []
    for i in range(n_requests):
        plen = max(2, int(prefill * (0.5 + 0.5 * (i % 2))))
        reqs.append((rng.randint(0, cfg.vocab_size, (plen,)),
                     new_tokens))
    for i, (p, n) in enumerate(reqs):
        router.submit(Request(uid=f"c{i}", prompt=p, max_new_tokens=n))
    for _ in range(kill_after):
        router.step()
    victim = max(router.replicas,
                 key=lambda r: r.eng.sched.n_active()).index
    rerouted = router.kill_replica(victim)
    out = router.run()

    bitwise = True
    divergent = []
    for i, (p, n) in enumerate(reqs):
        want = np.asarray(generate(params, cfg, jnp.asarray(p[None]),
                                   n))[0, len(p):]
        if not np.array_equal(out[f"c{i}"], want):
            bitwise = False
            divergent.append(f"c{i}")

    per = {lbl: round(reg.counter("serve_tokens_total").value - t0)
           for lbl, reg, t0 in zip(labels, regs, tok0)}
    delta = round(sum(per.values()))
    doc_reqs = tracer.to_doc_requests()
    trace_tokens = sum(r["tokens"] for r in doc_reqs.values())
    tokens_ok = delta == trace_tokens

    # the fleet-merged registry (obs.fleet): the ONE merge
    # implementation cross-checks the per-engine table it was built
    # from — counter sums through merge_registries, not hand math
    merged = fleet.merge_registries(regs)
    merged_total = round(
        merged.counter("serve_tokens_total").value - sum(tok0))

    return {
        "round": 0,
        "platform": jax.devices()[0].platform,
        "config": {
            "model": "gpt_tiny",
            "concurrency": n_requests,
            "topology": {"n_devices": len(jax.devices()),
                         **router.slices.describe()},
            "n_replicas": n_replicas, "slots_per_replica": slots,
            "prefill": prefill, "new_tokens": new_tokens,
            "block_size": block, "kill_after_steps": kill_after,
        },
        "requests": doc_reqs,
        "engine": {"serve_tokens_total": per, "delta_total": delta,
                   "fleet_merged_total": merged_total},
        "chaos": {"killed": [int(victim)], "rerouted": rerouted,
                  "divergent": divergent},
        "gate": {"bitwise_ok": bool(bitwise),
                 "tokens_ok": bool(tokens_ok),
                 "ok": bool(bitwise and tokens_ok)},
        "note": (
            "Request-trace artifact of the c16 disaggregated "
            "replica-kill drill: every lifecycle host-recorded at the "
            "existing step boundaries (zero added device syncs — the "
            "compiled programs are unchanged, OBS_r02 carries the "
            "syncs verdict), token accounting closed against the "
            "engines' own counters, rerouted requests reconstructed "
            "across two replicas with outputs bitwise vs solo "
            "generate().  Regenerate with tools/trace_report.py "
            "--emit-json TRACE_rN.json."),
        "_tracer": tracer,
        "_flight": flight,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-json", default=None,
                    metavar="TRACE_rN.json",
                    help="write the committed gate artifact")
    ap.add_argument("--chrome", default=None, metavar="PATH",
                    help="also write the lifecycles as chrome-trace "
                         "JSON (chrome://tracing / Perfetto)")
    ap.add_argument("--n-replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prefill", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--kill-after", type=int, default=3)
    opts = ap.parse_args(argv)

    doc = run_traced_drill(
        n_replicas=opts.n_replicas, slots=opts.slots,
        prefill=opts.prefill, new_tokens=opts.new_tokens,
        n_requests=opts.requests, kill_after=opts.kill_after)
    tracer = doc.pop("_tracer")
    doc.pop("_flight")

    if opts.chrome:
        with open(opts.chrome, "w") as f:
            json.dump(tracer.to_chrome_trace(), f)
        print(f"chrome trace written: {opts.chrome}", file=sys.stderr)

    if opts.emit_json:
        m = re.search(r"_r(\d+)\.json$",
                      os.path.basename(opts.emit_json))
        doc["round"] = int(m.group(1)) if m else 0
        from apex_tpu.analysis.trace import validate_trace
        problems = validate_trace(doc)
        if problems:
            print(f"trace_report: REFUSING schema-invalid artifact: "
                  f"{problems}", file=sys.stderr)
            return 1
        with open(opts.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"trace artifact written: {opts.emit_json}",
              file=sys.stderr)

    summary = {"gate": doc["gate"], "chaos": doc["chaos"],
               "engine": doc["engine"],
               "requests": len(doc["requests"]),
               "events": sum(len(r["events"])
                             for r in doc["requests"].values())}
    print(json.dumps(summary))
    return 0 if doc["gate"]["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
