"""Fleet observability (ISSUE 13): request tracing, the incident
flight recorder, and the one registry-merge layer.

Contracts under test: (a) :class:`apex_tpu.obs.RequestTracer` — the
closed event vocabulary, id minting, retired-trace bounding, span
derivation, chrome-trace export; (b) trace integrity under chaos —
kill the busiest decode replica mid-stream and the rerouted request's
trace reconstructs prefill -> ship -> decode on replica A, the reroute
naming A, re-prefill -> decode on replica B, while outputs stay
BITWISE vs solo ``generate()`` and the graph-lint syncs pass stays
clean on the instrumented compiled step; (c) the stdlib TRACE schema's
contradiction rejection (non-nesting spans, token accounting vs the
engines' own counters, reroutes naming live replicas, self-
contradicting gates) and the committed ``TRACE_r01.json``;
(d) :class:`apex_tpu.obs.FlightRecorder` — ring bound, ordering, the
INCIDENT schema's grown ``flight`` field; (e) :mod:`apex_tpu.obs.
fleet` — counter sums, bucket-union quantiles pinned against the old
``bench._merged_decode_quantile`` math on a recorded fixture, gauge
tables.
"""

import copy
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.analysis import trace as trace_schema
from apex_tpu.models import GPTModel, gpt_tiny
from apex_tpu.models.generate import generate
from apex_tpu.obs import FlightRecorder, RequestTracer, fleet
from apex_tpu.obs import reqtrace
from apex_tpu.obs.metrics import Histogram, Registry
from apex_tpu.resilience.incidents import make_incident, validate_incident
from apex_tpu.serve import (
    DisaggRouter,
    Request,
    RouterConfig,
    ServeConfig,
    ServeEngine,
)

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))


# ---------------------------------------------------------------------------
# RequestTracer mechanics
# ---------------------------------------------------------------------------

def test_tracer_vocabulary_is_closed_and_pinned():
    tr = RequestTracer()
    with pytest.raises(ValueError, match="vocabulary"):
        tr.record("decode", "u", "engine")       # typo'd kind is loud
    # the stdlib schema must carry the SAME vocabulary (it cannot
    # import the jax-adjacent obs package — gate_hygiene loads it by
    # file path)
    assert trace_schema.EVENT_KINDS == reqtrace.EVENT_KINDS
    assert trace_schema.TOKEN_KINDS == reqtrace.TOKEN_KINDS


def test_tracer_mint_lifecycle_and_token_sum():
    tr = RequestTracer()
    tid = tr.mint("a")
    assert tid == tr.mint("a")          # re-mint = same request
    tr.record("enqueue", "a", "router")
    tr.record("admit", "a", "prefill", slot=0, first_token=3,
              prompt_len=4, tokens=1)
    tr.record("decode_step", "a", "replica0", step=1, token=5,
              batch=2, tokens=1)
    tr.record("retire", "a", "replica0", tokens_out=2)
    assert tr.tokens_of("a") == 2
    doc = tr.to_doc_requests()["a"]
    assert [e["kind"] for e in doc["events"]] == [
        "enqueue", "admit", "decode_step", "retire"]
    assert doc["tokens"] == 2
    # spans: one root + one residency segment per contiguous where-run
    spans = doc["spans"]
    assert spans[0]["parent"] == -1
    assert [s["name"] for s in spans[1:]] == ["router", "prefill",
                                              "replica0"]
    assert trace_schema._validate_spans("a", spans) == []


def test_tracer_bounds_retired_traces():
    tr = RequestTracer(max_retired=2)
    for i in range(4):
        tr.record("enqueue", f"u{i}", "router")
        tr.record("retire", f"u{i}", "engine", tokens_out=0)
    assert tr.dropped == 2
    assert tr.events("u0") == [] and tr.events("u1") == []
    assert tr.events("u3") != []


def test_tracer_hard_cap_evicts_never_retired_traces():
    """Regression (review round 3): a request that never retires
    (abandoned client) must not hold its event list forever — total
    traces are capped at 2 * max_retired, oldest-minted evicted."""
    tr = RequestTracer(max_retired=2)
    for i in range(7):
        tr.record("enqueue", f"u{i}", "router")   # nobody retires
    assert len(tr.uids()) == 4
    assert tr.dropped == 3
    assert tr.events("u0") == [] and tr.events("u6") != []


def test_tracer_chrome_trace_export_shape():
    tr = RequestTracer()
    tr.record("enqueue", "a", "router")
    tr.record("admit", "a", "prefill", tokens=1)
    tr.record("decode_step", "a", "replica0", step=1, token=2,
              batch=1, tokens=1)
    tr.record("reroute", "a", "router", from_replica=0)
    tr.record("retire", "a", "replica1", tokens_out=2)
    ct = tr.to_chrome_trace()
    evs = ct["traceEvents"]
    json.dumps(ct)                       # serializable end to end
    procs = {e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"/fleet:router", "/fleet:prefill",
                     "/fleet:replica0", "/fleet:replica1"}
    assert any(e["ph"] == "X" for e in evs)          # residency spans
    instants = [e for e in evs if e["ph"] == "i"]
    assert {"reroute", "retire"} <= {e["name"] for e in instants}
    assert instants[0]["args"].get("from_replica", 0) in (0,)


# ---------------------------------------------------------------------------
# the chaos trace-integrity drill (the ISSUE-13 acceptance test)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_kill_drill():
    """ONE traced fleet + kill drill shared by the integrity tests
    (three engines' worth of compiles): 4 requests through 1 prefill
    worker + 2 two-slot decode replicas, the busiest replica killed
    after 3 fleet steps, the stream drained, and a TRACE document
    built exactly the way ``tools/trace_report.py`` builds the
    committed artifact."""
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    params = amp.initialize(
        opt_level="O2", verbosity=0).model_params_from(params)
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=17,
                       max_blocks_per_slot=8, prefill_chunk=4)
    tracer = RequestTracer()
    flight = FlightRecorder()
    router = DisaggRouter(
        params, cfg, scfg,
        RouterConfig(n_decode_replicas=2, transfer="ship"),
        registry=Registry(), tracer=tracer, flight=flight)
    labels = ["prefill", "replica0", "replica1"]
    regs = [router.prefill.eng.metrics] + [r.eng.metrics
                                           for r in router.replicas]
    tok0 = [r.counter("serve_tokens_total").value for r in regs]
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, cfg.vocab_size, (12 // (i + 1) + 2,)), 8)
            for i in range(4)]
    for i, (p, n) in enumerate(reqs):
        router.submit(Request(uid=f"c{i}", prompt=p, max_new_tokens=n))
    for _ in range(3):
        router.step()
    victim = max(router.replicas,
                 key=lambda r: r.eng.sched.n_active()).index
    rerouted = router.kill_replica(victim)
    out = router.run()
    per = {lbl: round(reg.counter("serve_tokens_total").value - t0)
           for lbl, reg, t0 in zip(labels, regs, tok0)}
    doc_reqs = tracer.to_doc_requests()
    delta = round(sum(per.values()))
    tokens = sum(r["tokens"] for r in doc_reqs.values())
    bitwise = all(
        np.array_equal(
            out[f"c{i}"],
            np.asarray(generate(params, cfg, jnp.asarray(p[None]),
                                n))[0, len(p):])
        for i, (p, n) in enumerate(reqs))
    doc = {
        "round": 1, "platform": "cpu",
        "config": {"model": "gpt_tiny"},
        "requests": doc_reqs,
        "engine": {"serve_tokens_total": per, "delta_total": delta},
        "chaos": {"killed": [int(victim)], "rerouted": rerouted},
        "gate": {"bitwise_ok": bool(bitwise),
                 "tokens_ok": delta == tokens,
                 "ok": bool(bitwise and delta == tokens)},
    }
    return {"doc": doc, "victim": victim, "rerouted": rerouted,
            "flight": flight, "router": router}


def test_killed_request_trace_reconstructs_both_replicas(
        traced_kill_drill):
    """THE integrity gate: a rerouted request's lifecycle shows
    prefill -> ship -> decode on replica A, the reroute naming A,
    re-prefill -> ship -> decode on replica B != A, retire — while
    every output stayed bitwise vs solo (gate.bitwise_ok)."""
    d = traced_kill_drill
    assert d["doc"]["gate"]["bitwise_ok"] is True
    assert d["rerouted"], "the drill must actually reroute something"
    victim = d["victim"]
    checked = 0
    for uid in d["rerouted"]:
        evs = d["doc"]["requests"][uid]["events"]
        kinds = [(e["kind"], e["where"]) for e in evs]
        ri = [i for i, e in enumerate(evs)
              if e["kind"] == "reroute"][0]
        assert evs[ri]["from_replica"] == victim
        before, after = evs[:ri], evs[ri + 1:]
        # decode work BEFORE the reroute ran on the killed replica
        # (requests rerouted out of the engine-local queue never
        # decoded there — skip those for the residency assertion)
        decoded_before = [e for e in before
                          if e["kind"] == "decode_step"]
        if not decoded_before:
            continue
        checked += 1
        assert all(e["where"] == f"replica{victim}"
                   for e in decoded_before)
        assert any(e[0] == "admit" and e[1] == "prefill"
                   for e in kinds[:ri])
        assert any(e["kind"] == "kv_install"
                   and e["where"] == f"replica{victim}"
                   for e in before)
        # ... and AFTER it: a fresh prefill, then decode on a live
        # replica that is NOT the killed one
        assert any(e["kind"] == "admit" and e["where"] == "prefill"
                   for e in after)
        decoded_after = {e["where"] for e in after
                         if e["kind"] == "decode_step"}
        assert decoded_after and f"replica{victim}" not in decoded_after
        assert evs[-1]["kind"] == "retire"
    assert checked >= 1, "no rerouted request had decoded pre-kill"


def test_drill_document_validates_and_accounts_tokens(
        traced_kill_drill):
    """The drill's document is schema-valid, its token accounting
    closes against the engines' own counters, and the flight ring
    recorded the kill + every reroute."""
    d = traced_kill_drill
    assert trace_schema.validate_trace(d["doc"]) == []
    assert d["doc"]["gate"]["tokens_ok"] is True
    dump = d["flight"].dump()
    kinds = [e["kind"] for e in dump["events"]]
    assert kinds.count("reroute") == len(d["rerouted"])
    assert "replica_kill" in kinds
    assert dump["events"][kinds.index("replica_kill")]["replica"] \
        == d["victim"]
    # the fleet merge layer agrees with the per-engine table
    router = d["router"]
    merged = fleet.merge_registries(
        [router.prefill.eng.metrics]
        + [r.eng.metrics for r in router.replicas])
    assert merged.counter("serve_reroute_total").value == 0  # router's
    got = merged.counter("serve_tokens_total").value
    assert round(got) >= d["doc"]["engine"]["delta_total"]


def test_kill_incident_record_is_schema_valid(traced_kill_drill,
                                               tmp_path):
    """RouterConfig.incident_path: the replica death leaves a
    schema-valid incident carrying the resolved router metrics AND
    the flight ring's tail (the grown INCIDENT ``flight`` field)."""
    import dataclasses
    d = traced_kill_drill
    router = d["router"]
    path = tmp_path / "INCIDENT_kill.json"
    router.rcfg = dataclasses.replace(router.rcfg,
                                      incident_path=str(path))
    router._write_kill_incident(int(d["victim"]), list(d["rerouted"]))
    rec = json.loads(path.read_text())
    assert validate_incident(rec) == []
    assert rec["status"] == "replica-killed"
    assert rec["replica"] == d["victim"]
    assert set(rec["rerouted"]) == set(d["rerouted"])
    kinds = [e["kind"] for e in rec["flight"]["events"]]
    assert "replica_kill" in kinds and "reroute" in kinds


def test_syncs_pass_clean_on_instrumented_decode_step():
    """Tracing is host-side bookkeeping at step boundaries: the
    compiled decode step is UNCHANGED, which the graph-lint syncs
    pass proves — zero host callbacks, zero static-scalar retrace
    hazards, zero errors on the instrumented serve lane (the same bar
    OBS_r02.json commits)."""
    import graph_lint
    rep = graph_lint.lint_serve("serve_step", passes=("syncs",))
    syncs = rep.by_pass("syncs")
    assert sum(1 for f in syncs if f.op == "host-callback") == 0
    assert sum(1 for f in syncs if f.op == "static-scalar") == 0
    assert len(rep.errors) == 0


# ---------------------------------------------------------------------------
# TRACE schema contradiction rejection + the committed artifact
# ---------------------------------------------------------------------------

def _minimal_doc():
    return {
        "round": 1, "platform": "cpu", "config": {},
        "requests": {
            "a": {
                "trace_id": "t00001",
                "events": [
                    {"seq": 1, "ts": 0.0, "kind": "enqueue",
                     "where": "router"},
                    {"seq": 2, "ts": 0.1, "kind": "admit",
                     "where": "prefill", "tokens": 1},
                    {"seq": 3, "ts": 0.2, "kind": "decode_step",
                     "where": "replica0", "tokens": 1},
                    {"seq": 4, "ts": 0.3, "kind": "retire",
                     "where": "replica0", "tokens_out": 2},
                ],
                "spans": [
                    {"name": "request", "where": "*", "t0": 0.0,
                     "t1": 0.3, "parent": -1},
                    {"name": "router", "where": "router", "t0": 0.0,
                     "t1": 0.0, "parent": 0},
                    {"name": "prefill", "where": "prefill", "t0": 0.1,
                     "t1": 0.1, "parent": 0},
                    {"name": "replica0", "where": "replica0",
                     "t0": 0.2, "t1": 0.3, "parent": 0},
                ],
                "tokens": 2,
            },
        },
        "engine": {"serve_tokens_total": {"prefill": 1, "replica0": 1},
                   "delta_total": 2},
        "chaos": {"killed": [], "rerouted": []},
        "gate": {"bitwise_ok": True, "tokens_ok": True, "ok": True},
    }


def test_trace_schema_accepts_minimal_valid():
    assert trace_schema.validate_trace(_minimal_doc()) == []


def test_trace_schema_rejects_nonnesting_spans():
    doc = _minimal_doc()
    doc["requests"]["a"]["spans"][3]["t1"] = 9.0   # escapes the root
    assert any("nest" in p for p in trace_schema.validate_trace(doc))


def test_trace_schema_rejects_token_mismatch():
    doc = _minimal_doc()
    doc["engine"]["delta_total"] = 5
    doc["engine"]["serve_tokens_total"]["replica0"] = 4
    doc["gate"]["tokens_ok"] = True     # lying gate: also caught
    probs = trace_schema.validate_trace(doc)
    assert any("serve_tokens_total delta" in p for p in probs)
    assert any("tokens_ok" in p for p in probs)
    # per-request recorded total disagreeing with its own events
    doc2 = _minimal_doc()
    doc2["requests"]["a"]["tokens"] = 7
    assert any("token-carrying events" in p
               for p in trace_schema.validate_trace(doc2))


def test_trace_schema_rejects_reroute_without_kill():
    doc = _minimal_doc()
    doc["requests"]["a"]["events"].insert(
        3, {"seq": 4, "ts": 0.25, "kind": "reroute", "where": "router",
            "from_replica": 1})
    doc["requests"]["a"]["events"][4]["seq"] = 5
    doc["chaos"] = {"killed": [], "rerouted": ["a"]}
    probs = trace_schema.validate_trace(doc)
    assert any("never lost" in p for p in probs)
    # and a chaos block whose rerouted list disagrees with the events
    doc["chaos"] = {"killed": [1], "rerouted": []}
    probs = trace_schema.validate_trace(doc)
    assert any("uids with reroute events" in p for p in probs)


def test_trace_schema_rejects_contradictory_gate():
    doc = _minimal_doc()
    doc["gate"]["ok"] = True
    doc["gate"]["bitwise_ok"] = False
    assert any("gate.ok" in p for p in trace_schema.validate_trace(doc))


def test_trace_schema_rejects_broken_lifecycle():
    doc = _minimal_doc()
    doc["requests"]["a"]["events"][0]["kind"] = "admit"
    assert any("begin with 'enqueue'" in p
               for p in trace_schema.validate_trace(doc))
    doc2 = _minimal_doc()
    doc2["requests"]["a"]["events"][1]["ts"] = -1.0   # time reversal
    assert any("precedes" in p
               for p in trace_schema.validate_trace(doc2))


def test_committed_trace_artifact_validates_and_tells_the_story():
    """The committed TRACE_r01.json (the c16 disagg chaos run): schema
    valid, gate ok, the killed request's lifecycle reconstructed
    across TWO replicas, decode-token totals agreeing with the
    engines' own counters."""
    path = REPO / "TRACE_r01.json"
    assert path.exists(), "TRACE_r01.json must be committed"
    assert trace_schema.validate_trace_file(str(path)) == []
    doc = json.loads(path.read_text())
    assert doc["gate"]["ok"] is True
    assert doc["gate"]["bitwise_ok"] is True
    killed = set(doc["chaos"]["killed"])
    assert killed and doc["chaos"]["rerouted"]
    crossed = 0
    for uid in doc["chaos"]["rerouted"]:
        wheres = {e["where"]
                  for e in doc["requests"][uid]["events"]
                  if e["kind"] in ("decode_step", "kv_install")}
        replicas = {w for w in wheres if w.startswith("replica")}
        if len(replicas) >= 2:
            crossed += 1
            assert any(int(w[len("replica"):]) in killed
                       for w in replicas)
    assert crossed >= 1, \
        "no rerouted request's trace spans two replicas"
    total = sum(r["tokens"] for r in doc["requests"].values())
    assert total == doc["engine"]["delta_total"]


# ---------------------------------------------------------------------------
# flight recorder + the INCIDENT flight field
# ---------------------------------------------------------------------------

def test_flight_ring_bounds_orders_and_counts_drops():
    fr = FlightRecorder(capacity=3)
    for i in range(5):
        fr.note("step", step=i)
    dump = fr.dump()
    assert dump["capacity"] == 3 and dump["dropped"] == 2
    assert [e["step"] for e in dump["events"]] == [2, 3, 4]
    ts = [e["ts"] for e in dump["events"]]
    assert ts == sorted(ts)
    with pytest.raises(ValueError, match="kind"):
        fr.note("")


def test_flight_note_metrics_is_resolved_state_only():
    reg = Registry()
    reg.counter("c_total").inc(3)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(0.25)
    fr = FlightRecorder()
    fr.note_metrics(reg)
    ev = fr.dump()["events"][0]
    assert ev["kind"] == "metrics"
    assert ev["values"]["c_total"] == 3.0
    assert ev["values"]["g"] == 1.5
    assert ev["values"]["h"] == {"count": 1, "sum": 0.25}


def test_incident_schema_validates_flight_field():
    rec = make_incident("recovered", "s", ["e"],
                        flight=FlightRecorder().dump())
    assert validate_incident(rec) == []
    # the r02-era shape (no flight) stays valid
    assert validate_incident({"status": "x", "utc": "t",
                              "evidence": ["e"]}) == []
    bad = copy.deepcopy(rec)
    bad["flight"]["events"] = [{"ts": 1.0, "kind": "a"},
                               {"ts": 0.5, "kind": "b"}]
    assert any("ordered" in p for p in validate_incident(bad))
    bad2 = copy.deepcopy(rec)
    bad2["flight"] = {"capacity": 1, "dropped": 0,
                      "events": [{"ts": 0.0, "kind": "a"},
                                 {"ts": 0.1, "kind": "b"}]}
    assert any("capacity" in p for p in validate_incident(bad2))
    bad3 = copy.deepcopy(rec)
    bad3["flight"]["events"] = [{"kind": "a"}]
    assert any("'ts'" in p for p in validate_incident(bad3))
    bad4 = copy.deepcopy(rec)
    bad4["flight"] = "tail"
    assert any("object" in p for p in validate_incident(bad4))


def test_run_resilient_result_carries_flight_history():
    """The loop's ring records steps/checkpoints and rides both the
    RunResult and every incident it writes (the chaos smoke pins the
    fault/rewind content; this pins the plumbing)."""
    import chaos_run
    from apex_tpu.resilience import ResilienceConfig, run_resilient
    _amp, step_fn, state, batch_fn = chaos_run.build_workload(0)
    res = run_resilient(step_fn, state, batch_fn, 4,
                        config=ResilienceConfig(checkpoint_every=2),
                        registry=Registry())
    kinds = [e["kind"] for e in res.flight.dump()["events"]]
    assert kinds.count("step") == 4
    assert "checkpoint" in kinds and "metrics" in kinds


# ---------------------------------------------------------------------------
# obs.fleet: the one merge implementation
# ---------------------------------------------------------------------------

def test_merged_quantile_pinned_against_old_bench_math():
    """The recorded-fixture pin: obs.fleet.merged_quantile must
    reproduce the merge math it was extracted from (inlined here as
    the frozen reference) exactly, windows and stale-max guard
    included."""
    import math as _math

    def old_bench_math(pairs, q):           # as of PR 10, verbatim
        merged = Histogram(Registry(), "_merged_decode_window")
        for hist, mark in pairs:
            merged.counts = merged.counts + (hist.counts - mark[0])
            merged.sum += hist.sum - mark[1]
            merged.count += hist.count - mark[2]
            if hist._max > mark[3]:
                merged._max = max(merged._max, hist._max)
        return merged.quantile(q)

    reg = Registry()
    rng = np.random.default_rng(7)
    h1, h2 = Histogram(reg, "a"), Histogram(reg, "b")
    h1.observe(12.0)                        # pre-mark compile outlier
    m1, m2 = h1.state(), h2.state()
    h1.observe(rng.uniform(0.001, 0.004, 200))
    h2.observe(rng.uniform(0.002, 0.05, 300))
    h2.observe(40.0)                        # post-mark overflow obs
    pairs = [(h1, m1), (h2, m2)]
    for q in (0.1, 0.5, 0.9, 0.99, 1.0):
        old = old_bench_math(pairs, q)
        new = fleet.merged_quantile(pairs, q)
        assert new == old or (
            _math.isnan(new) and _math.isnan(old)), (q, new, old)


def test_merge_histograms_rejects_mixed_ladders():
    reg = Registry()
    h1 = Histogram(reg, "a", buckets=(0.1, 0.2))
    h2 = Histogram(reg, "b", buckets=(0.1, 0.3))
    with pytest.raises(ValueError, match="bucket"):
        fleet.merge_histograms([(h1, None), (h2, None)])
    with pytest.raises(ValueError, match="at least one"):
        fleet.merge_histograms([])


def test_merge_registries_sums_counters_unions_histograms():
    r1, r2 = Registry(), Registry()
    r1.counter("tok_total").inc(5)
    r2.counter("tok_total").inc(7)
    r1.gauge("util").set(0.5)
    r2.gauge("util").set(0.9)
    r1.histogram("lat").observe([0.001] * 10)
    r2.histogram("lat").observe([0.004] * 10)
    merged = fleet.merge_registries([r1, r2])
    assert merged.counter("tok_total").value == 12
    h = merged.histogram("lat")
    assert h.count == 20
    assert abs(h.sum - 0.05) < 1e-12
    # gauges never merge into a scalar — they tabulate
    assert "util" not in merged._instruments
    table = fleet.gauge_table([r1, r2], labels=["replica0", "replica1"])
    assert table["util"] == {"replica0": 0.5, "replica1": 0.9}
    assert fleet.counter_sum([r1, r2], "tok_total") == 12
    assert fleet.counter_sum([r1, r2], "absent_total") == 0
    with pytest.raises(TypeError, match="not a counter"):
        fleet.counter_sum([r1], "util")


def test_merge_registries_rejects_kind_drift():
    r1, r2 = Registry(), Registry()
    r1.counter("x")
    r2.gauge("x")
    with pytest.raises(TypeError, match="vocabulary"):
        fleet.merge_registries([r1, r2])


def test_gauge_table_label_mismatch_is_loud():
    with pytest.raises(ValueError, match="labels"):
        fleet.gauge_table([Registry()], labels=["a", "b"])


# ---------------------------------------------------------------------------
# the OBS_r02 tracing lane (schema bar)
# ---------------------------------------------------------------------------

def test_obs_schema_enforces_tracing_budget():
    """The optional ``tracing`` section (r02+): per-event record cost
    gated at <= 1% of the bench-smoke decode step; the r01 shape
    (no tracing section) stays valid."""
    from apex_tpu.analysis import obs as obs_schema
    doc = json.loads((REPO / "OBS_r02.json").read_text())
    assert obs_schema.validate_obs(doc) == []
    assert doc["tracing"]["overhead_pct"] <= 1.0
    over = copy.deepcopy(doc)
    over["tracing"]["overhead_pct"] = 1.7
    assert any("budget" in p for p in obs_schema.validate_obs(over))
    broken = copy.deepcopy(doc)
    del broken["tracing"]["per_event_us"]
    assert any("per_event_us" in p
               for p in obs_schema.validate_obs(broken))
    legacy = copy.deepcopy(doc)
    del legacy["tracing"]
    assert obs_schema.validate_obs(legacy) == []


def test_flight_and_tracer_stay_ordered_under_concurrent_noters():
    """Regression (review round 2): timestamps are stamped INSIDE the
    lock, so a watchdog thread racing the main loop can never append
    ring/trace events whose ts go backwards (which the incident and
    TRACE schemas reject)."""
    import threading

    fr = FlightRecorder(capacity=4096)
    tr = RequestTracer()

    def hammer(tag):
        for i in range(300):
            fr.note("step", thread=tag, i=i)
            tr.record("decode_step", "u", f"replica{tag}", step=i,
                      token=0, batch=1, tokens=1)

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    ts = [e["ts"] for e in fr.dump()["events"]]
    assert ts == sorted(ts)
    evs = tr.events("u")
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    assert [e["ts"] for e in evs] == sorted(e["ts"] for e in evs)
