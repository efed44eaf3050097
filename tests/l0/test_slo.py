"""Declarative SLOs over the live registry (``apex_tpu.obs.slo``).

Contracts under test: (a) objective evaluation on scripted registry
states — met / violated / insufficient_window from the closed
vocabulary; (b) the windowed quantile burn-rate math against a numpy
reference (bad_frac over the trailing window divided by the error
budget ``1 − q``); (c) router de-eligibility — a scripted fleet with
one replica forced over its p99 objective routes every new admission
around it; (d) zero new host syncs: an SLO-instrumented serve lane
keeps one trace and the graph-lint syncs pass stays clean on the
compiled step (the evaluator reads resolved host state only).
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.models import GPTModel, gpt_tiny
from apex_tpu.obs.metrics import Registry
from apex_tpu.obs.slo import (
    STATUS_INSUFFICIENT,
    STATUS_MET,
    STATUS_VIOLATED,
    SLObjective,
    SLOEvaluator,
    serve_objectives,
)
from apex_tpu.serve import (
    DisaggRouter,
    Request,
    RouterConfig,
    ServeConfig,
    ServeEngine,
)

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))


# ---------------------------------------------------------------------------
# objective declaration
# ---------------------------------------------------------------------------

def test_objective_validation():
    with pytest.raises(ValueError, match="kind"):
        SLObjective(name="x", kind="median", threshold=1.0, metric="m")
    with pytest.raises(ValueError, match="op"):
        SLObjective(name="x", kind="gauge", threshold=1.0, metric="m",
                    op="eq")
    with pytest.raises(ValueError, match="q="):
        SLObjective(name="x", kind="quantile", threshold=1.0,
                    metric="m", q=1.0)
    with pytest.raises(ValueError, match="ratio_num"):
        SLObjective(name="x", kind="ratio", threshold=1.0)
    with pytest.raises(ValueError, match="metric"):
        SLObjective(name="x", kind="quantile", threshold=1.0)
    with pytest.raises(ValueError, match="objectives"):
        SLOEvaluator(Registry(), [])
    objs = serve_objectives(min_acceptance=0.5)
    assert {o.name for o in objs} == \
        {"decode_p99", "block_util", "spec_acceptance"}
    # window=0 (since-start) is quantile/ratio-only — a gauge has no
    # delta semantics to anchor it
    with pytest.raises(ValueError, match="since-start"):
        SLObjective(name="x", kind="gauge", metric="m", threshold=1.0,
                    window=0)


def test_since_start_window_pins_first_boundary():
    """window=0: the first boundary's snapshot is the permanent base
    (run-scoped objectives — the serve_scenarios cell verdicts), and
    the evaluator holds ONE extra snapshot instead of growing a ring."""
    reg = Registry()
    hist = reg.histogram("lat")
    ev = SLOEvaluator(reg, [SLObjective(
        name="p50", kind="quantile", metric="lat", q=0.5,
        threshold=0.0128, window=0, min_count=2)])
    assert ev.evaluate()["p50"]["status"] == STATUS_INSUFFICIENT
    for v in (0.001, 0.002, 0.003):
        hist.observe(v)
        ev.evaluate()
    rec = ev.last["p50"]
    # every observation since the FIRST boundary is in the window
    assert rec["observations"] == 3 and rec["status"] == STATUS_MET
    # the ring stays bounded at maxlen 1 regardless of boundaries
    assert ev._snaps.maxlen == 1


# ---------------------------------------------------------------------------
# scripted registry states
# ---------------------------------------------------------------------------

def test_quantile_objective_met_violated_insufficient():
    reg = Registry()
    hist = reg.histogram("lat")
    # 0.0128 is a LATENCY_BUCKETS bound — the snap is the identity
    obj = SLObjective(name="p99", kind="quantile", metric="lat",
                      q=0.9, threshold=0.0128, window=4, min_count=5)
    ev = SLOEvaluator(reg, [obj])
    assert ev.evaluate()["p99"]["status"] == STATUS_INSUFFICIENT
    for v in (0.001, 0.002):                 # 2 obs < min_count 5
        hist.observe(v)
    assert ev.evaluate()["p99"]["status"] == STATUS_INSUFFICIENT
    for v in (0.003, 0.004, 0.005, 0.001):
        hist.observe(v)
    rec = ev.evaluate()["p99"]
    assert rec["status"] == STATUS_MET and rec["burn_rate"] == 0.0
    for _ in range(4):                       # tail blowout
        hist.observe(0.05)
    rec = ev.evaluate()["p99"]
    assert rec["status"] == STATUS_VIOLATED and rec["burn_rate"] > 1.0
    assert not ev.violated() or True         # violated() reads .last
    assert ev.violated() is True
    assert ev.summary()["ok"] is False


def test_quantile_burn_rate_matches_numpy_reference():
    """burn = mean(window_obs > T) / (1 − q) — exactly, when T is a
    bucket bound (the evaluator snaps T up to one and records it)."""
    reg = Registry()
    hist = reg.histogram("lat")
    thresh, q, window = 0.0128, 0.9, 4
    obj = SLObjective(name="p99", kind="quantile", metric="lat", q=q,
                      threshold=thresh, window=window, min_count=5)
    ev = SLOEvaluator(reg, [obj])
    ev.evaluate()
    rng = np.random.RandomState(0)
    boundaries = []
    for b in range(6):
        obs = rng.uniform(0.001, 0.01, 20)
        if b >= 3:
            obs = np.concatenate([obs, np.full(8, 0.05)])
        for v in obs:
            hist.observe(float(v))
        boundaries.append(obs)
        rec = ev.evaluate()["p99"]
        win = np.concatenate(boundaries[max(0, len(boundaries)
                                            - window):])
        ref = float(np.mean(win > thresh)) / (1.0 - q)
        assert rec["burn_rate"] == pytest.approx(ref, abs=1e-4), b
        assert rec["observations"] == win.size
        assert rec["status"] == (STATUS_VIOLATED if ref > 1.0
                                 else STATUS_MET)


def test_quantile_threshold_snaps_down_never_fail_open():
    """A threshold between bucket bounds snaps DOWN: a value sitting
    over the declared threshold but under the next bound must still
    violate — the snap can only judge TIGHTER, never looser."""
    from apex_tpu.obs.metrics import LATENCY_BUCKETS
    reg = Registry()
    hist = reg.histogram("lat")
    obj = SLObjective(name="p99", kind="quantile", metric="lat",
                      q=0.99, threshold=0.25, window=2, min_count=1)
    ev = SLOEvaluator(reg, [obj])
    ev.evaluate()
    for _ in range(50):
        hist.observe(0.30)          # 63% over budget, under the next
    rec = ev.evaluate()["p99"]      # power-of-2 bound (0.4096)
    assert rec["snapped_threshold"] == pytest.approx(0.2048)
    assert rec["snapped_threshold"] in LATENCY_BUCKETS
    assert rec["status"] == STATUS_VIOLATED
    # past the whole ladder: judged via the +inf bucket
    reg2 = Registry()
    hist2 = reg2.histogram("lat", buckets=(0.1, 0.2))
    ev2 = SLOEvaluator(reg2, [SLObjective(
        name="p", kind="quantile", metric="lat", q=0.5,
        threshold=99.0, window=2, min_count=1)])
    ev2.evaluate()
    for v in (0.05, 0.15, 50.0, 60.0, 70.0):
        hist2.observe(v)
    rec = ev2.evaluate()["p"]
    assert rec["snapped_threshold"] == 0.2
    assert rec["status"] == STATUS_VIOLATED        # 3/5 > 50% budget
    # UNDER the whole ladder: nothing provably under the bar — every
    # observation counts as exceeding
    reg3 = Registry()
    hist3 = reg3.histogram("lat", buckets=(0.1, 0.2))
    ev3 = SLOEvaluator(reg3, [SLObjective(
        name="p", kind="quantile", metric="lat", q=0.5,
        threshold=0.01, window=2, min_count=1)])
    ev3.evaluate()
    hist3.observe(0.05)
    assert ev3.evaluate()["p"]["status"] == STATUS_VIOLATED


def test_gauge_and_ratio_objectives():
    reg = Registry()
    g = reg.gauge("util")
    acc, prop = reg.counter("acc"), reg.counter("prop")
    ev = SLOEvaluator(reg, [
        SLObjective(name="util", kind="gauge", metric="util", op="le",
                    threshold=0.9, window=4, min_count=1),
        SLObjective(name="rate", kind="ratio", ratio_num="acc",
                    ratio_den="prop", op="ge", threshold=0.5,
                    window=4, min_count=4),
    ])
    g.set(0.5)
    r = ev.evaluate()
    assert r["util"]["status"] == STATUS_MET
    assert r["util"]["burn_rate"] == pytest.approx(0.5 / 0.9, abs=1e-3)
    assert r["rate"]["status"] == STATUS_INSUFFICIENT   # no base yet
    acc.inc(3)
    prop.inc(10)
    r = ev.evaluate()
    assert r["rate"]["status"] == STATUS_VIOLATED       # 0.3 < 0.5
    assert r["rate"]["value"] == pytest.approx(0.3)
    acc.inc(17)
    prop.inc(10)
    r = ev.evaluate()                # window mean now covers 20/30
    assert r["rate"]["status"] == STATUS_MET
    # gauge windowed MEAN: a spike inside the window still judged
    g.set(3.0)
    r = ev.evaluate()
    assert r["util"]["value"] == pytest.approx(
        (0.5 + 0.5 + 0.5 + 3.0) / 4)
    assert r["util"]["status"] == STATUS_VIOLATED


# ---------------------------------------------------------------------------
# router de-eligibility
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    return cfg, a.model_params_from(params)


SCFG = ServeConfig(num_slots=2, block_size=4, num_blocks=17,
                   max_blocks_per_slot=8, prefill_chunk=4)


@pytest.mark.slow
def test_router_routes_around_slo_violating_replica(tiny_model):
    """Scripted fleet: one replica forced over its p99 objective
    loses admission eligibility — every new request lands on the
    other replica — and recovers nothing is special-cased: the gauge
    export says which replica is de-ranked."""
    cfg, params = tiny_model
    slo = (SLObjective(name="decode_p99", kind="quantile",
                       metric="serve_decode_step_seconds", q=0.5,
                       threshold=1e-7,     # impossible bar: any real
                       window=8,           # step violates it
                       min_count=2),)
    router = DisaggRouter(
        params, cfg, SCFG,
        RouterConfig(n_decode_replicas=2, transfer="ship", slo=slo),
        registry=Registry())
    rng = np.random.RandomState(0)
    # warm ONLY replica 0: its histogram gets observations, and the
    # impossible objective flips it to violated
    router.submit(Request(uid="w0",
                          prompt=rng.randint(0, cfg.vocab_size, (5,)),
                          max_new_tokens=6))
    router.run()
    assert [ev.violated() for ev in router.slo_evals] == [True, False]
    assert [g.value for g in router._m_rep_slo] == [0.0, 1.0]
    # new admissions must route around the violating replica
    for i in range(2):
        router.submit(Request(
            uid=f"q{i}", prompt=rng.randint(0, cfg.vocab_size, (4,)),
            max_new_tokens=4))
    router.step()
    assert router.replicas[0].eng.sched.n_active() == 0
    assert router.replicas[1].eng.sched.n_active() == 2
    summary = router.slo_summary()
    assert summary["replica0"]["ok"] is False
    assert summary["replica1"]["ok"] is True
    outs = router.run()
    assert set(outs) == {"w0", "q0", "q1"}   # fleet still drains


# ---------------------------------------------------------------------------
# zero new host syncs on the instrumented lane
# ---------------------------------------------------------------------------

def test_slo_instrumented_engine_one_trace_and_syncs_clean(tiny_model):
    """An engine driven with per-boundary SLO evaluation keeps ONE
    compiled decode step (no retrace), and the graph-lint syncs pass
    is clean on the serve lane — the evaluator reads resolved host
    state only, the compiled program is untouched."""
    cfg, params = tiny_model
    reg = Registry()
    eng = ServeEngine(params, cfg, SCFG, registry=reg)
    ev = SLOEvaluator(reg, serve_objectives(decode_p99_s=10.0,
                                            min_count=2))
    rng = np.random.RandomState(3)
    for i in range(3):
        eng.submit(Request(uid=f"s{i}",
                           prompt=rng.randint(0, cfg.vocab_size,
                                              (4 + 3 * i,)),
                           max_new_tokens=5))
    guard = 0
    while not eng.sched.idle():
        eng.step()
        ev.evaluate()               # the boundary the registry ticks
        guard += 1
        assert guard < 1000
    assert max(eng.trace_counts.values()) == 1
    rec = ev.last["decode_p99"]
    assert rec["status"] == STATUS_MET and rec["observations"] > 0
    # the machine check: syncs pass clean on the compiled serve step
    import graph_lint
    rep = graph_lint.lint_serve("serve_step", passes=("syncs",))
    syncs = rep.by_pass("syncs")
    assert sum(1 for f in syncs if f.op == "host-callback") == 0
    assert sum(1 for f in syncs if f.op == "static-scalar") == 0
    assert len(rep.errors) == 0
