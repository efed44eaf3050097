"""Continuous profiler + drift sentinel (apex_tpu.obs.contprof),
the shared step classifiers (apex_tpu.obs.stepclass), the
PROFILE_DRIFT schema's contradiction rejection, and the HTTP
exposition endpoint.

The sentinel tests are scripted (pure windows through the ONE rule in
apex_tpu/analysis/profile_drift.py); the capture tests run a real
jax.profiler window around a live tiny serve engine — the XLA:CPU
``tf_XLA*`` xplane fallback is what makes that possible in tier-1.
"""

import json
import sys
import urllib.request
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from apex_tpu import amp  # noqa: E402
from apex_tpu.analysis import decode_profile  # noqa: E402
from apex_tpu.analysis import profile_drift as pd  # noqa: E402
from apex_tpu.models.gpt import GPTModel, gpt_tiny  # noqa: E402
from apex_tpu.obs import contprof, stepclass  # noqa: E402
from apex_tpu.obs import metrics as obs_metrics  # noqa: E402
from apex_tpu.obs.exposition import MetricsServer  # noqa: E402
from apex_tpu.obs.flight import FlightRecorder  # noqa: E402
from apex_tpu.resilience import incidents as incidents_lib  # noqa: E402
from apex_tpu.serve import Request, ServeConfig, ServeEngine  # noqa: E402

BAND = 0.05
BASE = {"fractions": {"param_read": 0.1, "kv_read": 0.6,
                      "kv_write": 0.05, "attention": 0.02,
                      "sampling": 0.15, "host_sync": 0.0,
                      "other": 0.08},
        "step_wall_s": 0.003, "source": "test"}


def _frac(**over):
    f = dict(BASE["fractions"])
    for k, v in over.items():
        f[k] = v
    return f


def _windows(specs):
    """specs: [(fractions, wall), ...] -> schema-shaped windows with
    re-derivable out_of_band lists."""
    return [{"index": i, "fractions": fr, "step_wall_s": w,
             "out_of_band": pd.out_of_band(fr, w, BASE, BAND)}
            for i, (fr, w) in enumerate(specs)]


# ---------------------------------------------------------------------------
# vocabulary pins
# ---------------------------------------------------------------------------

def test_bucket_vocabularies_pinned_equal():
    """The duplicated tuples (stdlib schema modules are loaded
    standalone by gate_hygiene) must never drift apart."""
    assert stepclass.DECODE_BUCKETS == decode_profile.BUCKETS
    assert stepclass.DECODE_BUCKETS == pd.DECODE_BUCKETS
    assert stepclass.TRAIN_BUCKETS == pd.TRAIN_BUCKETS
    assert pd.KINDS["serve-decode"] == pd.DECODE_BUCKETS
    assert pd.KINDS["train"] == pd.TRAIN_BUCKETS


# ---------------------------------------------------------------------------
# the sentinel rule (scripted — no capture)
# ---------------------------------------------------------------------------

def test_sentinel_catches_seeded_drift_in_exactly_k_windows():
    sent = contprof.DriftSentinel(baseline=dict(BASE), band=BAND, k=3)
    drifted = _frac(kv_read=0.75, sampling=0.0)
    specs = [(_frac(), 0.003)] * 2 + [(drifted, 0.003)] * 4
    for w in _windows(specs):
        sent.observe(w)
    assert len(sent.drifts) == 1        # latched: no re-confirmation
    d = sent.drifts[0]
    # first out-of-band window is index 2; k=3 -> confirmed at 4
    assert d["window"] == 4
    assert d["bucket"] == "kv_read"
    assert d["windows_out"] == 3


def test_sentinel_quiet_on_in_band_noise_and_isolated_spikes():
    sent = contprof.DriftSentinel(baseline=dict(BASE), band=BAND, k=2)
    spike = _frac(kv_read=0.7, sampling=0.05)
    specs = [(_frac(kv_read=0.62, sampling=0.13), 0.0031),
             (spike, 0.003),            # isolated spike: no confirm
             (_frac(kv_read=0.58, other=0.1), 0.0029),
             (spike, 0.003),            # another isolated spike
             (_frac(), 0.003)]
    for w in _windows(specs):
        sent.observe(w)
    assert sent.drifts == []
    assert not sent.drifting


def test_sentinel_wall_regression_and_recovery_resets_gauge():
    reg = obs_metrics.Registry()
    sent = contprof.DriftSentinel(baseline=dict(BASE), band=BAND, k=2,
                                  registry=reg)
    slow = (_frac(), 0.004)             # +33% wall, fractions in band
    for w in _windows([slow, slow]):
        sent.observe(w)
    assert len(sent.drifts) == 1
    assert sent.drifts[0]["bucket"] == "step_wall"
    assert reg.gauge("serve_profile_drift").value == 1.0
    assert sent.drifting
    sent.observe(_windows([(_frac(), 0.003)])[0])   # recovery
    assert reg.gauge("serve_profile_drift").value == 0.0
    assert not sent.drifting


def test_sentinel_matches_schema_replay():
    """The online machine and the validator's replay are the same
    rule: scripted windows produce identical verdicts."""
    sent = contprof.DriftSentinel(baseline=dict(BASE), band=BAND, k=2)
    rng = np.random.RandomState(3)
    specs = []
    for i in range(12):
        kv = 0.6 + (0.12 if 4 <= i < 8 else rng.uniform(-0.03, 0.03))
        specs.append((_frac(kv_read=round(kv, 4)),
                      round(0.003 * rng.uniform(0.98, 1.02), 6)))
    windows = _windows(specs)
    for w in windows:
        sent.observe(w)
    derived = pd.replay_sentinel(windows, BASE, BAND, 2)
    assert [(d["window"], d["bucket"]) for d in sent.drifts] == \
        [(d["window"], d["bucket"]) for d in derived]


def test_sentinel_first_window_seeds_baseline():
    sent = contprof.DriftSentinel(baseline=None, band=BAND, k=2)
    w0 = {"index": 0, "fractions": _frac(), "step_wall_s": 0.003}
    sent.observe(w0)
    assert sent.baseline["source"] == "first-window"
    assert w0["out_of_band"] == []
    w1 = {"index": 1, "fractions": _frac(kv_read=0.8, sampling=0.0),
          "step_wall_s": 0.003}
    sent.observe(w1)
    assert [e["metric"] for e in w1["out_of_band"]] == \
        ["kv_read", "sampling"]


def test_sentinel_rejects_k1_and_bad_band():
    with pytest.raises(ValueError, match="k="):
        contprof.DriftSentinel(k=1)
    with pytest.raises(ValueError, match="band"):
        contprof.DriftSentinel(k=2, band=1.5)


def test_confirmed_drift_writes_incident_and_flight_tail(tmp_path):
    """The incident is schema-valid, names the bucket, and embeds the
    flight tail whose last events include the drift note."""
    fr = FlightRecorder(capacity=32)
    path = str(tmp_path / "drift_incident.json")
    sent = contprof.DriftSentinel(baseline=dict(BASE), band=BAND, k=2,
                                  flight=fr, incident_path=path)
    drifted = _frac(kv_read=0.8, sampling=0.0)
    windows = _windows([(drifted, 0.003)] * 2)
    windows[1]["top_ops"] = [
        {"op": "fusion.7", "ps": 999, "bucket": "kv_read"},
        {"op": "broadcast.1", "ps": 10, "bucket": "other"}]
    for w in windows:
        sent.observe(w)
    assert len(sent.incidents) == 1
    rec = sent.incidents[0]
    assert rec["status"] == "profile-drift"
    assert "kv_read" in rec["summary"]
    # top offending ops filtered to the drifting bucket
    assert rec["drift"]["top_ops"] == [
        {"op": "fusion.7", "ps": 999, "bucket": "kv_read"}]
    # the flight tail contains the drift event
    kinds = [e["kind"] for e in rec["flight"]["events"]]
    assert "profile_drift" in kinds
    # the written artifact validates against the incident schema
    assert Path(path).exists()
    assert incidents_lib.validate_incident_file(path) == []


def test_drift_objective_is_a_valid_slo():
    obj = contprof.drift_objective()
    assert obj.kind == "gauge"
    assert obj.metric == "serve_profile_drift"


# ---------------------------------------------------------------------------
# the train classifier (fixture-pinned)
# ---------------------------------------------------------------------------

_TRAIN_HLO = """\
HloModule jit_step

%fused_bwd (p: f32[8,8]) -> f32[8,8] {
  %m = f32[8,8] multiply(f32[8,8] %p, f32[8,8] %p), metadata={op_name="jit(step)/jit(main)/transpose(jvp(MLP))/mul"}
  ROOT %r = f32[8,8] add(f32[8,8] %m, f32[8,8] %m), metadata={op_name="jit(step)/jit(main)/jvp(MLP)/add"}
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %fwd.1 = f32[8,8] dot(f32[8,8] %a, f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/jvp(MLP)/dot_general"}
  %bwd.1 = f32[8,8] dot(f32[8,8] %a, f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/transpose(jvp(MLP))/dot_general"}
  %mixed.1 = f32[8,8] fusion(f32[8,8] %a), kind=kLoop, calls=%fused_bwd
  %opt.1 = f32[8,8] add(f32[8,8] %a, f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/amp_optimizer_step/cond/branch_1_fun/add"}
  %unscale.1 = f32[8,8] multiply(f32[8,8] %a, f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/amp_unscale/mul"}
  %scaler.1 = f32[] select(pred[] %p, f32[] %s, f32[] %s), metadata={op_name="jit(step)/jit(main)/amp_scaler_update/select_n"}
  %cast.1 = bf16[8,8] convert(f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/amp_cast/convert_element_type"}
  %other-cond.1 = f32[8,8] add(f32[8,8] %a, f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/cond/branch_1_fun/add"}
  %grad-ar = f32[8,8] all-reduce(f32[8,8] %bwd.1), to_apply=%fused_bwd, metadata={op_name="jit(step)/jit(main)/transpose(jvp(MLP))/psum"}
  %plain.1 = f32[8,8] add(f32[8,8] %a, f32[8,8] %a), metadata={op_name="jit(step)/jit(main)/convert_element_type"}
  ROOT %out = f32[8,8] add(f32[8,8] %opt.1, f32[8,8] %plain.1)
}
"""


def test_train_classifier_fixture():
    """The pinned vocabulary contract: jvp -> fwd, transpose(jvp ->
    bwd (winning over fwd inside a mixed fusion), the step's
    amp_optimizer_step / amp_unscale / amp_scaler_update scopes ->
    optimizer, collective opcode -> collectives (winning over its bwd
    scope), amp_cast and unscoped -> other, host_gap never classified."""
    clf = stepclass.TrainStepClassifier(_TRAIN_HLO)
    assert clf("fwd.1") == "fwd"
    assert clf("bwd.1") == "bwd"
    assert clf("mixed.1") == "bwd"          # precedence is the pin
    assert clf("opt.1") == "optimizer"
    assert clf("unscale.1") == "optimizer"
    assert clf("scaler.1") == "optimizer"
    assert clf("cast.1") is None            # the O2 cast is no update
    assert clf("grad-ar") == "collectives"
    assert clf("plain.1") is None           # -> other
    assert "host_gap" not in set(clf.buckets.values())
    assert {"fwd.1", "bwd.1", "mixed.1", "opt.1"} <= clf.step_ops()


def test_a_cond_outside_the_optimizer_step_is_not_the_optimizer():
    """The old rule took any ``cond`` scope segment for the overflow skip;
    only the one under ``amp_optimizer_step`` is."""
    assert "cond" not in stepclass.OPTIMIZER_SCOPES
    clf = stepclass.TrainStepClassifier(_TRAIN_HLO)
    assert clf("opt.1") == "optimizer"
    assert clf("other-cond.1") is None


def test_train_classifier_on_real_compiled_step():
    """The real amp mlp train step classifies non-trivially: forward,
    backward, AND optimizer ops all present (on graph_lint's lowering)."""
    sys.path.insert(0, str(REPO / "tools"))
    import graph_lint
    step, args, _ = graph_lint.build_train_step("mlp", opt_level="O2")
    state, *batch = args
    txt = step.lower(state, *batch).compile().as_text()
    clf = stepclass.TrainStepClassifier(txt)
    got = set(clf.buckets.values())
    assert {"fwd", "bwd", "optimizer"} <= got
    assert "host_gap" not in got


# ---------------------------------------------------------------------------
# live capture: one profiled serve session (module-scoped — compiles
# one tiny engine, captures two real windows)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_session():
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    params = amp.initialize(opt_level="O2",
                            verbosity=0).model_params_from(params)
    scfg = ServeConfig(num_slots=2, block_size=16, num_blocks=17,
                       max_blocks_per_slot=8, prefill_chunk=16)
    reg = obs_metrics.Registry()
    eng = ServeEngine(params, cfg, scfg, registry=reg)
    sent = contprof.DriftSentinel(band=0.25, k=2, registry=reg)
    pcfg = contprof.ContProfConfig(capture_every=5, capture_steps=2,
                                   warmup_steps=2, max_windows=2,
                                   max_overhead_pct=None)
    prof = contprof.serve_profiler(eng, config=pcfg, sentinel=sent)
    rng = np.random.RandomState(0)
    for i in range(2):
        eng.submit(Request(uid=f"s{i}",
                           prompt=rng.randint(0, cfg.vocab_size, (8,)),
                           max_new_tokens=20))
    steps = 0
    while not eng.sched.idle() and steps < 40:
        eng.step()
        steps += 1
    prof.abort_window()
    return eng, prof, sent, reg, steps


def test_capture_windows_parse_and_classify(profiled_session):
    _eng, prof, _sent, _reg, _steps = profiled_session
    assert len(prof.windows) == 2
    for w in prof.windows:
        assert w["source"] in ("xplane-host", "xplane-device",
                               "trace-json")
        assert w["total_ps"] > 0
        # the live executable's instruction names resolve against the
        # separately-lowered classifier: real attribution, not all-
        # "other"
        assert w["matched_frac"] > 0.3
        assert w["fractions"]["kv_read"] > 0.0
        assert abs(sum(w["fractions"].values()) - 1.0) < 0.02
        assert w["top_ops"]


def test_profiled_steps_excluded_from_latency_histogram(
        profiled_session):
    """The gate-exclusion contract: every step inside a capture
    window lands in serve_profiled_step_seconds, NOT in the
    histogram bench/SLO judge — and the two partitions cover every
    decode step exactly."""
    _eng, prof, _sent, reg, steps = profiled_session
    gated = reg.histogram("serve_decode_step_seconds").count
    profiled = reg.histogram("serve_profiled_step_seconds").count
    captured = sum(w["steps"] for w in prof.windows) \
        + sum(w["steps"] for w in prof.discarded)
    assert profiled == captured
    assert profiled >= 4                   # 2 windows x 2 steps
    assert gated + profiled == steps
    assert reg.counter("serve_profile_windows_total").value == \
        len(prof.windows)


def test_sentinel_saw_session_windows(profiled_session):
    _eng, prof, sent, _reg, _steps = profiled_session
    assert sent.baseline is not None
    assert sent.baseline["source"] == "first-window"
    replay = pd.replay_sentinel(prof.windows, sent.baseline,
                                sent.band, sent.k)
    assert [(d["window"], d["bucket"]) for d in sent.drifts] == \
        [(d["window"], d["bucket"]) for d in replay]


def test_serve_classifier_buckets_real_program(profiled_session):
    eng, _prof, _sent, _reg, _steps = profiled_session
    clf = contprof.serve_classifier_builder(eng)()
    got = set(clf.buckets.values())
    assert {"kv_read", "kv_write", "param_read", "sampling"} <= got


def test_capture_lock_skips_colliding_window():
    """A profiler whose window comes due while another holds the
    process-global tracer SKIPS (counted), never queues."""
    prof = contprof.ContinuousProfiler(
        config=contprof.ContProfConfig(capture_every=3,
                                       capture_steps=2,
                                       warmup_steps=0))
    assert contprof._capture_lock.acquire(blocking=False)
    try:
        opened = prof.step_begin()
    finally:
        contprof._capture_lock.release()
    assert opened is False
    assert prof.skipped_windows == 1
    assert not prof.in_window


def test_suppress_aborts_window_and_restarts_cadence():
    prof = contprof.ContinuousProfiler(
        config=contprof.ContProfConfig(capture_every=4,
                                       capture_steps=2,
                                       warmup_steps=1))
    assert prof.step_begin() is False      # warmup
    assert prof.step_begin() is True       # window opens (real trace)
    assert prof.in_window
    prof.suppress()
    assert not prof.in_window
    # the lock is released and a full interval must elapse again
    assert contprof._capture_lock.acquire(blocking=False)
    contprof._capture_lock.release()
    assert prof.step_begin() is False      # warmup restarted


def test_throttle_reanchors_next_window_a_full_interval_out():
    """After the auto-throttle widens the interval, the next window
    must start the FULL new interval after the window that proved it
    was needed — never at the next multiple of an absolute cadence
    grid (which could come almost immediately and run ~2x over the
    budget the throttle just enforced)."""
    prof = contprof.ContinuousProfiler(
        config=contprof.ContProfConfig(
            capture_every=20, capture_steps=2, warmup_steps=0,
            max_overhead_pct=1.0))
    # a window that opened at step 20 and cost 0.36 s against a 1 s
    # step wall needs a 36-step interval
    prof._step = 21
    prof._win_start_step = 20
    prof._next_start = 40                   # the pre-throttle anchor
    prof._throttle({"capture_s": 0.36, "parse_s": 0.0,
                    "sentinel_s": 0.0, "step_wall_s": 1.0})
    assert prof.effective_every == 36
    assert prof._next_start == 20 + 36     # not 36 (the old grid)


def test_close_path_failure_degrades_to_discarded_window():
    """A failing capture stop/parse must DEGRADE (discarded window,
    lock released), never propagate into the loop the profiler
    watches — and later steps must go back to the gated histogram."""
    class BrokenParse(contprof.ContinuousProfiler):
        def _parse_window(self):
            raise OSError("capture dir vanished")

    prof = BrokenParse(
        config=contprof.ContProfConfig(capture_every=4,
                                       capture_steps=1,
                                       warmup_steps=0))
    assert prof.step_begin() is True     # real trace opens
    w = prof.step_end(0.001)             # parse raises inside
    assert w is not None and "discarded" in w
    assert "parse failed" in w["discarded"]
    assert len(prof.discarded) == 1 and not prof.windows
    assert not prof.in_window
    assert contprof._capture_lock.acquire(blocking=False)
    contprof._capture_lock.release()
    assert prof.step_begin() is False    # back to the gated path


def test_obs_schema_rejects_zero_step_wall_contprof():
    """A contprof lane with step_wall_ms = 0 must be invalid — an inf
    'derived' overhead would make the re-derivation check vacuous."""
    from apex_tpu.analysis import obs as obs_schema
    doc = json.loads((REPO / "OBS_r03.json").read_text())
    assert obs_schema.validate_obs(doc) == []
    doc["contprof"]["step_wall_ms"] = 0
    assert any("step_wall_ms must be > 0" in p
               for p in obs_schema.validate_obs(doc))


def test_classifier_builder_drops_closure_and_captures_avals():
    """The train builder captures only ShapeDtypeStruct avals (never
    the live state/batch arrays — gigabytes on a real model), and the
    profiler drops the builder closure after its one build."""
    @jax.jit
    def stepf(s, x):
        return s * 2.0, {"loss": (s * x).sum()}

    state = jnp.ones((4,))
    batch = (jnp.arange(4, dtype=jnp.float32),)
    builder = contprof.train_classifier_builder(stepf, state, batch)
    cells = jax.tree_util.tree_leaves(
        [c.cell_contents for c in builder.__closure__])
    arrays = [c for c in cells if isinstance(c, jax.Array)]
    assert not arrays, f"builder closure pins live arrays: {arrays}"
    prof = contprof.ContinuousProfiler(
        buckets=contprof.TRAIN_BUCKETS, classifier_builder=builder)
    assert prof._classifier() is not None
    assert prof._builder is None            # closure released
    # "has a source" must survive the release, so run_resilient never
    # supplies (and pins) a second closure
    assert prof.has_classifier_builder


# ---------------------------------------------------------------------------
# schema: contradiction classes
# ---------------------------------------------------------------------------

def _valid_doc():
    clean = _windows([(_frac(kv_read=0.61), 0.003),
                      (_frac(kv_read=0.59), 0.0031)])
    drifted = _frac(kv_read=0.8, sampling=0.0)
    seeded_w = _windows([(_frac(), 0.003),
                         (drifted, 0.003), (drifted, 0.003)])
    return {
        "round": 1, "platform": "cpu", "kind": "serve-decode",
        "config": {}, "band": {"value": BAND, "source": "test"},
        "k": 2,
        "sessions": {
            "clean": {"baseline": dict(BASE), "windows": clean,
                      "drifts": [], "quiet": True},
            "seeded": {"baseline": dict(BASE), "windows": seeded_w,
                       "seed": {"bucket": "kv_read", "factor": 2.0,
                                "from_window": 1},
                       "drifts": pd.replay_sentinel(
                           seeded_w, BASE, BAND, 2),
                       "quiet": False},
        },
        "gate": {"clean_quiet": True, "seeded_caught": True,
                 "ok": True},
        "note": "test doc",
    }


def test_schema_valid_doc_passes():
    doc = _valid_doc()
    assert pd.validate_profile_drift(doc) == []
    drifts = doc["sessions"]["seeded"]["drifts"]
    assert [(d["window"], d["bucket"]) for d in drifts] == \
        [(2, "kv_read")]


def test_schema_rejects_quiet_verdict_over_out_of_band_run():
    doc = _valid_doc()
    doc["sessions"]["seeded"]["drifts"] = []
    doc["sessions"]["seeded"]["quiet"] = True
    doc["gate"]["seeded_caught"] = False
    doc["gate"]["ok"] = False
    problems = pd.validate_profile_drift(doc)
    assert any("CONTRADICTORY" in p and "replaying" in p
               for p in problems)


def test_schema_rejects_invented_drift():
    doc = _valid_doc()
    doc["sessions"]["clean"]["drifts"] = [
        {"window": 1, "bucket": "kv_read", "windows_out": 2}]
    doc["sessions"]["clean"]["quiet"] = False
    problems = pd.validate_profile_drift(doc)
    assert any("CONTRADICTORY" in p and "clean" in p
               for p in problems)


def test_schema_rejects_lying_out_of_band_list():
    """A window whose recorded excursion list contradicts its own
    recorded fractions is invalid — in BOTH directions."""
    doc = _valid_doc()
    doc["sessions"]["seeded"]["windows"][1]["out_of_band"] = []
    problems = pd.validate_profile_drift(doc)
    assert any("derive" in p and "out_of_band" in p
               for p in problems)


def test_schema_rejects_fabricated_excursion_numbers():
    """An excursion naming the RIGHT metric but carrying invented
    value/baseline/delta numbers (a dramatized or minimized drift) is
    the same fabrication class as a lying metric list — the numbers
    must re-derive from the recorded fractions too."""
    doc = _valid_doc()
    exc = doc["sessions"]["seeded"]["windows"][1]["out_of_band"]
    assert exc, "fixture window must be out of band"
    exc[0]["delta"] = round(exc[0]["delta"] * 10, 4)   # dramatized
    problems = pd.validate_profile_drift(doc)
    assert any("re-deriving from the recorded fractions" in p
               for p in problems)


def test_schema_rejects_gate_contradiction():
    doc = _valid_doc()
    doc["gate"]["ok"] = False
    problems = pd.validate_profile_drift(doc)
    assert any("gate.ok" in p for p in problems)


def test_schema_rejects_drift_not_naming_seeded_bucket():
    doc = _valid_doc()
    doc["sessions"]["seeded"]["seed"]["bucket"] = "attention"
    problems = pd.validate_profile_drift(doc)
    assert any("name the bucket" in p for p in problems)


def test_schema_rejects_k1_and_unknown_bucket():
    doc = _valid_doc()
    doc["k"] = 1
    assert any("k must be >= 2" in p
               for p in pd.validate_profile_drift(doc))
    doc = _valid_doc()
    doc["sessions"]["clean"]["windows"][0]["fractions"]["flops"] = 0.1
    assert any("unknown buckets" in p
               for p in pd.validate_profile_drift(doc))


def test_committed_profile_drift_artifact():
    """The committed PROFILE_DRIFT_r01.json is the schema's reference
    instance: valid, both lanes present, gate green."""
    arts = sorted(REPO.glob("PROFILE_DRIFT_r*.json"))
    assert arts, "PROFILE_DRIFT_r01.json must be committed"
    doc = json.loads(arts[-1].read_text())
    assert pd.validate_profile_drift(doc) == []
    assert doc["gate"]["ok"] is True
    assert doc["sessions"]["clean"]["quiet"] is True
    seeded = doc["sessions"]["seeded"]
    assert seeded["drifts"][0]["bucket"] == seeded["seed"]["bucket"]


def test_committed_obs_r03_contprof_lane():
    """The committed OBS round carries the contprof overhead lane
    under budget and the contprof-instrumented serve lane in its
    clean syncs table."""
    arts = sorted(REPO.glob("OBS_r*.json"))
    doc = json.loads(arts[-1].read_text())
    cp = doc.get("contprof")
    assert cp is not None, "newest OBS round must carry the lane"
    assert cp["overhead_pct"] <= 1.0
    assert "serve_step_contprof" in doc["syncs"]["lanes"]
    assert doc["syncs"]["clean"] is True


# ---------------------------------------------------------------------------
# exposition endpoint
# ---------------------------------------------------------------------------

def test_metrics_server_smoke():
    reg = obs_metrics.Registry()
    reg.counter("serve_tokens_total", "t").inc(5)
    reg.histogram("serve_decode_step_seconds", "h").observe(0.002)
    rep = obs_metrics.Registry()
    rep.counter("serve_tokens_total", "t").inc(7)
    rep.gauge("serve_block_utilization", "u").set(0.5)
    srv = MetricsServer(registry=reg,
                        fleet_registries={"replica0": reg,
                                          "replica1": rep})
    host, port = srv.start()
    try:
        def get(path):
            with urllib.request.urlopen(
                    f"http://{host}:{port}{path}", timeout=5) as r:
                return r.read().decode()
        body = get("/metrics")
        assert "# TYPE serve_tokens_total counter" in body
        assert "serve_tokens_total 5" in body
        assert "serve_decode_step_seconds_bucket" in body
        fleet = get("/fleet")
        assert "serve_tokens_total 12" in fleet   # counters SUM
        assert "# gauge-table" in fleet
        assert "replica1" in fleet
        assert get("/healthz").strip() == "ok"
        with pytest.raises(urllib.error.HTTPError):
            get("/nope")
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# router wiring (no captures: cadence far beyond the stream)
# ---------------------------------------------------------------------------

def test_router_contprof_wiring_and_drift_deranking():
    from apex_tpu.serve import DisaggRouter, RouterConfig
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    params = amp.initialize(opt_level="O2",
                            verbosity=0).model_params_from(params)
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=9,
                       max_blocks_per_slot=4, prefill_chunk=4)
    rcfg = RouterConfig(
        n_decode_replicas=2, transfer="recompute",
        contprof=contprof.ContProfConfig(capture_every=10_000,
                                         capture_steps=2))
    router = DisaggRouter(params, cfg, scfg, rcfg,
                          registry=obs_metrics.Registry())
    assert len(router.profilers) == 2
    # staggered phases: fleet windows never collide on the
    # process-global tracer
    phases = [p.config.phase for p in router.profilers]
    assert len(set(phases)) == 2
    # each replica's own registry carries the sentinel gauge
    for rep in router.replicas:
        assert "serve_profile_drift" in rep.eng.metrics._instruments
    # a confirmed-unrecovered drift DE-RANKS the replica: admission
    # prefers the clean one even when the drifted one is emptier
    router.sentinels[0]._active = True
    req = Request(uid="r", prompt=np.zeros(4, np.int32),
                  max_new_tokens=4)
    pick = router._pick_replica(req)
    assert pick is router.replicas[1]
    # ...but a fleet whose every replica drifted still serves
    router.sentinels[1]._active = True
    assert router._pick_replica(req) is not None
    # killing a replica mid-window must abort ITS open capture —
    # a dead replica steps no more, so a held capture lock would
    # silently stop fleet-wide profiling for the rest of the run
    p0 = router.profilers[0]
    p0._next_start = 2
    assert p0.step_begin() is False     # warmup
    assert p0.step_begin() is True      # real trace opens
    assert p0.in_window
    router.kill_replica(0)
    assert not p0.in_window
    assert contprof._capture_lock.acquire(blocking=False)
    contprof._capture_lock.release()


# ---------------------------------------------------------------------------
# run_resilient integration (train vocabulary, real capture)
# ---------------------------------------------------------------------------

def test_run_resilient_with_train_profiler():
    sys.path.insert(0, str(REPO / "tools"))
    import chaos_run

    from apex_tpu.resilience import run_resilient
    from apex_tpu.resilience.loop import ResilienceConfig
    _a, step_fn, state0, batch_fn = chaos_run.build_workload(
        0, features=(32, 32), batch=16, d_in=16)
    reg = obs_metrics.Registry()
    sent = contprof.DriftSentinel(band=0.5, k=2, name="train",
                                  registry=reg)
    prof = contprof.train_profiler(
        config=contprof.ContProfConfig(capture_every=4,
                                       capture_steps=2,
                                       warmup_steps=2, max_windows=1,
                                       max_overhead_pct=None),
        sentinel=sent, registry=reg)
    result = run_resilient(step_fn, state0, batch_fn, num_steps=10,
                           config=ResilienceConfig(
                               watchdog_timeout_s=120.0),
                           registry=reg, profiler=prof)
    assert result.steps_completed == 10
    assert len(prof.windows) == 1
    w = prof.windows[0]
    assert set(w["fractions"]) == set(stepclass.TRAIN_BUCKETS)
    named = sum(w["fractions"][b] for b in
                ("fwd", "bwd", "optimizer", "collectives"))
    assert named > 0.0                  # real attribution happened
    assert not prof.in_window           # nothing leaked
    assert contprof._capture_lock.acquire(blocking=False)
    contprof._capture_lock.release()
