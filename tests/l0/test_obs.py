"""apex_tpu.obs — unified runtime telemetry (ISSUE 7).

Contracts under test: (a) registry semantics — get-or-create
instruments, kind safety, host fast path vs deferred device values;
(b) the 1-step-lag resolution contract (a deferred value is never
fetched before ``lag`` ticks, tracers are rejected outright);
(c) histogram quantile correctness against numpy percentiles and the
windowed (``since=``) reads bench relies on; (d) Prometheus/JSON
export goldens; (e) spans land in HLO metadata and time into the
registry; (f) the xplane library — one REAL capture parsed per module
(the fast capture smoke), the chrome-trace fallback pinned on a
synthetic fixture, and all profile tools importing the ONE parser;
(g) the OBS / DECODE_PROFILE schemas, their acceptance bars, and the
committed artifacts; (h) the instrumentation-overhead smoke; (i) the
``tools/profile_decode.py`` CPU-xplane smoke whose bucket names match
DECODE_DECOMPOSE.
"""

import glob
import gzip
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.obs import metrics as obs_metrics
from apex_tpu.obs import spans, xplane
from apex_tpu.analysis import decode_decompose, decode_profile
from apex_tpu.analysis import obs as obs_schema

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_safety():
    reg = obs_metrics.Registry()
    c1 = reg.counter("x_total", "help text")
    assert reg.counter("x_total") is c1
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x_total")
    c1.inc()
    c1.inc(2.5)
    assert c1.value == 3.5
    g = reg.gauge("g")
    g.set(1.0)
    g.set(-2.0)
    assert g.value == -2.0
    # array observations: counter sums, gauge means
    c1.inc(np.asarray([1.0, 1.0]))
    assert c1.value == 5.5
    g.set(np.asarray([2.0, 4.0]))
    assert g.value == 3.0


def test_histogram_quantiles_match_numpy():
    """Dense linear buckets + interpolation track numpy percentiles to
    within one bucket width."""
    reg = obs_metrics.Registry()
    h = reg.histogram("lat", buckets=np.arange(0.01, 1.01, 0.01))
    rng = np.random.default_rng(0)
    data = rng.uniform(0.0, 1.0, 2000)
    h.observe(data)
    assert h.count == 2000
    for q in (0.5, 0.9, 0.99):
        want = float(np.quantile(data, q))
        assert abs(h.quantile(q) - want) <= 0.02, (q, h.quantile(q), want)


def test_histogram_windowed_quantile_and_empty():
    """``quantile(q, since=state())`` isolates one measurement window —
    how a caller reads per-load-level p50/p99 off a long-lived
    engine."""
    reg = obs_metrics.Registry()
    h = reg.histogram("lat", buckets=(0.1, 0.2, 0.4, 0.8))
    h.observe([0.05, 0.05, 0.05])           # old window: fast steps
    mark = h.state()
    assert math.isnan(h.quantile(0.5, since=mark))   # empty window
    h.observe([0.3, 0.3, 0.3, 0.3])         # new window: slower steps
    assert h.quantile(0.25) <= 0.1          # all-time p25: a fast step
    assert 0.2 <= h.quantile(0.5, since=mark) <= 0.4  # window: slow
    assert h.quantile(0.25, since=mark) >= 0.2        # no fast steps
    # in the window
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_histogram_rejects_bad_buckets():
    reg = obs_metrics.Registry()
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("bad", buckets=(1.0, 0.5))


# ---------------------------------------------------------------------------
# the lag contract
# ---------------------------------------------------------------------------

def test_deferred_values_resolve_with_exactly_one_step_lag():
    reg = obs_metrics.Registry(lag=1, resolve_every=1)
    g = reg.gauge("loss")
    g.set(jnp.float32(7.0))                 # device value: deferred
    assert g.value == 0.0 and reg.pending_groups == 1
    reg.tick()                              # seals; still within lag
    assert g.value == 0.0 and reg.pending_groups == 1
    g.set(jnp.float32(9.0))
    reg.tick()                              # first group now ripe
    assert g.value == 7.0
    reg.flush()
    assert g.value == 9.0 and reg.pending_groups == 0


def test_deferred_resolution_batches_but_never_under_lag():
    """resolve_every batches the device fetch; a value still never
    resolves earlier than ``lag`` ticks after it was recorded."""
    reg = obs_metrics.Registry(lag=1, resolve_every=3)
    c = reg.counter("n")
    for i in range(3):
        c.inc(jnp.float32(1.0))
        reg.tick()
        assert c.value == 0.0               # 3 sealed, none past batch
    c.inc(jnp.float32(1.0))
    reg.tick()                              # 4 sealed: 3 ripe -> fetch
    assert c.value == 3.0
    reg.flush()
    assert c.value == 4.0


def test_tracer_recording_is_an_error():
    reg = obs_metrics.Registry()
    g = reg.gauge("inside")

    @jax.jit
    def f(x):
        g.set(x)                            # recording a tracer: bug
        return x

    with pytest.raises(TypeError, match="never inside"):
        f(jnp.float32(1.0))


def test_discard_pending_drops_abandoned_timeline():
    reg = obs_metrics.Registry(lag=1, resolve_every=1)
    c = reg.counter("n")
    c.inc(jnp.float32(5.0))
    reg.discard_pending()
    reg.flush()
    assert c.value == 0.0


def test_instrument_step_wraps_and_lags():
    reg = obs_metrics.Registry()
    calls = []

    def step(state, x):
        calls.append(x)
        return state + 1, {"loss": jnp.float32(0.5),
                           "overflow": jnp.asarray(False)}

    wrapped = obs_metrics.instrument_step(step, registry=reg)
    s = 0
    for i in range(3):
        s, m = wrapped(s, i)
    assert s == 3 and len(calls) == 3
    assert reg.counter("train_steps_total").value == 3.0
    assert reg.histogram("train_step_dispatch_seconds").count == 3
    reg.flush()
    assert reg.gauge("train_loss").value == 0.5
    assert reg.counter("train_overflows_total").value == 0.0


def test_instrument_step_records_fp8_metrics_lagged():
    """The O4 regime's telemetry (amax-saturation gauge,
    overflow-to-rescale counter) rides the SAME deferred/lag machinery
    as loss/overflow — device values recorded at the step boundary,
    resolved by tick/flush, never a fresh host sync."""
    reg = obs_metrics.Registry()

    def step(state, x):
        return state + 1, {"loss": jnp.float32(0.1),
                           "overflow": jnp.asarray(False),
                           "fp8_amax_saturation": jnp.float32(0.97),
                           "fp8_rescales": jnp.asarray(2, jnp.int32)}

    wrapped = obs_metrics.instrument_step(step, registry=reg)
    s = 0
    for i in range(3):
        s, _m = wrapped(s, i)
    reg.flush()
    assert reg.gauge("train_fp8_amax_saturation").value ==         jnp.float32(0.97)
    assert reg.counter("train_fp8_rescales_total").value == 6.0


# ---------------------------------------------------------------------------
# export goldens
# ---------------------------------------------------------------------------

def _golden_registry():
    reg = obs_metrics.Registry()
    c = reg.counter("req_total", "requests served")
    c.inc(3)
    h = reg.histogram("lat_seconds", "step latency",
                      buckets=(0.1, 1.0))
    h.observe([0.05, 0.5, 5.0])
    return reg


def test_prometheus_export_golden():
    assert _golden_registry().to_prometheus() == (
        "# HELP lat_seconds step latency\n"
        "# TYPE lat_seconds histogram\n"
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1.0"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        "lat_seconds_sum 5.55\n"
        "lat_seconds_count 3\n"
        "# HELP req_total requests served\n"
        "# TYPE req_total counter\n"
        "req_total 3\n")


def test_json_export_golden():
    assert _golden_registry().snapshot() == {"metrics": [
        {"name": "lat_seconds", "type": "histogram",
         "help": "step latency",
         "buckets": {"0.1": 1, "1.0": 2, "+Inf": 3},
         "sum": 5.55, "count": 3},
        {"name": "req_total", "type": "counter",
         "help": "requests served", "value": 3.0},
    ]}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_paths_and_timing():
    reg = obs_metrics.Registry()
    assert spans.current_path() == ""
    with spans.span("serve", registry=reg):
        with spans.span("decode_step", registry=reg):
            assert spans.current_path() == "serve/decode_step"
        assert spans.current_path() == "serve"
    assert spans.current_path() == ""
    h = reg.histogram(spans.metric_name("serve/decode_step"))
    assert h.count == 1 and h.sum > 0


def test_span_lands_in_hlo_metadata_not_default_lowering():
    """Inside jit a span contributes metadata ONLY: the scope shows in
    the debug-info asm and the compiled module, while the default
    lowered text — what every analysis pass parses — is unchanged."""
    reg = obs_metrics.Registry()

    def f(x):
        with spans.span("obs_probe/region", registry=reg):
            return x * 2.0 + 1.0

    low = jax.jit(f).lower(jnp.float32(1.0))
    assert "obs_probe" not in low.as_text()
    dbg = low.compiler_ir(dialect="stablehlo").operation.get_asm(
        enable_debug_info=True)
    assert "obs_probe/region" in dbg
    # tracing suppressed the wall-clock observation (trace time is
    # compile cost, not runtime)
    assert reg.histogram(
        spans.metric_name("obs_probe/region")).count == 0


def test_traced_span_decorator():
    reg = obs_metrics.Registry()

    @spans.traced_span("my/step", registry=reg)
    def fn(x):
        return x + 1

    assert fn(1) == 2
    assert reg.histogram(spans.metric_name("my/step")).count == 1


# ---------------------------------------------------------------------------
# xplane library: one real capture (the fast capture smoke) + fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def capture_dir(tmp_path_factory):
    """One REAL profiler capture of a tiny jitted program, shared by
    the parser tests (also the fast replacement for the slow-marked
    capture case in test_profiling.py)."""
    logdir = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(logdir):
        for _ in range(2):
            r = f(x)
        r.block_until_ready()
    import time
    time.sleep(0.5)
    return logdir


def test_real_capture_parses_with_op_times(capture_dir):
    t = xplane.op_times(capture_dir)
    assert t.total_ps > 0
    assert t.by_op                      # op-level events present
    # CPU captures have no device plane: the host XLA executor lines
    # carry the per-instruction events (or, without the tsl proto,
    # the chrome-trace fallback)
    assert t.source in ("xplane-device", "xplane-host", "trace-json")
    by_name, by_cat, total = xplane.parse_xplane(capture_dir)
    assert total == t.total_ps and by_name == t.by_op
    assert xplane.step_markers(capture_dir) == []   # no Steps on CPU


def test_profile_tools_share_the_one_parser():
    """ISSUE 7 satellite: the xplane-parsing tool imports
    apex_tpu.obs.xplane — no private copy."""
    src_ca = (REPO / "tools" / "conv_attrib.py").read_text()
    assert "from apex_tpu.obs.xplane import parse_xplane" in src_ca
    assert "xplane_pb2" not in src_ca   # the copy is gone


def _write_trace_json(tmp_path, events):
    p = tmp_path / "plugins" / "profile" / "x"
    p.mkdir(parents=True)
    with gzip.open(p / "t.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": events}, f)
    return str(tmp_path)


def test_chrome_trace_fallback_device_planes_pinned(tmp_path):
    """The lossy chrome-trace path (behavior pinned when the copies
    were deleted): device-plane 'XLA Ops' events aggregate; host and
    non-op threads are ignored when a device plane produced data."""
    meta = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "name": "thread_name", "pid": 1, "tid": 2,
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 9, "tid": 1,
         "args": {"name": "tf_XLAEigen/1"}},
    ]
    events = meta + [
        {"ph": "X", "pid": 1, "tid": 2, "name": "%fusion.1 = f32[8]",
         "dur": 2.0, "args": {"hlo_category": "fusion"}},
        {"ph": "X", "pid": 1, "tid": 2, "name": "dot.3", "dur": 1.0,
         "args": {"hlo_category": "convolution"}},
        {"ph": "X", "pid": 9, "tid": 1, "name": "dot.9", "dur": 5.0},
        {"ph": "X", "pid": 1, "tid": 3, "name": "ignored", "dur": 9.0},
    ]
    by_name, by_cat, total = xplane.parse_trace_json(
        _write_trace_json(tmp_path, events))
    assert total == int(3.0 * 1e6)          # us -> ps
    assert by_name == {"fusion.1": 2_000_000, "dot.3": 1_000_000}
    assert by_cat == {"fusion": 2_000_000, "convolution": 1_000_000}


def test_chrome_trace_fallback_host_lines_when_no_device(tmp_path):
    """XLA:CPU captures have no device plane — the tf_XLA* executor
    lines are harvested instead, infra events filtered."""
    events = [
        {"ph": "M", "name": "process_name", "pid": 9,
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "name": "thread_name", "pid": 9, "tid": 1,
         "args": {"name": "tf_XLAEigen/1"}},
        {"ph": "X", "pid": 9, "tid": 1, "name": "dot.9", "dur": 5.0},
        {"ph": "X", "pid": 9, "tid": 1,
         "name": "ThreadpoolListener::Record", "dur": 4.0},
    ]
    by_name, _, total = xplane.parse_trace_json(
        _write_trace_json(tmp_path, events))
    assert by_name == {"dot.9": 5_000_000} and total == 5_000_000


def test_bucket_op_times_classifies_and_fills_all_buckets():
    table = xplane.bucket_op_times(
        {"dot.1": 100, "copy.2": 50, "weird.3": 25},
        classify=lambda n: {"dot.1": "kv_read",
                            "copy.2": "kv_write"}.get(n),
        buckets=["kv_read", "kv_write", "sampling"])
    assert table["bucket_ps"] == {"kv_read": 100, "kv_write": 50,
                                  "sampling": 0, "other": 25}
    assert table["total_ps"] == 175 and table["matched_ps"] == 150
    assert table["fractions"]["other"] == round(25 / 175, 4)


# ---------------------------------------------------------------------------
# schemas + committed artifacts
# ---------------------------------------------------------------------------

def test_profile_bucket_vocabulary_pinned_to_decompose():
    """decode_profile duplicates BUCKETS (gate_hygiene loads each
    schema standalone); the two vocabularies must never drift."""
    assert decode_profile.BUCKETS == decode_decompose.BUCKETS


def _valid_obs_doc():
    return {
        "round": 1, "platform": "cpu",
        "overhead": {"steps": 40, "bare_s": 0.5, "instrumented_s": 0.5,
                     "overhead_pct": 0.4},
        "syncs": {"clean": True,
                  "lanes": {"serve_step": {"host_callbacks": 0,
                                           "static_scalars": 0,
                                           "errors": 0}}},
        "export": {"metrics": [{"name": "x", "type": "counter"}]},
    }


def test_obs_schema_accepts_valid_and_enforces_bars():
    assert obs_schema.validate_obs(_valid_obs_doc()) == []
    over = _valid_obs_doc()
    over["overhead"]["overhead_pct"] = 1.7
    assert any("budget" in p for p in obs_schema.validate_obs(over))
    dirty = _valid_obs_doc()
    dirty["syncs"]["lanes"]["serve_step"]["host_callbacks"] = 2
    problems = obs_schema.validate_obs(dirty)
    assert any("hazard" in p for p in problems)
    unclean = _valid_obs_doc()
    unclean["syncs"]["clean"] = False
    assert any("contradiction" in p
               for p in obs_schema.validate_obs(unclean))
    empty = _valid_obs_doc()
    empty["export"] = {"metrics": []}
    assert any("export" in p for p in obs_schema.validate_obs(empty))


def test_decode_profile_schema_accepts_valid_and_rejects_drift():
    doc = {
        "round": 1, "platform": "cpu",
        "config": {"batch": 8, "prefill": 64, "new_tokens": 32},
        "method": "xplane-capture",
        "capture": {"iters": 2, "total_ps": 1000, "source": "xplane"},
        "device_time_ps": {k: 10 for k in decode_profile.BUCKETS},
        "device_time_fractions": {
            k: round(1 / 7, 4) for k in decode_profile.BUCKETS},
        "coverage": round(1 - 1 / 7, 4),
        "verdict": "smoke",
    }
    assert decode_profile.validate_profile(doc) == []
    drifted = dict(doc, device_time_ps=dict(doc["device_time_ps"],
                                            bogus_bucket=5))
    assert any("vocabulary" in p
               for p in decode_profile.validate_profile(drifted))
    empty = dict(doc, capture={"iters": 2, "total_ps": 0,
                               "source": "xplane"})
    assert any("empty capture" in p
               for p in decode_profile.validate_profile(empty))
    noverdict = dict(doc, verdict="  ")
    assert any("verdict" in p
               for p in decode_profile.validate_profile(noverdict))


def test_committed_obs_and_profile_artifacts_validate():
    """The committed OBS_r01 / DECODE_PROFILE_r01 are the schemas'
    reference instances — and OBS_r01 is the acceptance record: the
    measured instrumentation overhead under 1% and the clean syncs
    table over the instrumented serve + train lanes."""
    import gate_hygiene
    assert gate_hygiene._validate_obs(str(REPO)) == []
    assert gate_hygiene._validate_profiles(str(REPO)) == []
    with open(REPO / "OBS_r01.json") as f:
        doc = json.load(f)
    assert doc["overhead"]["overhead_pct"] < 1.0
    assert doc["syncs"]["clean"] is True
    assert "serve_step" in doc["syncs"]["lanes"]
    names = {m["name"] for m in doc["export"]["metrics"]}
    assert {"serve_decode_step_seconds", "serve_tokens_total",
            "train_steps_total"} <= names
    with open(REPO / "DECODE_PROFILE_r01.json") as f:
        prof = json.load(f)
    assert set(prof["device_time_ps"]) == set(decode_decompose.BUCKETS)


# ---------------------------------------------------------------------------
# overhead smoke + the profile_decode CPU-xplane smoke
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_instrumentation_overhead_smoke():
    """The chaos_run-style measurement at (reduced) bench-smoke scale:
    the deterministic per-step instrument cost must sit far under the
    step time.  The committed OBS_r01.json pins the real <1% number;
    this smoke allows noise headroom so a loaded CI box cannot flake
    it."""
    import obs_report
    out = obs_report.measure_overhead(steps=10, reps=2, calls=300)
    assert out["bare_s"] > 0 and out["instrument_us_per_step"] > 0
    assert out["overhead_pct"] < 5.0, out


@pytest.mark.slow
def test_profile_decode_cpu_xplane_smoke(tmp_path):
    """Acceptance: tools/profile_decode.py captures the decode program
    on this backend, buckets device time via obs.xplane into the
    DECODE_DECOMPOSE bucket names, and emits a schema-valid
    document."""
    import profile_decode
    doc = profile_decode.profile(batch=1, prefill=8, new_tokens=8,
                                 tiny=True, iters=1,
                                 logdir=str(tmp_path / "trace"))
    assert decode_profile.validate_profile(doc) == []
    assert set(doc["device_time_ps"]) == set(decode_decompose.BUCKETS)
    assert doc["capture"]["total_ps"] > 0
    assert doc["capture"]["step_ps"] > 0      # the while-body was found
    assert doc["device_time_fractions"]["host_sync"] == 0.0
    # the decode loop's time concentrates in the real buckets, not
    # "other" — the classifier understands the program
    assert doc["coverage"] >= 0.5
