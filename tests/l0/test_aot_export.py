"""Lint-gated AOT export (ISSUE 10): ``apex_tpu.analysis.export`` +
``tools/aot_export.py``.

The acceptance path lives here: the mlp train lane exports through the
full gate matrix, reloads from the content-addressed cache in a FRESH
process (subprocess --verify-reload), and the reloaded executable's
outputs are BITWISE equal to the freshly compiled ones; a seeded
``io_callback`` lane is refused with the documented
``export-host-callback`` finding id; cache invalidation (key mismatch
on mesh/policy/jax-version → miss + fallback compile) and corruption
(truncated or bit-flipped entry → skipped with a warning) are pinned;
and the committed EXPORT_r01.json stays schema-valid.
"""

import json
import pickle
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu import analysis  # noqa: E402
from apex_tpu.analysis import export as aot  # noqa: E402
from apex_tpu.analysis import export_schema  # noqa: E402

import aot_export  # noqa: E402


# ---------------------------------------------------------------------------
# the export-compat pass
# ---------------------------------------------------------------------------

def test_io_callback_fires_export_host_callback():
    from jax.experimental import io_callback

    def step(x):
        y = x * 2.0
        io_callback(lambda v: None, None, y.sum(), ordered=True)
        return y.sum()

    rep = analysis.analyze(step, jnp.ones((8, 8)),
                           passes=("export-compat",), compile=False)
    assert not rep.ok
    assert any(f.op == "export-host-callback" for f in rep.errors)


def test_platform_custom_call_fires_and_allowlist_is_quiet():
    line = ('  %0 = stablehlo.custom_call @lapack_sgeqrf'
            '(%arg0) : (tensor<4x4xf32>) -> tensor<4x4xf32>')
    ctx = analysis.PassContext(stablehlo_text=line)
    out = analysis.PASSES["export-compat"](ctx)
    assert len(out) == 1 and out[0].op == "export-platform-call"
    ok_line = ('  %0 = stablehlo.custom_call @Sharding(%arg0) : '
               '(tensor<4x4xf32>) -> tensor<4x4xf32>')
    assert analysis.PASSES["export-compat"](
        analysis.PassContext(stablehlo_text=ok_line)) == []


def test_infeed_fires_export_host_callback():
    ctx = analysis.PassContext(
        stablehlo_text='  %0 = "stablehlo.infeed"(%tok) : ...')
    out = analysis.PASSES["export-compat"](ctx)
    assert len(out) == 1 and out[0].op == "export-host-callback"


def test_static_capture_fires():
    jitted = jax.jit(lambda x, n: x * n, static_argnums=(1,))
    rep = analysis.analyze(jitted, jnp.ones((4,)), 3,
                           passes=("export-compat",), compile=False)
    assert not rep.ok
    assert any(f.op == "export-static-capture" for f in rep.errors)


def test_baked_constant_fires_and_clean_program_is_quiet():
    big = jax.random.normal(jax.random.PRNGKey(0), (512, 640))
    rep = analysis.analyze(lambda x: x @ big, jnp.ones((4, 512)),
                           passes=("export-compat",), compile=False)
    assert not rep.ok
    assert any(f.op == "export-baked-constant"
               and f.bytes == 512 * 640 * 4 for f in rep.errors)
    rep2 = analysis.analyze(lambda x, w: x @ w, jnp.ones((4, 512)), big,
                            passes=("export-compat",), compile=False)
    assert rep2.ok and not rep2.findings


# ---------------------------------------------------------------------------
# cache-key derivation: any part drift is a different key
# ---------------------------------------------------------------------------

def test_key_parts_discriminate_module_mesh_policy_version():
    from apex_tpu.amp import policy as policy_lib
    o1 = policy_lib.resolve(opt_level="O1")
    o2 = policy_lib.resolve(opt_level="O2")
    base = aot.key_parts("module text", mesh="cpu[1]", policy=o1)
    same = aot.key_parts("module text", mesh="cpu[1]", policy=o1)
    assert aot.cache_key(base) == aot.cache_key(same)
    for other in (
            aot.key_parts("module text 2", mesh="cpu[1]", policy=o1),
            aot.key_parts("module text", mesh="tpu[8]", policy=o1),
            aot.key_parts("module text", mesh="cpu[1]", policy=o2),
            aot.key_parts("module text", mesh="cpu[1]", policy=o1,
                          versions={"jax": "9.9.9", "jaxlib": "9.9.9",
                                    "backend": "cpu"})):
        assert aot.cache_key(other) != aot.cache_key(base)


# ---------------------------------------------------------------------------
# write/load invariants: an executable enters AND leaves the cache clean
# ---------------------------------------------------------------------------

def _small_exported(cache_dir):
    """Export a tiny clean program; returns (key, parts, compiled,
    args)."""
    jitted = jax.jit(lambda x, y: {"s": (x @ y).sum(), "p": x + y})
    args = (jnp.ones((16, 16)), jnp.full((16, 16), 2.0))
    lowered = aot.lower_quiet(jitted, *args)
    compiled = lowered.compile()
    ctx = analysis.build_context(lowered)
    report = analysis.run_passes(
        ctx, passes=("donation", "constant-capture", "syncs",
                     "export-compat"))
    parts = aot.key_parts(lowered.as_text(),
                          mesh=aot.mesh_descriptor(lowered))
    key = aot.cache_key(parts)
    aot.write_entry(cache_dir, key, parts, compiled, report,
                    lane="unit")
    return key, parts, compiled, args


def test_write_refuses_dirty_report(tmp_path):
    jitted = jax.jit(lambda x: x * 2)
    compiled = jitted.lower(jnp.ones((4,))).compile()
    dirty = analysis.Report(
        (analysis.Finding("export-compat", "error", "seeded",
                          op="export-host-callback"),),
        ("export-compat",))
    with pytest.raises(aot.ExportRefused) as e:
        aot.write_entry(tmp_path, "k" * 64, {}, compiled, dirty)
    assert e.value.finding_id == "export-host-callback"
    # ...and a clean report WITHOUT the export-compat pass is refused
    # too: serializability is part of the gate
    clean_but_unchecked = analysis.Report((), ("donation",))
    with pytest.raises(aot.ExportRefused) as e2:
        aot.write_entry(tmp_path, "k" * 64, {}, compiled,
                        clean_but_unchecked)
    assert e2.value.finding_id == "export-compat-not-run"
    assert not any(tmp_path.iterdir())   # nothing entered the cache


def test_round_trip_hit_is_bitwise_equal(tmp_path):
    key, parts, compiled, args = _small_exported(tmp_path)
    hit = aot.load_entry(tmp_path, key)
    assert hit is not None
    loaded, manifest = hit
    assert manifest["key"] == key and manifest["lint"]["ok"]
    o1, o2 = compiled(*args), loaded(*args)
    for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_plain_miss_is_silent(tmp_path):
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # any warning would fail
        assert aot.load_entry(tmp_path, "0" * 64) is None


@pytest.mark.parametrize("corruption", ["bitflip", "truncate",
                                        "manifest_lint", "manifest_key"])
def test_corrupt_entry_skipped_with_warning(tmp_path, corruption):
    key, _, compiled, args = _small_exported(tmp_path)
    entry = tmp_path / key
    blob_path = entry / "executable.bin"
    if corruption == "bitflip":
        raw = bytearray(blob_path.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        blob_path.write_bytes(bytes(raw))
    elif corruption == "truncate":
        blob_path.write_bytes(blob_path.read_bytes()[:100])
    elif corruption == "manifest_lint":
        doc = json.loads((entry / "manifest.json").read_text())
        doc["lint"]["ok"] = False     # a dirty gate must not serve
        (entry / "manifest.json").write_text(json.dumps(doc))
    elif corruption == "manifest_key":
        doc = json.loads((entry / "manifest.json").read_text())
        doc["key_parts"]["mesh"] = "tpu[8]"   # parts no longer hash
        (entry / "manifest.json").write_text(json.dumps(doc))
    with pytest.warns(RuntimeWarning, match="skipped"):
        assert aot.load_entry(tmp_path, key) is None
    # ...and probe falls back to a fresh compile on the same key
    jitted = jax.jit(lambda x, y: {"s": (x @ y).sum(), "p": x + y})
    with pytest.warns(RuntimeWarning):
        compiled2, info = aot.probe(jitted, *args,
                                    cache_dir=str(tmp_path))
    assert info["source"] == "compile"
    o1, o2 = compiled(*args), compiled2(*args)
    for a, b in zip(jax.tree.leaves(o1), jax.tree.leaves(o2)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_write_entry_same_key_keeps_existing(tmp_path):
    """Same key == same content: a second writer must keep the
    existing complete entry (never replace it under a concurrent
    reader's feet) and still report success."""
    key, _, _, _ = _small_exported(tmp_path)
    manifest_path = tmp_path / key / "manifest.json"
    before = manifest_path.read_text()
    key2, _, _, _ = _small_exported(tmp_path)   # same program again
    assert key2 == key
    assert manifest_path.read_text() == before  # untouched, not rewritten
    assert aot.load_entry(tmp_path, key) is not None


def test_write_entry_heals_poisoned_entry(tmp_path):
    """A corrupt entry (truncated blob under an intact manifest) made
    the caller miss — re-export under the same key must REBUILD it,
    or the poison would force every future replica through a fresh
    compile forever."""
    key, _, _, _ = _small_exported(tmp_path)
    blob_path = tmp_path / key / "executable.bin"
    blob_path.write_bytes(blob_path.read_bytes()[:50])
    with pytest.warns(RuntimeWarning):
        assert aot.load_entry(tmp_path, key) is None
    key2, _, _, _ = _small_exported(tmp_path)   # heals, not keeps
    assert key2 == key
    assert aot.load_entry(tmp_path, key) is not None


# ---------------------------------------------------------------------------
# probe: hit/miss semantics and key invalidation
# ---------------------------------------------------------------------------


def test_probe_refuses_static_capture_from_cache(tmp_path):
    """The gate path sees static captures exactly as analyze() does: a
    jit specialized on a statically-bound scalar is refused with the
    documented id — otherwise the cache would mint one entry per
    value."""
    jitted = jax.jit(lambda x, n: x * n, static_argnums=(1,))
    x = jnp.ones((4,))
    _, info = aot.probe(jitted, x, 3, cache_dir=str(tmp_path),
                        export_on_miss=True,
                        gate_passes=("export-compat",))
    assert info["source"] == "compile"
    assert info["exported"] is False
    assert info["refused"] == "export-static-capture"
    assert aot.list_entries(tmp_path) == []

def test_probe_miss_exports_then_hits_bitwise(tmp_path):
    jitted = jax.jit(lambda x: (x * 3).sum())
    x = jnp.arange(64, dtype=jnp.float32)
    c1, i1 = aot.probe(jitted, x, cache_dir=str(tmp_path),
                       export_on_miss=True,
                       gate_passes=("donation", "constant-capture",
                                    "syncs", "export-compat"))
    assert i1["source"] == "compile" and i1["exported"] is True
    c2, i2 = aot.probe(jitted, x, cache_dir=str(tmp_path))
    assert i2["source"] == "cache" and i2["key"] == i1["key"]
    assert np.asarray(c1(x)).tobytes() == np.asarray(c2(x)).tobytes()


def test_probe_key_mismatch_on_mesh_policy_version_misses(tmp_path,
                                                          monkeypatch):
    from apex_tpu.amp import policy as policy_lib
    jitted = jax.jit(lambda x: (x * 3).sum())
    x = jnp.arange(64, dtype=jnp.float32)
    gate = ("donation", "constant-capture", "syncs", "export-compat")
    _, i1 = aot.probe(jitted, x, cache_dir=str(tmp_path),
                      export_on_miss=True, gate_passes=gate)
    assert i1["exported"] is True
    # same everything → hit
    _, hit = aot.probe(jitted, x, cache_dir=str(tmp_path))
    assert hit["source"] == "cache"
    # a different mesh topology → different key → miss
    _, m1 = aot.probe(jitted, x, cache_dir=str(tmp_path),
                      mesh="tpu[8]")
    assert m1["source"] == "compile" and m1["key"] != i1["key"]
    # a different resolved policy → miss
    _, m2 = aot.probe(jitted, x, cache_dir=str(tmp_path),
                      policy=policy_lib.resolve(opt_level="O2"))
    assert m2["source"] == "compile" and m2["key"] != i1["key"]
    # a different jax version → miss (a PJRT executable is pinned)
    monkeypatch.setattr(aot, "runtime_versions",
                        lambda: {"jax": "9.9.9", "jaxlib": "9.9.9",
                                 "backend": "future"})
    _, m3 = aot.probe(jitted, x, cache_dir=str(tmp_path))
    assert m3["source"] == "compile" and m3["key"] != i1["key"]


# ---------------------------------------------------------------------------
# the tool: mlp lane round trip (fresh process) + the seeded refusal
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tool_cache(tmp_path_factory):
    """One mlp_o1 + seeded run of the tool's pipeline, shared by the
    round-trip and refusal tests (the mlp O2 + serve lanes ride the
    committed-artifact check and the slow full-tool test)."""
    cache = tmp_path_factory.mktemp("aot_cache")
    lanes = aot_export.run_lanes(["mlp_o1", "seeded"], str(cache))
    return cache, lanes


def test_mlp_lane_exports_clean(tool_cache):
    _, lanes = tool_cache
    rec = lanes["mlp_o1_train"]
    assert rec["export_ok"] and rec["lint"]["ok"]
    assert rec["bitwise_equal"] is True
    assert rec["compile_s"] > 0 and rec["load_s"] >= 0
    assert len(rec["cache_key"]) == 64


def test_seeded_io_callback_refused_with_documented_id(tool_cache):
    cache, lanes = tool_cache
    rec = lanes["seeded_io_callback"]
    assert rec["export_ok"] is False
    assert rec["refused"] == "export-host-callback"
    assert not rec["lint"]["ok"]
    # nothing of it entered the cache: every entry present is the mlp's
    assert all(m.get("lane") == "mlp_o1_train"
               for m in aot.list_entries(cache))


@pytest.mark.slow
def test_reload_in_fresh_process_is_bitwise_equal(tool_cache,
                                                  tmp_path):
    """The acceptance round trip: a SEPARATE python process loads only
    the cache entry (no model build, no trace) and reproduces the
    exporting process's outputs bit for bit."""
    cache, lanes = tool_cache
    key = lanes["mlp_o1_train"]["cache_key"]
    jitted, args, _, _ = aot_export.build_lane("mlp_o1")
    compiled = jitted.lower(*args).compile()
    inputs = [np.asarray(x) for x in
              jax.tree.leaves(aot_export._copy_args(args))]
    out = compiled(*aot_export._copy_args(args))
    expected = [np.asarray(x) for x in jax.tree.leaves(out)]
    io_path = tmp_path / "io.pkl"
    with open(io_path, "wb") as f:
        pickle.dump({"inputs": inputs, "expected": expected}, f)
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "aot_export.py"),
         "--verify-reload", key, "--io", str(io_path),
         "--cache-dir", str(cache)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict == {"hit": True, "bitwise_equal": True,
                       "lane": "mlp_o1_train"}


# ---------------------------------------------------------------------------
# serve engine + train-step startup probes
# ---------------------------------------------------------------------------

def _tiny_serve(cache):
    from apex_tpu import amp
    from apex_tpu.models.gpt import GPTModel, gpt_tiny
    from apex_tpu.serve import Request, ServeConfig, ServeEngine

    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=9,
                       max_blocks_per_slot=4, prefill_chunk=4,
                       aot_cache=cache)
    eng = ServeEngine(params, cfg, scfg)
    eng.submit(Request("a", np.arange(5), max_new_tokens=6))
    return eng, eng.run()


@pytest.mark.slow
def test_serve_engine_probe_miss_then_hit_same_tokens(tmp_path):
    eng1, out1 = _tiny_serve(str(tmp_path))
    assert eng1.aot_info["source"] == "compile"
    assert eng1.aot_info["exported"] is True
    eng2, out2 = _tiny_serve(str(tmp_path))
    assert eng2.aot_info["source"] == "cache"
    assert eng2.aot_info["key"] == eng1.aot_info["key"]
    # one trace for the key-derivation lowering (content addressing
    # needs the module text), and none after: the loaded executable
    # serves the whole stream without another python-body execution
    assert eng2.trace_counts["decode"] == 1
    assert np.array_equal(out1["a"], out2["a"])


def test_serve_engine_env_cache_fallback(tmp_path, monkeypatch):
    """One env var enables the fleet: ``APEX_TPU_AOT_CACHE`` makes an
    engine with no explicit ``aot_cache`` probe (and populate) the
    shared cache."""
    monkeypatch.setenv("APEX_TPU_AOT_CACHE", str(tmp_path))
    eng, _ = _tiny_serve(None)
    assert eng.aot_info is not None
    assert eng.aot_info["source"] == "compile"
    assert eng.aot_info["exported"] is True
    assert aot.list_entries(tmp_path)
    # the lint/export lowering surface survives the probe: with the
    # env var set, graph_lint's serve lane (and the export tool) still
    # get a lowerable jit from the engine, never a Compiled
    assert hasattr(eng._decode_step, "lower")


def test_make_train_step_probe_miss_then_hit_bitwise(tmp_path):
    import policy_audit
    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    loss_fn, p0, batch = policy_audit.RAW_CASES["mlp"]()
    a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level="O1",
                       verbosity=0)

    def run(cache):
        state = a.init(p0)
        if cache is None:
            step = jax.jit(amp.make_train_step(a, loss_fn),
                           donate_argnums=0)
        else:
            step = amp.make_train_step(a, loss_fn, aot_cache=cache)
        for _ in range(2):
            state, metrics = step(state, *batch)
        return float(metrics["loss"]), getattr(step, "aot_info", None)

    l_miss, i_miss = run(str(tmp_path))
    assert i_miss["source"] == "compile" and i_miss["exported"]
    l_hit, i_hit = run(str(tmp_path))
    assert i_hit["source"] == "cache"
    l_plain, _ = run(None)
    assert l_miss == l_hit == l_plain


# ---------------------------------------------------------------------------
# the EXPORT schema + the committed artifact
# ---------------------------------------------------------------------------

def _valid_export_doc():
    key = "a" * 64
    return {
        "round": 1, "platform": "cpu",
        "versions": {"jax": "0.4.37"},
        "cache": {"dir": ".aot_cache", "entries": 1},
        "lanes": {
            "mlp_o1_train": {
                "export_ok": True, "cache_key": key,
                "module_sha256": "b" * 64,
                "lint": {"ok": True, "counts": {"info": 3}},
                "compile_s": 0.3, "load_s": 0.01, "load_ratio": 0.03,
                "bitwise_equal": True},
            "seeded_io_callback": {
                "export_ok": False,
                "refused": "export-host-callback",
                "lint": {"ok": False, "counts": {"error": 2}}},
        },
        "cold_start": {"lane": "mlp_o1_train", "compile_s": 0.3,
                       "load_s": 0.01, "load_ratio": 0.03,
                       "budget": 0.5, "ok": True},
    }


def test_export_schema_valid_doc_passes():
    assert export_schema.validate_export(_valid_export_doc()) == []


def test_export_schema_contradictory_verdicts_fail():
    # exported with a FAILING gating lint report
    doc = _valid_export_doc()
    doc["lanes"]["mlp_o1_train"]["lint"]["ok"] = False
    assert any("contradictory" in p
               for p in export_schema.validate_export(doc))
    # exported without a passing bitwise round trip
    doc = _valid_export_doc()
    doc["lanes"]["mlp_o1_train"]["bitwise_equal"] = False
    assert any("bitwise" in p
               for p in export_schema.validate_export(doc))
    # refused without the documented finding id
    doc = _valid_export_doc()
    del doc["lanes"]["seeded_io_callback"]["refused"]
    assert any("finding id" in p
               for p in export_schema.validate_export(doc))
    # cold_start 'ok' contradicting its own numbers
    doc = _valid_export_doc()
    doc["cold_start"]["load_ratio"] = 0.9
    assert any("cold_start" in p
               for p in export_schema.validate_export(doc))
    # no lanes at all
    assert any("lanes" in p
               for p in export_schema.validate_export(
                   {"round": 1, "platform": "cpu"}))


def test_emit_export_doc_is_schema_valid(tmp_path):
    doc = _valid_export_doc()
    lanes = doc["lanes"]
    lanes["serve_step"] = dict(lanes["mlp_o1_train"],
                               cache_key="c" * 64)
    out = tmp_path / "EXPORT_r77.json"
    problems = aot_export.emit_export(str(out), lanes, tmp_path)
    assert problems == 0
    assert export_schema.validate_export_file(str(out)) == []
    written = json.loads(out.read_text())
    assert written["cold_start"]["lane"] == "serve_step"
    assert written["round"] == 77


def test_committed_export_artifact_validates():
    """EXPORT_r01.json is the schema's reference instance: the mlp
    O1/O2 + serve lanes exported clean with passing round trips, the
    seeded violation refused with the documented id, and the serve
    cold-start gate (load <= 0.5x compile) holding."""
    arts = sorted(REPO.glob("EXPORT_r*.json"))
    assert arts, "no committed EXPORT_r*.json"
    doc = json.loads(arts[-1].read_text())
    assert export_schema.validate_export_file(str(arts[-1])) == []
    lanes = doc["lanes"]
    for name in ("mlp_o1_train", "mlp_o2_train", "serve_step"):
        assert lanes[name]["export_ok"] and \
            lanes[name]["bitwise_equal"], name
    assert lanes["seeded_io_callback"]["refused"] == \
        "export-host-callback"
    assert doc["cold_start"]["lane"] == "serve_step"
    assert doc["cold_start"]["ok"] is True
    assert doc["cold_start"]["load_ratio"] <= 0.5
