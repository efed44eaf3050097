"""The memory / cost / syncs lint passes (ISSUE 4 tentpole).

Each pass must FIRE on a tiny crafted violating program — a known
dropped donation, a known io_callback, a known oversized temp against
a small budget, a known static-scalar retrace hazard — with the exact
finding code pinned, and stay QUIET (error-free) on clean programs.
Everything runs on CPU-jitted programs: the whole point of the memlint
passes is that XLA's ``memory_analysis()`` / ``cost_analysis()`` and
the callback/alias text are available without a TPU.
"""

import functools
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from apex_tpu import analysis  # noqa: E402
from apex_tpu.analysis import cost as cost_mod  # noqa: E402
from apex_tpu.analysis import memlint  # noqa: E402
from apex_tpu.analysis import memory as memory_mod  # noqa: E402


def _codes(report, pass_name, severity=None):
    return [f.op for f in report.by_pass(pass_name)
            if severity is None or f.severity == severity]


# ---------------------------------------------------------------------------
# memory
# ---------------------------------------------------------------------------

def test_memory_dropped_donation_is_error():
    """A donated arg with no same-shaped output: the compiled alias
    table omits it, and the memory pass reports the compiled OUTCOME
    (the donation pass reports the request — both fire)."""
    def g(x):
        return (x[:2] * 2.0).sum()

    rep = analysis.analyze(g, jnp.ones((128, 128)), donate_argnums=(0,),
                           passes=("memory",))
    assert not rep.ok
    errs = [f for f in rep.by_pass("memory") if f.severity == "error"]
    assert [f.op for f in errs] == ["donation-dropped"]
    assert errs[0].bytes == 128 * 128 * 4


def test_memory_budget_violation_fires_on_oversized_temp():
    """A matmul's temp buffers push the static peak over a deliberately
    tiny budget — the ``hbm-budget`` error carries the peak bytes."""
    def f(x):
        return (x @ x.T).sum()

    x = jnp.ones((256, 256), jnp.float32)
    rep = analysis.analyze(f, x, passes=("memory",),
                           options={"memory": {"budget_bytes": 1024}})
    assert not rep.ok
    errs = [f for f in rep.errors if f.op == "hbm-budget"]
    assert len(errs) == 1
    assert errs[0].bytes > 1024              # the recorded peak
    # the same program inside a sane budget is clean
    rep2 = analysis.analyze(f, x, passes=("memory",),
                            options={"memory":
                                     {"budget_bytes": 1 << 30}})
    assert rep2.ok


def test_memory_honored_donation_quiet_with_alias_table():
    def f(x):
        return x * 2.0

    rep = analysis.analyze(f, jnp.ones((64, 64)), donate_argnums=(0,),
                           passes=("memory",))
    assert rep.ok
    infos = rep.by_pass("memory")
    table = [f for f in infos if f.op == "donation-alias"]
    assert len(table) == 1 and "1/1" in table[0].message
    peak = [f for f in infos if f.op == "peak-hbm"]
    assert peak and peak[0].bytes > 0


def test_memory_pass_skips_uncompiled():
    rep = analysis.analyze(lambda x: x + 1.0, jnp.ones((4,)),
                           passes=("memory",), compile=False)
    assert rep.ok
    assert "skipped" in rep.by_pass("memory")[0].message


def test_memory_stats_peak_formula():
    """peak = args + outputs + temps − aliased, per device."""
    step = jax.jit(lambda w: w * 2.0, donate_argnums=(0,))
    compiled = step.lower(jnp.ones((64, 64))).compile()
    stats = memory_mod.memory_stats(compiled)
    assert stats["peak_hbm_bytes"] == (
        stats["argument_bytes"] + stats["output_bytes"]
        + stats["temp_bytes"] - stats["alias_bytes"])
    assert stats["alias_bytes"] == 64 * 64 * 4   # the honored donation


# ---------------------------------------------------------------------------
# syncs
# ---------------------------------------------------------------------------

def test_syncs_io_callback_on_step_path_is_error():
    from jax.experimental import io_callback

    def f(x):
        y = io_callback(lambda v: np.asarray(v),
                        jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y * 2.0

    rep = analysis.analyze(f, jnp.ones((4,)), passes=("syncs",))
    assert not rep.ok
    assert _codes(rep, "syncs", "error") == ["host-callback"]
    # the lowering-only fallback classifies from StableHLO attributes
    rep2 = analysis.analyze(f, jnp.ones((4,)), passes=("syncs",),
                            compile=False)
    assert not rep2.ok
    assert _codes(rep2, "syncs", "error") == ["host-callback"]


def test_syncs_debug_print_warns_not_gates():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 1.5

    rep = analysis.analyze(f, jnp.ones((4,)), passes=("syncs",))
    assert rep.ok, rep.format()   # warning, not error
    assert "debug-callback" in _codes(rep, "syncs", "warning")


def test_syncs_pure_callback_warns():
    def f(x):
        y = jax.pure_callback(lambda v: np.asarray(v),
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    rep = analysis.analyze(f, jnp.ones((4,)), passes=("syncs",))
    assert rep.ok
    assert "pure-callback" in _codes(rep, "syncs", "warning")


def test_syncs_infeed_crafted_hlo_is_error():
    hlo = ("ENTRY %main (t: token[]) -> f32[4] {\n"
           "  %infeed = ((f32[4]{0}), token[]) infeed(token[] %t)\n"
           "}\n")
    ctx = analysis.PassContext(stablehlo_text="", hlo_text=hlo)
    out = analysis.PASSES["syncs"](ctx)
    errs = [f for f in out if f.severity == "error"]
    assert errs and "infeed" in errs[0].message


def test_syncs_static_scalar_retrace_warns():
    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, n):
        return x * n

    rep = analysis.analyze(f, jnp.ones((8,)), 3, passes=("syncs",))
    assert rep.ok    # warning: legitimate shape statics exist
    warns = [f for f in rep.by_pass("syncs")
             if f.op == "static-scalar"]
    assert len(warns) == 1 and "recompiles" in warns[0].message
    assert "arg1=3" in warns[0].message   # exact attribution


def test_syncs_static_scalar_mixed_with_dynamic_is_not_misattributed():
    """A static int ALONGSIDE a dynamically-passed Python float: the
    traced signature cannot say which is which, so the finding names
    the candidate set at info severity — never a warning pointing at
    the dynamic arg alone."""
    @functools.partial(jax.jit, static_argnums=(0,))
    def f(n, x, s):
        return x * n * s

    rep = analysis.analyze(f, 4, jnp.ones((8,)), 2.0,
                           passes=("syncs",))
    hits = [f for f in rep.by_pass("syncs")
            if f.op == "static-scalar"]
    assert len(hits) == 1 and hits[0].severity == "info"
    assert "cannot say which" in hits[0].message
    assert "arg0=4" in hits[0].message and "arg2=2.0" in hits[0].message


def test_syncs_nonnumeric_static_does_not_misattribute_dynamic_float():
    """The real static is a mode STRING; the Python float is dynamic.
    The exact-attribution branch must not fire (it would name the
    dynamic float as static while the same run reports it weak-typed
    traced)."""
    @functools.partial(jax.jit, static_argnums=(1,))
    def f(scale, mode):
        return scale * (2.0 if mode == "mul" else 0.5)

    rep = analysis.analyze(f, 0.5, "mul", passes=("syncs",))
    hits = [f for f in rep.by_pass("syncs")
            if f.op == "static-scalar"]
    assert len(hits) == 1 and hits[0].severity == "info"
    assert "cannot say which" in hits[0].message
    # no warning-severity claim that arg0 is static
    assert not [f for f in rep.by_pass("syncs")
                if f.severity == "warning"]


def test_syncs_weak_scalar_info_and_clean_program_quiet():
    rep = analysis.analyze(lambda x, s: x * s, jnp.ones((8,)), 2.5,
                           passes=("syncs",))
    assert rep.ok
    assert "weak-scalar" in _codes(rep, "syncs", "info")
    # arrays-only program: nothing to say
    rep2 = analysis.analyze(lambda x: x * 2.0, jnp.ones((8,)),
                            passes=("syncs",))
    assert rep2.ok and not rep2.findings


def test_syncs_inplace_read_race_info():
    rep = analysis.analyze(lambda x: x * 2.0, jnp.ones((32, 32)),
                           donate_argnums=(0,), passes=("syncs",))
    assert rep.ok
    infos = [f for f in rep.by_pass("syncs")
             if f.op == "inplace-read-race"]
    assert len(infos) == 1 and infos[0].bytes == 32 * 32 * 4


# ---------------------------------------------------------------------------
# cost
# ---------------------------------------------------------------------------

def test_cost_pass_records_flops_and_bytes():
    rep = analysis.analyze(lambda a, b: (a @ b).sum(),
                           jnp.ones((64, 64)), jnp.ones((64, 64)),
                           passes=("cost",))
    assert rep.ok
    codes = _codes(rep, "cost")
    assert "flops" in codes and "hbm-bytes" in codes


def test_cost_roofline_expectation_math():
    exp = cost_mod.roofline_expectation(
        flops=1e6, hbm_bytes=1e6, peak_flops=100e12,
        peak_hbm_bytes_per_s=1e12)
    assert exp["intensity_flops_per_byte"] == 1.0
    assert exp["bound"] == "bandwidth"
    assert exp["ceiling_flops_per_s"] == 1e12
    assert exp["ceiling_util"] == pytest.approx(0.01)
    exp2 = cost_mod.roofline_expectation(
        flops=1e9, hbm_bytes=1.0, peak_flops=100e12,
        peak_hbm_bytes_per_s=1e12)
    assert exp2["bound"] == "compute" and exp2["ceiling_util"] == 1.0


# ---------------------------------------------------------------------------
# one-lowering sharing (the analyze double-lowering fix)
# ---------------------------------------------------------------------------

def test_mixed_pass_list_shares_one_context():
    """Compiled-evidence passes (memory/cost) and lowering-only passes
    (policy, constant-capture) run from ONE analyze call — a single
    lowering and a single compilation feed every pass."""
    def fwd(w, x):
        h = jnp.matmul(x, w).astype(jnp.bfloat16)
        return jax.nn.softmax(h, axis=-1).astype(jnp.float32).sum()

    w = jnp.ones((8, 8), jnp.float32)
    x = jnp.ones((4, 8), jnp.float32)
    rep = analysis.analyze(fwd, w, x,
                           passes=("constant-capture", "memory", "cost",
                                   "policy"))
    # policy fires from the shared stablehlo text while memory/cost
    # read the shared executable
    assert any(f.pass_name == "policy" and f.severity == "error"
               for f in rep.findings)
    assert any(f.op == "peak-hbm" for f in rep.by_pass("memory"))
    assert any(f.op == "flops" for f in rep.by_pass("cost"))


def test_build_context_carries_executable_and_outputs():
    lowered = jax.jit(lambda x: (x * 2, x.sum())).lower(
        jnp.ones((4, 4)))
    ctx = analysis.build_context(lowered)
    assert ctx.compiled is not None and ctx.hlo_text
    assert [o.nbytes for o in ctx.outputs] == [64, 4]
    ctx2 = analysis.build_context(lowered, compile=False)
    assert ctx2.compiled is None and ctx2.hlo_text is None


def test_derived_tables_memoized_per_context():
    """The alias set / kept map / donation table are parsed from the
    HLO text once per lowering, however many passes consume them —
    repeated calls return the SAME object from the context memo."""
    from apex_tpu.analysis import donation as donation_mod
    from apex_tpu.analysis import memory as memory_mod

    lowered = jax.jit(lambda s, x: (s + x, x.sum()),
                      donate_argnums=(0,)).lower(
        jnp.ones((16, 16)), jnp.ones((16, 16)))
    ctx = analysis.build_context(lowered)
    t1 = memory_mod.donation_table(ctx)
    t2 = memory_mod.donation_table(ctx)
    assert t1 is t2 and t1 and t1[0]["aliased"]
    assert donation_mod.kept_index_map(ctx) \
        is donation_mod.kept_index_map(ctx)
    assert donation_mod.aliased_parameter_set(ctx) \
        is donation_mod.aliased_parameter_set(ctx)
    # a second context has its own memo — no cross-lowering bleed
    ctx2 = analysis.build_context(
        jax.jit(lambda x: x * 2).lower(jnp.ones((4,))))
    assert memory_mod.donation_table(ctx2) == []


# ---------------------------------------------------------------------------
# memlint schema
# ---------------------------------------------------------------------------

def _valid_doc():
    return {"round": 1, "platform": "cpu", "budget_bytes": None,
            "lanes": {"mlp_o1_train": {
                "ok": True, "peak_hbm_bytes": 123,
                "breakdown": {"argument_bytes": 100},
                "donation": [{"arg": "w", "bytes": 4, "aliased": True}],
                "cost": {"flops": 1.0, "hbm_bytes": 2.0},
                "findings": {"info": 3}}},
            "multichip": {"n_devices": 8,
                          "slices": {"fsdp": {"ok": True,
                                              "hbm_bytes_per_device": 9}}}}


def test_memlint_schema_accepts_valid_doc():
    assert memlint.validate_memlint(_valid_doc()) == []


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.pop("lanes"), "lanes"),
    (lambda d: d.pop("round"), "round"),
    (lambda d: d["lanes"]["mlp_o1_train"].pop("peak_hbm_bytes"),
     "peak_hbm_bytes"),
    (lambda d: d["lanes"]["mlp_o1_train"].update(peak_hbm_bytes=-1),
     "peak_hbm_bytes"),
    (lambda d: d["lanes"]["mlp_o1_train"].update(
        donation=[{"nope": 1}]), "donation"),
    (lambda d: d["lanes"]["mlp_o1_train"].update(
        cost={"flops": "fast"}), "hbm_bytes"),
    (lambda d: d.update(multichip={"n_devices": 8}), "multichip"),
])
def test_memlint_schema_rejects(mutate, needle):
    doc = _valid_doc()
    mutate(doc)
    problems = memlint.validate_memlint(doc)
    assert problems and any(needle in p for p in problems), problems


def test_memlint_file_validator_and_repo_artifact(tmp_path):
    p = tmp_path / "MEMLINT_r09.json"
    p.write_text('{"round": ')
    assert any("unreadable" in m
               for m in memlint.validate_memlint_file(str(p)))
    committed = REPO / "MEMLINT_r01.json"
    assert committed.exists(), "MEMLINT_r01.json must be committed"
    assert memlint.validate_memlint_file(str(committed)) == []
    doc = json.loads(committed.read_text())
    # acceptance: all four families + the decode lanes, each with the
    # full static memory/cost story
    for family in ("mlp", "resnet", "gpt", "bert"):
        assert f"{family}_o1_train" in doc["lanes"]
        assert f"{family}_o2_train" in doc["lanes"]
    assert "decode_b1" in doc["lanes"] and "decode_b2" in doc["lanes"]
    for lane in doc["lanes"].values():
        assert lane["peak_hbm_bytes"] > 0
        assert lane["cost"].get("flops", 0) > 0
    assert doc["calibration"]["ok"] is True
    # the multichip table carries per-device HBM for the live slices
    slices = doc["multichip"]["slices"]
    assert any(rec.get("hbm_bytes_per_device") for rec in
               slices.values())
