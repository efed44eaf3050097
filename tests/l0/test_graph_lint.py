"""Graph-lint subsystem coverage (:mod:`apex_tpu.analysis`).

Each pass must (a) FIRE on a crafted violating program — a dropped
donation, a large replicated param on the 8-device mesh, over-budget
collective bytes, a captured weight-sized constant, an escaped 16-bit
softmax — and (b) stay QUIET on the clean in-tree model families'
O1 train steps (``tools/graph_lint.py``, the continuously-enforced
version of the "statically checkable guarantees" story).  Parser pins
on crafted HLO/StableHLO spellings keep the text walks trustworthy, and
the compat surfaces (``amp.audit``, ``__graft_entry__._collective_audit``)
are pinned by their own pre-existing suites.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu import analysis  # noqa: E402
from apex_tpu.analysis import Finding, Report  # noqa: E402

from jax import shard_map as _shard_map


def mesh8():
    return Mesh(np.array(jax.devices()[:8]), ("data",))


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

def test_dropped_donation_fires_with_wasted_bytes():
    """A donated arg with no same-shaped output cannot alias: the pass
    must report it as an error carrying the wasted buffer size."""
    def g(x, y):
        return (x[:2] * 2.0).sum() + y.sum()

    x = jnp.ones((128, 128), jnp.float32)
    y = jnp.ones((8,), jnp.float32)
    rep = analysis.analyze(g, x, y, donate_argnums=(0, 1),
                           passes=("donation",))
    assert not rep.ok
    errs = [f for f in rep.by_pass("donation") if f.severity == "error"]
    assert {f.bytes for f in errs} == {128 * 128 * 4, 8 * 4}
    assert all("dropped" in f.message for f in errs)


def test_honored_donation_is_quiet():
    def f(x):
        return x * 2.0

    rep = analysis.analyze(f, jnp.ones((64, 64)), donate_argnums=(0,),
                           passes=("donation",))
    assert rep.ok and not rep.findings


def test_no_donation_declared_is_quiet():
    rep = analysis.analyze(lambda x: x + 1.0, jnp.ones((4,)),
                           passes=("donation",))
    assert rep.ok and not rep.findings


def test_pruned_unused_arg_does_not_shift_donation_numbering():
    """jit prunes unused args (keep_unused=False), renumbering the
    compiled parameters — an honored donation AFTER a pruned arg must
    not be misreported as dropped; the pruned donated arg itself is a
    vacuous-donation warning, not an error."""
    def f(unused, y):
        return y * 2.0

    rep = analysis.analyze(f, jnp.ones((16, 16)), jnp.ones((8, 8)),
                           donate_argnums=(1,), passes=("donation",))
    assert rep.ok and not rep.findings
    rep2 = analysis.analyze(f, jnp.ones((16, 16)), jnp.ones((8, 8)),
                            donate_argnums=(0, 1), passes=("donation",))
    assert rep2.ok   # dead-arg donation warns, never gates
    warns = rep2.by_pass("donation")
    assert len(warns) == 1 and warns[0].severity == "warning"
    assert "pruned" in warns[0].message


def test_async_all_gather_spelling_is_seen():
    """XLA's latency-hiding scheduler emits big gathers as tuple-shaped
    ``all-gather-start`` — the replication check must see those too."""
    hlo = (
        "HloModule jit_f, is_scheduled=true, num_partitions=8\n"
        "ENTRY %main (p0: f32[128,64]) -> f32[1024,64] {\n"
        "  %p0 = f32[128,64]{1,0} parameter(0), "
        "sharding={devices=[8,1]<=[8]}\n"
        "  %ag-start = (f32[128,64]{1,0}, f32[1024,64]{1,0}) "
        "all-gather-start(f32[128,64]{1,0} %p0), dimensions={0}\n"
        "  ROOT %ag-done = f32[1024,64]{1,0} all-gather-done("
        "(f32[128,64]{1,0}, f32[1024,64]{1,0}) %ag-start)\n"
        "}\n")
    ctx = analysis.PassContext(stablehlo_text="", hlo_text=hlo)
    out = analysis.PASSES["sharding"](ctx, min_bytes=1024)
    gathers = [f for f in out if f.op == "all-gather"]
    assert len(gathers) == 1 and gathers[0].bytes == 1024 * 64 * 4


def test_sharded_donation_without_compile_is_not_misreported():
    """A sharded donated arg lowers as ``jax.buffer_donor`` (aliasing
    decided at compile time) with a sharding attr whose quoted value
    embeds braces — the lowering-only fallback must report it as
    inconclusive (info), never as a dropped-donation error; compiling
    resolves it to an honored alias."""
    mesh = mesh8()
    w = jax.device_put(jnp.ones((256, 64), jnp.float32),
                       NamedSharding(mesh, P("data", None)))
    step = jax.jit(lambda w: w * 2.0, donate_argnums=(0,))
    rep = analysis.analyze(step, w, passes=("donation",), compile=False)
    assert rep.ok, rep.format()
    infos = rep.by_pass("donation")
    assert len(infos) == 1 and infos[0].severity == "info"
    assert "buffer_donor" in infos[0].message
    rep2 = analysis.analyze(step, w, passes=("donation",), compile=True)
    assert rep2.ok and not rep2.findings


def test_sharded_dropped_donation_errors_when_compiled():
    """When the executable honored ZERO donations its header has no
    alias table at all — that absence is authoritative evidence of a
    drop, not a reason to fall back to inconclusive lowering markers."""
    mesh = mesh8()
    w = jax.device_put(jnp.ones((256, 64), jnp.float32),
                       NamedSharding(mesh, P("data", None)))
    step = jax.jit(lambda w: (w[:2] * 2.0).sum(), donate_argnums=(0,))
    rep = analysis.analyze(step, w, passes=("donation",), compile=True)
    assert not rep.ok
    assert rep.errors[0].bytes == 256 * 64 * 4
    assert "compiled executable" in rep.errors[0].message


def test_ambiguous_arg_numbering_degrades_to_info():
    """If the kept-arg inference (a private jax attribute) disagrees
    with the lowered signature's arg count, the pass must refuse to
    guess instead of emitting false dropped-donation errors."""
    from apex_tpu.analysis.core import ArgInfo
    args = tuple(ArgInfo(i, f"[{i}]", (4,), "float32", 16,
                         donated=(i == 1), kept=True)
                 for i in range(3))   # claims 3 kept ...
    stablehlo = ("func.func public @main(%arg0: tensor<4xf32>, "
                 "%arg1: tensor<4xf32>) -> (tensor<4xf32>) {")  # ... sig has 2
    ctx = analysis.PassContext(stablehlo_text=stablehlo, args=args)
    out = analysis.PASSES["donation"](ctx)
    assert len(out) == 1 and out[0].severity == "info"
    assert "ambiguous" in out[0].message


def test_hlo_alias_table_parser():
    # the compiled executable's header is the ground truth the pass reads
    hlo = ("HloModule jit_f, input_output_alias={ {0}: (0, {}, "
           "may-alias), {2}: (3, {}, must-alias) }, "
           "entry_computation_layout={...}")
    from apex_tpu.analysis.donation import aliased_parameters
    assert aliased_parameters(hlo) == {0, 3}
    assert aliased_parameters("HloModule jit_f") == set()


# ---------------------------------------------------------------------------
# sharding
# ---------------------------------------------------------------------------

def test_replicated_large_param_fires():
    mesh = mesh8()
    w = jax.device_put(jnp.ones((256, 64), jnp.float32),
                       NamedSharding(mesh, P()))
    xb = jax.device_put(jnp.ones((16, 256), jnp.float32),
                        NamedSharding(mesh, P("data")))

    def loss(w, xb):
        return jnp.sum(jnp.square(xb @ w))

    rep = analysis.analyze(loss, w, xb, passes=("sharding",),
                           options={"sharding": {"min_bytes": 1024}})
    hits = [f for f in rep.by_pass("sharding")
            if "replicated" in f.message]
    assert hits and hits[0].bytes == 256 * 64 * 4
    assert hits[0].severity == "warning"   # no intent declared
    assert rep.ok


def test_replicated_against_intent_is_error():
    mesh = mesh8()
    w = jax.device_put(jnp.ones((256, 64), jnp.float32),
                       NamedSharding(mesh, P()))
    xb = jax.device_put(jnp.ones((16, 256), jnp.float32),
                        NamedSharding(mesh, P("data")))

    def loss(w, xb):
        return jnp.sum(jnp.square(xb @ w))

    # the intent mapping an FSDP/TP layout would declare for w
    rep = analysis.analyze(
        loss, w, xb, passes=("sharding",),
        options={"sharding": {"min_bytes": 1024,
                              "intended": {"[0]": P("data", None)}}})
    assert not rep.ok
    assert any("intent declares" in f.message for f in rep.errors)


def test_sharded_params_are_quiet():
    mesh = mesh8()
    w = jax.device_put(jnp.ones((256, 64), jnp.float32),
                       NamedSharding(mesh, P("data", None)))

    def loss(w):
        return jnp.sum(jnp.square(w))   # elementwise: no gather needed

    rep = analysis.analyze(loss, w, passes=("sharding",),
                           options={"sharding": {"min_bytes": 1024}})
    assert rep.ok and not rep.by_pass("sharding")


def test_single_device_program_is_quiet():
    rep = analysis.analyze(lambda x: (x @ x.T).sum(),
                           jnp.ones((512, 512)), passes=("sharding",),
                           options={"sharding": {"min_bytes": 1024}})
    assert rep.ok and not rep.findings


def test_intended_specs_helper_builds_the_intent_mapping():
    from apex_tpu.parallel import intended_specs
    mesh = mesh8()
    tree = {"w1": NamedSharding(mesh, P("data", None)),
            "w2": P(None, "data"),
            "bias": P()}
    out = intended_specs(tree)
    assert set(out) == {"['w1']", "['w2']"}   # replicated intent dropped
    assert out["['w1']"] == P("data", None)


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def test_over_budget_collective_bytes_fires():
    mesh = mesh8()

    def step(x):
        return jax.lax.psum(x.sum(axis=0), "data")

    sm = jax.jit(_shard_map(step, mesh=mesh, in_specs=(P("data"),),
                            out_specs=P()))
    x = jnp.ones((8, 128), jnp.float32)
    rep = analysis.analyze(sm, x, passes=("collectives",),
                           options={"collectives":
                                    {"budget": {"total": 0}}})
    assert not rep.ok
    err = rep.errors[0]
    assert err.op == "total" and err.bytes and err.bytes > 0
    # the same program inside its budget passes, with the volume recorded
    rep2 = analysis.analyze(sm, x, passes=("collectives",),
                            options={"collectives":
                                     {"budget": {"total": 1 << 20}}})
    assert rep2.ok
    infos = rep2.by_pass("collectives")
    assert any(f.op == "all-reduce" and f.count == 1 for f in infos)


def test_per_kind_budget_and_async_tally():
    from apex_tpu.analysis import collective_table
    hlo = """
  %all-reduce.1 = f32[8,16]{1,0} all-reduce(f32[8,16]{1,0} %p), to_apply=%add
  %ag-start = (f32[4]{0}, f32[32]{0}) all-gather-start(f32[4]{0} %x), dimensions={0}
  %ag-done = f32[32]{0} all-gather-done((f32[4]{0}, f32[32]{0}) %ag-start)
"""
    table = collective_table(hlo)
    assert table["all-reduce"] == {"count": 1, "bytes": 8 * 16 * 4,
                                   "sync": 1, "async": 0,
                                   "channels": [], "replica_groups": [],
                                   "global_ids": 0}
    assert table["all-gather"] == {"count": 1, "bytes": 32 * 4,
                                   "sync": 0, "async": 1,
                                   "channels": [], "replica_groups": [],
                                   "global_ids": 0}
    ctx = analysis.PassContext(stablehlo_text="", hlo_text=hlo)
    out = analysis.PASSES["collectives"](
        ctx, budget={"all-reduce": 4, "all-gather": 1 << 20})
    errs = [f for f in out if f.severity == "error"]
    assert len(errs) == 1 and errs[0].op == "all-reduce"


# ---------------------------------------------------------------------------
# constant capture
# ---------------------------------------------------------------------------

def test_captured_weight_sized_constant_fires():
    big = jax.random.normal(jax.random.PRNGKey(0), (512, 640))

    def h(x):
        return x @ big   # closed over: baked into the jaxpr

    rep = analysis.analyze(h, jnp.ones((4, 512)),
                           passes=("constant-capture",), compile=False)
    assert not rep.ok
    err = rep.errors[0]
    assert err.bytes == 512 * 640 * 4 and err.dtype == "f32"


def test_splat_and_small_constants_are_quiet():
    zeros = jnp.zeros((512, 640))          # splat: scalar + broadcast
    small = jax.random.normal(jax.random.PRNGKey(1), (8, 8))

    def h(x):
        return (x @ zeros) * small.sum()

    rep = analysis.analyze(h, jnp.ones((4, 512)),
                           passes=("constant-capture",), compile=False)
    assert rep.ok and not rep.findings


def test_passed_as_argument_is_quiet():
    big = jax.random.normal(jax.random.PRNGKey(0), (512, 640))
    rep = analysis.analyze(lambda x, w: x @ w, jnp.ones((4, 512)), big,
                           passes=("constant-capture",), compile=False)
    assert rep.ok and not rep.findings


# ---------------------------------------------------------------------------
# policy (via the pass API; the legacy amp.audit surface has its own suite)
# ---------------------------------------------------------------------------

def test_policy_pass_flags_escaped_softmax():
    def escaped(w, x):
        h = jnp.matmul(x, w).astype(jnp.bfloat16)
        return jax.nn.softmax(h, axis=-1).astype(jnp.float32).sum()

    w = jnp.ones((8, 8), jnp.float32)
    x = jnp.ones((4, 8), jnp.float32)
    rep = analysis.analyze(escaped, w, x, passes=("policy",),
                           compile=False)
    assert not rep.ok
    assert any(f.op == "exponential" and f.dtype == "bf16"
               for f in rep.errors)


# ---------------------------------------------------------------------------
# report plumbing
# ---------------------------------------------------------------------------

def test_report_shapes_and_merge():
    f1 = Finding("donation", "error", "m1", bytes=4)
    f2 = Finding("policy", "info", "m2")
    rep = Report((f1,), ("donation",)).merged(
        Report((f2,), ("policy",)))
    assert not rep.ok and rep.passes == ("donation", "policy")
    d = rep.to_dict()
    assert d["counts"] == {"error": 1, "info": 1}
    assert d["findings"][0]["pass"] == "donation"
    assert "FAIL" in rep.format() and "m1" in rep.format()
    with pytest.raises(ValueError):
        Finding("x", "fatal", "bad severity")
    with pytest.raises(KeyError):
        analysis.run_passes(analysis.PassContext(""), passes=("nope",))


# ---------------------------------------------------------------------------
# the clean in-tree families (the CLI's continuously-enforced guarantee)
# ---------------------------------------------------------------------------

#: bert/gpt/resnet compiles cost 12-17s each on a 2-vCPU tier-1 box —
#: slow-marked so the tier-1 wall clock stays inside its timeout; the
#: mlp lane keeps the guarantee continuously enforced.
HEAVY_FAMILIES = ("resnet", "gpt", "bert")


def _marks_for(name):
    return (pytest.mark.slow,) if name in HEAVY_FAMILIES else ()


@pytest.mark.parametrize("family",
                         [pytest.param(f, id=f, marks=_marks_for(f))
                          for f in ["mlp", "resnet", "gpt", "bert"]])
def test_in_tree_family_train_step_lints_clean(family):
    import graph_lint
    report = graph_lint.lint_family(family)
    assert report.ok, report.format()
    # the guarantee is meaningful only if every pass actually ran
    assert set(graph_lint.ALL_PASSES) <= set(report.passes)


def test_cli_main_runs_selected_family(capsys):
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--lanes", "o1"]) == 0
    out = capsys.readouterr().out
    assert '"lane": "mlp_o1"' in out and '"ok": true' in out


# ---------------------------------------------------------------------------
# ISSUE 4: strict mode + every in-tree entry point lints clean
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_strict_mode_memory_budget_enforced(capsys):
    """Tier-1 strict-mode run over the smallest family: the memlint
    passes execute with the v5e 16 GiB device budget ARMED (bare
    ``--memory-budget``), so every tier-1 run proves the memory/cost/
    syncs passes fire on a real lane and the lane fits the chip."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp",
                            "--lanes", "o1,o2,decode",
                            "--memory-budget"]) == 0
    out = capsys.readouterr().out
    assert '"lane": "mlp_o1"' in out and '"lane": "mlp_o2"' in out
    assert '"lane": "decode_b1"' in out   # decode dispatch through main()
    for line in out.splitlines():
        rec = json.loads(line)
        assert {"memory", "cost", "syncs"} <= set(rec["passes"])
        assert rec["ok"], rec


def test_cli_serve_lane_dispatch_and_skip(capsys):
    """``--lanes serve`` dispatches the serve lane through main() —
    proven cheaply via the policy-pass skip path (no build, no
    compile; the serve lane linting CLEAN under the full pass matrix
    is the serve_step entry-point test below)."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--passes", "policy",
                            "--lanes", "o1,serve"]) == 0
    captured = capsys.readouterr()
    assert "serve_step" not in captured.out     # skipped, not ok:true
    assert "skipped: no requested pass applies" in captured.err
    with pytest.raises(SystemExit):             # typo'd lane refused
        graph_lint.main(["--lanes", "serv"])


def test_cli_memory_budget_violation_fails_exit_code(capsys):
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--lanes", "o1",
                            "--memory-budget", "1KiB"]) == 1
    out = capsys.readouterr().out
    assert '"hbm-budget"' in out


def test_parse_bytes_forms():
    import graph_lint
    assert graph_lint.parse_bytes("1048576") == 1 << 20
    assert graph_lint.parse_bytes("16GiB") == 16 << 30
    assert graph_lint.parse_bytes("512MiB") == 512 << 20
    assert graph_lint.parse_bytes("2GB") == 2 * 10**9
    with pytest.raises(ValueError):
        graph_lint.parse_bytes("lots")


def test_cli_emit_json_rejects_partial_modes(tmp_path):
    """--emit-json commits the full-matrix artifact; a restricted
    --passes or --no-compile run must be refused, never silently
    overridden into a partial document."""
    import graph_lint
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", str(tmp_path / "M_r99.json"),
                         "--no-compile"])
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", str(tmp_path / "M_r99.json"),
                         "--passes", "donation"])
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", str(tmp_path / "M_r99.json"),
                         "--families", "mlp"])
    with pytest.raises(SystemExit):
        graph_lint.main(["--emit-json", str(tmp_path / "M_r99.json"),
                         "--lanes", "o1"])
    assert not (tmp_path / "M_r99.json").exists()


def test_cli_emit_json_defaults_budget_armed(monkeypatch, tmp_path):
    """--emit-json without --memory-budget arms the v5e default — a
    regeneration must never quietly replace a budget-gated round with
    an unarmed one."""
    import graph_lint
    seen = {}

    def fake_emit(path, families, memory_budget=None, verbose=False):
        seen["budget"] = memory_budget
        return 0

    monkeypatch.setattr(graph_lint, "emit_memlint", fake_emit)
    assert graph_lint.main(
        ["--emit-json", str(tmp_path / "M_r99.json")]) == 0
    from apex_tpu.analysis.memory import V5E_HBM_BYTES
    assert seen["budget"] == V5E_HBM_BYTES


def test_cli_no_compile_rejects_armed_budget():
    """--memory-budget + --no-compile: the budget gate can't run
    without the compiled executable — refuse the combination rather
    than exit 0 having asserted nothing."""
    import graph_lint
    with pytest.raises(SystemExit):
        graph_lint.main(["--families", "mlp", "--lanes", "o1",
                         "--no-compile", "--memory-budget", "1KiB"])


def test_memory_pass_uncompiled_armed_budget_warns():
    """analyze(compile=False) with budget_bytes armed: the skip is a
    WARNING naming the unasserted gate, not a bare info."""
    from apex_tpu import analysis
    rep = analysis.analyze(lambda x: x * 2, jnp.ones((4,)),
                           compile=False, passes=("memory",),
                           options={"memory": {"budget_bytes": 1024}})
    skips = rep.by_pass("memory")
    assert len(skips) == 1 and skips[0].severity == "warning"
    assert "asserted NOTHING" in skips[0].message
    # without a budget the same skip stays informational
    rep2 = analysis.analyze(lambda x: x * 2, jnp.ones((4,)),
                            compile=False, passes=("memory",))
    assert rep2.by_pass("memory")[0].severity == "info"


def test_cli_zero_applicable_passes_fails(capsys):
    """``--passes policy --lanes o2``: policy only applies to O1
    forwards, so every selected lane would run ZERO passes — the
    lint-nothing-and-pass class the --lanes guard exists to stop must
    fail here too."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--passes", "policy",
                            "--lanes", "o2"]) == 1
    captured = capsys.readouterr()
    assert "ran zero passes" in captured.err


def test_cli_policy_only_with_default_lanes_still_passes(capsys):
    """``--passes policy`` without ``--lanes``: the default lane list
    includes decode lanes that can't host the policy pass — those are
    SKIPPED (never printed as ok), while the O1 lane runs policy and
    the invocation exits 0 (the pre-PR behavior)."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp",
                            "--passes", "policy"]) == 0
    captured = capsys.readouterr()
    assert '"lane": "mlp_o1"' in captured.out
    assert "decode_b1" not in captured.out      # no ok:true for a skip
    assert "skipped: no requested pass applies" in captured.err


def test_multichip_slice_table_refuses_missing_mesh(monkeypatch):
    """Fewer CPU devices than the virtual mesh needs (backend
    initialized before XLA_FLAGS could act): fail loudly rather than
    commit wrong per-device numbers under an n_devices: 8 header."""
    import graph_lint
    one = jax.devices("cpu")[:1]
    monkeypatch.setattr(graph_lint.jax, "devices",
                        lambda *a, **k: one)
    with pytest.raises(RuntimeError, match="need 8 CPU devices"):
        graph_lint.multichip_slice_table(8)


#: every in-tree lint entry point: the four families at both opt
#: levels plus the decode lanes — the parametrized "runs clean over
#: every example entry point" guarantee (the ResNet-50 ``entry()``
#: forward is the slow-marked flagship below).  The heavy-family lanes
#: carry the ``slow`` mark (tier-1 budget); mlp + decode stay tier-1.
def _entry_param(name, opt_level):
    return pytest.param(name, opt_level,
                        id=f"{name}_{opt_level}" if opt_level else name,
                        marks=_marks_for(name))


ENTRY_POINTS = ([_entry_param(f, o)
                 for f in ["mlp", "resnet", "gpt", "bert"]
                 for o in ["O1", "O2"]]
                + [_entry_param("decode_b1", None),
                   _entry_param("decode_b2", None),
                   _entry_param("serve_step", None),
                   # the disaggregated fleet's split steps: the prefill
                   # worker's chunk program stays tier-1 (a new program
                   # class); the replica-shaped decode lane duplicates
                   # serve_step's program class at another geometry and
                   # rides the slow lane (tier-1 budget)
                   _entry_param("serve_prefill", None),
                   pytest.param("serve_decode", None, id="serve_decode",
                                marks=(pytest.mark.slow,)),
                   # the speculative-decoding verifier: a NEW program
                   # class (b×(k+1) multi-token verify + on-device
                   # acceptance), so it rides tier-1 like serve_step
                   _entry_param("serve_verify", None)])


@pytest.mark.parametrize("name,opt_level", ENTRY_POINTS)
def test_every_entry_point_lints_clean(name, opt_level):
    import graph_lint
    if opt_level is None:
        if name in graph_lint.SERVE_PREFILL_LANES:
            lint = graph_lint.lint_serve_prefill
        elif name in graph_lint.SERVE_VERIFY_LANES:
            lint = graph_lint.lint_serve_verify
        elif name in graph_lint.SERVE_LANES:
            lint = graph_lint.lint_serve
        else:
            lint = graph_lint.lint_decode
        report = lint(
            name, memory_budget=graph_lint.memory_mod.V5E_HBM_BYTES)
    else:
        report = graph_lint.lint_family(
            name, opt_level=opt_level,
            memory_budget=graph_lint.memory_mod.V5E_HBM_BYTES)
    assert report.ok, report.format()
    assert any(f.op == "peak-hbm" for f in report.by_pass("memory"))


@pytest.mark.slow
def test_flagship_entry_forward_lints_clean():
    """``__graft_entry__.entry()`` — the ResNet-50 bf16 forward the
    driver compiles — through the full non-policy pass list."""
    sys.path.insert(0, str(REPO))
    import __graft_entry__ as graft
    fwd, args = graft.entry()
    rep = analysis.analyze(
        fwd, *args,
        passes=("donation", "collectives", "constant-capture",
                "memory", "cost", "syncs"),
        options={"memory": {"budget_bytes": 16 << 30},
                 "collectives": {"budget": {"total": 0}}})
    assert rep.ok, rep.format()


# ---------------------------------------------------------------------------
# ISSUE 9: the O4 (fp8) train lane and the int8-KV decode lane
# ---------------------------------------------------------------------------

def test_cli_o4_lane_full_matrix_clean(capsys):
    """The fp8 regime's train step — delayed-scaling state donated in
    AmpState, e4m3/e5m2 quantizes in the program — lints clean under
    the FULL pass matrix with the memory budget armed: donation covers
    the fp8 leaves, the syncs pass proves the instrumented-metrics
    design added no host sync, and the precision pass carries the
    three fp8 rules."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--lanes", "o4",
                            "--memory-budget"]) == 0
    out = capsys.readouterr().out
    rec = json.loads([line for line in out.splitlines()
                      if '"lane": "mlp_o4"' in line][0])
    assert rec["ok"]
    assert {"donation", "memory", "syncs", "precision"} \
        <= set(rec["passes"])


def test_cli_decode_kv8_lane_dispatch(capsys):
    """``--lanes decode`` dispatches the int8-KV lane alongside the
    dense ones (cheap lowering-only precision run)."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--lanes", "decode",
                            "--passes", "precision"]) == 0
    out = capsys.readouterr().out
    assert '"lane": "decode_b1_kv8"' in out
    rec = json.loads([line for line in out.splitlines()
                      if '"lane": "decode_b1_kv8"' in line][0])
    assert rec["ok"]


def test_decode_lanes_table_carries_kv8():
    import graph_lint
    assert graph_lint.DECODE_LANES["decode_b1_kv8"][3] == "int8"
    assert "o4" in graph_lint.TRAIN_LANES


# ---------------------------------------------------------------------------
# ISSUE 10: the export-compat pass rides the lint CLI too
# ---------------------------------------------------------------------------

def test_cli_export_compat_pass_clean(capsys):
    """``--passes export-compat`` over the train + serve lanes: the
    lanes the AOT export pipeline serializes lint serializable
    (lowering-only — the pass reads StableHLO text, so the CLI skips
    the per-lane compile exactly like the precision-only mode)."""
    import graph_lint
    assert graph_lint.main(["--families", "mlp",
                            "--passes", "export-compat",
                            "--lanes", "o1,serve"]) == 0
    out = capsys.readouterr().out
    assert '"lane": "mlp_o1"' in out and '"lane": "serve_step"' in out
    for line in out.splitlines():
        rec = json.loads(line)
        assert rec["ok"] and rec["passes"] == ["export-compat"]
