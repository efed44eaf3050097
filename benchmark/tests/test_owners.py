"""``benchmark/owners.py`` on small compiled programs written by hand in
the v5e compiler's text, the reader of ``train_step.unowned_share`` on
the recorded trace, and ``benchmark/compiled_step.py`` against the step
``drivers/train.py`` compiles, at rehearsal size on the CPU."""

import json
import os
import re
import types

import pytest

from benchmark import compiled_step, owners, scopes, trace as T
from benchmark.readers import train_step_unowned_share as reader
from benchmark.tests.conftest import ROOT
from benchmark.tests.test_scopes import (
    FWD, BWD, named_as_the_program_would, read, recorded)

LOOP = "jit(step)/jvp(M)/block_0/mlp/moe_dispatch/while"
ROOT_MUL = BWD + "block_0/attention/kda_recurrence/mul"
FUSED = """
%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(f32[8]{0} %param_0.3, f32[8]{0} %param_0.3), metadata={op_name="OP_NAME"}
}
""".replace("OP_NAME", ROOT_MUL)

ASYNC_COPY = FUSED + """
ENTRY %main.1 (p.1: f32[8]) -> f32[8] {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="state"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %p.1)
  %copy-done.1 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, f32[8]{0}, u32[]) %copy-start.1)
  ROOT %fusion.3 = f32[8]{0} fusion(f32[8]{0:S(1)} %copy-done.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="OP_NAME"}
}
""".replace("OP_NAME", ROOT_MUL)

LOOP_BODY = """
%body.1 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = s32[] get-tuple-element((s32[], f32[8]{0}) %arg.1), index=0
  %get-tuple-element.2 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %arg.1), index=1
  %copy.5 = f32[8]{0} copy(f32[8]{0} %get-tuple-element.2)
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(s32[] %get-tuple-element.1, f32[8]{0} %copy.5)
}

%cond.1 (arg.2: (s32[], f32[8])) -> pred[] {
  %arg.2 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element((s32[], f32[8]{0}) %arg.2), index=0
  %constant.3 = s32[] constant(4)
  ROOT %compare.1 = pred[] compare(s32[] %get-tuple-element.3, s32[] %constant.3), direction=LT
}

ENTRY %main.2 (p.2: f32[8]) -> (s32[], f32[8]) {
  %p.2 = f32[8]{0} parameter(0), metadata={op_name="state"}
  %constant.1 = s32[] constant(0)
  %copy.1 = s32[] copy(s32[] %constant.1)
  %copy.2 = f32[8]{0} copy(f32[8]{0} %p.2)
  %tuple.1 = (s32[], f32[8]{0}) tuple(s32[] %copy.1, f32[8]{0} %copy.2)
  ROOT %while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%cond.1, body=%body.1, metadata={op_name="OP_NAME"}
}
""".replace("OP_NAME", LOOP)

NAMELESS_FUSION = FUSED + """
ENTRY %main.3 (p.3: f32[8]) -> f32[8] {
  %p.3 = f32[8]{0} parameter(0)
  ROOT %fusion.4 = f32[8]{0} fusion(f32[8]{0} %p.3), kind=kLoop, calls=%fused_computation.3
}
"""

BROADCAST = FUSED.replace(".3", ".6") + """
ENTRY %main.4 (p.4: f32[]) -> f32[8] {
  %p.4 = f32[] parameter(0)
  %broadcast.2 = f32[8]{0} broadcast(f32[] %p.4), dimensions={}
  ROOT %fusion.6 = f32[8]{0} fusion(f32[8]{0} %broadcast.2), kind=kLoop, calls=%fused_computation.6, metadata={op_name="OP_NAME"}
}
""".replace("OP_NAME", FWD + "block_0/ln1/mul")

BETWEEN = """
%fused_computation.8 (param_0.8: f32[8]) -> f32[8] {
  %param_0.8 = f32[8]{0} parameter(0)
  ROOT %negate.8 = f32[8]{0} negate(%param_0.8)
}

ENTRY %main.6 (p.6: f32[8]) -> f32[8] {
  %p.6 = f32[8]{0} parameter(0)
  %fusion.8 = f32[8]{0} fusion(%p.6), kind=kLoop, calls=%fused_computation.8
  %copy.8 = f32[8]{0} copy(%fusion.8)
  %fusion.9 = f32[8]{0} fusion(%copy.8), kind=kLoop, calls=%fused_computation.8
  ROOT %add.9 = f32[8]{0} add(%fusion.9, %fusion.9), metadata={op_name="OP_NAME"}
}
""".replace("OP_NAME", BWD + "block_0/mlp/moe_route/add")

CAST = "jit(step)/amp_cast/convert_element_type"
SLICES = """
ENTRY %main.7 (w.1: f32[16,8]) -> bf16[16,8] {
  %w.1 = f32[16,8]{1,0:T(8,128)} parameter(0), metadata={op_name="state.master_params"}
  %slice-start.1 = ((f32[16,8]{1,0:T(8,128)}), f32[8,8]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%w.1), slice={[0:8], [0:8]}
  %slice-start.2 = ((f32[16,8]{1,0:T(8,128)}), f32[8,8]{1,0:T(8,128)S(1)}, s32[]{:S(2)}) slice-start(%w.1), slice={[8:16], [0:8]}
  %slice-done.1 = f32[8,8]{1,0:T(8,128)S(1)} slice-done(%slice-start.1)
  %slice-done.2 = f32[8,8]{1,0:T(8,128)S(1)} slice-done(%slice-start.2)
  %custom-call.3 = f32[16,8]{1,0:T(8,128)S(1)} custom-call(%slice-done.1, %slice-done.2), custom_call_target="ConcatBitcast"
  ROOT %convert_element_type.5 = bf16[16,8]{1,0:T(8,128)(2,1)} convert(%custom-call.3), metadata={op_name="OP_NAME"}
}
""".replace("OP_NAME", CAST)

ISLAND = """
ENTRY %main.5 (p.5: f32[8]) -> (f32[8], f32[8]) {
  %p.5 = f32[8]{0} parameter(0), metadata={op_name="state"}
  %copy.6 = f32[8]{0} copy(f32[8]{0} %p.5), metadata={op_name="state"}
  %copy.7 = f32[8]{0} copy(f32[8]{0} %copy.6)
  %add.1 = f32[8]{0} add(f32[8]{0} %p.5, f32[8]{0} %p.5), metadata={op_name="OP_NAME"}
  ROOT %tuple.7 = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %copy.7, f32[8]{0} %add.1)
}
""".replace("OP_NAME", FWD + "add")


@pytest.mark.parametrize("hlo,expected", [
    # a prefetch belongs to whoever waits for it, not to the argument
    (ASYNC_COPY, {"copy-start.1": ROOT_MUL, "copy-done.1": ROOT_MUL}),
    # a loop's operand copies reach it through a tuple
    (LOOP_BODY, {"copy.1": LOOP, "copy.2": LOOP, "tuple.1": LOOP,
                 "constant.1": LOOP}),
    # inside the body nothing is named: the caller names it
    (LOOP_BODY, {"copy.5": LOOP, "get-tuple-element.2": LOOP,
                 "tuple.5": LOOP, "compare.1": LOOP}),
    # a fusion line with no metadata takes its fused root's
    (NAMELESS_FUSION, {"fusion.4": ROOT_MUL}),
    (BROADCAST, {"broadcast.2": FWD + "block_0/ln1/mul"}),
    # nameless work on both sides: the owner the fusions inherit
    (BETWEEN, {"fusion.9": BWD + "block_0/mlp/moe_route/add",
               "copy.8": BWD + "block_0/mlp/moe_route/add",
               "fusion.8": BWD + "block_0/mlp/moe_route/add"}),
    # a parameter's slices, prefetched and joined by the compiler's own
    # custom call, belong to the cast that reads them
    (SLICES, {"slice-start.1": CAST, "slice-done.2": CAST,
              "custom-call.3": CAST}),
    # only the output tuple and the argument touch it (a copy the
    # compiler named after the argument names no work): nobody owns it
    (ISLAND, {"copy.7": None}),
], ids=["async_copy", "loop_operands", "loop_body", "nameless_fusion",
        "broadcast", "between_nameless_fusions", "compiler_custom_call",
        "island"])
def test_owners_on_hand_written_programs(hlo, expected):
    got = owners.owners(hlo)
    for name, op_name in expected.items():
        assert got[name] == op_name, name
    # it covers every nameless instruction and nothing named
    program = owners.parse(hlo)
    assert set(got) == set(program.instructions) - set(T.op_names(hlo))


def test_a_parameter_stands_for_its_callers_operand():
    program = owners.parse(LOOP_BODY)
    assert program.callers["body.1"] == ("while.1", 1)
    assert program.instructions["arg.1"].parameter == 0
    assert program.instructions["while.1"].operands == ("tuple.1",)
    assert program.instructions["copy.5"].operands == \
        ("get-tuple-element.2",)
    assert owners.parse(ASYNC_COPY).instructions["copy-done.1"].operands \
        == ("copy-start.1",)


@pytest.mark.parametrize("op_name,phase,expected", [
    (ROOT_MUL, "backward", "backward/kda_recurrence"),
    (LOOP, "forward", "forward/moe_dispatch"),
    ("jit(step)/amp_optimizer_step/cond/branch_1_fun/add", "optimizer",
     "optimizer/amp_optimizer_step"),
    (FWD + "block_0/ln1/mul", "forward", "forward/ln1"),
    (FWD + "block_0/add", "forward", "forward"),
])
def test_an_owner_is_labelled_by_its_deepest_scope(op_name, phase, expected):
    assert owners.label(op_name, phase, "gpt") == expected


def a_program_for_the_recording(by_instruction: dict, names: dict) -> str:
    """A compiled text over the recorded instructions: each nameless one
    (the copies) reads the argument and feeds the next named one, but
    the last, which feeds only the output, and nobody owns."""
    lines, pending = ["ENTRY %main.9 (p.9: f32[8]) -> f32[8] {",
                      "  %p.9 = f32[8]{0} parameter(0)"], []
    island = [i for i in by_instruction if i not in names][-1:]
    for instr in by_instruction:
        if instr in names:
            args = ", ".join(f"f32[8]{{0}} %{c}" for c in ["p.9"] + pending)
            lines.append(f'  %{instr} = f32[8]{{0}} fusion({args}), '
                         f'kind=kLoop, metadata={{op_name="{names[instr]}"}}')
            pending = []
        else:
            lines.append(f"  %{instr} = f32[8]{{0}} copy(f32[8]{{0}} %p.9)")
            if instr not in island:
                pending.append(instr)
    out = ", ".join(f"f32[8]{{0}} %{c}" for c in pending + island)
    lines += [f"  ROOT %tuple.9 = (f32[8]{{0}}) tuple({out})", "}"]
    return "\n".join(lines)


@pytest.fixture
def recording(monkeypatch):
    by_instruction, busy = recorded()
    names = named_as_the_program_would(by_instruction)
    hlo = a_program_for_the_recording(by_instruction, names)
    monkeypatch.setattr(compiled_step, "text", lambda run: hlo)
    run = types.SimpleNamespace(
        instruction_seconds=by_instruction, op_names=names, busy_s0=busy,
        cfg={"family": "gpt"}, chips=1, steps_traced=2, notes={})
    return run, hlo


def test_the_owners_add_up_to_the_unscoped_time(recording):
    run, hlo = recording
    unscoped = scopes.phase_seconds(run)["unscoped"]
    found = owners.split(run, owners.owners(hlo))
    assert found.unscoped == pytest.approx(unscoped, rel=1e-12)
    assert sum(found.by_owner.values()) + found.unowned == \
        pytest.approx(unscoped, rel=1e-12)
    assert found.by_owner and found.unowned > 0     # the recording has both
    assert set(found.unowned_by_instruction) <= {
        i for i in run.instruction_seconds if i.startswith("copy")}

    share = read("train_step.unowned_share", run)
    assert share == pytest.approx(100 * found.unowned / run.busy_s0)
    assert share < read("train_step.unscoped_share", run)
    note = run.notes["train_step.unscoped_owners"]
    entries = [float(x) for x in re.findall(r"\(([\d.]+)%\)", note)]
    assert sum(entries[:-1]) == pytest.approx(entries[-1], abs=0.01)
    assert entries[-1] == pytest.approx(
        read("train_step.unscoped_share", run), abs=0.001)
    assert "unowned" in note
    top = run.notes["train_step.unowned_top"].split("; ")
    assert 1 <= len(top) <= reader.UNOWNED_SHOWN
    assert all(" copy " in entry and entry.endswith(" ms") for entry in top)


def test_no_scope_no_trace_nothing_to_read(monkeypatch):
    def not_built(run):
        raise AssertionError("nothing to read: the step is not built again")

    monkeypatch.setattr(compiled_step, "text", not_built)
    by_instruction, _ = recorded()
    unnamed = {i: FWD + "lm_head/dot_general" for i in by_instruction}
    run = types.SimpleNamespace(
        instruction_seconds=by_instruction, op_names=unnamed, busy_s0=1.0,
        cfg={"family": "gpt"}, chips=1, steps_traced=1, notes={})
    assert read("train_step.unowned_share", run) is None
    untraced = types.SimpleNamespace(
        instruction_seconds={}, op_names={}, busy_s0=None,
        cfg={"family": "gpt"}, chips=1, steps_traced=0, notes={})
    assert read("train_step.unowned_share", untraced) is None
    assert not run.notes and not untraced.notes


def test_another_program_built_again_reads_nothing(recording, monkeypatch):
    run, hlo = recording
    monkeypatch.setattr(compiled_step, "text", lambda run: None)
    assert read("train_step.unowned_share", run) is None
    assert "no owner" in run.notes["train_step.unowned_share.cost"]


def test_the_metric_has_its_two_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(ROOT, "benchmark/metrics",
                           "train_step.unowned_share.json")) as f:
        spec = json.load(f)
    assert {k: v for k, v in spec.items() if k != "reader"} == \
        listed["train_step.unowned_share"]
    assert "workloads" not in spec            # every cell reports it
    assert spec["reader"] == reader.__name__.rsplit(".", 1)[1]


def instruction_lines(hlo_text: str) -> list:
    """The program without the stack-frame tables, which name the Python
    callers the step was traced under (one more on the CPU, where the
    innermost frame is not all the metadata keeps)."""
    return [re.sub(r" stack_frame_id=\d+", "", line)
            for line in hlo_text.splitlines()
            if line.startswith((" ", "%", "ENTRY", "HloModule", "}"))]


@pytest.mark.parametrize("cell", ["gpt2_medium.lm_b8_s1024",
                                  "gpt2_medium.ddp4_b32_s1024"])
def test_the_step_built_again_is_the_step_build_compiled(cell):
    import jax

    from benchmark import run as bench_run
    from benchmark.drivers import train

    _, c, cfg, family, _ = bench_run.resolve(cell, rehearse=True)
    b = train.build(c, cfg, family, 2147483777, jax.devices()[:c["chips"]],
                    train.Watch())
    want = b["compiled"].as_text()
    run = types.SimpleNamespace(traffic=c["parameters"], chips=c["chips"],
                                cfg=cfg, family=family,
                                op_names=T.op_names(want))
    got = compiled_step.text(run)
    assert got is not None
    assert instruction_lines(got) == instruction_lines(want)
    run.op_names = dict(run.op_names, extra="jit(step)/somewhere_else")
    assert compiled_step.text(run) is None
