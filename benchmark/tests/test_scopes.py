"""``benchmark/scopes.py`` and the eight readers built on it: ``phase``
and ``block`` on ``op_name`` strings as the v5e's compiler writes them,
the partition of a recorded trace, and what the readers return for a
program that carries the scopes and for one that does not."""

import functools
import importlib
import json
import os
import types

import pytest

from benchmark import scopes, trace as T
from benchmark.tests.conftest import ROOT

HERE = os.path.dirname(os.path.abspath(__file__))

NEW_METRICS = (
    "train_step.forward_share", "train_step.backward_share",
    "amp.overhead_share", "train_step.unscoped_share",
    "model.head_loss_share", "model.mlp_share",
    "attention.outside_kernel_share", "allreduce.pack_ms")

FWD, BWD = "jit(step)/jvp(GPTModel)/", "jit(step)/transpose(jvp(GPTModel))/"
ALLREDUCE = "%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %g), " \
            "replica_groups={{0,1,2,3}}"


@pytest.mark.parametrize("instruction,op_name,expected", [
    # autodiff's own stamps, Mosaic kernels included
    ("fusion.12", FWD + "block_3/mlp/ffn_in/dot_general", "forward"),
    ("flash_fwd.3", FWD + "block_3/attention/pallas_call", "forward"),
    ("fusion.13", BWD + "block_3/ln1/mul", "backward"),
    ("flash_bwd_fused.71", BWD + "block_3/attention/pallas_call", "backward"),
    ("fusion.14", "jit(step)/vjp(f)/mul", "backward"),
    ("fusion.15", "jit(step)/jvp(lm_loss)/reduce_max", "forward"),
    ("fusion.16", "jit(step)/transpose(jvp(lm_loss))/div", "backward"),
    # a scope of the program's inside autodiff stays forward or backward
    ("fusion.17", FWD + "amp_cast/convert_element_type", "forward"),
    # the program's phases
    ("convert.5", "jit(step)/amp_cast/convert_element_type", "amp"),
    ("fusion.18", "jit(step)/amp_unscale/mul", "amp"),
    ("is-finite_reduce_fusion.2", "jit(step)/amp_unscale/reduce_and", "amp"),
    ("fusion.19", "jit(step)/amp_scaler_update/select_n", "amp"),
    ("fusion.20", "jit(step)/amp_optimizer_step/cond/branch_1_fun/div",
     "optimizer"),
    ("subtract_add_fusion.4",
     "jit(sharded)/shard_map/amp_optimizer_step/cond/branch_1_fun/sub",
     "optimizer"),
    ("lamb_stage1.2", None, "optimizer"),
    ("mt_scale", None, "optimizer"),
    ("fusion.21", "jit(sharded)/shard_map/amp_reduce/ddp_allreduce/mul",
     "reduce"),
    ("copy.9",
     "jit(sharded)/shard_map/amp_reduce/ddp_allreduce/psum_invariant",
     "reduce"),
    # a collective goes by what it is, named or whole
    ("all-reduce.7",
     "jit(sharded)/shard_map/amp_reduce/ddp_allreduce/psum_invariant",
     "collective"),
    ("all-reduce-start.2", BWD + "block_3/psum", "collective"),
    (ALLREDUCE, None, "collective"),
    # what PR 24's chip runs showed, before the program named its phases
    ("fusion.22", "jit(step)/cond/branch_1_fun/div", "unscoped"),
    ("fusion.23", "jit(step)/convert_element_type", "unscoped"),
    ("copy.3", None, "unscoped"),
    # a scope is a whole path segment, not a substring of one
    ("fusion.24", "jit(step)/my_amp_cast_helper/mul", "unscoped"),
])
def test_phase(instruction, op_name, expected):
    assert scopes.phase(instruction, op_name) == expected
    assert expected in scopes.PHASES


@pytest.mark.parametrize("family,op_name,expected", [
    ("gpt", FWD + "lm_head/dot_general", "head_loss"),
    ("gpt", BWD + "lm_head/transpose", "head_loss"),
    ("gpt", "jit(step)/jvp(lm_loss)/reduce_max", "head_loss"),
    ("gpt", "jit(step)/transpose(jvp(lm_loss))/jit(take_along_axis)/"
            "scatter-add", "head_loss"),
    ("gpt", FWD + "block_3/mlp/ffn_in/dot_general", "mlp"),
    ("gpt", BWD + "block_11/mlp/tanh", "mlp"),
    ("gpt", FWD + "block_3/attention/qkv/dot_general", "attention"),
    ("gpt", BWD + "block_3/attention/pallas_call", "attention"),
    ("gpt", FWD + "block_3/ln2/pallas_call", "norm"),
    ("gpt", BWD + "ln_f/reduce_sum", "norm"),
    ("gpt", FWD + "tok_emb/jit(_take)/gather", "embed"),
    ("gpt", FWD + "block_3/add", "other"),          # the residual add
    ("gpt", "jit(step)/jvp()/slice", "other"),
    ("gpt", "jit(step)/amp_optimizer_step/cond/branch_1_fun/"
            "adam/mlp_not_a_segment", "other"),
    ("gpt", None, "other"),
    ("bert", "jit(step)/jvp(BertForPreTraining)/bert/layer_0/mlp/ffn_out/"
             "dot_general", "mlp"),
    ("bert", "jit(step)/jvp(BertForPreTraining)/bert/layer_0/attention_ln/"
             "pallas_call", "norm"),
    ("bert", "jit(step)/jvp(BertForPreTraining)/mlm_decoder/dot_general",
     "head_loss"),
    ("bert", "jit(step)/jvp(BertForPreTraining)/mlm_ln/pallas_call",
     "head_loss"),
    ("bert", "jit(step)/transpose(jvp(pretraining_loss))/mul", "head_loss"),
    ("bert", "jit(step)/jvp(BertForPreTraining)/bert/pos_emb/take", "embed"),
])
def test_block(family, op_name, expected):
    assert scopes.block(op_name, family) == expected
    assert expected in scopes.BLOCKS


@functools.cache
def recorded():
    with open(os.path.join(HERE, "fixtures",
                           "trace_gpt2_step_12ms.json")) as f:
        raw = json.load(f)
    planes = {plane: {line: [T.Event(*e) for e in events]
                      for line, events in lines.items()}
              for plane, lines in raw.items()}
    return T.instruction_seconds(planes, "/device:TPU:0"), \
        T.length(T.busy(planes, "/device:TPU:0")) * 1e-9


def a_run(op_names: dict, chips: int = 1):
    """What ``drivers/train.py`` ``Run`` hands a reader, from the recorded
    12 ms of the chip."""
    by_instruction, busy = recorded()
    return types.SimpleNamespace(
        instruction_seconds=by_instruction, op_names=op_names, busy_s0=busy,
        cfg={"family": "gpt"}, chips=chips, steps_traced=1, notes={})


def named_as_the_program_would(by_instruction: dict) -> dict:
    """``op_name``s for the recorded stretch (the start of a forward
    pass; the events themselves carry none): by kernel name, as the
    compiled step has them."""
    rules = (("flash_fwd", FWD + "block_0/attention/pallas_call"),
             ("layer_norm_fwd", FWD + "block_0/ln1/pallas_call"),
             ("convolution", FWD + "block_0/mlp/ffn_in/dot_general"),
             ("copy", None),
             ("convert", "jit(step)/amp_cast/convert_element_type"),
             ("", FWD + "block_0/attention/qkv/add"))
    names = {}
    for instr in by_instruction:
        name = next(n for prefix, n in rules if instr.startswith(prefix))
        if name:
            names[instr] = name
    names["the_update"] = "jit(step)/amp_optimizer_step/cond/branch_1_fun/add"
    return names


def test_the_recorded_trace_is_partitioned():
    by_instruction, busy = recorded()
    names = named_as_the_program_would(by_instruction)
    total = sum(T.kernel_seconds(by_instruction).values())
    for op_names in ({}, names):      # with no name at all, and with them
        sums = dict.fromkeys(scopes.PHASES, 0.0)
        for instr, seconds in by_instruction.items():
            sums[scopes.phase(instr, op_names.get(instr))] += seconds
        assert sum(sums.values()) == pytest.approx(total, rel=1e-12)
    assert sums["forward"] > 0 and sums["amp"] > 0 and sums["unscoped"] > 0
    run = a_run(names)
    assert sum(scopes.phase_seconds(run).values()) == \
        pytest.approx(total, rel=1e-12)
    assert sum(scopes.block_seconds(run).values()) == \
        pytest.approx(total, rel=1e-12)
    assert total == pytest.approx(busy, rel=1e-9)


def read(metric: str, run):
    with open(os.path.join(ROOT, "benchmark/metrics", metric + ".json")) as f:
        spec = json.load(f)
    return importlib.import_module(
        "benchmark.readers." + spec["reader"]).read(run)


def test_readers_on_a_program_that_carries_the_scopes():
    by_instruction, busy = recorded()
    run = a_run(named_as_the_program_would(by_instruction))
    got = {m: read(m, run) for m in NEW_METRICS}
    flash = sum(s for i, s in by_instruction.items()
                if i.startswith("flash_fwd"))
    attention = sum(s for i, s in by_instruction.items()
                    if scopes.block(run.op_names.get(i), "gpt") == "attention")
    assert got["attention.outside_kernel_share"] == pytest.approx(
        100 * (attention - flash) / busy)
    assert flash > 0 and attention > flash
    assert got["model.mlp_share"] == pytest.approx(100 * sum(
        s for i, s in by_instruction.items()
        if i.startswith("convolution")) / busy)
    assert got["train_step.backward_share"] is None     # none in 12 ms
    assert got["model.head_loss_share"] is None
    assert got["allreduce.pack_ms"] is None             # one chip
    shares = [got["train_step.forward_share"], got["amp.overhead_share"],
              got["train_step.unscoped_share"]]
    assert all(s > 0 for s in shares)
    assert sum(shares) == pytest.approx(100.0, abs=1e-6)
    assert run.notes["train_step.phases"].endswith("= 100.000% of busy time")
    for phase in scopes.PHASES:
        assert phase in run.notes["train_step.phases"]


def test_the_exchange_beside_the_collectives_is_read_on_several_chips():
    by_instruction, _ = recorded()
    names = named_as_the_program_would(by_instruction)
    packed = [i for i in by_instruction if i.startswith("copy")]
    assert packed
    for instr in packed:
        names[instr] = "jit(sharded)/shard_map/amp_reduce/ddp_allreduce/mul"
    seconds = sum(by_instruction[i] for i in packed)
    assert read("allreduce.pack_ms", a_run(names, chips=4)) == \
        pytest.approx(1e3 * seconds)
    assert read("allreduce.pack_ms", a_run(names, chips=1)) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_program_without_the_scopes_reads_nothing(metric):
    """The parent commit's program, or an executable cached from it: it
    has ``jvp(`` and ``lm_head`` and ``amp_unscale``, and no phase scope."""
    by_instruction, _ = recorded()
    names = {i: FWD + "lm_head/dot_general" for i in by_instruction}
    names["x"] = "jit(step)/amp_unscale/mul"
    names["y"] = "jit(step)/cond/branch_1_fun/add"
    assert not scopes.named(names)
    assert read(metric, a_run(names, chips=4)) is None
    untraced = types.SimpleNamespace(
        instruction_seconds={}, op_names={}, busy_s0=None,
        cfg={"family": "gpt"}, chips=4, steps_traced=0, notes={})
    assert read(metric, untraced) is None


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_has_its_two_files(metric):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    with open(os.path.join(ROOT, "benchmark/metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert {k: v for k, v in spec.items() if k != "reader"} == listed[metric]
    assert spec["source"] == "program_span" and spec["better"] == "lower"
    assert os.path.exists(os.path.join(
        ROOT, "benchmark/readers", spec["reader"] + ".py"))
