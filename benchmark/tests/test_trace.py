"""The reduction of ``benchmark/trace.py``: on hand-made events, and on
12 ms of one step of ``gpt2_medium.lm_b8_s1024`` recorded on the v5e
(``fixtures/``: the events of ``XLA Ops`` and ``Async XLA Ops``, names
cut to 400 characters)."""

import json
import os

import pytest

from benchmark import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
DEV = "/device:TPU:0"


def ev(name, start, duration):
    return T.Event(name, float(start), float(duration))


def planes(ops, asyncs=(), steps=(), host=()):
    return {DEV: {T.OPS_LINE: list(ops), T.ASYNC_LINE: list(asyncs),
                  T.STEPS_LINE: list(steps)},
            T.HOST_PLANE: {T.HOST_LINE: list(host)}}


FUSION = "%fusion.{} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"
LN = "%layer_norm_fwd.{} = (bf16[8,8]{{1,0:T(8,128)(2,1)S(1)}}) " \
     "custom-call(bf16[8,8]{{1,0}} %x)"
ALLREDUCE = "%all-reduce.{} = f32[8]{{0}} all-reduce(f32[8]{{0}} %g), " \
            "replica_groups={{{{0,1,2,3}}}}"
COND = "%conditional.{} = (f32[8]{{0}}) conditional(pred[] %p, f32[8] %a)"


def test_names_and_opcodes():
    assert T.instruction(LN.format(49)) == "layer_norm_fwd.49"
    assert T.kernel(LN.format(49)) == "layer_norm_fwd"
    assert T.opcode(LN.format(49)) == "custom-call"
    assert T.opcode(ALLREDUCE.format(1)) == "all-reduce"
    assert T.is_collective(ALLREDUCE.format(1))
    assert T.is_collective("%all-reduce-start.2 = f32[8]{0} "
                           "all-reduce-start(f32[8]{0} %g)")
    assert not T.is_collective(FUSION.format(3))
    assert T.opcode("bench/dispatch") == ""


def test_busy_union_idle_and_kernel_sums():
    ops = [ev(FUSION.format(1), 0, 100), ev(LN.format(1), 100, 50),
           ev(LN.format(2), 200, 50),            # 50 ns idle before it
           ev(COND.format(1), 300, 100),         # holds the next two
           ev(FUSION.format(2), 310, 40), ev(FUSION.format(3), 350, 40)]
    p = planes(ops, steps=[ev("0", 0, 200), ev("1", 200, 200)],
               host=[ev("bench/input", 140, 70), ev("other", 0, 400)])
    assert T.window(p, DEV) == (0.0, 400.0)
    assert T.busy(p, DEV) == [(0.0, 150.0), (200.0, 250.0), (300.0, 400.0)]
    assert T.length(T.busy(p, DEV)) == 300.0
    sums = T.kernel_seconds(T.instruction_seconds(p, DEV))
    assert sums["layer_norm_fwd"] == pytest.approx(100e-9)
    assert sums["fusion"] == pytest.approx(180e-9)
    assert "conditional" not in sums           # a container: not an op
    # what ran adds up to busy time less the conditional's own 20 ns
    assert sum(sums.values()) == pytest.approx(280e-9)
    assert T.steps_traced(p, DEV) == 2
    gaps = dict(T.idle_gaps(p, DEV))
    # 150-200 falls under the benchmark's own span, 250-300 under none
    assert gaps == {"host:input": pytest.approx(50e-9),
                    "host:unattributed": pytest.approx(50e-9)}
    assert T.top_ops(sums, 1) == [["fusion", pytest.approx(180e-9)]]


def test_an_operation_that_starts_a_rounding_error_early_is_not_nested():
    ops = [ev(FUSION.format(1), 0, 100.6), ev(LN.format(1), 100, 50)]
    own = {T.kernel(e.name): s for e, s in T.self_times(ops)}
    assert own == {"fusion": 100.6, "layer_norm_fwd": 50.0}


def test_exposed_collective_time():
    # the all-reduce is in flight from 100 to 400; compute covers 100-250
    # and 300-350 of it, so 250-300 and 350-400 are exposed
    ops = [ev(FUSION.format(1), 0, 250), ev(FUSION.format(2), 300, 50),
           ev("%all-reduce-done.1 = f32[8]{0} all-reduce-done(%s)", 350, 50)]
    asyncs = [ev("%all-reduce-start.1 = f32[8]{0} all-reduce-start(%g)",
                 100, 300)]
    p = planes(ops, asyncs, steps=[ev("0", 0, 400)])
    assert T.exposed_collective_seconds(p, DEV) == pytest.approx(100e-9)
    # a synchronous all-reduce is exposed for as long as it runs
    p = planes([ev(FUSION.format(1), 0, 100), ev(ALLREDUCE.format(1), 100, 60)],
               steps=[ev("0", 0, 160)])
    assert T.exposed_collective_seconds(p, DEV) == pytest.approx(60e-9)
    # and none where there is no collective
    p = planes([ev(FUSION.format(1), 0, 100)], steps=[ev("0", 0, 100)])
    assert T.exposed_collective_seconds(p, DEV) == 0.0


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert T.subtract([(0, 100)], [(10, 20), (50, 120)]) == [(0, 10), (20, 50)]
    assert T.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_optimizer_rule():
    cond = "jit(step)/cond/branch_1_fun/div"
    assert T.is_optimizer("fusion.3", cond)
    assert T.is_optimizer("fusion.3", "jit(step)/amp_unscale/mul")
    assert T.is_optimizer("lamb_stage1.2", None)
    assert T.is_optimizer("mt_scale", None)
    assert not T.is_optimizer(
        "fusion.4", "jit(step)/transpose(jvp(GPTModel))/block_3/ln1/mul")
    assert not T.is_optimizer("fusion.5", "jit(step)/jvp(GPTModel)/cond/x")
    assert not T.is_optimizer("fusion.6", None)
    assert not T.is_optimizer(ALLREDUCE.format(1), cond)
    hlo = ('  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, '
           'metadata={op_name="jit(step)/cond/branch_1_fun/div"}\n'
           '  ROOT %layer_norm_fwd.1 = bf16[8]{0} custom-call(%x), '
           'custom_call_target="tpu_custom_call", '
           'metadata={op_name="jit(step)/jvp(M)/ln/pallas_call"}\n')
    assert T.op_names(hlo) == {
        "fusion.3": "jit(step)/cond/branch_1_fun/div",
        "layer_norm_fwd.1": "jit(step)/jvp(M)/ln/pallas_call"}
    assert T.mosaic_kernels(hlo) == ["layer_norm_fwd"]


def test_recorded_fixture_from_the_chip():
    with open(os.path.join(HERE, "fixtures",
                           "trace_gpt2_step_12ms.json")) as f:
        raw = json.load(f)
    p = {plane: {line: [T.Event(*e) for e in events]
                 for line, events in lines.items()}
         for plane, lines in raw.items()}
    assert T.device_planes(p) == [DEV]
    assert len(p[DEV][T.OPS_LINE]) == 544
    sums = T.kernel_seconds(T.instruction_seconds(p, DEV))
    busy = T.length(T.busy(p, DEV)) * 1e-9
    # nothing nests in this stretch, so what ran adds up to busy time
    assert sum(sums.values()) == pytest.approx(busy, rel=1e-9)
    assert busy == pytest.approx(0.011934262, rel=1e-6)
    assert sums["flash_fwd"] == pytest.approx(0.004699679, rel=1e-6)
    assert sums["layer_norm_fwd"] == pytest.approx(0.000256366, rel=1e-6)
    assert T.top_ops(sums, 1)[0][0] == "flash_fwd"
    assert T.exposed_collective_seconds(p, DEV) == 0.0
    lo, hi = T.window(p, DEV)
    assert 1 - busy / ((hi - lo) * 1e-9) < 0.001
