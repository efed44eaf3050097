"""Parser units for tools/fusion_roofline.py (the RN50 roofline audit).

The tool's conclusions (round 4: the b256 step is HBM-bound, MFU
ceiling ~0.35) hang on its HLO accounting, so the shape/
byte/FLOP extraction is pinned here against a hand-written HLO snippet
with the wrinkles that broke earlier drafts: tuple-valued fusion outputs
whose type strings contain spaces and layout parens (``T(8,128)``),
operands resolved per-computation, duplicate operands counted once, and
the analytic conv-FLOP formula."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "tools"))

from fusion_roofline import _shape_bytes, parse_step  # noqa: E402

HLO = """\
HloModule test

%fused_computation.1 (param_0: bf16[8,16,16,64], param_1: bf16[1,1,64,32]) -> (f32[32], bf16[8,16,16,32]) {
  %param_0.1 = bf16[8,16,16,64]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %param_1.1 = bf16[1,1,64,32]{2,3,1,0:T(8,128)(2,1)} parameter(1)
  %conv.1 = bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)} convolution(%param_0.1, %param_1.1), window={size=1x1}, dim_labels=b01f_01io->b01f, metadata={op_name="test/conv"}
  %cvt.1 = f32[8,16,16,32]{3,0,2,1:T(8,128)} convert(%conv.1)
  %c0 = f32[] constant(0)
  %red.1 = f32[32]{0:T(256)} reduce(%cvt.1, %c0), dimensions={0,1,2}, to_apply=%add_comp
  ROOT %tup = (f32[32]{0:T(256)}, bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)}) tuple(%red.1, %conv.1)
}

ENTRY %main (p0: bf16[8,16,16,64], p1: bf16[1,1,64,32]) -> bf16[8,16,16,32] {
  %p0 = bf16[8,16,16,64]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[1,1,64,32]{2,3,1,0:T(8,128)(2,1)} parameter(1)
  %big_fusion.7 = (f32[32]{0:T(256)S(1)}, bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)}) fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="test/convfusion"}
  %gte.1 = bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)} get-tuple-element(%big_fusion.7), index=1
  %dup.1 = bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)} add(%gte.1, %gte.1)
  ROOT %out.1 = bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)} copy(%dup.1)
}
"""


def test_shape_bytes_tuple_and_layout_parens():
    t = ("(f32[32]{0:T(256)S(1)}, "
         "bf16[8,16,16,32]{3,0,2,1:T(8,128)(2,1)})")
    assert _shape_bytes(t) == 32 * 4 + 8 * 16 * 16 * 32 * 2
    assert _shape_bytes("pred[]{:T(512)}") == 1


def test_parse_step_tuple_fusion_record():
    rec = parse_step(HLO)
    # tuple-output fusion (type string with spaces + layout parens) must
    # produce a record — earlier drafts dropped exactly these, silently
    # excluding every conv mega-fusion from the audit
    f = rec["big_fusion.7"]
    assert f["read_b"] == (8 * 16 * 16 * 64 * 2) + (64 * 32 * 2)
    assert f["write_b"] == 32 * 4 + 8 * 16 * 16 * 32 * 2
    # 2 * out(8*16*16*32) * window(1*1) * Cin(64)
    assert f["conv_flops"] == 2.0 * 8 * 16 * 16 * 32 * 64
    assert f["meta"] == "test/convfusion"


GRAD_HLO = """\
HloModule grads

ENTRY %main (p0: bf16[8,16,16,64], p1: bf16[3,3,1,64], p2: bf16[8,14,14,32]) -> f32[3,3,64,32] {
  %p0 = bf16[8,16,16,64]{3,0,2,1:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[3,3,1,64]{2,3,1,0:T(8,128)(2,1)} parameter(1)
  %p2 = bf16[8,14,14,32]{3,0,2,1:T(8,128)(2,1)} parameter(2)
  %dw.1 = bf16[8,16,16,64]{3,0,2,1:T(8,128)(2,1)} convolution(%p0, %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, feature_group_count=64, metadata={op_name="test/depthwise"}
  %kg.1 = f32[3,3,64,32]{3,2,1,0:T(8,128)} convolution(%p0, %p2), window={size=14x14}, dim_labels=f01b_i01o->01bf, metadata={op_name="test/kernelgrad"}
  ROOT %out.1 = f32[3,3,64,32]{3,2,1,0:T(8,128)} copy(%kg.1)
}
"""


def test_conv_flops_contract_over_rhs_i_dim():
    rec = parse_step(GRAD_HLO)
    # depthwise (feature_group_count=64): per-output contraction is the
    # rhs i dim = 1, NOT the lhs f dim = 64 — reading lhs f overcounts
    # by the group count
    assert rec["dw.1"]["conv_flops"] == 2.0 * (8 * 16 * 16 * 64) * 9 * 1
    # kernel-grad conv (labels f01b_i01o): contraction is over batch,
    # surfaced as the rhs i dim = 8
    assert (rec["kg.1"]["conv_flops"]
            == 2.0 * (3 * 3 * 64 * 32) * (14 * 14) * 8)


def test_parse_step_duplicate_operands_counted_once():
    rec = parse_step(HLO)
    add = rec["dup.1"]
    assert add["read_b"] == 8 * 16 * 16 * 32 * 2  # gte.1 once, not twice
    assert add["conv_flops"] == 0.0
    # bookkeeping ops never become records
    assert "gte.1" not in rec and "p0" not in rec and "tup" not in rec
