"""``benchmark/flops.py`` and the families' counts against a hand count."""

import json
import os

import pytest

from benchmark import flops, peaks
from benchmark.families import bert, gpt
from benchmark.tests.conftest import ROOT


def cfg(name):
    with open(os.path.join(ROOT, "benchmark/configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_by_hand():
    # 24 blocks of 4*1024^2 (qkv + out) + 2*1024*4096 (ffn) = 12,582,912
    # weights each, and a 1024 x 50257 head: 301,989,888 + 51,463,168.
    # Six FLOPs a weight and token: 2,120,718,336.  Attention: one pass is
    # 2 * 1024 (keys) * 1024 (hidden) FLOPs a token and layer, causal half,
    # six passes, 24 layers: 6 * 24 * 1,048,576 = 150,994,944.
    got = gpt.flops_per_token(cfg("gpt2-medium"), {"seq": 1024})
    assert got == 6 * (301_989_888 + 51_463_168) + 150_994_944
    assert got == 2_271_713_280


def test_bert_large_by_hand():
    # encoder: 24 * 12,582,912 = 301,989,888 weights on every token.  MLM
    # transform 1024^2 and decoder 1024 * 30522 on 77 of 512 positions
    # (15% of 512 rounded): 32,303,104 * 77 / 512.  Pooler and NSP head
    # (1024^2 + 2048) once in 512 tokens.  Attention: 2 * 512 * 1024 a
    # pass, six passes, 24 layers, not causal: 150,994,944.
    traffic = {"seq": 512, "mask_rate": 0.15}
    assert bert.masked_per_row(traffic) == 77
    want = 6 * (301_989_888 + 32_303_104 * 77 / 512
                + (1_048_576 + 2048) / 512) + 150_994_944
    assert bert.flops_per_token(cfg("bert-large-uncased"), traffic) \
        == pytest.approx(want, rel=1e-12)


def test_attention_and_roofline_arithmetic():
    assert flops.attention_pass_flops_per_token(1024, 1024, 1, False) \
        == 2 * 1024 * 1024
    assert flops.attention_train_bytes_per_token(1024, 24) == 24 * 12 * 2048
    p = peaks.peak("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 1.0, p) == (1.0, "flops")
    assert flops.roofline_seconds(1.0, 819e9, p) == (1.0, "bytes")
    with pytest.raises(ValueError):
        peaks.peak("TPU v9")


def test_parameter_counts_are_the_ones_the_configurations_state():
    def count(spec):
        n = 0
        for shape, _ in spec.values():
            size = 1
            for d in shape:
                size *= d
            n += size
        return n
    assert round(count(gpt.reference.param_spec(cfg("gpt2-medium"))) / 1e6) \
        == 405
    assert round(count(bert.reference.param_spec(
        cfg("bert-large-uncased"))) / 1e6) == 367


def test_held_load_ratio_reads_the_steps_own_counters():
    """Pairs served by the held experts over ``tokens x k x held / n``,
    the mean over layers and traced steps, with the layers on ``note:``
    lines; nothing where the step carried no counters."""
    import types

    import numpy as np

    from benchmark.families import deepseek_v3 as family
    from benchmark.readers import moe_held_load_ratio as reader
    cfg = {"n_routed_experts": 128, "n_routed_experts_held": 16,
           "num_experts_per_tok": 6}
    traffic = {"rows_per_chip": 1, "seq": 8192}
    assert family.expected_held_pairs(cfg, traffic) == 6144.0
    steps = [{"pairs": np.array([6144, 3072]),
              "load_peak": np.array([1.5, 2.0]), "windows": np.array([1, 1])},
             {"pairs": np.array([6144, 9216]),
              "load_peak": np.array([1.25, 3.0]),
              "windows": np.array([1, 2])}]
    run = types.SimpleNamespace(family=family, cfg=cfg, traffic=traffic,
                                aux_traced=steps, notes={})
    assert reader.read(run) == 1.0
    assert run.notes["moe.held_load_ratio.by_layer"] == [1.0, 1.0]
    assert run.notes["moe.held_load_ratio.fullest_expert_by_layer"] == \
        [1.5, 3.0]
    assert run.notes["moe.held_load_ratio.windows_by_layer"] == [1, 2]
    run.aux_traced = []
    assert reader.read(run) is None
