"""The cut configuration's contract, and this model's own planted
faults.  ``test_contract.py`` holds every listed configuration to
``reduced == []``; this one is one chip's share of a deployment, so its
contract is here: published widths kept, exactly three keys cut, each
beside its published value, and the deployment stated."""

import json
import os

import pytest

from benchmark.tests.conftest import ROOT
from benchmark.tests.test_runs import in_process

CELL = "kanana2_30b_a3b.lm_b1_s8192"
FILE = "benchmark/configs/kanana-2-30b-a3b-instruct-2601.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_the_cut_is_three_keys_each_beside_its_published_value():
    cfg = load(FILE)
    listed = [c for c in load("BENCHMARK.json")["configs"]
              if c["file"] == FILE]
    assert len(listed) == 1 and listed[0]["reduced"] == cfg["reduced"]
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts_held"],
            cfg["vocab_size"]) == (5, 16, 16032)
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    assert cfg["n_routed_experts"] == 128          # the router's width stays
    assert "8 chips share each layer" in cfg["deployment"]
    # the floors of a cut: a whole period and four expert layers after
    # the dense one, at least 8 experts held, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]


def test_no_width_differs_from_the_published_configuration():
    cfg = load(FILE)
    widths = dict(hidden_size=2048, intermediate_size=6144,
                  moe_intermediate_size=768, num_attention_heads=32,
                  num_key_value_heads=32, kv_lora_rank=512, qk_head_dim=192,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  head_dim=64, n_routed_experts=128, n_shared_experts=2,
                  num_experts_per_tok=6, routed_scaling_factor=2.448,
                  rope_theta=1000000, rms_norm_eps=1e-06)
    for key, value in widths.items():
        assert cfg[key] == value, key
    assert not set(cfg["reduced"]) & set(widths)
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f) if r["name"] == cfg["name"]][0]
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
        else:
            assert cfg["published"][key] == value, key


def unnormalised(routing):
    """``norm_topk_prob`` ignored: the chosen scores as they are."""
    def route(logits, k=1, **opts):
        return routing(logits, k, **dict(opts, renormalize=False))
    return route


def unscaled(routing):
    """``routed_scaling_factor`` dropped."""
    def route(logits, k=1, **opts):
        return routing(logits, k, **dict(opts, scale=1.0))
    return route


@pytest.mark.parametrize("fault", [unnormalised, unscaled],
                         ids=lambda f: f.__name__)
def test_a_router_that_weighs_wrongly_comes_out_not_correct(
        capsys, monkeypatch, fault):
    from apex_tpu.parallel import moe
    monkeypatch.setattr(moe, "route", fault(moe.route))
    line, _, err = in_process(capsys, CELL, 2147484003)
    assert line["correct"] is False, line["compared"]
    assert "correct: False" in err


def test_the_rehearsal_of_the_cell_is_correct_on_another_seed(capsys):
    line, out, _ = in_process(capsys, CELL, 2147484005)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["rehearsal"] is True
    assert "compilations inside the window: 0" in out
