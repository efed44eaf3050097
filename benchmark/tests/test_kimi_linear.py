"""The cut Kimi-Linear configuration's contract, this model's own planted
faults (the two parts of its cell's recipe among them), and its
rehearsal on a second seed.  The configuration is one chip's share of a
deployment: published widths kept, exactly three keys cut, each beside
its published value, and the deployment stated."""

import json
import os

import pytest

from benchmark.tests.conftest import ROOT
from benchmark.tests.test_runs import in_process

CELL = "kimi_linear_48b_a3b.lm_b1_s8192_balanced"
FILE = "benchmark/configs/kimi-linear-48b-a3b-instruct.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: no key of ``reduced`` may be one of these, at the top or in a group
WIDTHS = dict(hidden_size=2304, intermediate_size=9216,
              moe_intermediate_size=1024, num_attention_heads=32,
              num_key_value_heads=32, kv_lora_rank=512,
              qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
              head_dim=72, num_experts=256, num_shared_experts=1,
              num_experts_per_token=8, routed_scaling_factor=2.446,
              rms_norm_eps=1e-05, first_k_dense_replace=1)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def test_the_cut_is_three_keys_each_beside_its_published_value():
    cfg = load(FILE)
    listed = [c for c in load("BENCHMARK.json")["configs"]
              if c["file"] == FILE]
    assert len(listed) == 1 and listed[0]["reduced"] == cfg["reduced"]
    assert listed[0]["source"] == cfg["source"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["published"] == {"num_hidden_layers": 27,
                                "num_experts": 256, "vocab_size": 163840}
    assert cfg["num_experts"] == 256               # the router's width stays
    assert "32 chips share each layer" in cfg["deployment"]
    # the floors of a cut: a whole period (3 KDA : 1 latent) and four
    # layers after the dense one, at least 8 experts held, an eighth of
    # the vocabulary
    from benchmark.reference import kimi_linear as reference
    kinds = reference.kinds(cfg)
    assert kinds[cfg["first_k_dense_replace"]:] == ["kda", "kda", "latent",
                                                    "kda"]
    assert kinds.count("kda") == 4 and kinds.count("latent") == 1
    assert cfg["num_experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    # every size the published config does not give is owned up to
    for key in ("kda_gate_rank", "kda_chunk_size", "kda_l2_norm_eps",
                "A_log", "dt_bias", "short_conv", "biases"):
        assert key in cfg["assumed"], key
    assert cfg["program"]["remat"] is True and "remat" in cfg["assumed"]
    # the rehearsal keeps three KDA layers to one latent layer
    small = dict(cfg, **cfg["rehearse"])
    assert reference.kinds(small) == ["kda", "kda", "kda", "latent"]


def test_no_width_differs_from_the_published_configuration():
    cfg = load(FILE)
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    assert not set(cfg["reduced"]) & set(WIDTHS)
    lin = cfg["linear_attn_config"]
    assert (lin["head_dim"], lin["num_heads"],
            lin["short_conv_kernel_size"]) == (128, 32, 4)
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r["source_url"] == cfg["source"]][0]
    for key, value in row["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key           # nested groups whole
        else:
            assert cfg["published"][key] == value, key
    assert cfg["published"]["num_experts"] == row["config"]["num_experts"]


@pytest.fixture(scope="module")
def followed():
    """The cell at rehearsal size, its first batches and the reference's
    readings on them, once for every planted fault."""
    import jax
    import numpy as np

    from benchmark import run
    from benchmark.drivers import train
    from benchmark.reference import train as ref

    _, cell, cfg, family, _ = run.resolve(CELL, rehearse=True)
    traffic = cell["parameters"]
    rng = np.random.default_rng(23)
    rows = traffic["rows_per_chip"] * cell["chips"]
    batches = [family.make_batch(rng, rows, cfg, traffic) for _ in range(3)]
    spec = family.reference.param_spec(cfg)

    def follow(cfg_, part=None, told=None):
        kept = batches if part is None else [part(b) for b in batches]
        kw = train.reference_kwargs(cfg_, dict(traffic, **(told or {})),
                                    jax.devices()[:1])
        return ref.follow(family.reference, cfg_, spec, 23, kept, **kw)

    return cell, cfg, family, traffic, follow, follow(cfg)


#: ``rotary_on_latent`` cannot show at rehearsal size: with 64-wide
#: layers the latent scores are a few hundredths, the softmax is even and
#: a rotation of q and k moves no norm (PERF.md section 2 has its reading
#: on the chip, at the published widths)
SEEN_AT_REHEARSAL_SIZE = ("half_tokens", "no_decay", "beta_one", "unscaled",
                          "no_warmup", "no_balance")
#: readings, not faults: no limit tells them from float32 (PERF.md 6f)
PROBES = ("probe_g_bfloat16", "probe_state_bfloat16")


def test_the_faults_and_the_probes_are_told_apart_by_name():
    from benchmark import run
    _, cell, cfg, family, _ = run.resolve(CELL, rehearse=True)
    names = tuple(family.planted_faults(cfg, cell["parameters"]))
    assert names == ("half_tokens", "no_decay", "beta_one",
                     "rotary_on_latent", "unscaled", "no_warmup",
                     "no_balance") + PROBES


def test_the_cell_trains_under_the_published_recipe():
    cell = load("benchmark/workloads", CELL + ".json")
    assert cell["parameters"] == {
        "rows_per_chip": 1, "seq": 8192, "lr_warmup_steps": 2000,
        "balance_rate": 0.001, "warmup_steps": 8, "traced_steps": 6,
        "reference_steps": 3, "reference_block_rows": 1}
    cfg = load(FILE)
    assert cfg["optimizer"]["args"]["lr"] == 0.0003
    for words in ("0.001", "arXiv:2412.19437", "not all-reduced", "256"):
        assert words in cfg["assumed"]["e_score_correction_bias"], words
    assert "arXiv:2412.19437 section 4.2" in cfg["assumed"]["lr_warmup_steps"]


@pytest.mark.parametrize("fault", SEEN_AT_REHEARSAL_SIZE
                         + ("rotary_on_latent",) + PROBES)
def test_a_planted_fault_comes_out_not_correct(followed, fault):
    """The reference under the fault in the program's place, judged by the
    cell's own rehearsal limits, as ``calibrate_faults.py`` reads it on
    the chip.  A probe is planted too and, as on the chip, passes: the
    day a limit fails one, it is a fault and moves to the other list."""
    from benchmark import run
    from benchmark.reference import train as ref
    cell, cfg, family, traffic, follow, want = followed
    gaps = ref.gaps(follow(*family.planted_faults(cfg, traffic)[fault]),
                    want)
    correct, table = run.judge({k: v for k, v in gaps.items()
                                if k.endswith("_gap")}, cell["limits"])
    if fault in SEEN_AT_REHEARSAL_SIZE:
        assert correct is False, table
        if fault == "no_balance":       # by the number that is the rule's
            assert gaps["state_gap"] == pytest.approx(1.0, abs=1e-3), table
    else:
        assert gaps["grad_gap"] > 0.0, table       # the plant is there
    if fault in PROBES:
        assert correct is True, table


def test_the_rehearsal_of_the_cell_is_correct_on_another_seed(capsys):
    line, out, _ = in_process(capsys, CELL, 2147484005)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["rehearsal"] is True
    assert "compilations inside the window: 0" in out
