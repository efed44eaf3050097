"""The cut configuration's contract, this model's own planted faults,
and the recipe its cell trains under: the rate's warm-up on both sides
of ``correct`` and the correction bias's balance update.  The
configuration is one chip's share of a deployment: published widths
kept, exactly three keys cut, each beside its published value, and the
deployment stated."""

import json
import os

import pytest

from benchmark.tests.conftest import ROOT
from benchmark.tests.test_runs import in_process

CELL = "kanana2_30b_a3b.lm_b1_s8192_balanced"
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


# --- the recipe: warm-up and balance update -------------------------------

def test_the_cell_trains_under_the_published_recipe():
    cell = load("benchmark/workloads", CELL + ".json")
    assert cell["parameters"] == {
        "rows_per_chip": 1, "seq": 8192, "lr_warmup_steps": 2000,
        "balance_rate": 0.001, "warmup_steps": 8, "traced_steps": 6,
        "reference_steps": 3, "reference_block_rows": 1}
    cfg = load(FILE)
    assert cfg["optimizer"]["args"]["lr"] == 0.0003
    said = cfg["assumed"]
    for words in ("0.001", "arXiv:2412.19437", "not all-reduced", "sign"):
        assert words in said["e_score_correction_bias"], words
    for words in ("2000", "arXiv:2412.19437 section 4.2"):
        assert words in said["lr_warmup_steps"], words
    assert "flash_grid" not in cell["why"]


@pytest.mark.parametrize("step", [1, 2, 3, 4, 5, 2000, 2001])
def test_the_two_sides_warm_up_at_the_same_rate(step):
    import jax.numpy as jnp
    from benchmark.drivers import train
    from benchmark.reference import optim
    program = train.warmup(3e-4, 2000)(jnp.int32(step))
    reference = optim.warmup(3e-4, jnp.float32(step), 2000)
    assert program.dtype == reference.dtype == jnp.float32
    assert float(program) == float(reference)
    assert float(program) == pytest.approx(3e-4 * min(1.0, step / 2000),
                                           rel=1e-6)
    assert optim.warmup(3e-4, jnp.float32(step), 0) == 3e-4


def test_both_optimizers_take_the_same_warmed_up_steps():
    """The program's FusedAdam under the driver's schedule and the
    reference's ``adam`` with ``lr_warmup_steps``, three steps of the
    same gradients: the same parameters, step for step."""
    import jax
    import jax.numpy as jnp
    import optax
    from apex_tpu import optimizers
    from benchmark.drivers import train
    from benchmark.reference import optim
    params = {"w": jnp.array([1.0, -2.0, 0.5], jnp.float32)}
    tx = optimizers.FusedAdam(lr=train.warmup(3e-4, 2000), betas=(0.9, 0.999),
                              eps=1e-8, weight_decay=0.0)
    theirs, state = params, tx.init(params)
    ours, ostate = params, optim.init(params)
    for n in range(3):
        grads = {"w": jnp.array([0.3, -0.1, 2.0]) * (n + 1)}
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(theirs, updates)
        ours, ostate = optim.adam(ours, grads, ostate, lr=3e-4,
                                  betas=(0.9, 0.999), eps=1e-8,
                                  lr_warmup_steps=2000)
        moved = jax.tree.map(lambda a, b: a - b, theirs, params)["w"]
        assert jnp.allclose(moved, ours["w"] - params["w"], rtol=1e-4,
                            atol=0.0), (n, moved)
    # 1.5e-7 + 3e-7 + 4.5e-7 an element, whatever the gradient's size
    assert jnp.allclose(jnp.abs(moved), 9e-7, rtol=0.35)


def _rules():
    from benchmark.families import deepseek_v3 as family
    from benchmark.reference import deepseek_v3 as reference
    return {"program": family.balance_update,
            "reference": reference.balance_update}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_balance_rule(side):
    """Up for an expert under the mean of *all* the experts' counts,
    down for one over it, unmoved at the mean; float32; no gradient."""
    import jax
    import jax.numpy as jnp
    rule = _rules()[side]
    bias = jnp.array([0.1, -0.2, 0.3, 0.0], jnp.float32)
    counts = jnp.array([0, 2, 4, 10], jnp.int32)           # the mean is 4
    out = rule(bias, counts, 0.001)
    assert out.dtype == jnp.float32
    assert jnp.allclose(out - bias, jnp.array([1e-3, 1e-3, 0.0, -1e-3]),
                        atol=1e-9)
    # the experts held elsewhere count towards the mean: with the last
    # one left out the third would be over the mean, not at it
    assert float(rule(bias[:3], counts[:3], 0.001)[2] - bias[2]) < 0.0
    d_bias, d_counts = jax.grad(
        lambda b, c: jnp.sum(rule(b, c, 0.001)), argnums=(0, 1))(
        bias, counts.astype(jnp.float32))
    assert jnp.all(d_bias == 1.0) and jnp.all(d_counts == 0.0)


def test_the_reference_counts_every_expert_of_the_router():
    import jax.numpy as jnp
    from benchmark.reference import deepseek_v3 as reference
    experts = jnp.array([[[0, 5], [5, 7]], [[7, 5], [1, 0]]])   # (B, L, k)
    counts = reference.expert_counts(experts, 8)
    assert counts.dtype == jnp.float32
    assert counts.tolist() == [2.0, 1.0, 0.0, 0.0, 0.0, 3.0, 0.0, 2.0]


@pytest.fixture(scope="module")
def resolved():
    from benchmark import run
    _, cell, cfg, family, _ = run.resolve(CELL, rehearse=True)
    return cell, cfg, family


def test_the_programs_counts_are_the_references_in_float32(resolved):
    """The step's ``aux`` at rehearsal size, float32 on both sides: the
    counts over all the router's experts are the reference's own, they
    sum to tokens x k in every layer, and the program's own counter of
    the pairs its held experts served is their part of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import weights
    cell, cfg, family = resolved
    traffic = cell["parameters"]
    spec = family.reference.param_spec(cfg)
    params = jax.jit(lambda k: weights.make(spec, k))(weights.seed_key(5))
    (ids,) = family.make_batch(np.random.default_rng(5), 2, cfg, traffic)
    kept = family.step_state(cfg, traffic)
    with jax.default_matmul_precision("highest"):
        _, aux = jax.jit(kept["loss"])(params, ids)
        _, want = jax.jit(lambda p, i: family.reference.logits_and_counts(
            p, i, cfg))(params, jnp.asarray(ids))
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert aux["counts"].shape == (layers, cfg["n_routed_experts"])
    assert np.array_equal(np.asarray(aux["counts"]), np.asarray(want))
    assert np.all(np.asarray(aux["counts"]).sum(-1)
                  == ids.size * cfg["num_experts_per_tok"])
    first, held = cfg.get("first_expert_held", 0), \
        cfg["n_routed_experts_held"]
    assert np.array_equal(
        np.asarray(aux["pairs"]),
        np.asarray(aux["counts"])[:, first:first + held].sum(-1))
    assert kept["paths"] == family.reference.bias_paths(cfg) \
        and len(kept["paths"]) == layers
    # no balance rate in the traffic: no state, on either side
    plain = {k: v for k, v in traffic.items() if k != "balance_rate"}
    assert family.step_state(cfg, plain) is None
    assert family.reference.step_state(cfg, plain) is None


def test_a_skipped_step_leaves_the_rules_state_as_it_was():
    import collections
    import jax.numpy as jnp
    from benchmark.drivers import train
    State = collections.namedtuple("State", "master_params")
    kept = {"paths": ["a/b"],
            "update": lambda values, aux: {"a/b": values["a/b"] + aux}}
    step = train.stateful(
        lambda state, skipped: (state, {"aux": 1.0, "overflow": skipped}),
        kept)
    start = State({"a": {"b": jnp.zeros(2)}, "c": jnp.ones(1)})
    taken, _ = step(start, jnp.bool_(False))
    skipped, _ = step(start, jnp.bool_(True))
    assert taken.master_params["a"]["b"].tolist() == [1.0, 1.0]
    assert skipped.master_params["a"]["b"].tolist() == [0.0, 0.0]
    assert taken.master_params["c"] is start.master_params["c"]


@pytest.fixture(scope="module")
def followed(resolved):
    """The cell at rehearsal size, its first batches and the reference's
    readings on them, once for every planted fault."""
    import jax
    import numpy as np
    from benchmark.drivers import train
    from benchmark.reference import train as ref
    cell, cfg, family = resolved
    traffic = cell["parameters"]
    rng = np.random.default_rng(29)
    rows = traffic["rows_per_chip"] * cell["chips"]
    batches = [family.make_batch(rng, rows, cfg, traffic) for _ in range(3)]
    spec = family.reference.param_spec(cfg)

    def follow(cfg_, part=None, told=None):
        kept = batches if part is None else [part(b) for b in batches]
        kw = train.reference_kwargs(cfg_, dict(traffic, **(told or {})),
                                    jax.devices()[:1])
        return ref.follow(family.reference, cfg_, spec, 29, kept, **kw)

    return cell, cfg, family, traffic, follow, follow(cfg)


FAULTS = ("half_tokens", "unnormalised", "unscaled", "no_warmup",
          "no_balance")


def test_the_faults_planted_are_these(resolved):
    cell, cfg, family = resolved
    assert tuple(family.planted_faults(cfg, cell["parameters"])) == FAULTS
    plain = {k: v for k, v in cell["parameters"].items()
             if k not in ("lr_warmup_steps", "balance_rate")}
    assert tuple(family.planted_faults(cfg, plain)) == FAULTS[:3]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(followed, fault):
    """The reference under the fault in the program's place, judged by
    the cell's own rehearsal limits, as ``calibrate_faults.py`` reads it
    on the chip: the recipe's two parts each by the number that is
    theirs."""
    from benchmark import run
    from benchmark.reference import train as ref
    cell, cfg, family, traffic, follow, want = followed
    gaps = ref.gaps(follow(*family.planted_faults(cfg, traffic)[fault]),
                    want)
    correct, table = run.judge({k: v for k, v in gaps.items()
                                if k.endswith("_gap")}, cell["limits"])
    assert correct is False, table
    if fault == "no_warmup":
        assert gaps["delta_gap"] > 100.0, table
    if fault == "no_balance":
        assert gaps["state_gap"] == pytest.approx(1.0, abs=1e-3), table
        assert gaps["delta_gap"] <= cell["limits"]["delta_gap"], table


def constant_rate(lr, steps):
    return lr


def bias_left_alone(bias, counts, rate):
    return bias


@pytest.mark.parametrize("where, fault, number", [
    ("benchmark.drivers.train:warmup", constant_rate, "delta_gap"),
    ("benchmark.families.deepseek_v3:balance_update", bias_left_alone,
     "state_gap")], ids=["no_warmup", "no_balance"])
def test_the_program_without_a_part_of_the_recipe_comes_out_not_correct(
        capsys, monkeypatch, where, fault, number):
    """A whole run with the timed path broken underneath: the program at
    the constant rate, or with its bias left alone."""
    import importlib
    module, name = where.split(":")
    monkeypatch.setattr(importlib.import_module(module), name, fault)
    line, _, err = in_process(capsys, CELL, 2147484007)
    assert line["correct"] is False, line["compared"]
    row = line["compared"][number]
    assert row["value"] > row["limit"], line["compared"]
    assert "correct: False" in err
