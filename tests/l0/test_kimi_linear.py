"""The Kimi-Linear-shaped decoder against the benchmark's plain reference
(``benchmark/reference/kimi_linear.py``, which imports nothing of
``apex_tpu`` and runs the recurrence token by token), on seeded weights
at a small size: the published first four layers' pattern (KDA + dense;
KDA, KDA, latent with experts), 8 experts of which 4 are held, 2 a
token, a sliced vocabulary.
"""

import hashlib
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, optimizers
from apex_tpu.utils import profiling

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from benchmark import kda_scopes, scopes, trace, weights  # noqa: E402
from benchmark.families import kimi_linear as family  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402

SEQ = 40            # two chunks of 16 and a half


def config(**changes) -> dict:
    with open(REPO / "benchmark/configs/kimi-linear-48b-a3b-instruct.json"
              ) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    cfg.update(changes)
    return cfg


@pytest.fixture(scope="module")
def case():
    cfg = config()
    params = weights.make(reference.param_spec(cfg), weights.seed_key(11))
    ids = jnp.asarray(family.make_batch(np.random.default_rng(5), 2, cfg,
                                        {"seq": SEQ})[0])
    return cfg, params, ids


@pytest.fixture(scope="module")
def compiled_step(case):
    """The O2 step at the small size, its state, and its HLO."""
    cfg, params, ids = case
    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O2", verbosity=0)
    state = a.init(params)
    step = jax.jit(amp.make_train_step(
        a, family.program_loss(cfg, {"seq": SEQ})))
    return a, state, step.lower(state, ids).compile()


def test_the_reference_imports_nothing_of_the_program():
    for name in ("kimi_linear", "deepseek_v3", "common"):
        text = (REPO / f"benchmark/reference/{name}.py").read_text()
        assert "apex_tpu" not in text.split('"""', 2)[2], name


def test_float32_logits_loss_and_gradients_match_the_reference(case):
    """Tight: the same float32 mathematics by two routes (the program's
    recurrence goes chunk by chunk through the triangular inverse, its
    experts through the sort and the grouped product; the reference's
    goes token by token and expert by expert).  2e-4 of the largest
    entry: the logits pass four layers, and the two recurrences round
    differently at about 1e-6 a layer's output."""
    cfg, params, ids = case
    with jax.default_matmul_precision("highest"):
        model = family.program_model(cfg)
        shifted = family.program_loss(cfg, {"seq": SEQ})
        got = jax.jit(jax.value_and_grad(shifted))(params, ids)
        want = jax.jit(jax.value_and_grad(
            lambda p, i: reference.block_loss(
                p, (i,), reference.totals((i,)), cfg)))(params, ids)
        logits = model.apply(
            {"params": family.with_dt_shift(params, cfg)}, ids)
        ref_logits = reference.logits(params, ids, cfg)
    np.testing.assert_allclose(
        logits, ref_logits, rtol=0,
        atol=2e-4 * float(jnp.abs(ref_logits).max()))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    flat_got, flat_want = (weights.flatten(t) for t in (got[1], want[1]))
    assert set(flat_got) == set(flat_want)
    for path, b in flat_want.items():
        if path.endswith("e_score_correction_bias"):
            continue                    # no gradient on either side
        np.testing.assert_allclose(
            flat_got[path], b, rtol=0,
            atol=2e-4 * float(jnp.abs(b).max()) + 1e-12, err_msg=path)


def test_the_layers_take_their_kind_from_the_published_lists(case):
    cfg, params, _ = case
    assert reference.kinds(cfg) == ["kda", "kda", "kda", "latent"]
    assert family.layer_counts(cfg) == (3, 1)
    assert "q_conv" in params["block_0"]["attention"]
    assert "kv_a_proj" in params["block_3"]["attention"]
    assert "ffn" in params["block_0"] and "router" in params["block_1"]
    with open(REPO / "benchmark/configs/kimi-linear-48b-a3b-instruct.json"
              ) as f:
        full = json.load(f)
    assert reference.kinds(full) == ["kda", "kda", "kda", "latent", "kda"]
    whole = dict(full, num_hidden_layers=27)
    assert reference.kinds(whole).count("kda") == 20
    assert family.program_model(full).cfg.kda_layers == (1, 2, 3, 5)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """8 experts in two shares of 4: what each share's routed experts
    give, plus the shared expert once, is what the reference gives for
    the whole layer with all 8 held."""
    from apex_tpu.models.deepseek_v3 import (DeepseekV3Config, GatedMLP,
                                             RoutedExperts, Router)
    cfg = reference.as_deepseek(config())
    key = jax.random.PRNGKey(3)
    h, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], 8
    x = jax.random.normal(key, (2, SEQ, h))
    leaves = {name: 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            shape)
              for i, (name, shape) in enumerate({
                  "router": (h, n), "gate": (n, h, f), "up": (n, h, f),
                  "down": (n, f, h), "s_gate": (h, f), "s_up": (h, f),
                  "s_down": (f, h)}.items())}
    router = {"kernel": leaves["router"],
              "e_score_correction_bias": jnp.zeros((n,))}
    shared = {k: {"kernel": leaves[f"s_{k}"]} for k in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        weights_, experts = reference.D.routing(x, router, cfg)
        whole = reference.D.gated(x, shared) + reference.D.routed_experts(
            x, {k: leaves[k] for k in ("gate", "up", "down")}, weights_,
            experts, 0)
        parts = GatedMLP(h, f).apply({"params": shared}, x)
        tokens = x.reshape(-1, h)
        for first in (0, 4):
            c = DeepseekV3Config(
                hidden_size=h, moe_intermediate_size=f, n_routed_experts=n,
                n_routed_experts_held=4, first_expert=first,
                num_experts_per_tok=cfg["num_experts_per_tok"],
                routed_scaling_factor=cfg["routed_scaling_factor"])
            routing = Router(c).apply({"params": router}, tokens)
            held = {k: leaves[k][first:first + 4]
                    for k in ("gate", "up", "down")}
            parts = parts + RoutedExperts(c).apply(
                {"params": held}, tokens, routing)[0].reshape(x.shape)
    np.testing.assert_allclose(parts, whole, rtol=0,
                               atol=1e-5 * float(jnp.abs(whole).max()))


def test_the_decay_parameters_and_the_gated_norm_gain_stay_float32(
        compiled_step):
    """amp O2 casts the model to bfloat16 and keeps, by name, every norm
    gain and the recurrence's ``A_log`` and ``dt_bias``."""
    a, state, _ = compiled_step
    cast = weights.flatten(a.model_params(state))
    kept = {p for p, x in cast.items() if x.dtype == jnp.float32}
    for leaf in ("A_log", "dt_bias", "o_norm/scale"):
        assert f"block_0/attention/{leaf}" in kept, leaf
    assert "block_3/attention/kv_norm/scale" in kept
    assert cast["block_0/attention/q_proj/kernel"].dtype == jnp.bfloat16
    assert cast["block_0/attention/q_conv/kernel"].dtype == jnp.bfloat16
    assert amp.default_keep_fp32_filter(("block_0", "attention", "A_log"))
    assert not amp.default_keep_fp32_filter(("block_0", "dt_bias_proj",
                                             "kernel"))


def test_scopes_agree_and_reach_the_compiled_step(compiled_step):
    """The benchmark's copy of the KDA scopes equals the program's; the
    compiled O2 step carries each of them inside ``attention``, and
    ``scopes.block`` finds the five blocks."""
    assert kda_scopes.KDA_SCOPES == profiling.KDA_SCOPES
    assert set(scopes.BLOCK_SEGMENTS["kimi_linear"]) == set(
        scopes.BLOCK_SEGMENTS["gpt"])
    names = trace.op_names(compiled_step[2].as_text())
    paths = [set(scopes.segments(n)) for n in names.values() if n]
    for scope in profiling.KDA_SCOPES + profiling.MOE_SCOPES + (
            "mlp", "lm_loss", "lm_head", "attention", "attn_norm",
            "ffn_norm", "final_norm"):
        assert any(scope in p for p in paths), scope
    for p in paths:
        if p & set(profiling.KDA_SCOPES) or "mla_project" in p:
            assert "attention" in p, p
        assert not (p & set(profiling.KDA_SCOPES) and "mla_project" in p)
    blocks = {scopes.block(n, "kimi_linear") for n in names.values()}
    assert {"head_loss", "mlp", "attention", "norm", "embed"} <= blocks


def test_the_model_returns_each_kind_of_layer_its_counters(case):
    cfg, params, ids = case
    _, stats = family.program_model(cfg).apply(
        {"params": family.with_dt_shift(params, cfg)}, ids, return_stats=True)
    assert set(stats) == {"kda", "experts"}
    assert set(stats["kda"]) == {"log_decay_min", "state_absmax"}
    assert stats["kda"]["log_decay_min"].shape == (3,)
    assert float(stats["kda"]["log_decay_min"].max()) < 0.0
    assert float(stats["kda"]["state_absmax"].min()) > 0.0
    assert set(stats["experts"]) == {"pairs", "load_peak", "windows"}
    assert stats["experts"]["pairs"].shape == (3,)


def test_flops_and_parameters_come_from_shapes():
    with open(REPO / "benchmark/configs/kimi-linear-48b-a3b-instruct.json"
              ) as f:
        cfg = json.load(f)
    n = sum(int(np.prod(shape)) for shape, _ in
            reference.param_spec(cfg).values())
    assert abs(n / 602e6 - 1.0) < 0.01              # ISSUE 32's count
    a = family.attention(cfg, {"seq": 8192})
    assert a["hidden"] == 32 * 160 and a["layers"] == 1
    per_token = family.flops_per_token(cfg, {"seq": 8192})
    assert abs(per_token / 2.319e9 - 1.0) < 0.01
    r = family.recurrence(cfg, {"seq": 8192})
    assert r == {"head_dim": 128, "heads": 32, "layers": 4}


#: ``jax.jit(step).lower(...).as_text()`` of the kanana configuration's
#: O2 step at its rehearsal size, taken at the commit before the rotary
#: switch (PR 31's tree) with this file's recipe
KANANA_STEP_SHA256 = \
    "3157298fd705882bf23f003312f23db66e04cd68d38e3f6dade38a82554f98a5"


def test_the_rotary_switch_left_alone_leaves_the_kanana_step_as_it_was():
    """``LatentAttention`` gained ``mla_use_nope`` and the block's second
    half moved into a function both models call: with the switch
    untouched the kanana step lowers to the same text, operation for
    operation, as before either."""
    from benchmark.families import deepseek_v3 as kanana
    with open(REPO / "benchmark/configs/kanana-2-30b-a3b-instruct-2601.json"
              ) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"])
    params = weights.make(kanana.reference.param_spec(cfg),
                          weights.seed_key(11))
    ids = jnp.asarray(kanana.make_batch(np.random.default_rng(5), 2, cfg,
                                        {"seq": 32})[0])
    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O2", verbosity=0)
    step = jax.jit(amp.make_train_step(a, kanana.program_loss(cfg,
                                                              {"seq": 32})))
    text = step.lower(a.init(params), ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == KANANA_STEP_SHA256
