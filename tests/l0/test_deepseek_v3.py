"""The DeepSeek-V3-shaped decoder against the benchmark's plain reference
(``benchmark/reference/deepseek_v3.py``, which imports nothing of
``apex_tpu``), on seeded weights at a small size: one dense and two
expert layers, 8 experts of which 4 are held, 2 a token, a sliced
vocabulary.
"""

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
from benchmark import moe_scopes, scopes, trace, weights  # noqa: E402
from benchmark.families import deepseek_v3 as family  # noqa: E402
from benchmark.reference import deepseek_v3 as reference  # noqa: E402

SEQ = 32


def config(**changes) -> dict:
    with open(REPO / "benchmark/configs/kanana-2-30b-a3b-instruct-2601.json"
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


def test_the_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmark/reference/deepseek_v3.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]


@pytest.mark.parametrize("kernels", ["jnp", "pallas"])
def test_float32_logits_loss_and_gradients_match_the_reference(
        monkeypatch, case, kernels):
    """Tight: the same float32 mathematics by two routes (the program's
    goes through the sort, the grouped product and, with ``pallas``, the
    flash, norm and megablox kernels in interpret mode)."""
    monkeypatch.setenv("APEX_TPU_KERNELS", kernels)
    cfg, params, ids = case
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, i: family.program_model(cfg).apply(
            {"params": p}, i))(params, ids)
        want = jax.jit(lambda p, i: reference.logits(p, i, cfg))(params, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)
        loss = family.program_loss(cfg, {"seq": SEQ})
        l_got, g_got = jax.jit(jax.value_and_grad(loss))(params, ids)
        l_want, g_want = jax.jit(jax.value_and_grad(
            lambda p, i: reference.block_loss(
                p, (i,), reference.totals((i,)), cfg)))(params, ids)
    np.testing.assert_allclose(float(l_got), float(l_want), rtol=1e-5)
    g_got, g_want = weights.flatten(g_got), weights.flatten(g_want)
    assert set(g_got) == set(g_want)
    for path in g_want:
        scale = float(jnp.abs(g_want[path]).max())
        np.testing.assert_allclose(
            np.asarray(g_got[path]), np.asarray(g_want[path]),
            rtol=2e-3, atol=2e-4 * scale + 1e-9, err_msg=path)
    # the correction bias moves the choice and gets no gradient
    for n in (1, 2):
        bias = f"block_{n}/router/e_score_correction_bias"
        assert float(jnp.abs(g_got[bias]).max()) == 0.0


def test_o2_loss_and_gradient_norms_stay_near_the_reference(case):
    """Under amp O2 the model and its activations are bfloat16 (8 bits
    of mantissa: 0.4% a rounding), so nothing is equal; the loss stays
    within 0.1% and each leaf's gradient norm within 5% of the float32
    reference's (a leaf whose norm is under a hundredth of the median
    is held to the median).  A token whose second and third scores lie
    within the rounding may go to another expert than in float32: the
    norms bear that, single entries would not."""
    cfg, params, ids = case
    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O2", verbosity=0)
    state = a.init(params)
    compute = a.model_params(state)
    flat = weights.flatten(compute)
    assert flat["block_1/attention/kv_norm/scale"].dtype == jnp.float32
    assert flat["block_1/ffn_norm/scale"].dtype == jnp.float32
    assert flat["final_norm/scale"].dtype == jnp.float32
    assert flat["block_1/router/kernel"].dtype == jnp.bfloat16
    assert flat["block_1/experts/gate"].dtype == jnp.bfloat16
    loss = family.program_loss(cfg, {"seq": SEQ})
    l_got, g_got = jax.jit(jax.value_and_grad(loss))(compute, ids)
    with jax.default_matmul_precision("highest"):
        l_want, g_want = jax.jit(jax.value_and_grad(
            lambda p, i: reference.block_loss(
                p, (i,), reference.totals((i,)), cfg)))(params, ids)
    assert abs(float(l_got) - float(l_want)) / float(l_want) < 1e-3
    norm = lambda t: {p: float(jnp.linalg.norm(x.astype(jnp.float32)))
                      for p, x in weights.flatten(t).items()}
    got, want = norm(g_got), norm(g_want)
    floor = float(np.median(list(want.values())))
    for path in want:
        gap = abs(got[path] - want[path]) / max(want[path], floor)
        assert gap < 0.05, (path, got[path], want[path])


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference(case):
    """Two chips that hold experts 0-3 and 4-7 of a layer: what each
    computes of it, less what both compute alike (the residual, the
    attention and the shared experts, counted once), adds up to the
    reference's uncut layer."""
    cfg, _, _ = case
    whole = config(n_routed_experts_held=8)
    full = weights.make(reference.param_spec(whole), weights.seed_key(4))
    p = full["block_1"]
    x = jax.random.normal(jax.random.PRNGKey(0), (2, SEQ, cfg["hidden_size"]))
    from apex_tpu.models.deepseek_v3 import DeepseekV3Block, DeepseekV3Config
    from apex_tpu.ops.rope import rope_tables_interleaved
    rope = rope_tables_interleaved(
        jnp.broadcast_to(jnp.arange(SEQ)[None], (2, SEQ)),
        cfg["qk_rope_head_dim"], float(cfg["rope_theta"]))
    with jax.default_matmul_precision("highest"):
        uncut = jax.jit(lambda x, p: reference.layer(x, p, whole))(x, p)
        alike = jax.jit(lambda x, p: reference.layer(
            x, p, dict(whole, routed_scaling_factor=0.0)))(x, p)
        shares = []
        for first in (0, 4):
            mine = dict(p, experts=jax.tree.map(
                lambda a: a[first:first + 4], p["experts"]))
            c = DeepseekV3Config(
                hidden_size=cfg["hidden_size"],
                num_heads=cfg["num_attention_heads"],
                moe_intermediate_size=cfg["moe_intermediate_size"],
                n_routed_experts=8, n_routed_experts_held=4,
                first_expert=first, num_experts_per_tok=2,
                kv_lora_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"])
            y, stats = jax.jit(lambda m, x, c=c: DeepseekV3Block(
                c, dense=False).apply({"params": m}, x, rope))(mine, x)
            shares.append(y - alike)
            assert 0 < int(stats["pairs"]) < 2 * SEQ * 2
    np.testing.assert_allclose(np.asarray(alike + shares[0] + shares[1]),
                               np.asarray(uncut), rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(shares[0]).max()) > 1e-3     # each share counts


def test_the_step_counts_the_pairs_it_routed_through_has_aux(case):
    """``metrics["aux"]`` of ``amp.make_train_step(has_aux=True)``: per
    expert layer the (token, expert) pairs the held experts served and
    the fullest one's load over the mean; the reference's routing on the
    same float32 weights gives the same count, so none was dropped."""
    cfg, params, ids = case
    model = family.program_model(cfg)
    from apex_tpu.models.gpt import lm_loss

    def loss_fn(p, ids):
        logits, stats = model.apply({"params": p}, ids, return_stats=True)
        return lm_loss(logits[:, :-1], ids[:, 1:]), stats

    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O0", verbosity=0)
    step = jax.jit(amp.make_train_step(a, loss_fn, has_aux=True))
    _, metrics = step(a.init(params), ids)
    aux = jax.device_get(metrics["aux"])
    assert aux["pairs"].shape == (2,) and aux["load_peak"].shape == (2,)
    assert (aux["load_peak"] >= 1.0).all()
    assert aux["windows"].shape == (2,) and (aux["windows"] >= 1).all()
    # layer 1's input does not depend on any routing: count it plainly
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"]["embedding"][ids]
        x = reference.layer(x, params["block_0"], cfg)
        p = params["block_1"]
        eps = cfg["rms_norm_eps"]
        x = x + reference.latent_attention(
            reference.rms_norm(x, p["attn_norm"], eps), p["attention"], cfg)
        _, experts = reference.routing(
            reference.rms_norm(x, p["ffn_norm"], eps), p["router"], cfg)
    assert int(aux["pairs"][0]) == int((np.asarray(experts) < 4).sum())


def test_interleaved_partial_rotary_matches_the_references():
    from apex_tpu.ops.rope import (apply_rope_interleaved,
                                   rope_tables_interleaved)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 24))
    pos = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    cos, sin = rope_tables_interleaved(pos, 8, 1e6)
    got = apply_rope_interleaved(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(got[..., :16]),
                                  np.asarray(x[..., :16]))
    np.testing.assert_allclose(
        np.asarray(got[..., 16:]),
        np.asarray(reference.rope_interleaved(x[..., 16:], 1e6)),
        rtol=1e-5, atol=1e-6)
    # the one shared key part: a head axis of 1, all of it turning
    k = x[:, :, :1, :8]
    np.testing.assert_allclose(
        np.asarray(apply_rope_interleaved(k, cos, sin)),
        np.asarray(reference.rope_interleaved(k, 1e6)), rtol=1e-5, atol=1e-6)
    # bfloat16 in, bfloat16 out, and a gradient that is the inverse turn
    g = jax.grad(lambda x: jnp.sum(apply_rope_interleaved(x, cos, sin)
                                   * got))(x)
    np.testing.assert_allclose(np.asarray(g), np.asarray(x), rtol=1e-4,
                               atol=1e-5)
    assert apply_rope_interleaved(x.astype(jnp.bfloat16), cos,
                                  sin).dtype == jnp.bfloat16


def test_scopes_agree_and_reach_the_compiled_step(case):
    """The benchmark's copy of the new scopes equals the program's; the
    compiled O2 step carries each of them, the ``mlp`` scope around every
    gated feed-forward and the module names ``scopes.block`` goes by."""
    assert moe_scopes.MOE_SCOPES == profiling.MOE_SCOPES
    assert set(scopes.BLOCK_SEGMENTS["deepseek_v3"]) == set(
        scopes.BLOCK_SEGMENTS["gpt"])
    cfg, params, ids = case
    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O2", verbosity=0)
    step = jax.jit(amp.make_train_step(
        a, family.program_loss(cfg, {"seq": SEQ})))
    names = trace.op_names(step.lower(a.init(params), ids).compile()
                           .as_text())
    paths = [set(scopes.segments(n)) for n in names.values() if n]
    for scope in profiling.MOE_SCOPES + ("mlp", "lm_loss", "lm_head",
                                         "attention", "attn_norm",
                                         "ffn_norm", "final_norm"):
        assert any(scope in p for p in paths), scope
    # the expert layer lies inside mlp, the projections inside attention
    for p in paths:
        if p & {"moe_route", "moe_dispatch", "moe_experts", "moe_shared"}:
            assert "mlp" in p, p
        if "mla_project" in p:
            assert "attention" in p, p
    blocks = {scopes.block(n, "deepseek_v3") for n in names.values()}
    assert {"head_loss", "mlp", "attention", "norm", "embed"} <= blocks


def test_flops_per_token_come_from_shapes_and_the_expected_load():
    with open(REPO / "benchmark/configs/kanana-2-30b-a3b-instruct-2601.json"
              ) as f:
        cfg = json.load(f)
    per_token = family.flops_per_token(cfg, {"seq": 8192})
    assert abs(per_token / 2.79e9 - 1.0) < 0.01           # ISSUE 27's count
    a = family.attention(cfg, {"seq": 8192})
    assert a["hidden"] == 32 * 160 and a["layers"] == 5
    n = sum(int(np.prod(shape)) for shape, _ in
            reference.param_spec(cfg).values())
    assert abs(n / 576e6 - 1.0) < 0.01
