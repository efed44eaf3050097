"""The cut Laguna-XS.2 configuration and its model: the contract of the
cut (published widths kept, exactly three keys cut, each beside its
published value, the deployment stated), the program against the plain
reference on seeded weights at a small size, the window's reach, the
expert shares with the shared expert counted once, the scope of the
window, this model's planted faults, and the LFM2 steps, which share
the flash kernels and lower to the text they lowered to before the
window came (``test_lfm2_moe.py`` holds the gpt2, kanana and Kimi steps
to the same text, taken before either PR)."""

import hashlib
import json
import os

import pytest

from benchmark.tests.conftest import ROOT

CELL = "laguna_xs2.lm_b1_s8192_balanced"
FILE = "benchmark/configs/laguna-xs.2.json"
#: no key of ``reduced`` may be one of these
WIDTHS = dict(hidden_size=2048, intermediate_size=8192, head_dim=128,
              moe_intermediate_size=512, shared_expert_intermediate_size=512,
              num_attention_heads=48, num_key_value_heads=8,
              num_experts=256, num_experts_per_tok=8, sliding_window=512,
              moe_routed_scaling_factor=2.5, rms_norm_eps=1e-06,
              partial_rotary_factor=0.5)
SEQ = 40


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def small(**changes) -> dict:
    cfg = load(FILE)
    cfg.update(cfg["rehearse"])
    cfg.update(changes)
    return cfg


def test_the_cut_is_three_keys_each_beside_its_published_value():
    from benchmark.reference import laguna as reference
    cfg = load(FILE)
    listed = [c for c in load("BENCHMARK.json")["configs"]
              if c["file"] == FILE]
    assert len(listed) == 1 and listed[0]["reduced"] == cfg["reduced"]
    assert listed[0]["source"] == cfg["source"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts_held",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts_held"],
            cfg["vocab_size"]) == (5, 32, 12544)
    assert cfg["published"] == {"num_hidden_layers": 40, "num_experts": 256,
                                "vocab_size": 100352}
    assert "8 chips share each layer" in cfg["deployment"]
    # the floors of a cut: a whole period (3 sliding : 1 full) and four
    # layers after the dense one, at least 8 experts held, an eighth of
    # the vocabulary or more
    assert reference.kinds(cfg) == [(False, True, 48), (True, False, 64),
                                    (True, False, 64), (True, False, 64),
                                    (False, False, 48)]
    assert cfg["num_experts_held"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    for key in ("first_layer_held", "gating", "router", "shared_expert",
                "rotary", "sliding_window", "remat", "lr", "lr_warmup_steps",
                "e_score_correction_bias", "num_experts_held"):
        assert key in cfg["assumed"], key
    assert cfg["program"]["remat"] is False
    # the rehearsal keeps the first two layers' kinds (full + dense,
    # sliding + experts), groups of 3 and 4 q heads a KV head and a
    # window shorter than its rows
    rehearsed = small()
    assert reference.kinds(rehearsed) == [(False, True, 6), (True, False, 8)]
    assert rehearsed["num_key_value_heads"] == 2
    assert rehearsed["sliding_window"] < 64


def test_no_width_differs_from_the_published_configuration():
    """The published config's widths, lists and rotary groups as they are
    in the file, each cut key beside its published value."""
    cfg = load(FILE)
    for key, value in WIDTHS.items():
        assert cfg[key] == value, key
    assert not set(cfg["reduced"]) & set(WIDTHS)
    assert cfg["layer_types"] == [
        "full_attention" if n % 4 == 0 else "sliding_attention"
        for n in range(40)]
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert cfg["num_attention_heads_per_layer"] == [
        48 if n % 4 == 0 else 64 for n in range(40)]
    assert cfg["rope_parameters"]["full_attention"] == {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
    assert cfg["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 10000,
        "partial_rotary_factor": 1}
    assert (cfg["attention_bias"], cfg["tie_word_embeddings"],
            cfg["gating"], cfg["moe_apply_router_weight_on_input"]) == (
        False, False, True, False)
    for key in cfg["reduced"]:
        assert key in cfg["published"] or key == "num_experts_held", key
    assert cfg["published"]["num_experts"] == cfg["num_experts"]


def test_the_cell_trains_under_the_expert_cells_recipe():
    cell = load("benchmark/workloads", CELL + ".json")
    assert cell["parameters"] == {
        "rows_per_chip": 1, "seq": 8192, "lr_warmup_steps": 2000,
        "balance_rate": 0.001, "warmup_steps": 8, "traced_steps": 6,
        "reference_steps": 3, "reference_block_rows": 1}
    assert load(FILE)["optimizer"]["args"]["lr"] == 0.0003


def test_parameters_and_flops_come_from_shapes():
    import numpy as np

    from benchmark import window_flops
    from benchmark.families import laguna as family
    from benchmark.reference import laguna as reference
    cfg = load(FILE)
    n = sum(int(np.prod(shape)) for shape, _ in
            reference.param_spec(cfg).values())
    # 691.6M at 32 experts held
    assert n == 691_624_960
    assert abs(n - 691.6e6) < 0.1e6
    traffic = {"seq": 8192, "rows_per_chip": 1}
    assert family.attention(cfg, traffic) == {
        "seq": 8192, "hidden": 6144, "layers": 2, "causal": True}
    assert family.windows(cfg, traffic) == {
        "seq": 8192, "window": 512, "hidden": 8192, "kv_hidden": 1024,
        "layers": 3}
    assert window_flops.mean_keys(8192, 512) == pytest.approx(496.03125)
    assert window_flops.mean_keys(100, 512) == pytest.approx(50.5)
    # 2.405 GFLOP a token at 32 experts held
    per_token = family.flops_per_token(cfg, traffic)
    assert per_token == pytest.approx(2.405e9, rel=1e-3)
    band = window_flops.train_flops_per_token(8192, 512, 8192, 3)
    assert band == pytest.approx(146.3e6, rel=1e-3)
    assert band * 8192 / 197e12 == pytest.approx(6.08e-3, rel=1e-3)
    assert window_flops.train_bytes_per_token(8192, 1024, 3) * 8192 \
        / 819e9 == pytest.approx(3.32e-3, rel=1e-3)
    assert family.expected_held_pairs(cfg, traffic) == 8192


@pytest.fixture(scope="module")
def case():
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights
    from benchmark.families import laguna as family
    from benchmark.reference import laguna as reference
    cfg = small()
    params = weights.make(reference.param_spec(cfg), weights.seed_key(11))
    ids = jnp.asarray(family.make_batch(np.random.default_rng(5), 2, cfg,
                                        {"seq": SEQ})[0])
    return cfg, params, ids


@pytest.fixture(scope="module")
def reference_readings(case):
    """The plain reference's logits, loss and gradients on ``case``,
    float32 at ``highest`` precision, taken once for both routes."""
    import jax

    from benchmark.reference import laguna as reference
    cfg, params, ids = case

    def readings(p, i):
        return reference.logits(p, i, cfg), jax.value_and_grad(
            lambda p_: reference.block_loss(
                p_, (i,), reference.totals((i,)), cfg))(p)

    with jax.default_matmul_precision("highest"):
        return jax.jit(readings)(params, ids)


@pytest.mark.parametrize("kernels", ["jnp", "pallas"])
def test_float32_logits_loss_and_gradients_match_the_reference(
        monkeypatch, case, reference_readings, kernels):
    """Tight: the same float32 mathematics by two routes (the program's
    window through the window kernels and its full layers through the
    causal ones, q and k in the rotary's lane order, under ``pallas``;
    the reference's window as a mask over blocks of queries, its rotary
    over the published lanes).  2e-4 of the largest entry, as for the
    other expert models; the per-leaf gradient norms agree within 1e-5."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import weights
    from benchmark.families import laguna as family
    monkeypatch.setenv("APEX_TPU_KERNELS", kernels)
    jax.clear_caches()
    cfg, params, ids = case
    model, loss = family.program_model(cfg), family.program_loss(
        cfg, {"seq": SEQ})

    def readings(p, i):
        return model.apply({"params": p}, i), jax.value_and_grad(loss)(p, i)

    with jax.default_matmul_precision("highest"):
        logits, got = jax.jit(readings)(params, ids)
    ref_logits, want = reference_readings
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
        np.testing.assert_allclose(jnp.linalg.norm(flat_got[path]),
                                   jnp.linalg.norm(b), rtol=1e-5,
                                   err_msg=path)


@pytest.mark.parametrize("kernels", ["jnp", "pallas"])
def test_a_sliding_layer_forgets_what_lies_a_window_back(monkeypatch, case,
                                                         kernels):
    """Changing token ``t`` moves a sliding layer's attention at ``t`` to
    ``t + W - 1`` and at no other position; a full layer's at every
    position from ``t`` on."""
    import jax
    import numpy as np

    from apex_tpu.models.laguna import LagunaAttention, rope_for
    from benchmark.families import laguna as family
    monkeypatch.setenv("APEX_TPU_KERNELS", kernels)
    cfg = family.program_model(case[0]).cfg
    w, t = cfg.sliding_window, 9
    x = jax.random.normal(jax.random.PRNGKey(0), (1, SEQ, cfg.hidden_size))
    moved = x.at[:, t].add(1.0)
    rope = rope_for(cfg, 1, SEQ)
    for sliding, reach in ((True, t + w), (False, SEQ)):
        layer = LagunaAttention(cfg, 8, sliding)
        p = layer.init(jax.random.PRNGKey(1), x, rope)
        a, b = (np.asarray(layer.apply(p, v, rope)) for v in (x, moved))
        changed = np.abs(a - b).max(axis=(0, 2)) > 1e-6
        assert not changed[:t].any() and not changed[reach:].any()
        assert changed[t:reach].all()


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """256 experts in eight shares of 32, as the deployment holds them,
    8 a token: what each share's routed experts give, with the shared
    expert counted once, adds up to what the reference gives for the
    whole layer with all 256 held and its shared expert."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.deepseek_v3 import GatedMLP, RoutedExperts, Router
    from apex_tpu.models.laguna import LagunaConfig
    from benchmark.reference import deepseek_v3 as D
    from benchmark.reference import laguna as reference
    cfg = reference.as_deepseek(small(num_experts=256, num_experts_per_tok=8))
    key = jax.random.PRNGKey(3)
    h, f, n = cfg["hidden_size"], cfg["moe_intermediate_size"], 256
    x = jax.random.normal(key, (1, SEQ, h))
    leaves = {name: 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            shape)
              for i, (name, shape) in enumerate({
                  "router": (h, n), "gate": (n, h, f), "up": (n, h, f),
                  "down": (n, f, h), "bias": (n,), "s_gate": (h, f),
                  "s_up": (h, f), "s_down": (f, h)}.items())}
    router = {"kernel": leaves["router"],
              "e_score_correction_bias": leaves["bias"]}
    shared = {k: {"kernel": leaves["s_" + k]} for k in ("gate", "up", "down")}
    with jax.default_matmul_precision("highest"):
        weights_, experts = D.routing(x, router, cfg)
        whole = D.gated(x, shared) + D.routed_experts(
            x, {k: leaves[k] for k in ("gate", "up", "down")}, weights_,
            experts, 0)
        parts = GatedMLP(h, f).apply({"params": shared}, x)
        tokens = x.reshape(-1, h)
        for first in range(0, n, 32):
            c = LagunaConfig(
                hidden_size=h, moe_intermediate_size=f, n_routed_experts=n,
                n_routed_experts_held=32, first_expert=first,
                num_experts_per_tok=8)
            routing = Router(c).apply({"params": router}, tokens)
            held = {k: leaves[k][first:first + 32]
                    for k in ("gate", "up", "down")}
            parts = parts + RoutedExperts(c).apply(
                {"params": held}, tokens, routing)[0].reshape(x.shape)
    np.testing.assert_allclose(parts, whole, rtol=0,
                               atol=1e-5 * float(jnp.abs(whole).max()))


@pytest.fixture(scope="module")
def compiled_step(case):
    """The O2 step at the small size under the Pallas kernels, its state,
    and its compiled text."""
    import jax

    from apex_tpu import amp, optimizers
    from benchmark.families import laguna as family
    cfg, params, ids = case
    prior = os.environ.get("APEX_TPU_KERNELS")
    os.environ["APEX_TPU_KERNELS"] = "pallas"
    jax.clear_caches()
    try:
        a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                           opt_level="O2", verbosity=0)
        state = a.init(params)
        step = jax.jit(amp.make_train_step(
            a, family.program_loss(cfg, {"seq": SEQ})))
        return a, state, step.lower(state, ids).compile()
    finally:
        if prior is None:
            del os.environ["APEX_TPU_KERNELS"]
        else:
            os.environ["APEX_TPU_KERNELS"] = prior
        jax.clear_caches()


def test_the_norm_gains_stay_float32(compiled_step):
    import jax.numpy as jnp

    from benchmark import weights
    a, state, _ = compiled_step
    cast = weights.flatten(a.model_params(state))
    for leaf in ("block_0/attn_norm/scale", "block_1/ffn_norm/scale",
                 "final_norm/scale"):
        assert cast[leaf].dtype == jnp.float32, leaf
    for leaf in ("block_1/attention/g_proj/kernel",
                 "block_1/attention/q_proj/kernel", "lm_head/kernel",
                 "tok_emb/embedding"):
        assert cast[leaf].dtype == jnp.bfloat16, leaf


def test_the_window_scope_agrees_and_reaches_the_compiled_step(
        compiled_step):
    """The benchmark's copy of the window's scope equals the program's;
    the compiled O2 step carries it inside the sliding layers'
    ``attention`` only, forward and backward; ``scopes.block`` finds the
    five blocks."""
    from apex_tpu.utils import profiling
    from benchmark import scopes, swa_scopes, trace
    from benchmark.families import laguna  # noqa: F401  (its blocks)
    assert swa_scopes.SWA_SCOPES == profiling.SWA_SCOPES
    text = compiled_step[2].as_text()
    names = [n for n in trace.op_names(text).values() if n]
    window = [n for n in names if "swa_window" in n.split("/")]
    assert any("transpose(" in n for n in window)
    assert any("transpose(" not in n for n in window)
    for n in window:
        parts = n.split("/")
        assert "attention" in parts
        assert "block_0" not in parts, n      # the full layer
    blocks = {scopes.block(n, "laguna") for n in names}
    assert {"head_loss", "mlp", "attention", "norm", "embed"} <= blocks


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


FAULTS = ("no_window", "no_gate", "no_yarn", "full_rotary",
          "kv_group_modulo", "no_warmup", "no_balance")


def test_the_faults_are_named():
    from benchmark import run
    _, cell, cfg, family, _ = run.resolve(CELL, rehearse=True)
    assert tuple(family.planted_faults(cfg, cell["parameters"])) == FAULTS


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_comes_out_not_correct(followed, fault):
    """The reference under the fault in the program's place, judged by the
    cell's own rehearsal limits, as ``calibrate_faults.py`` reads it on
    the chip."""
    from benchmark import run
    from benchmark.reference import train as ref
    cell, cfg, family, traffic, follow, want = followed
    gaps = ref.gaps(follow(*family.planted_faults(cfg, traffic)[fault]),
                    want)
    correct, table = run.judge({k: v for k, v in gaps.items()
                                if k.endswith("_gap")}, cell["limits"])
    assert correct is False, table
    if fault == "no_balance":           # by the number that is the rule's
        assert gaps["state_gap"] == pytest.approx(1.0, abs=1e-3), table


#: sha256 of ``jax.jit(step).lower(...).as_text()`` of the LFM2 cell's
#: step as the driver builds it, at its rehearsal size, with the kernels
#: the CPU picks and with the Pallas kernels in interpret mode, each
#: traced afresh: taken at the parent commit, before the window came
PARENT_STEPS = {
    ("auto", "lfm2_8b_a1b.lm_b2_s8192_balanced"):
        "86ba7446eeec039ac06252d0dddd0ade7975c59f13f3d9483e7eac9b5bd500ce",
    ("pallas", "lfm2_8b_a1b.lm_b2_s8192_balanced"):
        "d9e082c76c72b781f8109ece7be308b3b333da5b5877214858c3a01d83242604",
}


@pytest.mark.parametrize("kernels,cell", sorted(PARENT_STEPS))
def test_the_lfm2_steps_lower_to_the_parents_text(monkeypatch, kernels,
                                                  cell):
    import jax
    import numpy as np

    from benchmark import run, weights
    from benchmark.drivers import train
    monkeypatch.setenv("APEX_TPU_KERNELS", kernels)
    # an inner jit traced under the other kernel mode would answer from
    # its cache: the mode is read while tracing and is no part of the key
    jax.clear_caches()
    _, c, cfg, family, _ = run.resolve(cell, rehearse=True)
    made = train.make_step(c, cfg, family, jax.devices()[:1])
    state = jax.eval_shape(
        lambda k: made["a"].init(weights.make(made["spec"], k)),
        weights.seed_key(1))
    batch = family.make_batch(np.random.default_rng(0),
                              c["parameters"]["rows_per_chip"], cfg,
                              c["parameters"])
    text = jax.jit(made["step_fn"], donate_argnums=(0,)).lower(
        state, *batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_STEPS[(kernels, cell)]
