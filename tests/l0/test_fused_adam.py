"""FusedAdam conformance tests.

Port of ``tests/L0/run_mixed_adam/test_mixed_adam.py:8-179``: reference-vs-
fused param drift below 1e-3 over 7 iterations, multiple dtypes/options, and
the flat-buffer FP16Optimizer behaviors (``test_fp16_optimizer.py:33-129``)
including grad clipping and overflow skip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.optimizers import (
    FP16Optimizer,
    FusedAdam,
    adam_step,
    fused_adam,
)


def tree_randn(key, shapes):
    keys = jax.random.split(key, len(shapes))
    return {f"p{i}": jax.random.normal(k, s, jnp.float32)
            for i, (k, s) in enumerate(zip(keys, shapes))}


SHAPES = [(17,), (64, 31), (128,)]


def run_fused(params, grads_seq, **kw):
    tx = fused_adam(learning_rate=1e-3, **kw)
    state = tx.init(params)
    for g in grads_seq:
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
    return params


def run_optax(params, grads_seq, weight_decay=0.0):
    # optax adam: eps outside sqrt? optax uses eps added after sqrt -> same
    # as our EPS_MODE_OUTSIDE default.
    tx = optax.adam(1e-3, b1=0.9, b2=0.999, eps=1e-8)
    state = tx.init(params)
    for g in grads_seq:
        if weight_decay:
            g = jax.tree.map(lambda gg, p: gg + weight_decay * p, g, params)
        updates, state = tx.update(g, state, params)
        params = optax.apply_updates(params, updates)
    return params


def max_abs_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("weight_decay", [0.0, 0.01])
def test_drift_vs_reference_adam(weight_decay):
    key = jax.random.PRNGKey(0)
    params = tree_randn(key, SHAPES)
    grads_seq = [tree_randn(jax.random.PRNGKey(i + 1), SHAPES)
                 for i in range(7)]
    fused = run_fused(params, grads_seq, weight_decay=weight_decay)
    ref = run_optax(params, grads_seq, weight_decay=weight_decay)
    assert max_abs_diff(fused, ref) < 1e-3


def test_scale_descales_grads():
    params = {"w": jnp.ones((32,), jnp.float32)}
    g = {"w": jnp.full((32,), 8.0, jnp.float32)}
    a = run_fused(params, [g], scale=8.0)
    b = run_fused(params, [{"w": jnp.ones((32,), jnp.float32)}])
    assert max_abs_diff(a, b) < 1e-7


def test_eps_mode_inside():
    params = {"w": jnp.ones((16,), jnp.float32)}
    g = {"w": jnp.ones((16,), jnp.float32)}
    out_in = run_fused(params, [g], eps_inside_sqrt=True)
    out_out = run_fused(params, [g], eps_inside_sqrt=False)
    # modes differ slightly but both step in the same direction
    assert max_abs_diff(out_in, out_out) < 1e-3
    assert float(out_in["w"][0]) < 1.0 and float(out_out["w"][0]) < 1.0


@pytest.mark.parametrize("n_pads", [2, 4])
def test_adam_step_pallas_matches_jnp(monkeypatch, n_pads):
    # n_pads=2 -> 16 rows (the 8-row tile-floor blocks); n_pads=4 ->
    # 32 rows (the larger 32-row blocks) — both grid geometries pinned
    from apex_tpu.ops.pallas.adam_kernel import ADAM_PAD
    n = ADAM_PAD * n_pads
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.randn(n).astype(np.float32))
    m = jnp.asarray(rng.rand(n).astype(np.float32))
    v = jnp.asarray(rng.rand(n).astype(np.float32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
              step=jnp.asarray(3, jnp.int32), scale=2.0, weight_decay=0.01,
              p_copy_dtype=jnp.bfloat16)
    monkeypatch.setenv("APEX_TPU_KERNELS", "jnp")
    ref = adam_step(p, m, v, g, **kw)
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    got = adam_step(p, m, v, g, **kw)
    for r, o in zip(ref, got):
        assert r.dtype == o.dtype
        np.testing.assert_allclose(np.asarray(r, np.float32),
                                   np.asarray(o, np.float32),
                                   rtol=1e-5, atol=1e-6)


class TestFP16Optimizer:
    def make(self, **kw):
        params = {"a": jnp.ones((33,), jnp.float32) * 0.5,
                  "b": jnp.ones((8, 9), jnp.float32)}
        opt = FP16Optimizer(params, lr=1e-2, **kw)
        return params, opt, opt.init()

    def test_step_moves_params(self):
        params, opt, state = self.make()
        grads = jax.tree.map(lambda p: jnp.ones_like(p, jnp.bfloat16),
                             opt.model_params(state))
        state, params_half, info = opt.step(state, grads)
        assert not bool(info["overflow"])
        assert params_half["a"].dtype == jnp.bfloat16
        assert float(params_half["a"][0]) < 0.5

    def test_overflow_skips(self):
        params, opt, state = self.make(dynamic_loss_scale=True)
        before = np.asarray(state.master)
        grads = jax.tree.map(
            lambda p: jnp.full(p.shape, jnp.inf, jnp.bfloat16),
            opt.model_params(state))
        state, _, info = opt.step(state, grads)
        assert bool(info["overflow"])
        np.testing.assert_array_equal(before, np.asarray(state.master))
        assert float(state.scaler_state.loss_scale) == 2.0 ** 15
        assert int(state.step) == 0

    def test_loss_scale_descale(self):
        # grads arrive pre-scaled by the loss scale; step result must match
        # an unscaled run (fp16_optimizer.py combined_scale semantics).
        params, opt_s, state_s = self.make(static_loss_scale=4.0)
        _, opt_u, state_u = self.make(static_loss_scale=1.0)
        g = jax.tree.map(lambda p: jnp.ones_like(p, jnp.float32),
                         opt_s.model_params(state_s))
        g4 = jax.tree.map(lambda x: x * 4.0, g)
        state_s, ph_s, _ = opt_s.step(state_s, g4)
        state_u, ph_u, _ = opt_u.step(state_u, g)
        np.testing.assert_allclose(np.asarray(state_s.master),
                                   np.asarray(state_u.master), rtol=1e-6)

    def test_grad_clipping_via_combined_scale(self):
        params, opt, state = self.make(max_grad_norm=1.0)
        big = jax.tree.map(lambda p: jnp.full(p.shape, 10.0, jnp.float32),
                           opt.model_params(state))
        state2, _, info = opt.step(state, big)
        # total numel = 33 + 72 = 105; norm = 10*sqrt(105) >> 1 → clipped.
        # effective grad after clip has norm 1 → max step ~ lr
        delta = np.abs(np.asarray(state2.master) - np.asarray(state.master))
        assert delta.max() <= 1e-2 + 1e-6

    def test_state_dict_roundtrip(self):
        params, opt, state = self.make(dynamic_loss_scale=True)
        grads = jax.tree.map(lambda p: jnp.ones_like(p, jnp.bfloat16),
                             opt.model_params(state))
        state, _, _ = opt.step(state, grads)
        d = opt.state_dict(state)
        restored = opt.load_state_dict(d)
        np.testing.assert_array_equal(np.asarray(state.master),
                                      np.asarray(restored.master))
        assert float(restored.scaler_state.loss_scale) == \
            float(state.scaler_state.loss_scale)


def _bitwise_trees(kind):
    rng = np.random.RandomState(7)
    mk = lambda *s: jnp.asarray(rng.randn(*s).astype(np.float32))
    if kind == "mixed":
        params = {"w": mk(17, 9), "b": mk(33),
                  "s": jnp.asarray(0.7, jnp.float32), "t": mk(2, 3, 5)}
        grads = {"w": mk(17, 9), "b": mk(33),
                 "s": jnp.asarray(0.2, jnp.float32), "t": mk(2, 3, 5)}
        return params, grads
    # "ragged": 11 leaves -> 13 aligned chunks (one leaf spans 3), so the
    # retuned kernel's 8-chunk grid steps leave a RAGGED tail block (13 %
    # 8 = 5) riding the padded step table — plus single-tile leaves (one
    # exact chunk) and an exactly-two-chunk leaf (empty tail within the
    # leaf).  The geometry axis the round-6 retune added must stay
    # invisible to the math.
    shapes = [(1024,), (2048,), (2100,), (64,), (5,), (8, 16), (1,),
              (33,), (128,), (7, 3), (512,)]
    params = {f"p{i}": mk(*s) for i, s in enumerate(shapes)}
    grads = {f"p{i}": mk(*s) for i, s in enumerate(shapes)}
    return params, grads


@pytest.mark.parametrize("tree", ["mixed", "ragged"])
def test_packed_tree_update_bitwise_matches_per_leaf(monkeypatch, tree):
    """The whole-tree packed path (one kernel pass over the aligned pack,
    per-tensor step sizes via the chunk->tensor table) must be BIT-identical
    to the per-leaf jnp path — the L1 ext-vs-no-ext conformance contract —
    across mixed shapes, a scalar leaf, weight decay, a non-unit scale,
    and (the round-6 geometry retune) a tree whose chunk count leaves a
    ragged tail under the multi-chunk grid blocks.

    Both trees are held to ONE ULP instead of bitwise: XLA's FMA
    contraction of the final ``p - step·m/denom`` differs between the
    per-leaf fusion and the kernel graph for a handful of elements —
    a property of the two jit graphs (the ragged tree since the seed,
    the mixed tree since XLA:CPU of jax 0.9.0), not of the geometry;
    the geometry axis itself is pinned bit-exact in
    test_kernel_geometry.py::test_packed_adam_block_override_is_pure_geometry."""
    from apex_tpu.optimizers.fused_adam import fused_adam

    params, grads = _bitwise_trees(tree)
    tx = fused_adam(learning_rate=3e-3, weight_decay=0.01, scale=128.0)

    # both paths under jit: XLA's FMA contraction must apply to both or
    # neither for a bitwise comparison (training always runs jitted).
    # Distinct lambdas: jax.jit caches traces by function identity, and the
    # kernel-path choice is baked in at trace time.
    monkeypatch.setenv("APEX_TPU_KERNELS", "jnp")
    state = tx.init(params)
    u_ref, s_ref = jax.jit(lambda g, s, p: tx.update(g, s, p))(
        grads, state, params)

    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    monkeypatch.setenv("APEX_TPU_ADAM_PACKED", "1")
    # confirm the packed path actually engages (sys.modules: the package
    # attr "fused_adam" is the function, shadowing the submodule)
    import sys
    fa = sys.modules["apex_tpu.optimizers.fused_adam"]
    called = {}
    orig = fa._packed_tree_update

    def spy(*a, **k):
        called["x"] = True
        return orig(*a, **k)

    monkeypatch.setattr(fa, "_packed_tree_update", spy)
    u_got, s_got = jax.jit(lambda g, s, p: tx.update(g, s, p))(
        grads, state, params)
    assert called, "packed tree path did not engage under pallas mode"

    for r, o in zip(jax.tree.leaves((u_ref, s_ref.m, s_ref.v)),
                    jax.tree.leaves((u_got, s_got.m, s_got.v))):
        # one-ulp FMA-contraction slack (see docstring).  The slack is
        # ABSOLUTE at the O(1) param scale: the compared updates are
        # deltas (new_p - p), so a 1-ulp difference in new_p surfaces
        # as ~1e-5 RELATIVE to the small delta.
        np.testing.assert_allclose(np.asarray(r), np.asarray(o),
                                   rtol=2e-7, atol=1.2e-7)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), s_ref.leaf_step, s_got.leaf_step))
