"""Weight-norm reparameterization tests.

The reference subsystem is import-broken (SURVEY.md §0.3) and untested;
these tests define the intended semantics (torch.nn.utils.weight_norm
behavior, per the reference docstrings in
``apex/reparameterization/__init__.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from apex_tpu.models.mlp import MLP, cross_entropy_loss
from apex_tpu.reparameterization import (
    WeightNorm,
    apply_weight_norm,
    merge,
    remove_weight_norm,
    reparameterized_apply,
)


def _params():
    model = MLP(features=(16, 16), num_classes=4)
    p = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8)))["params"]
    return model, p


def test_decomposition_shapes_and_identity():
    model, p = _params()
    pw = apply_weight_norm(p)
    # kernels (2-d) decomposed, biases (1-d) untouched
    l0 = pw["AmpDense_0"]
    assert "kernel_g" in l0 and "kernel_v" in l0 and "kernel" not in l0
    assert "bias" in l0
    # per-output-channel g: kernel (in, out) → g (1, out)
    assert l0["kernel_g"].shape == (1, 16)
    merged = merge(pw, WeightNorm())
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(merged)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_dim_none_whole_tensor_norm():
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
    wn = WeightNorm(dim=None)
    aux = wn.reparameterize("kernel", w)
    assert aux["kernel_g"].shape == (1, 1)
    np.testing.assert_allclose(float(aux["kernel_g"][0, 0]),
                               float(jnp.linalg.norm(w)), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(wn.compute_weight("kernel", aux)),
                               np.asarray(w), atol=1e-6)


def test_effective_weight_norm_equals_g():
    """After scaling g, the effective weight's per-column norm equals g
    (magnitude/direction decoupling — the point of the method)."""
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    wn = WeightNorm()
    aux = wn.reparameterize("kernel", w)
    aux["kernel_g"] = aux["kernel_g"] * 2.0
    merged = wn.compute_weight("kernel", aux)
    np.testing.assert_allclose(
        np.asarray(jnp.linalg.norm(merged, axis=0)),
        np.asarray(aux["kernel_g"][0]), rtol=1e-5)


@pytest.mark.slow
def test_gradients_flow_and_training_improves():
    model, p = _params()
    pw = apply_weight_norm(p)
    apply_wn = reparameterized_apply(model.apply, WeightNorm())

    x = jax.random.normal(jax.random.PRNGKey(3), (64, 8))
    y = (x[:, 0] > 0).astype(jnp.int32)

    def loss_fn(pw):
        return cross_entropy_loss(apply_wn({"params": pw}, x), y)

    tx = optax.sgd(0.5)
    opt = tx.init(pw)
    l0 = float(loss_fn(pw))
    g = jax.grad(loss_fn)(pw)
    # every decomposed leaf gets a gradient
    assert float(jnp.abs(g["AmpDense_0"]["kernel_g"]).sum()) > 0
    assert float(jnp.abs(g["AmpDense_0"]["kernel_v"]).sum()) > 0

    @jax.jit
    def step(pw, opt):
        grads = jax.grad(loss_fn)(pw)
        updates, opt = tx.update(grads, opt)
        return optax.apply_updates(pw, updates), opt

    for _ in range(20):
        pw, opt = step(pw, opt)
    assert float(loss_fn(pw)) < l0


def test_remove_weight_norm_roundtrip_after_training():
    model, p = _params()
    pw = apply_weight_norm(p)
    # perturb g to make the effective weight differ from the original
    pw["AmpDense_0"]["kernel_g"] = pw["AmpDense_0"]["kernel_g"] * 1.5
    plain = remove_weight_norm(pw)
    assert "kernel" in plain["AmpDense_0"]
    assert "kernel_g" not in plain["AmpDense_0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 8))
    apply_wn = reparameterized_apply(model.apply, WeightNorm())
    np.testing.assert_allclose(
        np.asarray(apply_wn({"params": pw}, x)),
        np.asarray(model.apply({"params": plain}, x)), atol=1e-5)


def test_named_leaf_restriction():
    model, p = _params()
    pw = apply_weight_norm(p, name="kernel")
    assert "kernel_v" in pw["AmpDense_0"]
    pw2 = apply_weight_norm(p, name="nonexistent")
    assert jax.tree.structure(pw2) == jax.tree.structure(
        jax.tree.map(lambda x: x, p))
