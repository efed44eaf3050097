"""FusedLayerNorm tests.

Port of ``tests/L0/run_fused_layer_norm/test_fused_layer_norm.py:9-41``
(fused output vs reference path, affine and not) extended with gradient
checks and pallas(interpret)-vs-jnp conformance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import (
    FusedLayerNorm,
    fused_layer_norm,
    fused_layer_norm_affine,
)


def ref_layer_norm(x, w, b, nshape, eps=1e-5):
    n2 = int(np.prod(nshape))
    x32 = np.asarray(x, np.float32).reshape(-1, n2)
    mean = x32.mean(1, keepdims=True)
    var = x32.var(1, keepdims=True)
    y = (x32 - mean) / np.sqrt(var + eps)
    if w is not None:
        y = y * np.asarray(w, np.float32).reshape(1, n2)
    if b is not None:
        y = y + np.asarray(b, np.float32).reshape(1, n2)
    return y.reshape(x.shape)


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
@pytest.mark.parametrize("affine", [False, True])
@pytest.mark.parametrize("shape,nshape", [((16, 32, 256), (256,)),
                                          ((8, 100), (100,)),
                                          ((4, 2, 3, 128), (128,))])
def test_forward_matches_reference(monkeypatch, mode, affine, shape, nshape):
    monkeypatch.setenv("APEX_TPU_KERNELS", mode)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    w = jnp.asarray(rng.rand(*nshape).astype(np.float32)) if affine else None
    b = jnp.asarray(rng.randn(*nshape).astype(np.float32)) if affine else None
    y = fused_layer_norm_affine(x, w, b, nshape)
    np.testing.assert_allclose(np.asarray(y), ref_layer_norm(x, w, b, nshape),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
def test_gradients_match_reference(monkeypatch, mode):
    monkeypatch.setenv("APEX_TPU_KERNELS", mode)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(160, 256).astype(np.float32))
    w = jnp.asarray(1.0 + 0.1 * rng.randn(256).astype(np.float32))
    b = jnp.asarray(0.1 * rng.randn(256).astype(np.float32))

    def fused_loss(x, w, b):
        return jnp.sum(jnp.sin(fused_layer_norm_affine(x, w, b, (256,))))

    def ref_loss(x, w, b):
        x32 = x.astype(jnp.float32)
        mean = x32.mean(-1, keepdims=True)
        var = x32.var(-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
        return jnp.sum(jnp.sin(y))

    gf = jax.grad(fused_loss, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
def test_bf16_input_fp32_stats(monkeypatch, mode):
    monkeypatch.setenv("APEX_TPU_KERNELS", mode)
    rng = np.random.RandomState(2)
    # large offset: fp32 stats keep precision where bf16 stats would not.
    # Reference runs on the SAME bf16-quantized input so only the stat/output
    # precision is under test, not input rounding.
    x = jnp.asarray((100.0 + rng.randn(64, 128)).astype(np.float32))
    xbf = x.astype(jnp.bfloat16)
    y_ref = fused_layer_norm(xbf.astype(jnp.float32), (128,))
    ybf = fused_layer_norm(xbf, (128,))
    assert ybf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ybf, np.float32),
                               np.asarray(y_ref), atol=0.05)


def test_module_api():
    m = FusedLayerNorm(normalized_shape=64)
    x = jnp.ones((4, 64))
    variables = m.init(jax.random.PRNGKey(0), x)
    assert variables["params"]["scale"].shape == (64,)
    assert variables["params"]["bias"].shape == (64,)
    y = m.apply(variables, x)
    # ones input → zero centered → y == bias == 0
    np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-5)

    m2 = FusedLayerNorm(normalized_shape=64, elementwise_affine=False)
    v2 = m2.init(jax.random.PRNGKey(0), x)
    assert "params" not in v2 or not v2["params"]


def test_rejects_bad_trailing_shape():
    x = jnp.ones((4, 32))
    with pytest.raises(AssertionError):
        fused_layer_norm(x, (64,))


def test_kernel_backward_types_with_replicated_weight_under_shard_map(
        monkeypatch):
    """Rows that vary over a mesh axis, a weight that does not (sequence
    parallelism): the kernel path's custom_vjp must hand back a weight
    cotangent summed over that axis.  Trace-only — the CPU tier cannot
    run Mosaic; the numbers are tests/distributed/
    test_onchip_pallas_shardmap.py's on hardware."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.ops.pallas import layer_norm_kernels as lnk
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    monkeypatch.setattr(lnk, "on_tpu", lambda: True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("seq",))
    x = jnp.ones((2, 512, 256), jnp.bfloat16)
    w = jnp.ones((256,), jnp.bfloat16)
    b = jnp.zeros((256,), jnp.bfloat16)

    def loss(w, b, x):
        def inner(w, b, x):
            y = fused_layer_norm_affine(x, w, b, 256)
            return jax.lax.psum(jnp.sum(y.astype(jnp.float32)), "seq")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(), P(), P(None, "seq")),
                         out_specs=P())(w, b, x)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        w, b, x).lower(lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "layer_norm_bwd"' in text
    assert "all_reduce" in text


# ---------------------------------------------------------------------------
# FusedRMSNorm: the LayerNorm kernels in their rms mode
# ---------------------------------------------------------------------------

def ref_rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
            * w.astype(jnp.float32))


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
@pytest.mark.parametrize("shape", [(16, 32, 256), (8, 100), (3, 130, 128)])
def test_rms_forward_matches_reference(monkeypatch, mode, shape):
    from apex_tpu.normalization import fused_rms_norm_affine
    monkeypatch.setenv("APEX_TPU_KERNELS", mode)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(*shape).astype(np.float32)) + 0.5
    w = jnp.asarray(rng.rand(shape[-1]).astype(np.float32))
    y = fused_rms_norm_affine(x, w, shape[-1], 1e-6)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref_rms_norm(x, w, 1e-6)),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("mode", ["jnp", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_gradients_match_reference(monkeypatch, mode, dtype):
    """dx and the gain's gradient through the kernel's backward in rms
    mode (no mean comes back) against autodiff of the jnp formula; the
    mean of x is far from nought so that a centred norm would differ."""
    from apex_tpu.normalization import fused_rms_norm_affine
    monkeypatch.setenv("APEX_TPU_KERNELS", mode)
    rng = np.random.RandomState(1)
    x = (jnp.asarray(rng.randn(6, 50, 256).astype(np.float32)) + 1.0
         ).astype(dtype)
    w = jnp.asarray(1.0 + 0.1 * rng.randn(256).astype(np.float32))
    t = jnp.asarray(rng.randn(6, 50, 256).astype(np.float32))

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32) * t)

    got = jax.grad(loss(lambda x, w: fused_rms_norm_affine(x, w, 256, 1e-6)),
                   argnums=(0, 1))(x, w)
    want = jax.grad(loss(lambda x, w: ref_rms_norm(x, w, 1e-6).astype(dtype)),
                    argnums=(0, 1))(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol * 10)


def test_rms_module_has_one_gain_and_no_bias():
    from apex_tpu.normalization import FusedRMSNorm
    m = FusedRMSNorm(128, eps=1e-6)
    x = jnp.ones((2, 4, 128)) * 3.0
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    assert set(params) == {"scale"} and params["scale"].shape == (128,)
    np.testing.assert_allclose(np.asarray(m.apply({"params": params}, x)),
                               1.0, rtol=1e-5)


def test_rms_is_a_mode_of_the_layer_norm_kernels(monkeypatch):
    """RMS is a mode of ``layer_norm_fwd`` / ``layer_norm_bwd``, not a
    kernel of its own: forward and backward name those two kernels and
    no other."""
    import re
    from apex_tpu.normalization import fused_rms_norm_affine
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    x, w = jnp.ones((64, 256)), jnp.ones((256,))
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(fused_rms_norm_affine(x, w, 256, 1e-6)),
        argnums=(0, 1)))(x, w))
    names = set(re.findall(r"name=(\w+)\n\s+out_avals", text))
    assert {"layer_norm_fwd", "layer_norm_bwd"} <= set(
        re.findall(r"layer_norm_\w+", text))
    assert not {n for n in names if not n.startswith("layer_norm_")}, names
