"""Multi-head BLHD-native flash kernel + head-major layout conformance.

``flash_mh`` is kept as a documented experiment (measured slower than
the BHLD kernel on v5e — see its module docstring); its numerics stay
pinned here.  The production head-major pieces — ``flash_attention(
layout="bhld")``, the ``_QKVProj``/``_OutProj`` Dense-compatible
projections, and the MXU rope spelling — are what BERT's fast path
runs, and they are pinned against the reference spellings exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas.flash_attention import _jnp_attention, \
    flash_attention
from apex_tpu.ops.pallas.experimental.flash_mh import flash_attention_mh

B, L, H, D = 2, 256, 4, 64
SCALE = 1.0 / 8.0
# On real hardware the MXU computes fp32 dots via bf16 passes (default
# precision); interpret mode on CPU is exact fp32 — same tolerance split
# as tests/l0/test_flash_attention.py.
_ON_CPU = jax.default_backend() == "cpu"
RTOL = 2e-5 if _ON_CPU else 2e-2
ATOL = 2e-5 if _ON_CPU else 2e-2


def _qkv(l=L, seed=0):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (B, l, H, D), jnp.float32),
            jax.random.normal(kk, (B, l, H, D), jnp.float32),
            jax.random.normal(kv, (B, l, H, D), jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.experimental
def test_mh_forward_matches_reference(causal):
    q, k, v = _qkv()
    out, lse = flash_attention_mh(q, k, v, causal=causal, block_q=128,
                                  block_k=128, return_lse=True)
    ref, rlse = _jnp_attention(q, k, v, causal=causal, kv_mask=None,
                               scale=SCALE, return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(rlse),
                               rtol=RTOL, atol=ATOL)


@pytest.mark.slow
@pytest.mark.experimental
def test_mh_padded_mask_and_grads():
    q, k, v = _qkv(l=200, seed=1)          # padding active
    mask = jnp.asarray(np.random.RandomState(1).rand(B, 200) > 0.2
                       ).at[:, 0].set(True)

    def loss(f):
        return lambda q, k, v: jnp.sum(
            f(q, k, v, kv_mask=mask, block_q=128, block_k=128) ** 2)

    got = jax.grad(loss(flash_attention_mh), (0, 1, 2))(q, k, v)
    want = jax.grad(
        lambda q, k, v: jnp.sum(_jnp_attention(
            q, k, v, causal=False, kv_mask=mask, scale=SCALE) ** 2),
        (0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=max(RTOL, 1e-4),
                                   atol=max(ATOL, 1e-4))


def test_bhld_layout_matches_blhd():
    """flash_attention(layout='bhld') == the blhd result transposed —
    forward, lse, and gradients (the production head-major path)."""
    q, k, v = _qkv(seed=2)
    qh, kh, vh = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
    out_b = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    out_h, lse_h = flash_attention(qh, kh, vh, causal=True, block_q=128,
                                   block_k=128, layout="bhld",
                                   return_lse=True)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(out_h, 1, 2)),
                               np.asarray(out_b), rtol=1e-6, atol=1e-6)
    _, lse_b = flash_attention(q, k, v, causal=True, block_q=128,
                               block_k=128, return_lse=True)
    np.testing.assert_array_equal(np.asarray(lse_h), np.asarray(lse_b))

    g_b = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128) ** 2))(q)
    g_h = jax.grad(lambda qh: jnp.sum(flash_attention(
        qh, kh, vh, causal=True, block_q=128, block_k=128,
        layout="bhld") ** 2))(qh)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(g_h, 1, 2)),
                               np.asarray(g_b), rtol=1e-6, atol=1e-6)


def test_attention_dispatcher_bhld_routes_and_falls_back():
    """attention(layout='bhld'): honors impl='jnp' (head-major in/out via
    the jnp math), rejects sequence-parallel axes, and matches the blhd
    dispatch numerically."""
    from apex_tpu.attention import attention
    q, k, v = _qkv(seed=5)
    qh, kh, vh = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
    out_jnp = attention(qh, kh, vh, impl="jnp", causal=True,
                        layout="bhld")
    want = attention(q, k, v, impl="jnp", causal=True)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(out_jnp, 1, 2)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    out_auto = attention(qh, kh, vh, causal=True, layout="bhld")
    # auto-dispatch hits the Pallas kernel on hardware: platform tols
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(out_auto, 1, 2)),
                               np.asarray(want), rtol=max(RTOL, 1e-4),
                               atol=max(ATOL, 1e-4))
    with pytest.raises(ValueError, match="bhld"):
        attention(qh, kh, vh, axis_name="seq", layout="bhld")


def test_bhld_cross_attention_falls_back():
    q, k, v = _qkv(seed=3)
    qh = jnp.moveaxis(q, 1, 2)
    kh = jnp.moveaxis(k, 1, 2)[:, :, :128]
    vh = jnp.moveaxis(v, 1, 2)[:, :, :128]
    out = flash_attention(qh, kh, vh, layout="bhld")
    ref = _jnp_attention(q, k[:, :128], v[:, :128], causal=False,
                         kv_mask=None, scale=SCALE)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(out, 1, 2)),
                               np.asarray(ref), rtol=RTOL, atol=ATOL)


def test_rope_mxu_matches_concat_spelling():
    from apex_tpu.models.gpt import (apply_rope, apply_rope_mxu,
                                     rope_tables)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, L, H, D), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
    cos, sin = rope_tables(positions, D, 10000.0)
    want = apply_rope(x, cos, sin)                       # (B, L, H, D)
    xh = jnp.moveaxis(x, 1, 2)
    cos_h = jnp.moveaxis(jnp.concatenate([cos, cos], -1), 1, 2)
    sin_h = jnp.moveaxis(jnp.concatenate([sin, sin], -1), 1, 2)
    got = jnp.moveaxis(apply_rope_mxu(xh, cos_h, sin_h), 1, 2)
    # exact on both backends: the rotation matmul runs precision=highest
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_head_major_projections_match_dense_split():
    """_QKVProj/_OutProj: identical params to Dense(3E)/Dense(E) and
    identical math to the split+reshape spelling — the checkpoint/param
    compatibility BERT's fast path relies on."""
    from apex_tpu.layers import Dense
    from apex_tpu.layers import HeadMajorOutProj as _OutProj, \
        HeadMajorQKVProj as _QKVProj
    E, Hh = 64, 4
    Dh = E // Hh
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, E), jnp.float32)
    proj = _QKVProj(E, Hh)
    params = proj.init(jax.random.PRNGKey(1), x)["params"]
    assert params["kernel"].shape == (E, 3 * E)
    assert params["bias"].shape == (3 * E,)
    qkv_h = proj.apply({"params": params}, x)            # (3, B, H, L, D)
    dense = Dense(3 * E)
    ref = dense.apply({"params": params}, x)             # (B, L, 3E)
    q, k, v = jnp.split(ref, 3, axis=-1)
    for i, t in enumerate((q, k, v)):
        want = jnp.moveaxis(t.reshape(2, 16, Hh, Dh), 1, 2)
        np.testing.assert_allclose(np.asarray(qkv_h[i]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    out = _OutProj(E, Hh)
    oparams = out.init(jax.random.PRNGKey(2), qkv_h[0])["params"]
    assert oparams["kernel"].shape == (E, E)
    got = out.apply({"params": oparams}, qkv_h[0])
    want = Dense(E).apply(
        {"params": oparams},
        jnp.moveaxis(qkv_h[0], 1, 2).reshape(2, 16, E))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
