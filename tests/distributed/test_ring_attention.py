"""Sequence-parallel attention tests on the 8-device CPU mesh.

Exactness contract: ring/ulysses attention over a sequence sharded across
the mesh must equal full single-device attention to float tolerance —
including causal masking, key padding masks, and gradients.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.attention import attention, ring_attention, ulysses_attention
from apex_tpu.parallel import data_parallel_mesh
from jax import shard_map

WORLD = 8
B, L, H, D = 2, 64, 8, 16   # L/W = 8 per device


@pytest.fixture(scope="module")
def mesh():
    # first 8 devices of the 16-device test platform (L/W = 8/device)
    return data_parallel_mesh(num_devices=8)


def _qkv(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    mk = lambda k: jax.random.normal(k, (B, L, H, D), dtype)
    return mk(ks[0]), mk(ks[1]), mk(ks[2])


def _reference(q, k, v, causal=False, kv_mask=None):
    # Pin the oracle to the jnp path: on hardware the auto-dispatching
    # attention() would route to the Pallas flash kernel, making this a
    # kernel-vs-kernel comparison instead of kernel-vs-jnp.
    return attention(q, k, v, axis_name=None, impl="jnp", causal=causal,
                     kv_mask=kv_mask)


def _run_sharded(mesh, fn, q, k, v, kv_mask=None):
    in_specs = [P(None, "data"), P(None, "data"), P(None, "data")]
    args = [q, k, v]
    if kv_mask is not None:
        in_specs.append(P(None, "data"))
        args.append(kv_mask)
    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=P(None, "data")))(*args)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_full_attention(mesh, causal):
    q, k, v = _qkv()
    want = _reference(q, k, v, causal=causal)
    got = _run_sharded(
        mesh, lambda q, k, v: ring_attention(q, k, v, "data",
                                             causal=causal), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(mesh, causal):
    q, k, v = _qkv(1)
    want = _reference(q, k, v, causal=causal)
    got = _run_sharded(
        mesh, lambda q, k, v: ulysses_attention(q, k, v, "data",
                                                causal=causal), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_with_key_padding_mask(mesh):
    q, k, v = _qkv(2)
    mask = jnp.asarray(np.random.RandomState(0).rand(B, L) > 0.3)
    want = _reference(q, k, v, kv_mask=mask)
    got = _run_sharded(
        mesh, lambda q, k, v, m: ring_attention(q, k, v, "data",
                                                kv_mask=m),
        q, k, v, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fully_masked_rows_are_zero(mesh):
    q, k, v = _qkv(3)
    mask = jnp.zeros((B, L), bool)
    got = _run_sharded(
        mesh, lambda q, k, v, m: ring_attention(q, k, v, "data",
                                                kv_mask=m),
        q, k, v, kv_mask=mask)
    assert bool(jnp.isfinite(got).all())


@pytest.mark.slow
def test_ring_gradients_match(mesh):
    """Differentiated OUTSIDE the shard_map (the replicated-scalar-loss
    form, like the flash-grad test below): grad-of-psum placed inside
    the region is a jax-version semantic (legacy shard_map transposes
    it to a W-times-counted cotangent; the VMA API doesn't), while this
    form pins the package contract — ring backward == full-attention
    backward — identically on both."""
    q, k, v = _qkv(4)

    def sharded_loss(q, k, v):
        def inner(q, k, v):
            o = ring_attention(q, k, v, "data", causal=True)
            return jax.lax.psum(jnp.sum(o.astype(jnp.float32) ** 2),
                                "data")
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(None, "data"),) * 3, out_specs=P())(q, k, v)

    def loss_ref(q, k, v):
        o = _reference(q, k, v, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    got = jax.grad(sharded_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def test_ring_bf16_inputs(mesh):
    q, k, v = _qkv(5, jnp.bfloat16)
    want = _reference(q, k, v)
    got = _run_sharded(
        mesh, lambda q, k, v: ring_attention(q, k, v, "data"), q, k, v)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0.05, atol=0.05)


def test_ulysses_rejects_bad_head_count(mesh):
    q = k = v = jnp.zeros((B, L, 4, D))  # 4 heads, 8 devices
    with pytest.raises(Exception):
        _run_sharded(mesh,
                     lambda q, k, v: ulysses_attention(q, k, v, "data"),
                     q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_blocks_match_reference(mesh, causal):
    """Ring with the flash block engine == full jnp attention.

    On this CPU mesh the engine transparently substitutes its equivalent
    jnp math (interpret-mode pallas under shard_map trips a jax VMA
    limitation), so this pins the ring merge algebra — the branch
    selection, logsumexp-weighted merge, and masked-row conventions.  The
    compiled kernel-under-shard_map path is covered on hardware by
    test_ring_flash_kernel_on_tpu."""
    q, k, v = _qkv(5)
    want = _reference(q, k, v, causal=causal)
    got = _run_sharded(
        mesh, lambda q, k, v: ring_attention(q, k, v, "data",
                                             causal=causal, impl="flash"),
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_blocks_with_mask(mesh):
    q, k, v = _qkv(6)
    rng = np.random.RandomState(6)
    mask = jnp.asarray(rng.rand(B, L) > 0.3).at[:, 0].set(True)
    want = _reference(q, k, v, kv_mask=mask)
    got = _run_sharded(
        mesh, lambda q, k, v, m: ring_attention(q, k, v, "data",
                                                kv_mask=m, impl="flash"),
        q, k, v, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ring_flash_gradients_match_reference(mesh):
    """Gradients through the flash-block ring merge (differentiable lse).
    On CPU the jnp block engine stands in; the kernel dlse term is pinned
    by test_ring_flash_kernel_on_tpu on hardware."""
    q, k, v = _qkv(7)

    def sharded_loss(q, k, v):
        def inner(q, k, v):
            o = ring_attention(q, k, v, "data", causal=True, impl="flash")
            return jax.lax.psum(jnp.sum(jnp.sin(o)), "data")
        return shard_map(
            inner, mesh=mesh, in_specs=(P(None, "data"),) * 3,
            out_specs=P())(q, k, v)

    def ref_loss(q, k, v):
        return jnp.sum(jnp.sin(_reference(q, k, v, causal=True)))

    g = jax.grad(sharded_loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_flash_blocks_match_reference(mesh):
    """impl="flash" forces the flash branch (its all_to_all layout swap);
    on this CPU mesh the engine substitutes equivalent jnp math, as in the
    ring flash tests."""
    q, k, v = _qkv(8)
    want = _reference(q, k, v, causal=True)
    got = _run_sharded(
        mesh, lambda q, k, v: ulysses_attention(q, k, v, "data",
                                                causal=True, impl="flash"),
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.skipif(jax.default_backend() == "cpu",
                    reason="compiled pallas under shard_map needs hardware")
def test_ring_flash_kernel_on_tpu():
    """Mosaic-compiled flash kernel inside shard_map on a 1-device mesh:
    exercises the vma-tagged out_shapes and the kernel dlse backward."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 4, 64),
                          jnp.float32)

    def run(qq):
        return shard_map(
            lambda q: ring_attention(q, q, q, "data", causal=True,
                                     impl="flash"),
            mesh=mesh, in_specs=(P(None, "data"),),
            out_specs=P(None, "data"))(qq)

    out = jax.jit(run)(q)
    ref = _reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    g = jax.grad(lambda q: jnp.sum(jax.jit(run)(q).astype(jnp.float32)))(q)
    assert bool(jnp.isfinite(g).all())


def test_ring_flash_kernel_backward_types_under_shard_map(monkeypatch, mesh):
    """The flash custom_vjp's zero cotangents (bias, rope tables) carry
    their primals' varying-axes types: the ring's backward through the
    KERNEL path traces and lowers for TPU under shard_map.  Trace-only
    (the CPU tier cannot run Mosaic); the numbers are
    test_ring_flash_kernel_on_tpu's on hardware, where this was first
    met (PR 21)."""
    from apex_tpu.ops.pallas import flash_attention as fa
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    q, _, _ = _qkv(11)

    def run(qq):
        return shard_map(
            lambda q: ring_attention(q, q, q, "data", causal=True,
                                     impl="flash"),
            mesh=mesh, in_specs=(P(None, "data"),),
            out_specs=P(None, "data"))(qq)

    grad = jax.jit(jax.grad(
        lambda q: jnp.sum(run(q).astype(jnp.float32))))
    text = grad.trace(q).lower(lowering_platforms=("tpu",)).as_text()
    assert 'kernel_name = "flash_fwd"' in text
    assert 'kernel_name = "flash_bwd' in text


@pytest.mark.parametrize("impl", ["jnp", "flash", "ring", "ulysses"])
def test_dispatcher_forwards_impl_with_axis(mesh, impl):
    """attention() with an axis_name accepts every impl: ring/ulysses
    dispatch their path, flash/jnp select the ring block engine."""
    q, k, v = _qkv(9)
    want = _reference(q, k, v, causal=True)
    got = _run_sharded(
        mesh, lambda q, k, v: attention(q, k, v, axis_name="data",
                                        impl=impl, causal=True), q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_dispatcher_rejects_unknown_impl():
    q = jnp.zeros((1, 8, 2, 4))
    with pytest.raises(ValueError):
        attention(q, q, q, impl="flsah")
    with pytest.raises(ValueError):
        attention(q, q, q, axis_name="data", impl="flsah")
