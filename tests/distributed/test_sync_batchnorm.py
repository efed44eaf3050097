"""SyncBatchNorm distributed tests.

Port of ``tests/distributed/synced_batchnorm/``: the single-device unit test
against a hand-rolled reference (``single_gpu_unit_test.py:94-145``), the
sharded-batch vs whole-batch comparison (``two_gpu_unit_test.py``, here
8-way), and group sub-partitioning (``test_groups.py``) — all on the virtual
CPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.parallel import (
    SyncBatchNorm,
    create_syncbn_process_group,
    data_parallel_mesh,
    welford_parallel,
)
from jax import shard_map

WORLD = 8
TOL = dict(rtol=1e-5, atol=1e-5)  # fp32 tolerance from two_gpu_unit_test.py


@pytest.fixture(scope="module")
def mesh():
    # first 8 devices only: the platform carries 16 virtual devices
    # (the disaggregated-serving fleet topology); the process groups
    # and batch shapes here are built for an 8-wide mesh
    return data_parallel_mesh(num_devices=8)


def ref_bn(x, ch_axis=-1, eps=1e-5):
    """Hand-rolled whole-batch reference (numpy)."""
    x = np.asarray(x, np.float32)
    axes = tuple(a for a in range(x.ndim) if a != (ch_axis % x.ndim))
    mean = x.mean(axis=axes, keepdims=True)
    var = x.var(axis=axes, keepdims=True)
    return (x - mean) / np.sqrt(var + eps), mean.squeeze(), var.squeeze()


def test_local_bn_matches_reference():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 6, 6, 4).astype(np.float32))
    bn = SyncBatchNorm(use_running_average=False)
    vars_ = bn.init(jax.random.PRNGKey(0), x)
    y, updated = bn.apply(vars_, x, mutable=["batch_stats"])
    ref_y, ref_mean, ref_var = ref_bn(x)
    np.testing.assert_allclose(np.asarray(y), ref_y, **TOL)
    # running stats after one step: (1-m)*init + m*batch, unbiased var
    n = 16 * 36
    m = 0.1
    np.testing.assert_allclose(
        np.asarray(updated["batch_stats"]["mean"]), m * ref_mean, **TOL)
    np.testing.assert_allclose(
        np.asarray(updated["batch_stats"]["var"]),
        (1 - m) * 1.0 + m * ref_var * n / (n - 1), **TOL)


def test_welford_parallel_merge():
    rng = np.random.RandomState(1)
    chunks = [rng.randn(5, 3).astype(np.float32) for _ in range(4)]
    means = jnp.asarray([c.mean(0) for c in chunks])
    vars_ = jnp.asarray([c.var(0) for c in chunks])
    counts = jnp.full((4, 1), 5.0)
    mean, var = welford_parallel(means, vars_, counts)
    full = np.concatenate(chunks, 0)
    np.testing.assert_allclose(np.asarray(mean), full.mean(0), **TOL)
    np.testing.assert_allclose(np.asarray(var), full.var(0), **TOL)


def test_sharded_batch_matches_whole_batch(mesh):
    """8-way batch shard == single-process whole batch
    (two_gpu_unit_test.py generalization)."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(WORLD * 4, 5, 5, 3).astype(np.float32))

    bn_sync = SyncBatchNorm(use_running_average=False, axis_name="data")
    bn_local = SyncBatchNorm(use_running_average=False)
    vars_ = bn_local.init(jax.random.PRNGKey(0), x)

    def fwd(v, xx):
        y, upd = bn_sync.apply(v, xx, mutable=["batch_stats"])
        return y, upd["batch_stats"]

    y_sh, stats_sh = shard_map(
        fwd, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=(P("data"), P()))(vars_, x)
    y_ref, stats_ref = bn_local.apply(vars_, x, mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref), **TOL)
    np.testing.assert_allclose(
        np.asarray(stats_sh["mean"]),
        np.asarray(stats_ref["batch_stats"]["mean"]), **TOL)
    np.testing.assert_allclose(
        np.asarray(stats_sh["var"]),
        np.asarray(stats_ref["batch_stats"]["var"]), **TOL)


@pytest.mark.slow
def test_sync_bn_gradients_match_whole_batch(mesh):
    """Backward through the synced stats == whole-batch backward
    (the reference's two-stage reduce_bn/batchnorm_backward correctness)."""
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(WORLD * 2, 4, 3).astype(np.float32))
    bn_sync = SyncBatchNorm(use_running_average=False, axis_name="data")
    bn_local = SyncBatchNorm(use_running_average=False)
    vars_ = bn_local.init(jax.random.PRNGKey(0), x)

    def sharded_loss(v, xx):
        def inner(v, xb):
            y, _ = bn_sync.apply(v, xb, mutable=["batch_stats"])
            # psum the local loss so the total matches the whole-batch loss
            return jax.lax.psum(jnp.sum(jnp.sin(y)), "data")
        return shard_map(
            inner, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=P())(v, xx)

    def whole_loss(v, xx):
        y, _ = bn_local.apply(v, xx, mutable=["batch_stats"])
        return jnp.sum(jnp.sin(y))

    g_sh = jax.grad(lambda v: sharded_loss(v, x))(vars_)
    g_ref = jax.grad(lambda v: whole_loss(v, x))(vars_)
    for a, b in zip(jax.tree.leaves(g_sh), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_process_groups(mesh):
    """group_size=4 → two independent stat groups (test_groups.py)."""
    groups = create_syncbn_process_group(4, WORLD)
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(WORLD * 2, 3).astype(np.float32))
    bn = SyncBatchNorm(use_running_average=False, axis_name="data",
                       process_group=groups)
    bn_local = SyncBatchNorm(use_running_average=False)
    vars_ = bn_local.init(jax.random.PRNGKey(0), x)

    def fwd(v, xx):
        y, _ = bn.apply(v, xx, mutable=["batch_stats"])
        return y

    y = shard_map(fwd, mesh=mesh, in_specs=(P(), P("data")),
                      out_specs=P("data"))(vars_, x)
    # Each half of the batch normalized with its own group's stats.
    y_ref0, _, _ = ref_bn(np.asarray(x)[:8])
    y_ref1, _, _ = ref_bn(np.asarray(x)[8:])
    np.testing.assert_allclose(np.asarray(y)[:8], y_ref0, **TOL)
    np.testing.assert_allclose(np.asarray(y)[8:], y_ref1, **TOL)


@pytest.mark.slow
def test_process_group_gradients_match_per_group_reference(mesh):
    """Backward through GROUPED stats == per-group whole-batch backward —
    pins the hand-written grouped collectives in _bn_train_bwd (group
    all_gather+mean for mean_dy/mean_dy_xmu, full-axis psum for gw/gb)."""
    groups = create_syncbn_process_group(4, WORLD)
    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(WORLD * 2, 3).astype(np.float32))
    bn = SyncBatchNorm(use_running_average=False, axis_name="data",
                       process_group=groups)
    bn_local = SyncBatchNorm(use_running_average=False)
    vars_ = bn_local.init(jax.random.PRNGKey(0), x)

    def sharded_loss(v, xx):
        def inner(v, xb):
            y, _ = bn.apply(v, xb, mutable=["batch_stats"])
            return jax.lax.psum(jnp.sum(jnp.sin(y)), "data")
        return shard_map(inner, mesh=mesh,
                             in_specs=(P(), P("data")),
                             out_specs=P())(v, xx)

    def grouped_ref_loss(v, xx):
        # Each group is an independent whole-batch BN over its half.
        total = 0.0
        for half in (xx[:8], xx[8:]):
            y, _ = bn_local.apply(v, half, mutable=["batch_stats"])
            total = total + jnp.sum(jnp.sin(y))
        return total

    g_sh = jax.grad(lambda v: sharded_loss(v, x))(vars_)
    g_ref = jax.grad(lambda v: grouped_ref_loss(v, x))(vars_)
    for a, b in zip(jax.tree.leaves(g_sh), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_group_validation():
    with pytest.raises(ValueError):
        create_syncbn_process_group(3, WORLD)
    with pytest.raises(ValueError):
        create_syncbn_process_group(16, WORLD)
    assert create_syncbn_process_group(0, WORLD) is None


def test_eval_uses_running_stats():
    x = jnp.ones((4, 3)) * 5.0
    bn = SyncBatchNorm(use_running_average=True)
    vars_ = bn.init(jax.random.PRNGKey(0), x)
    y = bn.apply(vars_, x)
    # running mean 0, var 1 → y == x
    np.testing.assert_allclose(np.asarray(y), 5.0, rtol=1e-3)


def test_channels_first_layout():
    """The reference needed separate NCHW kernels; here channel_axis=1."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(8, 3, 6, 6).astype(np.float32))
    bn = SyncBatchNorm(use_running_average=False, channel_axis=1)
    vars_ = bn.init(jax.random.PRNGKey(0), x)
    y, _ = bn.apply(vars_, x, mutable=["batch_stats"])
    ref_y, _, _ = ref_bn(x, ch_axis=1)
    np.testing.assert_allclose(np.asarray(y), ref_y, **TOL)


def test_fp16_running_buffers():
    x = jnp.asarray(np.random.RandomState(6).randn(8, 4).astype(np.float32))
    bn = SyncBatchNorm(use_running_average=False, running_dtype=jnp.bfloat16)
    vars_ = bn.init(jax.random.PRNGKey(0), x)
    _, upd = bn.apply(vars_, x, mutable=["batch_stats"])
    assert upd["batch_stats"]["mean"].dtype == jnp.bfloat16


def test_reduce_bn_backward_blocks_match_autodiff():
    """The exported backward split (reduce_bn → batchnorm_backward,
    welford.cu:323-411) must equal autodiff's grad_input for a local BN."""
    from apex_tpu.parallel import (batchnorm_backward, batchnorm_forward,
                                   reduce_bn, welford_mean_var)
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(8, 5, 5, 3).astype(np.float32))
    w = jnp.asarray(rng.rand(3).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(3).astype(np.float32))
    dy = jnp.asarray(rng.randn(8, 5, 5, 3).astype(np.float32))
    eps = 1e-5

    def fwd(x):
        mean, var, _ = welford_mean_var(x, (0, 1, 2))
        invstd = jax.lax.rsqrt(var + eps)
        return batchnorm_forward(x, mean, invstd, w, b, -1)

    _, vjp = jax.vjp(fwd, x)
    (auto_gi,) = vjp(dy)

    mean, var, _ = welford_mean_var(x, (0, 1, 2))
    invstd = jax.lax.rsqrt(var + eps)
    mean_dy, mean_dy_xmu, gw, gb = reduce_bn(dy, x, mean, invstd, w, -1)
    gi = batchnorm_backward(dy, x, mean, invstd, w,
                            mean_dy, mean_dy_xmu, -1)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(auto_gi),
                               rtol=1e-4, atol=1e-4)

    # grad_weight / grad_bias against autodiff on (w, b) with stats fixed
    def fwd_wb(w_, b_):
        return batchnorm_forward(x, mean, invstd, w_, b_, -1)
    _, vjp_wb = jax.vjp(fwd_wb, w, b)
    auto_gw, auto_gb = vjp_wb(dy)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(auto_gw),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(auto_gb),
                               rtol=1e-4, atol=1e-4)


def test_c_last_aliases_match_generic():
    from apex_tpu.parallel import (batchnorm_forward_c_last,
                                   welford_mean_var_c_last)
    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(4, 3, 3, 5).astype(np.float32))
    mean, var, count = welford_mean_var_c_last(x)
    assert count == 4 * 9
    invstd = jax.lax.rsqrt(var + 1e-5)
    y = batchnorm_forward_c_last(x, mean, invstd, None, None)
    ref_y, _, _ = ref_bn(x)
    np.testing.assert_allclose(np.asarray(y), ref_y, **TOL)


class TestFusedBackwardFlag:
    """fused_backward=False (plain autodiff) must match the hand-written
    two-stage backward exactly in total derivative, locally and across a
    mesh axis; it is rejected with BN sub-groups (grouped gathered stats
    have no VMA-checkable transpose)."""

    def _grads(self, fused, axis_name=None):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 4, 6))
        bn = SyncBatchNorm(axis_name=axis_name, fused_backward=fused)
        v = bn.init(jax.random.PRNGKey(1), x, use_running_average=False)

        def loss(params, xin):
            def fwd(p, xb):
                y, _ = bn.apply(
                    {"params": p, "batch_stats": v["batch_stats"]}, xb,
                    use_running_average=False, mutable=["batch_stats"])
                return jnp.sum((y.astype(jnp.float32)) ** 2)
            if axis_name is None:
                return fwd(params, xin)
            mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]),
                                     (axis_name,))
            return shard_map(
                lambda p, xb: jax.lax.pmean(fwd(p, xb), axis_name),
                mesh=mesh, in_specs=(P(), P(axis_name)),
                out_specs=P())(params, xin)

        return jax.grad(loss, argnums=(0, 1))(v["params"], x)

    @pytest.mark.parametrize("axis_name", [
        None, pytest.param("data", marks=pytest.mark.slow)])
    def test_autodiff_matches_fused(self, axis_name):
        g_fused = self._grads(True, axis_name)
        g_auto = self._grads(False, axis_name)
        for a, b in zip(jax.tree.leaves(g_fused), jax.tree.leaves(g_auto)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_grouped_sync_rejects_autodiff_backward(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 4, 6))
        bn = SyncBatchNorm(axis_name="data",
                           process_group=((0, 1), (2, 3)),
                           fused_backward=False)
        v = bn.init(jax.random.PRNGKey(1), x, use_running_average=False)
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
        with pytest.raises(ValueError, match="process_group"):
            shard_map(
                lambda p, xb: bn.apply(
                    {"params": p, "batch_stats": v["batch_stats"]}, xb,
                    use_running_average=False, mutable=["batch_stats"])[0],
                mesh=mesh, in_specs=(P(), P("data")),
                out_specs=P("data"))(v["params"], x)
