"""Pallas-under-shard_map on real hardware (VERDICT r1 item 4).

The virtual-CPU distributed tier pins ``APEX_TPU_KERNELS=jnp`` because the
interpret-mode pallas evaluator has a VMA limitation under shard_map; this
module is the hardware half of that bargain: a FULL amp-O2 training step —
packed two-stage LAMB Pallas kernels, DDP gradient reduction, dynamic loss
scaling — Mosaic-compiled inside ``shard_map`` over every chip of the
host (up to 8; the mesh axis is real on one chip too).

Run with ``APEX_TPU_TEST_PLATFORM=tpu`` (tools/onchip_run.py does, and
writes the result under ``chiprun_out/``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

pytestmark = pytest.mark.skipif(
    jax.default_backend() == "cpu",
    reason="Mosaic-compiled pallas under shard_map needs hardware")


def test_pallas_train_step_under_shard_map(monkeypatch):
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    from apex_tpu import amp
    from apex_tpu.models.mlp import MLP, cross_entropy_loss
    from apex_tpu.optimizers import FusedLAMB
    from apex_tpu.parallel import DistributedDataParallel

    n = min(len(jax.devices()), 8)
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    model = MLP(features=(128, 64))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64)))["params"]
    a = amp.initialize(optimizer=FusedLAMB(lr=1e-2), opt_level="O2",
                       verbosity=0)
    state = a.init(params)
    ddp = DistributedDataParallel(axis_name="data")

    def loss_fn(p, xb, yb):
        return cross_entropy_loss(model.apply({"params": p}, xb), yb)

    inner = amp.make_train_step(a, loss_fn, axis_name="data",
                                reduce_fn=ddp.reduce)

    def train_step(state, xb, yb):
        state, m = inner(state, xb, yb)
        return state, jax.lax.pmean(m["loss"], "data")

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))

    x = jax.random.normal(jax.random.PRNGKey(1), (16 * n, 64))
    y = (jnp.arange(16 * n) % 10).astype(jnp.int32)
    losses = []
    for _ in range(8):
        state, loss = step(state, x, y)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses


def test_pallas_multi_tensor_under_shard_map(monkeypatch):
    """The packed scale/l2norm kernels (SMEM overflow flag + per-chunk
    tables) compiled by Mosaic inside a shard_map region."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    from apex_tpu.ops.multi_tensor import (
        multi_tensor_l2norm, multi_tensor_scale)

    n = min(len(jax.devices()), 8)
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    xs = [jax.random.normal(jax.random.PRNGKey(i), (4096 + i,))
          for i in range(3)]

    def body(*ts):
        outs, flag = multi_tensor_scale(4096, [list(ts)], 0.5)
        total, per = multi_tensor_l2norm(4096, [outs], per_tensor=True)
        return total, per, flag

    f = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(), P(), P()),
        out_specs=(P(), P(), P())))
    total, per, flag = f(*xs)
    ref = np.array([np.linalg.norm(np.asarray(t) * 0.5) for t in xs])
    np.testing.assert_allclose(np.asarray(per), ref, rtol=1e-5)
    np.testing.assert_allclose(float(total), np.sqrt((ref ** 2).sum()),
                               rtol=1e-5)
    assert int(flag) == 0


def test_layer_norm_replicated_weight_grads_under_shard_map(monkeypatch):
    """Sequence parallelism hands the LayerNorm kernel rows that vary over
    a mesh axis and a weight that does not: the backward must sum the
    weight's cotangent over that axis.  First met on four chips (PR 21:
    custom_vjp rejected the varying dγ of ``examples/gpt_lm.py
    --seq-parallel``)."""
    from apex_tpu.normalization import fused_layer_norm_affine

    n = min(len(jax.devices()), 8)
    mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k[0], (2, 128 * n, 256), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(k[1], (256,), jnp.float32)
    b = 0.1 * jax.random.normal(k[2], (256,), jnp.float32)

    def loss(w, b, x):
        def inner(w, b, x):
            y = fused_layer_norm_affine(x, w, b, 256)
            return jax.lax.psum(jnp.sum(jnp.sin(y)), "seq")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(), P(), P(None, "seq")),
                         out_specs=P())(w, b, x)

    monkeypatch.setenv("APEX_TPU_KERNELS", "jnp")
    want = jax.jit(jax.grad(lambda *a: loss(*a), argnums=(0, 1, 2)))(w, b, x)
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    got = jax.jit(jax.grad(lambda *a: loss(*a), argnums=(0, 1, 2)))(w, b, x)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=1e-4)
