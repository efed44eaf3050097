"""amp + DDP master-param consistency (port of
``tests/distributed/amp_master_params/``): after O2 DDP training, every
rank holds identical params, and the half model params equal the fp32
masters cast to half.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models.mlp import MLP, cross_entropy_loss
from apex_tpu.parallel import DistributedDataParallel, data_parallel_mesh
from jax import shard_map

WORLD = 8


@pytest.fixture(scope="module")
def mesh():
    # first WORLD devices only: the platform carries 16 virtual devices
    # (the disaggregated-serving fleet topology); these WORLD=8-shaped
    # tests keep their original 8-wide mesh
    return data_parallel_mesh(num_devices=WORLD)


def test_master_and_model_params_consistent_across_ranks(mesh):
    model = MLP(features=(32,))
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16)))["params"]
    a = amp.initialize(optimizer=optax.sgd(0.1), opt_level="O2",
                       verbosity=0)
    state = a.init(params)
    ddp = DistributedDataParallel(axis_name="data")
    inner = amp.make_train_step(
        a, lambda p, x, y: cross_entropy_loss(
            model.apply({"params": p}, x), y),
        axis_name="data", reduce_fn=ddp.reduce)

    def sharded(s, x, y):
        s2, m = inner(s, x, y)
        return s2, jax.lax.pmean(m["loss"], "data")

    step = jax.jit(shard_map(
        sharded, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))

    # rank-varying shards (the reference runs different data per rank)
    x = jax.random.normal(jax.random.PRNGKey(1), (WORLD * 8, 16))
    y = jax.random.randint(jax.random.PRNGKey(2), (WORLD * 8,), 0, 10)
    for _ in range(5):
        state, _ = step(state, x, y)

    # 1) masters stay fp32 and are replicated: every device shard equal
    #    (reference compare.py: rank0 == rank1)
    for leaf in jax.tree.leaves(state.master_params):
        assert leaf.dtype == jnp.float32
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)

    # 2) model params == masters cast to half (reference:
    #    model == master.half())
    model_p = a.model_params(state)
    for mp, ms in zip(jax.tree.leaves(model_p),
                      jax.tree.leaves(state.master_params)):
        assert mp.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(mp), np.asarray(ms.astype(jnp.bfloat16)))
