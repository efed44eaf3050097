"""amp × Reducer grad-accumulation cadence on the 8-device CPU mesh
(VERDICT r2 item 6).

The reference's ``Reducer`` (``apex/parallel/distributed.py:94-131``) is the
manual-trigger reduction: users accumulate local grads for N micro-batches
and call ``reducer.reduce`` only on the boundary iteration, under amp's
scaled-loss loop.  Here the same cadence is expressed two ways — the manual
per-micro loop (stashed grads, one reduce, one ``apply_gradients``) and the
compiled ``make_train_step(accum_steps=N, reduce_fn=reducer.reduce)`` — and
both must match the plain every-step DDP run on the equivalent big batch.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models.mlp import MLP, cross_entropy_loss
from apex_tpu.parallel import Reducer, data_parallel_mesh, pvary_params
from jax import shard_map

WORLD = 8
N_MICRO = 2
BATCH = 4          # per-rank, per-micro
DIM, CLASSES = 8, 4
LR = 0.05


@pytest.fixture(scope="module")
def mesh():
    # first WORLD devices only: the platform carries 16 virtual devices
    # (the disaggregated-serving fleet topology); these WORLD=8-shaped
    # tests keep their original 8-wide mesh
    return data_parallel_mesh(num_devices=WORLD)


def _invariant_step(step):
    """Per-rank metrics (the local loss) are device-varying; pmean them
    so the shard_map out_specs can be fully replicated."""
    def wrapped(state, xr, yr):
        new_state, m = step(state, xr, yr)
        m = dict(m, loss=jax.lax.pmean(m["loss"], "data"))
        return new_state, m
    return wrapped


def _setup(seed=0):
    model = MLP(features=(16, CLASSES))
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, DIM)))["params"]
    rng = np.random.RandomState(seed)
    x = jnp.asarray(
        rng.randn(WORLD * N_MICRO * BATCH, DIM).astype(np.float32))
    y = jnp.asarray(rng.randint(0, CLASSES, WORLD * N_MICRO * BATCH))
    a = amp.initialize(optimizer=optax.sgd(LR), opt_level="O2",
                       verbosity=0)
    def loss_fn(p, xb, yb):
        return cross_entropy_loss(model.apply({"params": p}, xb), yb)
    return a, params, x, y, loss_fn


def test_manual_reducer_cadence_matches_big_batch(mesh):
    """N_MICRO stashed micro-grads per rank, ONE reducer.reduce at the
    boundary, one apply_gradients — vs the single big-batch step whose
    loss is the mean of the per-micro means.  The manual path is the
    reference's steady-state Reducer loop under amp."""
    a, params, x, y, loss_fn = _setup()
    reducer = Reducer(axis_name="data")
    state0 = a.init(params)

    def manual(state, xr, yr):
        # xr: (N_MICRO*BATCH, DIM) on this rank
        params_c = pvary_params(a.model_params(state), "data")
        sstate = state.scaler_states[0]
        accum = None
        for i in range(N_MICRO):
            xb = xr[i * BATCH:(i + 1) * BATCH]
            yb = yr[i * BATCH:(i + 1) * BATCH]
            # a.run mirrors make_train_step's input casting (batch ->
            # bf16 under O2)
            g = jax.grad(lambda p: a.scale_loss(
                a.run(loss_fn, p, xb, yb) / N_MICRO, state))(params_c)
            if accum is None:
                accum, _ = a.scaler.unscale(g, sstate)
            else:
                accum, _ = a.scaler.unscale_with_stashed(g, accum, sstate)
        # boundary iteration: the ONE collective of the cadence
        reduced = reducer.reduce(accum)
        # grads are already unscaled: feed them as the stash with a zero
        # fresh-grad tree so apply_gradients' unscale adds nothing
        zeros = jax.tree.map(jnp.zeros_like, reduced)
        new_state, info = a.apply_gradients(state, zeros,
                                            stashed_grads=reduced)
        return new_state, info["overflow"]

    step = jax.jit(shard_map(
        manual, mesh=mesh,
        in_specs=(P(), P("data"), P("data")), out_specs=(P(), P())))
    acc_state, overflow = step(state0, x, y)
    assert not bool(overflow)

    # plain DDP big-batch reference: every-step reduce, same global batch
    big = jax.jit(shard_map(
        _invariant_step(amp.make_train_step(a, loss_fn, axis_name="data")),
        mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))
    big_state, m = big(state0, x, y)
    assert not bool(m["overflow"])

    for acc, ref in zip(jax.tree.leaves(acc_state.master_params),
                        jax.tree.leaves(big_state.master_params)):
        # bf16 micro-grads round differently from the one big backward
        # (the l0 grad-accum suite observes ~2e-4 absolute)
        np.testing.assert_allclose(np.asarray(acc), np.asarray(ref),
                                   rtol=1e-3, atol=2e-4)


def test_compiled_accum_with_reducer_matches_manual(mesh):
    """make_train_step(accum_steps=N, reduce_fn=reducer.reduce): the
    delay_allreduce economics as one jit — reduction fires once on the
    accumulated grads and must land on the same masters as the manual
    cadence."""
    a, params, x, y, loss_fn = _setup(seed=1)
    reducer = Reducer(axis_name="data")
    state0 = a.init(params)

    compiled = jax.jit(shard_map(
        _invariant_step(amp.make_train_step(
            a, loss_fn, axis_name="data", reduce_fn=reducer.reduce,
            accum_steps=N_MICRO)),
        mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))
    comp_state, m = compiled(state0, x, y)
    assert not bool(m["overflow"])

    big = jax.jit(shard_map(
        _invariant_step(amp.make_train_step(a, loss_fn, axis_name="data")),
        mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))
    big_state, _ = big(state0, x, y)

    for acc, ref in zip(jax.tree.leaves(comp_state.master_params),
                        jax.tree.leaves(big_state.master_params)):
        np.testing.assert_allclose(np.asarray(acc), np.asarray(ref),
                                   rtol=1e-3, atol=5e-5)


def test_reducer_cadence_overflow_on_one_rank_skips_globally(mesh):
    """An inf in one rank's micro-batch 0 must poison the reduced grads
    everywhere (inf rides the all-reduce) and skip the step globally —
    the amp x Reducer failure-detection composition."""
    a, params, x, y, loss_fn = _setup(seed=2)
    reducer = Reducer(axis_name="data")
    state0 = a.init(params)
    x_bad = x.at[0, 0].set(jnp.inf)      # rank 0, micro 0

    compiled = jax.jit(shard_map(
        _invariant_step(amp.make_train_step(
            a, loss_fn, axis_name="data", reduce_fn=reducer.reduce,
            accum_steps=N_MICRO)),
        mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P())))
    new_state, m = compiled(state0, x_bad, y)
    assert bool(m["overflow"])
    for old, new in zip(jax.tree.leaves(state0.master_params),
                        jax.tree.leaves(new_state.master_params)):
        np.testing.assert_array_equal(np.asarray(old), np.asarray(new))
    assert float(new_state.scaler_states[0].loss_scale) == 2.0 ** 15
