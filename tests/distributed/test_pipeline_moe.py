"""Pipeline- and expert-parallel tests on the virtual CPU mesh.

Both modes are beyond the reference (SURVEY.md section 2: apex has no
tp/pp/sp/ep), but complete the dp/tp/pp/sp/ep surface this framework
validates multi-device (conftest: 8 virtual CPU devices)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map

from apex_tpu.parallel.pipeline import pipeline_apply, stack_stage_params
from apex_tpu.parallel.moe import (grouped_matmul, load_balance_loss,
                                   moe_apply, route)

D = 8


def _mesh(n, name):
    devs = jax.devices()[:n]
    if len(devs) < n:
        pytest.skip(f"needs {n} devices")
    return Mesh(np.array(devs), (name,))


def stage_fn(p, x):
    return jax.nn.relu(x @ p["w"] + p["b"])


def make_stage(key, d):
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (d, d)) * 0.5,
            "b": jax.random.normal(kb, (d,)) * 0.1}


class TestPipeline:
    S = 4

    def setup_method(self, _):
        keys = jax.random.split(jax.random.PRNGKey(0), self.S)
        self.stages = [make_stage(k, D) for k in keys]
        self.stacked = stack_stage_params(self.stages)
        self.x = jax.random.normal(jax.random.PRNGKey(1), (16, D))

    def reference(self, stages, x):
        h = x
        for i in range(self.S):
            h = stage_fn(jax.tree.map(lambda l: l[i], stages), h)
        return h

    @pytest.mark.parametrize("n_micro", [4, 8])
    def test_forward_matches_sequential(self, n_micro):
        mesh = _mesh(self.S, "pipe")
        f = shard_map(
            lambda sp, x: pipeline_apply(stage_fn, sp, x, "pipe",
                                         n_microbatches=n_micro),
            mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())
        y = jax.jit(f)(self.stacked, self.x)
        ref = self.reference(self.stacked, self.x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_backward_matches_sequential(self):
        mesh = _mesh(self.S, "pipe")

        def loss_pp(sp, x):
            f = shard_map(
                lambda sp, x: pipeline_apply(stage_fn, sp, x, "pipe"),
                mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())
            return jnp.mean(f(sp, x) ** 2)

        def loss_ref(sp, x):
            return jnp.mean(self.reference(sp, x) ** 2)

        g_pp = jax.jit(jax.grad(loss_pp))(self.stacked, self.x)
        g_ref = jax.grad(loss_ref)(self.stacked, self.x)
        for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_batch_divisibility_error(self):
        mesh = _mesh(self.S, "pipe")
        f = shard_map(
            lambda sp, x: pipeline_apply(stage_fn, sp, x, "pipe",
                                         n_microbatches=3),
            mesh=mesh, in_specs=(P("pipe"), P()), out_specs=P())
        with pytest.raises(ValueError, match="microbatch"):
            jax.eval_shape(f, self.stacked, self.x)


def expert_fn(p, x):
    return jax.nn.gelu(x @ p["wi"]) @ p["wo"]


def grouped_expert_fn(p, rows, group_sizes):
    """``expert_fn`` over rows sorted by expert, as ``moe_apply`` calls
    it."""
    h = jax.nn.gelu(grouped_matmul(rows, p["wi"], group_sizes))
    return grouped_matmul(h, p["wo"], group_sizes)


def make_experts(key, n, d, hidden=16):
    k1, k2 = jax.random.split(key)
    return {"wi": jax.random.normal(k1, (n, d, hidden)) * 0.3,
            "wo": jax.random.normal(k2, (n, hidden, d)) * 0.3}


ROUTERS = {"switch_top1": dict(k=1),
           "softmax_top2": dict(k=2),
           "sigmoid_top3_renormalised": dict(k=3, scoring="sigmoid",
                                             renormalize=True, scale=2.0)}


class TestMoE:
    """Experts and tokens both sharded over the ``expert`` axis: the
    rows travel to the ranks that hold their experts and back."""
    RANKS, E_LOCAL = 4, 2

    def setup_method(self, _):
        E = self.RANKS * self.E_LOCAL
        self.experts = make_experts(jax.random.PRNGKey(0), E, D)
        self.router = jax.random.normal(jax.random.PRNGKey(1), (D, E))
        # tokens: (ranks * T_local, D)
        self.x = jax.random.normal(jax.random.PRNGKey(2),
                                   (self.RANKS * 32, D))

    def exchanged(self, router, **opts):
        E = self.RANKS * self.E_LOCAL

        def layer(ep, rw, x):
            r = route(x @ rw, **opts)
            y, stats = moe_apply(grouped_expert_fn, ep, x, r, n_experts=E,
                                 axis_name="expert")
            aux = jax.lax.pmean(load_balance_loss(r), "expert")
            return y, aux, jax.lax.psum(stats["pairs"], "expert")

        f = shard_map(layer, mesh=_mesh(self.RANKS, "expert"),
                      in_specs=(P("expert"), P(), P("expert")),
                      out_specs=(P("expert"), P(), P()))
        return jax.jit(f)(self.experts, router, self.x)

    @pytest.mark.parametrize("name", sorted(ROUTERS))
    def test_matches_per_token_reference(self, name):
        """Independent semantics check (no shared routing code): y[t] is
        the sum over token t's chosen experts of weight * expert_fn(x[t]),
        for every token, whichever rank holds the expert."""
        opts = dict(ROUTERS[name])
        y, _, pairs = self.exchanged(self.router, **opts)
        assert int(pairs) == self.x.shape[0] * opts["k"]
        logits = self.x @ self.router
        scores = (jax.nn.sigmoid(logits) if opts.get("scoring") == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        for t in range(0, self.x.shape[0], 7):
            ids = np.argsort(-np.asarray(scores[t]),
                             kind="stable")[:opts["k"]]
            w = np.asarray(scores[t])[ids]
            if opts.get("renormalize"):
                w = w / w.sum()
            w = w * opts.get("scale", 1.0)
            ref = sum(float(w[j]) * expert_fn(
                jax.tree.map(lambda l: l[int(e)], self.experts),
                self.x[t][None, :])[0] for j, e in enumerate(ids))
            np.testing.assert_allclose(np.asarray(y[t]), np.asarray(ref),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("skew", ["as_drawn", "all_on_one_rank",
                                      "all_on_one_expert"])
    def test_matches_the_layer_on_one_device(self, skew):
        """The exchange reproduces the layer that holds every expert
        itself, shard of tokens by shard, however uneven the routing: a
        capacity would drop tokens under the two skews, here none is."""
        router = self.router
        if skew == "all_on_one_rank":      # experts 2 and 3, rank 1
            router = router.at[:, 2:4].add(50.0)
        if skew == "all_on_one_expert":
            router = router.at[:, 5].add(50.0)
        y, aux, pairs = self.exchanged(router, k=2)
        assert int(pairs) == self.x.shape[0] * 2
        E = self.RANKS * self.E_LOCAL
        refs = []
        for shard in self.x.reshape(self.RANKS, -1, D):
            r = route(shard @ router, k=2)
            refs.append((moe_apply(grouped_expert_fn, self.experts, shard,
                                   r, n_experts=E)[0],
                         load_balance_loss(r)))
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(jnp.concatenate([r[0] for r in refs])),
            rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            float(aux), float(jnp.mean(jnp.stack([r[1] for r in refs]))),
            rtol=1e-5)
        if skew == "all_on_one_expert":
            assert float(jnp.abs(y).sum(-1).min()) > 0   # no zero row

    def test_gradients_flow_to_all_experts(self):
        mesh = _mesh(self.RANKS, "expert")
        E = self.RANKS * self.E_LOCAL

        def loss(ep, rw, x):
            def layer(ep, rw, x):
                r = route(x @ rw)
                y, _ = moe_apply(grouped_expert_fn, ep, x, r, n_experts=E,
                                 axis_name="expert")
                return y, jax.lax.pmean(load_balance_loss(r), "expert")
            f = shard_map(layer, mesh=mesh,
                          in_specs=(P("expert"), P(), P("expert")),
                          out_specs=(P("expert"), P()))
            y, aux = f(ep, rw, x)
            return jnp.mean(y ** 2) + 0.01 * aux

        g = jax.jit(jax.grad(loss, argnums=(0, 1)))(self.experts,
                                                    self.router, self.x)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.isfinite(leaf).all())
        # every expert receives tokens under this router, so every
        # expert's weights must receive gradient, and so must the router
        per_expert = jnp.asarray(
            [float(jnp.abs(g[0]["wi"][e]).max()) for e in range(E)])
        assert int((per_expert > 0).sum()) == E, per_expert
        assert float(jnp.abs(g[1]).max()) > 0


class TestShardedOverflowSkip:
    """finite_axes: with params sharded over a mesh axis, an overflow on ONE
    rank must skip the step on EVERY rank (globally consistent scaler
    trajectory) — the sharded-param extension of the reference's shared
    overflow buffer."""

    def test_one_rank_overflow_skips_all(self):
        import optax
        from apex_tpu import amp as amp_mod

        n = 4
        mesh = _mesh(n, "shard")
        a = amp_mod.initialize(optimizer=optax.sgd(0.1), opt_level="O2",
                               loss_scale=64.0, verbosity=0)
        params = {"w": jnp.ones((n, D))}
        state = a.init(params)

        def step(state, grads):
            new_state, info = a.apply_gradients(state, grads,
                                                finite_axes=("shard",))
            return new_state, info["overflow"]

        def spec_state(s):
            return jax.tree.map(
                lambda l: P("shard") if getattr(l, "ndim", 0) >= 1
                and l.shape[0] == n else P(), s)

        grads = jnp.zeros((n, D)).at[2, 0].set(jnp.inf)  # rank 2 only
        f = jax.jit(shard_map(
            step, mesh=mesh,
            in_specs=(spec_state(state), P("shard")),
            out_specs=(spec_state(state), P())))
        new_state, overflow = f(state, {"w": grads})
        assert bool(overflow)
        # every rank's param slice unchanged — including the finite ones
        np.testing.assert_array_equal(
            np.asarray(new_state.master_params["w"]),
            np.asarray(params["w"]))
