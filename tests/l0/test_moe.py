"""The router and the held-experts layer (``apex_tpu.parallel.moe``).

The oracle is per token and per expert, in plain loops: nothing of the
sort, the grouped product or the gathers is shared with the code under
test.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.parallel import moe

T, D, F, E = 48, 16, 24, 8


def experts(key, n=E, d=D, f=F):
    k = jax.random.split(key, 3)
    return {"gate": jax.random.normal(k[0], (n, d, f)) * 0.3,
            "up": jax.random.normal(k[1], (n, d, f)) * 0.3,
            "down": jax.random.normal(k[2], (n, f, d)) * 0.3}


def one_expert(p, e, x):
    h = x @ p["gate"][e]
    return ((h / (1 + jnp.exp(-h))) * (x @ p["up"][e])) @ p["down"][e]


def oracle(p, x, routing, held):
    """Token by token, expert by expert, in numpy; experts outside
    ``held`` add nothing."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    y = np.zeros((x.shape[0], p["down"].shape[-1]))
    w, ids = np.asarray(routing.weights), np.asarray(routing.experts)
    for t in range(x.shape[0]):
        for j in range(ids.shape[1]):
            e = int(ids[t, j])
            if e in held:
                h = x[t] @ p["gate"][e]
                y[t] += w[t, j] * (((h / (1 + np.exp(-h)))
                                    * (x[t] @ p["up"][e])) @ p["down"][e])
    return y.astype(np.float32)


def take(p, held):
    return jax.tree.map(lambda a: a[held.start:held.stop], p)


@pytest.fixture(scope="module")
def data():
    key = jax.random.PRNGKey(0)
    return (experts(key), jax.random.normal(jax.random.PRNGKey(1), (T, D)),
            jax.random.normal(jax.random.PRNGKey(2), (T, E)))


# ------------------------------------------------------------------ router

ROUTERS = {
    "switch_top1": dict(k=1, scoring="softmax"),
    "softmax_top2": dict(k=2, scoring="softmax"),
    "softmax_top2_renormalised": dict(k=2, scoring="softmax",
                                      renormalize=True),
    "sigmoid_top2": dict(k=2, scoring="sigmoid"),
    "sigmoid_top3_renormalised_scaled": dict(k=3, scoring="sigmoid",
                                             renormalize=True, scale=2.448),
}


@pytest.mark.parametrize("name", sorted(ROUTERS))
@pytest.mark.parametrize("with_bias", [False, True])
def test_route_matches_the_published_formulas(data, name, with_bias):
    _, _, logits = data
    opts = dict(ROUTERS[name])
    k = opts.pop("k")
    bias = (jax.random.normal(jax.random.PRNGKey(7), (E,))
            if with_bias else None)
    got = moe.route(logits, k, bias=bias, **opts)
    scores = (jax.nn.softmax(logits, -1) if opts["scoring"] == "softmax"
              else jax.nn.sigmoid(logits))
    choice = scores + (bias if with_bias else 0.0)
    _, ids = jax.lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, ids, -1)       # the bias moves no weight
    if opts.get("renormalize"):
        w = w / w.sum(-1, keepdims=True)
    w = w * opts.get("scale", 1.0)
    np.testing.assert_array_equal(np.asarray(got.experts), np.asarray(ids))
    np.testing.assert_allclose(np.asarray(got.weights), np.asarray(w),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got.scores), np.asarray(scores),
                               rtol=1e-6)
    assert got.experts.dtype == jnp.int32 and got.weights.dtype == jnp.float32


def test_switch_router_is_the_k1_softmax_case(data):
    """What ``top1_routing`` gave: the arg-max expert and its softmax
    probability."""
    _, _, logits = data
    r = moe.route(logits)
    probs = jax.nn.softmax(logits, -1)
    np.testing.assert_array_equal(np.asarray(r.experts[:, 0]),
                                  np.asarray(jnp.argmax(logits, -1)))
    np.testing.assert_allclose(np.asarray(r.weights[:, 0]),
                               np.asarray(probs.max(-1)), rtol=1e-6)


def test_ties_go_to_the_lowest_index_and_no_expert_twice():
    r = moe.route(jnp.zeros((5, E)), 3, scoring="sigmoid")
    np.testing.assert_array_equal(np.asarray(r.experts),
                                  np.tile(np.arange(3), (5, 1)))


def test_the_bias_gets_no_gradient_and_the_router_matrix_does(data):
    p, x, _ = data
    w_r = jax.random.normal(jax.random.PRNGKey(3), (D, E))
    bias = jnp.zeros((E,)) + 0.01

    def loss(w_r, bias):
        r = moe.route(x @ w_r, 2, scoring="sigmoid", bias=bias,
                      renormalize=True)
        y, _ = moe.moe_apply(moe.gated_ffn, p, x, r, n_experts=E)
        return jnp.sum(y ** 2)

    g_w, g_b = jax.jit(jax.grad(loss, argnums=(0, 1)))(w_r, bias)
    assert float(jnp.abs(g_b).max()) == 0.0
    assert float(jnp.abs(g_w).max()) > 0.0


def test_load_balance_loss_is_one_when_even():
    scores = jnp.full((E * 4, E), 1.0 / E)
    ids = (jnp.arange(E * 4) % E)[:, None].astype(jnp.int32)
    r = moe.Routing(jnp.ones((E * 4, 1)), ids, scores)
    np.testing.assert_allclose(float(moe.load_balance_loss(r)), 1.0,
                               rtol=1e-6)
    # all on one expert, whose score is 1: E times as much
    hot = moe.Routing(jnp.ones((8, 1)), jnp.zeros((8, 1), jnp.int32),
                      jax.nn.one_hot(jnp.zeros(8, jnp.int32), E))
    np.testing.assert_allclose(float(moe.load_balance_loss(hot)), float(E),
                               rtol=1e-6)


# ------------------------------------------------------- the experts held

HELD = {"all": range(0, E), "first_half": range(0, 4),
        "second_half": range(4, 8), "two_in_the_middle": range(3, 5)}


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("k", [1, 2, 6])
def test_held_experts_match_the_per_token_oracle(data, held, k):
    p, x, logits = data
    r = moe.route(logits, k, scoring="sigmoid", renormalize=True, scale=2.448)
    y, stats = jax.jit(lambda p_, x, r: moe.moe_apply(
        moe.gated_ffn, p_, x, r, n_experts=E,
        first=HELD[held].start))(take(p, HELD[held]), x, r)
    np.testing.assert_allclose(np.asarray(y), oracle(p, x, r, HELD[held]),
                               rtol=2e-4, atol=2e-5)
    on_held = np.isin(np.asarray(r.experts), list(HELD[held])).sum()
    assert int(stats["pairs"]) == on_held


def test_the_shares_of_a_layer_add_up_to_the_whole(data):
    p, x, logits = data
    r = moe.route(logits, 3, scoring="sigmoid", renormalize=True)
    whole, _ = moe.moe_apply(moe.gated_ffn, p, x, r, n_experts=E)
    parts = [moe.moe_apply(moe.gated_ffn, take(p, range(f, f + 2)), x, r,
                           n_experts=E, first=f)[0] for f in range(0, E, 2)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_no_token_is_dropped_when_the_router_picks_one_expert(data):
    """Every token on expert 5: a capacity-bounded layer would drop all
    but a few; here each comes back as expert 5's output under its
    weight, and the counter says all ``T`` pairs were served."""
    p, x, _ = data
    logits = jnp.full((T, E), -4.0).at[:, 5].set(4.0)
    r = moe.route(logits)
    y, stats = moe.moe_apply(moe.gated_ffn, p, x, r, n_experts=E)
    want = np.asarray(r.weights) * np.asarray(one_expert(p, 5, x))
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert int(stats["pairs"]) == T
    np.testing.assert_allclose(float(stats["load_peak"]), E, rtol=1e-6)
    assert float(jnp.abs(y).min(axis=-1).max()) > 0       # no zero row


def test_gradients_match_the_dense_formulation(data):
    """d/d(experts), d/dx and d/d(weights) against every expert applied
    densely to every token under its weight."""
    p, x, logits = data
    held = range(2, 6)

    def layer(p_held, x, logits):
        r = moe.route(logits, 2, scoring="sigmoid", renormalize=True)
        return moe.moe_apply(moe.gated_ffn, p_held, x, r, n_experts=E,
                             first=held.start)[0]

    def dense(p_held, x, logits):
        r = moe.route(logits, 2, scoring="sigmoid", renormalize=True)
        y = 0.0
        for i, e in enumerate(held):
            w = jnp.sum(jnp.where(r.experts == e, r.weights, 0.0), -1)
            y = y + w[:, None] * one_expert(p_held, i, x)
        return y

    t = jax.random.normal(jax.random.PRNGKey(9), (T, D))
    grads = [jax.jit(jax.grad(lambda *a, f=f: jnp.sum(f(*a) * t),
                              argnums=(0, 1, 2)))(take(p, held), x, logits)
             for f in (layer, dense)]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


@pytest.mark.parametrize("m,sizes", [(256, (100, 0, 60, 30)),
                                     (200, (50, 50, 50, 50)),
                                     (384, (0, 0, 0, 7))])
def test_grouped_matmul_kernels_match_ragged_dot(monkeypatch, m, sizes):
    """jax's megablox kernels (interpret mode here) through
    ``grouped_matmul`` against ``lax.ragged_dot`` over the rows the
    groups cover, forward and both gradients; ``m`` not a multiple of the
    row tile is padded."""
    k, n = 128, 256
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n)) * 0.1
    gs = jnp.asarray(sizes, jnp.int32)
    covered = (jnp.arange(m) < sum(sizes))[:, None]
    t = jax.random.normal(jax.random.PRNGKey(2), (m, n))

    def loss(lhs, rhs):
        return jnp.sum(jnp.where(covered, moe.grouped_matmul(lhs, rhs, gs),
                                 0.0) * t)

    want = jax.value_and_grad(loss, argnums=(0, 1))(lhs, rhs)
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    got = jax.value_and_grad(loss, argnums=(0, 1))(lhs, rhs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)


def test_held_experts_must_lie_among_the_routers(data):
    p, x, logits = data
    r = moe.route(logits, 2)
    with pytest.raises(ValueError, match="are not among"):
        moe.moe_apply(moe.gated_ffn, take(p, range(0, 4)), x, r,
                      n_experts=E, first=6)
