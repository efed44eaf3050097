"""The router and the held-experts layer (``apex_tpu.parallel.moe``).

The oracle is per token and per expert, in plain loops: nothing of the
sort, the grouped product or the gathers is shared with the code under
test.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.parallel import moe
from apex_tpu.utils.profiling import MOE_DISPATCH, MOE_EXPERTS

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


# ------------------------------------------------- the walk in windows
#
# 64 tokens, 2 experts each of 16, experts 3 and 4 held: 128 pairs, 16 of
# them held when routing is even, so a window is 2 x 16 + 2 x 8 = 48 rows
# and the buffer (128 + 2 x 8 rows) is three of them.

WT, WK, WE, WHELD = 64, 2, 16, range(3, 5)

#: the pairs on the two held experts -> the windows the walk runs
LOADS = {
    "even": ((8, 8), 1),
    "one_row_past_a_window": ((49, 0), 2),
    "a_run_straddles_two_windows": ((30, 40), 2),
    "every_pair_held": ((64, 64), 3),
    "no_pair_held": ((0, 0), 1),        # the first window always runs
}


def boosts(loads, n_experts=WE, held=WHELD, tokens=WT):
    """Added to the router's logits: the first ``loads[0]`` tokens choose
    the first held expert, the last ``loads[1]`` the second, and whoever
    has a choice left takes experts 0 and 1, which are held elsewhere."""
    b = np.zeros((tokens, n_experts), np.float32)
    b[:, 0], b[:, 1] = 2.0, 1.0
    b[:loads[0], held.start] = 4.0
    b[tokens - loads[1]:, held.start + 1] = 4.0
    return jnp.asarray(b)


def walked(p_held, x, w_r, boost, n_experts=WE, first=WHELD.start):
    r = moe.route(x @ w_r + boost, WK, scoring="sigmoid", renormalize=True)
    return moe.moe_apply(moe.gated_ffn, p_held, x, r, n_experts=n_experts,
                         first=first)


def densely(p_held, x, w_r, boost, first=WHELD.start):
    """Every held expert on every token under the weight the router gave
    it (nought where it was not chosen)."""
    r = moe.route(x @ w_r + boost, WK, scoring="sigmoid", renormalize=True)
    y = 0.0
    for i in range(p_held["gate"].shape[0]):
        w = jnp.sum(jnp.where(r.experts == first + i, r.weights, 0.0), -1)
        y = y + w[:, None] * one_expert(p_held, i, x)
    return y


@pytest.fixture(scope="module")
def walk_data():
    return (experts(jax.random.PRNGKey(4), WE),
            jax.random.normal(jax.random.PRNGKey(5), (WT, D)),
            jax.random.normal(jax.random.PRNGKey(6), (D, WE)) * 0.03,
            jax.random.normal(jax.random.PRNGKey(7), (WT, D)))


@pytest.mark.parametrize("name", sorted(LOADS))
def test_the_walk_matches_the_oracle_and_the_dense_layer(walk_data, name):
    """Outputs against the per-token oracle, gradients of the rows, the
    experts' weights and the router's against the dense formulation, and
    the windows the walk ran, whatever the load on the held experts."""
    p, x, w_r, t = walk_data
    loads, windows = LOADS[name]
    boost, p_held = boosts(loads), take(p, WHELD)
    assert moe._window_rows(WT * WK, len(WHELD), WT * WK * 2 // WE) == 48
    y, stats = jax.jit(walked)(p_held, x, w_r, boost)
    assert int(stats["pairs"]) == sum(loads)
    assert int(stats["windows"]) == windows
    r = moe.route(x @ w_r + boost, WK, scoring="sigmoid", renormalize=True)
    np.testing.assert_allclose(np.asarray(y), oracle(p, x, r, WHELD),
                               rtol=2e-4, atol=2e-5)
    got, want = [jax.jit(jax.grad(
        lambda *a, f=f: jnp.sum(f(*a, boost) * t), argnums=(0, 1, 2)))(
            p_held, x, w_r)
        for f in (lambda *a: walked(*a)[0], densely)]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)
    if sum(loads):
        assert float(jnp.abs(got[2]).max()) > 0     # the router learns


def test_the_walk_under_the_exchange_matches_the_dense_layer(walk_data):
    """Four ranks of two experts, every token on the two of rank 1: that
    rank's buffer (4 x 32 slots and 2 x 8 rows of alignment, in windows
    of 2 x 32 + 2 x 8) fills both its windows and the others' first runs
    on no row."""
    from jax.sharding import Mesh, PartitionSpec as P
    ranks, n_experts, first = 4, 8, 2
    if len(jax.devices()) < ranks:
        pytest.skip(f"needs {ranks} devices")
    mesh = Mesh(np.array(jax.devices()[:ranks]), ("expert",))
    _, x, _, t = walk_data
    p = experts(jax.random.PRNGKey(8), n_experts)
    w_r = jax.random.normal(jax.random.PRNGKey(9), (D, n_experts)) * 0.03
    boost = boosts((WT, WT), n_experts, range(first, first + 2))

    def exchanged(p, x, w_r):
        def layer(p, x, w_r, boost):
            r = moe.route(x @ w_r + boost, WK, scoring="sigmoid",
                          renormalize=True)
            y, stats = moe.moe_apply(moe.gated_ffn, p, x, r,
                                     n_experts=n_experts, axis_name="expert")
            return y, stats["windows"][None]
        return jax.shard_map(
            layer, mesh=mesh,
            in_specs=(P("expert"), P("expert"), P(), P("expert")),
            out_specs=(P("expert"), P("expert")))(p, x, w_r, boost)

    y, windows = jax.jit(exchanged)(p, x, w_r)
    np.testing.assert_array_equal(np.asarray(windows), [1, 2, 1, 1])

    def whole(p, x, w_r):
        return densely(p, x, w_r, boost, first=0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole(p, x, w_r)),
                               rtol=2e-4, atol=2e-5)
    got, want = [jax.jit(jax.grad(
        lambda *a, f=f: jnp.sum(f(*a) * t), argnums=(0, 1, 2)))(p, x, w_r)
        for f in (lambda *a: exchanged(*a)[0], whole)]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", ["a_run_straddles_two_windows",
                                  "every_pair_held"])
def test_the_walk_equals_the_layer_on_one_buffer(walk_data, name, dtype):
    """The same pairs through three windows and through one that is the
    whole buffer (``expected`` every pair).  An expert whose run straddles
    two windows gets its weights' gradient as the sum of both parts; in
    bfloat16 the grouped product has rounded each part before they meet
    (on the chip too: the kernel's output is the weights' dtype), so the
    sum lies within a rounding of the larger part of the one product over
    the whole run, and every other number within a rounding of itself."""
    p, x, w_r, t = jax.tree.map(lambda a: a.astype(dtype), walk_data)
    p_held, n = take(p, WHELD), WT * WK
    r = moe.route(x @ w_r + boosts(LOADS[name][0]), WK, scoring="sigmoid",
                  renormalize=True)
    flat = moe._pairs(r.experts) - WHELD.start
    ids = jnp.where((flat >= 0) & (flat < len(WHELD)), flat, len(WHELD))

    def layer(expected, p_held, x, weights):
        y, _, windows = moe._grouped_apply(
            moe.gated_ffn, p_held, x, ids, weights, len(WHELD), expected)
        return jnp.sum(y.astype(jnp.float32) * t), windows

    walk, whole = [jax.jit(jax.value_and_grad(
        lambda *a, e=e: layer(e, *a), argnums=(0, 1, 2), has_aux=True))(
            p_held, x, moe._pairs(r.weights)) for e in (n * 2 // WE, n)]
    assert int(walk[0][1]) == LOADS[name][1] and int(whole[0][1]) == 1
    eps = 2e-5 if dtype == jnp.float32 else 2.0 ** -7
    for a, b in zip(jax.tree.leaves((walk[0][0], walk[1])),
                    jax.tree.leaves((whole[0][0], whole[1]))):
        assert a.dtype == b.dtype
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=eps,
                                   atol=eps * np.abs(b).max())


def grad_jaxpr(n_experts, held):
    p = experts(jax.random.PRNGKey(4), len(held))
    x, w_r = jnp.zeros((WT, D)), jnp.zeros((D, n_experts))
    boost = boosts((8, 8), n_experts, held)
    return str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(walked(*a, boost, n_experts, held.start)[0]),
        argnums=(0, 1, 2)))(p, x, w_r))


def test_a_layer_that_holds_every_expert_has_one_window_and_no_loop():
    """Its buffer is no longer than a window: the walk's body once, with
    no ``while`` over windows and no ``cond``; a longer buffer is a
    ``while`` forward and one backward, and no ``cond`` either."""
    whole, walk = grad_jaxpr(2, range(0, 2)), grad_jaxpr(WE, WHELD)

    def count(word, text):
        return len(re.findall(rf"\b{word}\[", text))
    assert count("while", whole) == 0 and count("cond", whole) == 0
    assert count("while", walk) == 2 and count("cond", walk) == 0
    assert count("scan", walk) == count("scan", whole)


@pytest.mark.parametrize("n_experts,windows", [(WE, 3), (2 * WE, 5)])
def test_the_walk_holds_the_layer_once(n_experts, windows):
    """However many windows, the program holds one body: the gated
    feed-forward's three grouped products forward, and in the backward
    pass those three once more (under the loop a window keeps nothing
    but what it was made from) and their six transposes, each at a
    window's rows; the layer that holds every expert runs its one window
    outside any loop, keeps what autodiff keeps and has the nine."""
    rows = moe._window_rows(WT * WK, len(WHELD), WT * WK * 2 // n_experts)
    assert -(-(WT * WK + 16) // rows) == windows
    products = re.findall(r":f32\[([\d,]+)\] = ragged_dot_general\[",
                          grad_jaxpr(n_experts, WHELD))
    assert len(products) == 9 + 3
    assert len(re.findall(r"= ragged_dot_general\[",
                          grad_jaxpr(2, range(0, 2)))) == 9
    per_row = [shape for shape in products if shape.count(",") == 1]
    assert len(per_row) == 9                       # the other 3: d(weights)
    assert {int(shape.split(",")[0]) for shape in per_row} == {rows}


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_the_scopes_stand_on_the_instructions_inside_the_walk(request,
                                                             route):
    """``benchmark/scopes.py`` reads the ``op_name`` of each instruction
    of the compiled step: inside the loop's body every instruction of a
    window is under ``moe_dispatch`` or ``moe_experts``, never both.  On
    the kernel route (a width the combine's kernel takes, the kernels
    asked for) every instruction of ``moe_combine``, forward and
    backward, is under ``moe_dispatch``."""
    if route == "kernel":
        request.getfixturevalue("kernel_route")
    p, x, w_r, _ = request.getfixturevalue(
        "kernel_walk_data" if route == "kernel" else "walk_data")
    hlo = jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(walked(*a, boosts((8, 8)))[0]),
        argnums=(0, 1, 2))).lower(take(p, WHELD), x, w_r).compile().as_text()
    inside = [set(re.split(r"[/()]+", name))
              for name in set(re.findall(r'op_name="([^"]*)"', hlo))
              if "/while/body/" in name and "searchsorted" not in name]
    for backward in (False, True):
        found = [s for s in inside if ("transpose" in s) == backward]
        assert any(MOE_DISPATCH in s for s in found), backward
        assert any(MOE_EXPERTS in s for s in found), backward
    assert all(len(s & {MOE_DISPATCH, MOE_EXPERTS}) <= 1 for s in inside)
    # the loop's own counter apart, every instruction of the body is scoped
    bare = [s for s in inside if not s & {MOE_DISPATCH, MOE_EXPERTS}]
    assert len(bare) <= 3, bare
    combine = [s for s in inside if "moe_combine" in s]
    assert bool(combine) == (route == "kernel")
    assert all(MOE_DISPATCH in s for s in combine)
    assert {"transpose" in s for s in combine} == (
        {False, True} if combine else set())


# --------------------------------------------- the walk on the kernel route
#
# The kernels asked for on the CPU (interpret mode): the grouped products
# by megablox, the combine and its transpose by ``moe_combine``, at a
# width the combine's kernel takes.

KD, KF = 256, 128


@pytest.fixture
def kernel_route(monkeypatch):
    """The kernels asked for.  ``use_pallas()`` is read when a function is
    traced, and the walk's jitted parts keep their traces: none is kept
    from before or carried past the test."""
    jax.clear_caches()
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    yield
    monkeypatch.delenv("APEX_TPU_KERNELS")
    jax.clear_caches()


@pytest.fixture(scope="module")
def kernel_walk_data():
    return (jax.tree.map(lambda a: a * 0.25,
                         experts(jax.random.PRNGKey(4), WE, KD, KF)),
            jax.random.normal(jax.random.PRNGKey(5), (WT, KD)),
            jax.random.normal(jax.random.PRNGKey(6), (KD, WE)) * 0.03,
            jax.random.normal(jax.random.PRNGKey(7), (WT, KD)))


def test_the_walk_on_the_kernel_route_matches_the_oracle_and_the_dense_layer(
        kernel_walk_data, kernel_route):
    """A run straddles two windows, so the second window's combine adds
    to what the first left and the cotangent comes home from both: the
    output against the per-token oracle, the gradients of the rows, the
    experts' weights and the router's against the dense formulation."""
    p, x, w_r, t = kernel_walk_data
    loads, windows = LOADS["a_run_straddles_two_windows"]
    boost, p_held = boosts(loads), take(p, WHELD)
    y, stats = jax.jit(walked)(p_held, x, w_r, boost)
    assert int(stats["windows"]) == windows
    r = moe.route(x @ w_r + boost, WK, scoring="sigmoid", renormalize=True)
    np.testing.assert_allclose(np.asarray(y), oracle(p, x, r, WHELD),
                               rtol=2e-4, atol=2e-5)

    def loss(f):
        return jax.grad(lambda *a: jnp.sum(f(*a, boost) * t),
                        argnums=(0, 1, 2))
    walk = loss(lambda *a: walked(*a)[0])
    assert "moe_combine" in str(jax.make_jaxpr(walk)(p_held, x, w_r))
    got, want = [jax.jit(g)(p_held, x, w_r) for g in (walk, loss(densely))]
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_the_kernel_route_holds_no_array_of_a_row_per_pair(
        kernel_walk_data, request):
    """The XLA form gathers a row for every (token, choice) pair, ``(k T,
    d)``, forward and backward; the lowered value and gradient of the
    layer on the kernel route holds no array of that shape."""
    p, x, w_r, _ = kernel_walk_data
    k = 3

    def layer(p_held, x, w_r):
        r = moe.route(x @ w_r + boosts((8, 8)), k, scoring="sigmoid",
                      renormalize=True)
        return jnp.sum(moe.moe_apply(moe.gated_ffn, p_held, x, r,
                                     n_experts=WE, first=WHELD.start)[0])

    def lowered():
        return jax.jit(jax.value_and_grad(layer, argnums=(0, 1, 2))).lower(
            take(p, WHELD), x, w_r).as_text()

    per_pair = re.compile(rf"tensor<{k * WT}x{KD}x(bf16|f32)>")
    assert per_pair.search(lowered())
    request.getfixturevalue("kernel_route")
    text = lowered()
    assert "moe_combine" in text and not per_pair.search(text)
