"""The chunkwise gated delta rule against the recurrence it stands for,
token by token (``benchmark/reference/kimi_linear.py`` ``delta_rule``,
which imports nothing of ``apex_tpu``): outputs and every gradient in
float32, at lengths that are and are not whole chunks, with decays from
mild to ``exp(-30)`` and far beyond a chunk, by both routes (full
128-lane heads take the Mosaic kernels of both parts, interpret mode
here); the state's walk by its two routes on the same operands, and
which shapes take which; what the compiled rule loops over; the scope
its loop bodies and its kernels carry.
"""

import math
import re
import sys
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.attention import gated_delta
from apex_tpu.attention.gated_delta import chunk_gated_delta_rule
from apex_tpu.ops.pallas import gated_delta_chunk, gated_delta_walk
from apex_tpu.utils.profiling import KDA_RECURRENCE

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from benchmark.reference import kimi_linear as reference  # noqa: E402

B, H, D = 2, 3, 16
#: heads the kernels take: full lanes, ``B x WIDE_H`` one group of eight
#: (six heads of any width keep the scan and ``_without_state``)
WIDE_H, WIDE_D = 4, 128
ROUTES = {"scan": (H, D), "kernels": (WIDE_H, WIDE_D)}


def inputs(length, per_token_decay, seed=0, h=H, d=D):
    """q and k normalised as the layer hands them over; ``g`` uniform in
    ``[-per_token_decay, 0]`` per channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = reference.unit(jax.random.normal(ks[0], (B, length, h, d)),
                       1e-6) * d ** -0.5
    k = reference.unit(jax.random.normal(ks[1], (B, length, h, d)), 1e-6)
    v = jax.random.normal(ks[2], (B, length, h, d))
    g = -per_token_decay * jax.random.uniform(ks[3], (B, length, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, h)))
    return q, k, v, g, beta


# a chunk of 16: 1.9 a token is exp(-30) a chunk; 40 a token underflows
# float32 within three tokens; by the kernels besides 150 tokens, no whole
# chunk of 64, and 24, two chunks of float32's sublane tile.  (At 128
# channels g's cotangent under 40 a token cancels to 1.7e-5 of its largest
# entry by either route: the kernels' underflow is tested against the XLA
# part in test_gated_delta_chunk.py)
@pytest.mark.parametrize("length,chunk,decay,route", [
    (64, 16, 0.1, "scan"), (50, 16, 0.1, "scan"), (37, 16, 1.9, "scan"),
    (48, 16, 40.0, "scan"), (70, 64, 0.47, "scan"),
    (150, 64, 0.47, "kernels"), (24, 8, 3.8, "kernels"),
    (64, 16, 0.1, "kernels"), (50, 16, 0.1, "kernels"),
    (37, 16, 1.9, "kernels")])
def test_outputs_and_every_gradient_match_the_token_recurrence(
        length, chunk, decay, route):
    """Float32 by two routes; 1e-5 of the largest entry is a few
    roundings of a sum over a chunk's tokens."""
    h, d = ROUTES[route]
    args = inputs(length, decay, length, h, d)
    assert (gated_delta_walk.walk_geometry(B * h, chunk, d, d, jnp.float32)
            is None) == (route == "scan")
    weigh = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)

    def both(rule):
        def weighed(*a):
            out = rule(*a)
            return jnp.sum(out * weigh), out
        return jax.jit(jax.value_and_grad(weighed, argnums=range(5),
                                          has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, out), d_got = both(lambda *a: chunk_gated_delta_rule(
            *a, chunk_size=chunk))(*args)
        (_, ref), d_want = both(reference.delta_rule)(*args)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, ref, rtol=0,
                               atol=1e-5 * float(jnp.abs(ref).max()))
    for name, a, b in zip("q k v g beta".split(), d_got, d_want):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            a, b, rtol=0, atol=1e-5 * float(jnp.abs(b).max()) + 1e-30,
            err_msg=name)


def test_keys_that_repeat_do_not_cost_the_inverse_its_digits():
    """One key, written at full strength with no decay, 64 times: the
    triangular system is all ones below its diagonal, where a sum of
    powers cancels binomials of 1e17; block forward substitution does
    not."""
    length = 64
    q, k, v, g, beta = inputs(length, 0.0)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.ones_like(beta)
    with jax.default_matmul_precision("highest"):
        out = chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=64)
        ref = reference.delta_rule(q, k, v, g, beta)
    np.testing.assert_allclose(out, ref, rtol=0,
                               atol=1e-5 * float(jnp.abs(ref).max()))


def test_bfloat16_operands_keep_float32_decay_and_state():
    """bfloat16 q, k, v give bfloat16 outputs within bfloat16's rounding
    of the float32 recurrence on the same rounded inputs; ``g`` stays
    float32 and gets a float32 gradient."""
    q, k, v, g, beta = inputs(96, 0.3)
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out, stats = chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=16,
                                        return_stats=True)
    ref = reference.delta_rule(*(t.astype(jnp.float32) for t in (q, k, v)),
                               g, beta)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32), ref, rtol=0,
                               atol=2.0 ** -6 * float(jnp.abs(ref).max()))
    d_g = jax.grad(lambda g: jnp.sum(chunk_gated_delta_rule(
        q, k, v, g, beta, chunk_size=16).astype(jnp.float32)))(g)
    assert d_g.dtype == jnp.float32 and np.isfinite(np.asarray(d_g)).all()
    # the counters: the most negative running log-decay of a chunk, and
    # the state's largest magnitude at a chunk's end
    chunks = np.asarray(g).reshape(B, 6, 16, H, D).sum(axis=2)
    np.testing.assert_allclose(stats["log_decay_min"], chunks.min(),
                               rtol=1e-5)
    assert 0.0 < float(stats["state_absmax"]) < 10.0


def eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold,
    but not of a kernel's body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from eqns(inner)


@pytest.mark.parametrize("route", ROUTES)
def test_no_loop_runs_a_trip_a_token(route):
    """The state's walk is one loop of ``L / chunk`` trips forward and
    one backward, and what needs no state runs in at most as many slices
    (narrow heads); or each part is one kernel forward and one backward,
    and nothing loops (full-lane heads); no ``while``, and no scan over
    tokens (the reference's own scan, by contrast, has ``L`` trips in
    blocks of 64)."""
    length, chunk = 384, 64
    args = inputs(length, 0.1, 0, *ROUTES[route])
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(chunk_gated_delta_rule(*a, chunk_size=chunk)),
        argnums=range(5)))(*args)
    names = [e.primitive.name for e in eqns(jaxpr.jaxpr)]
    assert "while" not in names
    trips = [e.params["length"] for e in eqns(jaxpr.jaxpr)
             if e.primitive.name == "scan"]
    kernels = [e.params["name"] for e in eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    if route == "scan":
        assert trips.count(length // chunk) >= 2 and not kernels
        assert max(trips) == length // chunk
    else:
        assert kernels == ["kda_chunk_fwd", "kda_walk_fwd", "kda_walk_bwd",
                           "kda_chunk_bwd"]
        assert not trips
    slow = jax.make_jaxpr(lambda *a: reference.delta_rule(*a))(*args)
    slow = [e.params["length"] for e in eqns(slow.jaxpr)
            if e.primitive.name == "scan"]
    assert sorted(slow) == [length // reference.BLOCK, reference.BLOCK]


@pytest.mark.parametrize("route", ROUTES)
def test_the_scope_stands_on_the_instructions_inside_the_loops(route):
    """``benchmark/kda_scopes.py`` reads the ``op_name`` of each
    instruction of the compiled step: inside both loops' bodies every
    instruction that has one carries ``kda_recurrence``, and the
    backward loop's are autodiff's ``transpose``; so do the four kernels'
    calls as they are lowered for the chip, each under its own name, the
    backward ones under ``transpose``."""
    args = inputs(64, 0.1, 0, *ROUTES[route])
    grad = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunk_gated_delta_rule(*a, chunk_size=16)),
        argnums=range(5)))
    if route == "scan":
        hlo = grad.lower(*args).compile().as_text()
        inside = [set(re.split(r"[/()]+", name))
                  for name in set(re.findall(r'op_name="([^"]*)"', hlo))
                  if "/while/body/" in name]
        assert len(inside) > 10
    else:
        names = ["kda_chunk_bwd", "kda_chunk_fwd", "kda_walk_bwd",
                 "kda_walk_fwd"]
        with mock.patch.object(gated_delta_walk, "on_tpu", lambda: True), \
                mock.patch.object(gated_delta_chunk, "on_tpu", lambda: True):
            text = grad.trace(*args).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
        assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == names
        # each kernel is lowered inside a function of its own, as
        # ``<name>/pallas_call``; XLA puts the call's ``op_name`` before
        # it when it inlines the function
        assert all(f'loc("{name}/pallas_call"' in text for name in names)
        inside = [set(re.split(r"[/()]+", name)) for name in re.findall(
            r'loc\("([^"]*/jit\((?:walk|chunk)_\w+\))"', text)]
        assert len(inside) == 4
        backward = [s for s in inside if "transpose" in s]
        assert len(backward) == 2
        assert {"walk_bwd", "chunk_bwd"} <= set().union(*backward)
    assert all(KDA_RECURRENCE in s for s in inside)
    assert any("transpose" in s for s in inside)
    assert any("transpose" not in s for s in inside)


def walk_operands(n, b, h, c, d, dtype):
    """Operands of the walk as the stateless part leaves them, at sizes
    that keep the state of order one, and a cotangent for the outputs."""
    ks = jax.random.split(jax.random.PRNGKey(n), 7)
    w_v, w_k, q_in, k_out, d_outs = (
        (0.3 * jax.random.normal(key, (n, b, h, c, d))).astype(dtype)
        for key in ks[:5])
    scores = jnp.tril(0.3 * jax.random.normal(ks[5], (n, b, h, c, c))
                      ).astype(dtype)
    decay = jnp.exp(-jax.random.uniform(ks[6], (n, b, h, d)))
    return (w_v, w_k, q_in, scores, k_out, decay), d_outs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernels_walk_as_the_scan_walks(dtype):
    """Both routes on the same operands, two groups of sixteen heads over
    three chunks of 64 at 128 channels: outputs, the state's largest
    magnitudes and all six cotangents, with and without the kept states.
    The products and their roundings are the same; only the order of a
    product's sum may differ."""
    args, d_outs = walk_operands(3, 2, 16, 64, 128, dtype)

    def walk(*a):
        (outs, tops), back = jax.vjp(gated_delta._walk, *a)
        return (outs, tops) + back((d_outs, jnp.zeros_like(tops)))

    assert gated_delta._kernels(*args[:2]) == 16
    got = jax.jit(walk)(*args)
    primal = jax.jit(gated_delta._walk)(*args)
    with mock.patch.object(gated_delta, "walk_geometry", lambda *a: None):
        want = jax.jit(walk)(*args)
    names = "outs tops d_w_v d_w_k d_q_in d_scores d_k_out d_decay".split()
    for name, a, b in zip(names + names[:2], got + primal, want + want[:2]):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        a, b = (np.asarray(t, np.float32) for t in (a, b))
        # float32: 1e-6 of the largest entry; bfloat16: one rounding
        np.testing.assert_allclose(
            a, b, rtol=2.0 ** -8 if dtype == jnp.bfloat16 else 0,
            atol=1e-6 * np.abs(b).max(), err_msg=name)


@pytest.mark.parametrize("b,h,chunk,d,dtype,heads", [
    (1, 32, 64, 128, jnp.bfloat16, 16),        # the Kimi cell's
    (1, 24, 64, 128, jnp.bfloat16, 8),         # no groups of sixteen
    (2, 2, 16, 128, jnp.float32, 4),           # one group under eight
    (1, 32, 64, 64, jnp.bfloat16, None),       # lanes half full
    (1, 32, 8, 128, jnp.bfloat16, None),       # half a bfloat16 tile
    (2, 5, 64, 128, jnp.float32, None),        # ten heads: no group
])
def test_the_route_is_read_from_the_shape(b, h, chunk, d, dtype, heads):
    """Kernels for both parts where the heads fill the lanes, the chunk
    whole sublane tiles of the operand dtype and the heads come in
    groups of sixteen or eight or as one smaller group; for any other
    shape the scan, and ``_without_state`` under ``jax.checkpoint`` in
    slices of the chunks (``lax.map``)."""
    q = jax.ShapeDtypeStruct((b, 4 * chunk, h, d), dtype)
    g = jax.ShapeDtypeStruct(q.shape, jnp.float32)
    beta = jax.ShapeDtypeStruct(q.shape[:3], jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: chunk_gated_delta_rule(
        *a, chunk_size=chunk))(q, q, q, g, beta)
    kernels = [e for e in eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    walks = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "scan"
             and e.params["length"] == 4 and e.params["num_carry"]]
    slices = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "scan"
              and not e.params["num_carry"]]
    remat = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "remat2"]
    if heads is None:
        assert not kernels and len(walks) == 1
        assert len(slices) == 1 and len(remat) == 1
    else:
        assert [e.params["name"] for e in kernels] == ["kda_chunk_fwd",
                                                       "kda_walk_fwd"]
        assert not walks and not slices and not remat
        # the stateless part's kernels take up to eight of them a step
        assert [e.params["grid_mapping"].grid for e in kernels] == [
            (4, b * h // math.gcd(heads, 8)), (b * h // heads, 4)]
