"""The chunkwise gated delta rule against the recurrence it stands for,
token by token (``benchmark/reference/kimi_linear.py`` ``delta_rule``,
which imports nothing of ``apex_tpu``): outputs and every gradient in
float32, at lengths that are and are not whole chunks, with decays from
mild to ``exp(-30)`` and far beyond a chunk; what the compiled rule
loops over; the scope its loop bodies carry.
"""

import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.attention.gated_delta import chunk_gated_delta_rule
from apex_tpu.utils.profiling import KDA_RECURRENCE

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
from benchmark.reference import kimi_linear as reference  # noqa: E402

B, H, D = 2, 3, 16


def inputs(length, per_token_decay, seed=0, d_v=D):
    """q and k normalised as the layer hands them over; ``g`` uniform in
    ``[-per_token_decay, 0]`` per channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = reference.unit(jax.random.normal(ks[0], (B, length, H, D)),
                       1e-6) * D ** -0.5
    k = reference.unit(jax.random.normal(ks[1], (B, length, H, D)), 1e-6)
    v = jax.random.normal(ks[2], (B, length, H, d_v))
    g = -per_token_decay * jax.random.uniform(ks[3], (B, length, H, D))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, length, H)))
    return q, k, v, g, beta


# a chunk of 16: 1.9 a token is exp(-30) a chunk; 40 a token underflows
# float32 within three tokens
@pytest.mark.parametrize("length,chunk,decay", [
    (64, 16, 0.1), (50, 16, 0.1), (37, 16, 1.9), (48, 16, 40.0),
    (70, 64, 0.47)])
def test_outputs_and_every_gradient_match_the_token_recurrence(
        length, chunk, decay):
    """Float32 by two routes; 1e-5 of the largest entry is a few
    roundings of a sum over a chunk's tokens."""
    args = inputs(length, decay, seed=length)
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
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from eqns(inner)


def test_no_loop_runs_a_trip_a_token():
    """The state's walk is one loop of ``L / chunk`` trips forward and
    one backward; what needs no state runs in at most as many slices; no
    ``while``, and no scan over tokens (the reference's own scan, by
    contrast, has ``L`` trips in blocks of 64)."""
    length, chunk = 256, 64
    args = inputs(length, 0.1)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(chunk_gated_delta_rule(*a, chunk_size=chunk)),
        argnums=range(5)))(*args)
    names = [e.primitive.name for e in eqns(jaxpr.jaxpr)]
    assert "while" not in names
    trips = [e.params["length"] for e in eqns(jaxpr.jaxpr)
             if e.primitive.name == "scan"]
    assert trips.count(length // chunk) >= 2
    assert max(trips) == length // chunk
    slow = jax.make_jaxpr(lambda *a: reference.delta_rule(*a))(*args)
    slow = [e.params["length"] for e in eqns(slow.jaxpr)
            if e.primitive.name == "scan"]
    assert sorted(slow) == [length // reference.BLOCK, reference.BLOCK]


def test_the_scope_stands_on_the_instructions_inside_the_loops():
    """``benchmark/kda_scopes.py`` reads the ``op_name`` of each
    instruction of the compiled step: inside both loops' bodies every
    instruction that has one carries ``kda_recurrence``, and the
    backward loop's are autodiff's ``transpose``."""
    args = inputs(64, 0.1)
    hlo = jax.jit(jax.grad(
        lambda *a: jnp.sum(chunk_gated_delta_rule(*a, chunk_size=16)),
        argnums=range(5))).lower(*args).compile().as_text()
    inside = [set(re.split(r"[/()]+", name))
              for name in set(re.findall(r'op_name="([^"]*)"', hlo))
              if "/while/body/" in name]
    assert len(inside) > 10
    assert all(KDA_RECURRENCE in s for s in inside)
    assert any("transpose" in s for s in inside)
    assert any("transpose" not in s for s in inside)
