"""What the gated delta rule's chunks need no state for, by the Mosaic
kernels (:mod:`apex_tpu.ops.pallas.gated_delta_chunk`, interpret mode
here) against the XLA part they stand in for
(:func:`apex_tpu.attention.gated_delta._without_state`): the six values,
and the five cotangents against ``jax.vjp`` of it, in float32 and
bfloat16, at chunk 64 with 128 channels, over one and two groups of
heads; decays from mild to a ``g`` of -40 a token that underflows within
a token; tokens that pad a length that is no whole chunk; one key
written again and again.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.attention import gated_delta
from apex_tpu.ops.pallas.gated_delta_chunk import chunk_bwd, chunk_fwd

C, D, HEADS = 64, 128, 8
VALUES = "w_v w_k q_in scores k_out decay".split()
GRADIENTS = "dq dk dv dg dbeta".split()


def unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def operands(n, h, dtype, g_of, seed=0):
    """``q, k, v (n, 1, h, C, D)`` in ``dtype``, normalised as the layer
    hands q and k over; ``g`` from ``g_of(key, shape)``; ``beta`` a
    sigmoid."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = (n, 1, h, C, D)
    q = (unit(jax.random.normal(ks[0], shape)) * D ** -0.5).astype(dtype)
    k = unit(jax.random.normal(ks[1], shape)).astype(dtype)
    v = jax.random.normal(ks[2], shape).astype(dtype)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:4]))
    return q, k, v, g_of(ks[3], shape), beta


def cotangents(values, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), len(values))
    return tuple(jax.random.normal(key, x.shape).astype(x.dtype)
                 for key, x in zip(ks, values))


def both_routes(args):
    """``({name: kernels'}, {name: XLA's})`` over the six values and the
    five cotangents; float32 products at ``highest`` on the XLA side, as
    the chip's three-pass ones stand for them."""
    with jax.default_matmul_precision("highest"):
        want, back = jax.vjp(gated_delta._without_state, *args)
        d_values = cotangents(want)
        d_want = back(d_values)
    got = chunk_fwd(*args, heads=HEADS)
    d_got = chunk_bwd(*args, *d_values, heads=HEADS)
    names = VALUES + GRADIENTS
    return dict(zip(names, got + d_got)), dict(zip(names, want + d_want))


def assert_close(got, want, dtype):
    """Float32: 2^-15 of the largest entry, a few times the 2^-17 a term
    that the kernels' three-pass products keep (the XLA side is exact
    float32 off the chip).  Bfloat16: 2^-6 of it, a few bfloat16
    roundings (the XLA side rounds each level's cotangent of the decayed
    keys to bfloat16, the kernels keep it in float32)."""
    for name, w in want.items():
        a = got[name]
        assert a.dtype == w.dtype and a.shape == w.shape, name
        a, w = (np.asarray(t, np.float32) for t in (a, w))
        assert np.isfinite(a).all(), name
        top = float(np.abs(w).max())
        np.testing.assert_allclose(
            a, w, rtol=0, err_msg=name,
            atol=2.0 ** (-15 if dtype == jnp.float32 else -6) * top + 1e-30)


def uniform(per_token):
    """``g`` uniform in ``[-per_token, 0]`` per channel."""
    return lambda key, shape: -per_token * jax.random.uniform(key, shape)


# mild: 0.1 a token; steep: exp(-64) over a chunk at most; -40 a token
# underflows float32 within three tokens
DECAYS = {"mild": uniform(0.1), "steep": uniform(2.0),
          "underflow": lambda key, shape: jnp.full(shape, -40.0)}


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("decay", DECAYS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_kernels_give_the_xla_parts_values_and_cotangents(dtype, decay,
                                                              groups):
    """Two chunks of ``groups`` groups of eight heads."""
    args = operands(2, HEADS * groups, dtype, DECAYS[decay])
    got, want = both_routes(args)
    assert_close(got, want, dtype)
    if decay == "underflow":
        # nothing outlives a chunk, nor three tokens
        assert not np.asarray(got["decay"]).any()
        assert not np.asarray(jnp.tril(got["scores"].astype(jnp.float32),
                                       -3)).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tokens_that_pad_a_chunk_write_nothing(dtype):
    """100 tokens in two chunks of 64, padded as ``chunk_gated_delta_rule``
    pads them (``g = 0``, ``beta = 0``, zero ``q, k, v``): the padding's
    rows of ``W_v`` and ``W_k`` are nought, and everything matches the
    XLA part."""
    length = 100
    args = operands(2, HEADS, dtype, uniform(0.5), seed=3)
    tail = (jnp.arange(2 * C) >= length).reshape(2, 1, 1, C)
    args = tuple(jnp.where(tail[..., None] if x.ndim == 5 else tail,
                           jnp.zeros((), x.dtype), x) for x in args)
    got, want = both_routes(args)
    assert_close(got, want, dtype)
    for name in ("w_v", "w_k"):
        assert not np.asarray(got[name][1, :, :, length - C:]).any(), name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_keys_that_repeat_do_not_cost_the_inverse_its_digits(dtype):
    """One key, written at full strength with no decay, 64 times: ``I +
    diag(beta) A`` is ones below its diagonal, whose inverse is ``I``
    less the diagonal below it, so ``W_v`` is ``v_r - v_(r-1)``; block
    forward substitution in three bfloat16 passes finds it."""
    q, k, v, g, beta = operands(1, HEADS, dtype, uniform(0.0))
    k = jnp.broadcast_to(k[:, :, :, :1], k.shape)
    beta = jnp.ones_like(beta)
    got, want = both_routes((q, k, v, g, beta))
    assert_close(got, want, dtype)
    if dtype == jnp.float32:
        exact = v - jnp.pad(v, ((0, 0),) * 3 + ((1, 0), (0, 0)))[..., :-1, :]
        np.testing.assert_allclose(got["w_v"], exact, rtol=0,
                                   atol=1e-5 * float(jnp.abs(exact).max()))
