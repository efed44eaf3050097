"""The expert walk's combine by its two routes: the Mosaic kernel
``moe_combine`` (:mod:`apex_tpu.ops.pallas.moe_combine`, interpret mode
here) against the XLA form it replaces on the chip
(``acc + _combine(_taken_rows(...))`` forward, the unweighted sum over
the choices backward), over the loads a window meets; and which shapes
take which route.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas import moe_combine as mc
from apex_tpu.parallel import moe

T, BLOCK, ROWS, HELD, E = 64, 16, 48, 4, 16


def window(load, k, d, dtype, seed=0):
    """``acc``, a window's rows ``src`` and, choice-major, each pair's
    row in the window, whether it lies inside, and its weight.  The
    buffer is three windows of ``ROWS`` rows and the window looked at is
    the second unless ``load`` is ``straddles``; a held pair lies on a
    buffer row of its own.  ``even``: each pair on a held expert with
    chance ``HELD / E``; ``one_expert``: every token's pair of one
    (random) choice on the one held expert, ``ROWS`` of them; ``none``:
    no pair held; ``straddles``: even, in the first window, whose runs
    go on into the second."""
    rng = np.random.default_rng(seed)
    n = k * T
    if load == "one_expert":
        held = np.zeros((k, T), bool)
        held[rng.integers(0, k, T), np.arange(T)] = True
        held[:, ROWS:] = False
        held = held.reshape(-1)
    else:
        held = rng.random(n) < (HELD / E if load != "none" else 0.0)
    lies = rng.permutation(3 * ROWS)[:held.sum()] if load != "one_expert" \
        else ROWS + rng.permutation(ROWS)
    first = 0 if load == "straddles" else ROWS
    at = np.full(n, -7, np.int64)
    at[held] = lies - first
    inside = held & (at >= 0) & (at < ROWS)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (T, d)),
            jax.random.normal(ks[1], (ROWS, d)).astype(dtype),
            jnp.asarray(at, jnp.int32), jnp.asarray(inside),
            jax.random.uniform(ks[2], (n,), minval=0.05, maxval=1.0))


def xla(acc, src, at, inside, weights):
    """The walk's XLA form: a row for every pair, the sum over the
    choices in float32, then the accumulator."""
    rows = moe._taken_rows(src, at, inside)
    if weights is None:
        return acc + jnp.sum(rows.reshape(-1, T, src.shape[-1])
                             .astype(jnp.float32), axis=0)
    return acc + moe._combine(rows, weights, T, jnp.float32)


def magnitude(acc, src, at, inside, weights):
    """``|acc|`` plus each token's sum of its terms' magnitudes: the
    scale a rounding of one term is measured at."""
    w = jnp.ones_like(weights) if weights is None else weights
    return np.asarray(xla(jnp.abs(acc), jnp.abs(src), at, inside, w))


CASES = [("even", 8, 384, jnp.bfloat16), ("even", 6, 256, jnp.float32),
         ("one_expert", 4, 256, jnp.bfloat16),
         ("one_expert", 1, 384, jnp.float32), ("none", 8, 256, jnp.bfloat16),
         ("straddles", 6, 384, jnp.bfloat16)]


@pytest.mark.parametrize("load,k,d,dtype", CASES)
def test_the_kernel_matches_the_xla_combine_and_its_transpose(load, k, d,
                                                              dtype):
    """Forward (weighted) and backward (unweighted) against the XLA form,
    the accumulator going in and out through the kernel: the backward
    bit for bit; the forward within a rounding of one term (XLA:CPU
    contracts the interpreted kernel's multiply and add into one fused
    operation; on the chip neither form fuses them), and bit for bit
    where a token has no more than one term.  Four blocks of tokens share
    one ring of copies; tokens with no pair held keep their row."""
    acc, src, at, inside, w = window(load, k, d, dtype)
    count = np.asarray(inside).reshape(k, T).sum(0)
    if load == "one_expert":
        assert count.sum() == ROWS > mc._RING
    for weights in (None, w):
        got = np.asarray(mc.moe_combine(acc, src, at, inside, weights,
                                        block=BLOCK))
        want = np.asarray(xla(acc, src, at, inside, weights))
        np.testing.assert_array_equal(got[count == 0],
                                      np.asarray(acc)[count == 0])
        if weights is None:
            np.testing.assert_array_equal(got, want)
            continue
        np.testing.assert_array_equal(got[count <= 1], want[count <= 1])
        # half a unit of the token's sum, then the rounding of the
        # addition to the accumulator: two units of the magnitude's last
        # place at most
        bound = 2 * np.spacing(magnitude(acc, src, at, inside, weights))
        assert (np.abs(got - want) <= bound).all()


#: (tokens a step, hidden, choices, experts, held) of the expert cells'
#: configurations, one chip's share (benchmark/configs): Kimi-Linear,
#: kanana-2, Laguna-XS.2, LFM2-8B-A1B
CONFIGURATIONS = {"kimi": (8192, 2304, 8, 256, 8),
                  "kanana": (8192, 2048, 6, 128, 16),
                  "laguna": (8192, 2048, 8, 256, 32),
                  "lfm2": (16384, 2048, 4, 32, 8)}


@pytest.mark.parametrize("name", sorted(CONFIGURATIONS))
def test_the_expert_configurations_take_the_kernel(name):
    """The window the walk runs at each configuration's shape (both
    dtypes) picks the kernel, with blocks of 256 tokens."""
    t, d, k, e, held = CONFIGURATIONS[name]
    n = t * k
    rows = moe._window_rows(n, held, -(-n * held // e))
    for dtype in (jnp.bfloat16, jnp.float32):
        assert mc.combine_geometry(t, d, rows, n, dtype) == 256


@pytest.mark.parametrize("t,d,rows,pairs,dtype", [
    (64, 16, 48, 128, jnp.float32),       # the walk tests' width
    (64, 200, 48, 128, jnp.bfloat16),     # no whole number of lanes
    (64, 256, 44, 128, jnp.bfloat16),     # a window of no whole tile
    (64, 256, 48, 128, jnp.float16),
    (60, 256, 48, 120, jnp.float32),      # no block of tokens divides 60
    (8192, 256, 65536, 1 << 20, jnp.bfloat16),   # lists past SMEM
])
def test_other_shapes_take_the_xla_form(t, d, rows, pairs, dtype):
    assert mc.combine_geometry(t, d, rows, pairs, dtype) is None


def test_the_walk_routes_by_use_pallas_and_the_geometry(monkeypatch):
    """On the CPU the walk takes the XLA form; with the kernels asked for,
    a shape the kernel takes runs ``moe_combine`` and one it does not
    keeps the XLA form."""
    acc, src, at, _, _ = window("even", 4, 256, jnp.float32)
    assert moe._combine_block(acc, src, at) is None
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    assert moe._combine_block(acc, src, at) == 64
    assert moe._combine_block(acc[:, :200], src[:, :200], at) is None
