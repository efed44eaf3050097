"""The sliding-window flash kernels (``flash_window_fwd`` /
``flash_window_bwd``) against the materializing path, the band of key
blocks they visit, the scaled and partial rotary tables, and the grid
walk's default blocks in fp32.

Interpret mode on CPU; set APEX_TPU_TEST_PLATFORM to run Mosaic-compiled
on hardware."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas import flash_attention as fa
from apex_tpu.ops.rope import (KernelRopeTables, apply_rope,
                               partial_rope_kernel_tables, rope_inv_freq,
                               rope_tables, scaled_rope_tables,
                               to_rotary_lanes)

_ON_CPU = jax.default_backend() == "cpu"
TOL = 2e-5 if _ON_CPU else 2e-2

#: Laguna-XS.2's full layers
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}


def _inputs(l, heads, kv_heads, d=64, rope=False, seed=0):
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(1, l, n, d).astype(np.float32))
               for n in (heads, kv_heads, kv_heads))
    if not rope:
        return (q, k, v), {}, lambda q, k: (q, k)
    cos, sin = rope_tables(jnp.arange(l)[None, :], d, 1e4)
    return ((q, k, v), dict(rope=(cos, sin)),
            lambda q, k: (apply_rope(q, cos, sin), apply_rope(k, cos, sin)))


def _check(qkv, kw, rotate, window, **blocks):
    """Output and dq, dk, dv of the window kernels against
    ``_jnp_attention`` with the same window (K and V repeated there)."""
    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, window=window,
                                  **kw, **blocks)

    def oracle(q, k, v):
        q, k = rotate(q, k)
        return fa._jnp_attention(q, k, v, causal=True, kv_mask=None,
                                 scale=q.shape[-1] ** -0.5, window=window)

    np.testing.assert_allclose(np.asarray(kernel(*qkv)),
                               np.asarray(oracle(*qkv)), rtol=TOL, atol=TOL)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(*qkv)
    want = jax.grad(loss(oracle), argnums=(0, 1, 2))(*qkv)
    assert [g.shape for g in got] == [t.shape for t in qkv]
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=TOL,
                                   atol=TOL)


@pytest.mark.parametrize("window,rope", [(256, False), (200, True),
                                         (77, False), (128, True)])
def test_window_matches_the_materializing_path(window, rope):
    """A window a whole number of 128-row blocks and not, with and
    without in-kernel rope, 8 q heads on 2 KV heads."""
    _check(*_inputs(384, 8, 2, rope=rope), window, block_q=128)


@pytest.mark.parametrize("heads", [64, 48])
def test_grouped_heads_as_the_laguna_layers_have_them(heads):
    """64 and 48 q heads on 8 KV heads (groups of 8 and 6): dk and dv are
    summed over each group inside the backward."""
    _check(*_inputs(256, heads, 8, d=16, rope=True), 100, block_q=128)


def test_a_window_as_long_as_the_row_is_causal():
    (q, k, v), kw, _ = _inputs(256, 4, 2, rope=True)
    for window in (256, 1000):
        np.testing.assert_array_equal(
            np.asarray(fa.flash_attention(q, k, v, causal=True,
                                          window=window, **kw)),
            np.asarray(fa.flash_attention(q, k, v, causal=True, **kw)))


def test_the_window_kernels_are_the_ones_in_the_program():
    (q, k, v), kw, _ = _inputs(256, 4, 2, rope=True)
    text = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, window=100, block_q=128, **kw)),
        argnums=(0, 1, 2))).lower(q, k, v).as_text()
    assert "flash_window_fwd" in text and "flash_window_bwd" in text
    assert "flash_fwd" not in text.replace("flash_window_fwd", "")


@pytest.mark.parametrize("l,block,window", [(8192, 256, 512),
                                            (8192, 512, 512),
                                            (8192, 128, 512),
                                            (1024, 128, 77),
                                            (1024, 256, 300),
                                            (512, 256, 600)])
def test_each_kernel_visits_the_bands_key_blocks_and_no_other(l, block,
                                                              window):
    """The forward's and the backward's pair for q block ``iq`` covers key
    blocks ``start .. start + n - 1`` (``_band``, ``_band_start``), n =
    ``ceil((W - 1) / block) + 1`` (at most the head's): every block that
    holds a key some query of the block sees, and, away from the row's
    start, only those.  The mirror set: each key block is met by the q
    blocks whose keys reach it, ``n`` of them away from the row's ends
    (the first q blocks' bands start at the row's first block)."""
    nk = l // block
    n = fa._band(l, block, window)
    assert n == min(math.ceil((window - 1) / block) + 1, nk)
    met = np.zeros(nk, int)
    for iq in range(nk):
        start = int(fa._band_start(iq, n, nk))
        visited = set(range(start, start + n))
        rows = np.arange(iq * block, (iq + 1) * block)
        needed = {j // block for i in rows
                  for j in range(max(0, i - window + 1), i + 1)}
        assert needed <= visited
        if iq >= n - 1:
            assert visited == needed
        met[start:start + n] += 1
    assert (met[n:nk - n + 1] == n).all()


def test_a_window_needs_causal_and_no_mask():
    (q, k, v), _, _ = _inputs(128, 2, 1)
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, k, v, window=16)
    with pytest.raises(ValueError, match="mask"):
        fa.flash_attention(q, k, v, causal=True, window=16,
                           kv_mask=jnp.ones((1, 128), bool))


def test_ring_and_ulysses_refuse_a_window():
    from apex_tpu.attention import attention
    (q, k, v), _, _ = _inputs(128, 2, 2)
    for impl in ("ring", "ulysses"):
        with pytest.raises(ValueError, match="window"):
            attention(q, k, v, axis_name="seq", impl=impl, causal=True,
                      window=16)
    want = fa._jnp_attention(q, k, v, causal=True, kv_mask=None,
                             scale=64 ** -0.5, window=16)
    for impl in ("flash", "jnp"):
        np.testing.assert_allclose(
            np.asarray(attention(q, k, v, impl=impl, causal=True,
                                 window=16)), np.asarray(want),
            rtol=TOL, atol=TOL)


def test_yarn_frequencies_are_the_published_formulas():
    """``_compute_yarn_parameters`` for Laguna-XS.2's full layers: 32
    frequencies; the correction range floor(5.66) = 5 to ceil(15.80) =
    16; below it the original frequencies, above it the original over
    64, between them the linear blend; the given attention factor,
    ``0.1 ln 64 + 1``."""
    inv_freq, factor = rope_inv_freq(YARN, 128)
    assert inv_freq.shape == (32,) and inv_freq.dtype == np.float32
    assert factor == pytest.approx(0.1 * math.log(64) + 1, rel=1e-12)
    low = 64 * math.log(4096 / (64 * 2 * math.pi)) / (2 * math.log(5e5))
    high = 64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    assert low == pytest.approx(5.66, abs=0.01)
    assert high == pytest.approx(15.80, abs=0.01)
    original = 5e5 ** (-np.arange(32) / 32.0)
    ramp = np.clip((np.arange(32) - 5) / 11.0, 0, 1)
    want = original * (1 - ramp) + original / 64 * ramp
    np.testing.assert_allclose(inv_freq, want, rtol=1e-5)
    np.testing.assert_allclose(inv_freq[:6], original[:6], rtol=1e-5)
    np.testing.assert_allclose(inv_freq[16:], original[16:] / 64, rtol=1e-5)
    no_factor = dict(YARN)
    del no_factor["attention_factor"]
    assert rope_inv_freq(no_factor, 128)[1] == pytest.approx(factor)
    default, one = rope_inv_freq({"rope_theta": 1e4}, 128)
    np.testing.assert_allclose(default, 1e4 ** (-np.arange(64) / 64.0),
                               rtol=1e-6)
    assert one == 1.0


def _published_partial_rotary(x, cos, sin, rotary):
    """Hugging Face's rotate-half over the first ``rotary`` lanes."""
    half = rotary // 2
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., rotary:]], axis=-1)


def test_the_partial_rotary_turns_the_first_64_lanes_and_passes_the_rest():
    """In :func:`to_rotary_lanes`' order with its tables, the kernels' rotation
    (``x cos + rot_half(x) sin``, lane ``j`` with ``j + 64``) is the
    published one: lanes ``i`` and ``i + 32`` of the first 64 turn,
    lanes 64..127 come out as they went in."""
    l = 48
    order = np.asarray(to_rotary_lanes(jnp.arange(128), 64))
    np.testing.assert_array_equal(order, np.concatenate([
        np.arange(32), np.arange(64, 96), np.arange(32, 64),
        np.arange(96, 128)]))
    x = jnp.asarray(np.random.RandomState(1).randn(1, l, 3, 128),
                    jnp.float32)
    np.testing.assert_array_equal(np.asarray(to_rotary_lanes(x, 64)),
                                  np.asarray(x)[..., order])
    inv_freq, factor = rope_inv_freq(YARN, 128)
    cos, sin = scaled_rope_tables(jnp.arange(l)[None, :], inv_freq, factor)
    tables = partial_rope_kernel_tables(cos, sin, 128)
    assert isinstance(tables, KernelRopeTables)
    assert tables.cos_full.shape == (1, l, 128)
    xp = to_rotary_lanes(x, 64)
    half = 64
    turned = (xp * tables.cos_full[:, :, None]
              + jnp.concatenate([xp[..., half:], xp[..., :half]], -1)
              * tables.sin_signed[:, :, None])
    back = np.empty_like(np.asarray(turned))
    back[..., order] = np.asarray(turned)
    np.testing.assert_array_equal(back[..., 64:], np.asarray(x)[..., 64:])
    np.testing.assert_allclose(
        back, _published_partial_rotary(x, cos, sin, 64), rtol=1e-6,
        atol=1e-6)
    # and through the flash kernels: scores of the permuted q, k turned
    # in-kernel are those of the published rotary
    q, k, v = (jnp.asarray(np.random.RandomState(s).randn(1, 128, 2, 128),
                           jnp.float32) for s in (2, 3, 4))
    cos, sin = scaled_rope_tables(jnp.arange(128)[None, :], inv_freq,
                                  factor)
    got = fa.flash_attention(to_rotary_lanes(q, 64), to_rotary_lanes(k, 64),
                             v, causal=True,
                             rope=partial_rope_kernel_tables(cos, sin, 128))
    want = fa._jnp_attention(_published_partial_rotary(q, cos, sin, 64),
                             _published_partial_rotary(k, cos, sin, 64), v,
                             causal=True, kv_mask=None, scale=128 ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=TOL,
                               atol=TOL)


def test_full_rotary_lane_order_is_the_identity():
    x = jnp.arange(2 * 64, dtype=jnp.float32).reshape(2, 64)
    np.testing.assert_array_equal(
        np.asarray(to_rotary_lanes(jnp.arange(64), 64)), np.arange(64))
    assert to_rotary_lanes(x, 64) is x


def test_fp32_grid_walk_takes_blocks_its_fused_backward_compiles():
    """fp32, non-causal, without rope at L >= 2048 used to take 1024-row
    blocks, whose fused backward Mosaic refuses; fp32 takes 512 whatever
    the rope, and bf16 keeps its 1024 (the benchmark's steps do not
    move)."""
    for rope in (False, True):
        assert fa._geometry(2048, 64, 4, False, rope, False) == \
            fa._Geometry(False, 512)
        assert fa._geometry(2048, 64, 2, False, rope, False) == \
            fa._Geometry(False, 1024)
    assert fa._grid_block(4096, 4) == 512
    assert fa._grid_block(1024, 4) == fa._grid_block(1024, 2) == 512
