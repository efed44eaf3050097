"""Flash-attention kernel conformance (interpret mode on CPU; set
APEX_TPU_TEST_PLATFORM to run Mosaic-compiled on hardware).

The harness mirrors the multi-tensor fuzz style (SURVEY.md §4.1): kernel
output and gradients vs a pure-jnp oracle across causal/mask/dtype/odd-
length axes, with the masked-row and padding edge cases planted explicitly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.pallas.flash_attention import flash_attention

B, L, H, D = 2, 384, 4, 64

# The oracle einsums run at precision="highest" so they are exact on TPU
# too; the kernel's MXU matmuls use the default f32 decomposition
# (bf16-multipass), which differs from a full-f32 oracle at the ~1e-2
# level after softmax renormalization — the same precision class as
# jax's own TPU flash kernel, hence the looser on-hardware tolerance.
_ON_CPU = jax.default_backend() == "cpu"
RTOL = 1e-5 if _ON_CPU else 2e-2
ATOL = 1e-5 if _ON_CPU else 2e-2
GTOL = 1e-4 if _ON_CPU else 2e-2


def ref_attn(q, k, v, causal=False, kv_mask=None):
    """jnp oracle; fully-masked rows emit zeros like the kernel."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision="highest") * scale
    neg = jnp.asarray(-1e30, jnp.float32)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, neg)
    if causal:
        tri = jnp.tril(jnp.ones((q.shape[1], q.shape[1]), bool))
        s = jnp.where(tri[None, None], s, neg)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if kv_mask is not None:
        p = jnp.where(kv_mask[:, None, None, :], p, 0.0)
    if causal:
        p = jnp.where(tri[None, None], p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", p / safe_l, v.astype(jnp.float32),
                     precision="highest")
    return out.astype(q.dtype)


def _qkv(dtype=jnp.float32, l=L, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, l, H, D).astype(np.float32)
                             ).astype(dtype)
    return mk(), mk(), mk()


def _check_grads(q, k, v, causal, mask, **flash_kwargs):
    """Gradients of a sin-sum loss through the kernel vs the jnp oracle."""
    def loss(fn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(fn(q, k, v)).astype(jnp.float32))

    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, kv_mask=mask, **flash_kwargs)),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(lambda q, k, v: ref_attn(
        q, k, v, causal=causal, kv_mask=mask)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=GTOL, atol=GTOL)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
def test_forward_matches_reference(causal, use_mask):
    q, k, v = _qkv()
    mask = None
    if use_mask:
        rng = np.random.RandomState(1)
        mask = jnp.asarray(rng.rand(B, L) > 0.2).at[:, 0].set(True)
    out = flash_attention(q, k, v, causal=causal, kv_mask=mask,
                          block_q=128, block_k=128)
    ref = ref_attn(q, k, v, causal=causal, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_gradients_match_reference():
    q, k, v = _qkv()
    rng = np.random.RandomState(1)
    mask = jnp.asarray(rng.rand(B, L) > 0.2).at[:, 0].set(True)
    _check_grads(q, k, v, True, mask, block_q=128, block_k=128)


def test_odd_length_padding_and_bf16():
    q, k, v = _qkv(jnp.bfloat16, l=300)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = ref_attn(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16 and out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_noncausal_padded_keys_do_not_attend():
    """Regression: with no kv_mask and a non-causal odd length, the
    zero-padded key columns must not enter the softmax (they ride the
    NEG_INF padding bias _prep builds — the fast no-bias kernel path is
    only legal when nothing is padded or causality hides the pad)."""
    q, k, v = _qkv(l=300, seed=3)
    out = flash_attention(q, k, v, causal=False, block_q=128, block_k=128)
    ref = ref_attn(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_explicit_block_override_warns():
    """Explicit sub-granularity block sizes are rounded up to Mosaic
    tile legality (block_k < 128 miscompiles on hardware); the caller
    asked for a specific blocking, so the adjustment must be audible
    (ADVICE r2)."""
    import warnings
    from apex_tpu.ops.pallas import flash_attention as fa
    fa._warn_block_override.cache_clear()
    q, k, v = _qkv(l=256)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        flash_attention(q, k, v, block_q=100, block_k=64)
    msgs = [str(w.message) for w in caught
            if "adjusted to" in str(w.message)]
    assert any("block_q=100 adjusted to 104" in m for m in msgs)
    assert any("block_k=64 adjusted to 128" in m for m in msgs)
    # Defaulted block sizes never warn.
    fa._warn_block_override.cache_clear()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        flash_attention(q, k, v)
    assert not [w for w in caught if "adjusted to" in str(w.message)]


def test_two_pass_backward_matches_reference(monkeypatch):
    """The long-context two-pass backward (dq + dkv kernels) is the
    fallback above the fused dq-partials budget; force it here (via the
    public env override) so both backward implementations keep gradient
    coverage."""
    monkeypatch.setenv("APEX_TPU_FLASH_FUSED_BWD_MAX_BYTES", "0")
    q, k, v = _qkv()
    rng = np.random.RandomState(1)
    mask = jnp.asarray(rng.rand(B, L) > 0.2).at[:, 0].set(True)
    _check_grads(q, k, v, True, mask, block_q=128, block_k=128)


def test_fully_masked_rows_emit_zeros():
    q, k, v = _qkv(l=256)
    mask = jnp.zeros((B, 256), bool).at[0].set(True)   # batch 1 all-masked
    out = flash_attention(q, k, v, kv_mask=mask, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out[1]), 0.0)
    assert bool(jnp.any(out[0] != 0.0))


def test_fully_masked_rows_zero_gradients():
    q, k, v = _qkv(l=256)
    mask = jnp.zeros((B, 256), bool).at[0].set(True)
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, kv_mask=mask, block_q=128, block_k=128)
        .astype(jnp.float32)))(q)
    np.testing.assert_allclose(np.asarray(g[1]), 0.0)


def test_dispatcher_uses_flash():
    from apex_tpu.attention import attention
    q, k, v = _qkv(l=256)
    out = attention(q, k, v, impl="flash", causal=True)
    ref = ref_attn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)


def test_default_blocks_scale_with_length():
    """The block-size default switches to 1024 at L >= 2048 (per-step
    overhead amortization measured on chip); the selection logic is
    checked here, the numerics hardware-side below."""
    from apex_tpu.ops.pallas.flash_attention import _default_block
    cases = (
        (512, 512), (2047, 512), (2048, 1024), (4096, 1024), (16384, 1024),
        # 1024 blocks would pad 4608 -> 5120 (~23% extra quadratic work)
        # while 512 pads nothing: stay at 512.
        (4608, 512),
        # 4609 pads to 5120 under either block size: take the big block.
        (4609, 1024),
    )
    for l, expect in cases:
        assert _default_block(l) == expect, l


@pytest.mark.skipif(_ON_CPU, reason="interpret-mode 4096^2 attention is "
                    "prohibitively slow; run with APEX_TPU_TEST_PLATFORM")
def test_long_sequence_default_blocks_match_oracle():
    """L=4096 exercises the 1024-block default hot path on hardware:
    values must match the jnp oracle within the on-chip tolerance."""
    l = 4096
    q = jax.random.normal(jax.random.PRNGKey(0), (1, l, 2, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, l, 2, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, l, 2, 64), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = ref_attn(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=RTOL, atol=ATOL)


class TestRopeFused:
    """In-kernel rotary embedding (``rope=(cos, sin)``): q/k pass in
    unrotated and the kernel rotates VMEM blocks before the score
    matmul (and inverse-rotates dq/dk at emit).  Oracle: pre-rotate
    with :func:`apply_rope` and run the rope-free kernel — on CPU/fp32
    both paths do the identical fp32 rotation arithmetic, so
    tolerances stay at the kernel-parity level."""

    def _setup(self, l=L, dtype=jnp.float32, seed=0):
        from apex_tpu.ops.rope import rope_tables
        q, k, v = _qkv(dtype, l=l, seed=seed)
        pos = jnp.broadcast_to(jnp.arange(l)[None, :], (B, l))
        cos, sin = rope_tables(pos, D, 10000.0)
        return q, k, v, cos, sin

    def _oracle(self, q, k, v, cos, sin, **kw):
        from apex_tpu.ops.rope import apply_rope
        return flash_attention(apply_rope(q, cos, sin),
                               apply_rope(k, cos, sin), v, **kw)

    @pytest.mark.parametrize("use_mask", [False, True])
    def test_forward_and_grads_match_prerotated(self, use_mask):
        q, k, v, cos, sin = self._setup()
        mask = None
        if use_mask:
            rng = np.random.RandomState(1)
            mask = jnp.asarray(rng.rand(B, L) > 0.2).at[:, 0].set(True)
        kw = dict(causal=True, kv_mask=mask, block_q=128, block_k=128)
        out = flash_attention(q, k, v, rope=(cos, sin), **kw)
        ref = self._oracle(q, k, v, cos, sin, **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)
        self._check_rope_grads(q, k, v, cos, sin, kw)

    def _check_rope_grads(self, q, k, v, cos, sin, kw, tol=GTOL):
        def loss(fn):
            return lambda q, k, v: jnp.sum(
                jnp.sin(fn(q, k, v)).astype(jnp.float32))

        gf = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, rope=(cos, sin), **kw)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: self._oracle(
            q, k, v, cos, sin, **kw)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tol, atol=tol)

    def test_stream_mode_matches(self, monkeypatch):
        """Above the resident budget the tables stream per block; same
        numbers either way."""
        from apex_tpu.ops.pallas import flash_attention as fa
        q, k, v, cos, sin = self._setup()
        kw = dict(causal=True, block_q=128, block_k=128)
        ref = self._oracle(q, k, v, cos, sin, **kw)
        monkeypatch.setattr(fa, "_ROPE_RESIDENT_MAX_BYTES", 0)
        out = flash_attention(q, k, v, rope=(cos, sin), **kw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)
        self._check_rope_grads(q, k, v, cos, sin, kw)

    def test_two_pass_backward_matches(self, monkeypatch):
        """The long-context two-pass backward rotates for the
        probability recompute and inverse-rotates dq/dk at emit too."""
        monkeypatch.setenv("APEX_TPU_FLASH_FUSED_BWD_MAX_BYTES", "0")
        q, k, v, cos, sin = self._setup()
        self._check_rope_grads(q, k, v, cos, sin,
                               dict(causal=True, block_q=128, block_k=128))

    def test_bhld_layout(self):
        q, k, v, cos, sin = self._setup()
        kw = dict(causal=True, block_q=128, block_k=128)
        ref = self._oracle(q, k, v, cos, sin, **kw)
        qh, kh, vh = (jnp.moveaxis(t, 1, 2) for t in (q, k, v))
        out = flash_attention(qh, kh, vh, layout="bhld", rope=(cos, sin),
                              **kw)
        np.testing.assert_allclose(np.asarray(jnp.moveaxis(out, 1, 2)),
                                   np.asarray(ref), rtol=RTOL, atol=ATOL)

    def test_odd_length_bf16(self):
        """Sequence padding: zero-padded table rows rotate the (already
        zero) padded q/k rows to zero; bf16 tables add the same rounding
        class as bf16 q/k storage."""
        q, k, v, cos, sin = self._setup(l=300, dtype=jnp.bfloat16, seed=3)
        kw = dict(causal=True, block_q=128, block_k=128)
        out = flash_attention(q, k, v, rope=(cos, sin), **kw)
        ref = self._oracle(q, k, v, cos, sin, **kw)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_cross_attention_rejected(self):
        q, k, v, cos, sin = self._setup()
        with pytest.raises(ValueError, match="self-attention"):
            flash_attention(q, k[:, :128], v[:, :128], rope=(cos, sin))

    def test_fp32_defaults_capped_at_512(self, monkeypatch):
        """fp32 + rope caps *defaulted* blocks at 512 (1024-blocks blow
        the scoped-VMEM limit in the fused backward — measured on the
        O0 L2048 train step); explicit requests pass through."""
        from apex_tpu.ops.pallas import flash_attention as fa
        seen = []
        real = fa._flash

        def spy(q, k, v, bias, cos_t, sin_t, scale, causal, bq, bk,
                has_bias, rope_mode, layout):
            seen.append((bq, bk, rope_mode))
            return real(q, k, v, bias, cos_t, sin_t, scale, causal, bq,
                        bk, has_bias, rope_mode, layout)

        monkeypatch.setattr(fa, "_flash", spy)
        l = 2048
        rng = np.random.RandomState(0)
        mk = lambda: jnp.asarray(rng.randn(1, l, 1, D).astype(np.float32))
        q, k, v = mk(), mk(), mk()
        pos = jnp.broadcast_to(jnp.arange(l)[None, :], (1, l))
        from apex_tpu.ops.rope import rope_tables
        cos, sin = rope_tables(pos, D, 10000.0)
        fa.flash_attention(q, k, v, causal=True, rope=(cos, sin))
        assert seen[-1][:2] == (512, 512)
        # bf16 keeps the length-scaled default
        fa.flash_attention(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                           v.astype(jnp.bfloat16), causal=True,
                           rope=(cos, sin))
        assert seen[-1][:2] == (1024, 1024)
        # no rope: fp32 keeps the 1024 default (unchanged behavior)
        fa.flash_attention(q, k, v, causal=True)
        assert seen[-1][:2] == (1024, 1024)
        assert seen[-1][2] is None

    @pytest.mark.slow
    def test_rope_under_shard_map_fallback(self):
        """Off-TPU, a varying-under-shard_map q routes to the jnp
        fallback (interpreter VMA limitation); with rope it must rotate
        out-of-kernel via apply_rope_tables and still match the
        pre-rotated oracle — the data-parallel GPT step hits exactly
        this path in the CPU dryruns."""
        import numpy as onp
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map
        # 2-way data mesh on CPU (8 virtual devices); on the one-chip
        # TPU a 1-device mesh still compiles flash+rope under shard_map
        # (the kernel path — hardware coverage the fallback test line
        # can't get), so the test adapts instead of skipping.
        devs = jax.devices()[:min(2, len(jax.devices()))]
        q, k, v, cos, sin = self._setup(l=256)
        kw = dict(causal=True, block_q=128, block_k=128)
        ref = self._oracle(q, k, v, cos, sin, **kw)
        mesh = Mesh(onp.array(devs), ("data",))

        def fwd(q, k, v, cos, sin):
            return flash_attention(q, k, v, rope=(cos, sin), **kw)

        out = shard_map(
            fwd, mesh=mesh,
            in_specs=(P("data"), P("data"), P("data"), P("data"),
                      P("data")),
            out_specs=P("data"))(q, k, v, cos, sin)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=GTOL, atol=GTOL)

    def test_dispatcher_passthrough_and_seq_parallel_rejection(self):
        from apex_tpu.attention import attention
        q, k, v, cos, sin = self._setup(l=256)
        kw = dict(causal=True, block_q=128, block_k=128)
        ref = self._oracle(q, k, v, cos, sin, **kw)
        out = attention(q, k, v, impl="flash", causal=True,
                        block_q=128, block_k=128, rope=(cos, sin))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=RTOL, atol=ATOL)
        # jnp local path rotates out-of-kernel, same convention
        out_jnp = attention(q, k, v, impl="jnp", causal=True,
                            rope=(cos, sin))
        np.testing.assert_allclose(np.asarray(out_jnp), np.asarray(ref),
                                   rtol=GTOL, atol=GTOL)
        with pytest.raises(ValueError, match="axis_name"):
            attention(q, k, v, axis_name="seq", rope=(cos, sin))
        # cross-attention + rope raises the same clear error on the jnp
        # fallback as on the kernel path
        with pytest.raises(ValueError, match="self-attention"):
            attention(q, k[:, :128], v[:, :128], impl="jnp",
                      rope=(cos, sin))


@pytest.mark.parametrize("bq,bk", [(64, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_mask", [False, True])
def test_unequal_blocks_fuzz(bq, bk, causal, use_mask):
    """Sweep the (causal x has_bias x block-shape) kernel dispatch matrix
    with UNEQUAL q/k blocks: the straddle predicate, the exp-underflow
    masked-entry zeroing, and the no-bias fast path must all hold when
    a block can contain rows with zero visible keys (bq > bk) or keys
    spanning several diagonals (bk > bq).  Forward and gradients vs the
    jnp oracle; L=192 pads to lcm(bq, bk).  (Block sizes must be legal
    post-round-up — block_k below 128 is silently raised to 128, so
    bq > bk regimes use bq = 256.)"""
    l = 192
    q, k, v = _qkv(l=l, seed=7)
    mask = None
    if use_mask:
        rng = np.random.RandomState(2)
        mask = jnp.asarray(rng.rand(B, l) > 0.3).at[:, 0].set(True)

    out = flash_attention(q, k, v, causal=causal, kv_mask=mask,
                          block_q=bq, block_k=bk)
    ref = ref_attn(q, k, v, causal=causal, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=RTOL, atol=ATOL)
    _check_grads(q, k, v, causal, mask, block_q=bq, block_k=bk)
