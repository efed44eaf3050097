"""KV-cached generation vs naive full-forward decoding.

The oracle re-runs ``GPTModel.apply`` on the whole growing sequence
every step (no cache) and takes the last-position argmax; the cached
decoder must produce the IDENTICAL token sequence (and matching final
logits) from the same training checkpoint — this pins the manual layer
math (fused LN, rope positions, fp32 softmax, gelu flavor), the cache
write offsets, and the decode-time causal mask all at once.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, gpt_tiny
from apex_tpu.models.generate import generate

B, L_PROMPT, NEW = 2, 12, 8


@pytest.fixture(scope="module")
def setup():
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, L_PROMPT)))
    params = model.init(jax.random.PRNGKey(1), prompt)["params"]
    return cfg, model, params, prompt


def _naive_generate(model, params, prompt, steps):
    ids = prompt
    for _ in range(steps):
        logits = model.apply({"params": params}, ids)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(ids.dtype)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return ids


@pytest.mark.slow
def test_greedy_matches_naive_full_forward(setup):
    cfg, model, params, prompt = setup
    want = _naive_generate(model, params, prompt, NEW)
    got = generate(params, cfg, prompt, NEW)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_scan_layout_checkpoint(setup):
    """Scan-layout params (stacked ``layers/block``) decode to the same
    tokens as the loop layout they were stacked from."""
    cfg, model, params, prompt = setup
    p = dict(params)
    blocks = [p.pop(f"block_{i}") for i in range(cfg.num_layers)]
    p["layers"] = {"block": jax.tree.map(lambda *xs: jnp.stack(xs),
                                         *blocks)}
    want = generate(params, cfg, prompt, NEW)
    got = generate(p, cfg, prompt, NEW)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_temperature_sampling_deterministic_and_varied(setup):
    cfg, _, params, prompt = setup
    a = generate(params, cfg, prompt, NEW, temperature=1.0,
                 rng=jax.random.PRNGKey(7))
    b = generate(params, cfg, prompt, NEW, temperature=1.0,
                 rng=jax.random.PRNGKey(7))
    c = generate(params, cfg, prompt, NEW, temperature=1.0,
                 rng=jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    # prompts are preserved verbatim
    np.testing.assert_array_equal(np.asarray(a[:, :L_PROMPT]),
                                  np.asarray(prompt))
    with pytest.raises(ValueError, match="rng"):
        generate(params, cfg, prompt, NEW, temperature=0.7)


def test_single_token_decode(setup):
    cfg, model, params, prompt = setup
    want = _naive_generate(model, params, prompt, 1)
    got = generate(params, cfg, prompt, 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_tpu_head_geometry_config():
    """Wide heads (d=128 class for the tiny scale) decode exactly too —
    the geometry the TPU configs use."""
    cfg = dc.replace(gpt_tiny(), num_heads=2)
    model = GPTModel(cfg)
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, cfg.vocab_size, (1, 6)))
    params = model.init(jax.random.PRNGKey(2), prompt)["params"]
    want = _naive_generate(model, params, prompt, 5)
    got = generate(params, cfg, prompt, 5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prefill_accepts_concrete_zero_start(setup):
    """The multi-token prefill guard takes a CONCRETE 0-d zero, not just
    Python ``int`` 0 — a caller that keeps its position counter
    on-device (``jnp.int32(0)``) must hit the flash-prefill path, not a
    spurious NotImplementedError.  Logits must match the int-0 call."""
    from apex_tpu.models.generate import (_forward_cached,
                                          _stack_layer_params)
    cfg, _, params, prompt = setup
    stacked = _stack_layer_params(params, cfg.num_layers)
    top = {k: v for k, v in params.items() if not k.startswith("block_")}
    head_dim = cfg.hidden_size // cfg.num_heads
    m = L_PROMPT + 2

    def caches():
        kc = jnp.zeros((cfg.num_layers, B, m, cfg.num_heads, head_dim),
                       jnp.float32)
        return kc, jnp.zeros_like(kc)

    kc, vc = caches()
    want, _, _, _, _ = _forward_cached(top, stacked, cfg, prompt,
                                       kc, vc, start=0)
    kc, vc = caches()
    got, _, _, _, _ = _forward_cached(top, stacked, cfg, prompt,
                                      kc, vc, start=jnp.int32(0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)


def _prefill_fixture(setup, m):
    from apex_tpu.models.generate import _stack_layer_params
    cfg, _, params, prompt = setup
    stacked = _stack_layer_params(params, cfg.num_layers)
    top = {k: v for k, v in params.items() if not k.startswith("block_")}
    head_dim = cfg.hidden_size // cfg.num_heads

    def caches():
        kc = jnp.zeros((cfg.num_layers, B, m, cfg.num_heads, head_dim),
                       jnp.float32)
        return kc, jnp.zeros_like(kc)

    return cfg, top, stacked, prompt, caches


def test_chunked_prefill_matches_full_prefill_and_decode(setup):
    """A prompt appended in multi-token chunks at traced mid-sequence
    ``start`` values (the serve engine's admission path) must land the
    SAME cache contents bitwise and matching final logits as the
    one-shot flash prefill — the chunk attends to the cached history
    plus causally to itself through the einsum path — and the greedy
    token it implies must equal solo ``generate``'s (mid-stream
    admission cannot perturb decode)."""
    from apex_tpu.models.generate import _forward_cached
    cfg, _, params, prompt = setup
    m = L_PROMPT + 2
    _, top, stacked, _, caches = _prefill_fixture(setup, m)

    kc, vc = caches()
    want, kc_w, vc_w, _, _ = _forward_cached(top, stacked, cfg, prompt,
                                             kc, vc, start=0)
    kc, vc = caches()
    got = None
    for j in range(0, L_PROMPT, 4):
        got, kc, vc, _, _ = _forward_cached(top, stacked, cfg,
                                            prompt[:, j:j + 4], kc, vc,
                                            start=jnp.int32(j))
    # cache contents are pure data movement + the same per-position
    # math: bitwise equal.  Logits of the last chunk row go through a
    # different attention SHAPE (4-row einsum vs full flash prefill),
    # so they match to fp tolerance, not bitwise.
    np.testing.assert_array_equal(np.asarray(kc), np.asarray(kc_w))
    np.testing.assert_array_equal(np.asarray(vc), np.asarray(vc_w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    first = jnp.argmax(got, axis=-1)
    solo = generate(params, cfg, prompt, 1)
    np.testing.assert_array_equal(np.asarray(first),
                                  np.asarray(solo[:, L_PROMPT]))
