"""RNN stack tests.

Port of ``tests/L0/run_amp/test_rnn.py:10-116`` adapted to the scanned-cell
implementation: every cell type forward+backward, stacked and bidirectional
shapes, hidden-state dtype under O1, projection, and an LSTM-vs-flax
reference check.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import flax.linen as fnn

from apex_tpu import amp
from apex_tpu import rnn as apex_rnn

T, B, F, H = 5, 3, 4, 8

# The padded-batch and per-sequence runs take different MXU tilings on
# hardware (bf16-multipass f32 accumulation differs by batch shape), the
# same precision class as the flash-attention suite's on-chip tolerance.
_ON_CPU = jax.default_backend() == "cpu"
VTOL = dict(rtol=1e-5, atol=1e-6) if _ON_CPU else dict(rtol=4e-2, atol=5e-3)


def data(seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(T, B, F)
                       .astype(np.float32))


@pytest.mark.parametrize("mode", [
    pytest.param("relu", marks=pytest.mark.slow), "tanh",
    pytest.param("gru", marks=pytest.mark.slow), "lstm", "mlstm"])
def test_forward_backward(mode):
    model = apex_rnn.RNN(mode=mode, hidden_size=H)
    x = data()
    params = model.init(jax.random.PRNGKey(0), x)
    (ys, finals), grads = jax.value_and_grad(
        lambda p: (lambda o: jnp.sum(o[0] ** 2))(model.apply(p, x)),
        has_aux=False)(params), None
    ys_out, _ = model.apply(params, x)
    assert ys_out.shape == (T, B, H)
    g = jax.grad(lambda p: jnp.sum(model.apply(p, x)[0] ** 2))(params)
    assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))
    assert all(float(jnp.abs(l).max()) > 0 for l in jax.tree.leaves(g)
               if l.ndim == 2)


def test_stacked_bidirectional_shapes():
    model = apex_rnn.LSTM(hidden_size=H, num_layers=3, bidirectional=True)
    x = data()
    params = model.init(jax.random.PRNGKey(0), x)
    ys, finals = model.apply(params, x)
    assert ys.shape == (T, B, 2 * H)
    assert len(finals) == 3
    fin_f, fin_b = finals[0]
    assert fin_f.h.shape == (B, H) and fin_b.c.shape == (B, H)


def test_recurrent_projection():
    model = apex_rnn.LSTM(hidden_size=H, output_size=6)
    x = data()
    params = model.init(jax.random.PRNGKey(0), x)
    ys, finals = model.apply(params, x)
    assert ys.shape == (T, B, 6)
    assert finals[0].h.shape == (B, 6)   # projected h re-enters recurrence
    assert finals[0].c.shape == (B, H)


def test_lstm_matches_flax_reference():
    """Same weights → same outputs as flax's LSTMCell (gate order i,f,g,o)."""
    model = apex_rnn.LSTM(hidden_size=H)
    x = data(1)
    params = model.init(jax.random.PRNGKey(0), x)
    p = params["params"]["layer_0_fwd"]

    cell = fnn.OptimizedLSTMCell(features=H)
    # flax LSTMCell params: ii/if/ig/io (kernel from input), hi/hf/hg/ho
    w_ih = np.asarray(p["w_ih"])  # (F, 4H) order i,f,g,o
    w_hh = np.asarray(p["w_hh"])
    b = np.asarray(p["b_ih"]) + np.asarray(p["b_hh"])
    carry = (jnp.zeros((B, H)), jnp.zeros((B, H)))
    flax_params = {"params": {
        "ii": {"kernel": w_ih[:, 0:H]}, "if": {"kernel": w_ih[:, H:2*H]},
        "ig": {"kernel": w_ih[:, 2*H:3*H]}, "io": {"kernel": w_ih[:, 3*H:]},
        "hi": {"kernel": w_hh[:, 0:H], "bias": b[0:H]},
        "hf": {"kernel": w_hh[:, H:2*H], "bias": b[H:2*H]},
        "hg": {"kernel": w_hh[:, 2*H:3*H], "bias": b[2*H:3*H]},
        "ho": {"kernel": w_hh[:, 3*H:], "bias": b[3*H:]},
    }}
    # flax carry is (c, h)
    c = jnp.zeros((B, H))
    h = jnp.zeros((B, H))
    outs = []
    for t in range(T):
        (c, h), y = cell.apply(flax_params, (c, h), x[t])
        outs.append(y)
    ref = jnp.stack(outs)
    ys, _ = model.apply(params, x)
    np.testing.assert_allclose(np.asarray(ys), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_o1_casts_rnn_matmuls():
    """Under an O1 cast context the recurrence runs in bf16
    (the rnn_compat capability: RNN compute follows the policy)."""
    model = apex_rnn.GRU(hidden_size=H)
    x = data()
    params = model.init(jax.random.PRNGKey(0), x)
    with amp.cast_context(amp.O1()):
        ys, _ = model.apply(params, x)
    assert ys.dtype == jnp.bfloat16
    ys32, _ = model.apply(params, x)
    np.testing.assert_allclose(np.asarray(ys, np.float32), np.asarray(ys32),
                               atol=0.05)


def test_initial_state_passthrough():
    model = apex_rnn.Tanh(hidden_size=H)
    x = data()
    params = model.init(jax.random.PRNGKey(0), x)
    h0 = jnp.ones((B, H))
    ys, finals = model.apply(params, x, [h0])
    ys_zero, _ = model.apply(params, x)
    assert not np.allclose(np.asarray(ys[0]), np.asarray(ys_zero[0]))


@pytest.mark.parametrize("mode", ["tanh", "gru", "lstm"])
def test_variable_length_matches_per_sequence(mode):
    """The PackedSequence analog (reference test_rnn.py:104-116): a padded
    batch with seq_lengths must match running each sequence unpadded, with
    zero outputs in the padded region and final state at t = length-1."""
    model = apex_rnn.RNN(mode=mode, hidden_size=H)
    x = data()
    lengths = jnp.asarray([T, 3, 1], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)
    ys, finals = model.apply(params, x, seq_lengths=lengths)
    assert ys.shape == (T, B, H)
    for b in range(B):
        L = int(lengths[b])
        ys_b, fin_b = model.apply(params, x[:L, b:b + 1, :])
        np.testing.assert_allclose(np.asarray(ys[:L, b]),
                                   np.asarray(ys_b[:, 0]), **VTOL)
        # padded region is zero
        np.testing.assert_array_equal(np.asarray(ys[L:, b]), 0.0)
        # final state matches the unpadded run's final state
        fin_full = jax.tree.leaves(finals[0])
        fin_solo = jax.tree.leaves(fin_b[0])
        for lf, ls in zip(fin_full, fin_solo):
            np.testing.assert_allclose(np.asarray(lf[b]), np.asarray(ls[0]),
                                       **VTOL)


def test_variable_length_bidirectional():
    """Reverse direction processes x[L-1]..x[0] per sequence — the padded
    tail contributes nothing (pad_packed_sequence semantics)."""
    model = apex_rnn.RNN(mode="gru", hidden_size=H, bidirectional=True)
    x = data()
    lengths = jnp.asarray([T, 3, 2], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)
    ys, _ = model.apply(params, x, seq_lengths=lengths)
    assert ys.shape == (T, B, 2 * H)
    for b in range(B):
        L = int(lengths[b])
        ys_b, _ = model.apply(params, x[:L, b:b + 1, :])
        np.testing.assert_allclose(np.asarray(ys[:L, b]),
                                   np.asarray(ys_b[:, 0]), **VTOL)
        np.testing.assert_array_equal(np.asarray(ys[L:, b]), 0.0)


def test_variable_length_grads_flow_only_through_valid_steps():
    model = apex_rnn.RNN(mode="lstm", hidden_size=H)
    x = data()
    lengths = jnp.asarray([T, 3, 1], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), x)

    def loss(xin):
        ys, _ = model.apply(params, xin, seq_lengths=lengths)
        return jnp.sum(ys ** 2)

    gx = jax.grad(loss)(x)
    # no gradient reaches padded inputs
    for b in range(B):
        L = int(lengths[b])
        np.testing.assert_array_equal(np.asarray(gx[L:, b]), 0.0)
        assert float(jnp.abs(gx[:L, b]).max()) > 0
