"""GPT causal-LM tests: the long-context / sequence-parallel workload.

Beyond the reference (SURVEY.md section 5.7: apex has no long-context
story); checks causality, rope position handling under sequence sharding,
scan/remat equivalence, ring-attention equivalence on the virtual mesh,
and an amp-O2 training run.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import GPTModel, gpt_tiny, lm_loss
from apex_tpu.optimizers import FusedAdam
from jax import shard_map

B, L = 2, 32


def data(vocab):
    rng = np.random.RandomState(0)
    return jnp.asarray(rng.randint(0, vocab, (B, L)))


class TestGPT:
    def setup_method(self, _):
        self.cfg = gpt_tiny()
        self.model = GPTModel(self.cfg)
        self.ids = data(self.cfg.vocab_size)
        self.vars = self.model.init(jax.random.PRNGKey(0), self.ids)

    def test_forward_shape(self):
        logits = self.model.apply(self.vars, self.ids)
        assert logits.shape == (B, L, self.cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())

    def test_causality(self):
        """Changing a future token must not change earlier logits."""
        logits = self.model.apply(self.vars, self.ids)
        ids2 = self.ids.at[:, L // 2:].set(
            (self.ids[:, L // 2:] + 1) % self.cfg.vocab_size)
        logits2 = self.model.apply(self.vars, ids2)
        np.testing.assert_allclose(
            np.asarray(logits[:, :L // 2]),
            np.asarray(logits2[:, :L // 2]), rtol=1e-5, atol=1e-5)
        assert not np.allclose(np.asarray(logits[:, -1]),
                               np.asarray(logits2[:, -1]))

    def test_scan_and_remat_match_loop(self):
        logits = self.model.apply(self.vars, self.ids)
        p = dict(self.vars["params"])
        blocks = [p.pop(f"block_{i}") for i in range(self.cfg.num_layers)]
        p["layers"] = {"block": jax.tree.map(
            lambda *xs: jnp.stack(xs), *blocks)}
        stacked = {"params": p}
        for remat in (False, True):
            cfg = dc.replace(self.cfg, scan_layers=True, remat=remat)
            got = GPTModel(cfg).apply(stacked, self.ids)
            np.testing.assert_allclose(np.asarray(got), np.asarray(logits),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_sequence_parallel_matches_local(self):
        """Ring attention over a ("seq",) mesh with global rope positions
        reproduces the single-device logits."""
        sp = 4
        devs = jax.devices()[:sp]
        if len(devs) < sp:
            pytest.skip("needs 4 devices")
        mesh = Mesh(np.array(devs), ("seq",))
        cfg_sp = dc.replace(self.cfg, seq_axis_name="seq")
        model_sp = GPTModel(cfg_sp)
        local = self.model.apply(self.vars, self.ids)

        def fwd(v, ids_shard, pos_shard):
            return model_sp.apply(v, ids_shard, positions=pos_shard)

        positions = jnp.broadcast_to(jnp.arange(L)[None, :], (B, L))
        sharded = shard_map(
            fwd, mesh=mesh,
            in_specs=(P(), P(None, "seq"), P(None, "seq")),
            out_specs=P(None, "seq"))(self.vars, self.ids, positions)
        np.testing.assert_allclose(np.asarray(sharded), np.asarray(local),
                                   rtol=2e-3, atol=2e-3)

    def test_amp_o2_training_descends(self):
        a = amp.initialize(optimizer=FusedAdam(lr=3e-3), opt_level="O2",
                           verbosity=0)
        state = a.init(self.vars["params"])

        def loss_fn(p, ids):
            logits = self.model.apply({"params": p}, ids)
            return lm_loss(logits[:, :-1], ids[:, 1:])

        step = jax.jit(amp.make_train_step(a, loss_fn))
        losses = []
        for _ in range(8):
            state, m = step(state, self.ids)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_lm_loss_mask(self):
        logits = self.model.apply(self.vars, self.ids)
        full = lm_loss(logits[:, :-1], self.ids[:, 1:])
        mask = jnp.ones((B, L - 1)).at[:, : (L - 1) // 2].set(0.0)
        half = lm_loss(logits[:, :-1], self.ids[:, 1:], mask=mask)
        assert float(full) != float(half)
        assert np.isfinite(float(half))


class TestGPTKernelPathParity:
    """The pallas branch of CausalSelfAttention (split projection +
    flash with in-kernel rope) must reproduce the jnp branch (explicit
    apply_rope + attention dispatcher) — same params, same logits.
    This pins the fused-rope wiring: q/k reach the kernel UNROTATED and
    the rotation happens on VMEM blocks (round-4 fast path)."""

    @pytest.mark.parametrize("num_heads,label", [
        pytest.param(4, "narrow-16", marks=pytest.mark.slow),
        pytest.param(1, "wide-64", marks=pytest.mark.slow)])
    def test_pallas_matches_jnp(self, monkeypatch, num_heads, label):
        cfg = dc.replace(gpt_tiny(), num_heads=num_heads)
        model = GPTModel(cfg)
        ids = data(cfg.vocab_size)

        monkeypatch.setenv("APEX_TPU_KERNELS", "jnp")
        variables = model.init(jax.random.PRNGKey(0), ids)
        logits_jnp = model.apply(variables, ids)

        monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
        logits_pl = model.apply(variables, ids)
        np.testing.assert_allclose(
            np.asarray(logits_pl, np.float32),
            np.asarray(logits_jnp, np.float32),
            rtol=5e-2, atol=5e-2, err_msg=label)

        # gradients through the fused-rope custom VJP agree too
        def loss(v):
            lg = model.apply(v, ids)
            return lm_loss(lg[:, :-1], ids[:, 1:])

        monkeypatch.setenv("APEX_TPU_KERNELS", "jnp")
        g_jnp = jax.grad(loss)(variables)
        monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
        g_pl = jax.grad(loss)(variables)
        for a, b in zip(jax.tree.leaves(g_pl), jax.tree.leaves(g_jnp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-2, atol=5e-2,
                                       err_msg=label)


def test_tpu_head_geometry_same_params():
    """The TPU-native config factories change only the head split:
    head_dim 128 (full MXU lane width) at an identical parameter count
    to the conventional shapes — the claim behind gpt_small_tpu /
    gpt_medium_tpu / bert_large_tpu (docs/source/attention.rst)."""
    from apex_tpu.models.bert import (
        BertForPreTraining, bert_large, bert_large_tpu)
    from apex_tpu.models.gpt import gpt_medium_tpu, gpt_small, gpt_small_tpu

    def n_params(init_fn):
        shapes = jax.eval_shape(init_fn)["params"]
        return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))

    def count(model_cls, cfg):
        m = model_cls(cfg)
        return n_params(lambda: m.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))

    for model_cls, conv, tpu in (
            (GPTModel, gpt_small(), gpt_small_tpu()),
            (BertForPreTraining, bert_large(), bert_large_tpu())):
        assert tpu.hidden_size // tpu.num_heads == 128
        assert count(model_cls, conv) == count(model_cls, tpu)
    med = gpt_medium_tpu()
    assert med.hidden_size // med.num_heads == 128
