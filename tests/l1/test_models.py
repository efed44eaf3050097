"""L1-style end-to-end workload tests at test scale.

Each of the five BASELINE configs gets a miniature end-to-end run: forward,
backward, one or more amp train steps, loss finite and decreasing where
meaningful.  The full-scale entry points live in ``examples/``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models import (
    BertForPreTraining,
    BertModel,
    Discriminator,
    Generator,
    ResNet18,
    bert_tiny,
    gan_losses,
    pretraining_loss,
)
from apex_tpu.optimizers import FusedAdam, fused_lamb
from apex_tpu.parallel import DistributedDataParallel, data_parallel_mesh
from jax import shard_map


class TestResNet:
    def setup_method(self, _):
        self.model = ResNet18(num_classes=10, width=16)
        self.x = jnp.asarray(np.random.RandomState(0)
                             .randn(4, 32, 32, 3).astype(np.float32))

    # The ResNet-50 variants sum to ~50s of jit compiles on the 2-vCPU
    # tier-1 box (ROADMAP wall-clock item): the Bottleneck gradient run
    # and the full O2 FusedAdam step are slow-marked; the S2D stem
    # variant (Bottleneck-based, ~2s) and the ResNet18 forward/cast
    # tests stay tier-1 as the fast representatives.
    @pytest.mark.slow
    def test_bottleneck_variant_trains(self):
        """Small-scale coverage of the Bottleneck block — the block of the
        flagship ResNet-50 — since ResNet18 is BasicBlock-based."""
        from apex_tpu.models.resnet import ResNet50
        model = ResNet50(num_classes=10, width=8)
        x = self.x
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        logits, updated = model.apply(
            variables, x, train=True, mutable=["batch_stats"])
        assert logits.shape == (4, 10)
        assert bool(jnp.isfinite(logits).all())
        g = jax.grad(lambda p: jnp.sum(model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, x,
            train=True, mutable=["batch_stats"])[0]))(variables["params"])
        assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))

    @pytest.mark.slow
    def test_s2d_stem_variant(self):
        """The TPU-native space-to-depth stem keeps the stage geometry
        (same output head, spatial/4 stem output) and trains; non-
        divisible spatial dims fail loudly."""
        from apex_tpu.models.resnet import ResNet50S2D
        model = ResNet50S2D(num_classes=10, width=8)
        x = self.x
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        # stem conv runs on the 16x-channel space-to-depth reshuffle
        assert variables["params"]["stem_conv"]["kernel"].shape == \
            (2, 2, 48, 8)
        logits, _ = model.apply(variables, x, train=True,
                                mutable=["batch_stats"])
        assert logits.shape == (4, 10)
        assert bool(jnp.isfinite(logits).all())
        with pytest.raises(ValueError, match="divisible by 4"):
            model.init(jax.random.PRNGKey(0), x[:, :30], train=True)

    def init(self):
        return self.model.init(jax.random.PRNGKey(0), self.x, train=True)

    def test_forward_shapes_and_stats(self):
        variables = self.init()
        logits, updated = self.model.apply(
            variables, self.x, train=True, mutable=["batch_stats"])
        assert logits.shape == (4, 10)
        assert bool(jnp.isfinite(logits).all())
        # running stats moved
        stem_mean = updated["batch_stats"]["stem_bn"]["mean"]
        assert float(jnp.abs(stem_mean).max()) > 0

    @pytest.mark.slow
    def test_o2_train_step_with_fused_adam(self):
        variables = self.init()
        params, batch_stats = variables["params"], variables["batch_stats"]
        a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level="O2",
                           verbosity=0)
        state = a.init(params)
        y = jnp.asarray(np.random.RandomState(1).randint(0, 10, (4,)))

        def loss_fn(p, x, y):
            logits, _ = self.model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

        step = jax.jit(amp.make_train_step(a, loss_fn))
        losses = []
        for _ in range(3):
            state, m = step(state, self.x, y)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_bn_params_stay_fp32_under_o2(self):
        variables = self.init()
        a = amp.initialize(optimizer=optax.sgd(0.1), opt_level="O2",
                           verbosity=0)
        state = a.init(variables["params"])
        compute = a.model_params(state)
        stem_bn_scale = compute["stem_bn"]["scale"]
        conv_kernel = compute["stem_conv"]["kernel"]
        assert stem_bn_scale.dtype == jnp.float32   # keep_batchnorm_fp32
        assert conv_kernel.dtype == jnp.bfloat16

    @pytest.mark.slow
    def test_sync_bn_conversion_and_ddp_step(self):
        from apex_tpu.parallel import convert_syncbn_model
        # first 8 devices: the x8 batch shards over an 8-wide mesh
        mesh = data_parallel_mesh(num_devices=8)
        sync_model = convert_syncbn_model(self.model, axis_name="data")
        assert sync_model.bn_axis_name == "data"
        variables = sync_model.init(jax.random.PRNGKey(0), self.x, train=True)
        x8 = jnp.asarray(np.random.RandomState(2)
                         .randn(8, 32, 32, 3).astype(np.float32))

        def fwd(v, xb):
            logits, _ = sync_model.apply(v, xb, train=True,
                                         mutable=["batch_stats"])
            return logits

        logits = shard_map(
            fwd, mesh=mesh, in_specs=(P(), P("data")),
            out_specs=P("data"))(variables, x8)
        assert logits.shape == (8, 10)
        assert bool(jnp.isfinite(logits).all())


class TestBert:
    def setup_method(self, _):
        self.cfg = bert_tiny()
        self.model = BertForPreTraining(self.cfg)
        rng = np.random.RandomState(0)
        B, L = 2, 16
        self.ids = jnp.asarray(rng.randint(0, self.cfg.vocab_size, (B, L)))
        self.mask = jnp.ones((B, L), jnp.int32)
        self.mlm_labels = jnp.asarray(
            rng.randint(0, self.cfg.vocab_size, (B, L)))
        self.mlm_mask = jnp.asarray((rng.rand(B, L) < 0.15)
                                    .astype(np.float32))
        self.nsp = jnp.asarray(rng.randint(0, 2, (B,)))

    def test_forward(self):
        variables = self.model.init(jax.random.PRNGKey(0), self.ids,
                                    attention_mask=self.mask)
        mlm, nsp = self.model.apply(variables, self.ids,
                                    attention_mask=self.mask)
        assert mlm.shape == (2, 16, self.cfg.vocab_size)
        assert nsp.shape == (2, 2)

    def test_lamb_pretraining_steps(self):
        variables = self.model.init(jax.random.PRNGKey(0), self.ids,
                                    attention_mask=self.mask)
        a = amp.initialize(optimizer=fused_lamb(learning_rate=1e-3),
                           opt_level="O2", verbosity=0)
        state = a.init(variables["params"])

        def loss_fn(p, ids, mask, mlm_labels, mlm_mask, nsp):
            mlm, nspl = self.model.apply({"params": p}, ids,
                                         attention_mask=mask)
            return pretraining_loss(mlm, nspl, mlm_labels=mlm_labels,
                                    nsp_labels=nsp, mlm_mask=mlm_mask)

        step = jax.jit(amp.make_train_step(a, loss_fn))
        losses = []
        for _ in range(3):
            state, m = step(state, self.ids, self.mask, self.mlm_labels,
                            self.mlm_mask, self.nsp)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]


class TestDCGAN:
    @pytest.mark.slow
    def test_two_loss_scaler_training(self):
        """The num_losses=2 machinery: independent scalers for G and D."""
        G, D = Generator(feature_maps=8, n_upsample=1), \
            Discriminator(feature_maps=8, n_down=2)
        rng = np.random.RandomState(0)
        z = jnp.asarray(rng.randn(4, 16).astype(np.float32))
        real = jnp.asarray(rng.rand(4, 16, 16, 3).astype(np.float32) * 2 - 1)

        gv = G.init(jax.random.PRNGKey(0), z, train=True)
        dv = D.init(jax.random.PRNGKey(1), real, train=True)

        a_g = amp.initialize(optimizer=optax.adam(2e-4), opt_level="O1",
                             verbosity=0)
        a_d = amp.initialize(optimizer=optax.adam(2e-4), opt_level="O1",
                             verbosity=0)
        gs = a_g.init(gv["params"])
        ds = a_d.init(dv["params"])

        def d_loss_fn(dp, gp):
            fake = G.apply({"params": gp, "batch_stats": gv["batch_stats"]},
                           z, train=True, mutable=["batch_stats"])[0]
            d_real = D.apply({"params": dp, "batch_stats": dv["batch_stats"]},
                             real, train=True, mutable=["batch_stats"])[0]
            d_fake = D.apply({"params": dp, "batch_stats": dv["batch_stats"]},
                             fake, train=True, mutable=["batch_stats"])[0]
            d_loss, _ = gan_losses(d_real, d_fake, d_fake)
            return d_loss

        def g_loss_fn(gp, dp):
            fake = G.apply({"params": gp, "batch_stats": gv["batch_stats"]},
                           z, train=True, mutable=["batch_stats"])[0]
            g_logits = D.apply(
                {"params": dp, "batch_stats": dv["batch_stats"]},
                fake, train=True, mutable=["batch_stats"])[0]
            _, g_loss = gan_losses(g_logits, g_logits, g_logits)
            return g_loss

        @jax.jit
        def step(gs, ds):
            d_grads = jax.grad(
                lambda dp: a_d.scaler.scale_loss(
                    d_loss_fn(dp, a_g.model_params(gs)),
                    ds.scaler_states[0]))(a_d.model_params(ds))
            ds2, d_info = a_d.apply_gradients(ds, d_grads)
            g_grads = jax.grad(
                lambda gp: a_g.scaler.scale_loss(
                    g_loss_fn(gp, a_d.model_params(ds2)),
                    gs.scaler_states[0]))(a_g.model_params(gs))
            gs2, g_info = a_g.apply_gradients(gs, g_grads)
            return gs2, ds2, d_info, g_info

        for _ in range(2):
            gs, ds, d_info, g_info = step(gs, ds)
        assert not bool(d_info["overflow"])
        assert not bool(g_info["overflow"])
        # scalers advanced independently
        assert float(ds.scaler_states[0].loss_scale) == 2.0 ** 16
        assert float(gs.scaler_states[0].loss_scale) == 2.0 ** 16


class TestBertScanRemat:
    """scan_layers / remat variants must match the unrolled loop exactly in
    values and gradients (scan reuses the same per-layer math; remat only
    changes the backward schedule, not the numbers)."""

    def _outputs_and_grads(self, cfg, params_loop=None):
        model = BertModel(cfg)
        ids = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (2, 16)))
        if params_loop is None:
            variables = model.init(jax.random.PRNGKey(0), ids)
        else:
            variables = params_loop
        y = model.apply(variables, ids)

        def loss(v):
            return jnp.sum(model.apply(v, ids).astype(jnp.float32) ** 2)

        g = jax.grad(loss)(variables)
        return variables, y, g

    @staticmethod
    def _stack_loop_params(params, num_layers):
        """Rearrange layer_{i} param trees into the scanned stacked layout
        (layers/layer/... with a leading layer axis)."""
        p = dict(params["params"])
        layers = [p.pop(f"layer_{i}") for i in range(num_layers)]
        p["layers"] = {"layer": jax.tree.map(
            lambda *xs: jnp.stack(xs), *layers)}
        return {"params": p}

    @pytest.mark.slow
    def test_scan_and_remat_match_loop(self):
        import dataclasses as dc
        cfg_loop = dc.replace(bert_tiny(), scan_layers=False)
        v_loop, y_loop, g_loop = self._outputs_and_grads(cfg_loop)

        # remat on the unrolled loop: same params tree, same numbers
        cfg_lr = dc.replace(bert_tiny(), scan_layers=False, remat=True)
        _, y_lr, g_lr = self._outputs_and_grads(cfg_lr, v_loop)
        np.testing.assert_allclose(np.asarray(y_lr), np.asarray(y_loop),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(g_lr), jax.tree.leaves(g_loop)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-3, atol=1e-4)

        for remat in (False, True):
            cfg = dc.replace(bert_tiny(), scan_layers=True, remat=remat)
            v = self._stack_loop_params(v_loop, cfg.num_layers)
            _, y, g = self._outputs_and_grads(cfg, v)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_loop),
                                       rtol=1e-5, atol=1e-5)
            g_restacked = self._stack_loop_params(g_loop, cfg.num_layers)
            for a, b in zip(jax.tree.leaves(g),
                            jax.tree.leaves(g_restacked)):
                # scan vs unrolled reassociates reductions: near-zero grad
                # elements wobble at ~1e-5 absolute; structure must agree
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-3, atol=1e-4)


class TestBertHeadWidthDispatch:
    """SelfAttention dispatches by head width under the kernel gate:
    narrow heads ride the head-major layout, wide heads (>= 128) the
    split+flash path — both must match the jnp einsum branch (which the
    tiny default configs alone never check for the wide branch)."""

    @pytest.mark.parametrize("num_heads,label", [(4, "narrow-32"),
                                                 (1, "wide-128")])
    def test_pallas_branches_match_jnp(self, monkeypatch, num_heads,
                                       label):
        import dataclasses as dc
        cfg = dc.replace(bert_tiny(), num_heads=num_heads)
        model = BertForPreTraining(cfg)
        rng = np.random.RandomState(3)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 16)))
        mask = jnp.ones((2, 16), jnp.int32).at[:, -3:].set(0)

        monkeypatch.setenv("APEX_TPU_KERNELS", "jnp")
        variables = model.init(jax.random.PRNGKey(0), ids,
                               attention_mask=mask)
        mlm_jnp, _ = model.apply(variables, ids, attention_mask=mask)

        monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
        mlm_pl, _ = model.apply(variables, ids, attention_mask=mask)
        np.testing.assert_allclose(
            np.asarray(mlm_pl, np.float32), np.asarray(mlm_jnp, np.float32),
            rtol=5e-2, atol=5e-2, err_msg=label)
