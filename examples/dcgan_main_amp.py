"""BASELINE config 5: DCGAN with two optimizers and two loss scalers.

The workload the reference's stub ``examples/dcgan`` was meant to carry: a
generator and a discriminator, each with its own optimizer, trained with
*independent* dynamic loss scalers — the ``num_losses`` / ``loss_id``
machinery (``apex/amp/handle.py:53-58``).  Here each network gets its own
:class:`~apex_tpu.amp.Amp` (the functional analog of two loss_ids), so an
overflow in D's backward never shrinks G's scale.
"""

# Make the repo root importable when run as "python examples/<name>.py"
# without an install (the environment forbids pip install).
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import jax.numpy as jnp
import optax

from apex_tpu import amp
from apex_tpu.models.dcgan import Discriminator, Generator, gan_losses


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--zdim", type=int, default=100)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--image-size", type=int, default=32,
                   choices=[32, 64])
    p.add_argument("--print-freq", type=int, default=20)
    return p.parse_args()


def main():
    args = parse_args()
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    n_up = {32: 2, 64: 3}[args.image_size]
    G = Generator(feature_maps=64, n_upsample=n_up)
    D = Discriminator(feature_maps=64, n_down=n_up + 1)

    kz = jax.random.PRNGKey(0)
    z0 = jax.random.normal(kz, (2, args.zdim))
    img0 = jnp.zeros((2, args.image_size, args.image_size, 3))
    gv = G.init(jax.random.PRNGKey(1), z0, train=True)
    dv = D.init(jax.random.PRNGKey(2), img0, train=True)

    adam = lambda: optax.adam(args.lr, b1=0.5, b2=0.999)
    a_g = amp.initialize(optimizer=adam(), opt_level=args.opt_level)
    a_d = amp.initialize(optimizer=adam(), opt_level=args.opt_level)
    gs, ds = a_g.init(gv["params"]), a_d.init(dv["params"])
    g_stats, d_stats = gv["batch_stats"], dv["batch_stats"]

    # Stats are *closed over* (never passed through Amp.run's arg caster) so
    # keep_batchnorm_fp32 holds: running buffers stay fp32 under O2/O3.
    # Update cadence matches the reference DCGAN loop: per iteration G's BN
    # stats update once (G's own forward in the G step; the fake used by D
    # is a stats-frozen forward) while D's update three times (real + fake
    # in the D step, fake again in the G step).
    def make_d_loss(g_stats, d_stats):
        def d_loss(dp, gp, z, real):
            fake = G.apply({"params": gp, "batch_stats": g_stats}, z,
                           train=True, mutable=["batch_stats"])[0]
            d_real, d_mut = D.apply(
                {"params": dp, "batch_stats": d_stats}, real,
                train=True, mutable=["batch_stats"])
            d_fake, d_mut = D.apply(
                {"params": dp, "batch_stats": d_mut["batch_stats"]},
                jax.lax.stop_gradient(fake), train=True,
                mutable=["batch_stats"])
            loss, _ = gan_losses(d_real, d_fake, d_fake)
            return loss, d_mut["batch_stats"]
        return d_loss

    def make_g_loss(g_stats, d_stats):
        def g_loss(gp, dp, z):
            fake, g_mut = G.apply({"params": gp, "batch_stats": g_stats},
                                  z, train=True, mutable=["batch_stats"])
            logits, d_mut = D.apply({"params": dp, "batch_stats": d_stats},
                                    fake, train=True,
                                    mutable=["batch_stats"])
            _, loss = gan_losses(logits, logits, logits)
            return loss, (g_mut["batch_stats"], d_mut["batch_stats"])
        return g_loss

    @jax.jit
    def train_step(gs, ds, g_stats, d_stats, z, real):
        # D step (loss_id 0 of the reference's shared-model two-scaler run)
        def scaled_d(dp):
            l, stats = a_d.run(make_d_loss(g_stats, d_stats), dp,
                               a_g.model_params(gs), z, real)
            return a_d.scale_loss(l, ds), (l, stats)
        d_grads, (dl, d_stats_) = \
            jax.grad(scaled_d, has_aux=True)(a_d.model_params(ds))
        ds, d_info = a_d.apply_gradients(ds, d_grads)

        # G step (loss_id 1)
        def scaled_g(gp):
            l, stats = a_g.run(make_g_loss(g_stats, d_stats_), gp,
                               a_d.model_params(ds), z)
            return a_g.scale_loss(l, gs), (l, stats)
        g_grads, (gl, (g_stats_, d_stats_)) = \
            jax.grad(scaled_g, has_aux=True)(a_g.model_params(gs))
        gs, g_info = a_g.apply_gradients(gs, g_grads)
        return gs, ds, g_stats_, d_stats_, dl, gl, d_info, g_info

    for i in range(args.steps):
        k = jax.random.PRNGKey(100 + i)
        z = jax.random.normal(k, (args.batch_size, args.zdim))
        # synthetic "real" images: smooth blobs
        real = jnp.tanh(jax.random.normal(
            k, (args.batch_size, args.image_size, args.image_size, 3)))
        gs, ds, g_stats, d_stats, dl, gl, d_info, g_info = train_step(
            gs, ds, g_stats, d_stats, z, real)
        if i % args.print_freq == 0 or i == args.steps - 1:
            print(f"step {i:4d}  D {float(dl):.4f} G {float(gl):.4f}  "
                  f"scales D {float(d_info['loss_scale']):.0f} "
                  f"G {float(g_info['loss_scale']):.0f}")


if __name__ == "__main__":
    main()
