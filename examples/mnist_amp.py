"""BASELINE config 1: MNIST-scale MLP with amp O1.

Port of the reference's ``examples/simple`` role: the smallest end-to-end
amp workload.  Accepts the amp flags as argparse strings exactly like the
reference examples (``frontend.py:74-92`` parses "dynamic"/"True" directly).

Run (any backend):
    python examples/mnist_amp.py --opt-level O1 --steps 200
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
import numpy as np
import optax

from apex_tpu import amp
from apex_tpu.models.mlp import MLP, cross_entropy_loss


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O1")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--print-freq", type=int, default=50)
    p.add_argument("--deterministic", action="store_true")
    return p.parse_args()


def synthetic_mnist(key, n, batch):
    """Deterministic synthetic MNIST-shaped data (class-dependent means so
    the model has something to learn)."""
    ks = jax.random.split(key, 2)
    y = jax.random.randint(ks[0], (n, batch), 0, 10)
    centers = jax.random.normal(ks[1], (10, 784)) * 0.5
    x = centers[y] + 0.3 * jax.random.normal(ks[0], (n, batch, 784))
    return x, y


def main():
    args = parse_args()
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    model = MLP(features=(256, 256))
    key = jax.random.PRNGKey(0 if args.deterministic else int(time.time()))
    params = model.init(key, jnp.zeros((1, 784)))["params"]

    a = amp.initialize(optimizer=optax.sgd(args.lr),
                       opt_level=args.opt_level, loss_scale=args.loss_scale)
    state = a.init(params)
    step = jax.jit(amp.make_train_step(
        a, lambda p, x, y: cross_entropy_loss(
            model.apply({"params": p}, x), y)))

    xs, ys = synthetic_mnist(jax.random.PRNGKey(1), args.steps,
                             args.batch_size)
    t0 = time.time()
    for i in range(args.steps):
        state, m = step(state, xs[i], ys[i])
        if i % args.print_freq == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                  f"scale {float(m['loss_scale']):.0f}  "
                  f"overflow {bool(m['overflow'])}")
    dt = time.time() - t0
    print(f"done: {args.steps} steps, "
          f"{args.steps * args.batch_size / dt:.0f} samples/s")


if __name__ == "__main__":
    main()
