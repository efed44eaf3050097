"""Minimal data-parallel training example.

Port of the reference's ``examples/simple/distributed/
distributed_data_parallel.py``: the smallest program showing the DDP wrapper
— there, one Linear layer per process with ``torch.distributed.launch``;
here, the same model SPMD-sharded over a device mesh with
``DistributedDataParallel.reduce`` doing the flat-bucket gradient allreduce.

Run on the real chip(s), or anywhere on a virtual mesh:
    python examples/simple_ddp.py --world-size 8 --force-cpu
"""

# Make the repo root importable when run as "python examples/<name>.py"
# without an install (the environment forbids pip install).
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--world-size", type=int, default=0,
                   help="devices to use (0 = all available)")
    p.add_argument("--force-cpu", action="store_true",
                   help="run on a virtual CPU mesh (sets "
                        "xla_force_host_platform_device_count)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--allreduce-always-fp32", action="store_true")
    return p.parse_args()


def main():
    args = parse_args()
    if args.force_cpu:
        import os
        n = args.world_size or 8
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
        # config-level pin, not jax.devices("cpu"): the latter still
        # initializes every registered platform (incl. the TPU plugin,
        # which can block when the device is held elsewhere)
        jax.config.update("jax_platforms", "cpu")
    from apex_tpu.utils import compile_cache
    compile_cache.enable()

    from apex_tpu.parallel import DistributedDataParallel

    devices = (jax.devices("cpu") if args.force_cpu else jax.devices())
    world = args.world_size or len(devices)
    devices = devices[:world]
    mesh = Mesh(np.array(devices), ("data",))
    print(f"world size {world} on {devices[0].platform}")

    # One linear layer, rank-varying data — the reference example's setup.
    in_dim, out_dim, per_rank = 16, 4, 32
    params = {
        "w": jnp.zeros((in_dim, out_dim), jnp.float32),
        "b": jnp.zeros((out_dim,), jnp.float32),
    }
    tx = optax.sgd(0.05)
    opt_state = tx.init(params)
    ddp = DistributedDataParallel(
        axis_name="data",
        allreduce_always_fp32=args.allreduce_always_fp32)

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(world * per_rank, in_dim).astype(np.float32))
    t = jnp.asarray(rng.randn(world * per_rank, out_dim).astype(np.float32))

    def loss_fn(p, xb, tb):
        pred = xb @ p["w"] + p["b"]
        return jnp.mean(jnp.square(pred - tb))

    def train_step(p, opt_state, xb, tb):
        from apex_tpu.parallel import pvary_params
        p_local = pvary_params(p, "data")
        loss, grads = jax.value_and_grad(loss_fn)(p_local, xb, tb)
        grads = ddp.reduce(grads)          # flat-bucket mean-allreduce
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, \
            jax.lax.pmean(loss, "data")

    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P())))

    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, x, t)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {float(loss):.6f}")


if __name__ == "__main__":
    main()
