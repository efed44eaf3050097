"""Pipeline- and expert-parallel training demo.

Capabilities the reference lacks entirely (2019-era apex has only data
parallelism — SURVEY.md §2 "NOT present"): this example trains with

- ``--mode pp``: a GPipe-style pipeline — each mesh rank owns one stage's
  params (and Adam moments), microbatch activations flow over ICI via
  ``ppermute`` inside one ``lax.scan`` schedule, and the backward pipeline
  falls out of autodiff;
- ``--mode ep``: a switch top-1 MoE FFN — experts sharded over the mesh,
  each token's row sorted to the rank that holds its expert and back by
  a pair of ``all_to_all`` exchanges (no capacity, no dropped token), the
  experts run as grouped matrix products, with the load-balancing aux
  loss.

Both run under amp O2 (bf16 compute, fp32 masters, dynamic loss scaling)
with ``finite_axes`` keeping the overflow-skip decision globally
consistent across the sharded ranks.

Run anywhere (virtual device mesh on CPU):
    python examples/pipeline_moe.py --mode pp --steps 20
    python examples/pipeline_moe.py --mode ep --steps 20
On a real TPU slice the mesh spans the chips; drop --force-cpu.
"""

# Make the repo root importable when run as "python examples/<name>.py"
# without an install (the environment forbids pip install).
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--mode", choices=["pp", "ep"], default="pp")
    p.add_argument("--devices", type=int, default=4)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--print-freq", type=int, default=5)
    p.add_argument("--force-cpu", action=argparse.BooleanOptionalAction,
                   default=True,
                   help="run on a virtual CPU mesh (default; use "
                        "--no-force-cpu on a real multi-chip slice)")
    return p.parse_args()


def main():
    args = parse_args()
    if args.force_cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    import jax
    from jax import shard_map
    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.optimizers import FusedAdam

    n = min(args.devices, len(jax.devices()))
    devices = np.array(jax.devices()[:n])
    d, batch = args.dim, args.batch
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, d))
    target = jnp.tanh(x @ jax.random.normal(jax.random.PRNGKey(2), (d, d)))

    a = amp.initialize(optimizer=FusedAdam(lr=args.lr),
                       opt_level=args.opt_level, verbosity=0)

    if args.mode == "pp":
        from apex_tpu.parallel import pipeline_apply, stack_stage_params
        mesh = Mesh(devices, ("pipe",))
        keys = jax.random.split(rng, n)
        params = stack_stage_params(
            [{"w": jax.random.normal(k, (d, d)) * 0.4} for k in keys])
        axis = "pipe"

        def loss_fn(p, xb):
            y = pipeline_apply(lambda sp, h: jnp.tanh(h @ sp["w"]), p, xb,
                               "pipe")
            return jnp.mean(jnp.square((y - target).astype(jnp.float32)))

        def match(path, leaf):
            return getattr(leaf, "ndim", 0) >= 1   # all params stage-stacked
        data_spec = P()
    else:
        from apex_tpu.parallel import (grouped_matmul, load_balance_loss,
                                       moe_apply, route)
        mesh = Mesh(devices, ("expert",))
        e_local, hidden = 2, 4 * d
        E = n * e_local
        k = jax.random.split(rng, 3)
        params = {
            "experts": {
                "wi": jax.random.normal(k[0], (E, d, hidden)) * 0.3,
                "wo": jax.random.normal(k[1], (E, hidden, d)) * 0.3,
            },
            "router": jax.random.normal(k[2], (d, E)),
        }
        axis = "expert"

        def loss_fn(p, xb):
            def ffn(ep, rows, group_sizes):
                h = jax.nn.gelu(grouped_matmul(
                    rows, ep["wi"].astype(rows.dtype), group_sizes))
                return grouped_matmul(h, ep["wo"].astype(rows.dtype),
                                      group_sizes)
            routing = route(xb @ p["router"].astype(xb.dtype))
            y, _ = moe_apply(ffn, p["experts"], xb, routing, n_experts=E,
                             axis_name="expert")
            aux = jax.lax.pmean(load_balance_loss(routing), "expert")
            y = xb + y
            # target shard for this rank's tokens
            i = jax.lax.axis_index("expert")
            tgt = jax.lax.dynamic_slice_in_dim(target, i * xb.shape[0],
                                               xb.shape[0])
            return (jnp.mean(jnp.square((y - tgt).astype(jnp.float32)))
                    + 0.01 * aux.astype(jnp.float32))

        def match(path, leaf):
            # router stays replicated; scalar leaves (per-leaf optimizer
            # step counters) always replicate
            return "experts" in path and getattr(leaf, "ndim", 0) >= 1
        data_spec = P("expert")

    state = a.init(params)
    if args.mode == "ep":
        # the replicated router's grads are cross-rank reduced
        # EXPLICITLY (axis_name pvary's the params — identity on legacy
        # jax — so no jax version's SPMD autodiff auto-psums them, and
        # reduce_fn pmean's only the router; expert grads are per-rank
        # shards and stay local)
        def reduce_grads(g):
            return {"experts": g["experts"],
                    "router": jax.lax.pmean(g["router"], axis)}
        train = amp.make_train_step(a, loss_fn, axis_name=axis,
                                    reduce_fn=reduce_grads,
                                    finite_axes=(axis,))
    else:
        train = amp.make_train_step(a, loss_fn, finite_axes=(axis,))

    def train_step(state, xb):
        new_state, metrics = train(state, xb)
        return new_state, jax.lax.pmean(metrics["loss"], axis)

    import jax.tree_util as jtu
    state_specs = jtu.tree_map_with_path(
        lambda path, leaf: P(axis) if match(jtu.keystr(path), leaf) else P(),
        state)
    step = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(state_specs, data_spec),
        out_specs=(state_specs, P())))

    for i in range(args.steps):
        state, loss = step(state, x)
        if i % args.print_freq == 0 or i == args.steps - 1:
            print(f"step {i:4d}  loss {float(loss):.4f}")
    print(f"done: {args.mode} over {n} devices "
          f"({jax.devices()[0].platform})")


if __name__ == "__main__":
    main()
