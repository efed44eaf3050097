"""Long-context attention demo: sequence parallelism over a device mesh.

Capability the reference lacks entirely (SURVEY.md §5.7 — it predates
long-context training): a sequence too long for one device's attention is
sharded over the mesh's sequence axis and attended exactly with

- ``ring``: K/V shards rotate by ``ppermute`` while each device keeps its
  query shard; per-hop blocks run the Pallas flash kernel and merge by
  logsumexp weighting, and
- ``ulysses``: one fused ``all_to_all`` each way trades the sequence
  sharding for a head sharding.

Run anywhere (virtual 8-device CPU mesh):
    python examples/long_context_attention.py --seq-len 8192 --impl ring
On real multi-chip TPU, drop --force-cpu and the mesh spans the slice.
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
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--seq-len", type=int, default=8192)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--impl", choices=["ring", "ulysses"], default="ring")
    p.add_argument("--causal", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--world-size", type=int, default=0)
    p.add_argument("--force-cpu", action="store_true",
                   help="virtual CPU mesh (for laptops/CI)")
    p.add_argument("--iters", type=int, default=3)
    return p.parse_args()


def main():
    args = parse_args()
    # The CPU-mesh decision must happen BEFORE any jax.devices() call:
    # device enumeration initializes the backend, after which neither
    # xla_force_host_platform_device_count nor jax_platforms can take
    # effect.  Hence an explicit flag rather than auto-detection.
    if args.force_cpu:
        n = args.world_size or 8
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={n}"
            ).strip()
        # Make CPU the *default* platform, not just the mesh devices: the
        # kernel layer keys interpret-vs-Mosaic off the default backend.
        jax.config.update("jax_platforms", "cpu")
        devices = jax.devices("cpu")
    else:
        devices = jax.devices()
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    world = min(len(devices), args.world_size or len(devices))
    if world < 2:
        print("NOTE: only one device visible — running a degenerate "
              "1-way mesh; pass --force-cpu for a virtual 8-device demo")
    mesh = Mesh(np.array(devices[:world]), ("seq",))

    from apex_tpu.attention import ring_attention, ulysses_attention

    B, L, H, D = args.batch, args.seq_len, args.heads, args.head_dim
    assert L % world == 0, "seq-len must divide the mesh"
    print(f"{args.impl} attention: B={B} L={L} H={H} D={D} over "
          f"{world}x {devices[0].platform} (L/W = {L // world})")

    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, L, H, D), jnp.bfloat16)
               for kk in jax.random.split(key, 3))

    fn = {"ring": ring_attention, "ulysses": ulysses_attention}[args.impl]
    step = jax.jit(shard_map(
        lambda q, k, v: fn(q, k, v, "seq", causal=args.causal),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq")))

    out = step(q, k, v)
    checksum = float(jnp.sum(out.astype(jnp.float32)))   # full sync
    t0 = time.perf_counter()
    for _ in range(args.iters):
        out = step(q, k, v)
    checksum = float(jnp.sum(out.astype(jnp.float32)))
    dt = (time.perf_counter() - t0) / args.iters
    toks = B * L / dt
    print(f"{dt * 1e3:.1f} ms/attention  ({toks / 1e3:.0f}K tokens/s)  "
          f"checksum {checksum:.3f}")


if __name__ == "__main__":
    main()
