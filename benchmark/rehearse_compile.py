"""Compile a cell's step at its real size for a described v5e:2x2, with
no chip attached (the on-chip-measurement guide, section 2.3).

Run by hand before the first chip call of a cell:
``JAX_PLATFORMS=cpu python3 -m benchmark.rehearse_compile <cell> [...]``.
It prints what the chip's compiler says of memory (does the batch fit
beside the state?), the Mosaic kernels in the program and the replica
groups of its all-reduces.  Nothing runs; no time or rate comes of it.
"""

import os
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402


def take_the_chip_branch() -> None:
    """The program asks ``jax.default_backend()`` which kernels to use and
    here sees the CPU.  Steer it from this script, not through an option
    of the program: every module that holds ``on_tpu`` gets one that says
    yes, so the Mosaic kernels are traced and compiled, not interpreted."""
    import importlib
    import pkgutil

    import apex_tpu.ops.pallas as pallas_pkg
    for info in pkgutil.iter_modules(pallas_pkg.__path__):
        importlib.import_module(f"apex_tpu.ops.pallas.{info.name}")
    import apex_tpu.attention  # noqa: F401
    import apex_tpu.normalization  # noqa: F401
    import apex_tpu.optimizers  # noqa: F401
    for name, module in list(sys.modules.items()):
        if name.startswith("apex_tpu") and hasattr(module, "on_tpu"):
            module.on_tpu = lambda: True


def main(cells) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import run as bench_run, trace, weights
    from benchmark.drivers import train

    jax.config.update("jax_enable_compilation_cache", False)
    take_the_chip_branch()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in cells:
        _, cell, cfg, family, _ = bench_run.resolve(name, rehearse=False)
        traffic = cell["parameters"]
        devices = list(topo.devices)[:cell["chips"]]
        made = train.make_step(cell, cfg, family, devices)
        one = made["replicated"] or SingleDeviceSharding(devices[0])
        rows = made["by_rows"] or one
        state = jax.eval_shape(
            lambda k: made["a"].init(weights.make(made["spec"], k)),
            jax.ShapeDtypeStruct((2,), np.uint32))
        state = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one),
            state)
        batch = family.make_batch(
            np.random.default_rng(0),
            traffic["rows_per_chip"] * cell["chips"], cfg, traffic)
        batch = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
                      for a in batch)
        t = time.perf_counter()
        compiled = jax.jit(made["step_fn"], donate_argnums=(0,)).lower(
            state, *batch).compile()
        hlo = compiled.as_text()
        m = compiled.memory_analysis()
        groups = sorted(set(re.findall(
            r"all-reduce[^\n]*?replica_groups=(\{\{[^}]*\}\}|\[[^\]]*\]<=\[[^\]]*\])",
            hlo)))
        print(f"{name}: compiled for {len(devices)} described chip(s) in "
              f"{time.perf_counter() - t:.0f} s")
        print(f"  per chip: arguments {m.argument_size_in_bytes / 2**30:.2f} "
              f"GiB, temporaries {m.temp_size_in_bytes / 2**30:.2f} GiB, "
              f"outputs {m.output_size_in_bytes / 2**30:.2f} GiB (aliased "
              f"{m.alias_size_in_bytes / 2**30:.2f}), code "
              f"{m.generated_code_size_in_bytes / 2**30:.2f} GiB")
        print(f"  Mosaic kernels: {trace.mosaic_kernels(hlo)}")
        print(f"  all-reduce replica groups: {groups}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or ["bert_large.pretrain_b16_s512",
                                   "gpt2_medium.lm_b8_s1024",
                                   "gpt2_medium.ddp4_b32_s1024"]))
