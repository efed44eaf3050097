"""Test config: force a 16-device CPU platform before jax initializes.

This is the test strategy SURVEY.md §4.3 prescribes: every collective
component gets a multi-device test runnable without TPU hardware via
``--xla_force_host_platform_device_count`` (strictly better than the
reference, which could only test distributed paths on a multi-GPU rig).
16 devices (was 8) carries the disaggregated-serving fleet topology —
1 prefill slice + decode replicas on disjoint slices at the c16 bench
shape — while every older multi-device test keeps slicing its first 8.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=16").strip()

import jax  # noqa: E402  (import after env setup)

# Tests run on the virtual 16-device CPU platform with Pallas in
# interpret mode.  Set APEX_TPU_TEST_PLATFORM=tpu to run them on the
# chip instead (tools/onchip_run.py does) — that validates the Pallas
# kernels compiled by Mosaic; multi-device tests fail where they need
# more chips than the host has.
_platform = os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu")
jax.config.update("jax_platforms", _platform)
jax.config.update("jax_threefry_partitionable", True)
if _platform != "cpu":      # skip the import on the CPU tier
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
