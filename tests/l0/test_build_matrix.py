"""Build-matrix checks — the ext-vs-no-ext install axis.

The reference's CI compiled its five CUDA extensions against ~7 docker
images and separately pip-installed with and without extensions
(``tests/docker_extension_builds/run.sh``, ``tests/L1/common/run_test.sh``).
The analog here: the C++ host library must rebuild from scratch with the
in-tree Makefile, and the package must import and train with the native
layer disabled (``APEX_TPU_NATIVE=0``) and with either kernel path
(``APEX_TPU_KERNELS=jnp|pallas``) — every combination a user install can
land in.
"""

import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]


@pytest.mark.skipif(shutil.which("g++") is None
                    or shutil.which("make") is None,
                    reason="needs g++ and make")
def test_native_lib_rebuilds_from_scratch(tmp_path):
    """Fresh compile of csrc with the in-tree Makefile (the reference's
    per-image extension build), into an out-of-tree copy so the repo's
    own build products are untouched."""
    src = tmp_path / "csrc"
    shutil.copytree(REPO / "csrc", src, ignore=shutil.ignore_patterns(
        "*.so", "*.o"))
    out = subprocess.run(["make", "-C", str(src)], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    # the Makefile places the library at ../apex_tpu/_native/ relative to
    # csrc (where the ctypes loader looks)
    built = list(tmp_path.rglob("*.so"))
    assert built, "make produced no shared library"


@pytest.mark.parametrize("env_overrides", [
    {"APEX_TPU_NATIVE": "0"},
    pytest.param({"APEX_TPU_NATIVE": "0", "APEX_TPU_KERNELS": "jnp"},
                 marks=pytest.mark.slow),
    pytest.param({"APEX_TPU_KERNELS": "pallas"}, marks=pytest.mark.slow),
])
def test_package_trains_in_every_install_mode(env_overrides, tmp_path):
    """Import + one amp train step in a fresh interpreter per mode (the
    reference literally pip-reinstalled apex with and without extensions
    and re-ran the harness, run_test.sh:1-150)."""
    script = tmp_path / "probe.py"
    script.write_text(
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import jax.numpy as jnp, optax\n"
        "import apex_tpu\n"
        "from apex_tpu import amp\n"
        "from apex_tpu._native import available\n"
        "import os\n"
        "if os.environ.get('APEX_TPU_NATIVE') == '0':\n"
        "    assert not available, 'native layer must be disabled'\n"
        "a = amp.initialize(optimizer=optax.sgd(0.1), opt_level='O2',\n"
        "                   verbosity=0)\n"
        "state = a.init({'w': jnp.ones((4, 4))})\n"
        "step = jax.jit(amp.make_train_step(\n"
        "    a, lambda p, x: jnp.sum((x @ p['w'].astype(jnp.float32))**2)))\n"
        "state, m = step(state, jnp.ones((2, 4)))\n"
        "assert float(m['loss']) > 0\n"
        "print('MODE-OK')\n")
    # start from a CLEAN install-mode state: an outer conformance-axis
    # APEX_TPU_KERNELS/NATIVE (e.g. PARITY.md row 25's jnp runs) must not
    # bleed into the parametrized combinations
    env = {k: v for k, v in os.environ.items()
           if k not in ("APEX_TPU_NATIVE", "APEX_TPU_KERNELS")}
    env["PYTHONPATH"] = f"{REPO}:" + env.get("PYTHONPATH", "")
    env.update(env_overrides)
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=300, env=env)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-1000:])
    assert "MODE-OK" in out.stdout
