"""apex_tpu.ops — fused TPU kernels (Pallas) and their jnp reference paths.

Layer L1/L2 of the design (SURVEY.md §7): every op here has
(a) a pure ``jax.numpy`` reference implementation — always correct, used on
    CPU and as the conformance oracle (the analog of the reference's
    Python-fallback paths), and
(b) a Pallas TPU kernel used on TPU for explicit single-pass fusion control
    (the analog of ``csrc/``).

Selection is automatic (`on_tpu()`), overridable via the environment variable
``APEX_TPU_KERNELS={pallas,jnp,auto}`` for A/B conformance testing — the port
of the reference L1 harness's ext-vs-no-ext install axis
(``tests/L1/common/run_test.sh``).
"""

import os
import re

import jax


def kernel_mode() -> str:
    """'pallas' | 'jnp' | 'auto' from APEX_TPU_KERNELS (default auto)."""
    return os.environ.get("APEX_TPU_KERNELS", "auto")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    mode = kernel_mode()
    if mode == "pallas":
        return True
    if mode == "jnp":
        return False
    return on_tpu()


_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


def mosaic_kernels(hlo_text: str) -> list:
    """Sorted names of the Mosaic custom calls (compiled Pallas kernels)
    in a compiled program's HLO text — what ran, read off the executable
    rather than assumed from ``use_pallas()``.  A name is the scope just
    above ``pallas_call`` in the call's ``op_name`` metadata: the
    ``name=`` every production ``pallas_call`` in this package passes."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _OP_NAME_RE.search(line)
        scopes = m.group(1).split("/") if m else []
        if "pallas_call" in scopes[1:]:
            # autodiff wraps the scope: transpose(jvp(layer_norm_bwd))
            scope = scopes[scopes.index("pallas_call", 1) - 1]
            names.add(re.findall(r"[A-Za-z_]\w*", scope)[-1])
        else:
            names.add(m.group(1) if m else "<no op_name>")
    return sorted(names)


def sds(shape, dtype, *likes):
    """ShapeDtypeStruct for a pallas_call output, carrying the union of the
    varying-across-mesh-axes (vma) types of the ``likes`` operands —
    required when the kernel runs inside ``shard_map`` under VMA checking
    (multi-chip optimizer steps, sequence-parallel attention).  Pass every
    operand the output depends on; an output computed from any varying
    input is varying."""
    vma = None
    for like in likes:
        try:
            v = jax.typeof(like).vma
        except Exception:
            continue
        if v is not None:
            vma = frozenset(v) if vma is None else vma | frozenset(v)
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    # NB: an empty frozenset (fully replicated operands) must still be
    # passed through — under shard_map's VMA checking "vma=None" is an
    # error even for replicated outputs.
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
