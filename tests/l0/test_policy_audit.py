"""O1 policy-audit coverage (VERDICT r3 #6 / missing #3).

The reference's O1 guarantee is structural — the whole ``torch``
namespace is patched (``apex/amp/amp.py:68-177``), so no model can
escape the cast lists.  apex_tpu's guarantee is *checked* instead:
``amp.audit`` walks the lowered StableHLO and flags FP32-list work
executing in 16-bit.  These tests pin (a) the walker's parsing against
crafted StableHLO spellings, (b) that a policy-escaping model (raw
``jnp`` softmax on bf16) IS flagged, and (c) that all four in-tree
model families' O1 forwards audit clean — the continuously-enforced
version of the namespace-patch guarantee.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from apex_tpu import amp  # noqa: E402


# ---------------------------------------------------------------------------
# (a) parser pins on crafted StableHLO
# ---------------------------------------------------------------------------

def test_flags_16bit_blacklist_pointwise():
    txt = """
    %0 = stablehlo.exponential %a : tensor<8x16xbf16>
    %1 = stablehlo.log %b : tensor<4xf16>
    %2 = stablehlo.rsqrt %c : tensor<2x2xbf16>
    """
    rep = amp.audit_text(txt)
    assert not rep["ok"]
    ops = {(v["op"], v["dtype"]) for v in rep["violations"]}
    assert ops == {("exponential", "bf16"), ("log", "f16"),
                   ("rsqrt", "bf16")}


def test_fp32_blacklist_ops_are_clean():
    txt = """
    %0 = stablehlo.exponential %a : tensor<8x16xf32>
    %1 = stablehlo.log %b : tensor<4xf32>
    """
    assert amp.audit_text(txt)["ok"]


def test_half_safe_activations_not_flagged():
    # tanh/logistic/erf stay in autocast dtype in the reference too
    txt = """
    %0 = stablehlo.tanh %a : tensor<8xbf16>
    %1 = stablehlo.logistic %b : tensor<8xbf16>
    %2 = chlo.erf %c : tensor<8xbf16>
    """
    assert amp.audit_text(txt)["ok"]


def test_reduce_accumulation_dtype_rules():
    # max-reduce is exact in any dtype; add-reduce in bf16 is lossy;
    # jnp's own upcast pattern (f32 operand) is clean
    flagged = ("%0 = stablehlo.reduce(%x init: %c) applies stablehlo.add "
               "across dimensions = [1] : (tensor<8x16xbf16>, "
               "tensor<bf16>) -> tensor<8xbf16>")
    exact = flagged.replace("stablehlo.add", "stablehlo.maximum")
    upcast = flagged.replace("bf16", "f32")
    assert not amp.audit_text(flagged)["ok"]
    assert amp.audit_text(exact)["ok"]
    assert amp.audit_text(upcast)["ok"]
    rep = amp.audit_text(flagged)
    assert rep["violations"][0]["category"] == "16-bit accumulation"


def test_info_counters():
    txt = """
    %0 = stablehlo.dot_general %a, %b : (tensor<4x8xf32>, tensor<8x4xf32>) -> tensor<4x4xf32>
    %1 = stablehlo.convolution(%x, %w) : (tensor<1x8x8x3xbf16>, tensor<3x3x3x8xbf16>) -> tensor<1x8x8x8xbf16>
    %2 = stablehlo.custom_call @tpu_custom_call(%q) : (tensor<4xf32>) -> tensor<4xf32>
    """
    rep = amp.audit_text(txt)
    assert rep["ok"]
    assert rep["fp32_matmul_count"] == 1  # the bf16 conv is a half hit
    assert rep["custom_call_count"] == 1


def test_violation_aggregation_counts():
    txt = "\n".join("%%%d = stablehlo.exponential %%a : tensor<4xbf16>"
                    % i for i in range(3))
    rep = amp.audit_text(txt)
    assert len(rep["violations"]) == 1
    assert rep["violations"][0]["count"] == 3
    assert "exponential" in amp.format_report(rep)


# ---------------------------------------------------------------------------
# (b) a policy-escaping model IS caught end-to-end
# ---------------------------------------------------------------------------

def test_raw_jnp_softmax_escape_is_flagged():
    """A user model calling raw jax.nn.softmax on bf16 activations
    bypasses amp.ops — exactly the coverage gap the audit closes."""
    def escaped(w, x):
        h = jnp.matmul(x, w).astype(jnp.bfloat16)
        return jax.nn.softmax(h, axis=-1).astype(jnp.float32).sum()

    w = jnp.ones((8, 8), jnp.float32)
    x = jnp.ones((4, 8), jnp.float32)
    rep = amp.audit(escaped, w, x)
    assert not rep["ok"]
    assert any(v["op"] == "exponential" and v["dtype"] == "bf16"
               for v in rep["violations"])


def test_amp_ops_softmax_is_clean():
    """The same model through the policy layer audits clean: amp.ops
    casts softmax inputs to fp32 per the FP32 list."""
    from apex_tpu.amp import ops as amp_ops
    a = amp.initialize(opt_level="O1", verbosity=0)

    def policied(w, x):
        h = amp_ops.matmul(x, w)
        return amp_ops.softmax(h, axis=-1).astype(jnp.float32).sum()

    w = jnp.ones((8, 8), jnp.float32)
    x = jnp.ones((4, 8), jnp.float32)
    rep = amp.audit(lambda *args: a.run(policied, *args), w, x)
    assert rep["ok"], rep["violations"]


# ---------------------------------------------------------------------------
# (c) the four in-tree families' O1 forwards audit clean
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", [
    "mlp", pytest.param("resnet", marks=pytest.mark.slow), "gpt",
    pytest.param("bert", marks=pytest.mark.slow)])
def test_model_family_o1_forward_is_policy_clean(family):
    sys.path.insert(0, str(REPO / "tools"))
    import policy_audit
    fn, args = policy_audit.CASES[family]()
    rep = amp.audit(fn, *args)
    assert rep["ok"], (family, rep["violations"])


def test_region_form_reduce_is_flagged():
    """The generic (multi-result / custom-reducer) reduce prints its
    header without an ``applies`` clause — the adds live in a reducer
    REGION.  A bf16 accumulation in that form must still be flagged."""
    import jax.numpy as jnp
    from jax import lax

    def escaped(x):
        s, p = lax.reduce((x, x), (jnp.bfloat16(0), jnp.bfloat16(1)),
                          lambda a, b: (a[0] + b[0], a[1] * b[1]), (0,))
        return s.astype(jnp.float32).sum() + p.astype(jnp.float32).sum()

    rep = amp.audit(escaped, jnp.ones((8, 4), jnp.bfloat16))
    assert not rep["ok"]
    assert any(v["op"] == "reduce" and v["dtype"] == "bf16"
               for v in rep["violations"])


def test_region_form_max_reduce_is_clean():
    # an exact (max) reducer region must not trip the accumulation flag
    txt = """
    %0 = stablehlo.reduce(%arg0 init: %cst) across dimensions = [0] : (tensor<8x4xbf16>, tensor<bf16>) -> tensor<4xbf16>
     reducer(%a: tensor<bf16>, %b: tensor<bf16>) {
      %1 = stablehlo.maximum %a, %b : tensor<bf16>
      stablehlo.return %1 : tensor<bf16>
    }
    """
    assert amp.audit_text(txt)["ok"]


def test_ops_after_reducer_region_not_misattributed():
    # an add AFTER the region closes is a plain add, not an accumulation
    txt = """
    %0 = stablehlo.reduce(%arg0 init: %cst) across dimensions = [0] : (tensor<8x4xbf16>, tensor<bf16>) -> tensor<4xbf16>
     reducer(%a: tensor<bf16>, %b: tensor<bf16>) {
      %1 = stablehlo.maximum %a, %b : tensor<bf16>
      stablehlo.return %1 : tensor<bf16>
    }
    %2 = stablehlo.add %x, %y : tensor<4xbf16>
    """
    assert amp.audit_text(txt)["ok"]
