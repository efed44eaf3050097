"""Seeded-bug fixtures for the bitwise-determinism lint
(:mod:`apex_tpu.analysis.determinism`) and its committed artifact.

Every per-lane rule id gets a minimal program built to trip it AND a
clean twin that differs only in the one property the rule checks — so
a rule that goes quiet (regression) or noisy (false positive) fails
here, not in a committed DETLINT round.  The comparator tests pin the
sweep's headline claim — the ``_attn_cached`` b1-vs-b8 suspect is
mechanically CLEARED with positionally identical reduction-signature
streams — on the real decode lowerings, and the artifact tests hold
the committed ``DETLINT_r01.json`` to the contradiction-rejecting
schema plus its recorded verdicts.
"""

import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu import analysis                            # noqa: E402
from apex_tpu.analysis import determinism, detlint       # noqa: E402
from apex_tpu.models.generate import (                   # noqa: E402
    greedy_argmax, pin_logits)
from apex_tpu.parallel.moe import route                  # noqa: E402


def _findings(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return determinism.determinism_findings(text)


def _error_ids(findings):
    return sorted({f.op for f in findings if f.severity == "error"})


def _counter(findings, op):
    return sum(f.count for f in findings
               if f.severity == "info" and f.op == op)


_X = jnp.ones((4, 8), jnp.float32)
_W = jnp.ones((8, 16), jnp.float32)


# ---------------------------------------------------------------------------
# the rule lists cannot drift
# ---------------------------------------------------------------------------

def test_rule_lists_pinned_equal():
    """detlint.py mirrors the rule ids so gate_hygiene stays
    stdlib-only; this pin is what keeps the mirror honest."""
    assert tuple(determinism.RULES) == tuple(detlint.RULES)
    assert len(set(determinism.RULES)) == 5
    assert tuple(determinism.LANE_RULES) == tuple(detlint.LANE_RULES)
    assert detlint.PAIR_RULE == "det-lane-shape-variant"


def test_pass_registered():
    assert "determinism" in analysis.PASSES


# ---------------------------------------------------------------------------
# det-tie-argmax: raw float argmax/top-k vs the greedy_argmax form
# ---------------------------------------------------------------------------

def test_tie_argmax_fires_on_raw_argmax():
    f = _findings(lambda x: jnp.argmax(x, -1), _X)
    assert "det-tie-argmax" in _error_ids(f)


def test_tie_argmax_fires_on_top_k():
    f = _findings(lambda x: jax.lax.top_k(x, 3), _X)
    assert "det-tie-argmax" in _error_ids(f)


def test_tie_argmax_quiet_on_greedy_argmax():
    f = _findings(lambda x: greedy_argmax(x), _X)
    assert _error_ids(f) == []
    # and not by vacuum: the reductions were walked
    assert _counter(f, "det-epilogue-sites") == 0


def test_tie_argmax_key_perturbed_draw_is_legal():
    """jax.random.categorical is gumbel-noise + argmax: the argmax
    operand derives from a random-bits expansion, so a ulp tie-flip is
    just a different legal sample — info, not error."""
    key = jax.random.PRNGKey(0)
    f = _findings(lambda k, l: jax.random.categorical(k, l), key, _X)
    assert "det-tie-argmax" not in _error_ids(f)
    assert _counter(f, "det-epilogue-sites") >= 1


# ---------------------------------------------------------------------------
# det-multi-materialize: a value both returned and argmax'd, unpinned
# ---------------------------------------------------------------------------

def test_multi_materialize_fires_on_shared_unpinned_logits():
    def seed(x, w):
        logits = x @ w          # ONE binding: both uses share the value
        return logits.argmax(-1), logits
    ids = _error_ids(_findings(seed, _X, _W))
    assert "det-multi-materialize" in ids
    assert "det-tie-argmax" in ids


def test_multi_materialize_quiet_under_pin_logits():
    def clean(x, w):
        logits = pin_logits(x @ w)
        return greedy_argmax(logits), logits
    f = _findings(clean, _X, _W)
    assert _error_ids(f) == []
    assert _counter(f, "det-barriers") >= 1


# ---------------------------------------------------------------------------
# det-scatter-order: non-provably-disjoint scatter windows
# ---------------------------------------------------------------------------

_BUF = jnp.zeros((16, 8), jnp.float32)
_IDX = jnp.array([1, 3, 5], jnp.int32)
_UPD = jnp.ones((3, 8), jnp.float32)


def test_scatter_order_fires_on_unguarded_indices():
    f = _findings(lambda b, i, u: b.at[i].set(u), _BUF, _IDX, _UPD)
    assert "det-scatter-order" in _error_ids(f)
    assert _counter(f, "det-scatter-sites") == 1


def test_scatter_order_quiet_on_trash_guard():
    """The serving pool's form: masked rows route to a sacrificial
    index, so colliding writes statically land in the trash block."""
    mask = jnp.array([True, True, False])
    f = _findings(lambda b, i, u, m: b.at[jnp.where(m, i, 15)].set(u),
                  _BUF, _IDX, _UPD, mask)
    assert _error_ids(f) == []
    assert _counter(f, "det-scatter-sites") == 1


def test_scatter_order_quiet_on_unique_indices():
    f = _findings(
        lambda b, u: b.at[jnp.arange(3)].set(u, unique_indices=True),
        _BUF, _UPD)
    assert _error_ids(f) == []


# ---------------------------------------------------------------------------
# det-prng-reuse: one key feeding two independent expansions
# ---------------------------------------------------------------------------

def test_prng_reuse_fires_on_shared_key():
    key = jax.random.PRNGKey(0)
    f = _findings(lambda k: jax.random.normal(k, (4,))
                  + jax.random.uniform(k, (4,)), key)
    assert "det-prng-reuse" in _error_ids(f)
    assert _counter(f, "det-rng-calls") >= 2


def test_prng_reuse_quiet_after_split():
    key = jax.random.PRNGKey(0)

    def clean(k):
        k1, k2 = jax.random.split(k)
        return jax.random.normal(k1, (4,)) + jax.random.uniform(k2, (4,))
    f = _findings(clean, key)
    assert "det-prng-reuse" not in _error_ids(f)


# ---------------------------------------------------------------------------
# the MoE router rides the greedy_argmax form (the fixed raw-argmax site)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,scoring", [(1, "softmax"), (2, "softmax"),
                                       (6, "sigmoid")])
def test_moe_router_lints_clean(k, scoring):
    logits = jnp.ones((8, 8), jnp.float32)
    f = _findings(lambda lg: route(lg, k, scoring=scoring,
                                   bias=jnp.zeros((8,))).experts, logits)
    assert "det-tie-argmax" not in _error_ids(f)


def test_moe_router_raw_argmax_twin_would_fire():
    """The before-image of the fix: the same router with a raw
    jnp.argmax tie-break trips the rule, so the greedy_argmax rounds in
    route's top-k are load-bearing, not decorative."""
    def raw_router(lg):
        probs = jax.nn.softmax(lg, axis=-1)
        return jnp.argmax(probs, axis=-1)
    f = _findings(raw_router, jnp.ones((8, 4), jnp.float32))
    assert "det-tie-argmax" in _error_ids(f)


# ---------------------------------------------------------------------------
# the comparator, pinned on the real decode lanes (the _attn_cached
# b1-vs-b8 suspect: mechanically cleared)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def decode_pair_texts():
    import det_lint
    return (det_lint.lane_text("decode", (1, 8, 8, None)),
            det_lint.lane_text("decode", (8, 8, 8, None)))


def test_decode_b1_b8_signatures_cleared(decode_pair_texts):
    ta, tb = decode_pair_texts
    sa = determinism.reduction_signatures(ta)
    sb = determinism.reduction_signatures(tb)
    assert sa, "decode_b1 recorded no float reductions (vacuum)"
    res = determinism.compare_signatures("decode_b1", sa,
                                         "decode_b8", sb)
    assert res["verdict"] == "cleared"
    assert res["positional"] is True
    assert res["variants"] == []


def test_decode_lanes_lint_clean(decode_pair_texts):
    for text in decode_pair_texts:
        f = determinism.determinism_findings(text)
        assert _error_ids(f) == []


def test_signature_diff_detects_an_injected_variant(decode_pair_texts):
    """The comparator cannot be cleared-by-construction: perturbing one
    stream flips the verdict."""
    ta, _ = decode_pair_texts
    sa = determinism.reduction_signatures(ta)
    sb = list(sa) + [("dot", (999,), ("f32", "f32", "f32"))]
    res = determinism.compare_signatures("a", sa, "b", sb)
    assert res["verdict"] == "variant"
    assert res["positional"] is False
    assert any(v["dims"] == [999] for v in res["variants"])


# ---------------------------------------------------------------------------
# the committed artifact: schema-valid, verdicts as documented
# ---------------------------------------------------------------------------

_ARTIFACT = REPO / "DETLINT_r01.json"


def _load_artifact():
    return json.loads(_ARTIFACT.read_text())


def test_committed_detlint_exists_and_validates():
    assert _ARTIFACT.exists(), "DETLINT_r01.json must be committed"
    assert detlint.validate_detlint_file(str(_ARTIFACT)) == []


def test_committed_detlint_gate_and_verdicts():
    doc = _load_artifact()
    assert doc["gate"]["ok"] is True
    assert doc["rules"] == list(detlint.RULES)
    # the _attn_cached suspect: cleared with positional evidence
    pair = doc["pairs"]["decode_b1|decode_b8"]
    assert pair["verdict"] == "cleared"
    assert pair["positional"] is True
    assert pair["signatures"]["decode_b1"]  # evidence, not a claim
    # the kv8 tolerance class: a variant, documented
    kv8 = doc["pairs"]["decode_b1|decode_b1_kv8"]
    assert kv8["verdict"] == "variant"
    assert kv8["expected"] is True and kv8["reason"].strip()
    # spec's step-vs-verify contract holds
    assert doc["pairs"]["serve_step|serve_verify"]["verdict"] == "cleared"


# ---------------------------------------------------------------------------
# the schema rejects contradictions (the gate_hygiene enforcement path)
# ---------------------------------------------------------------------------

def test_schema_rejects_ok_contradicting_findings():
    doc = _load_artifact()
    doc["lanes"]["decode_b1"]["findings"]["det-tie-argmax"] = 3
    assert any("contradicts" in p
               for p in detlint.validate_detlint(doc))


def test_schema_rejects_clean_by_vacuum():
    doc = _load_artifact()
    lane = doc["lanes"]["decode_b1"]
    lane["checked"] = {k: 0 for k in lane["checked"]}
    assert any("examined nothing" in p
               for p in detlint.validate_detlint(doc))


def test_schema_rejects_fabricated_cleared_verdict():
    doc = _load_artifact()
    kv8 = doc["pairs"]["decode_b1|decode_b1_kv8"]
    kv8["verdict"] = "cleared"          # signatures still diverge
    assert any("contradicts the recorded signatures" in p
               for p in detlint.validate_detlint(doc))


def test_schema_rejects_suppressed_variant_list():
    doc = _load_artifact()
    doc["pairs"]["decode_b1|decode_b1_kv8"]["variants"] = []
    assert any("disagree" in p for p in detlint.validate_detlint(doc))


def test_schema_rejects_expected_variant_without_reason():
    doc = _load_artifact()
    doc["pairs"]["decode_b1|decode_b1_kv8"].pop("reason")
    assert any("reason" in p for p in detlint.validate_detlint(doc))


def test_schema_rejects_gate_contradiction():
    doc = _load_artifact()
    doc["gate"]["lanes_clean"] = 0
    assert any("gate.lanes_clean" in p
               for p in detlint.validate_detlint(doc))


def test_schema_rejects_stale_waiver():
    doc = _load_artifact()
    doc["lanes"]["decode_b1"]["waivers"] = {
        "det-tie-argmax": "documented"}
    assert any("stale waiver" in p
               for p in detlint.validate_detlint(doc))


def test_gate_hygiene_validates_detlints(tmp_path):
    """gate_hygiene's stdlib-only loader path: a tampered artifact in a
    checkout fails the hygiene gate with a named problem."""
    import gate_hygiene
    (tmp_path / "apex_tpu" / "analysis").mkdir(parents=True)
    shutil.copy(REPO / "apex_tpu" / "analysis" / "detlint.py",
                tmp_path / "apex_tpu" / "analysis" / "detlint.py")
    doc = _load_artifact()
    doc["gate"]["ok"] = False           # contradicts the clean records
    (tmp_path / "DETLINT_r01.json").write_text(json.dumps(doc))
    problems = gate_hygiene._validate_detlints(str(tmp_path))
    assert problems and "DETLINT_r01.json" in problems[0]


# ---------------------------------------------------------------------------
# partial-config emits are refused, not silently committed
# ---------------------------------------------------------------------------

def _refuses(argv):
    import graph_lint
    with pytest.raises(SystemExit) as e:
        graph_lint.main(argv)
    assert e.value.code == 2


def test_graph_lint_refuses_detlint_with_lanes(tmp_path):
    out = str(tmp_path / "DETLINT_r09.json")
    _refuses(["--emit-json", out, "--lanes", "decode"])
    assert not Path(out).exists()


def test_graph_lint_refuses_detlint_with_foreign_passes(tmp_path):
    _refuses(["--emit-json", str(tmp_path / "DETLINT_r09.json"),
              "--passes", "precision"])


def test_graph_lint_refuses_detlint_with_families(tmp_path):
    _refuses(["--emit-json", str(tmp_path / "DETLINT_r09.json"),
              "--families", "gpt"])


def test_graph_lint_refuses_detlint_with_budget(tmp_path):
    _refuses(["--emit-json", str(tmp_path / "DETLINT_r09.json"),
              "--passes", "determinism", "--memory-budget", "1.0"])
