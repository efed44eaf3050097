"""Seeded-bug fixtures for the Pallas kernel sanitizer
(:mod:`apex_tpu.analysis.pallas_lint`).

Every rule id gets a minimal kernel built to trip it AND a clean twin
that differs only in the one property the rule checks — so a rule that
goes quiet (regression) or noisy (false positive) fails here, not in a
committed KERNLINT round.  The shipped-kernel assertions pin the
sweep's headline claims (adam donation aliasing is sound both ways,
the layer-norm backward routes over-budget widths to the fallback)
as importable regression tests.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "tools"))

from apex_tpu.analysis import kernlint, pallas_lint  # noqa: E402


def _error_ids(report):
    return sorted({f.op for f in report.findings
                   if f.severity == "error"})


def _copy_k(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _accum_k(x_ref, o_ref):
    o_ref[...] += x_ref[...]


_X = jnp.ones((4 * 8, 128), jnp.float32)


def _call(out_shape, in_map, out_map, grid, sem, kern=_copy_k,
          scratch=(), vmem_limit_bytes=None, **kw):
    """One-input one-output 8x128-block pallas_call fixture factory."""
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((8, 128), in_map)],
        out_specs=pl.BlockSpec((8, 128), out_map),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=sem, vmem_limit_bytes=vmem_limit_bytes),
        interpret=True, **kw)(_X)


def _lint(*call_args, **call_kw):
    return pallas_lint.lint_fn(lambda x: _call(*call_args, **call_kw),
                               _X)


# ---------------------------------------------------------------------------
# the rule lists cannot drift
# ---------------------------------------------------------------------------

def test_rule_lists_pinned_equal():
    """kernlint.py mirrors the rule ids so gate_hygiene stays
    stdlib-only; this pin is what keeps the mirror honest."""
    assert tuple(pallas_lint.RULES) == tuple(kernlint.RULES)
    assert len(set(pallas_lint.RULES)) == 6


# ---------------------------------------------------------------------------
# seeded bugs: one fixture per rule id + a clean twin
# ---------------------------------------------------------------------------

def test_parallel_race_fires_on_colliding_writes():
    # all four parallel grid points write output block (0, 0)
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (0, 0),
                (4,), ("parallel",))
    assert "pallas-parallel-race" in _error_ids(rep)


def test_parallel_race_clean_twin_disjoint_blocks():
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("parallel",))
    assert _error_ids(rep) == []


def test_seq_accum_parallel_fires_on_parallel_accumulator():
    # dw-style accumulator (read-modify-write of a revisited block)
    # under a dim declared parallel: the accumulation order does not
    # exist on a parallel dim
    rep = _lint((8, 128), lambda i: (i, 0), lambda i: (0, 0),
                (4,), ("parallel",), kern=_accum_k)
    assert "pallas-seq-accum-parallel" in _error_ids(rep)


def test_seq_accum_clean_twin_arbitrary_dim():
    # the identical accumulator on a sequential grid is the layer-norm
    # backward pattern — legal
    rep = _lint((8, 128), lambda i: (i, 0), lambda i: (0, 0),
                (4,), ("arbitrary",), kern=_accum_k)
    assert _error_ids(rep) == []


def test_oob_unmasked_fires_on_shifted_index_map():
    # input walk starts one whole block past the data
    rep = _lint((4 * 8, 128), lambda i: (i + 1, 0), lambda i: (i, 0),
                (4,), ("arbitrary",))
    assert "pallas-oob-unmasked" in _error_ids(rep)


def test_oob_clean_twin_overhanging_tail_is_masked():
    # a ragged last block ORIGINATING inside the array is the legal
    # Mosaic-masked tail (the layer-norm forward relies on it)
    y = jnp.ones((28, 128), jnp.float32)

    def f(x):
        return pl.pallas_call(
            _copy_k, grid=(4,),
            in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((28, 128), jnp.float32),
            interpret=True)(x)
    assert _error_ids(pallas_lint.lint_fn(f, y)) == []


def test_uncovered_output_fires_on_short_grid():
    # grid of 3 over a 4-block output: the last block is never written
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (3,), ("arbitrary",))
    assert "pallas-uncovered-output" in _error_ids(rep)


def test_uncovered_clean_twin_full_grid():
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",))
    assert _error_ids(rep) == []


def test_vmem_overflow_fires_on_giant_scratch():
    def scratch_k(x_ref, o_ref, s_ref):
        o_ref[...] = x_ref[...]
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",), kern=scratch_k,
                scratch=[pltpu.VMEM((4096, 4096), jnp.float32)])  # 64 MiB
    assert "pallas-vmem-overflow" in _error_ids(rep)


@pytest.mark.parametrize("limit_mib,overflows", [(96, False), (48, True)])
def test_vmem_ceiling_is_the_calls_own_limit(limit_mib, overflows):
    """A call that sets ``vmem_limit_bytes`` is held to what it asked
    Mosaic for: the 64 MiB scratch that overflows the default ceiling
    passes under 96 MiB and still overflows 48."""
    def scratch_k(x_ref, o_ref, s_ref):
        o_ref[...] = x_ref[...]
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",), kern=scratch_k,
                scratch=[pltpu.VMEM((4096, 4096), jnp.float32)],
                vmem_limit_bytes=limit_mib << 20)
    assert ("pallas-vmem-overflow" in _error_ids(rep)) == overflows


def test_vmem_clean_twin_small_scratch():
    def scratch_k(x_ref, o_ref, s_ref):
        o_ref[...] = x_ref[...]
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",), kern=scratch_k,
                scratch=[pltpu.VMEM((8, 128), jnp.float32)])
    assert _error_ids(rep) == []


def test_alias_race_fires_on_torn_conditional_store():
    # donated alias whose ONLY store hides under pl.when: grid points
    # where the predicate is false leave the aliased block torn
    def torn_k(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[...] = x_ref[...] * 2.0
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",), kern=torn_k,
                input_output_aliases={0: 0})
    assert "pallas-alias-race" in _error_ids(rep)


def test_alias_race_fires_on_footprint_mismatch():
    # in-place alias where the read walks the array in the opposite
    # order to the write: block i reads data block 3-i AFTER the write
    # to block 3-i already clobbered it
    rep = _lint((4 * 8, 128), lambda i: (3 - i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",), input_output_aliases={0: 0})
    assert "pallas-alias-race" in _error_ids(rep)


def test_alias_clean_twin_inplace_same_footprint():
    # the multi-tensor in-place pattern: unconditional store, read and
    # write footprints identical at every grid point
    rep = _lint((4 * 8, 128), lambda i: (i, 0), lambda i: (i, 0),
                (4,), ("arbitrary",), input_output_aliases={0: 0})
    assert _error_ids(rep) == []


# ---------------------------------------------------------------------------
# extraction + report plumbing
# ---------------------------------------------------------------------------

def test_no_pallas_call_reports_info_count_zero():
    rep = pallas_lint.lint_fn(lambda x: x * 2.0, _X)
    assert rep.ok
    calls = [f for f in rep.findings if f.op == "pallas-call"]
    assert len(calls) == 1 and calls[0].count == 0


def test_extracts_calls_nested_under_transforms():
    def f(x):
        def step(c, _):
            return _call((4 * 8, 128), lambda i: (i, 0),
                         lambda i: (i, 0), (4,), ("arbitrary",)), None
        y, _ = jax.lax.scan(step, x, None, length=2)
        return y
    jaxpr = jax.make_jaxpr(f)(_X)
    calls = pallas_lint.extract_pallas_calls(jaxpr)
    assert len(calls) == 1 and calls[0].grid == (4,)


# ---------------------------------------------------------------------------
# shipped kernels: the sweep's headline claims, pinned
# ---------------------------------------------------------------------------

def test_fused_adam_clean_both_donation_modes():
    """The PR-2 ``donate=`` aliasing audit: p/m/v in-place updates lint
    clean with donation ON and OFF (identical read/write footprints,
    unconditional stores)."""
    from apex_tpu.ops.pallas.adam_kernel import ADAM_PAD, packed_adam
    n = ADAM_PAD
    args = [jnp.ones((n,), jnp.float32) for _ in range(4)]
    kw = dict(step_size=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
              scale=1.0, weight_decay=0.01, eps_mode=0)
    for donate in (False, True):
        rep = pallas_lint.lint_fn(
            lambda p, m, v, g: packed_adam(p, m, v, g, donate=donate,
                                           **kw), *args)
        assert rep.ok, (donate, rep.format())


def test_layer_norm_supported_is_budget_aware():
    """Widths whose backward working set exceeds the VMEM ceiling are
    unsupported WITH a dtype (they route to the jnp fallback instead
    of shipping a kernel the sanitizer rejects)."""
    from apex_tpu.ops.pallas import layer_norm_kernels as lnk
    # dtype-less: the legacy alignment-only check
    assert lnk.supported(8192)
    # fp32 caps at n2=5376, bf16 at 10752 (the KERNLINT boundaries)
    assert lnk.supported(5376, jnp.float32)
    assert not lnk.supported(5504, jnp.float32)
    assert not lnk.supported(8192, jnp.float32)
    assert lnk.supported(10752, jnp.bfloat16)
    assert not lnk.supported(10880, jnp.bfloat16)
    assert not lnk.supported(16384, jnp.bfloat16)


def test_layer_norm_boundary_backward_lints_clean():
    """The widest supported fp32 shape's fwd+bwd pallas calls pass all
    six rules — the ``supported()`` boundary and the sanitizer's VMEM
    ceiling agree."""
    from apex_tpu.ops.pallas import layer_norm_kernels as lnk
    n2 = 5376
    x = jnp.ones((256, n2), jnp.float32)
    w = jnp.ones((n2,), jnp.float32)
    b = jnp.zeros((n2,), jnp.float32)

    def f(x, w, b):
        y, vjp = jax.vjp(
            lambda xx, ww, bb: lnk.layer_norm_fwd_vjp(xx, ww, bb, 1e-5),
            x, w, b)
        return vjp(y)
    rep = pallas_lint.lint_fn(f, x, w, b)
    assert rep.ok, rep.format()
    ncalls = sum(f.count for f in rep.findings
                 if f.op == "pallas-call")
    assert ncalls >= 2   # forward + fused backward


def test_fused_layer_norm_routes_overbudget_width_to_fallback(
        monkeypatch):
    """The call site honors the budget-aware ``supported()``: an
    8192-wide fp32 norm traces with ZERO pallas calls."""
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    from apex_tpu.normalization import fused_layer_norm_affine
    x = jnp.ones((8, 8192), jnp.float32)
    w = jnp.ones((8192,), jnp.float32)
    b = jnp.zeros((8192,), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda x, w, b: fused_layer_norm_affine(x, w, b, 8192))(x, w, b)
    assert pallas_lint.extract_pallas_calls(jaxpr) == []


# ---------------------------------------------------------------------------
# the registered pass + CLI lane
# ---------------------------------------------------------------------------

def test_pass_registered_under_pallas_kernel():
    from apex_tpu.analysis.core import PASSES
    assert pallas_lint.PASS_NAME in PASSES


def test_graph_lint_pallas_lane_runs_via_cli(capsys):
    import graph_lint
    assert graph_lint.main(["--families", "mlp", "--lanes", "o1",
                            "--passes", "pallas"]) == 0
    out = capsys.readouterr().out
    assert '"pallas-kernel"' in out and '"ok": true' in out
