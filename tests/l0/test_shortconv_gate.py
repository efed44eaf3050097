"""The gate of a gated short convolution, ``y = C * conv(B * u)``, by its
two routes: the Mosaic kernels (:mod:`apex_tpu.ops.pallas.shortconv_gate`,
interpret mode here) against the XLA gate
(:func:`apex_tpu.models.lfm2_moe._xla_gate`) on ``y``, ``dB``, ``dC``,
``du`` and the taps' gradient, over several blocks of rows and two rows;
that nothing crosses from one row into the next and no token reaches
back; which shapes take which route; and a small LFM2 model through
both routes, whose compiled step carries both kernels under the gate's
scope.
"""

import re
import sys
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import lfm2_moe
from apex_tpu.ops.pallas import shortconv_gate
from apex_tpu.ops.pallas.shortconv_gate import (gate_bwd, gate_fwd,
                                                gate_geometry)
from apex_tpu.utils.profiling import SHORTCONV_GATE

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

H = 256


def operands(b, length, dtype, seed=0, h=H):
    """``bcu``, taps drawn as the LFM2 cell draws them (gains about 1)
    and a cotangent for ``y``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcu = jax.random.normal(ks[0], (b, length, 3 * h)).astype(dtype)
    taps = (1.0 + 0.3 * jax.random.normal(ks[1], (3, h))).astype(dtype)
    d_y = jax.random.normal(ks[2], (b, length, h)).astype(dtype)
    return bcu, taps, d_y


def kernels(bcu, taps, d_y, rows):
    """``y``, ``d bcu`` and ``d taps`` by the kernels."""
    return (gate_fwd(bcu, taps, rows=rows),
            *gate_bwd(d_y, bcu, taps, rows=rows))


def xla(bcu, taps, d_y):
    y, back = jax.vjp(lfm2_moe._xla_gate, bcu, taps)
    return (y, *back(d_y))


def named(y, d_bcu, d_taps):
    d_b, d_c, d_u = jnp.split(d_bcu, 3, axis=-1)
    return {"y": y, "dB": d_b, "dC": d_c, "du": d_u, "d_taps": d_taps}


@pytest.mark.parametrize("b,length,rows,dtype", [
    (1, 128, 32, jnp.float32),      # four blocks: halos cross their edges
    (2, 64, 16, jnp.float32),       # two rows of four blocks
    (2, 64, 16, jnp.bfloat16),
    (2, 96, 32, jnp.bfloat16)])
def test_the_kernels_give_the_xla_gates_values_and_cotangents(b, length, rows,
                                                              dtype):
    """Float32 operands: the same products to float32 rounding; only the
    order of the taps' gradient's sum over the tokens differs.  Bfloat16
    operands: formed in float32 and rounded once on both routes, within
    one bfloat16 rounding."""
    args = operands(b, length, dtype, seed=length)
    got = named(*kernels(*args, rows))
    want = named(*xla(*args))
    for name, a in got.items():
        w = want[name]
        assert a.dtype == w.dtype and a.shape == w.shape, name
        a, w = (np.asarray(t, np.float32) for t in (a, w))
        top = float(np.abs(w).max())
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, w, rtol=0, atol=1e-6 * top,
                                       err_msg=name)
        else:
            np.testing.assert_allclose(a, w, rtol=2.0 ** -8,
                                       atol=1e-6 * top, err_msg=name)


def test_nothing_crosses_from_one_row_into_the_next():
    """Two rows of four blocks: the last tokens of row 0 move nothing of
    row 1, the first tokens of row 1 (and their cotangents) nothing of
    row 0, forward or backward."""
    bcu, taps, d_y = operands(2, 64, jnp.float32, seed=3)
    base = kernels(bcu, taps, d_y, 16)
    for row, tokens, other in ((0, slice(-2, None), 1), (1, slice(0, 2), 0)):
        moved = kernels(bcu.at[row, tokens].add(5.0), taps,
                        d_y.at[row, tokens].add(5.0), 16)
        for a, b in zip(base[:2], moved[:2]):
            np.testing.assert_array_equal(a[other], b[other])
            assert np.abs(np.asarray(a[row] - b[row])).max() > 1e-3


def test_a_token_moves_no_output_before_it():
    """Forward, a change at token ``t`` (a block's first row) moves no
    ``y`` before ``t``; backward, a change of ``dy`` at ``t`` moves no
    cotangent after ``t``."""
    bcu, taps, d_y = operands(2, 64, jnp.bfloat16, seed=4)
    t = 32
    y, d_bcu, _ = kernels(bcu, taps, d_y, 16)
    y2, _, _ = kernels(bcu.at[:, t].add(1.0), taps, d_y, 16)
    _, d_bcu2, _ = kernels(bcu, taps, d_y.at[:, t].add(1.0), 16)
    np.testing.assert_array_equal(y[:, :t], y2[:, :t])
    assert np.abs(np.asarray(y[:, t] - y2[:, t], np.float32)).max() > 1e-3
    np.testing.assert_array_equal(d_bcu[:, t + 1:], d_bcu2[:, t + 1:])
    assert np.abs(np.asarray(d_bcu[:, t - 2:t + 1] - d_bcu2[:, t - 2:t + 1],
                             np.float32)).min(axis=1).max() > 1e-3


@pytest.mark.parametrize("length,hidden,taps,dtype,rows", [
    (8192, 2048, 3, jnp.bfloat16, 512),     # the LFM2 cell's
    (64, 128, 3, jnp.float32, 64),
    (96, 256, 3, jnp.bfloat16, 32),
    (64, 200, 3, jnp.bfloat16, None),       # lanes not whole
    (64, 64, 3, jnp.float32, None),         # the cell's rehearsal width
    (8192, 2048, 18, jnp.bfloat16, None),   # taps beyond a halo
    (8192, 2048, 3, jnp.float16, None),
    (40, 128, 3, jnp.bfloat16, None)])      # no block of rows divides
def test_the_route_is_read_from_the_shape(length, hidden, taps, dtype, rows):
    assert gate_geometry(length, hidden, taps, dtype) == rows


@pytest.mark.parametrize("mode,route", [("jnp", None), ("pallas", 16),
                                        ("auto", None)])
def test_the_kernels_run_where_pallas_is_asked_for(monkeypatch, mode, route):
    """``APEX_TPU_KERNELS=jnp`` keeps the XLA gate; off the chip ``auto``
    keeps it too; ``pallas`` takes the kernels at a shape of theirs."""
    monkeypatch.setenv("APEX_TPU_KERNELS", mode)
    bcu, taps, _ = operands(2, 16, jnp.bfloat16)
    assert lfm2_moe._kernel_rows(bcu, taps) == route
    jaxpr = jax.make_jaxpr(jax.grad(lambda a: jnp.sum(
        lfm2_moe.gate(a, taps).astype(jnp.float32))))(bcu)
    calls = re.findall(r"name=(shortconv_gate_\w+)", str(jaxpr))
    assert calls == ([] if route is None else
                     ["shortconv_gate_fwd", "shortconv_gate_bwd"])


SEQ = 256


@pytest.fixture(scope="module")
def wide():
    """The LFM2 cell's rehearsal configuration at a hidden width the
    kernels take, 128, two rows of 256 tokens, seeded weights."""
    import json

    from benchmark import weights
    from benchmark.families import lfm2_moe as family
    from benchmark.reference import lfm2_moe as reference
    with open(REPO / "benchmark/configs/lfm2-8b-a1b.json") as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearse"], hidden_size=128)
    params = weights.make(reference.param_spec(cfg), weights.seed_key(7))
    ids = jnp.asarray(family.make_batch(np.random.default_rng(7), 2, cfg,
                                        {"seq": SEQ})[0])
    return cfg, family, params, ids


def test_a_small_model_trains_alike_by_both_routes(wide):
    """Bfloat16 weights, loss and every gradient leaf: the gate's two
    routes round the same float32 values once, so the model's numbers
    agree to bfloat16 rounding."""
    from benchmark import weights
    cfg, family, params, ids = wide
    params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    loss = jax.value_and_grad(family.program_loss(cfg, {"seq": SEQ}))
    want = jax.jit(loss)(params, ids)
    with mock.patch.object(lfm2_moe, "use_pallas", lambda: True):
        assert lfm2_moe._kernel_rows(jnp.zeros((2, SEQ, 384), jnp.bfloat16),
                                     jnp.zeros((3, 128))) == SEQ
        got = jax.jit(loss)(params, ids)
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=2.0 ** -8)
    flat_got, flat_want = (weights.flatten(t[1]) for t in (got, want))
    assert set(flat_got) == set(flat_want)
    for path, w in flat_want.items():
        a, w = (np.asarray(t, np.float32) for t in (flat_got[path], w))
        np.testing.assert_allclose(a, w, rtol=0,
                                   atol=2.0 ** -7 * np.abs(w).max() + 1e-30,
                                   err_msg=path)


def test_the_compiled_step_holds_both_kernels_under_the_gate(monkeypatch,
                                                             wide):
    """``benchmark/shortconv_scopes.py`` reads the ``op_name`` of each
    instruction of the compiled step.  Under ``APEX_TPU_KERNELS=pallas``
    what both kernels compile to (interpreted here) carries
    ``shortconv_gate`` inside ``conv`` in each of the three conv layers,
    the backward kernel's under autodiff's ``transpose(``; lowered for the
    chip, each conv layer calls both Mosaic kernels inside the scope."""
    from apex_tpu import amp, optimizers
    cfg, family, params, ids = wide
    monkeypatch.setenv("APEX_TPU_KERNELS", "pallas")
    jax.clear_caches()
    a = amp.initialize(optimizer=optimizers.FusedAdam(lr=1e-3),
                       opt_level="O2", verbosity=0)
    step = jax.jit(amp.make_train_step(a, family.program_loss(
        cfg, {"seq": SEQ})))
    state = a.init(params)
    compiled = set(re.findall(r'op_name="([^"]*)"',
                              step.lower(state, ids).compile().as_text()))
    jax.clear_caches()          # the kernels' traces hold ``interpret``
    with mock.patch.object(shortconv_gate, "on_tpu", lambda: True):
        lowered = step.trace(state, ids).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert sorted(re.findall(r'kernel_name = "(shortconv\w+)"', lowered)) \
        == ["shortconv_gate_bwd", "shortconv_gate_fwd"]
    # each kernel is lowered once, inside a function of its own that each
    # layer calls; XLA puts the call's ``op_name`` before the kernel's when
    # it inlines the function
    calls = re.findall(r'loc\("([^"]*/jit\(gate_\w+\))"', lowered)
    for jitted, backward in (("gate_fwd", False), ("gate_bwd", True)):
        found = {"compiled": [n for n in compiled
                              if f"jit({jitted})/shortconv_{jitted}/" in n],
                 "lowered": [n for n in calls
                             if n.endswith(f"jit({jitted})")]}
        for where, names in found.items():
            blocks = set()
            for n in names:
                path = n.split("/")
                assert "conv" in path and SHORTCONV_GATE in path, n
                assert path.index("conv") < path.index(SHORTCONV_GATE), n
                assert ("transpose(jvp(" in n) == backward, n
                blocks.add(next(p for p in path if p.startswith("block_")))
            assert len(blocks) == 3, (where, jitted, blocks)
