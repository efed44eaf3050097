"""The gate of a gated short convolution, ``y = C * conv(B * u)``, as two
Mosaic kernels.

:class:`apex_tpu.models.lfm2_moe.ShortConvMixer` splits its
in-projection's output ``bcu (B, L, 3h)`` into ``[B | C | u]``.  The gate
forms ``z = B u``, its causal convolution ``s_t = sum_j w_j z_(t - (n -
1) + j)`` over ``n`` taps (zeros before a row's start) and ``y = C s``,
all in float32 from operands in ``bcu``'s dtype, rounded once.  Here the
forward pass is one ``pallas_call`` and the backward pass another, both
on the grid ``(B, L / T)``, every axis ``parallel``.  A grid step takes
``T`` rows of ``bcu`` across all ``3h`` lanes, so that ``B``, ``C`` and
``u`` are lane slices of one block, and one sublane tile of the rows the
taps reach beyond them: of ``B`` and ``u`` before the block and,
backward, of ``C`` and ``dy`` after it.  Those halos are blocks of the
same arrays, zeroed at a row's start and end, so nothing crosses from
one row into the next.  A row is shifted by ``pltpu.roll`` of the block
with its halo stacked on.

Backward, the kernel forms ``s`` again, then ``dC = dy s``, ``ds = dy
C``, ``dz_t = sum_j w_j ds_(t + n - 1 - j)``, ``dB = dz u`` and ``du = dz
B``, and writes ``d bcu`` as one block.  It also writes a grid step's
partial sums of the taps' gradient, ``sum_t ds_t z_(t - (n - 1) + j)``,
in float32; they are summed outside.  The residuals are ``bcu`` and the
taps alone.

Off the chip both kernels run in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops import on_tpu, sds as _sds
from apex_tpu.ops.pallas.geometry import DEFAULT_SCOPED_VMEM

_LANES = 128
#: rows of a grid step, the first that divides the length: at the LFM2
#: cell's (2, 8192, 2048) a layer's forward and backward take 1.127 ms
#: at 512 rows, 1.139 at 256 and 1.177 at 128 (PERF.md, PR 37)
_ROWS = (512, 256, 128, 64, 32, 16)
#: lanes of the hidden width the kernels' bodies form at once, the first
#: that divides it: what a body holds in float32 stays a few MiB (256 to
#: 1024 lanes time alike)
_CHUNKS = (512, 256, 128)
_DTYPES = (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _halo(dtype) -> int:
    """Rows of a halo: one sublane tile of ``dtype``."""
    return 32 // jnp.dtype(dtype).itemsize


def gate_geometry(length: int, hidden: int, taps: int,
                  dtype) -> "int | None":
    """Rows a grid step of the kernels takes for rows of ``length``
    tokens of a gate ``hidden`` wide over ``taps`` taps, ``bcu`` in
    ``dtype``; ``None`` where the shape is not theirs: a width that does
    not fill the 128 lanes, more taps than a halo reaches, a dtype other
    than float32 or bfloat16, or a length no block of rows divides."""
    if (jnp.dtype(dtype) not in _DTYPES or hidden % _LANES
            or not 1 <= taps <= _halo(dtype) + 1):
        return None
    return next((rows for rows in _ROWS
                 if length % rows == 0 and rows % _halo(dtype) == 0), None)


def _moved(ext, k: int, start: int, rows: int):
    """``rows`` rows of ``ext`` from ``start``, each the row ``k`` before
    it (``k < 0``: after it)."""
    k %= ext.shape[0]
    return (pltpu.roll(ext, k, 0) if k else ext)[start:start + rows]


def _taps(w_ref, lanes, ext, start: int, rows: int, ahead: bool):
    """``sum_j w_j x_(t - (n - 1) + j)`` over the block's rows, or with
    ``ahead`` ``sum_j w_j x_(t + n - 1 - j)``; ``ext`` is the block's
    ``x`` with its halo stacked on, the block's rows from ``start``.  The
    taps are summed in their order, as :func:`apex_tpu.layers.short_conv`
    sums them."""
    n = w_ref.shape[0]
    total = None
    for j in range(n):
        k = n - 1 - j
        term = _moved(ext, -k if ahead else k, start, rows) \
            * w_ref[j:j + 1, lanes]
        total = term if total is None else total + term
    return total


def _parts(bcu_ref, hidden: int, lanes):
    """``B``, ``C`` and ``u`` of the block over ``lanes``, in float32."""
    return [bcu_ref[:, p * hidden + lanes.start:p * hidden + lanes.stop]
            .astype(jnp.float32) for p in range(3)]


def _fwd_kernel(bcu_ref, b_ref, u_ref, w_ref, y_ref, *, hidden, chunk):
    rows, halo = y_ref.shape[0], b_ref.shape[0]
    first = pl.program_id(1) == 0
    for c in range(0, hidden, chunk):
        lanes = slice(c, c + chunk)
        b, g, u = _parts(bcu_ref, hidden, lanes)
        before = jnp.where(first, 0.0, b_ref[:, lanes].astype(jnp.float32)
                           * u_ref[:, lanes].astype(jnp.float32))
        s = _taps(w_ref, lanes, jnp.concatenate([before, b * u]), halo,
                  rows, ahead=False)
        y_ref[:, lanes] = (g * s).astype(y_ref.dtype)


def _bwd_kernel(bcu_ref, d_y_ref, b_ref, u_ref, c_ref, d_y_next_ref, w_ref,
                d_bcu_ref, d_w_ref, *, hidden, chunk):
    n, rows, halo = w_ref.shape[0], d_y_ref.shape[0], b_ref.shape[0]
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    for c in range(0, hidden, chunk):
        lanes = slice(c, c + chunk)
        b, g, u = _parts(bcu_ref, hidden, lanes)
        d_y = d_y_ref[:, lanes].astype(jnp.float32)
        before = jnp.where(first, 0.0, b_ref[:, lanes].astype(jnp.float32)
                           * u_ref[:, lanes].astype(jnp.float32))
        z = jnp.concatenate([before, b * u])
        s = _taps(w_ref, lanes, z, halo, rows, ahead=False)
        d_s = d_y * g
        after = jnp.where(last, 0.0,
                          d_y_next_ref[:, lanes].astype(jnp.float32)
                          * c_ref[:, lanes].astype(jnp.float32))
        d_z = _taps(w_ref, lanes, jnp.concatenate([d_s, after]), 0, rows,
                    ahead=True)
        for p, grad in enumerate((d_z * u, d_y * s, d_z * b)):
            d_bcu_ref[:, p * hidden + c:p * hidden + c + chunk] = \
                grad.astype(d_bcu_ref.dtype)
        for j in range(n):
            d_w_ref[j:j + 1, lanes] = jnp.sum(
                d_s * _moved(z, n - 1 - j, halo, rows), axis=0, keepdims=True)


def _call(kernel, name: str, operands, specs, out_shape, out_specs, grid):
    """``kernel`` over ``grid``, its body forming a chunk of lanes at a
    time, under a scoped-VMEM limit of its own where its double-buffered
    blocks and a body's float32 values (a dozen of a chunk's rows and
    halo) pass three quarters of the default."""
    hidden = operands[0].shape[-1] // 3
    chunk = next(c for c in _CHUNKS if hidden % c == 0)
    rows, halo = specs[0].block_shape[1], _halo(operands[0].dtype)
    blocks = sum(math.prod(d for d in s.block_shape if d is not None)
                 * x.dtype.itemsize for s, x in zip(
                     [*specs, *out_specs], [*operands, *out_shape]))
    need = 5 * (2 * blocks + 12 * (rows + halo) * chunk * 4) // 4
    return pl.pallas_call(
        functools.partial(kernel, hidden=hidden, chunk=chunk), grid=grid,
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=(need if need > 3 * DEFAULT_SCOPED_VMEM // 4
                              else None)),
        name=name, interpret=not on_tpu(),
    )(*operands)


def _specs(bcu, taps, rows: int):
    """The grid, and the blocks every kernel reads: ``bcu``'s rows, the
    halo of ``B`` and ``u`` before them, the taps."""
    n_b, length, three_h = bcu.shape
    hidden, halo = three_h // 3, _halo(bcu.dtype)
    per = rows // halo

    def before(part):
        return pl.BlockSpec((None, halo, hidden), lambda b, i: (
            b, jnp.maximum(i * per - 1, 0), part))

    return (n_b, length // rows), [
        pl.BlockSpec((None, rows, three_h), lambda b, i: (b, i, 0)),
        before(0), before(2),
        pl.BlockSpec(taps.shape, lambda b, i: (0, 0))]


@functools.partial(jax.jit, static_argnames=("rows",))
def gate_fwd(bcu, taps, *, rows: int):
    """``y (B, L, h)`` in ``bcu``'s dtype from ``bcu (B, L, 3h)`` and the
    taps ``(n, h)``, ``rows`` rows a grid step (:func:`gate_geometry`).
    One trace serves every layer of a model (``jax.jit``)."""
    n_b, length, three_h = bcu.shape
    w = taps.astype(jnp.float32)
    grid, specs = _specs(bcu, w, rows)
    return _call(
        _fwd_kernel, "shortconv_gate_fwd", [bcu, bcu, bcu, w], specs,
        [_sds((n_b, length, three_h // 3), bcu.dtype, bcu, w)],
        [pl.BlockSpec((None, rows, three_h // 3), lambda b, i: (b, i, 0))],
        grid)[0]


@functools.partial(jax.jit, static_argnames=("rows",))
def gate_bwd(d_y, bcu, taps, *, rows: int):
    """The cotangents of ``bcu`` (in its dtype) and of the taps (summed in
    float32, then in their dtype) from ``d_y``; ``B u`` and its
    convolution are formed again from ``bcu``."""
    n_b, length, three_h = bcu.shape
    hidden, halo = three_h // 3, _halo(bcu.dtype)
    w = taps.astype(jnp.float32)
    grid, specs = _specs(bcu, w, rows)
    per, end = rows // halo, length // halo - 1

    def after(part):
        return pl.BlockSpec((None, halo, hidden), lambda b, i: (
            b, jnp.minimum((i + 1) * per, end), part))

    specs = [specs[0],
             pl.BlockSpec((None, rows, hidden), lambda b, i: (b, i, 0)),
             *specs[1:3], after(1), after(0), specs[3]]
    operands = [bcu, d_y, bcu, bcu, bcu, d_y, w]
    d_bcu, d_w = _call(
        _bwd_kernel, "shortconv_gate_bwd", operands, specs,
        [_sds(bcu.shape, bcu.dtype, *operands),
         _sds((n_b, grid[1], *w.shape), jnp.float32, *operands)],
        [specs[0], pl.BlockSpec((None, None, *w.shape),
                                lambda b, i: (b, i, 0, 0))], grid)
    return d_bcu, jnp.sum(d_w, axis=(0, 1)).astype(taps.dtype)
