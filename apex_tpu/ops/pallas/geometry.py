"""Block-geometry selection shared by the streaming Pallas kernels.

Every optimizer/norm kernel in this package is an elementwise or
row-reduction pass whose roofline is HBM bandwidth, and the knob that
decides how close it gets is the ROW-BLOCK geometry: how many rows of
the 2-D flat-buffer view one grid step streams through VMEM.  Fewer
grid steps amortize the per-step DMA setup: larger blocks replaced the
fused Adam kernel's 8-row blocks and the LAMB kernels' single (8, 128)
chunk tile per step (the gain is unmeasured on this benchmark, whose
cells run Adam as XLA fusions).  This module is the one selector all
streaming kernels share, instead of each kernel hard-coding its own
magic block.

Two selection surfaces:

- :func:`select_block_rows` — flat-view kernels (packed Adam, LayerNorm
  forward): the largest ladder block whose double-buffered working set
  across all operand/result streams fits the VMEM budget.  Ragged row
  counts need NO fallback to the tile floor: Mosaic masks the
  out-of-bounds tail of the last grid block (reads padded, writes
  dropped), so the grid is simply ``cdiv(rows, block_rows)``.
- :func:`select_chunks_per_block` — chunk-aligned kernels (LAMB stages,
  whole-tree Adam) whose per-tensor scalars ride chunk→tensor SMEM
  tables: the grid step grows to K chunks, statically unrolled inside
  the kernel so each chunk keeps its own table scalars (and its own
  partial-norm slot).  K is capped by ``max_unroll`` — Mosaic compile
  time scales with the unrolled sub-block count.

The VMEM budget is half of Mosaic's default 16 MiB scoped limit (the
other half belongs to the kernel body's own working set),
overridable via ``APEX_TPU_VMEM_BUDGET_MB`` for experiments; per-call
geometry overrides are the ``block_rows=`` / ``chunks_per_block=``
kwargs on the kernels.

Selection never changes element math — blocks partition the same rows
with the same per-chunk scalars — so the L1 conformance contract
(pallas bit-identical to the jnp reference) is geometry-independent;
``tests/l0/test_kernel_geometry.py`` pins that across ragged shapes.
"""

from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.packing import round_up as _round_up

#: Descending candidate ladder for flat-view row blocks.  Powers of two
#: only: every rung is a multiple of both tile floors (8 fp32 / 16 bf16
#: sublanes), and halving steps keep the autotune sweep small.
BLOCK_ROWS_LADDER = (1024, 512, 256, 128, 64, 32, 16, 8)

#: Mosaic's default scoped-VMEM limit for one kernel (v5e); a kernel that
#: needs more says so with ``vmem_limit_bytes``.
DEFAULT_SCOPED_VMEM = 16 * 1024 * 1024

#: Default streaming VMEM budget (bytes): half of that limit.
DEFAULT_VMEM_BUDGET = DEFAULT_SCOPED_VMEM // 2

#: Static-unroll cap for multi-chunk grid steps (compile-time bound).
DEFAULT_MAX_UNROLL = 8


def vmem_budget() -> int:
    """Streaming VMEM budget in bytes (``APEX_TPU_VMEM_BUDGET_MB`` or
    the 8 MiB default).  Malformed env values fall back silently — a
    typo'd override must degrade to the default, not crash a train
    step."""
    raw = os.environ.get("APEX_TPU_VMEM_BUDGET_MB")
    if raw:
        try:
            return max(1, int(float(raw) * 1024 * 1024))
        except ValueError:
            pass
    return DEFAULT_VMEM_BUDGET


def select_block_rows(rows: int, row_bytes: int, *, multiple_of: int = 8,
                      max_rows: int = 1024,
                      budget: "int | None" = None) -> int:
    """Largest ladder block (a multiple of ``multiple_of``) whose
    double-buffered working set ``2 * block_rows * row_bytes`` fits the
    VMEM budget, clamped to ``max_rows`` and to the data itself.

    ``row_bytes`` is the total bytes one row costs across EVERY
    operand/result stream the kernel touches per grid step (lanes ×
    Σ dtype sizes) — the quantity the double-buffer pipeline must hold
    twice.  The block never exceeds the data rounded up to
    ``multiple_of`` — small inputs step down the ladder instead of
    allocating a mostly-masked giant block (they may still take a
    multi-step grid: rows=100 selects 64, grid 2).
    """
    assert rows >= 1 and row_bytes >= 1
    cap = (budget if budget is not None else vmem_budget()) \
        // (2 * row_bytes)
    cap = min(cap, max_rows)
    for cand in BLOCK_ROWS_LADDER:
        if cand % multiple_of:
            continue
        if cand <= cap and cand <= _round_up(rows, multiple_of):
            return cand
    return multiple_of  # tile floor: always legal, never worse than today


def select_chunks_per_block(n_chunks: int, chunk_rows: int, row_bytes: int,
                            *, max_unroll: int = DEFAULT_MAX_UNROLL,
                            budget: "int | None" = None) -> int:
    """How many aligned chunks one grid step of a chunk-tabled kernel
    should stream: bounded by the VMEM budget (double-buffered), the
    static-unroll cap, and the chunk count itself.  Returns ≥ 1."""
    assert n_chunks >= 1 and chunk_rows >= 1
    cap_rows = (budget if budget is not None else vmem_budget()) \
        // (2 * row_bytes)
    k = max(1, cap_rows // chunk_rows)
    return max(1, min(k, max_unroll, n_chunks))


def chunked_geometry(n: int, chunk_size: int, row_bytes: int, *,
                     lanes: int, chunks_per_block: "int | None" = None,
                     max_unroll: int = DEFAULT_MAX_UNROLL
                     ) -> "StreamGeometry":
    """Resolved geometry for a chunk-tabled kernel at ``n`` elements —
    THE one body behind the per-kernel helpers (LAMB stage 1/2,
    whole-tree Adam): K chunks per grid step, ceiling grid, and the
    padded-table slot count derived as ``grid × chunks_per_block``.
    Keeping it single-sourced means the grid and the SMEM-table padding
    can never desync between kernels."""
    n_chunks = n // chunk_size
    chunk_rows = chunk_size // lanes
    k = chunks_per_block or select_chunks_per_block(
        n_chunks, chunk_rows, row_bytes, max_unroll=max_unroll)
    return StreamGeometry(block_rows=k * chunk_rows, lanes=lanes,
                          grid=-(-n_chunks // k), chunks_per_block=k)


def pad_table(t: jax.Array, slots: int) -> jax.Array:
    """Pad a per-chunk SMEM scalar table to the grid's slot count
    (``grid × chunks_per_block``) so the masked tail of a ragged last
    block indexes real (dead) entries instead of running off the table —
    shared by every chunk-tabled kernel (LAMB stages, whole-tree
    Adam)."""
    return t if t.shape[0] == slots else jnp.pad(t, (0, slots - t.shape[0]))


class StreamGeometry(NamedTuple):
    """Resolved geometry of one streaming pallas_call."""

    block_rows: int      # rows per grid step (chunks_per_block * chunk rows
                         # for chunk-tabled kernels)
    lanes: int           # width of the 2-D flat view
    grid: int            # number of grid steps (ceil division: ragged
                         # tails ride the masked last block)
    chunks_per_block: int = 1

    def asdict(self) -> dict:
        return {"block_rows": self.block_rows, "lanes": self.lanes,
                "grid": self.grid,
                "chunks_per_block": self.chunks_per_block}
