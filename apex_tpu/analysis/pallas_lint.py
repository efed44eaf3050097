"""Pallas kernel sanitizer: grid races, index-map OOB/coverage, VMEM
budget, and sequential-contract proofs for every hand-written kernel.

Every other analysis pass stops at StableHLO, where a ``pallas_call``
is an opaque custom call — yet the hand-written kernels are exactly
where this repo has shipped real bugs (the shape-lucky "bitwise"
ragged trees, the never-overwritten draft-cache hole, the documented
``donate=`` in-place skip-``cond`` caveat).  This pass opens the box:
it extracts every ``pallas_call`` from the *jaxpr* (grid, BlockSpecs,
index maps, ``dimension_semantics``, scratch shapes, input/output
aliasing), evaluates each index map **concretely over the full grid**
to build per-operand block-footprint sets, and proves four rule
families:

``pallas-parallel-race`` (error)
    Two grid points that differ in a ``parallel`` dimension write the
    same output block (write-write race: parallel iterations execute
    in unspecified order, possibly on different cores), or — for an
    aliased input/output pair — one parallel iteration reads a block
    another parallel iteration writes (read-after-write carried
    across parallel iterations).
``pallas-alias-race`` (error)
    A donated/aliased input-output pair whose footprints diverge at
    some grid point (the read walks a block an earlier step already
    overwrote in place), or whose output ref is ONLY ever stored
    conditionally (``pl.when``): the skipped-store path leaves the
    block holding the donated input's bytes — the torn-alias class
    behind the documented ``donate=`` skip-``cond`` caveat.
``pallas-oob-unmasked`` (error)
    A block origin that escapes the (padded) array entirely.  Mosaic
    masks the *overhang* of the last partial block — the legal
    ragged-tail idiom — but an origin at or past the array end reads
    or writes memory no mask covers.
``pallas-uncovered-output`` (error)
    An output tile no grid point ever writes (the draft-cache-hole
    class): the union of evaluated output footprints must cover the
    full ceil-division tiling of every output.
``pallas-vmem-overflow`` (error)
    The per-grid-step working set — double-buffered grid-varying
    operand blocks, single-buffered grid-invariant blocks, plus VMEM
    scratch, all dtype-sized — exceeds the VMEM ceiling.  The ceiling
    is ``2 x geometry.vmem_budget()`` (the ``APEX_TPU_VMEM_BUDGET_MB``
    knob names the *streaming half* of VMEM; the checker counts each
    stream's double-buffer partner explicitly, so the ceiling is the
    whole 2x budget = ~16 MiB at defaults).  This turns the geometry
    ladder's promise into a verified invariant for every (shape,
    dtype, knob) a bench config or autotune table can select.
``pallas-seq-accum-parallel`` (error)
    An output ref the kernel *reads* (an accumulator — the
    layer-norm-backward dγ/dβ digest contract) that is revisited
    across a ``parallel`` dimension: carried accumulator state
    requires sequential (``arbitrary``) semantics on the carrying
    dimension.

Registered as the ``pallas-kernel`` pass (reads
``PassContext.closed_jaxpr``; :func:`~apex_tpu.analysis.analyze`
captures the jaxpr automatically when the pass is requested).  The
standalone API needs no lowering at all::

    from apex_tpu.analysis import pallas_lint
    report = pallas_lint.lint_fn(kernel_wrapper, *example_args)
    assert report.ok, report.format()

``tools/kernel_lint.py`` sweeps every shipped kernel across the
geometry ladder and adversarial ragged shapes with exactly this API
and commits the verdict as ``KERNLINT_r*.json``
(:mod:`apex_tpu.analysis.kernlint` is the stdlib-only schema
``tools/gate_hygiene.py`` validates in tier-1);
``tools/graph_lint.py --passes pallas`` runs the pass over the
optimizer-bearing train lanes.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.analysis.core import register_pass
from apex_tpu.analysis.report import Finding, Report, make_report

PASS_NAME = "pallas-kernel"

#: the six rule ids, in severity-of-consequence order (all errors)
RULES = ("pallas-parallel-race", "pallas-alias-race",
         "pallas-oob-unmasked", "pallas-uncovered-output",
         "pallas-vmem-overflow", "pallas-seq-accum-parallel")

#: full-enumeration cap: grids larger than this are probed on their
#: boundary slices instead (first/middle/last two indices per axis) and
#: the coverage rule — which needs exhaustiveness — reports itself
#: skipped rather than asserting over a subsample
MAX_GRID_POINTS = 65536


# ---------------------------------------------------------------------------
# extraction: pallas_call eqns out of a (nested) jaxpr
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Operand:
    """One block-mapped operand (inputs first, then outputs)."""

    index: int            # position in grid_mapping.block_mappings
    role: str             # "in" | "out"
    name: str             # BlockSpec origin (e.g. "p_ref", "outputs[0]")
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    dtype: str
    itemsize: int
    smem: bool
    index_map: Any        # ClosedJaxpr over the grid indices


@dataclasses.dataclass
class Scratch:
    """One scratch operand (persists across grid steps, per core)."""

    shape: Tuple[int, ...]
    dtype: str
    nbytes: int
    smem: bool


@dataclasses.dataclass
class KernelCall:
    """Everything the sanitizer reads from one ``pallas_call`` eqn."""

    name: str
    grid: Tuple[Any, ...]
    semantics: Tuple[str, ...]      # per-dim, "parallel"/"arbitrary"
    operands: List[Operand]
    num_inputs: int
    num_outputs: int
    scratch: List[Scratch]
    aliases: Tuple[Tuple[int, int], ...]   # (input idx, output idx)
    body: Any                       # the kernel body jaxpr
    num_index_operands: int
    vmem_limit: Optional[int] = None   # the call's own vmem_limit_bytes


def _sub_jaxprs(value):
    """Jaxpr objects reachable from one eqn param value."""
    for item in (value if isinstance(value, (list, tuple)) else [value]):
        inner = getattr(item, "jaxpr", None)
        if inner is not None and hasattr(inner, "eqns"):
            yield inner
        elif hasattr(item, "eqns"):
            yield item


def _walk_eqns(jaxpr, out: list) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
            continue           # a pallas body cannot nest another call
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                _walk_eqns(sub, out)


def _itemsize(dtype) -> int:
    try:
        return int(np.dtype(dtype).itemsize)
    except TypeError:
        return int(getattr(dtype, "itemsize", 4))


def _is_smem(aval) -> bool:
    return "smem" in str(aval).lower()


def _block_dims(block_shape) -> Tuple[int, ...]:
    """Block extents as ints — ``Squeezed`` dims (no ``block_size``)
    are size 1."""
    return tuple(int(getattr(b, "block_size", 1)) for b in block_shape)


def describe_call(eqn) -> KernelCall:
    """Normalize one ``pallas_call`` eqn into a :class:`KernelCall`."""
    params = eqn.params
    gm = params["grid_mapping"]
    grid = tuple(gm.grid)
    name = (params.get("name")
            or getattr(params["jaxpr"].debug_info, "func_name", None)
            or "pallas_call")

    mosaic = (params.get("compiler_params") or {}).get("mosaic_tpu")
    sem_raw = getattr(mosaic, "dimension_semantics", None)
    sem = tuple(getattr(s, "value", s) for s in sem_raw) if sem_raw else ()
    # undeclared dims default to "arbitrary" (sequential) — Mosaic's own
    # default, and the conservative one for the race rules
    sem = sem + ("arbitrary",) * (len(grid) - len(sem))

    operands: List[Operand] = []
    n_in = int(gm.num_inputs)
    for i, bm in enumerate(gm.block_mappings):
        sd = bm.array_aval
        operands.append(Operand(
            index=i, role="in" if i < n_in else "out",
            name=str(getattr(bm, "origin", "") or f"operand{i}"),
            block_shape=_block_dims(bm.block_shape),
            array_shape=tuple(int(d) for d in sd.shape),
            dtype=str(sd.dtype), itemsize=_itemsize(sd.dtype),
            smem=_is_smem(getattr(bm, "transformed_block_aval", "")),
            index_map=bm.index_map_jaxpr))

    body = params["jaxpr"]
    n_idx = int(gm.num_index_operands)
    scratch: List[Scratch] = []
    for var in body.invars[n_idx + len(gm.block_mappings):]:
        aval = var.aval
        shape = tuple(int(d) for d in getattr(aval, "shape", ()))
        dtype = getattr(aval, "dtype", np.float32)
        scratch.append(Scratch(
            shape=shape, dtype=str(dtype),
            nbytes=int(math.prod(shape)) * _itemsize(dtype),
            smem=_is_smem(aval)))

    aliases = tuple((int(a), int(b))
                    for a, b in params.get("input_output_aliases", ()))
    return KernelCall(
        name=name, grid=grid, semantics=sem, operands=operands,
        num_inputs=n_in, num_outputs=int(gm.num_outputs),
        scratch=scratch, aliases=aliases, body=body,
        num_index_operands=n_idx,
        vmem_limit=getattr(mosaic, "vmem_limit_bytes", None))


def extract_pallas_calls(closed_jaxpr) -> List[KernelCall]:
    """Every ``pallas_call`` in a (closed) jaxpr, however deeply nested
    under pjit/cond/scan/custom-vjp wrappers."""
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    eqns: list = []
    _walk_eqns(jaxpr, eqns)
    return [describe_call(e) for e in eqns]


# ---------------------------------------------------------------------------
# concrete index-map evaluation over the grid
# ---------------------------------------------------------------------------

def _grid_points(grid: Sequence[int]) -> Tuple[np.ndarray, bool]:
    """``(points, exhaustive)`` — all grid index tuples when the grid is
    small enough, else the boundary-slice subsample (every combination
    of {0, 1, mid, n-2, n-1} per axis)."""
    if not grid:
        return np.zeros((1, 0), np.int64), True
    total = math.prod(int(g) for g in grid)
    if total <= MAX_GRID_POINTS:
        axes = [range(int(g)) for g in grid]
        return np.array(list(itertools.product(*axes)),
                        np.int64).reshape(total, len(grid)), True
    axes = []
    for g in grid:
        g = int(g)
        axes.append(sorted({0, min(1, g - 1), g // 2,
                            max(g - 2, 0), g - 1}))
    pts = np.array(list(itertools.product(*axes)), np.int64)
    return pts, False


def _eval_index_map(index_map, pts: np.ndarray) -> np.ndarray:
    """Evaluate one BlockSpec index-map ClosedJaxpr at every grid point:
    ``(N, n_grid_dims) -> (N, n_block_dims)`` of block indices."""
    import jax
    import jax.numpy as jnp
    from jax import core as jax_core

    def one(*idx):
        return tuple(jax_core.eval_jaxpr(index_map.jaxpr,
                                         index_map.consts, *idx))

    if pts.shape[1] == 0:
        res = one()
        return np.asarray([[int(r) for r in res]], np.int64)
    try:
        cols = [jnp.asarray(pts[:, d], jnp.int32)
                for d in range(pts.shape[1])]
        outs = jax.vmap(one)(*cols)
        return np.stack([np.asarray(o, np.int64) for o in outs], axis=1)
    except Exception:  # noqa: BLE001 - fall back to per-point eval
        rows = []
        for row in pts:
            res = one(*[jnp.int32(int(x)) for x in row])
            rows.append([int(r) for r in res])
        return np.asarray(rows, np.int64)


# ---------------------------------------------------------------------------
# kernel-body ref usage (reads / writes / conditional writes per operand)
# ---------------------------------------------------------------------------

def _ref_usage(call: KernelCall) -> Dict[int, Dict[str, int]]:
    """``{operand index: {"reads": n, "writes": n, "cond_writes": n}}``
    over the kernel body (scratch operands keyed past the block-mapped
    ones).  ``pl.when`` lowers to ``cond``, so stores under it count as
    conditional; loop bodies (scan/while/fori) count as unconditional —
    the torn-alias rule targets *skippable* stores, not repeated ones."""
    usage: Dict[int, Dict[str, int]] = {}

    def rec(idx: int) -> Dict[str, int]:
        return usage.setdefault(idx, {"reads": 0, "writes": 0,
                                      "cond_writes": 0})

    def look(refmap, v) -> Optional[int]:
        try:                     # Literal invars are unhashable
            return refmap.get(v)
        except TypeError:
            return None

    def remap(refmap, sub_vars, outer_vars) -> Dict[Any, int]:
        out = {}
        for sv, ov in zip(sub_vars, outer_vars):
            idx = look(refmap, ov)
            if idx is not None:
                out[sv] = idx
        return out

    def walk(jaxpr, refmap: Dict[Any, int], in_cond: bool) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "get":
                idx = look(refmap, eqn.invars[0])
                if idx is not None:
                    rec(idx)["reads"] += 1
                continue
            if prim in ("swap", "addupdate"):
                idx = look(refmap, eqn.invars[0])
                if idx is not None:
                    u = rec(idx)
                    if prim == "addupdate":
                        u["reads"] += 1
                    u["cond_writes" if in_cond else "writes"] += 1
                continue
            if prim == "cond":
                branches = eqn.params.get("branches", ())
                for br in branches:
                    sub = getattr(br, "jaxpr", br)
                    walk(sub, remap(refmap, sub.invars, eqn.invars[1:]),
                         True)
                continue
            if prim == "while":
                cn = int(eqn.params.get("cond_nconsts", 0))
                bn = int(eqn.params.get("body_nconsts", 0))
                carry = eqn.invars[cn + bn:]
                for key, consts in (("cond_jaxpr", eqn.invars[:cn]),
                                    ("body_jaxpr",
                                     eqn.invars[cn:cn + bn])):
                    cj = eqn.params.get(key)
                    if cj is None:
                        continue
                    sub = getattr(cj, "jaxpr", cj)
                    walk(sub, remap(refmap, sub.invars,
                                    list(consts) + list(carry)),
                         in_cond)
                continue
            # generic descent (pjit, scan, custom_* ...): positional
            # alignment when the sub-jaxpr's invars match 1:1
            for value in eqn.params.values():
                for sub in _sub_jaxprs(value):
                    if len(sub.invars) != len(eqn.invars):
                        continue
                    walk(sub, remap(refmap, sub.invars, eqn.invars),
                         in_cond)

    refmap = {}
    start = call.num_index_operands
    for j, var in enumerate(call.body.invars[start:]):
        refmap[var] = j          # 0..nin+nout-1 block-mapped, then scratch
    walk(call.body, refmap, False)
    return usage


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------

def vmem_ceiling() -> int:
    """The VMEM working-set ceiling in bytes: twice the streaming
    budget (``APEX_TPU_VMEM_BUDGET_MB`` names the *half* reserved for
    one copy of the streams; the checker counts every stream's
    double-buffer partner explicitly, so the ceiling is the full 2x
    budget — ~16 MiB, the physical VMEM, at defaults)."""
    from apex_tpu.ops.pallas.geometry import vmem_budget
    return 2 * vmem_budget()


def _cdiv(a: int, b: int) -> int:
    return -(-a // b) if b else 0


def _varies_along(pts: np.ndarray, blocks: List[tuple], d: int) -> bool:
    groups: Dict[tuple, tuple] = {}
    for p, b in zip(map(tuple, pts), blocks):
        key = p[:d] + p[d + 1:]
        prev = groups.setdefault(key, b)
        if prev != b:
            return True
    return False


def _fmt_bytes(n: int) -> str:
    return f"{n / (1 << 20):.2f} MiB" if n >= 1 << 20 else f"{n} B"


def lint_call(call: KernelCall,
              budget_bytes: Optional[int] = None) -> List[Finding]:
    """All six rule families over one extracted ``pallas_call``."""
    findings: List[Finding] = []
    f = findings.append

    if not all(isinstance(g, int) or hasattr(g, "__index__")
               for g in call.grid):
        f(Finding(PASS_NAME, "warning",
                  f"{call.name}: grid {call.grid} is not concrete — "
                  f"footprints unevaluable, rules skipped",
                  op="pallas-unevaluable"))
        return findings
    grid = tuple(int(g) for g in call.grid)
    pts, exhaustive = _grid_points(grid)
    par_dims = [d for d in range(len(grid))
                if call.semantics[d] == "parallel" and grid[d] > 1]

    # -- footprints: per operand, the evaluated block index per point --
    blocks: Dict[int, List[tuple]] = {}
    for op in call.operands:
        try:
            arr = _eval_index_map(op.index_map, pts)
        except Exception as e:  # noqa: BLE001 - per-operand isolation
            f(Finding(PASS_NAME, "warning",
                      f"{call.name}: index map of {op.name} failed to "
                      f"evaluate ({type(e).__name__}: {e}) — rules "
                      f"skipped for this operand",
                      op="pallas-unevaluable"))
            continue
        blocks[op.index] = [tuple(int(x) for x in row) for row in arr]

    usage = _ref_usage(call)

    # -- (b1) OOB: a block origin at/past the array end has no mask ----
    for op in call.operands:
        bl = blocks.get(op.index)
        if bl is None:
            continue
        for pt, b in zip(map(tuple, pts), bl):
            bad = [d for d in range(len(b))
                   if b[d] < 0
                   or (op.array_shape[d] > 0
                       and b[d] * op.block_shape[d] >= op.array_shape[d])]
            if bad:
                d = bad[0]
                f(Finding(
                    PASS_NAME, "error",
                    f"{call.name}: {op.role}put {op.name} block index "
                    f"{b} at grid point {pt} puts dim {d} origin "
                    f"{b[d] * op.block_shape[d]} outside the array "
                    f"{op.array_shape} — Mosaic masks only the "
                    f"overhang of the last in-bounds block; this "
                    f"block is fully out of bounds",
                    op="pallas-oob-unmasked", dtype=op.dtype,
                    example=f"grid={grid} block={op.block_shape}"))
                break

    # -- (b2) coverage: every output tile must be written by some point
    for op in call.operands:
        if op.role != "out":
            continue
        bl = blocks.get(op.index)
        if bl is None:
            continue
        if not exhaustive:
            f(Finding(PASS_NAME, "info",
                      f"{call.name}: grid {grid} exceeds "
                      f"{MAX_GRID_POINTS} points — output coverage of "
                      f"{op.name} checked on boundary slices only",
                      op="pallas-coverage-sampled"))
            continue
        tiles = [_cdiv(op.array_shape[d], op.block_shape[d])
                 for d in range(len(op.block_shape))]
        if math.prod(tiles) > MAX_GRID_POINTS:
            f(Finding(PASS_NAME, "info",
                      f"{call.name}: {op.name} tiling {tiles} too "
                      f"large to enumerate — coverage unchecked",
                      op="pallas-coverage-sampled"))
            continue
        missing = set(itertools.product(*[range(t) for t in tiles])) \
            - set(bl)
        if missing:
            ex = sorted(missing)[0]
            f(Finding(
                PASS_NAME, "error",
                f"{call.name}: output {op.name} tile {ex} (of "
                f"{len(missing)} uncovered tile(s) in the "
                f"{tiles} tiling) is never written by any grid "
                f"point — it ships whatever HBM held before the "
                f"kernel ran",
                op="pallas-uncovered-output", dtype=op.dtype,
                count=len(missing),
                example=f"grid={grid} block={op.block_shape} "
                        f"array={op.array_shape}"))

    # -- (a1)+(d): races and carried accumulators across parallel dims
    for op in call.operands:
        if op.role != "out":
            continue
        bl = blocks.get(op.index)
        if bl is None or not par_dims:
            continue
        u = usage.get(op.index, {})
        reads = u.get("reads", 0) > 0
        seen: Dict[tuple, tuple] = {}
        hit = None
        for pt, b in zip(map(tuple, pts), bl):
            parc = tuple(pt[d] for d in par_dims)
            prev = seen.setdefault(b, parc)
            if prev != parc:
                hit = (b, prev, parc)
                break
        if hit is None:
            continue
        b, p1, p2 = hit
        par_names = [f"dim {d}" for d in par_dims]
        if reads:
            f(Finding(
                PASS_NAME, "error",
                f"{call.name}: output {op.name} carries accumulator "
                f"state (the kernel reads it) but is revisited at "
                f"block {b} by grid points whose parallel "
                f"coordinates differ ({p1} vs {p2} on "
                f"{'/'.join(par_names)}) — accumulation order needs "
                f"sequential ('arbitrary') semantics on the carrying "
                f"dimension",
                op="pallas-seq-accum-parallel", dtype=op.dtype,
                example=f"grid={grid} semantics={call.semantics}"))
        else:
            f(Finding(
                PASS_NAME, "error",
                f"{call.name}: output {op.name} block {b} is written "
                f"by grid points with different parallel coordinates "
                f"({p1} vs {p2} on {'/'.join(par_names)}) — "
                f"write-write race: parallel iterations execute in "
                f"unspecified order",
                op="pallas-parallel-race", dtype=op.dtype,
                example=f"grid={grid} semantics={call.semantics}"))

    # -- (a2) aliased input/output pairs ------------------------------
    for ain, aout in call.aliases:
        out_idx = call.num_inputs + aout
        if ain >= len(call.operands) or out_idx >= len(call.operands):
            continue
        in_op, out_op = call.operands[ain], call.operands[out_idx]
        bi, bo = blocks.get(ain), blocks.get(out_idx)
        if bi is None or bo is None:
            continue
        mismatch = next((i for i, (a, b) in enumerate(zip(bi, bo))
                         if a != b), None)
        if mismatch is not None:
            pt = tuple(pts[mismatch])
            f(Finding(
                PASS_NAME, "error",
                f"{call.name}: aliased pair ({in_op.name} -> "
                f"{out_op.name}) walks different blocks at grid point "
                f"{pt} (read {bi[mismatch]}, write {bo[mismatch]}) — "
                f"the in-place read can observe a block an earlier "
                f"step already overwrote",
                op="pallas-alias-race", dtype=in_op.dtype,
                example=f"grid={grid}"))
        u = usage.get(out_idx, {})
        if u.get("writes", 0) == 0 and u.get("cond_writes", 0) > 0:
            f(Finding(
                PASS_NAME, "error",
                f"{call.name}: aliased output {out_op.name} (donated "
                f"from {in_op.name}) is only ever stored under a "
                f"condition (pl.when) — the skipped-store path "
                f"leaves the block holding the donated input's "
                f"bytes, the torn-alias class behind the donate= "
                f"skip-cond caveat",
                op="pallas-alias-race", dtype=out_op.dtype,
                example=f"cond_writes={u.get('cond_writes', 0)}"))
        if par_dims:
            # RAW carried across parallel iterations: a parallel
            # sibling's write lands in a block this point reads
            writers = {b: tuple(pt[d] for d in par_dims)
                       for pt, b in zip(map(tuple, pts), bo)}
            for pt, b in zip(map(tuple, pts), bi):
                parc = tuple(pt[d] for d in par_dims)
                w = writers.get(b)
                if w is not None and w != parc:
                    f(Finding(
                        PASS_NAME, "error",
                        f"{call.name}: aliased read {in_op.name} at "
                        f"grid point {pt} touches block {b}, which a "
                        f"grid point with different parallel "
                        f"coordinates ({w}) writes in place — "
                        f"read-after-write carried across parallel "
                        f"iterations",
                        op="pallas-parallel-race", dtype=in_op.dtype,
                        example=f"grid={grid} "
                                f"semantics={call.semantics}"))
                    break

    # -- (c) VMEM working set vs the budget ceiling -------------------
    working = 0
    detail = []
    for op in call.operands:
        if op.smem:
            continue
        nbytes = int(math.prod(op.block_shape)) * op.itemsize
        bl = blocks.get(op.index)
        varying = bl is not None and any(
            _varies_along(pts, bl, d) for d in range(len(grid)))
        mult = 2 if varying else 1
        working += mult * nbytes
        detail.append(f"{op.name} {mult}x{_fmt_bytes(nbytes)}")
    for i, scr in enumerate(call.scratch):
        if scr.smem:
            continue
        working += scr.nbytes
        detail.append(f"scratch[{i}] {_fmt_bytes(scr.nbytes)}")
    # A call that asks Mosaic for a scoped limit of its own is held to
    # what it asked for, not to the default every other call gets.
    if budget_bytes is not None:
        ceiling, origin = int(budget_bytes), "the caller's budget"
    elif call.vmem_limit is not None:
        ceiling, origin = int(call.vmem_limit), "the call's own " \
            "vmem_limit_bytes"
    else:
        ceiling, origin = vmem_ceiling(), "2x the " \
            "APEX_TPU_VMEM_BUDGET_MB streaming budget"
    if working > ceiling:
        f(Finding(
            PASS_NAME, "error",
            f"{call.name}: per-grid-step VMEM working set "
            f"{_fmt_bytes(working)} exceeds the ceiling "
            f"{_fmt_bytes(ceiling)} ({origin}) — "
            f"{'; '.join(detail)}",
            op="pallas-vmem-overflow", bytes=working))

    f(Finding(
        PASS_NAME, "info",
        f"{call.name}: grid={grid} semantics={call.semantics} "
        f"operands={len(call.operands)} scratch={len(call.scratch)} "
        f"aliases={len(call.aliases)} working set "
        f"{_fmt_bytes(working)} / {_fmt_bytes(ceiling)}",
        op="pallas-call", bytes=working))
    return findings


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def lint_jaxpr(closed_jaxpr,
               budget_bytes: Optional[int] = None) -> List[Finding]:
    """All rule findings over every ``pallas_call`` in a jaxpr."""
    calls = extract_pallas_calls(closed_jaxpr)
    if not calls:
        return [Finding(PASS_NAME, "info",
                        "no pallas_call in this program (0 kernels "
                        "checked)", op="pallas-call", count=0)]
    findings: List[Finding] = []
    for call in calls:
        findings.extend(lint_call(call, budget_bytes=budget_bytes))
    return findings


def lint_fn(fn, *args, budget_bytes: Optional[int] = None,
            **kwargs) -> Report:
    """The standalone API: trace ``fn`` on example args (no lowering,
    no compilation) and run every rule over the pallas_calls found.

    ``fn`` may be jitted or plain; kernels traced with
    ``interpret=True`` (the off-TPU path) lint identically — the
    jaxpr-level ``pallas_call`` carries the same grid/BlockSpec
    metadata either way.
    """
    import jax
    closed = jax.make_jaxpr(lambda *a, **k: fn(*a, **k))(*args, **kwargs)
    return make_report(lint_jaxpr(closed, budget_bytes=budget_bytes),
                       (PASS_NAME,))


def pallas_kernel_pass(ctx, budget_bytes: Optional[int] = None,
                       **_opts) -> List[Finding]:
    """The registered pass: reads the jaxpr captured on the context
    (:func:`~apex_tpu.analysis.analyze` records it whenever this pass
    is requested); degrades to an info finding when absent — StableHLO
    alone has already erased the BlockSpec structure."""
    closed = getattr(ctx, "closed_jaxpr", None)
    if closed is None:
        return [Finding(
            PASS_NAME, "info",
            "skipped: no jaxpr captured on this context — request the "
            "pass through analyze() (which traces the jaxpr alongside "
            "the lowering) or use pallas_lint.lint_fn directly")]
    return lint_jaxpr(closed, budget_bytes=budget_bytes)


register_pass(PASS_NAME, pallas_kernel_pass)
