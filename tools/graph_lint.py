"""Graph lint over the in-tree model families' train and decode lanes.

Runs every :mod:`apex_tpu.analysis` pass over the four model families
(MLP, ResNet, GPT, BERT — tiny configs, CPU-safe, seconds per family):

- the **graph passes** (donation, sharding, collectives,
  constant-capture) and the **memlint passes** (memory, cost, syncs)
  run on the full O1/O2 ``amp.make_train_step`` programs with the Amp
  state donated — the program production actually runs, lowered and
  compiled ONCE per lane on the host backend (no device execution);
  every pass shares that single :class:`~apex_tpu.analysis.PassContext`;
- the **policy pass** runs on the O1 *forward* (the audit's documented
  scope — the AD-generated backward legitimately accumulates in the
  wire dtype, see ``apex_tpu/analysis/policy.py``), sharing the model
  builders with ``tools/policy_audit.py``;
- the **decode lanes** lint the jitted KV-cached generation step
  (``apex_tpu.models.generate._generate_impl``) at bench-shaped tiny
  configs, and ``--emit-json`` additionally lowers the
  ``dryrun_multichip`` slices on the 8-device virtual CPU mesh to
  record each slice's static per-device HBM;
- the **serve lanes** lint the continuous-batching engine's compiled
  programs (``apex_tpu.serve.ServeEngine``: paged KV pools, page
  tables, fused sampling epilogue, donated carries) — the serving
  static-shape contract's static half: no host callback and no
  retrace hazard on the token loop.  Since the disaggregated fleet
  (``apex_tpu.serve.router``) split the phases onto separate mesh
  slices, the lane family covers BOTH split steps: ``serve_step``
  (monolithic shape) + ``serve_decode`` (decode-replica shape) for
  the decode program, and ``serve_prefill`` for the prefill worker's
  chunked-prefill program.

Per-family collective byte budgets are pinned at zero: a single-chip
train step has no collectives, so ANY appearing is a comm-volume
regression (multi-chip programs get their budgets where their meshes
are built — the dryrun slices in ``__graft_entry__.py``).

``--memory-budget [BYTES]`` arms the per-device peak-HBM gate on every
lane (bare flag = the v5e 16 GiB default; suffixes ``KiB``/``MiB``/
``GiB`` accepted).  ``--emit-json MEMLINT_rN.json`` writes the
committed memory-lint artifact — per-lane ``peak_hbm_bytes``,
donation-aliasing table, cost-model flops/bytes and the multichip slice
table — validated by ``tools/gate_hygiene.py`` against
``apex_tpu/analysis/memlint.py``.

One JSON line per lane plus a human summary; exit 1 on any finding of
``error`` severity — wired as ``tests/l0/test_graph_lint.py`` so the
clean-program guarantee is continuously enforced.

The **precision pass** (``apex_tpu/analysis/precision.py``) also runs
on every lane, with the lane's resolved ``amp.policy.Properties`` in
the PassContext: forced sub-f32 matmul accumulation, long 16-bit
reductions, f32→16→f32 double rounds, non-f32 masters/moments under
O2, and loss-scale placement (scale dominates the backward, unscale
dominates the update).  ``--passes precision`` defaults to the full
O0–O4 train matrix plus decode (o4 is the fp8 regime — delayed-scaling
state, e4m3/e5m2 quantizes — carrying the three fp8 contract rules);
``--emit-json PRECLINT_rN.json``
writes the committed precision artifact (schema in
``apex_tpu/analysis/preclint.py``, validated by gate hygiene).

The **export-compat pass** (``apex_tpu/analysis/export.py``) is
registered too — ``--passes export-compat`` lints any lane's
AOT-serializability (host callbacks, platform-pinned custom calls,
static captures, baked constants); ``tools/aot_export.py`` runs it as
part of the export gate that builds the content-addressed executable
cache from these same lanes.

Usage:
    python tools/graph_lint.py [--families mlp,gpt] [--passes donation,...]
                               [--lanes o0,o1,o2,o3,decode,serve]
                               [--no-compile]
                               [--memory-budget [BYTES]]
                               [--emit-json MEMLINT_r01.json|PRECLINT_r01.json]
                               [-v]
"""

import argparse
import json
import os
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

# CPU-safe by default: lint lowers/compiles for the host platform unless
# the caller pins a real chip (same env knob as the test suite).  Must
# happen before any jax backend initialization.
# The multichip lanes additionally need 8 virtual host devices, which
# only an XLA_FLAGS set before backend init can provide.
os.environ.setdefault("APEX_TPU_KERNELS", "jnp")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu"))

from apex_tpu import amp, analysis  # noqa: E402
from apex_tpu.analysis import cost as cost_mod  # noqa: E402
from apex_tpu.analysis import memory as memory_mod  # noqa: E402
from apex_tpu.optimizers import FusedAdam  # noqa: E402

import policy_audit  # noqa: E402  (sibling tool: shared model builders)

GRAPH_PASSES = ("donation", "sharding", "collectives", "constant-capture")
#: the compiled-evidence memory/cost/sync passes — run on every lane,
#: sharing the lane's single lowering+compilation with the graph passes
MEMLINT_PASSES = ("memory", "cost", "syncs")
#: the precision-flow pass runs on every lane too (lowering-only; the
#: lane's resolved amp policy rides in the PassContext), as does the
#: SPMD deadlock-shape check (a collective under a rank-divergent
#: predicate — trivially clean on single-chip lanes, load-bearing on
#: the fleet lanes)
ALL_PASSES = GRAPH_PASSES + MEMLINT_PASSES + ("precision",
                                              "spmd-consistency",
                                              "policy")

#: train lanes the CLI can run (opt levels); decode rides separately.
#: o4 = the fp8 regime (apex_tpu.quant): delayed-scaling state in the
#: donated AmpState, e4m3/e5m2 quantizes in the lowered program — the
#: lane the three fp8 precision rules run against.
TRAIN_LANES = ("o0", "o1", "o2", "o3", "o4")

#: single-chip train steps imply ZERO collective bytes; any regression
#: that introduces one (an accidental psum, a sharding annotation leak)
#: fails the gate like an MFU-floor violation fails the bench.
COLLECTIVE_BUDGETS = {"mlp": {"total": 0}, "resnet": {"total": 0},
                      "gpt": {"total": 0}, "bert": {"total": 0}}

FAMILIES = tuple(policy_audit.RAW_CASES)

#: decode lanes: (batch, prefill, new_tokens, kv_dtype) at the tiny
#: config — the static analog of the bench's gpt_small_tpu_decode_b{1,8}
#: lanes; decode_b1_kv8 is the int8-KV path (quantize-on-write,
#: dequant fused into the attention read — the kv8 bench config's
#: program, machine-checked like the dense one).
DECODE_LANES = {"decode_b1": (1, 8, 8, None),
                "decode_b2": (2, 8, 8, None),
                "decode_b1_kv8": (1, 8, 8, "int8")}

#: serve lanes: (num_slots, block_size, num_blocks, max_blocks_per_slot)
#: — the continuous-batching engine's compiled decode step
#: (``apex_tpu.serve.ServeEngine``) at a tiny config.  The lane is the
#: static half of the serving static-shape contract: the step must
#: carry no host callback on the token loop and no statically-bound
#: numeric scalar (either would serialize or retrace the serving
#: fleet's hot loop); the runtime half (one trace across a whole
#: admit/retire stream) lives in tests/l0/test_serve_engine.py.
#: ``serve_step`` is the monolithic engine's shape; ``serve_decode``
#: is the SAME program class at a disaggregated decode-replica shape
#: (``apex_tpu.serve.router.DecodeReplica`` — more slots, its own
#: pool), so the split fleet's decode half is machine-checked at its
#: own geometry.
SERVE_LANES = {"serve_step": (2, 4, 9, 4),
               "serve_decode": (4, 4, 17, 4)}

#: the split fleet's OTHER compiled program: the prefill worker's
#: chunked prefill (``ServeEngine._prefill_chunk`` — what
#: ``apex_tpu.serve.router.PrefillWorker`` dispatches per chunk on the
#: prefill mesh slice).  Same tuple shape as SERVE_LANES; the chunk
#: length is the config's ``prefill_chunk`` (= block_size here).
SERVE_PREFILL_LANES = {"serve_prefill": (2, 4, 9, 4)}

#: the speculative-decoding verifier (``apex_tpu.serve.spec.
#: SpecEngine._verify_step``): the b×(k+1) multi-token cached forward
#: that scores every slot's draft proposals in ONE dispatch, samples
#: the target's draw at each position with the slot's key ladder, and
#: returns the accepted counts — the serve engine's third compiled
#: program class.  Tuple = (num_slots, block_size, num_blocks,
#: max_blocks_per_slot, k); lints through the same full pass matrix
#: as the decode step (no host callback / no static scalar on the
#: speculation loop, donated carry fully aliased).
SERVE_VERIFY_LANES = {"serve_verify": (2, 4, 9, 4, 3)}


def build_train_step(family: str, raw=None, opt_level: str = "O1"):
    """(jitted_step, example_args, properties): the full train step —
    FusedAdam, dynamic loss scaling, Amp state donated — for one model
    family at ``opt_level``, plus the resolved policy for the
    precision pass's :class:`~apex_tpu.analysis.PassContext`.  ``raw``
    reuses an already-built ``(loss_fn, params, batch)``."""
    loss_fn, params, batch = raw or policy_audit.RAW_CASES[family]()
    a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level=opt_level,
                       verbosity=0)
    state = a.init(params)
    step = jax.jit(amp.make_train_step(a, loss_fn), donate_argnums=0)
    return step, (state, *batch), a.properties


def build_decode_step(batch: int = 1, prefill: int = 8,
                      new_tokens: int = 8, kv_dtype=None):
    """(jitted_decode, args, kwargs, properties): the KV-cached
    generation step at a tiny config in the bf16 serving layout — the
    program ``apex_tpu.models.generate.generate`` dispatches — plus
    the O2 serving policy it was cast under.  ``kv_dtype="int8"``
    builds the int8-KV variant (per-position scales, fused dequant)."""
    from importlib import import_module
    gen = import_module("apex_tpu.models.generate")   # the module —
    # ``apex_tpu.models`` re-exports the ``generate`` FUNCTION under
    # the same name, shadowing a ``from ... import generate``
    from apex_tpu.models.gpt import GPTModel, gpt_tiny

    cfg = gpt_tiny()
    model = GPTModel(cfg)
    prompt = jnp.zeros((batch, prefill), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), prompt)["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)   # bf16, the serving layout
    stacked = gen._stack_layer_params(params, cfg.num_layers)
    top = {k: v for k, v in params.items()
           if not k.startswith("block_") and k != "layers"}
    args = (top, stacked, prompt, jnp.float32(0.0),
            jax.random.PRNGKey(0))
    kwargs = dict(cfg=cfg, max_new_tokens=new_tokens, sample=False,
                  kv_dtype=kv_dtype)
    return gen._generate_impl, args, kwargs, a.properties


def build_serve_engine(num_slots: int = 2, block_size: int = 4,
                       num_blocks: int = 9,
                       max_blocks_per_slot: int = 4,
                       prefill_chunk: int = None, registry=None):
    """(engine, properties): the ONE construction of the tiny-gpt
    serve engine every serve lane shares — gpt_tiny init, the O2
    serving cast, ``ServeConfig`` — used by the lint lanes here, the
    obs_report overhead/lint lanes, and ``tools/continuous_profile``,
    so a carry or scheduler change can never leave an overhead lane
    measuring a different engine than the one the serve gate lints."""
    from apex_tpu.models.gpt import GPTModel, gpt_tiny
    from apex_tpu.serve import ServeConfig, ServeEngine

    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)
    scfg = ServeConfig(num_slots=num_slots, block_size=block_size,
                       num_blocks=num_blocks,
                       max_blocks_per_slot=max_blocks_per_slot,
                       prefill_chunk=prefill_chunk or block_size)
    eng = ServeEngine(params, cfg, scfg, registry=registry)
    return eng, a.properties


def build_serve_step(num_slots: int = 2, block_size: int = 4,
                     num_blocks: int = 9, max_blocks_per_slot: int = 4):
    """(jitted_step, args, properties): the serve engine's compiled
    continuous-batching decode step at a tiny config — paged KV pools
    + per-slot page tables + fused sampling epilogue, carries donated —
    plus the O2 serving policy the params were cast under."""
    eng, props = build_serve_engine(num_slots, block_size, num_blocks,
                                    max_blocks_per_slot)
    return eng._decode_step, eng.decode_step_args(), props


def build_serve_prefill(num_slots: int = 2, block_size: int = 4,
                        num_blocks: int = 9,
                        max_blocks_per_slot: int = 4):
    """(jitted_chunk, args, properties): the serve engine's compiled
    chunked-prefill program — one ``(1, prefill_chunk)`` prompt chunk
    written through a slot's page table, KV pools donated — the
    program the disaggregated fleet's prefill worker dispatches on its
    own mesh slice.  ``start``/``n_valid`` are DYNAMIC int32 args
    (one executable per chunk shape, never per position)."""
    eng, a_props = build_serve_engine(num_slots, block_size,
                                      num_blocks, max_blocks_per_slot)
    scfg = eng.scfg
    s = eng.sched
    args = (eng.top, eng.stacked, eng.carry["kc"], eng.carry["vc"],
            eng.carry.get("ks"), eng.carry.get("vs"),
            jnp.asarray(s.page_table[0]),
            jnp.zeros((1, scfg.prefill_chunk), jnp.int32),
            jnp.int32(0), jnp.int32(scfg.prefill_chunk))
    return eng._prefill_chunk, args, a_props


def build_serve_verify(num_slots: int = 2, block_size: int = 4,
                       num_blocks: int = 9, max_blocks_per_slot: int = 4,
                       k: int = 3):
    """(jitted_verify, args, properties): the speculative-decoding
    verify step at a tiny config — the target model scoring ``k``
    draft proposals per slot in one b×(k+1) dispatch (KV written for
    every fed position through the paged pools, acceptance computed
    on device, carry donated) — plus the O2 serving policy.  The
    draft is the target's truncated first layer (the layer-skip
    self-draft), which shapes the proposal argument without needing a
    second checkpoint."""
    from apex_tpu.models.gpt import GPTModel, gpt_tiny
    from apex_tpu.serve import (ServeConfig, SpecConfig, SpecEngine,
                                truncated_draft)

    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)
    scfg = ServeConfig(num_slots=num_slots, block_size=block_size,
                       num_blocks=num_blocks,
                       max_blocks_per_slot=max_blocks_per_slot,
                       prefill_chunk=block_size)
    dp, dcfg = truncated_draft(params, cfg, max(1, cfg.num_layers - 1))
    eng = SpecEngine(params, cfg, scfg, dp, dcfg, SpecConfig(k=k))
    s = eng.sched
    args = (eng.top, eng.stacked, eng.carry,
            jnp.zeros((num_slots, k), jnp.int32),
            jnp.asarray(s.last_tok), jnp.asarray(s.lengths),
            jnp.asarray(s.active), jnp.asarray(s.page_table),
            jnp.asarray(s.temperature), jnp.asarray(s.top_k),
            jnp.asarray(s.top_p))
    return eng._verify_step, args, a.properties


def _lint_serve_program(lane: str, fn, args, props, passes, compile,
                        memory_budget, _collect):
    passes = tuple(
        p for p in (passes or GRAPH_PASSES + MEMLINT_PASSES
                    + ("precision",))
        if p not in ("policy", "pallas-kernel"))
    if not passes:
        return analysis.Report()
    lowered = analysis.lower_quiet(fn, *args)
    ctx = analysis.build_context(lowered, compile=compile, policy=props)
    options = {"collectives": {"budget": {"total": 0}}}
    options.update(_memlint_options(memory_budget))
    report = analysis.run_passes(ctx, passes=passes, options=options)
    if _collect is not None:
        _collect[lane] = _lane_record(ctx, report)
    return report


def lint_serve(lane: str, passes=None, compile: bool = True,
               memory_budget=None, _collect=None):
    """Lint one serve decode-step lane (graph + memlint + precision
    passes; no policy — the serving step is a bf16 forward by design,
    like the decode lanes)."""
    if passes is not None and not tuple(
            p for p in passes if p not in ("policy", "pallas-kernel")):
        return analysis.Report()
    slots, bs, nb, mb = SERVE_LANES[lane]
    fn, args, props = build_serve_step(slots, bs, nb, mb)
    return _lint_serve_program(lane, fn, args, props, passes, compile,
                               memory_budget, _collect)


def lint_serve_prefill(lane: str, passes=None, compile: bool = True,
                       memory_budget=None, _collect=None):
    """Lint one serve prefill-chunk lane — the split fleet's other
    compiled program, under the same pass matrix as the decode
    lanes."""
    if passes is not None and not tuple(
            p for p in passes if p not in ("policy", "pallas-kernel")):
        return analysis.Report()
    slots, bs, nb, mb = SERVE_PREFILL_LANES[lane]
    fn, args, props = build_serve_prefill(slots, bs, nb, mb)
    return _lint_serve_program(lane, fn, args, props, passes, compile,
                               memory_budget, _collect)


def lint_serve_verify(lane: str, passes=None, compile: bool = True,
                      memory_budget=None, _collect=None):
    """Lint one speculative-verify lane — the b×(k+1) verifier step
    the spec engine dispatches once per speculation round, under the
    same pass matrix as the decode lanes."""
    if passes is not None and not tuple(
            p for p in passes if p not in ("policy", "pallas-kernel")):
        return analysis.Report()
    slots, bs, nb, mb, k = SERVE_VERIFY_LANES[lane]
    fn, args, props = build_serve_verify(slots, bs, nb, mb, k)
    return _lint_serve_program(lane, fn, args, props, passes, compile,
                               memory_budget, _collect)


def _memlint_options(memory_budget=None):
    opts = {}
    if memory_budget is not None:
        opts["memory"] = {"budget_bytes": int(memory_budget)}
    return opts


def _lane_record(ctx, report) -> dict:
    """The MEMLINT lane record for one analyzed program (see
    ``apex_tpu/analysis/memlint.py`` for the schema)."""
    stats = memory_mod.context_memory_stats(ctx) \
        if ctx.compiled is not None else None
    ct = cost_mod.context_cost_table(ctx) \
        if ctx.compiled is not None else None
    rec = {
        "ok": report.ok,
        "peak_hbm_bytes": int(stats["peak_hbm_bytes"]) if stats else 0,
        "breakdown": {k: v for k, v in (stats or {}).items()
                      if k != "peak_hbm_bytes"},
        # None = numbering ambiguous on this jax version; the memory
        # pass records that as its own finding
        "donation": memory_mod.donation_table(ctx) or [],
        "cost": ct or {},
        "findings": report.to_dict()["counts"],
    }
    return rec


def lint_family(family: str, passes=ALL_PASSES, compile: bool = True,
                opt_level: str = "O1", memory_budget=None,
                raw=None, _collect=None):
    """Run the requested passes over one family; returns the merged
    :class:`~apex_tpu.analysis.Report` (train-step graph+memlint passes
    + forward policy pass).  The model is built once (``raw`` reuses an
    already-built ``(loss_fn, params, batch)`` across lanes); the train
    step is lowered ONCE and compiled at most once, and every
    non-policy pass shares that PassContext (the policy pass analyzes
    the forward — a different program — and is the only second
    lowering)."""
    step_passes = tuple(p for p in passes if p != "policy")
    run_policy = "policy" in passes and opt_level == "O1"
    if not step_passes and not run_policy:
        # nothing to run on this lane: skip before paying the model
        # build (main() reports the empty report as a skipped lane)
        return analysis.Report()
    raw = loss_fn, params, batch = \
        raw or policy_audit.RAW_CASES[family]()
    report = analysis.Report()
    ctx = None
    if step_passes:
        step, args, props = build_train_step(family, raw=raw,
                                             opt_level=opt_level)
        closed_jaxpr = None
        if "pallas-kernel" in step_passes:
            # the pallas pass reads jaxpr-level BlockSpec structure,
            # and the step must TRACE with the pallas kernels routed
            # in (the CLI pins APEX_TPU_KERNELS=jnp for the text
            # passes) — a fresh jit wrapper keeps the jnp trace/lower
            # cache unpolluted
            prev = os.environ.get("APEX_TPU_KERNELS")
            os.environ["APEX_TPU_KERNELS"] = "pallas"
            try:
                pstep, pargs, _ = build_train_step(
                    family, raw=raw, opt_level=opt_level)
                closed_jaxpr = pstep.trace(*pargs).jaxpr
            except Exception:  # noqa: BLE001 - degrades to "skipped"
                closed_jaxpr = None
            finally:
                if prev is None:
                    os.environ.pop("APEX_TPU_KERNELS", None)
                else:
                    os.environ["APEX_TPU_KERNELS"] = prev
        lowered = analysis.lower_quiet(step, *args)
        ctx = analysis.build_context(lowered, compile=compile,
                                     policy=props,
                                     closed_jaxpr=closed_jaxpr)
        options = {"collectives":
                   {"budget": COLLECTIVE_BUDGETS.get(family, {})}}
        options.update(_memlint_options(memory_budget))
        report = analysis.run_passes(ctx, passes=step_passes,
                                     options=options)
    if run_policy:
        a = amp.initialize(opt_level="O1", verbosity=0)
        fwd = lambda p, *b: a.run(loss_fn, p, *b)  # noqa: E731
        report = report.merged(analysis.analyze(
            fwd, params, *batch, passes=("policy",), compile=False))
    if _collect is not None and ctx is not None:
        # the MERGED report: a policy error must show in the lane
        # record's ok/findings, or the CLI's "see the artifact"
        # failure message would point at a clean document
        _collect[f"{family}_{opt_level.lower()}_train"] = \
            _lane_record(ctx, report)
    return report


def lint_decode(lane: str, passes=None, compile: bool = True,
                memory_budget=None, _collect=None):
    """Lint one decode lane (graph + memlint passes; no policy — the
    decode program is a bf16 serving forward by design)."""
    passes = tuple(
        p for p in (passes or GRAPH_PASSES + MEMLINT_PASSES
                    + ("precision",))
        if p not in ("policy", "pallas-kernel"))
    if not passes:
        # e.g. --passes policy: nothing applies to a decode lane —
        # skip before paying the build + XLA compilation
        return analysis.Report()
    batch, prefill, new_tokens, kv_dtype = DECODE_LANES[lane]
    fn, args, kwargs, props = build_decode_step(batch, prefill,
                                                new_tokens, kv_dtype)
    lowered = fn.lower(*args, **kwargs)
    ctx = analysis.build_context(lowered, compile=compile, policy=props)
    options = {"collectives": {"budget": {"total": 0}}}
    options.update(_memlint_options(memory_budget))
    report = analysis.run_passes(ctx, passes=passes, options=options)
    if _collect is not None:
        _collect[lane] = _lane_record(ctx, report)
    return report


def multichip_slice_table(n_devices: int = 8) -> dict:
    """Static per-device HBM of each ``dryrun_multichip`` slice: build
    and lower+compile every slice on the virtual CPU mesh (nothing
    executes) and read XLA's memory analysis — the
    ``hbm_bytes_per_device`` column of ``MULTICHIP_SLICES.json``,
    derived from analysis instead of hand-waving.  A slice that cannot
    build/compile on this jax version records its error and moves on,
    exactly like the dryrun itself."""
    import __graft_entry__ as graft

    devices = jax.devices("cpu")[:n_devices]
    if len(devices) < n_devices:
        # same hazard __graft_entry__._dryrun_impl guards: if another
        # caller initialized jax's backends before this module's
        # XLA_FLAGS append, the virtual mesh is missing and every
        # per-device number would be silently wrong — fail, never
        # commit wrong gate memory under an "n_devices": 8 header
        raise RuntimeError(
            f"need {n_devices} CPU devices for the multichip slice "
            f"table, have {len(devices)}; jax's backends initialized "
            f"before xla_force_host_platform_device_count could take "
            f"effect — run tools/graph_lint.py as the entry point")
    out = {}
    for name, build in graft.SLICE_BUILDERS:
        try:
            step, args, _check = build(devices)
            compiled = step.lower(*args).compile()
            stats = memory_mod.per_device_stats(compiled)
            rec = {"ok": True}
            if stats:
                rec["hbm_bytes_per_device"] = stats["peak_hbm_bytes"]
                rec["breakdown"] = {k: v for k, v in stats.items()
                                    if k != "peak_hbm_bytes"}
            out[name] = rec
        except Exception as e:  # noqa: BLE001 - per-slice isolation
            out[name] = {"ok": False,
                         "error": f"{type(e).__name__}: {e}"[:200]}
    return out


#: ranks the fleet lanes simulate: every rank of a data-parallel fleet
#: lowers the SAME program, so each lane lowers the step once per rank
#: on the virtual mesh and cross-checks the collective schedules —
#: exactly what the runtime preflight
#: (:func:`apex_tpu.parallel.multiproc.spmd_preflight`) does with an
#: all-gather on a real cluster.
FLEET_RANKS = 8

#: fleet lanes: the DDP O1/O2 train steps (per-rank schedule
#: consistency + the conditional-collective deadlock check) and the
#: elastic reshape pair (8→4 shrink / 4→8 regrow — the
#: DurableCheckpointManager reshape lanes, which must stay
#: opcode-consistent even though groups/bytes legally change).
FLEET_LANES = ("ddp_o1_train", "ddp_o2_train",
               "reshape_8to4", "reshape_4to8")


def build_fleet_step(opt_level: str = "O1", n_devices: int = 8):
    """(jitted_step, example_args, properties): the DDP + amp train
    step under ``shard_map`` on the first ``n_devices`` of the virtual
    mesh — the program every rank of a data-parallel fleet compiles
    (grads reduced through ``DistributedDataParallel.reduce``, loss
    ``pmean``-ed, so the lowering carries the fleet's real collective
    schedule)."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.parallel import DistributedDataParallel
    from jax import shard_map

    devices = jax.devices("cpu")[:n_devices]
    if len(devices) < n_devices:
        # same hazard as multichip_slice_table: a mesh missing devices
        # would silently lower a different (smaller) schedule
        raise RuntimeError(
            f"need {n_devices} CPU devices for the fleet lanes, have "
            f"{len(devices)}; run tools/graph_lint.py as the entry "
            f"point so xla_force_host_platform_device_count applies")
    mesh = Mesh(np.array(devices), ("data",))
    params = {"w1": jax.random.normal(jax.random.PRNGKey(0), (8, 16)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}

    def loss_fn(p, xb):
        h = jax.nn.relu(xb @ p["w1"])
        return jnp.mean(jnp.square(h @ p["w2"]))

    ddp = DistributedDataParallel(axis_name="data")
    a = amp.initialize(optimizer=FusedAdam(lr=1e-3),
                       opt_level=opt_level, verbosity=0)
    state = a.init(params)
    step = amp.make_train_step(a, loss_fn, axis_name="data",
                               reduce_fn=ddp.reduce)

    def inner(s, xb):
        s2, m = step(s, xb[0])
        return s2, jax.lax.pmean(m["loss"], "data")

    fn = jax.jit(shard_map(inner, mesh=mesh,
                           in_specs=(P(), P("data")),
                           out_specs=(P(), P())))
    x = jax.random.normal(jax.random.PRNGKey(2), (n_devices, 4, 8))
    return fn, (state, x), a.properties


def _fleet_rank_schedule(opt_level: str, n_devices: int):
    """(stablehlo_text, collective_schedule) of one rank's lowering."""
    from apex_tpu.analysis import spmd as spmd_mod

    fn, args, _props = build_fleet_step(opt_level, n_devices)
    text = analysis.lower_quiet(fn, *args).as_text()
    return text, spmd_mod.collective_schedule(text)


def fleet_lane_result(lane: str, n_ranks: int = FLEET_RANKS):
    """(findings, lane_record) for one fleet lane — the shared core of
    :func:`lint_fleet` (CLI verdict) and :func:`emit_fleetlint` (the
    committed artifact), so the two can never diverge.  ``lane_record``
    matches the FLEETLINT schema's per-lane shape
    (:mod:`apex_tpu.analysis.fleetlint`), its ``consistent`` verdict
    re-derivable from the recorded per-rank hashes."""
    from apex_tpu.analysis import spmd as spmd_mod

    findings = []
    mismatches = []
    if lane in ("ddp_o1_train", "ddp_o2_train"):
        opt = lane.split("_")[1].upper()
        compare, div_keys = "schedule", spmd_mod._IDENTITY_KEYS
        scheds = {}
        ref_text = None
        for r in range(n_ranks):
            text, sched = _fleet_rank_schedule(opt, 8)
            if ref_text is None:
                ref_text = text
            scheds[str(r)] = sched
        findings.extend(spmd_mod.conditional_collective_findings(ref_text))
    elif lane in ("reshape_8to4", "reshape_4to8"):
        compare, div_keys = "opcodes", ("kind", "variant")
        text8, s8 = _fleet_rank_schedule("O2", 8)
        text4, s4 = _fleet_rank_schedule("O2", 4)
        scheds = {"mesh8": s8, "mesh4": s4} if lane == "reshape_8to4" \
            else {"mesh4": s4, "mesh8": s8}
        findings.extend(spmd_mod.conditional_collective_findings(
            text8 if lane == "reshape_8to4" else text4))
    else:
        raise KeyError(f"unknown fleet lane {lane!r}; have {FLEET_LANES}")

    labels = list(scheds)
    ref = labels[0]
    for lbl in labels[1:]:
        if compare == "schedule":
            findings.extend(spmd_mod.diff_schedules(
                f"rank {ref}", scheds[ref], f"rank {lbl}", scheds[lbl]))
        else:
            findings.extend(spmd_mod.reshape_pair_findings(
                ref, scheds[ref], lbl, scheds[lbl]))
        d = spmd_mod.first_divergence(scheds[ref], scheds[lbl], div_keys)
        if d is not None:
            mismatches.append({"ranks": [ref, lbl], "index": d[0],
                               "a": d[1], "b": d[2]})

    ranks = {
        lbl: {"schedule_hash": spmd_mod.schedule_fingerprint(s),
              "opcode_hash": spmd_mod.schedule_fingerprint(
                  s, opcodes_only=True),
              "n_collectives": len(s)}
        for lbl, s in scheds.items()}
    key = "schedule_hash" if compare == "schedule" else "opcode_hash"
    consistent = len({rec[key] for rec in ranks.values()}) == 1
    if compare == "schedule" and consistent:
        findings.append(analysis.Finding(
            "spmd-consistency", "info",
            f"{len(ranks)} per-rank lowerings schedule-consistent "
            f"({ranks[ref]['n_collectives']} collective(s), fingerprint "
            f"{ranks[ref]['schedule_hash'][:12]})",
            op="fleet", count=len(ranks)))
    return findings, {"compare": compare, "consistent": consistent,
                      "ranks": ranks, "mismatches": mismatches}


def lint_fleet(lane: str, passes=None, n_ranks: int = FLEET_RANKS,
               _collect=None):
    """Lint one fleet lane: per-rank lowerings of the DDP train step
    (or the reshape pair) diffed for SPMD schedule consistency.  Only
    the ``spmd-consistency`` pass applies — any other requested pass
    set skips the lane."""
    from apex_tpu.analysis.report import make_report

    if passes is not None and "spmd-consistency" not in passes:
        return analysis.Report()
    findings, rec = fleet_lane_result(lane, n_ranks=n_ranks)
    report = make_report(findings, ("spmd-consistency",))
    if _collect is not None:
        counts: dict = {}
        for f in findings:
            counts[f.severity] = counts.get(f.severity, 0) + 1
        _collect[lane] = dict(rec, findings=counts)
    return report


def emit_fleetlint(path: str, verbose: bool = False) -> int:
    """Write the FLEETLINT artifact: every fleet lane's per-rank
    schedule fingerprints, mismatch rows naming the first diverging op,
    and the re-derivable gate verdict.  Returns the number of error
    findings across all lanes."""
    lanes: dict = {}
    n_errors = 0
    for lane in FLEET_LANES:
        findings, rec = fleet_lane_result(lane)
        counts: dict = {}
        for f in findings:
            counts[f.severity] = counts.get(f.severity, 0) + 1
        lanes[lane] = dict(rec, findings=counts)
        n_errors += counts.get("error", 0)
        if verbose or counts.get("error", 0):
            print(f"--- {lane} ---", file=sys.stderr)
            for f in findings:
                print(f"  [{f.severity}] {f.op}: {f.message}",
                      file=sys.stderr)
    bad = sorted(n for n, rec in lanes.items() if not rec["consistent"])
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    doc = {
        "round": int(m.group(1)) if m else 0,
        "platform": jax.devices()[0].platform,
        "n_ranks": FLEET_RANKS,
        "lanes": lanes,
        "gate": {"ok": not bad, "inconsistent_lanes": len(bad)},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"fleetlint artifact written: {path} ({len(lanes)} lanes)",
          file=sys.stderr)
    return n_errors


def emit_memlint(path: str, families, memory_budget=None,
                 verbose: bool = False) -> int:
    """Write the MEMLINT artifact: every family's O1+O2 train lanes,
    the decode lanes and the multichip slice table.  Returns the number of error findings across all lanes."""
    lanes: dict = {}
    n_errors = 0
    for family in families:
        raw = policy_audit.RAW_CASES[family]()   # one build, three lanes
        for opt_level in ("O1", "O2", "O4"):
            rep = lint_family(family, compile=True, opt_level=opt_level,
                              memory_budget=memory_budget,
                              raw=raw, _collect=lanes)
            n_errors += len(rep.errors)
            if verbose:
                print(f"--- {family} {opt_level} ---\n{rep.format()}",
                      file=sys.stderr)
    for lane in DECODE_LANES:
        rep = lint_decode(lane, memory_budget=memory_budget,
                          _collect=lanes)
        n_errors += len(rep.errors)
        if verbose:
            print(f"--- {lane} ---\n{rep.format()}", file=sys.stderr)
    for lane in SERVE_LANES:
        rep = lint_serve(lane, memory_budget=memory_budget,
                         _collect=lanes)
        n_errors += len(rep.errors)
        if verbose:
            print(f"--- {lane} ---\n{rep.format()}", file=sys.stderr)
    for lane in SERVE_PREFILL_LANES:
        rep = lint_serve_prefill(lane, memory_budget=memory_budget,
                                 _collect=lanes)
        n_errors += len(rep.errors)
        if verbose:
            print(f"--- {lane} ---\n{rep.format()}", file=sys.stderr)
    for lane in SERVE_VERIFY_LANES:
        rep = lint_serve_verify(lane, memory_budget=memory_budget,
                                _collect=lanes)
        n_errors += len(rep.errors)
        if verbose:
            print(f"--- {lane} ---\n{rep.format()}", file=sys.stderr)

    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    doc = {
        "round": int(m.group(1)) if m else 0,
        "platform": jax.devices()[0].platform,
        "budget_bytes": int(memory_budget) if memory_budget else None,
        "lanes": lanes,
        "multichip": {"n_devices": 8,
                      "slices": multichip_slice_table(8)},
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"memlint artifact written: {path} ({len(lanes)} lanes)",
          file=sys.stderr)
    return n_errors


def emit_preclint(path: str, families, verbose: bool = False) -> int:
    """Write the PRECLINT artifact: the precision pass over every
    family's O0–O3 train lanes plus both decode lanes (lowering only —
    the precision pass needs no compiled executable, so the full
    18-lane matrix costs 18 lowerings and zero compiles).  Returns the
    number of error findings across all lanes."""
    from apex_tpu.analysis import precision as precision_mod

    lanes: dict = {}
    n_errors = 0

    def record(name, ctx):
        nonlocal n_errors
        findings, stats = precision_mod.precision_report(ctx)
        counts: dict = {}
        for f in findings:
            counts[f.severity] = counts.get(f.severity, 0) + 1
        ok = counts.get("error", 0) == 0
        n_errors += counts.get("error", 0)
        lanes[name] = {"ok": ok, "findings": counts, "checked": stats}
        if verbose or not ok:
            print(f"--- {name} ---", file=sys.stderr)
            for f in findings:
                print(f"  [{f.severity}] {f.op}: {f.message}",
                      file=sys.stderr)

    for family in families:
        raw = policy_audit.RAW_CASES[family]()   # one build, five lanes
        for opt_level in ("O0", "O1", "O2", "O3", "O4"):
            step, args, props = build_train_step(family, raw=raw,
                                                 opt_level=opt_level)
            lowered = analysis.lower_quiet(step, *args)
            ctx = analysis.build_context(lowered, compile=False,
                                         policy=props)
            record(f"{family}_{opt_level.lower()}_train", ctx)
    for lane, (b, p, n, kvd) in DECODE_LANES.items():
        fn, args, kwargs, props = build_decode_step(b, p, n, kvd)
        lowered = fn.lower(*args, **kwargs)
        ctx = analysis.build_context(lowered, compile=False, policy=props)
        record(lane, ctx)
    for lane, (slots, bs, nb, mb) in SERVE_LANES.items():
        fn, args, props = build_serve_step(slots, bs, nb, mb)
        lowered = analysis.lower_quiet(fn, *args)
        ctx = analysis.build_context(lowered, compile=False, policy=props)
        record(lane, ctx)
    for lane, (slots, bs, nb, mb) in SERVE_PREFILL_LANES.items():
        fn, args, props = build_serve_prefill(slots, bs, nb, mb)
        lowered = analysis.lower_quiet(fn, *args)
        ctx = analysis.build_context(lowered, compile=False, policy=props)
        record(lane, ctx)
    for lane, (slots, bs, nb, mb, k) in SERVE_VERIFY_LANES.items():
        fn, args, props = build_serve_verify(slots, bs, nb, mb, k)
        lowered = analysis.lower_quiet(fn, *args)
        ctx = analysis.build_context(lowered, compile=False, policy=props)
        record(lane, ctx)

    import numpy as np
    m = re.search(r"_r(\d+)\.json$", os.path.basename(path))
    doc = {
        "round": int(m.group(1)) if m else 0,
        "platform": jax.devices()[0].platform,
        "half_dtype": np.dtype(jnp.bfloat16).name,
        "lanes": lanes,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"preclint artifact written: {path} ({len(lanes)} lanes)",
          file=sys.stderr)
    return n_errors


def parse_bytes(text: str) -> int:
    """``"16GiB"`` / ``"512MiB"`` / ``"1048576"`` -> bytes."""
    m = re.fullmatch(r"\s*([0-9.]+)\s*([KMG]i?B)?\s*", text)
    if not m:
        raise ValueError(f"unparsable byte size {text!r}")
    mult = {None: 1, "KB": 10**3, "MB": 10**6, "GB": 10**9,
            "KiB": 1 << 10, "MiB": 1 << 20, "GiB": 1 << 30}[m.group(2)]
    return int(float(m.group(1)) * mult)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--families", default=",".join(FAMILIES),
                    help=f"comma list from {FAMILIES}")
    ap.add_argument("--passes", default=",".join(ALL_PASSES),
                    help=f"comma list from {ALL_PASSES}; 'pallas' (= "
                         f"pallas-kernel) additionally runs the Pallas "
                         f"kernel sanitizer over the train lanes "
                         f"(opt-in: it re-traces the step with the "
                         f"pallas kernels routed in)")
    ap.add_argument("--lanes", default=None,
                    help="comma list from o0,o1,o2,o3,o4,decode,serve,"
                         "fleet (train opt levels incl. the fp8 O4 "
                         "regime + the decode lanes [decode_b1_kv8 = "
                         "int8 KV] + the serve-engine step + the "
                         "cross-rank SPMD fleet lanes); default "
                         "o1,decode,serve — except --passes precision, "
                         "whose contract is the full O0–O4 matrix, "
                         "where the default is "
                         "o0,o1,o2,o3,o4,decode,serve")
    ap.add_argument("--no-compile", action="store_true",
                    help="lower only (donation falls back to lowering-"
                         "time aliasing; sharding/collectives/memory/"
                         "cost passes report themselves skipped)")
    ap.add_argument("--memory-budget", nargs="?", default=None,
                    const=str(memory_mod.V5E_HBM_BYTES),
                    metavar="BYTES",
                    help="arm the per-device peak-HBM gate (bare flag "
                         "= v5e 16 GiB; 512MiB / 2GiB forms accepted)")
    ap.add_argument("--emit-json", default=None,
                    metavar="MEMLINT_rN.json|PRECLINT_rN.json|"
                            "FLEETLINT_rN.json|DETLINT_rN.json",
                    help="write a committed lint artifact, dispatched "
                         "on the file name: MEMLINT_r*.json = all "
                         "passes over O1+O2 train + decode + serve + "
                         "multichip slices; "
                         "PRECLINT_r*.json = the precision pass over "
                         "every O0–O4 train lane + decode + serve "
                         "(lowering only); FLEETLINT_r*.json = the "
                         "cross-rank SPMD consistency lanes (per-rank "
                         "DDP O1/O2 schedules + the reshape pair, "
                         "lowering only); DETLINT_r*.json = the "
                         "determinism pass + cross-lane reduction "
                         "comparator over every gated decode/serve "
                         "lane (lowering only, via tools/det_lint.py)")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print every finding, not just errors")
    opts = ap.parse_args(argv)

    families = [f.strip() for f in opts.families.split(",") if f.strip()]
    passes = tuple("pallas-kernel" if p.strip() == "pallas"
                   else p.strip()
                   for p in opts.passes.split(",") if p.strip())
    lanes_explicit = opts.lanes is not None
    if opts.lanes is None:
        # the precision pass's documented contract is the full O0–O3
        # matrix; every other pass combination keeps the historical
        # o1,decode default (+ the serve-engine step)
        if passes == ("precision",):
            opts.lanes = "o0,o1,o2,o3,o4,decode,serve"
        elif passes == ("determinism",):
            # the bitwise-gated programs: every decode + serve lane
            # (train steps emit no tokens; nothing there is gated
            # on bitwise equality)
            opts.lanes = "decode,serve"
        else:
            opts.lanes = "o1,decode,serve"
    lanes = [x.strip().lower() for x in opts.lanes.split(",") if x.strip()]
    unknown = [f for f in families if f not in FAMILIES]
    if unknown:
        ap.error(f"unknown families {unknown}; have {FAMILIES}")
    bad_lanes = [x for x in lanes
                 if x not in TRAIN_LANES + ("decode", "serve", "fleet")]
    if bad_lanes or not lanes:
        ap.error(f"unknown lanes {bad_lanes or opts.lanes!r}; have "
                 f"{', '.join(TRAIN_LANES)}, decode, serve, fleet — a "
                 f"typo'd lane list must not pass the gate by linting "
                 f"nothing")
    try:
        budget = parse_bytes(opts.memory_budget) \
            if opts.memory_budget is not None else None
    except ValueError as e:
        ap.error(str(e))
    if budget is not None and opts.no_compile:
        ap.error("--memory-budget needs the compiled executable's "
                 "memory analysis; it cannot combine with "
                 "--no-compile (an armed budget that asserts nothing "
                 "must not pass the gate)")
    # lowering-only pass sets never read the compiled executable: skip
    # the (expensive) per-lane XLA compilation the same way the
    # PRECLINT artifact path does — but an armed memory budget with no
    # memory pass requested must be refused, not silently unasserted
    lowering_only = set(passes) <= {"precision", "policy",
                                    "constant-capture", "export-compat",
                                    "spmd-consistency", "pallas-kernel",
                                    "determinism"}
    if lowering_only and budget is not None:
        ap.error("--memory-budget needs the memory pass; the requested "
                 f"--passes {','.join(passes)} never reads it (an "
                 "armed budget that asserts nothing must not pass "
                 "the gate)")
    if lowering_only and opts.emit_json is None:
        # (not under --emit-json: the artifact branches own their
        # compile story and their --passes diagnostics)
        opts.no_compile = True

    if opts.emit_json and \
            os.path.basename(opts.emit_json).startswith("DETLINT"):
        # the determinism artifact's contract is the full gated-lane
        # matrix + every comparator pair under the determinism pass
        # alone — a restricted run must be refused, never silently
        # committed as a full document (the armed-gate-asserts-nothing
        # class)
        if passes not in (ALL_PASSES, ("determinism",)):
            ap.error("--emit-json DETLINT_r*.json runs exactly the "
                     "determinism pass over the gated-program lanes; "
                     "drop --passes (or pass --passes determinism)")
        if tuple(families) != FAMILIES:
            ap.error("--families does not apply to the determinism "
                     "lanes (they lower the decode/serve programs, "
                     "not a model family); drop --families")
        if lanes_explicit:
            ap.error("--emit-json DETLINT_r*.json always writes every "
                     "gated lane (decode b1/b8/kv8 + serve step/"
                     "decode/prefill/verify) and every comparator "
                     "pair; drop --lanes")
        if budget is not None:
            ap.error("--memory-budget does not apply to the "
                     "determinism artifact (lowering-only; no "
                     "compiled memory analysis) — an armed budget "
                     "that asserts nothing must not pass the gate")
        import det_lint                       # sibling tool: the sweep
        rc = det_lint.main(["--out", opts.emit_json]
                           + (["-v"] if opts.verbose else []))
        if rc:
            print("graph lint FAILED: determinism sweep recorded "
                  "unwaived findings, an undocumented lane-shape "
                  "variant, or schema problems — see the artifact",
                  file=sys.stderr)
        return rc

    if opts.emit_json and \
            os.path.basename(opts.emit_json).startswith("FLEETLINT"):
        # the fleet artifact's contract is every fleet lane under the
        # spmd-consistency pass alone — a restricted run must be
        # refused, never silently committed as a full document
        if passes not in (ALL_PASSES, ("spmd-consistency",)):
            ap.error("--emit-json FLEETLINT_r*.json runs exactly the "
                     "spmd-consistency pass over the fleet lanes; drop "
                     "--passes (or pass --passes spmd-consistency)")
        if tuple(families) != FAMILIES:
            ap.error("--families does not apply to the fleet lanes "
                     "(they lower the DDP step, not a model family); "
                     "drop --families")
        if lanes_explicit and lanes != ["fleet"]:
            ap.error("--emit-json FLEETLINT_r*.json always writes "
                     "every fleet lane; drop --lanes (or pass "
                     "--lanes fleet)")
        if budget is not None:
            ap.error("--memory-budget does not apply to the fleet "
                     "artifact (lowering-only; no compiled memory "
                     "analysis) — an armed budget that asserts "
                     "nothing must not pass the gate")
        n_errors = emit_fleetlint(opts.emit_json, verbose=opts.verbose)
        if n_errors:
            print(f"graph lint FAILED: {n_errors} SPMD consistency "
                  f"error finding(s) — see the artifact",
                  file=sys.stderr)
            return 1
        return 0

    if opts.emit_json and \
            os.path.basename(opts.emit_json).startswith("PRECLINT"):
        # the precision artifact's contract is the full O0–O3 + decode
        # matrix under the precision pass alone — a restricted run
        # must be refused, never silently committed as a full document
        if passes not in (ALL_PASSES, ("precision",)):
            ap.error("--emit-json PRECLINT_r*.json runs exactly the "
                     "precision pass over every lane; drop --passes "
                     "(or pass --passes precision)")
        if tuple(families) != FAMILIES:
            ap.error("--emit-json PRECLINT_r*.json covers every model "
                     "family; drop --families")
        if lanes_explicit:
            ap.error("--emit-json PRECLINT_r*.json always writes every "
                     "lane (O0–O4 train + decode + serve); drop "
                     "--lanes")
        if budget is not None:
            ap.error("--memory-budget does not apply to the precision "
                     "artifact (lowering-only; no compiled memory "
                     "analysis) — an armed budget that asserts "
                     "nothing must not pass the gate")
        n_errors = emit_preclint(opts.emit_json, families,
                                 verbose=opts.verbose)
        if n_errors:
            print(f"graph lint FAILED: {n_errors} precision error "
                  f"finding(s) — see the artifact", file=sys.stderr)
            return 1
        return 0

    if opts.emit_json:
        # the memlint artifact's contract is the FULL matrix (all
        # passes, every lane, compiled evidence) — silently honoring a
        # restricted --passes or --no-compile would commit a partial
        # document under the full schema
        if opts.no_compile:
            ap.error("--emit-json needs compiled evidence (memory/"
                     "cost tables); it cannot combine with "
                     "--no-compile")
        if passes != ALL_PASSES:
            ap.error("--emit-json always runs the full pass matrix; "
                     "drop --passes (restricted lint is the "
                     "per-lane mode)")
        if tuple(families) != FAMILIES:
            ap.error("--emit-json covers every model family; drop "
                     "--families (a partial lane set would commit a "
                     "schema-valid artifact with most of the HBM "
                     "story silently missing)")
        if lanes_explicit:
            ap.error("--emit-json always writes every lane (O1+O2+O4 "
                     "train, decode, serve, multichip); drop --lanes")
        if budget is None:
            # the artifact's whole point is the asserted per-device
            # budget — a regeneration that forgot --memory-budget
            # must not quietly replace a gated round with an
            # unarmed one
            budget = memory_mod.V5E_HBM_BYTES
        n_errors = emit_memlint(opts.emit_json, families,
                                memory_budget=budget,
                                verbose=opts.verbose)
        if n_errors:
            print(f"graph lint FAILED: {n_errors} error finding(s) — "
                  f"see the artifact", file=sys.stderr)
            return 1
        return 0

    failed = []
    linted = []

    def run(label, fn):
        report = fn()
        if not report.passes:
            # e.g. --passes policy on a decode lane: the requested
            # pass set legitimately doesn't apply — SKIP the lane
            # (no "ok" line for a program nothing looked at); the
            # no-lane-linted-anything check below still fails the run
            # where EVERY lane skips
            print(f"--- {label} --- skipped: no requested pass "
                  f"applies to this lane", file=sys.stderr)
            return
        linted.append(label)
        print(json.dumps({"lane": label, **report.to_dict()}))
        if not report.ok:
            failed.append(label)
            print(f"--- {label} ---\n{report.format()}", file=sys.stderr)
        elif opts.verbose:
            print(f"--- {label} ---\n{report.format()}", file=sys.stderr)

    for family in families:
        for opt_level in ("O0", "O1", "O2", "O3", "O4"):
            if opt_level.lower() not in lanes:
                continue
            run(f"{family}_{opt_level.lower()}",
                lambda f=family, o=opt_level: lint_family(
                    f, passes=passes, compile=not opts.no_compile,
                    opt_level=o, memory_budget=budget))
    if "decode" in lanes:
        for lane in DECODE_LANES:
            run(lane, lambda ln=lane: lint_decode(
                ln, passes=passes, compile=not opts.no_compile,
                memory_budget=budget))
    if "serve" in lanes:
        for lane in SERVE_LANES:
            run(lane, lambda ln=lane: lint_serve(
                ln, passes=passes, compile=not opts.no_compile,
                memory_budget=budget))
        for lane in SERVE_PREFILL_LANES:
            run(lane, lambda ln=lane: lint_serve_prefill(
                ln, passes=passes, compile=not opts.no_compile,
                memory_budget=budget))
        for lane in SERVE_VERIFY_LANES:
            run(lane, lambda ln=lane: lint_serve_verify(
                ln, passes=passes, compile=not opts.no_compile,
                memory_budget=budget))
    if "fleet" in lanes:
        for lane in FLEET_LANES:
            run(lane, lambda ln=lane: lint_fleet(ln, passes=passes))
    if failed:
        print(f"graph lint FAILED for: {failed}", file=sys.stderr)
        return 1
    if not linted:
        print("graph lint FAILED: no requested pass applied to ANY "
              "selected lane (ran zero passes) — linting nothing "
              "must not pass the gate", file=sys.stderr)
        return 1
    print(f"graph lint: all lanes OK "
          f"({', '.join(families)}; lanes: {', '.join(lanes)}; "
          f"passes: {', '.join(passes)})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
