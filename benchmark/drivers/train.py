"""A window of training steps.

Set-up builds one object, the compiled ``amp.make_train_step`` with its
state, drives it from ``--seed`` through its first steps (whose losses,
first gradient and parameter change ``correct`` compares), warms it up
and hands that same object to the window.

The window: the device is drained and the clock starts.  The host draws
every batch from the seed (a different one each step), feeds it through
``apex_tpu.data.prefetch_to_device``, dispatches step *i* without
waiting, then blocks on the loss of step *i-1* and stamps its
completion, so one step is always queued ahead.  The window ends at
the first completion at or after ``--seconds``; the step that was
queued behind it is waited for and counted too.

After the window the peak memory is read, the program's state is freed,
and the plain reference follows the first steps on the same batches.

Two parts of a training recipe come from the cell's ``parameters``
(they define the job), not from the configuration: ``lr_warmup_steps``,
a linear warm-up of the optimizer's rate (:func:`warmup`), and whatever
a family's ``step_state(cfg, traffic)`` reads there (the expert
families: ``balance_rate``).  A family that has such a function and
returns something from it says that its step keeps state which no
gradient moves: ``loss`` (``loss_fn(params, *batch) -> (loss, aux)``),
the ``paths`` of the leaves, their ``update(values, aux) -> values`` on
the float32 masters, and optionally ``describe(aux of some steps) ->
str``.  The driver then builds the step with ``has_aux`` and applies
the update inside the same compiled step, where the optimizer's step
was not skipped (:func:`stateful`); the reference's side is
``reference/train.py`` ``follow``.  A cell with neither key gets the
step it always got.
"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import statistics
import sys
import time

import numpy as np

from benchmark import peaks, trace, weights
from benchmark.reference import train as reference_train

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT, CACHE_MISS = ("/jax/compilation_cache/cache_hits",
                         "/jax/compilation_cache/cache_misses")


def say(*words) -> None:
    print(*words, flush=True)


@dataclasses.dataclass
class Run:
    """What a finished run hands to the per-layer readers."""
    family: object
    cfg: dict
    traffic: dict
    chips: int
    peak: "peaks.ChipPeak | None"
    tokens_per_step: int
    flops_per_token: float
    tokens_per_s: "float | None" = None
    memory_peak_bytes: "int | None" = None
    compile_warm_s: "float | None" = None
    steps_traced: int = 0
    kernel_seconds: dict = dataclasses.field(default_factory=dict)
    instruction_seconds: dict = dataclasses.field(default_factory=dict)
    op_names: dict = dataclasses.field(default_factory=dict)
    busy_by_chip: list = dataclasses.field(default_factory=list)
    busy_s0: "float | None" = None
    exposed_collective_s: "float | None" = None
    aux_traced: list = dataclasses.field(default_factory=list)
    notes: dict = dataclasses.field(default_factory=dict)


class Watch:
    """Counts what JAX's monitoring reports: backend compilations (all,
    and those while ``in_window``), persistent-cache hits and misses."""

    def __init__(self):
        import jax.monitoring as monitoring
        self.in_window = False
        self.compiles = self.compiles_in_window = 0
        self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compiles_in_window += self.in_window

    def _event(self, event, **_):
        self.hits += event == CACHE_HIT
        self.misses += event == CACHE_MISS


def moment(opt_state):
    """The first-moment tree of the program's optimizer state."""
    found = []

    def visit(node):
        if hasattr(node, "m") and hasattr(node, "v"):
            found.append(node.m)
        elif isinstance(node, (tuple, list)):
            for child in node:
                visit(child)

    visit(opt_state)
    if len(found) != 1:
        raise ValueError(f"expected one optimizer state with moments "
                         f"m and v, found {len(found)}")
    return found[0]


def warmup(lr: float, steps: int):
    """``learning_rate(step)`` for the program's optimizer (``step`` its
    own 1-based counter): ``lr * min(1, step / steps)`` in float32, the
    rate ``reference/optim.py`` ``warmup`` gives its ``t``.  The
    program's counter stands still on a step that a gradient overflow
    skips (``Amp.step_if`` leaves the optimizer's state as it was), so
    the rate is a function of the steps *applied*; the reference has no
    loss scale, skips nothing and counts every step.  The two counters
    agree while no step is skipped, and a step skipped among the first,
    which ``correct`` compares, is a step the reference took and the
    program did not: it reads 1 in ``grad_gap`` (the first) or a third
    and more in ``delta_gap``, as before there was a schedule.  The
    family's update of its own state is skipped with the optimizer's
    (:func:`stateful`), so all the step's state stands still together."""
    import jax.numpy as jnp

    def rate(step):
        return jnp.float32(lr) * jnp.minimum(
            jnp.float32(1.0),
            step.astype(jnp.float32) / jnp.float32(steps))

    return rate


def stateful(inner, kept: dict):
    """``inner`` (a step built with ``has_aux``) followed, in the same
    compiled step, by the family's update of the leaves no gradient
    moves: on the float32 masters (the bfloat16 view is cast from them
    at every step's start), from this step's ``aux``, and not where the
    optimizer's step was skipped."""
    import jax.numpy as jnp

    def step(state, *batch):
        state, m = inner(state, *batch)
        flat = weights.flatten(state.master_params)
        moved = kept["update"]({p: flat[p] for p in kept["paths"]},
                               m["aux"])
        flat.update({p: jnp.where(m["overflow"], flat[p], v)
                     for p, v in moved.items()})
        return state._replace(master_params=weights.nest(flat)), m

    return step


def make_step(cell: dict, cfg: dict, family, devices, step_wrapper=None):
    """The program's train step for this cell, not yet compiled: the
    ``Amp`` object, the step function, the parameter spec, (for a
    data-parallel cell) the mesh with its two shardings, and what the
    family's ``step_state`` said (``hook``)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu import amp, optimizers

    traffic = cell["parameters"]
    chips = cell["chips"]
    spec = family.reference.param_spec(cfg)
    opt = cfg["optimizer"]
    args = dict(opt["args"], betas=tuple(opt["args"]["betas"]))
    if traffic.get("lr_warmup_steps"):
        args["lr"] = warmup(args["lr"], traffic["lr_warmup_steps"])
    a = amp.initialize(optimizer=getattr(optimizers, opt["program"])(**args),
                       opt_level=cfg["opt_level"], verbosity=0)
    kept = family.step_state(cfg, traffic) \
        if hasattr(family, "step_state") else None
    if kept and traffic.get("data_parallel"):
        raise ValueError("a step with state of its own is not built for a "
                         "data-parallel cell yet: what its rule reads would "
                         "have to be summed over the chips")
    loss_fn = family.program_loss(cfg, traffic)

    replicated = by_rows = None
    if traffic.get("data_parallel"):
        from jax import shard_map
        from apex_tpu.parallel import DistributedDataParallel
        mesh = Mesh(np.array(devices[:chips]), ("data",))
        replicated = NamedSharding(mesh, P())
        by_rows = NamedSharding(mesh, P("data"))
        ddp = DistributedDataParallel(axis_name="data")
        inner = amp.make_train_step(a, loss_fn, axis_name="data",
                                    reduce_fn=ddp.reduce)

        def sharded(state, *batch):
            state, m = inner(state, *batch)
            return state, dict(m, loss=jax.lax.pmean(m["loss"], "data"))

        n_args = len(family.make_batch(np.random.default_rng(0), chips, cfg,
                                       traffic))
        step_fn = shard_map(sharded, mesh=mesh,
                            in_specs=(P(),) + (P("data"),) * n_args,
                            out_specs=(P(), P()))
    elif chips != 1:
        raise ValueError("a cell on several chips has to say how it uses "
                         "them (parameters.data_parallel)")
    elif kept:
        step_fn = stateful(amp.make_train_step(a, kept["loss"],
                                               has_aux=True), kept)
    else:
        step_fn = amp.make_train_step(a, loss_fn)
    if step_wrapper is not None:
        step_fn = step_wrapper(step_fn)
    return dict(a=a, step_fn=step_fn, spec=spec, replicated=replicated,
                by_rows=by_rows, beta1=args["betas"][0], hook=kept)


def reference_kwargs(cfg: dict, traffic: dict, devices) -> dict:
    """How ``reference.train.follow`` is to follow this cell."""
    args = cfg["optimizer"]["args"]
    opt_kwargs = dict(args, betas=tuple(args["betas"]))
    if traffic.get("lr_warmup_steps"):
        opt_kwargs["lr_warmup_steps"] = traffic["lr_warmup_steps"]
    return dict(optimizer=cfg["optimizer"]["reference"],
                opt_kwargs=opt_kwargs,
                block_rows=traffic["reference_block_rows"], devices=devices,
                traffic=traffic)


def build(cell: dict, cfg: dict, family, seed: int, devices, watch: Watch,
          step_wrapper=None):
    """The compiled step, its state made on the device from the seed, and
    the feed.  ``step_wrapper`` lets a test plant a fault under the timed
    path; nothing else passes it."""
    import jax

    from apex_tpu.data import prefetch_to_device

    traffic = cell["parameters"]
    rows = traffic["rows_per_chip"] * cell["chips"]
    made = make_step(cell, cfg, family, devices, step_wrapper)
    a, step_fn, spec = made["a"], made["step_fn"], made["spec"]
    replicated, by_rows = made["replicated"], made["by_rows"]
    key = weights.seed_key(seed)

    t = time.perf_counter()
    state = jax.jit(lambda k: a.init(weights.make(spec, k)),
                    out_shardings=replicated)(key)
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t

    rng = np.random.default_rng(seed)
    kept: list = []          # the first batches, for the reference

    def host_batches():
        while True:
            batch = family.make_batch(rng, rows, cfg, traffic)
            if len(kept) < traffic["reference_steps"]:
                kept.append(batch)
            yield batch

    feed = prefetch_to_device(host_batches(), lookahead=2, sharding=by_rows)
    first = next(feed)

    step = jax.jit(step_fn, donate_argnums=(0,))
    hits, misses = watch.hits, watch.misses
    t = time.perf_counter()
    compiled = step.lower(state, *first).compile()
    compile_s = time.perf_counter() - t
    hit = watch.hits > hits and watch.misses == misses
    warm_s = compile_s
    if not hit and jax.default_backend() != "cpu":
        # this run compiled: read the warm time too, by getting the same
        # program once more from the persistent cache (the caches in
        # memory are dropped first, or they would answer in no time)
        jax.clear_caches()
        t = time.perf_counter()
        step.lower(state, *first).compile()
        warm_s = time.perf_counter() - t
    return dict(a=a, spec=spec, key=key, state=state, compiled=compiled,
                feed=feed, first=first, kept=kept,
                init_s=init_s, compile_s=compile_s, warm_s=warm_s,
                cache_hit=hit, beta1=made["beta1"], hook=made["hook"])


def first_steps(b: dict, n: int):
    """Drive the compiled step through its first ``n`` steps and take the
    program's readings: every loss, the first gradient's norms from the
    optimizer's first moment after one step, and the norms of the
    parameters' change after the ``n``."""
    import jax
    state, feed, batch = b["state"], b["feed"], b["first"]
    metrics, grad_norms = [], None
    for i in range(n):
        state, m = b["compiled"](state, *batch)
        metrics.append(m)
        if i == 0:
            grad_norms = jax.jit(reference_train.first_gradient_norms,
                                 static_argnums=1)(
                moment(state.opt_state), b["beta1"])
        batch = next(feed)
    deltas = jax.jit(lambda p, k: reference_train.delta_norms(
        p, b["spec"], k))(state.master_params, b["key"])
    metrics = jax.device_get(metrics)
    b["state"], b["first"] = state, batch
    return reference_train.on_host([m["loss"] for m in metrics], grad_norms,
                                   deltas), metrics


def steps(b: dict, *, seconds: "float | None" = None,
          count: "int | None" = None, annotate: bool = False):
    """Run steps with one queued ahead until ``seconds`` have passed or
    ``count`` are done.  Returns completion stamps (from the start),
    per-step metrics (still on the device) and input waits."""
    import jax
    span = jax.profiler.TraceAnnotation if annotate \
        else (lambda name: contextlib.nullcontext())
    state, feed, batch, compiled = b["state"], b["feed"], b["first"], \
        b["compiled"]
    jax.block_until_ready(state)
    stamps, metrics, waits = [], [], []
    t0 = time.perf_counter()
    while True:
        with span("bench/dispatch"):
            state, m = compiled(state, *batch)
        metrics.append(m)
        if len(metrics) > 1:
            with span("bench/wait_previous_step"):
                jax.block_until_ready(metrics[-2]["loss"])
            stamps.append(time.perf_counter() - t0)
        done = (stamps and seconds is not None and stamps[-1] >= seconds) \
            or (count is not None and len(metrics) >= count)
        t = time.perf_counter()
        with span("bench/input"):
            batch = next(feed)
        waits.append(time.perf_counter() - t)
        if done:
            break
    with span("bench/wait_last_step"):
        jax.block_until_ready(metrics[-1]["loss"])
    stamps.append(time.perf_counter() - t0)
    b["state"], b["first"] = state, batch
    return stamps, metrics, waits


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by linear interpolation between ranks."""
    ordered = sorted(values)
    k = (len(ordered) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (k - lo)


def reduce_trace(run: Run, trace_dir: str, hlo_text: str, keep: bool):
    """Fill ``run`` from the trace; returns ``(busy_s, window_s,
    breakdown)``."""
    planes = trace.read(trace.find_xplane(trace_dir))
    if not keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    chips = trace.device_planes(planes)[:run.chips]
    if not chips:
        raise RuntimeError("the trace holds no device plane")
    for plane in chips:
        lo, hi = trace.window(planes, plane)
        run.busy_by_chip.append(
            (trace.length(trace.busy(planes, plane)) * 1e-9,
             (hi - lo) * 1e-9))
    first = chips[0]
    run.steps_traced = trace.steps_traced(planes, first)
    run.instruction_seconds = trace.instruction_seconds(planes, first)
    run.kernel_seconds = trace.kernel_seconds(run.instruction_seconds)
    run.op_names = trace.op_names(hlo_text)
    run.busy_s0 = run.busy_by_chip[0][0]
    run.exposed_collective_s = trace.exposed_collective_seconds(planes, first)
    busy_s = statistics.fmean(b for b, _ in run.busy_by_chip)
    window_s = statistics.fmean(w for _, w in run.busy_by_chip)
    breakdown = {"device_ops": trace.top_ops(run.kernel_seconds),
                 "idle_gaps": trace.idle_gaps(planes, first)[:10]}
    return busy_s, window_s, breakdown


def run(cell: dict, cfg: dict, family, args, t_start: float, root: str,
        step_wrapper=None) -> dict:
    """One run of one cell; returns the parts of the result line."""
    import jax

    from apex_tpu.utils import compile_cache

    rehearse = args.rehearse
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        sys.exit(f"no accelerator: JAX reports platform {platform!r}")
    if len(devices) < cell["chips"]:
        sys.exit(f"the cell needs {cell['chips']} chips, JAX reports "
                 f"{len(devices)}")
    devices = devices[:cell["chips"]]
    cache_dir = compile_cache.enable()
    watch = Watch()
    traffic = cell["parameters"]
    tokens_per_step = traffic["rows_per_chip"] * cell["chips"] \
        * family.tokens_per_row(traffic)
    run_ = Run(family=family, cfg=cfg, traffic=traffic, chips=cell["chips"],
               peak=None if rehearse else peaks.peak(devices[0].device_kind),
               tokens_per_step=tokens_per_step,
               flops_per_token=family.flops_per_token(cfg, traffic))

    b = build(cell, cfg, family, args.seed, devices, watch, step_wrapper)
    hlo_text = b["compiled"].as_text()
    kernels = trace.mosaic_kernels(hlo_text)
    run_.compile_warm_s = b["warm_s"]
    say(f"compile cache: {cache_dir}; the step "
        f"{'came from it' if b['cache_hit'] else 'was compiled'} in "
        f"{b['compile_s']:.2f} s (from the cache: {b['warm_s']:.2f} s); "
        f"weights and state made in {b['init_s']:.2f} s")
    say(f"Mosaic kernels in the step: {kernels}")

    got, early = first_steps(b, traffic["reference_steps"])
    _, warm, _ = steps(b, count=traffic["warmup_steps"])
    warm = jax.device_get(warm)
    say("first losses: " + " ".join(f"{x:.4f}" for x in got["losses"])
        + f"; after warm-up {float(warm[-1]['loss']):.4f}; overflow-skipped "
        f"steps before the window: "
        f"{sum(bool(m['overflow']) for m in list(early) + warm)}")

    trace_dir = f"{root}/.bench_trace/{cell['name']}"
    traced = None
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        try:
            _, traced, _ = steps(b, count=traffic["traced_steps"],
                                 annotate=True)
        finally:
            jax.profiler.stop_trace()

    jax.block_until_ready(b["state"])
    setup_s = time.perf_counter() - t_start
    watch.in_window = True
    stamps, metrics, waits = steps(b, seconds=args.seconds)
    watch.in_window = False
    metrics = jax.device_get(metrics + (traced or []))

    n = len(stamps)
    if b["hook"]:
        # the counters of the step's own state: the traced steps' for the
        # per-layer readers, the window's on a line of their own
        run_.aux_traced = [m["aux"] for m in metrics[n:]]
        if "describe" in b["hook"]:
            say("step state: " + b["hook"]["describe"](
                [m["aux"] for m in metrics[:n]]))
    intervals = [1e3 * (y - x) for x, y in zip(stamps, stamps[1:])]
    run_.tokens_per_s = n * tokens_per_step / stamps[-1]
    losses = [float(m["loss"]) for m in metrics]
    failed = sum(bool(m["overflow"]) or not np.isfinite(float(m["loss"]))
                 for m in metrics)
    run_.memory_peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices) or None
    say(f"window: {n} steps in {stamps[-1]:.3f} s, {len(intervals)} "
        f"intervals behind step_ms_p95 (median "
        f"{statistics.median(intervals):.3f} ms, max {max(intervals):.3f} "
        f"ms); compilations inside the window: {watch.compiles_in_window}")
    say(f"input: the step waited {1e3 * sum(waits):.2f} ms for batches in "
        f"all, {1e3 * max(waits):.2f} ms at most")
    say(f"loss {losses[0]:.4f} at the window's first step, "
        f"{losses[n - 1]:.4f} at its last; final loss scale "
        f"{float(metrics[n - 1]['loss_scale']):.0f}; steps skipped for "
        f"overflow or not finite: {failed}")

    busy = breakdown = None
    if args.trace and rehearse:        # a CPU trace has no device plane
        shutil.rmtree(trace_dir, ignore_errors=True)
    elif args.trace:
        busy_s, window_s, breakdown = reduce_trace(
            run_, trace_dir, hlo_text, args.keep_trace)
        busy = {"busy_s": busy_s, "window_s": window_s}

    # the program's state goes before the reference comes
    b.pop("state"), b.pop("compiled"), b.pop("first"), b.pop("feed")
    del metrics, traced
    t = time.perf_counter()
    want = reference_train.follow(
        family.reference, cfg, b["spec"], args.seed, b["kept"],
        **reference_kwargs(cfg, traffic, devices))
    say(f"reference: {traffic['reference_steps']} plain float32 steps in "
        f"{time.perf_counter() - t:.2f} s (outside set-up and the window)")
    compared = reference_train.gaps(got, want)
    say(f"compared: worst leaves {compared.pop('worst_leaves')}, "
        f"{compared.pop('leaves_left_out')} leaves left out of delta_gap; "
        f"reference losses " + " ".join(f"{x:.4f}" for x in want["losses"]))

    end_to_end = {"tokens_per_s": run_.tokens_per_s,
                  "step_ms_p95": percentile(intervals, 95.0),
                  "setup_s": setup_s}
    return {"run": run_, "end_to_end": end_to_end, "attempted": n,
            "failed": int(failed), "compared": compared,
            "compilations_in_window": watch.compiles_in_window,
            "busy": busy, "breakdown": breakdown, "devices": devices}
