"""Disaggregated prefill/decode serving: separate mesh slices behind
one KV-shipping router.

The monolithic engine (:mod:`apex_tpu.serve.engine`) interleaves
prefill chunks and decode steps on ONE set of devices, so a bursty
long-prompt admission stalls every in-flight decode behind it.
Production fleets (the DistServe/Splitwise result) split the two
phases onto different replicas at equal chip count: prefill is
compute-bound and bursty, decode is HBM-bound and steady, and
separating them removes the interference that dominates decode p99.
This module is that topology, built from parts that already exist:

- the **prefill worker** (:class:`PrefillWorker`) is a
  :class:`~apex_tpu.serve.engine.ServeEngine` on its own mesh slice
  used only for its chunked paged prefill + first-token sample; the
  finished slot's KV blocks are gathered into a fixed-shape
  :class:`~apex_tpu.serve.transfer.KVShipment` and the slot is freed
  immediately — the worker's pool only ever holds in-flight prompts;

- each **decode replica** (:class:`DecodeReplica`) is the existing
  one-compiled-step engine on its own slice; a shipment installs
  through one donated scatter (page-table row and slot index TRACED —
  one executable per replica across every admit/transfer/retire), and
  the replica decodes exactly as the monolithic engine would;

- the **router** (:class:`DisaggRouter`) does admission control off
  the obs gauges the engines already export — per-replica queue
  depth, slot occupancy, block utilization, decode-p99 — ships
  finished prefill KV to the least-loaded eligible replica
  (``transfer="ship"``), or hands the original request to the replica
  to re-prefill locally (``transfer="recompute"`` — the
  recompute-on-miss fallback riding the same admission path the
  preempt-and-recompute machinery uses), and recovers from a replica
  death (:meth:`DisaggRouter.kill_replica`) by rebuilding
  continuation requests from its streamed-token log and re-prefilling
  them elsewhere: greedy outputs stay BITWISE equal to solo
  ``generate()`` through the kill, and sampled requests resume their
  exact PRNG chain via :func:`apex_tpu.serve.sampling.advance_key`.

Every replica cold-starts through ``ServeConfig.aot_cache``
(:mod:`apex_tpu.analysis.export`): a placed engine keys its cache
entry per-slice (the device ids join the mesh descriptor — a PJRT
executable is pinned to its devices, so a cross-slice load would be
wrong-device, not fast), so a restarted replica loads its slice's
lint-gated executable instead of compiling.

Everything here is host-side control: the compiled programs are the
engines' own (the graph-lint ``serve_prefill``/``serve_decode`` lanes
lint them), and every router metric is a host number recorded at a
step boundary — the syncs pass stays clean on every replica's step.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.obs import metrics as obs_metrics
from apex_tpu.serve import transfer
from apex_tpu.serve.engine import ServeConfig, ServeEngine
from apex_tpu.serve.paged import PoolExhausted
from apex_tpu.serve.sampling import advance_key
from apex_tpu.serve.scheduler import Request, validate_request
from apex_tpu.serve.transfer import (
    FleetSlices,
    KVShipment,
    place_tree,
    placement,
    slice_fleet,
)


@dataclasses.dataclass(frozen=True)
class RouterConfig:
    """Fleet shape + policy knobs.  ``transfer`` picks the KV path:
    ``"ship"`` moves prefilled blocks device-to-device, ``"recompute"``
    re-prefills on the decode replica (the miss fallback, runnable as
    the whole policy for parity tests and transfer-starved topologies).
    ``admit_block_util`` is the admission-control headroom bar: a
    replica whose block-utilization gauge is at/over it takes no new
    admissions even with a free slot (whole-footprint allocation
    already guarantees no mid-decode death; the bar keeps headroom so
    a burst lands on the emptiest pool)."""

    n_decode_replicas: int = 2
    n_prefill_devices: int = 1
    devices_per_replica: int = 1
    transfer: str = "ship"
    admit_block_util: float = 0.97
    #: where :meth:`DisaggRouter.kill_replica` writes its incident
    #: record (schema :mod:`apex_tpu.resilience.incidents`, with the
    #: router's flight-recorder tail under the validated ``flight``
    #: field).  ``None`` = no artifact; the flight ring records either
    #: way.
    incident_path: Optional[str] = None
    #: declarative SLO objectives (a tuple of
    #: :class:`apex_tpu.obs.slo.SLObjective`) evaluated per replica
    #: over its OWN registry at every fleet step boundary — an
    #: SLO-violating replica loses admission ELIGIBILITY (the
    #: gauge-ranking hook, objective-driven) until its windowed burn
    #: rate recovers; insufficient windows never de-rank a fresh
    #: replica.  ``None`` = ranking only, no objectives.
    slo: Optional[tuple] = None
    #: continuous profiling (an :class:`apex_tpu.obs.contprof.
    #: ContProfConfig`): every decode replica gets its own profiler —
    #: capture phases STAGGERED across replicas (the tracer is
    #: process-global; a colliding window is skipped, not queued) —
    #: and its own :class:`~apex_tpu.obs.contprof.DriftSentinel`
    #: over the replica's registry.  A confirmed drift flips the
    #: replica's ``serve_profile_drift`` gauge (SLO-consumable via
    #: :func:`apex_tpu.obs.contprof.drift_objective`), notes the
    #: router's flight recorder, writes a ``profile-drift`` incident
    #: to ``incident_path``, and DE-RANKS the replica in admission
    #: (preferred last, never hard-blocked: a fleet whose every
    #: replica drifted must still serve).  ``None`` = no profiling.
    contprof: Optional[Any] = None
    #: sentinel band width / confirmation count for ``contprof``
    #: (the PR-13 band rule's fallback default; a caller with a
    #: recorded variance-derived width passes it here)
    contprof_band: float = 0.03
    contprof_k: int = 2

    def __post_init__(self):
        if self.transfer not in ("ship", "recompute"):
            raise ValueError(
                f"transfer={self.transfer!r}; pick 'ship' (KV block "
                f"shipment) or 'recompute' (re-prefill on the decode "
                f"replica)")
        if not 0.0 < self.admit_block_util <= 1.0:
            raise ValueError(
                f"admit_block_util={self.admit_block_util} outside "
                f"(0, 1]")


class PrefillWorker:
    """The prefill slice: a :class:`ServeEngine` whose decode step is
    never dispatched.  ``prefill()`` runs the existing chunked paged
    prefill + first-token sample for ONE request, gathers the slot's
    KV through its page table into the fixed shipment shape, frees
    the slot, and returns the shipment — or the finished output when
    the request ends at its first token (budget 1 / immediate EOS),
    which never needs a decode slice at all."""

    def __init__(self, params, cfg, serve_cfg: ServeConfig,
                 mesh, registry: Optional[obs_metrics.Registry] = None,
                 tracer: Optional[Any] = None):
        # the worker's pool only holds ONE in-flight prompt: one slot,
        # one slot's worth of blocks (+ trash).  Shapes that must agree
        # with the decode replicas (block_size, max_blocks_per_slot,
        # kv_dtype) are taken from the SAME ServeConfig the replicas
        # use, so a shipment always fits its destination.  aot_cache is
        # CLEARED: the engine's probe resolves the DECODE step, which
        # the worker never dispatches — probing here would eagerly
        # compile+export an executable nobody loads, making fleet cold
        # start slower, not faster.
        # prefix_cache is OFF on the worker: its pool holds exactly
        # one transient slot (freed after every gather), and the
        # router already short-circuits prefix-hit requests STRAIGHT
        # to a decode replica before they ever reach this worker —
        # sharing belongs to the replicas, whose installs register
        # shipped blocks in the DESTINATION index via arm().
        self.scfg = dataclasses.replace(
            serve_cfg, num_slots=1,
            num_blocks=serve_cfg.max_blocks_per_slot + 1,
            aot_cache=False, prefix_cache=False)
        self.mesh = mesh
        self.placement = placement(mesh)
        self.eng = ServeEngine(params, cfg, self.scfg,
                               registry=registry or obs_metrics.Registry(),
                               placement=self.placement,
                               tracer=tracer, trace_name="prefill")
        self.trace_counts = {"gather": 0}
        names = [n for n in ("kc", "vc", "ks", "vs")
                 if n in self.eng.carry]
        self._pool_names = names
        self._gather = transfer.make_gather(
            names, trace_counts=self.trace_counts)

    def prefill(self, req: Request):
        """``("done", tokens)`` when the request finished at its first
        sample, else ``("kv", KVShipment)`` with the slot already
        freed (the worker holds nothing between calls)."""
        eng, sched = self.eng, self.eng.sched
        # only the PROMPT's blocks: the worker never decodes, so the
        # generation budget's footprint belongs to the decode slice
        need = -(-len(req.prompt) // sched.block_size)
        blocks = sched.allocator.alloc(need, req)
        sched._install(0, req, blocks)
        eng._run_prefill(0, req)
        if sched.slots[0] is None:
            # finished at the prefill sample (_run_prefill retired it)
            out = eng._outputs.pop(req.uid)
            eng.metrics.tick()
            return ("done", out)
        slot = sched.slots[0]
        first = int(slot.emitted[0])
        plen = int(sched.lengths[0])
        kv = self._gather(eng.carry, jnp.asarray(sched.page_table[0]))
        key = eng.carry["keys"][0]
        shp = KVShipment(request=req, kv=kv, first_token=first,
                         prompt_len=plen, key=key,
                         nbytes=transfer.shipment_bytes(kv, key))
        # free, don't retire: the request's life continues elsewhere
        sched.allocator.free(blocks, req)
        sched._clear(0)
        sched._update_gauges()
        eng.metrics.tick()
        return ("kv", shp)


class DecodeReplica:
    """One decode slice: the existing engine plus the one donated
    install scatter that accepts shipments.  ``alive`` is the router's
    view — a killed replica takes no work and steps no more."""

    def __init__(self, index: int, params, cfg, serve_cfg: ServeConfig,
                 mesh, registry: Optional[obs_metrics.Registry] = None,
                 tracer: Optional[Any] = None):
        self.index = index
        self.mesh = mesh
        self.placement = placement(mesh)
        self.eng = ServeEngine(params, cfg, serve_cfg,
                               registry=registry or obs_metrics.Registry(),
                               placement=self.placement,
                               tracer=tracer,
                               trace_name=f"replica{index}")
        self.alive = True
        self.trace_counts = {"install": 0}
        names = [n for n in ("kc", "vc", "ks", "vs")
                 if n in self.eng.carry]
        self._install = transfer.make_install(
            names, trace_counts=self.trace_counts)
        self._hist = self.eng.metrics.histogram(
            "serve_decode_step_seconds")
        #: histogram window mark taken after the replica's FIRST
        #: decode step (the compile): the p99 the router ranks and
        #: exports is steady-state — a compile outlier must not steer
        #: admissions away from a fresh replica for its first 100
        #: steps
        self._p99_window = None

    # -- admission ----------------------------------------------------

    def can_admit(self, req: Request) -> bool:
        """A free slot and the whole footprint coverable, without
        side effects (the router checks BEFORE paying the wire).
        Reclaimable = free + refcount-0 cached prefix blocks — the
        allocator reclaims its LRU cache transparently inside
        ``alloc``, so counting only the free list would wedge a
        replica whose whole pool had parked in the prefix cache."""
        sched = self.eng.sched
        return bool(self.alive and sched.free_slots()
                    and sched.blocks_needed(req)
                    <= sched.allocator.reclaimable_count)

    def admit_shipment(self, shp: KVShipment) -> Optional[int]:
        """Install a prefilled request: allocate its FULL footprint,
        scatter the shipped blocks into this replica's pools through
        the assigned page-table row, drop the PRNG key at the slot,
        and arm the slot for decode — one donated executable across
        every installation (the slot index and row are traced).
        Returns the slot index, or ``None`` when the replica could
        not take the shipment (dead / no slot / capacity race)."""
        eng, sched = self.eng, self.eng.sched
        free = sched.free_slots()
        if not self.alive or not free:
            return None
        req = shp.request
        try:
            blocks = sched.allocator.alloc(sched.blocks_needed(req), req)
        except PoolExhausted:
            return None
        slot = free[0]
        sched._install(slot, req, blocks)
        eng.carry = self._install(
            eng.carry, jnp.asarray(sched.page_table[slot]), shp.kv,
            jnp.int32(slot), shp.key)
        # the install scatter is an admission dispatch like a prefill
        # chunk: bump the engine's contamination marker so a shipment
        # landing inside a replica's capture window discards that
        # window (its scatter ops would misattribute into the decode
        # buckets)
        eng._admission_dispatches += 1
        sched.arm(slot, shp.first_token, shp.prompt_len)
        return slot

    def submit(self, req: Request) -> None:
        """The recompute path: the replica re-prefills locally through
        its own admission machinery (exactly what a transfer miss
        falls back to)."""
        self.eng.submit(req)

    # -- stepping / introspection -------------------------------------

    def step(self) -> Dict[str, np.ndarray]:
        if not self.alive:
            return {}
        out = self.eng.step()
        if self._p99_window is None and self._hist.count > 0:
            self._p99_window = self._hist.state()
        return out

    def idle(self) -> bool:
        return (not self.alive) or self.eng.sched.idle()

    def p99(self) -> float:
        """Steady-state decode-step p99 (first step — the compile —
        windowed out); ``nan`` before any post-window observation."""
        if self._p99_window is None:
            return math.nan
        return self._hist.quantile(0.99, since=self._p99_window)

    def load(self) -> tuple:
        """The admission-control score, read from the obs gauges the
        engine already exports (lower = preferred): outstanding work
        (queue + active slots), then block utilization, then the
        steady-state decode-step p99 this replica has been
        delivering."""
        reg = self.eng.metrics
        q = reg.gauge("serve_queue_depth").value
        occ = reg.gauge("serve_slot_occupancy").value
        util = reg.gauge("serve_block_utilization").value
        p99 = self.p99()
        return (q + occ * self.eng.scfg.num_slots, util,
                0.0 if math.isnan(p99) else p99)


class DisaggRouter:
    """The fleet front door.  ``submit()`` then ``step()``/``run()``
    exactly like a single engine; behind it requests prefill on the
    prefill slice, their KV ships to a decode slice, and the decode
    replicas run the one-compiled-step engine unchanged.

    >>> router = DisaggRouter(params, cfg, ServeConfig(num_slots=4, ...))
    >>> router.submit(Request("a", prompt, max_new_tokens=32))
    >>> outputs = router.run()       # {"a": generated ids}

    ``serve_cfg`` describes ONE decode replica (every replica is
    identical; the prefill worker derives its single-slot config from
    it).  Failure semantics: :meth:`kill_replica` loses a replica's
    device state mid-stream; the router rebuilds each in-flight
    request as a continuation from its streamed-token log (prompt +
    emitted tokens, remaining budget, the PRNG chain re-derived by
    draw count) and re-prefills it elsewhere — the recompute-on-miss
    machinery, pointed at a death instead of a cache miss."""

    def __init__(self, params, cfg, serve_cfg: ServeConfig,
                 router_cfg: Optional[RouterConfig] = None,
                 devices: Optional[Sequence] = None,
                 registry: Optional[obs_metrics.Registry] = None,
                 slices: Optional[FleetSlices] = None,
                 tracer: Optional[Any] = None,
                 flight: Optional[Any] = None):
        self.rcfg = router_cfg or RouterConfig()
        self.scfg = serve_cfg
        #: per-request lifecycle tracer (apex_tpu.obs.reqtrace): the
        #: router MINTS the request id at admission and hands the one
        #: tracer to the prefill worker ("prefill") and every replica
        #: ("replica{i}"); None = tracing off
        self.tracer = tracer
        #: incident flight recorder (apex_tpu.obs.flight): the ring
        #: kill_replica dumps into its incident record; None = off
        self.flight = flight
        self.slices = slices if slices is not None else slice_fleet(
            devices,
            n_prefill_devices=self.rcfg.n_prefill_devices,
            n_decode_replicas=self.rcfg.n_decode_replicas,
            devices_per_replica=self.rcfg.devices_per_replica)
        if len(self.slices.decode) != self.rcfg.n_decode_replicas:
            raise ValueError(
                f"slices carry {len(self.slices.decode)} decode "
                f"replicas, RouterConfig says "
                f"{self.rcfg.n_decode_replicas}")
        self.metrics = registry if registry is not None \
            else obs_metrics.DEFAULT
        self.prefill = PrefillWorker(params, cfg, serve_cfg,
                                     self.slices.prefill,
                                     tracer=tracer)
        self.replicas: List[DecodeReplica] = [
            DecodeReplica(i, params, cfg, serve_cfg, mesh,
                          tracer=tracer)
            for i, mesh in enumerate(self.slices.decode)]
        self.queue: List[Request] = []
        self._outputs: Dict[str, np.ndarray] = {}
        # -- router telemetry (apex_tpu.obs): host numbers recorded at
        # step boundaries — never on any replica's compiled step path
        self._m_queue = self.metrics.gauge(
            "serve_router_queue_depth",
            "requests held by the router (admission control: no "
            "eligible replica under the block-utilization bar)")
        self._m_ship = self.metrics.counter(
            "serve_kv_shipments_total",
            "prefilled requests shipped to a decode replica")
        self._m_bytes = self.metrics.counter(
            "serve_kv_transfer_bytes",
            "device-to-device bytes of shipped prefill KV (pools + "
            "PRNG key; zero under transfer='recompute')")
        self._m_reroute = self.metrics.counter(
            "serve_reroute_total",
            "requests rebuilt from the streamed-token log and "
            "re-prefilled elsewhere after a replica death")
        self._m_rep_q = [
            self.metrics.gauge(
                f"serve_replica{i}_queue_depth",
                f"replica {i} engine-local queue (recompute "
                f"admissions + preemption continuations)")
            for i in range(len(self.replicas))]
        self._m_rep_occ = [
            self.metrics.gauge(
                f"serve_replica{i}_slot_occupancy",
                f"replica {i} active slots / num_slots")
            for i in range(len(self.replicas))]
        self._m_rep_util = [
            self.metrics.gauge(
                f"serve_replica{i}_block_utilization",
                f"replica {i} live KV blocks / usable pool")
            for i in range(len(self.replicas))]
        self._m_rep_p99 = [
            self.metrics.gauge(
                f"serve_replica{i}_decode_p99_seconds",
                f"replica {i} decode-step p99 (from its own "
                f"serve_decode_step_seconds histogram)")
            for i in range(len(self.replicas))]
        # -- prefix sharing (per-replica indexes): mirrors of each
        # replica's own prefix gauges at the same lag-resolved
        # boundary as every fleet gauge above, plus the router's
        # straight-to-decode counter — all host bookkeeping, zero new
        # syncs on any compiled step
        self._m_prefix_direct = None
        self._m_rep_hit: List = []
        self._m_rep_shared: List = []
        if serve_cfg.prefix_cache:
            self._m_prefix_direct = self.metrics.counter(
                "serve_prefix_direct_admissions_total",
                "prefix-hit requests admitted STRAIGHT to a decode "
                "replica — no prefill-slice time, no KV shipment for "
                "the shared span")
            self._m_rep_hit = [
                self.metrics.gauge(
                    f"serve_replica{i}_prefix_hit_rate",
                    f"replica {i} prefix-cache hit rate (mirror of "
                    f"its serve_prefix_hit_rate gauge)")
                for i in range(len(self.replicas))]
            self._m_rep_shared = [
                self.metrics.gauge(
                    f"serve_replica{i}_prefix_shared_blocks",
                    f"replica {i} blocks mapped by more than one slot "
                    f"(mirror of its serve_prefix_shared_blocks "
                    f"gauge)")
                for i in range(len(self.replicas))]
        # -- SLO admission (apex_tpu.obs.slo): one evaluator per
        # replica over its OWN registry, judged at the same boundary
        # _record_metrics already owns — resolved host state only,
        # zero new host syncs on any replica's compiled step
        self.slo_evals = None
        self._m_rep_slo = []
        if self.rcfg.slo:
            from apex_tpu.obs.slo import SLOEvaluator
            self.slo_evals = [SLOEvaluator(rep.eng.metrics,
                                           self.rcfg.slo)
                              for rep in self.replicas]
            self._m_rep_slo = [
                self.metrics.gauge(
                    f"serve_replica{i}_slo_ok",
                    f"replica {i} SLO eligibility (1 = no objective "
                    f"violated in its window; 0 = de-ranked from "
                    f"admission)")
                for i in range(len(self.replicas))]
        # -- continuous profiling (apex_tpu.obs.contprof): one
        # profiler + drift sentinel per replica, phases staggered so
        # fleet windows never collide on the process-global tracer
        self.profilers = None
        self.sentinels = None
        self._m_rep_drift = []
        if self.rcfg.contprof is not None:
            import dataclasses as _dc

            from apex_tpu.obs import contprof as contprof_lib
            n = len(self.replicas)
            stride = max(self.rcfg.contprof.capture_steps + 1,
                         self.rcfg.contprof.capture_every // max(n, 1))
            self.profilers, self.sentinels = [], []
            for i, rep in enumerate(self.replicas):
                sent = contprof_lib.DriftSentinel(
                    band=self.rcfg.contprof_band,
                    k=self.rcfg.contprof_k,
                    registry=rep.eng.metrics,
                    flight=self.flight,
                    incident_path=self.rcfg.incident_path,
                    name="serve")
                cfg_i = _dc.replace(
                    self.rcfg.contprof,
                    phase=self.rcfg.contprof.phase + i * stride)
                self.sentinels.append(sent)
                self.profilers.append(contprof_lib.serve_profiler(
                    rep.eng, config=cfg_i, sentinel=sent))
            self._m_rep_drift = [
                self.metrics.gauge(
                    f"serve_replica{i}_profile_drift",
                    f"replica {i} confirmed-unrecovered op-level "
                    f"drift (mirror of its serve_profile_drift "
                    f"gauge; drifting replicas rank last in "
                    f"admission)")
                for i in range(len(self.replicas))]

    # -- submission ----------------------------------------------------

    def submit(self, req: Request) -> None:
        """Validate against ONE decode replica's shapes
        (:func:`~apex_tpu.serve.scheduler.validate_request` — the
        scheduler's own check; every replica is identical) and
        enqueue, so a request no replica could ever hold is rejected
        here, not deadlocked later."""
        validate_request(req, self.scfg.block_size,
                         self.scfg.max_blocks_per_slot,
                         self.scfg.num_blocks)
        self.queue.append(req)
        if self.tracer is not None:
            # router admission is the request id's birthplace: mint
            # the trace here, then every engine the request touches
            # appends to the SAME lifecycle under its own label
            self.tracer.mint(req.uid)
            self.tracer.record("enqueue", req.uid, "router",
                               queue_depth=len(self.queue))
        self._m_queue.set(float(len(self.queue)))

    # -- routing -------------------------------------------------------

    def _eligible(self, req: Request) -> List[tuple]:
        """``(load, replica)`` for every replica that may take ``req``
        this boundary: alive, a free slot + footprint coverage, block
        utilization under the admission bar, SLO window clean."""
        scored = [((self._drifting(r),) + r.load(), r)
                  for r in self.replicas
                  if r.can_admit(req) and not self._slo_violating(r)]
        return [(load, r) for load, r in scored
                if load[2] < self.rcfg.admit_block_util]

    def _pick_replica(self, req: Request) -> Optional[DecodeReplica]:
        """Least-loaded eligible replica, from the obs gauges: alive,
        a free slot + footprint coverage, block utilization under the
        admission bar; ranked by (outstanding work, utilization,
        decode p99)."""
        eligible = self._eligible(req)
        if not eligible:
            return None
        return min(eligible, key=lambda lr: lr[0])[1]

    def _pick_prefix_replica(self, req: Request):
        """Straight-to-decode probe: ``(replica, matched_tokens)`` for
        the eligible replica whose prefix index covers the most
        leading prompt tokens (load breaks ties), or ``(None, 0)``
        when no index covers any — per-replica indexes, so the probe
        asks each replica's OWN scheduler.  Side-effect-free:
        ``probe_prefix_tokens`` touches no refcounts."""
        best = None
        for load, r in self._eligible(req):
            hit = r.eng.sched.probe_prefix_tokens(req.prompt)
            if hit > 0 and (best is None or (-hit, load) < best[0]):
                best = ((-hit, load), r)
        if best is None:
            return None, 0
        return best[1], -best[0][0]

    def _drifting(self, rep: DecodeReplica) -> bool:
        """True when the replica's drift sentinel holds a confirmed,
        unrecovered op-level drift — it ranks LAST in admission (a
        soft de-rank, not a block: a fleet whose every replica
        drifted must still serve)."""
        if self.sentinels is None:
            return False
        return self.sentinels[rep.index].drifting

    def _slo_violating(self, rep: DecodeReplica) -> bool:
        """True when the replica's LAST boundary evaluation has a
        violated objective — it keeps decoding what it holds, but
        takes no new admissions until the window recovers."""
        if self.slo_evals is None:
            return False
        return self.slo_evals[rep.index].violated()

    def _route_one(self) -> bool:
        """Route the head-of-queue request; False = held (admission
        control: no eligible replica this boundary)."""
        req = self.queue[0]
        # prefix hit → STRAIGHT to the decode replica holding the
        # match: its own admission increfs the shared span and
        # prefills only the unmatched suffix locally — no prefill
        # slice, no shipment for bytes the destination already holds.
        # kill_replica recovery re-enqueues continuations through this
        # same probe, so a rerouted request re-prefills only what the
        # surviving replicas' indexes don't cover.
        hit_rep, hit_tokens = self._pick_prefix_replica(req)
        if hit_rep is not None:
            self.queue.pop(0)
            hit_rep.submit(req)
            self._m_prefix_direct.inc()
            if self.tracer is not None:
                self.tracer.record("prefix_direct", req.uid, "router",
                                   to_replica=hit_rep.index,
                                   matched_tokens=hit_tokens)
            return True
        rep = self._pick_replica(req)
        if rep is None:
            return False
        self.queue.pop(0)
        if self.rcfg.transfer == "recompute":
            rep.submit(req)
            return True
        verdict = self.prefill.prefill(req)
        if verdict[0] == "done":
            self._outputs[req.uid] = verdict[1]
            return True
        shp = transfer.ship(verdict[1], rep.placement)
        if self.tracer is not None:
            self.tracer.record("kv_ship", req.uid, "router",
                               to_replica=rep.index,
                               nbytes=int(shp.nbytes))
        slot = rep.admit_shipment(shp)
        if slot is not None:
            self._m_ship.inc()
            self._m_bytes.inc(shp.nbytes)
            if self.tracer is not None:
                self.tracer.record("kv_install", req.uid,
                                   f"replica{rep.index}", slot=slot)
        else:
            # transfer miss (the capacity check raced a same-boundary
            # admission): recompute-on-miss — the ORIGINAL request
            # re-prefills through the replica's own machinery
            rep.submit(req)
        return True

    def step(self) -> Dict[str, np.ndarray]:
        """One fleet step boundary: route admissions (prefill + ship),
        then one decode step on every live replica; returns the
        requests that finished this boundary."""
        while self.queue and self._route_one():
            pass
        finished: Dict[str, np.ndarray] = {}
        for rep in self.replicas:
            finished.update(rep.step())
        self._outputs.update(finished)
        self._record_metrics()
        return finished

    def _record_metrics(self) -> None:
        self._m_queue.set(float(len(self.queue)))
        for i, rep in enumerate(self.replicas):
            reg = rep.eng.metrics
            self._m_rep_q[i].set(reg.gauge("serve_queue_depth").value)
            self._m_rep_occ[i].set(
                reg.gauge("serve_slot_occupancy").value)
            self._m_rep_util[i].set(
                reg.gauge("serve_block_utilization").value)
            p99 = rep.p99()
            self._m_rep_p99[i].set(0.0 if math.isnan(p99) else p99)
            if self._m_rep_hit:
                self._m_rep_hit[i].set(
                    reg.gauge("serve_prefix_hit_rate").value)
                self._m_rep_shared[i].set(
                    reg.gauge("serve_prefix_shared_blocks").value)
            if self.slo_evals is not None and rep.alive:
                self.slo_evals[i].evaluate()
                self._m_rep_slo[i].set(
                    0.0 if self.slo_evals[i].violated() else 1.0)
            if self.sentinels is not None:
                self._m_rep_drift[i].set(
                    1.0 if self.sentinels[i].drifting else 0.0)
        self.metrics.tick()

    def slo_summary(self) -> "Optional[dict]":
        """Per-replica SLO verdicts from the last boundary (the block
        the serving tools record into their artifacts); ``None`` when
        no objectives are configured."""
        if self.slo_evals is None:
            return None
        return {f"replica{i}": ev.summary()
                for i, ev in enumerate(self.slo_evals)}

    def idle(self) -> bool:
        return not self.queue and all(r.idle() for r in self.replicas)

    def run(self, max_steps: int = 100_000) -> Dict[str, np.ndarray]:
        """Drain the fleet; ``{uid: generated token ids}`` for every
        request ever submitted (prompt not repeated)."""
        steps = 0
        try:
            while not self.idle():
                outstanding = len(self.queue) + sum(
                    r.eng.sched.n_active() + len(r.eng.sched.queue)
                    for r in self.replicas if r.alive)
                self.step()
                steps += 1
                if steps > max_steps:
                    raise RuntimeError(
                        f"router loop exceeded {max_steps} steps with "
                        f"{outstanding} request(s) outstanding")
        finally:
            if self.profilers is not None:
                for prof in self.profilers:
                    prof.abort_window()
        return dict(self._outputs)

    # -- failure semantics --------------------------------------------

    def kill_replica(self, index: int) -> List[str]:
        """Lose replica ``index`` mid-stream (its device state —
        pools, keys — is gone).  Every in-flight request is rebuilt
        from the router's streamed-token log as a continuation
        (original prompt + every token streamed so far, remaining
        budget, PRNG chain re-derived by draw count via
        :func:`~apex_tpu.serve.sampling.advance_key`) and re-queued
        AT THE FRONT to re-prefill on a live replica; the replica's
        engine-local queue re-queues as-is.  Returns the rerouted
        uids; greedy outputs stay bitwise equal to solo
        ``generate()`` through the whole event."""
        rep = self.replicas[index]
        if not rep.alive:
            return []
        rep.alive = False
        if self.profilers is not None:
            # a dead replica steps no more, so its open capture window
            # would hold the process-global capture lock forever and
            # silently stop fleet-wide profiling during exactly the
            # incident the sentinel exists for
            self.profilers[index].abort_window()
        if self.flight is not None:
            self.flight.note("replica_kill", replica=index,
                             active=rep.eng.sched.n_active(),
                             queued=len(rep.eng.sched.queue))
        rerouted: List[Request] = []
        sched = rep.eng.sched
        for slot in range(sched.num_slots):
            s = sched.slots[slot]
            if s is None:
                continue
            req = s.request
            if req.max_new_tokens - len(s.emitted) < 1:
                continue           # retired the same boundary it died
            # one PRNG draw per streamed token (prefill sample
            # included) — the chain position is the draw count, so a
            # lost device key is re-derivable from the seed; the
            # continuation record itself is the scheduler's own
            # (preempt's builder — one contract for both interrupts)
            draws = len(req.prior_tokens) + len(s.emitted)
            key = advance_key(jax.random.PRNGKey(req.seed), draws)
            rerouted.append(
                sched.continuation(slot, np.asarray(key)))
        # engine-local queue (recompute admissions, preemption
        # continuations): nothing emitted since queuing — re-route
        # them unchanged
        rerouted.extend(sched.queue)
        self.queue[:0] = rerouted
        for r in rerouted:
            if self.tracer is not None:
                # every reroute NAMES the killed replica — the TRACE
                # schema rejects a reroute citing a live one
                self.tracer.record("reroute", r.uid, "router",
                                   from_replica=index)
            if self.flight is not None:
                self.flight.note("reroute", uid=r.uid,
                                 from_replica=index)
        self._m_reroute.inc(len(rerouted))
        self._m_queue.set(float(len(self.queue)))
        if self.rcfg.incident_path:
            self._write_kill_incident(index, [r.uid for r in rerouted])
        return [r.uid for r in rerouted]

    def _write_kill_incident(self, index: int,
                             rerouted: List[str]) -> None:
        """The replica death's black box: a schema-valid incident
        record carrying the resolved router metrics AND the flight
        ring's tail (the events that led here — admissions, ships,
        the kill, the reroutes), so the chaos drill interrogates a
        history instead of two end-state gauges."""
        from apex_tpu.resilience import incidents as incidents_lib
        extra: Dict[str, Any] = {
            "artifact": "disagg-router replica-death record",
            "replica": index, "rerouted": rerouted,
            "metrics": self.metrics.snapshot(),
        }
        if self.flight is not None:
            extra["flight"] = self.flight.dump()
        try:
            incidents_lib.write_incident(
                self.rcfg.incident_path, "replica-killed",
                f"decode replica {index} lost mid-stream; "
                f"{len(rerouted)} request(s) rebuilt from the "
                f"streamed-token log and re-prefilled elsewhere",
                [f"replica {index} killed with "
                 f"{len(rerouted)} in-flight/queued request(s)",
                 {"rerouted_uids": rerouted}],
                **extra)
        except Exception:
            import traceback
            traceback.print_exc()   # the drill must not die on its
            #                         own forensics
