"""KV shipment between mesh slices: the transfer path of the
disaggregated prefill/decode fleet.

A disaggregated fleet runs prefill (compute-bound, bursty) and decode
(HBM-bound, steady) on SEPARATE mesh slices — disjoint device subsets
of one platform, each wrapped in its own :class:`jax.sharding.Mesh`
(SNIPPETS [2]/[3]: ``NamedSharding`` placement over
``create_device_mesh``-style slices; ``--xla_force_host_platform_
device_count`` makes the whole topology CPU-testable).  A request
prefills once on the prefill slice and decodes on a decode slice, so
its KV cache must MOVE between block pools that live on different
devices.  This module is that move:

- **slice layout** (:func:`slice_fleet`): carve the platform's devices
  into one prefill slice plus N decode slices, disjoint by
  construction; each replica places its params and pools with
  ``NamedSharding(mesh, P())`` (replicated within the slice — the
  within-slice model sharding story composes later, the BETWEEN-slice
  topology is what this module owns).  Committed placement is what
  pins execution: jax runs a program where its donated carry lives;

- **shipment format** (:class:`KVShipment`): one FIXED-shape bundle
  per prefilled request — every pool of the engine carry gathered
  through the slot's page-table row into ``(L, max_blocks_per_slot,
  block_size, ...)`` (trash-padded rows gather trash-block garbage
  that the destination scatter routes straight back into ITS trash
  block), plus the first sampled token, the prompt length, and the
  slot's live PRNG key.  Fixed shape is the point: one gather program
  and one install program serve every prompt length, so transfer
  never retraces a replica (the one-trace pins in
  ``tests/l0/test_serve_disagg.py``);

- **the wire** (:func:`ship`): ``jax.device_put`` of the bundle onto
  the destination slice's placement — the device-to-device copy
  (ICI/DMA on a real fleet, a buffer copy on the CPU platform) —
  with the byte count returned for the router's
  ``serve_kv_transfer_bytes`` counter;

- **install** (:func:`make_install`): one donated scatter on the
  destination replica writes the shipped blocks into its own pool at
  the page-table row its allocator assigned and drops the PRNG key
  into the keys carry at a TRACED slot index (a static slot would
  mint one executable per slot).

Recompute-on-miss is the fallback, not a mode of this module: when a
shipment cannot be installed (or the router runs ``transfer=
"recompute"``), the ORIGINAL request goes to the decode replica's own
admission path and re-prefills there through the existing
preempt-and-recompute machinery — bitwise the same tokens, paid in
decode-slice compute instead of transfer bytes
(:mod:`apex_tpu.serve.router`).

Prefix sharing composes ON TOP of shipment, not inside it.  A
prefix-HIT request never reaches this module: the router admits it
straight to the decode replica holding the match, which prefills only
the unmatched suffix locally — zero shipped bytes for the shared
span.  A shipped (miss) request still feeds the sharing machinery at
its destination: ``admit_shipment`` arms through the scheduler, whose
``arm()`` registers the installed full blocks in the DESTINATION
replica's content index, so the next same-prefix request hits there.
The shipment format and the gather/install programs are untouched.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


@dataclasses.dataclass(frozen=True)
class FleetSlices:
    """The fleet's device topology: ONE prefill slice plus
    ``len(decode)`` decode slices, pairwise-disjoint device subsets of
    one platform.  ``placement(mesh)`` is the committed sharding a
    replica pins its params/pools with."""

    prefill: Mesh
    decode: tuple

    @property
    def n_devices(self) -> int:
        return len(self.prefill.devices.ravel()) + sum(
            len(m.devices.ravel()) for m in self.decode)

    def describe(self) -> dict:
        """JSON-friendly slice table."""
        return {
            "prefill": [d.id for d in self.prefill.devices.ravel()],
            "decode": [[d.id for d in m.devices.ravel()]
                       for m in self.decode],
        }


def placement(mesh: Mesh) -> NamedSharding:
    """Replicated-within-the-slice placement: the committed sharding
    that pins a replica's arrays (and therefore its compiled programs)
    to its own slice."""
    return NamedSharding(mesh, PartitionSpec())


def slice_fleet(devices: Optional[Sequence] = None,
                n_prefill_devices: int = 1,
                n_decode_replicas: int = 2,
                devices_per_replica: int = 1) -> FleetSlices:
    """Carve ``devices`` (default: every local device) into the fleet
    topology.  Slices are DISJOINT by construction — a prefill burst
    must not steal a decode replica's cycles, which is the whole
    disaggregation claim — and a short device list is an error, never
    a silent overlap."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    need = n_prefill_devices + n_decode_replicas * devices_per_replica
    if n_prefill_devices < 1 or n_decode_replicas < 1 \
            or devices_per_replica < 1:
        raise ValueError(
            f"need >= 1 prefill device, >= 1 decode replica, >= 1 "
            f"device per replica; got {n_prefill_devices}/"
            f"{n_decode_replicas}/{devices_per_replica}")
    if len(devices) < need:
        raise ValueError(
            f"fleet topology needs {need} devices "
            f"({n_prefill_devices} prefill + {n_decode_replicas} x "
            f"{devices_per_replica} decode), have {len(devices)} — "
            f"overlapping slices would fake the disaggregation")
    prefill = Mesh(np.array(devices[:n_prefill_devices]), ("slice",))
    decode = []
    off = n_prefill_devices
    for _ in range(n_decode_replicas):
        decode.append(Mesh(
            np.array(devices[off:off + devices_per_replica]),
            ("slice",)))
        off += devices_per_replica
    return FleetSlices(prefill=prefill, decode=tuple(decode))


def place_tree(tree: Any, sharding: NamedSharding) -> Any:
    """``device_put`` every leaf onto ``sharding`` (committed — the
    arrays, and every program consuming them, belong to the slice)."""
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


# ---------------------------------------------------------------------------
# shipment
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KVShipment:
    """One prefilled request, packaged for a decode slice: the
    fixed-shape per-pool gathers ``{name: (L, max_blocks_per_slot,
    block_size, ...)}``, the first sampled token, the prompt length
    (= the destination slot's starting ``lengths`` entry), the live
    per-request PRNG key ``(2,) uint32``, and the original
    :class:`~apex_tpu.serve.scheduler.Request` (the destination
    allocates the request's FULL footprint — remaining budget
    included — exactly as its own admission path would)."""

    request: Any
    kv: Dict[str, jax.Array]
    first_token: int
    prompt_len: int
    key: jax.Array
    #: device-visible bytes of the kv bundle (counted at gather time,
    #: recorded by the router when the wire copy actually happens)
    nbytes: int = 0

    @property
    def uid(self) -> str:
        return self.request.uid


def shipment_bytes(kv: Dict[str, jax.Array], key: jax.Array) -> int:
    """Bytes the wire moves for one shipment (pools + key; the token
    and length ride the host-side control message)."""
    total = int(np.asarray(key).nbytes)
    for arr in kv.values():
        total += arr.size * arr.dtype.itemsize
    return total


def make_gather(pool_names: Sequence[str],
                trace_counts: Optional[dict] = None,
                count_key: str = "gather"):
    """The prefill worker's one compiled extraction: gather every pool
    of ``carry`` through a page-table ``row (max_blocks_per_slot,)``
    into the fixed shipment shape ``(L, mb, bs, ...)``.  Trash-padded
    row entries gather trash-block contents — garbage by contract,
    masked out at the destination by the slot's ``lengths`` validity
    window and re-routed into the destination's own trash block by
    the install scatter.  ``trace_counts[count_key]`` increments per
    python trace (the one-trace pin's probe)."""
    names = tuple(pool_names)

    def gather(carry, row):
        if trace_counts is not None:
            trace_counts[count_key] += 1
        return {n: jnp.take(carry[n], row, axis=1) for n in names}

    return jax.jit(gather)


def make_install(pool_names: Sequence[str],
                 trace_counts: Optional[dict] = None,
                 count_key: str = "install"):
    """The decode replica's one compiled installation: scatter every
    shipped pool into the replica's own pools at its allocator's
    page-table ``row`` and drop the PRNG ``key`` into the keys carry
    at a TRACED ``slot`` index.  The carry is DONATED — installation
    updates the pools in place, exactly like a decode step — and
    every index is traced, so one executable serves every slot, every
    block layout, and every request of the replica's lifetime.
    ``trace_counts[count_key]`` increments per python trace."""
    names = tuple(pool_names)

    def install(carry, row, shipped, slot, key):
        if trace_counts is not None:
            trace_counts[count_key] += 1
        out = dict(carry)
        for n in names:
            # duplicate trash entries in `row` collapse onto the trash
            # block (last-writer-wins over garbage — block 0 is never
            # read through a live page table)
            out[n] = carry[n].at[:, row].set(shipped[n])
        out["keys"] = carry["keys"].at[slot].set(key)
        return out

    return jax.jit(install, donate_argnums=(0,))


def ship(shipment: KVShipment, dst: NamedSharding) -> KVShipment:
    """The wire: copy the shipment's device payload onto the
    destination slice's placement (device-to-device — jax moves
    buffers directly between devices of one platform) and return the
    shipment re-pointed at the destination copies, ``nbytes``
    stamped for the router's ``serve_kv_transfer_bytes`` counter."""
    kv = {n: jax.device_put(a, dst) for n, a in shipment.kv.items()}
    key = jax.device_put(shipment.key, dst)
    return dataclasses.replace(
        shipment, kv=kv, key=key,
        nbytes=shipment_bytes(kv, key))
