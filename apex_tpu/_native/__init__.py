"""ctypes bindings for the native host-runtime library (``csrc/``).

The reference shipped five CUDA extension modules whose *host* halves did
tensor-list packing and metadata planning (``csrc/flatten_unflatten.cpp``,
``csrc/multi_tensor_apply.cuh:39-125``).  On TPU the device kernels are
Pallas; this module is the native host runtime: multithreaded
flatten/unflatten of numpy buffers, DDP bucket planning, and the digest
primitive for the L1 conformance harness.

Every import runs ``make -C csrc`` when the source tree is present (a
no-op unless ``csrc/`` is newer than the library); everything has a
pure-numpy fallback, taken with a warning when the build or load fails, and
``available`` mirrors ``multi_tensor_applier.available`` in the reference —
consumers probe it and degrade gracefully.  Set ``APEX_TPU_NATIVE=0`` to
force the fallback.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import warnings
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_HERE, "libapex_tpu_C.so")
_CSRC = os.path.normpath(os.path.join(_HERE, "..", "..", "csrc"))

available = False
import_err: Optional[BaseException] = None
_lib = None


def _load() -> None:
    global available, import_err, _lib
    if os.environ.get("APEX_TPU_NATIVE", "1") == "0":
        import_err = RuntimeError("disabled via APEX_TPU_NATIVE=0")
        return
    try:
        if os.path.isdir(_CSRC):
            # make decides: its rule rebuilds only when csrc/ is newer,
            # so a stale binary never shadows the source
            subprocess.run(["make", "-C", _CSRC], check=True,
                           capture_output=True)
        lib = ctypes.CDLL(_LIB_PATH)
        lib.apex_flatten.argtypes = [
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_char_p,
            ctypes.c_int]
        lib.apex_unflatten.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_void_p), ctypes.c_int]
        lib.apex_plan_buckets.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_int64)]
        lib.apex_plan_buckets.restype = ctypes.c_int64
        lib.apex_fingerprint64.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]
        lib.apex_fingerprint64.restype = ctypes.c_uint64
        lib.apex_native_abi_version.restype = ctypes.c_int
        if lib.apex_native_abi_version() != 1:
            raise RuntimeError("apex_tpu_C ABI version mismatch")
        _lib = lib
        available = True
    except Exception as e:  # noqa: BLE001 — mirror reference import probe
        import_err = e
        warnings.warn(f"apex_tpu._native unavailable, using the numpy "
                      f"fallback: {type(e).__name__}: {e}", RuntimeWarning)


_load()

_N_THREADS = min(8, os.cpu_count() or 1)


def _as_i64(seq) -> "ctypes.Array":
    return (ctypes.c_int64 * len(seq))(*seq)


def flatten(arrays: Sequence[np.ndarray]) -> np.ndarray:
    """Pack host arrays (same dtype) into one flat 1-D array
    (``apex_C.flatten``)."""
    if not arrays:
        raise ValueError("flatten requires at least one array")
    arrays = [np.ascontiguousarray(a) for a in arrays]
    dtype = arrays[0].dtype
    if any(a.dtype != dtype for a in arrays):
        raise ValueError("flatten requires a single dtype per call "
                         "(group_by_dtype first)")
    nbytes = [a.nbytes for a in arrays]
    offsets = np.concatenate([[0], np.cumsum(nbytes[:-1])]).astype(np.int64)
    out = np.empty(sum(nbytes) // dtype.itemsize, dtype=dtype)
    if not available:
        for a, off in zip(arrays, offsets):
            start = int(off) // dtype.itemsize
            out[start:start + a.size] = a.ravel()
        return out
    srcs = (ctypes.c_void_p * len(arrays))(
        *[a.ctypes.data_as(ctypes.c_void_p).value for a in arrays])
    _lib.apex_flatten(srcs, _as_i64(nbytes),
                      _as_i64([int(o) for o in offsets]),
                      len(arrays), out.ctypes.data_as(ctypes.c_char_p),
                      _N_THREADS)
    return out


def unflatten(flat: np.ndarray,
              shapes: Sequence[Tuple[int, ...]]) -> List[np.ndarray]:
    """Split a flat array back into arrays of ``shapes``
    (``apex_C.unflatten``)."""
    flat = np.ascontiguousarray(flat)
    sizes = [int(np.prod(s)) if len(s) else 1 for s in shapes]
    if sum(sizes) != flat.size:
        raise ValueError(f"flat buffer has {flat.size} elements, shapes "
                         f"require {sum(sizes)}")
    outs = [np.empty(s, dtype=flat.dtype) for s in shapes]
    offsets = np.concatenate([[0], np.cumsum(sizes[:-1])]).astype(np.int64)
    if not available:
        for o, size, off in zip(outs, sizes, offsets):
            start = int(off)
            o.ravel()[:] = flat[start:start + size]
        return outs
    itemsize = flat.dtype.itemsize
    nbytes = [s * itemsize for s in sizes]
    byte_offsets = [int(o) * itemsize for o in offsets]
    dsts = (ctypes.c_void_p * len(outs))(
        *[o.ctypes.data_as(ctypes.c_void_p).value for o in outs])
    _lib.apex_unflatten(flat.ctypes.data_as(ctypes.c_char_p),
                        _as_i64(nbytes), _as_i64(byte_offsets),
                        len(outs), dsts, _N_THREADS)
    return outs


def plan_buckets(numels: Sequence[int], message_numel: int,
                 triggers: Optional[Sequence[bool]] = None) -> np.ndarray:
    """Greedy in-order bucket assignment (apex DDP first-iteration bucketing,
    ``apex/parallel/distributed.py:339-362``): close the running bucket once
    its cumulative numel reaches ``message_numel`` or at a trigger tensor.

    Returns an int64 array of bucket ids, one per tensor.
    """
    n = len(numels)
    ids = np.empty(n, dtype=np.int64)
    if triggers is not None and len(triggers) != n:
        raise ValueError(f"triggers has {len(triggers)} entries for "
                         f"{n} tensors")
    trig = (np.asarray(triggers, dtype=np.uint8) if triggers is not None
            else np.zeros(n, dtype=np.uint8))
    if not available:
        bucket = acc = 0
        for i in range(n):
            ids[i] = bucket
            acc += int(numels[i])
            if acc >= message_numel or trig[i]:
                bucket += 1
                acc = 0
        return ids
    _lib.apex_plan_buckets(
        _as_i64([int(x) for x in numels]),
        trig.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n, int(message_numel),
        ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
    return ids


def fingerprint64(data, seed: int = 0) -> int:
    """FNV-1a digest of an array's (or bytes') raw contents — the primitive
    behind the L1 golden-digest comparisons."""
    if isinstance(data, (bytes, bytearray)):
        buf = np.frombuffer(bytes(data), dtype=np.uint8)
    else:
        buf = np.ascontiguousarray(data).view(np.uint8).ravel()
    if not available:
        h = seed if seed else 0xCBF29CE484222325
        for b in buf.tobytes():
            h = ((h ^ b) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
        return h
    return int(_lib.apex_fingerprint64(
        buf.ctypes.data_as(ctypes.c_void_p), buf.nbytes, seed))
