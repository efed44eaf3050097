"""Lint-gated AOT export: the executable cache the lint gate builds.

Every laned entry point is already lowered and compiled once per
graph-lint run, and the :class:`~apex_tpu.analysis.PassContext` holds
the compiled executable — which until now was thrown away after the
verdict.  This module turns ``analyze()``'s machinery into the build
step of a deployable artifact: after a lane passes its pass matrix,
the compiled executable is AOT-serialized (PJRT executable
serialization via :mod:`jax.experimental.serialize_executable`, the
compiled-program half of the ``jax.export`` story) into a
content-addressed cache, and serve/train startup probes that cache
instead of paying XLA compilation on every cold replica.

Cache-key derivation
--------------------

An entry is keyed by the sha256 of the canonical JSON of
:func:`key_parts`:

- ``module_sha256`` — sha256 of the lowered StableHLO module text
  (the program the user asked for, before XLA's backend passes);
- ``mesh`` — the device topology the program was lowered against
  (``platform[n]``, from the lowering's device assignment);
- ``policy`` — the resolved :class:`apex_tpu.amp.policy.Properties`
  descriptor (opt level, cast dtype, loss-scale mode, fp8 fields);
- ``jax`` / ``jaxlib`` / ``backend`` — the versions that produced the
  executable (a PJRT executable is not portable across them).

Any drift in any part — a one-op program change, a different mesh, a
policy override, a jax upgrade — is a different key, hence a cache
MISS and a fresh compile: stale executables are unreachable by
construction, never "probably compatible".

The lint-gate invariant
-----------------------

An executable can only ENTER the cache clean: :func:`write_entry`
refuses any :class:`~apex_tpu.analysis.Report` carrying an error
finding, and refuses a report whose pass list does not include
``export-compat`` (serializability is part of clean).  The gating
Report is embedded in the per-entry manifest, so an entry can only
LEAVE the cache clean too: :func:`load_entry` re-verifies the
manifest (recomputed key, executable sha256, lint verdict) and skips
— with a warning, never trusting — any entry that is truncated,
bit-flipped, key-inconsistent, or gated by a failing report.

The ``export-compat`` pass
--------------------------

Registered like every other lint pass; statically rejects lanes whose
executables cannot be serialized into a deployable artifact:

==========================  =============================================
finding id (``op``)         rejects
==========================  =============================================
``export-host-callback``    io/pure/debug callbacks, infeed/outfeed: the
                            serialized executable cannot carry the
                            Python callable / host coupling
``export-platform-call``    a ``stablehlo.custom_call`` outside the
                            portable allowlist — backend-library calls
                            resolve against the producing process, not
                            the artifact
``export-static-capture``   a numeric example argument bound statically
                            at trace time: one cache entry per VALUE
                            (a step counter would mint an unbounded
                            entry stream and every replica still misses)
``export-baked-constant``   a weight-sized constant baked into the
                            module: the artifact weighs the checkpoint
                            and the key churns on every new value
==========================  =============================================

Fallback semantics
------------------

:func:`probe` is the startup path (:class:`apex_tpu.serve.ServeEngine`
and ``amp.make_train_step(aot_cache=...)`` ride it): lower once, key,
try the cache; on a verified hit return the deserialized executable,
on a miss (or a corrupted/stale entry, which is skipped with a
warning) fall back to ``lowered.compile()`` and — when
``export_on_miss`` — relint and populate the cache for the next
replica.  The fallback is always a full fresh compile: a bad cache
can cost cold-start time, never correctness.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import re
import shutil
import time
import warnings
from pathlib import Path
from typing import Any, List, Mapping, Optional, Sequence, Tuple

import jax

from apex_tpu.analysis.core import (
    PassContext,
    _args_info,
    _out_info,
    _static_scalars,
    lower_quiet,
    register_pass,
    run_passes,
)
from apex_tpu.analysis.report import Finding, Report
from apex_tpu.analysis.constants import (
    DEFAULT_MIN_BYTES as _CONST_MIN_BYTES,
    constant_capture_pass,
)
from apex_tpu.analysis.syncs import (
    _CALLBACK_TARGETS,
    _INFEED_RE,
    _OUTFEED_RE,
)

#: env knob naming the fleet-wide cache directory.
#: ``tools/aot_export.py`` and :class:`apex_tpu.serve.ServeEngine`
#: fall back to it when no explicit directory is given (one env var
#: enables the whole serving fleet); ``make_train_step(aot_cache=...)``
#: stays EXPLICIT — the cache changes its return contract from a
#: plain jittable to a self-jitting step, which must never flip on an
#: ambient env var.
CACHE_ENV = "APEX_TPU_AOT_CACHE"

#: the full gate matrix an exported lane must pass — ``precision`` is
#: dropped by :func:`probe` when no resolved policy is available (the
#: pass's contract needs one), ``export-compat`` is never droppable.
EXPORT_GATE_PASSES = ("donation", "sharding", "collectives",
                      "constant-capture", "memory", "cost", "syncs",
                      "precision", "export-compat")

#: custom-call targets that serialize portably: sharding annotations
#: are partitioning metadata the artifact's own platform consumes, not
#: references into the producing process.  Everything else — LAPACK
#: wrappers on CPU, cuDNN/cuBLAS handles on GPU, ad-hoc FFI targets —
#: resolves against libraries of the process that compiled it and is
#: refused (``export-platform-call``).
PORTABLE_CUSTOM_CALLS = frozenset({
    "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
    "annotate_device_placement",
})

_EXECUTABLE = "executable.bin"
_MANIFEST = "manifest.json"

_CC_TARGET = re.compile(r"stablehlo\.custom_call\s+@([\w.]+)")


class ExportRefused(Exception):
    """The lint gate refused this executable from the cache.

    ``finding_id`` is the documented id of the first refusing finding
    (an ``export-compat`` op code, or ``lint-error`` when a non-export
    pass gated) — what tools record in the artifact's ``refused``
    field."""

    def __init__(self, finding_id: str, message: str,
                 report: Optional[Report] = None):
        super().__init__(message)
        self.finding_id = finding_id
        self.report = report


# ---------------------------------------------------------------------------
# cache-key derivation
# ---------------------------------------------------------------------------

def module_sha256(stablehlo_text: str) -> str:
    """sha256 of the lowered StableHLO module text — the content half
    of the content address."""
    return hashlib.sha256(stablehlo_text.encode("utf-8")).hexdigest()


def policy_descriptor(policy: Any) -> str:
    """Canonical string of a resolved ``amp.policy.Properties`` (or
    ``"none"``): every field, sorted, dtypes stringified — two
    policies that resolve differently can never share a key."""
    if policy is None:
        return "none"
    if dataclasses.is_dataclass(policy):
        fields = dataclasses.asdict(policy)
    elif hasattr(policy, "_asdict"):
        fields = policy._asdict()
    else:
        return repr(policy)
    return json.dumps(fields, sort_keys=True, default=str)


def mesh_descriptor(lowered: Any = None) -> str:
    """``platform[n]`` of the topology the program was lowered
    against, from the lowering's device assignment when readable
    (best-effort: the process default backend otherwise)."""
    platform = jax.default_backend()
    n = None
    if lowered is not None:
        try:
            da = lowered._lowering.compile_args["device_assignment"]
            n = len(da)
            platform = da[0].platform
        except (AttributeError, KeyError, TypeError, IndexError):
            n = None
    if n is None:
        n = jax.local_device_count()
    return f"{platform}[{n}]"


def runtime_versions() -> dict:
    """The version triple a PJRT executable is pinned to."""
    import jaxlib
    try:
        backend = jax.extend.backend.get_backend()
        backend_v = f"{backend.platform}:{backend.platform_version}"
    except Exception:  # noqa: BLE001 - descriptor stays best-effort
        backend_v = jax.default_backend()
    return {"jax": jax.__version__,
            "jaxlib": getattr(jaxlib, "__version__", "unknown"),
            "backend": backend_v}


def key_parts(stablehlo_text: str, mesh: Optional[str] = None,
              policy: Any = None,
              versions: Optional[Mapping[str, str]] = None) -> dict:
    """The key's preimage: every fact an executable's validity depends
    on.  ``mesh`` defaults to the process topology (pass
    :func:`mesh_descriptor` of the lowering for exactness)."""
    parts = {"module_sha256": module_sha256(stablehlo_text),
             "mesh": mesh if mesh is not None else mesh_descriptor(),
             "policy": policy if isinstance(policy, str)
             else policy_descriptor(policy)}
    parts.update(versions if versions is not None else runtime_versions())
    return parts


def cache_key(parts: Mapping[str, Any]) -> str:
    """sha256 over the canonical JSON of :func:`key_parts`."""
    return hashlib.sha256(
        json.dumps(dict(parts), sort_keys=True).encode("utf-8")
    ).hexdigest()


# ---------------------------------------------------------------------------
# the export-compat pass
# ---------------------------------------------------------------------------

def export_compat_pass(ctx: PassContext,
                       min_const_bytes: int = _CONST_MIN_BYTES,
                       ) -> List[Finding]:
    """Statically reject non-serializable lanes (see the module
    docstring's finding-id table)."""
    findings: List[Finding] = []
    for lineno, line in enumerate(ctx.stablehlo_text.splitlines(), 1):
        if "stablehlo.custom_call" not in line:
            if _INFEED_RE.search(line) or _OUTFEED_RE.search(line):
                findings.append(Finding(
                    "export-compat", "error",
                    "infeed/outfeed inside the program — a serialized "
                    "executable cannot carry the host feeding coupling",
                    op="export-host-callback", lineno=lineno,
                    example=line.strip()[:160]))
            continue
        m = _CC_TARGET.search(line)
        if not m:
            continue
        target = m.group(1)
        if target in _CALLBACK_TARGETS:
            findings.append(Finding(
                "export-compat", "error",
                f"host callback custom_call @{target} — the Python "
                f"callable lives in THIS process; a deserialized "
                f"executable would call into a dangling reference.  "
                f"Strip the callback (or keep this lane compile-only)",
                op="export-host-callback", lineno=lineno,
                example=line.strip()[:160]))
        elif target not in PORTABLE_CUSTOM_CALLS:
            findings.append(Finding(
                "export-compat", "error",
                f"platform-dependent custom_call @{target} — resolves "
                f"against the producing process's backend libraries, "
                f"not the serialized artifact; not exportable",
                op="export-platform-call", lineno=lineno,
                example=line.strip()[:160]))
    for label, typename, value in ctx.static_scalars:
        findings.append(Finding(
            "export-compat", "error",
            f"example argument {label}={value} ({typename}) was bound "
            f"STATICALLY at trace time — the executable is specialized "
            f"per value, so the cache would mint one entry per value "
            f"and every replica still misses; make it a dynamic "
            f"argument (shape-determining statics belong in the lane "
            f"definition, not the call site)",
            op="export-static-capture"))
    for f in constant_capture_pass(ctx, min_bytes=min_const_bytes):
        findings.append(Finding(
            "export-compat", "error",
            f"weight-sized constant baked into the module "
            f"({f.bytes} bytes) — the cache artifact would embed the "
            f"checkpoint and the content key would churn on every new "
            f"value; pass it as an argument",
            op="export-baked-constant", dtype=f.dtype, bytes=f.bytes,
            lineno=f.lineno, example=f.example))
    return findings


register_pass("export-compat", export_compat_pass)


# ---------------------------------------------------------------------------
# cache entries
# ---------------------------------------------------------------------------

def _entry_dir(cache_dir, key: str) -> Path:
    return Path(cache_dir) / key


def serialize_compiled(compiled) -> bytes:
    """One blob for one ``jax.stages.Compiled``: the PJRT executable
    serialization, the arg/out pytree structure it is called through
    (``jax.experimental.serialize_executable`` returns them
    separately; the cache stores the whole calling convention), and
    the ids of the devices it was compiled for, in assignment order —
    a loaded executable runs on those and no others."""
    from jax.experimental import serialize_executable as se
    device_ids = [d.id for d in
                  compiled._executable.xla_executable.local_devices()]
    return pickle.dumps(se.serialize(compiled) + (device_ids,))


def deserialize_compiled(blob: bytes, backend=None):
    from jax.experimental import serialize_executable as se
    serialized, in_tree, out_tree, device_ids = pickle.loads(blob)
    if backend is None or isinstance(backend, str):
        backend = jax.devices(backend)[0].client
    by_id = {d.id: d for d in backend.devices()}
    return se.deserialize_and_load(
        serialized, in_tree, out_tree, backend=backend,
        execution_devices=[by_id[i] for i in device_ids])


def write_entry(cache_dir, key: str, parts: Mapping[str, Any],
                compiled, report: Report, lane: Optional[str] = None,
                extra: Optional[Mapping[str, Any]] = None) -> dict:
    """Serialize ``compiled`` into the cache under ``key`` — ONLY if
    ``report`` gates it clean (no error finding, ``export-compat``
    among the passes that ran).  Returns the manifest.  The write is
    atomic at the entry level (tmp dir + rename), so a concurrent
    reader sees either no entry or a complete one."""
    if "export-compat" not in report.passes:
        raise ExportRefused(
            "export-compat-not-run",
            "the export-compat pass did not run — serializability is "
            "part of the gate, not optional", report)
    if not report.ok:
        # an export-compat id names the hazard most precisely (the
        # syncs pass flags the same io_callback as a host sync, but
        # the EXPORT story is serializability)
        first = next((f for f in report.errors
                      if f.pass_name == "export-compat"),
                     report.errors[0])
        fid = first.op if first.pass_name == "export-compat" \
            else "lint-error"
        raise ExportRefused(
            fid,
            f"lint gate refused the executable: [{first.pass_name}] "
            f"{first.message}", report)
    blob = serialize_compiled(compiled)
    manifest = {
        "key": key,
        "key_parts": dict(parts),
        "sha256": hashlib.sha256(blob).hexdigest(),
        "size": len(blob),
        "lane": lane,
        "lint": report.to_dict(),
        "created_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
    }
    if extra:
        manifest.update(extra)
    dest = _entry_dir(cache_dir, key)
    if dest.exists():
        # same key == same content: keep an INTACT existing entry
        # rather than replace it under a concurrent reader's feet —
        # but a torn or corrupt one (unreadable manifest, sha
        # mismatch, dirty embedded verdict: exactly what made the
        # caller miss) must be healed, or the poisoned entry would
        # force every future replica through a fresh compile forever
        if _entry_intact(dest, key):
            with open(dest / _MANIFEST) as f:
                return json.load(f)
        shutil.rmtree(dest, ignore_errors=True)
    tmp = dest.parent / f".tmp-{key[:16]}-{os.getpid()}"
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    try:
        (tmp / _EXECUTABLE).write_bytes(blob)
        with open(tmp / _MANIFEST, "w") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
            f.write("\n")
        try:
            os.rename(tmp, dest)
        except OSError:
            if not dest.exists():   # not a lost same-key race: real IO
                raise
            # a concurrent writer landed the same content first —
            # their complete entry serves every replica equally well
    finally:
        if tmp.exists():
            shutil.rmtree(tmp, ignore_errors=True)
    return manifest


def _entry_intact(d: Path, key: str) -> bool:
    """Cheap integrity check of an existing entry (no
    deserialization): readable manifest whose key matches, executable
    bytes matching the manifest's sha256, clean embedded verdict."""
    try:
        with open(d / _MANIFEST) as f:
            manifest = json.load(f)
        blob = (d / _EXECUTABLE).read_bytes()
    except (OSError, ValueError):
        return False
    return (isinstance(manifest, dict)
            and manifest.get("key") == key
            and hashlib.sha256(blob).hexdigest() == manifest.get("sha256")
            and isinstance(manifest.get("lint"), dict)
            and manifest["lint"].get("ok") is True)


def _skip(key: str, why: str) -> None:
    warnings.warn(f"aot cache entry {key[:16]}… skipped ({why}) — "
                  f"falling back to a fresh compile; the entry is "
                  f"never trusted", RuntimeWarning, stacklevel=3)


def load_entry(cache_dir, key: str, backend=None
               ) -> "Optional[Tuple[Any, dict]]":
    """``(compiled, manifest)`` on a VERIFIED hit, ``None`` on a miss.

    A present-but-unverifiable entry — unreadable or key-inconsistent
    manifest, sha256 mismatch (truncated/bit-flipped blob), a gating
    report that is not clean, an undeserializable executable — is
    skipped with a :class:`RuntimeWarning`, never trusted."""
    d = _entry_dir(cache_dir, key)
    if not d.is_dir():
        return None                      # plain miss: no entry at all
    try:
        with open(d / _MANIFEST) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        _skip(key, f"unreadable manifest: {e}")
        return None
    if not isinstance(manifest, dict) or manifest.get("key") != key:
        _skip(key, "manifest key mismatch")
        return None
    parts = manifest.get("key_parts")
    if not isinstance(parts, dict) or cache_key(parts) != key:
        _skip(key, "key_parts do not hash to the entry key")
        return None
    lint = manifest.get("lint")
    if not isinstance(lint, dict) or lint.get("ok") is not True:
        _skip(key, "gating lint report absent or not clean")
        return None
    try:
        blob = (d / _EXECUTABLE).read_bytes()
    except OSError as e:
        _skip(key, f"unreadable executable: {e}")
        return None
    if hashlib.sha256(blob).hexdigest() != manifest.get("sha256"):
        _skip(key, "executable sha256 mismatch (truncated or "
                    "bit-flipped)")
        return None
    try:
        compiled = deserialize_compiled(blob, backend=backend)
    except Exception as e:  # noqa: BLE001 - corrupt blobs must not crash startup
        _skip(key, f"deserialization failed: {type(e).__name__}: {e}")
        return None
    return compiled, manifest


def list_entries(cache_dir) -> "List[dict]":
    """Manifests of every complete entry (unreadable ones skipped)."""
    out = []
    root = Path(cache_dir)
    if not root.is_dir():
        return out
    for d in sorted(root.iterdir()):
        mf = d / _MANIFEST
        if not mf.is_file():
            continue
        try:
            with open(mf) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(doc, dict):
            out.append(doc)
    return out


# ---------------------------------------------------------------------------
# the startup probe
# ---------------------------------------------------------------------------

def gate_passes_for(policy: Any) -> Tuple[str, ...]:
    """:data:`EXPORT_GATE_PASSES`, minus ``precision`` when no
    resolved policy is available (the pass's contract needs one);
    ``export-compat`` always stays."""
    if policy is None:
        return tuple(p for p in EXPORT_GATE_PASSES if p != "precision")
    return EXPORT_GATE_PASSES


def probe(jitted, *args, cache_dir, policy=None, mesh: Optional[str] = None,
          lane: Optional[str] = None, export_on_miss: bool = False,
          gate_passes: Optional[Sequence[str]] = None,
          options: Optional[Mapping] = None, **kwargs):
    """``(compiled, info)``: the cold-start path.

    Lowers ``jitted`` on the example args (ONE lowering, exactly like
    ``analyze()``), derives the cache key, and tries ``cache_dir``:

    - verified HIT → the deserialized executable,
      ``info = {"source": "cache", "load_s": ...}``;
    - MISS (or a skipped corrupt/stale entry) → ``lowered.compile()``,
      ``info = {"source": "compile", "compile_s": ...}``; with
      ``export_on_miss`` the fresh executable is relinted under
      :func:`gate_passes_for` and — only if clean — exported, so the
      first replica builds the entry every later replica loads
      (``info["exported"]`` / ``info["refused"]`` record the gate's
      verdict).

    ``cache_dir=None`` degrades to plain compile (the fallback is
    always a full fresh compile — a bad cache can cost cold-start
    time, never correctness)."""
    lowered = lower_quiet(jitted, *args, **kwargs)
    text = lowered.as_text()
    parts = key_parts(text, mesh=mesh if mesh is not None
                      else mesh_descriptor(lowered), policy=policy)
    key = cache_key(parts)
    info: dict = {"key": key, "lane": lane}
    if cache_dir:
        t0 = time.perf_counter()
        hit = load_entry(cache_dir, key)
        if hit is not None:
            compiled, manifest = hit
            info.update(source="cache",
                        load_s=time.perf_counter() - t0,
                        manifest_lane=manifest.get("lane"))
            return compiled, info
    t0 = time.perf_counter()
    compiled = lowered.compile()
    info.update(source="compile", compile_s=time.perf_counter() - t0)
    if cache_dir and export_on_miss:
        ctx = PassContext(
            stablehlo_text=text, hlo_text=compiled.as_text(),
            args=_args_info(lowered), outputs=_out_info(lowered),
            compiled=compiled, policy=policy,
            # the export-static-capture rule reads these: a jit that
            # bound an example scalar statically is specialized per
            # VALUE and must be refused, exactly as analyze() sees it
            static_scalars=_static_scalars(args, kwargs,
                                           lowered.args_info))
        report = run_passes(
            ctx, passes=tuple(gate_passes) if gate_passes is not None
            else gate_passes_for(policy), options=options)
        try:
            write_entry(cache_dir, key, parts, compiled, report,
                        lane=lane)
            info["exported"] = True
        except ExportRefused as e:
            info["exported"] = False
            info["refused"] = e.finding_id
        except OSError as e:   # read-only cache dir: never fail startup
            info["exported"] = False
            info["refused"] = f"io-error: {e}"
    return compiled, info
