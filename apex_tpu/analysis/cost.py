"""XLA cost-model lint: static flops / HBM traffic and the roofline
expectation they imply.

``Compiled.cost_analysis()`` is XLA's own per-executable estimate of
floating-point work and bytes accessed — the *static* half of a
roofline: from ``flops`` and ``bytes`` alone the arithmetic intensity
and the best-case utilization of a given chip follow, before anything
runs.  This pass records those numbers per lane.

Finding codes (``op`` field):

=====================  ==================================================
``flops``              info: cost-model flops of the executable
``hbm-bytes``          info: cost-model bytes accessed
``roofline``           info: intensity + static ceiling utilization
                       (needs ``peak_flops`` / ``peak_hbm_bytes_per_s``)
=====================  ==================================================
"""

from __future__ import annotations

from typing import Dict, List, Optional

from apex_tpu.analysis.core import PassContext, register_pass
from apex_tpu.analysis.report import Finding


def cost_table(compiled) -> Optional[Dict[str, float]]:
    """``{"flops", "hbm_bytes"}`` from XLA's cost model, or ``None``
    when the backend doesn't report one.  ``cost_analysis()`` returns a
    dict on some backends and a one-element list of dicts on others;
    both shapes are absorbed here."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend-optional API
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    nbytes = ca.get("bytes accessed")
    if flops is None and nbytes is None:
        return None
    return {"flops": float(flops or 0.0),
            "hbm_bytes": float(nbytes or 0.0)}


def context_cost_table(ctx: PassContext) -> Optional[Dict[str, float]]:
    """:func:`cost_table` of the context's executable, memoized — the
    cost pass and graph_lint's lane record share one HloCostAnalysis
    run per lowering."""
    return ctx.memo("cost_table", lambda: cost_table(ctx.compiled))


def roofline_expectation(flops: float, hbm_bytes: float,
                         peak_flops: float,
                         peak_hbm_bytes_per_s: float) -> dict:
    """The static roofline of a program on a chip: arithmetic
    intensity, the binding resource, and the ceiling utilization — the
    highest MFU any measurement of this program can honestly reach.
    A committed MFU floor for the lane must sit at or under
    ``ceiling_util``."""
    intensity = flops / hbm_bytes if hbm_bytes else float("inf")
    bw_bound_flops_per_s = intensity * peak_hbm_bytes_per_s
    ceiling = min(peak_flops, bw_bound_flops_per_s)
    return {
        "intensity_flops_per_byte": intensity,
        "bound": "compute" if bw_bound_flops_per_s >= peak_flops
                 else "bandwidth",
        "ceiling_flops_per_s": ceiling,
        "ceiling_util": ceiling / peak_flops if peak_flops else 0.0,
    }


def cost_pass(ctx: PassContext,
              peak_flops: Optional[float] = None,
              peak_hbm_bytes_per_s: Optional[float] = None,
              ) -> List[Finding]:
    """Record the executable's cost-model flops/bytes; with chip peaks
    supplied, derive the static roofline expectation (see module
    docstring)."""
    if ctx.compiled is None:
        return [Finding("cost", "info",
                        "skipped: program was not compiled "
                        "(analyze(..., compile=True) to read the "
                        "cost model)")]
    table = context_cost_table(ctx)
    if table is None:
        return [Finding("cost", "info",
                        "this backend exposes no cost_analysis(); "
                        "static roofline not derivable here")]
    findings = [
        Finding("cost", "info",
                f"cost model: {table['flops']:.4g} flops per step",
                op="flops", count=1, bytes=None),
        Finding("cost", "info",
                f"cost model: {table['hbm_bytes']:.4g} bytes accessed "
                f"per step", op="hbm-bytes",
                bytes=int(table["hbm_bytes"])),
    ]
    if peak_flops and peak_hbm_bytes_per_s:
        exp = roofline_expectation(table["flops"], table["hbm_bytes"],
                                   peak_flops, peak_hbm_bytes_per_s)
        findings.append(Finding(
            "cost", "info",
            f"static roofline: intensity "
            f"{exp['intensity_flops_per_byte']:.2f} flop/byte, "
            f"{exp['bound']}-bound, ceiling utilization "
            f"{exp['ceiling_util']:.3f} — any committed MFU floor for "
            f"this lane must sit under that",
            op="roofline"))
    return findings


register_pass("cost", cost_pass)
