"""SCENARIO_r*.json — schema for the committed serve scenario-matrix
gate artifact.

``tools/serve_scenarios.py`` writes one of these per round: the serve
engine driven through a MATRIX of scenarios — mixed context lengths,
burst vs steady arrivals, per-slot sampling knobs, slot churn /
preemption, the int8 KV cache on/off, speculative decoding on/off —
with every cell carrying its own latency-tail gate (``p99 <= K * p50``
and ``retraces == 1``) and the spec-enabled cells paired against their
baselines in a tokens-per-decode-step A/B.  "Handles many scenarios"
thereby becomes a committed, machine-checked artifact instead of a
claim, and the speculative-decoding latency win is a gated number.

Contradiction rejection, like every gate schema in this family: a
cell's recorded ``gate`` verdict must AGREE with its own numbers (the
tail bound re-derived from p50/p99 and ``gate_k``, the retrace bound
from ``retraces``), an A/B row's ``spec_wins`` must agree with the two
tokens-per-step numbers it cites (which must in turn match the cells
they cite), and the document verdict must be the conjunction of every
cell gate plus every GATED A/B win — so the artifact can never say
"ok" over numbers that derive otherwise.

The committed round must cover at least :data:`MIN_CELLS` cells —
the scenario matrix is the point; a two-cell document is not one.

This module is deliberately **stdlib-only** (no jax import):
``tools/gate_hygiene.py`` loads it directly by file path in tier-1.

Document shape::

    {
      "round": 1,
      "platform": "cpu",
      "model": "gpt_tiny",
      "gate_k": 20.0,               # the p99 <= K * p50 multiplier
      "cells": {
        "ctx128_steady_greedy": {
          "config": {"context": 128, "new_tokens": 16, "num_slots": 4,
                     "arrival": "steady", "sampling": "greedy",
                     "kv8": false, "spec": false, "churn": false},
          "tok_s": ..., "p50_ms": ..., "p99_ms": ...,
          "decode_steps": ..., "decode_tokens": ...,
          "tokens_per_step": ..., "retraces": 1, "preemptions": 0,
          "acceptance_rate": 0.62,           # spec cells only
          "prefix": {"probes": 4, "hits": 3,  # optional: engines with
                     "hit_rate": 0.75},       # the prefix cache on
          "gate": {"tail_ok": true, "retrace_ok": true, "ok": true}
        }, ...
      },
      "ab": [
        {"on": "ctx128_steady_greedy_spec", "off": "ctx128_steady_greedy",
         "tokens_per_step_on": 1.9, "tokens_per_step_off": 1.0,
         "spec_wins": true, "gated": true},
        ...
      ],
      "gate": {"cells_ok": true, "ab_ok": true, "ok": true},
      "note": "..."
    }
"""

from __future__ import annotations

import json
from typing import List

#: a committed scenario round must actually be a matrix
MIN_CELLS = 10

ARRIVALS = ("steady", "burst")
SAMPLINGS = ("greedy", "mixed")

#: the closed SLO status vocabulary (apex_tpu.obs.slo) — cells may
#: carry an OPTIONAL ``slo`` verdict block; when present it is
#: validated: statuses from this vocabulary only, and the block's
#: ``ok`` must re-derive from them (no self-citing SLO verdicts).
SLO_STATUSES = ("met", "violated", "insufficient_window")


def _check_slo_block(name: str, slo, problems: List[str]):
    """Validate one optional SLO verdict block; returns its ok when
    well-formed, else None."""
    if not isinstance(slo, dict) or \
            not isinstance(slo.get("objectives"), dict) or \
            not isinstance(slo.get("ok"), bool):
        problems.append(f"{name} must carry an 'objectives' map and "
                        f"an 'ok' bool")
        return None
    violated = False
    for oname, rec in slo["objectives"].items():
        st = rec.get("status") if isinstance(rec, dict) else None
        if st not in SLO_STATUSES:
            problems.append(f"{name}.objectives[{oname}].status "
                            f"{st!r} not in {SLO_STATUSES}")
            return None
        violated = violated or (st == "violated")
    if slo["ok"] != (not violated):
        problems.append(
            f"CONTRADICTORY verdict: {name}.ok={slo['ok']} but the "
            f"objective statuses derive {not violated}")
    return slo["ok"]


def _num(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _check_prefix_block(name: str, prefix, problems: List[str]):
    """Validate one optional per-cell prefix-sharing block: the hit
    rate must RE-DERIVE from the recorded probe/hit counts."""
    if not isinstance(prefix, dict) or \
            not isinstance(prefix.get("probes"), int) or \
            not isinstance(prefix.get("hits"), int) or \
            not _num(prefix.get("hit_rate")):
        problems.append(f"{name} must carry probes/hits ints and a "
                        f"hit_rate number")
        return
    if not 0 <= prefix["hits"] <= prefix["probes"]:
        problems.append(f"{name}: hits {prefix['hits']} outside "
                        f"[0, probes={prefix['probes']}]")
        return
    derived = round(prefix["hits"] / max(prefix["probes"], 1), 6)
    if abs(prefix["hit_rate"] - derived) > 1e-6:
        problems.append(
            f"CONTRADICTORY record: {name}.hit_rate="
            f"{prefix['hit_rate']} but hits/probes derives {derived}")


def _check_cell(name: str, cell, gate_k, problems: List[str]):
    """Validate one cell; returns its (ok, tokens_per_step) when the
    record is well-formed enough to cite, else None."""
    if not isinstance(cell, dict):
        problems.append(f"cells[{name}] is not an object")
        return None
    cfg = cell.get("config")
    if not isinstance(cfg, dict):
        problems.append(f"cells[{name}].config missing")
        return None
    if not (isinstance(cfg.get("context"), int) and cfg["context"] > 0):
        problems.append(f"cells[{name}].config.context must be a "
                        f"positive int")
    if cfg.get("arrival") not in ARRIVALS:
        problems.append(f"cells[{name}].config.arrival "
                        f"{cfg.get('arrival')!r} not in {ARRIVALS}")
    if cfg.get("sampling") not in SAMPLINGS:
        problems.append(f"cells[{name}].config.sampling "
                        f"{cfg.get('sampling')!r} not in {SAMPLINGS}")
    for flag in ("kv8", "spec", "churn"):
        if not isinstance(cfg.get(flag), bool):
            problems.append(f"cells[{name}].config.{flag} missing "
                            f"(bool)")
    for k in ("tok_s", "p50_ms", "p99_ms", "tokens_per_step"):
        if not _num(cell.get(k)) or cell[k] < 0:
            problems.append(f"cells[{name}].{k} missing or not a "
                            f"non-negative number: {cell.get(k)!r}")
            return None
    if cell["p99_ms"] < cell["p50_ms"]:
        problems.append(f"cells[{name}]: p99 {cell['p99_ms']} under "
                        f"p50 {cell['p50_ms']} — not a percentile pair")
    for k in ("decode_steps", "decode_tokens", "retraces"):
        if not isinstance(cell.get(k), int) or cell[k] < 1:
            problems.append(f"cells[{name}].{k} missing or < 1")
            return None
    # tokens_per_step must BE decode_tokens / decode_steps (the tool
    # records it at 4 decimals) — otherwise the whole A/B chain is
    # anchored to a free-floating number a fabricated win could edit
    derived_tps = round(cell["decode_tokens"] / cell["decode_steps"], 4)
    if cell["tokens_per_step"] != derived_tps:
        problems.append(
            f"CONTRADICTORY record: cells[{name}].tokens_per_step="
            f"{cell['tokens_per_step']} but decode_tokens/"
            f"decode_steps = {cell['decode_tokens']}/"
            f"{cell['decode_steps']} derives {derived_tps}")
    if cfg.get("spec") is True and not _num(cell.get("acceptance_rate")):
        problems.append(f"cells[{name}]: spec cell without a recorded "
                        f"acceptance_rate")
    if cfg.get("churn") is True and not (
            isinstance(cell.get("preemptions"), int)
            and cell["preemptions"] >= 1):
        problems.append(f"cells[{name}]: a churn cell that preempted "
                        f"nothing churned nothing (preemptions >= 1)")
    gate = cell.get("gate")
    if not isinstance(gate, dict) or not all(
            isinstance(gate.get(k), bool)
            for k in ("tail_ok", "retrace_ok", "ok")):
        problems.append(f"cells[{name}].gate missing tail_ok/"
                        f"retrace_ok/ok bools")
        return None
    # -- verdicts must agree with their own numbers -------------------
    if _num(gate_k):
        derived_tail = cell["p99_ms"] <= gate_k * cell["p50_ms"]
        if gate["tail_ok"] != derived_tail:
            problems.append(
                f"CONTRADICTORY verdict: cells[{name}].gate.tail_ok="
                f"{gate['tail_ok']} but p99 {cell['p99_ms']} vs "
                f"{gate_k} x p50 {cell['p50_ms']} derives "
                f"{derived_tail}")
    derived_retrace = cell["retraces"] == 1
    if gate["retrace_ok"] != derived_retrace:
        problems.append(
            f"CONTRADICTORY verdict: cells[{name}].gate.retrace_ok="
            f"{gate['retrace_ok']} but retraces={cell['retraces']}")
    if gate["ok"] != (gate["tail_ok"] and gate["retrace_ok"]):
        problems.append(
            f"CONTRADICTORY verdict: cells[{name}].gate.ok="
            f"{gate['ok']} but tail_ok={gate['tail_ok']} and "
            f"retrace_ok={gate['retrace_ok']}")
    if cell.get("slo") is not None:
        _check_slo_block(f"cells[{name}].slo", cell["slo"], problems)
    if cell.get("prefix") is not None:
        _check_prefix_block(f"cells[{name}].prefix", cell["prefix"],
                            problems)
    return gate["ok"], cell["tokens_per_step"]


def validate_scenario(doc) -> List[str]:
    """Problems with one parsed SCENARIO document (empty = valid)."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]
    if not isinstance(doc.get("round"), int):
        problems.append("missing/invalid 'round' (int)")
    if not isinstance(doc.get("platform"), str):
        problems.append("missing/invalid 'platform' (str)")
    if not isinstance(doc.get("model"), str):
        problems.append("missing/invalid 'model' (str)")
    gate_k = doc.get("gate_k")
    if not _num(gate_k) or gate_k <= 1:
        problems.append(f"missing/invalid 'gate_k' (> 1): {gate_k!r}")
        gate_k = None

    cells = doc.get("cells")
    cell_facts = {}
    if not isinstance(cells, dict) or not cells:
        problems.append("missing/empty 'cells' object")
        cells = {}
    elif len(cells) < MIN_CELLS:
        problems.append(
            f"only {len(cells)} cells — a scenario MATRIX round "
            f"covers >= {MIN_CELLS} (the coverage claim is the "
            f"artifact's whole point)")
    for name, cell in cells.items():
        fact = _check_cell(name, cell, gate_k, problems)
        if fact is not None:
            cell_facts[name] = fact

    # -- the spec-vs-baseline A/B table -------------------------------
    ab = doc.get("ab")
    ab_gated_wins = []
    if not isinstance(ab, list) or not ab:
        problems.append("missing/empty 'ab' list (the spec-vs-baseline "
                        "tokens-per-step A/B is the latency-win gate)")
        ab = []
    for i, row in enumerate(ab):
        if not isinstance(row, dict):
            problems.append(f"ab[{i}] is not an object")
            continue
        on, off = row.get("on"), row.get("off")
        ok_row = True
        for side, cid in (("on", on), ("off", off)):
            if cid not in cell_facts:
                problems.append(f"ab[{i}].{side} cites unknown/invalid "
                                f"cell {cid!r}")
                ok_row = False
        if not _num(row.get("tokens_per_step_on")) \
                or not _num(row.get("tokens_per_step_off")) \
                or not isinstance(row.get("spec_wins"), bool) \
                or not isinstance(row.get("gated"), bool):
            problems.append(f"ab[{i}] missing tokens_per_step_on/off "
                            f"numbers + spec_wins/gated bools")
            continue
        if ok_row:
            for side, cid in (("on", on), ("off", off)):
                if row[f"tokens_per_step_{side}"] != cell_facts[cid][1]:
                    problems.append(
                        f"ab[{i}].tokens_per_step_{side}="
                        f"{row[f'tokens_per_step_{side}']} does not "
                        f"match cells[{cid}].tokens_per_step="
                        f"{cell_facts[cid][1]}")
            spec_flags = (cells[on].get("config", {}).get("spec"),
                          cells[off].get("config", {}).get("spec"))
            if spec_flags != (True, False):
                problems.append(
                    f"ab[{i}]: 'on' must cite a spec cell and 'off' "
                    f"its baseline (got spec={spec_flags})")
        derived = row["tokens_per_step_on"] > row["tokens_per_step_off"]
        if row["spec_wins"] != derived:
            problems.append(
                f"CONTRADICTORY verdict: ab[{i}].spec_wins="
                f"{row['spec_wins']} but "
                f"{row['tokens_per_step_on']} vs "
                f"{row['tokens_per_step_off']} derives {derived}")
        if row["gated"]:
            ab_gated_wins.append(row["spec_wins"])

    # -- the optional document-level SLO verdict ----------------------
    doc_slo = doc.get("slo")
    if doc_slo is not None:
        if not isinstance(doc_slo, dict) or \
                not isinstance(doc_slo.get("ok"), bool):
            problems.append("'slo' block must carry an ok bool")
        else:
            derived_slo = all(
                c["slo"].get("ok") is True
                for c in cells.values()
                if isinstance(c, dict)
                and isinstance(c.get("slo"), dict))
            if doc_slo["ok"] != derived_slo:
                problems.append(
                    f"CONTRADICTORY verdict: slo.ok={doc_slo['ok']} "
                    f"but the cells' SLO blocks derive {derived_slo}")

    # -- the document verdict -----------------------------------------
    gate = doc.get("gate")
    if not isinstance(gate, dict) or not all(
            isinstance(gate.get(k), bool)
            for k in ("cells_ok", "ab_ok", "ok")):
        problems.append("missing/invalid 'gate' "
                        "(cells_ok + ab_ok + ok bools)")
    elif not problems:
        # only re-derive the top verdict from a structurally-valid
        # document: a malformed cell already failed the round
        derived_cells = all(ok for ok, _ in cell_facts.values())
        if gate["cells_ok"] != derived_cells:
            problems.append(
                f"CONTRADICTORY verdict: gate.cells_ok="
                f"{gate['cells_ok']} but the cell gates derive "
                f"{derived_cells}")
        derived_ab = bool(ab_gated_wins) and all(ab_gated_wins)
        if gate["ab_ok"] != derived_ab:
            problems.append(
                f"CONTRADICTORY verdict: gate.ab_ok={gate['ab_ok']} "
                f"but the gated A/B rows derive {derived_ab} "
                f"({sum(ab_gated_wins)}/{len(ab_gated_wins)} wins)")
        if gate["ok"] != (gate["cells_ok"] and gate["ab_ok"]):
            problems.append(
                f"CONTRADICTORY verdict: gate.ok={gate['ok']} but "
                f"cells_ok={gate['cells_ok']} and "
                f"ab_ok={gate['ab_ok']}")
    return problems


def validate_scenario_file(path: str) -> List[str]:
    """Problems with one SCENARIO_r*.json file (empty = valid)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return [f"unreadable scenario JSON: {e}"]
    return validate_scenario(doc)
