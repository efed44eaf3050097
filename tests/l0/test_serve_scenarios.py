"""tools/serve_scenarios.py — the scenario-matrix harness.

The committed SCENARIO_r*.json's schema validity and gate verdict are
pinned by ``tests/l0/test_gate_hygiene.py`` (the artifact is gate
memory).  Here: the cell driver emits schema-shaped records whose
gates derive from their own numbers, the committed matrix covers the
contexts the roadmap names, and the 32k-context cell runs (slow
lane)."""

import copy
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO / "tools"))

import serve_scenarios  # noqa: E402

from apex_tpu import amp  # noqa: E402
from apex_tpu.analysis.scenario import validate_scenario  # noqa: E402
from apex_tpu.models import GPTModel, gpt_tiny  # noqa: E402
from apex_tpu.serve import truncated_draft  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    params = amp.initialize(
        opt_level="O2", verbosity=0).model_params_from(params)
    ids = np.asarray((np.arange(8 * 32).reshape(8, 32) * 7) % 16,
                     np.int32)
    return cfg, params, ids


@pytest.mark.slow
def test_run_cell_records_are_schema_shaped(tiny):
    """One spec-off/spec-on cell pair at a tiny shape: both records
    carry the schema's numbers and a gate DERIVED from them, and a
    document assembled from them (replicated to the matrix minimum)
    validates clean."""
    cfg, params, ids = tiny
    draft = truncated_draft(params, cfg, 1)
    knobs = dict(context=32, new_tokens=4, num_slots=2,
                 arrival="steady", sampling="greedy", kv8=False,
                 churn=False, spec_k=2)
    reqs = serve_scenarios._requests(ids, 32, 4, 4, "greedy")
    off = serve_scenarios.run_cell(cfg, params, draft, list(reqs),
                                   spec=False, **knobs)
    on = serve_scenarios.run_cell(cfg, params, draft, list(reqs),
                                  spec=True, **knobs)
    assert off["retraces"] == 1 and on["retraces"] == 1
    assert on["config"]["spec"] and not off["config"]["spec"]
    assert "acceptance_rate" in on
    cells, ab = {}, []
    for i in range(5):
        o, s = copy.deepcopy(off), copy.deepcopy(on)
        cells[f"c{i}"], cells[f"c{i}_spec"] = o, s
        ab.append({"on": f"c{i}_spec", "off": f"c{i}",
                   "tokens_per_step_on": s["tokens_per_step"],
                   "tokens_per_step_off": o["tokens_per_step"],
                   "spec_wins": s["tokens_per_step"]
                   > o["tokens_per_step"],
                   "gated": i == 0})
    cells_ok = all(c["gate"]["ok"] for c in cells.values())
    ab_ok = all(r["spec_wins"] for r in ab if r["gated"])
    doc = {"round": 1, "platform": "cpu", "model": "gpt_tiny",
           "gate_k": serve_scenarios.GATE_K, "cells": cells, "ab": ab,
           "gate": {"cells_ok": cells_ok, "ab_ok": ab_ok,
                    "ok": cells_ok and ab_ok}}
    assert validate_scenario(doc) == []


def test_cell_matrix_covers_contexts_and_axes():
    """The committed matrix names the roadmap's axes: contexts
    128-2048, burst + steady arrivals, a mixed-sampling cell, a churn
    cell, a kv8 cell — and ``--full`` adds the 32k slow cell."""
    base = serve_scenarios.cell_matrix(full=False)
    contexts = {k["context"] for _, k, _ in base}
    assert {128, 512, 2048} <= contexts
    assert 32768 not in contexts
    assert any(k["arrival"] == "burst" for _, k, _ in base)
    assert any(k["sampling"] == "mixed" for _, k, _ in base)
    assert any(k["churn"] for _, k, _ in base)
    assert any(k["kv8"] for _, k, _ in base)
    gated = [g for _, _, g in base if g]
    assert len(gated) >= 3       # the steady greedy pairs are gated
    full = serve_scenarios.cell_matrix(full=True)
    assert any(k["context"] == 32768 for _, k, _ in full)


@pytest.mark.slow
def test_chat_cell_reuses_history_and_churn_pins_sharing_off(tiny):
    """The multi-turn chat cell's second turn resubmits each request's
    own prompt + streamed reply, so the content index must HIT (prompt
    blocks registered at arm, reply blocks at decode boundaries) and
    the cell records its prefix counters; a churn cell pins sharing
    OFF — the repeated training-stream prompts would dedupe and absorb
    the engineered block shortage — so its record carries NO prefix
    block."""
    cfg, params, ids = tiny
    draft = truncated_draft(params, cfg, 1)
    knobs = dict(context=32, new_tokens=4, num_slots=2,
                 arrival="steady", sampling="greedy", kv8=False,
                 spec=False, spec_k=2)
    reqs = serve_scenarios._requests(ids, 16, 4, 2, "greedy")
    chat = serve_scenarios.run_cell(cfg, params, draft, list(reqs),
                                    churn=False, chat=True, **knobs)
    assert chat["prefix"]["probes"] >= 4     # both turns probe
    assert chat["prefix"]["hits"] >= 2       # every turn-2 admission
    assert chat["prefix"]["hit_rate"] > 0
    assert chat["gate"]["retrace_ok"], chat

    reqs = serve_scenarios._requests(ids, 16, 4, 2, "greedy")
    churn = serve_scenarios.run_cell(cfg, params, draft, list(reqs),
                                     churn=True, chat=False, **knobs)
    assert "prefix" not in churn


def test_committed_artifact_round_trips_the_tool_gate():
    """The committed r01 carries the tool's own derived verdict: the
    gated A/B rows all won (tokens/step strictly greater with spec
    on) — the speculative latency win as committed gate memory."""
    arts = sorted(REPO.glob("SCENARIO_r*.json"))
    assert arts, "SCENARIO_r01.json must be committed"
    doc = json.loads(arts[-1].read_text())
    gated = [r for r in doc["ab"] if r["gated"]]
    assert gated and all(r["spec_wins"] for r in gated)
    specs = [c for c in doc["cells"].values() if c["config"]["spec"]]
    assert specs and all(c["acceptance_rate"] > 0 for c in specs)


@pytest.mark.slow
def test_32k_cell_runs_and_gates(tiny):
    """The 32k-context cell (slow lane): a whole-pool-reach page
    table, 512 prefill chunks, and the same tail/retrace gate as
    every other cell."""
    cfg, params, ids = tiny
    draft = truncated_draft(params, cfg, 1)
    name, knobs, _g = next(c for c in serve_scenarios.cell_matrix(True)
                           if c[1]["context"] == 32768)
    knobs = dict(knobs)
    num_slots = knobs.pop("num_slots")
    n_requests = knobs.pop("n_requests")
    block_size = knobs.pop("block_size")
    reqs = serve_scenarios._requests(ids, knobs["context"],
                                     knobs["new_tokens"], n_requests,
                                     knobs["sampling"])
    rec = serve_scenarios.run_cell(cfg, params, draft, reqs,
                                   num_slots=num_slots,
                                   block_size=block_size,
                                   spec=False, spec_k=2, **knobs)
    assert rec["config"]["context"] == 32768
    assert rec["gate"]["retrace_ok"], rec
    # the measuring window opens AFTER the first (compile) step, so
    # it sees new_tokens minus the prefill sample and that first step
    assert rec["decode_tokens"] >= 1 and rec["decode_steps"] >= 1
    assert rec["tokens_per_step"] >= 1.0
