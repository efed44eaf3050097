"""Serve scenario-matrix harness: every scenario cell gated, the
speculative-decoding latency win A/B'd, committed as
``SCENARIO_r*.json``.

Drives the serve engine (and its speculative variant,
:class:`apex_tpu.serve.SpecEngine`) through a MATRIX of scenarios —

- mixed context lengths (128 / 512 / 2048 committed; a 32k cell rides
  ``--full``, the slow lane),
- **burst vs steady** arrivals (all requests up front vs one per step
  boundary),
- per-slot **sampling knobs** (all-greedy vs greedy+temperature/top-k
  mixed in one batch),
- **slot churn**: a deliberately tight block pool so admission
  preempts mid-stream (the cell gate additionally requires
  ``preemptions >= 1`` — a churn cell that churned nothing measured
  nothing),
- the **int8 KV cache** on/off,
- **speculative decoding** on/off (truncated layer-skip draft,
  ``k`` proposals/round),
- **cross-request prefix sharing** exercised two ways: a multi-turn
  chat column (turn 2 resubmits turn 1's prompt + streamed reply —
  the content index must match the whole history) and a
  common-system-prompt burst column (every request shares a
  block-aligned system prefix).  Every cell whose engine runs the
  prefix cache records a ``prefix`` block (probes/hits/hit_rate,
  schema-validated: the rate must re-derive from the counts)

— and emits one schema-valid document (``apex_tpu/analysis/
scenario.py``, validated by ``tools/gate_hygiene.py`` in tier-1) in
which every cell carries the latency-tail gate the serve bench config
uses (``p99 <= K x p50`` from the engine's OWN
``serve_decode_step_seconds`` histogram, ``retraces == 1``), and each
spec cell is paired with its identical-workload baseline in a
tokens-per-decode-step A/B.  The ``gated`` rows — the steady greedy
cells — are the committed claim: speculative decoding converts
bandwidth into tokens/step on this host, strictly.

The model is BRIEFLY TRAINED (the PR 8 fixture pattern): a random-init
model's near-uniform logits make acceptance rates meaningless and put
ulp noise above the argmax margins; the trained tiny model gives the
draft something real to predict.

Usage:
    python tools/serve_scenarios.py --emit-json SCENARIO_r01.json \
        [--cpu-smoke] [--full] [--spec-k 3]

``--cpu-smoke`` is the committed-r01 shape (gpt_tiny, trained
in-process, the full 128-2048 matrix); without it the sweep runs
gpt_small_tpu (a chip-round config).  ``--full`` adds the 32k-context
cell (slow — minutes on CPU).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

os.environ.setdefault("APEX_TPU_KERNELS", "jnp")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=16").strip()
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms",
                  os.environ.get("APEX_TPU_TEST_PLATFORM", "cpu"))
jax.config.update("jax_threefry_partitionable", True)

#: the latency-tail multiplier (a mid-serve retrace or host sync shows
#: up as 100-1000x)
GATE_K = 20.0

#: absolute decode-step p99 SLO budget (seconds) recorded per cell via
#: apex_tpu.obs.slo: the tail gate above is the RELATIVE witness
#: (p99 vs p50); this is the absolute one — a retrace/host-sync
#: blowout (100-1000x a normal step) violates it on any host, normal
#: CPU-smoke noise does not.  A chip round tightens it to serving
#: budgets.
SLO_DECODE_P99_S = 0.25

#: spec cells additionally carry an acceptance-rate floor objective
#: (accepted/proposed over the cell window; the measured briefly-
#: trained rates run 0.8-1.0 — 0.2 is the drafts-are-working bar)
SLO_MIN_ACCEPTANCE = 0.2


def trained_model(tiny: bool):
    """``(cfg, params, ids)`` — briefly trained on a periodic stream
    (the ONE shared recipe,
    :func:`apex_tpu.models.gpt.train_toy_lm`) so argmax margins are
    real and the truncated draft has structure to predict."""
    from apex_tpu.models.gpt import gpt_small_tpu, gpt_tiny, \
        train_toy_lm

    return train_toy_lm(gpt_tiny() if tiny else gpt_small_tpu())


def _requests(ids, context, new_tokens, n, sampling,
              shared_system=False, block_size=4):
    """``n`` requests whose prompts come from the training stream
    (predictable for the draft), lengths alternating full/0.75 of the
    cell's prompt budget, knobs per the cell's sampling mode.  With
    ``shared_system`` every prompt opens with the SAME block-aligned
    system prefix (half the budget) — the chat-service shape the
    prefix-sharing columns exercise."""
    from apex_tpu.serve import Request

    plen_full = context - new_tokens
    sys_len = max((plen_full // 2) // block_size * block_size,
                  block_size) if shared_system else 0
    system = np.asarray(
        [ids[0][j % ids[0].shape[0]] for j in range(sys_len)],
        np.int32)
    reqs = []
    rng = np.random.RandomState(17)
    for i in range(n):
        plen = max(2, int(plen_full * (0.75 + 0.25 * ((i + 1) % 2))))
        row = ids[i % ids.shape[0]]
        tail = np.asarray(
            [row[j % row.shape[0]] for j in range(plen - sys_len)],
            np.int32)
        prompt = np.concatenate([system, tail]) if sys_len else tail
        kw = {}
        if sampling == "mixed" and i % 2 == 1:
            kw = dict(temperature=0.8, top_k=20,
                      seed=int(rng.randint(1 << 16)))
        reqs.append(Request(uid=f"q{i}", prompt=prompt,
                            max_new_tokens=new_tokens, **kw))
    return reqs


def run_cell(cfg, params, draft, reqs, *, context, new_tokens,
             num_slots, arrival, sampling, kv8, spec, churn, spec_k,
             block_size=4, chat=False):
    """One scenario cell: build a fresh engine of the cell's shape,
    drive the request stream ``reqs`` with the cell's arrival process,
    and return the schema's cell record (numbers + the derived
    gate).  Under ``chat`` a second turn follows the first: each
    request resubmits its own prompt + streamed reply + a recycled
    user turn, so the content index must match the whole history
    (prompt blocks registered at arm, reply blocks at decode block
    boundaries)."""
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import (ServeConfig, ServeEngine, SpecConfig,
                                SpecEngine)

    mb = -(-context // block_size)
    if churn:
        # churn: half-context requests into a pool that covers exactly
        # TWO of their worst-case footprints with THREE slots — the
        # third admission meets a free slot but a block shortage, so
        # the scheduler preempts the youngest (recompute-on-resume)
        # and the continuation re-queues; the cell gate requires the
        # preemption to actually have fired
        foot = -(-(context // 2) // block_size)
        num_blocks = 2 * foot + 1
    else:
        num_blocks = num_slots * mb + 1
    scfg = ServeConfig(
        num_slots=num_slots, block_size=block_size,
        num_blocks=num_blocks, max_blocks_per_slot=mb,
        prefill_chunk=min(64, max(block_size, context - new_tokens)),
        kv_dtype="int8" if kv8 else None,
        # churn pins sharing OFF: the training-stream prompts repeat
        # rows, so the content index would dedupe them and absorb the
        # engineered block shortage — and this column exists to
        # measure the preempt/recompute path, not prefix reuse
        prefix_cache=not churn)
    reg = Registry()
    if spec:
        dp, dcfg = draft
        eng = SpecEngine(params, cfg, scfg, dp, dcfg,
                         SpecConfig(k=spec_k), registry=reg)
    else:
        eng = ServeEngine(params, cfg, scfg, registry=reg)
    hist = reg.histogram("serve_decode_step_seconds")
    toks = reg.counter("serve_tokens_total")

    pending = list(reqs)
    if arrival == "burst":
        for r in pending:
            eng.submit(r)
        pending = []
    else:
        eng.submit(pending.pop(0))
    eng.step()                       # admission + compile + 1st step
    mark = hist.state()
    tok0 = toks.value
    # SLO verdicts ride the cell (apex_tpu.obs.slo): evaluated at the
    # same boundaries the registry already ticks, over resolved host
    # state only — the first evaluate() below just seeds the window
    # base at the post-compile mark
    from apex_tpu.obs.slo import SLObjective, SLOEvaluator
    objectives = [SLObjective(
        name="decode_p99", kind="quantile",
        metric="serve_decode_step_seconds", q=0.99,
        threshold=SLO_DECODE_P99_S, window=0, min_count=4)]
    if spec:
        objectives.append(SLObjective(
            name="spec_acceptance", kind="ratio",
            ratio_num="serve_spec_accepted_total",
            ratio_den="serve_spec_proposed_total", op="ge",
            threshold=SLO_MIN_ACCEPTANCE, window=0, min_count=4))
    slo_ev = SLOEvaluator(reg, objectives)
    slo_ev.evaluate()
    t0 = time.perf_counter()
    guard = 0
    done = {}
    while pending or not eng.sched.idle():
        if pending:
            eng.submit(pending.pop(0))
        done.update(eng.step())
        slo_ev.evaluate()
        guard += 1
        if guard > 100_000:
            raise RuntimeError("scenario cell stalled")
    if chat:
        # turn 2 of the chat: history (prompt + reply) + a recycled
        # user turn, through the SAME engine — the turn-1 blocks are
        # cached (refcount 0, still matchable) after retirement
        from apex_tpu.serve import Request
        for r in reqs:
            out = np.asarray(done[r.uid], np.int32)
            prompt2 = np.concatenate(
                [np.asarray(r.prompt, np.int32), out,
                 np.asarray(r.prompt[:block_size], np.int32)])
            eng.submit(Request(uid=f"{r.uid}t2", prompt=prompt2,
                               max_new_tokens=new_tokens))
        while not eng.sched.idle():
            done.update(eng.step())
            slo_ev.evaluate()
            guard += 1
            if guard > 100_000:
                raise RuntimeError("scenario chat turn stalled")
    wall = time.perf_counter() - t0
    decode_steps = int(hist.count - mark[2])
    decode_tokens = int(toks.value - tok0)
    p50 = hist.quantile(0.5, since=mark) * 1e3 if decode_steps else 0.0
    p99 = hist.quantile(0.99, since=mark) * 1e3 if decode_steps else 0.0
    retraces = max(eng.trace_counts.values())
    preempts = int(reg.counter("serve_preemptions_total").value)
    # gate on the ROUNDED values the record stores: the schema
    # re-derives tail_ok from the recorded numbers, so gating on the
    # raw floats could make this tool refuse its own honest artifact
    # on a borderline cell ("CONTRADICTORY" over a rounding epsilon)
    p50_r = round(p50, 3)
    p99_r = round(max(p99, p50), 3)
    tail_ok = p99_r <= GATE_K * p50_r
    retrace_ok = retraces == 1
    rec = {
        "config": {"context": int(context),
                   "new_tokens": int(new_tokens),
                   "num_slots": int(num_slots),
                   "arrival": arrival, "sampling": sampling,
                   "kv8": bool(kv8), "spec": bool(spec),
                   "churn": bool(churn)},
        "tok_s": round(decode_tokens / wall, 2) if wall else 0.0,
        "p50_ms": p50_r, "p99_ms": p99_r,
        # the REAL counts, zeros included: a cell that measured no
        # decode steps must fail the schema's >= 1 rule, not be
        # dressed up as a 1-step measurement that never happened
        "decode_steps": decode_steps,
        "decode_tokens": decode_tokens,
        "tokens_per_step": round(decode_tokens / decode_steps, 4)
        if decode_steps else 0.0,
        "retraces": int(retraces),
        # a churn cell that never preempted is schema-INVALID (the
        # scenario schema requires preemptions >= 1 under churn), so
        # the gate needs no extra term here — mutating gate.ok would
        # only make an honest churnless record read as contradictory
        "preemptions": preempts,
        "gate": {"tail_ok": bool(tail_ok),
                 "retrace_ok": bool(retrace_ok),
                 "ok": bool(tail_ok and retrace_ok)},
        # the SLO verdict block (schema-validated when present): the
        # absolute latency budget + (spec) acceptance floor, judged by
        # apex_tpu.obs.slo over the cell's own window
        "slo": slo_ev.summary(),
    }
    if spec:
        rec["acceptance_rate"] = round(
            float(reg.gauge("serve_spec_acceptance_rate").value), 4)
    # every engine running the prefix cache reports its cell-level
    # hit accounting (schema-validated: the rate must re-derive)
    if getattr(eng.sched, "prefix_cache", False):
        probes = int(eng.sched.prefix_probes)
        rec["prefix"] = {
            "probes": probes,
            "hits": int(eng.sched.prefix_hits),
            "hit_rate": round(
                eng.sched.prefix_hits / max(probes, 1), 6),
        }
    return rec


#: the committed matrix: (name, dict(cell knobs), gated-A/B?).  Cells
#: come in spec-off/spec-on pairs over the SAME request stream; the
#: steady greedy pairs carry the committed tokens-per-step gate.
def cell_matrix(full: bool):
    base = [
        ("ctx128_steady_greedy",
         dict(context=128, new_tokens=16, arrival="steady",
              sampling="greedy", kv8=False, churn=False), True),
        ("ctx128_burst_greedy",
         dict(context=128, new_tokens=16, arrival="burst",
              sampling="greedy", kv8=False, churn=False), False),
        ("ctx128_steady_mixed",
         dict(context=128, new_tokens=16, arrival="steady",
              sampling="mixed", kv8=False, churn=False), False),
        ("ctx128_burst_churn",
         dict(context=128, new_tokens=16, arrival="burst",
              sampling="greedy", kv8=False, churn=True,
              num_slots=3), False),
        # the prefix-sharing columns: multi-turn chat (turn 2 reuses
        # the whole turn-1 history through the content index) and a
        # common-system-prompt burst (every request shares a
        # block-aligned prefix) — each carries its cell-level
        # prefix_hit_rate, schema-validated against its own counts
        ("ctx128_multiturn_chat",
         dict(context=128, new_tokens=16, arrival="steady",
              sampling="greedy", kv8=False, churn=False,
              chat=True), False),
        ("ctx128_burst_sysprompt",
         dict(context=128, new_tokens=16, arrival="burst",
              sampling="greedy", kv8=False, churn=False,
              sysprompt=True), False),
        ("ctx512_steady_greedy",
         dict(context=512, new_tokens=16, arrival="steady",
              sampling="greedy", kv8=False, churn=False), True),
        ("ctx512_steady_kv8",
         dict(context=512, new_tokens=16, arrival="steady",
              sampling="greedy", kv8=True, churn=False), False),
        ("ctx2048_steady_greedy",
         dict(context=2048, new_tokens=8, arrival="steady",
              sampling="greedy", kv8=False, churn=False), True),
    ]
    if full:
        # the 32k cell: the slow lane (minutes of chunked prefill on
        # CPU); bigger blocks keep the page table sane at this reach
        base.append(("ctx32k_steady_greedy",
                     dict(context=32768, new_tokens=4, arrival="steady",
                          sampling="greedy", kv8=False, churn=False,
                          block_size=64, n_requests=1, num_slots=1),
                     False))
    return base


def sweep(tiny: bool, full: bool, spec_k: int, verbose: bool = True):
    """Run the whole matrix; returns ``(cells, ab)`` for the
    artifact."""
    from apex_tpu.serve import truncated_draft

    cfg, params, ids = trained_model(tiny)
    draft = truncated_draft(params, cfg, max(1, cfg.num_layers - 1))
    cells, ab = {}, []
    for name, knobs, gated in cell_matrix(full):
        knobs = dict(knobs)
        num_slots = knobs.pop("num_slots", 2)
        n_requests = knobs.pop("n_requests", None)
        block_size = knobs.pop("block_size", 4)
        sysprompt = knobs.pop("sysprompt", False)
        chat = knobs.pop("chat", False)
        # churn cells run half-context requests (the pool is sized to
        # cover exactly two of their footprints — see run_cell); chat
        # cells too, so turn 2 (history + reply + next turn) still
        # fits the per-slot footprint; config.context stays the
        # cell's context CAPACITY
        req_ctx = knobs["context"] // 2 if (knobs["churn"] or chat) \
            else knobs["context"]
        reqs = _requests(ids, req_ctx, knobs["new_tokens"],
                         n_requests or 2 * num_slots, knobs["sampling"],
                         shared_system=sysprompt,
                         block_size=block_size)
        pair = {}
        for spec in (False, True):
            cell_name = f"{name}_spec" if spec else name
            t0 = time.perf_counter()
            rec = run_cell(cfg, params, draft, list(reqs),
                           num_slots=num_slots, block_size=block_size,
                           spec=spec, spec_k=spec_k, chat=chat,
                           **knobs)
            cells[cell_name] = rec
            pair[spec] = (cell_name, rec)
            if verbose:
                print(f"  {cell_name}: tok/step "
                      f"{rec['tokens_per_step']} p50 {rec['p50_ms']}ms "
                      f"p99 {rec['p99_ms']}ms ok={rec['gate']['ok']} "
                      f"({time.perf_counter() - t0:.1f}s)",
                      file=sys.stderr)
        on_name, on = pair[True]
        off_name, off = pair[False]
        ab.append({
            "on": on_name, "off": off_name,
            "tokens_per_step_on": on["tokens_per_step"],
            "tokens_per_step_off": off["tokens_per_step"],
            "spec_wins": bool(on["tokens_per_step"]
                              > off["tokens_per_step"]),
            "gated": bool(gated),
        })
    return cfg, cells, ab


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-json", default=None,
                    metavar="SCENARIO_rN.json",
                    help="write the committed gate artifact")
    ap.add_argument("--cpu-smoke", action="store_true",
                    help="gpt_tiny trained in-process (the committed-"
                         "r01 shape); default gpt_small_tpu")
    ap.add_argument("--full", action="store_true",
                    help="add the 32k-context cell (slow)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft proposals per speculation round")
    opts = ap.parse_args(argv)

    cfg, cells, ab = sweep(opts.cpu_smoke, opts.full, opts.spec_k)
    cells_ok = all(c["gate"]["ok"] for c in cells.values())
    gated = [r["spec_wins"] for r in ab if r["gated"]]
    ab_ok = bool(gated) and all(gated)
    # fleet-level SLO verdict: every cell's objective block clean
    slo_ok = all(c.get("slo", {}).get("ok", True)
                 for c in cells.values())
    doc = {
        "slo": {"decode_p99_budget_s": SLO_DECODE_P99_S,
                "min_acceptance": SLO_MIN_ACCEPTANCE,
                "ok": bool(slo_ok)},
        "round": 0,
        "platform": jax.devices()[0].platform,
        "model": "gpt_tiny" if opts.cpu_smoke else "gpt_small_tpu",
        "gate_k": GATE_K,
        "cells": cells,
        "ab": ab,
        "gate": {"cells_ok": bool(cells_ok), "ab_ok": bool(ab_ok),
                 "ok": bool(cells_ok and ab_ok)},
        "note": (
            "CPU smoke: wall-clock latencies are host-core numbers; "
            "what the cells pin structurally is the tail bound (no "
            "mid-serve retrace/host-sync), retraces==1 across every "
            "arrival/sampling/churn/kv8/spec combination, and the "
            "spec-vs-baseline tokens-per-decode-step win at equal "
            "work.  The chip round re-runs the same matrix at "
            "gpt_small_tpu scale."
            if jax.devices()[0].platform == "cpu" else
            "on-chip scenario matrix at serving scale"),
    }
    if opts.emit_json:
        m = re.search(r"_r(\d+)\.json$", os.path.basename(opts.emit_json))
        doc["round"] = int(m.group(1)) if m else 0
        from apex_tpu.analysis.scenario import validate_scenario
        problems = validate_scenario(doc)
        if problems:
            print(f"serve_scenarios: REFUSING schema-invalid artifact: "
                  f"{problems}", file=sys.stderr)
            return 1
        with open(opts.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"scenario artifact written: {opts.emit_json} "
              f"({len(cells)} cells)", file=sys.stderr)
    print(json.dumps(doc))
    return 0 if doc["gate"]["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
