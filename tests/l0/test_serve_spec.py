"""Speculative decoding in the serve engine (:mod:`apex_tpu.serve.spec`).

The acceptance contracts: (a) a spec-enabled mixed greedy stream —
including through a preemption and under the int8 KV cache — produces
outputs BITWISE equal to solo :func:`apex_tpu.models.generate.generate`
with measured acceptance > 0 and exactly ONE trace each for the draft
and verify steps; (b) sampled streams are bitwise equal to the
NON-speculative engine (the key-ladder verification draws exactly the
draws the baseline step would have made); (c) the per-slot PRNG chain
still advances one draw per EMITTED token under partial accepts
(``j < k``), so :func:`~apex_tpu.serve.sampling.advance_key` by draw
count — the router's replica-kill recovery — reconstructs the exact
key a spec-enabled slot holds; (d) the verify step carries no host
callback or retrace hazard (the graph-lint ``serve_verify`` lane's
runtime half).

The model is BRIEFLY TRAINED (the PR 8 pattern): a random-init model's
near-uniform logits put quantization/ulp noise above the argmax
margins, which tests tie-breaking rather than the speculation
machinery, and makes acceptance rates meaninglessly low.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, analysis
from apex_tpu.models import GPTModel, gpt_tiny
from apex_tpu.models.generate import generate
from apex_tpu.obs.metrics import Registry
from apex_tpu.optimizers import FusedAdam
from apex_tpu.serve import (
    Request,
    ServeConfig,
    ServeEngine,
    SpecConfig,
    SpecEngine,
    advance_key,
    truncated_draft,
)


@pytest.fixture(scope="module")
def setup():
    """Briefly-trained gpt_tiny in the bf16 serving layout + mixed
    prompts drawn from its training distribution (real argmax margins,
    prompts the truncated draft can actually predict) — the ONE
    shared recipe, :func:`apex_tpu.models.gpt.train_toy_lm`."""
    from apex_tpu.models.gpt import train_toy_lm

    cfg, params, ids = train_toy_lm()
    prompts = [np.asarray(ids[i % 8, s:s + n], np.int32)
               for i, (s, n) in enumerate(
                   ((0, 5), (3, 12), (7, 3), (1, 20), (4, 9)))]
    return cfg, params, prompts


SCFG = ServeConfig(num_slots=2, block_size=4, num_blocks=17,
                   max_blocks_per_slot=8, prefill_chunk=4)


def _solo(params, cfg, prompt, n, kv_dtype=None):
    out = generate(params, cfg, jnp.asarray(prompt[None]), n,
                   kv_dtype=kv_dtype)
    return np.asarray(out)[0, len(prompt):]


@pytest.fixture(scope="module")
def engine(setup):
    """ONE spec engine (truncated layer-skip draft, k=3) shared by the
    greedy stream tests — every extra engine is four more XLA compiles
    (draft, verify, two prefills), and sharing makes the one-trace
    pins span the whole module's request history."""
    cfg, params, _ = setup
    dp, dcfg = truncated_draft(params, cfg, cfg.num_layers - 1)
    return SpecEngine(params, cfg, SCFG, dp, dcfg, SpecConfig(k=3),
                      registry=Registry())


def test_spec_mixed_stream_matches_solo_bitwise(setup, engine):
    """THE speculative-decoding gate: 5 mixed-length greedy requests
    through 2 slots with a truncated draft proposing 3 tokens per
    round — every output bitwise equal to its solo generate() run,
    measured acceptance rate > 0 (the draft is the target's own first
    layer, so it predicts the trained distribution), and ONE trace +
    one executable each for the draft and verify programs across the
    whole stream."""
    cfg, params, prompts = setup
    eng = engine
    news = (8, 6, 10, 4, 7)
    for i, (p, n) in enumerate(zip(prompts, news)):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
    out = eng.run()
    for i, (p, n) in enumerate(zip(prompts, news)):
        np.testing.assert_array_equal(
            out[f"r{i}"], _solo(params, cfg, p, n),
            err_msg=f"r{i} diverged from solo through speculation")
    assert eng.trace_counts["draft"] == 1
    assert eng.trace_counts["verify"] == 1
    assert eng.trace_counts["decode"] == 0      # never dispatched
    assert eng._draft_step._cache_size() == 1
    assert eng._verify_step._cache_size() == 1
    m = eng.metrics
    assert m.counter("serve_spec_rounds_total").value > 0
    proposed = m.counter("serve_spec_proposed_total").value
    accepted = m.counter("serve_spec_accepted_total").value
    assert proposed > 0 and accepted > 0
    rate = m.gauge("serve_spec_acceptance_rate").value
    assert rate == pytest.approx(accepted / proposed)
    # speculation must BEAT one-token-per-step: emitted decode tokens
    # per verify round strictly above 1 per active slot on average
    decode_tokens = m.counter("serve_tokens_total").value - 5
    rounds = m.counter("serve_spec_rounds_total").value
    assert decode_tokens > rounds, (
        f"{decode_tokens} tokens over {rounds} rounds: speculation "
        f"accepted nothing a plain engine wouldn't have emitted")


def test_spec_through_preemption_matches_solo(setup):
    """Block pressure preempts the youngest request mid-speculation
    (recompute-on-resume rebuilds BOTH the target and draft caches);
    every output — the evicted one included — still bitwise-matches
    solo."""
    cfg, params, prompts = setup
    scfg = ServeConfig(num_slots=3, block_size=4, num_blocks=9,
                       max_blocks_per_slot=8, prefill_chunk=4)
    dp, dcfg = truncated_draft(params, cfg, cfg.num_layers - 1)
    eng = SpecEngine(params, cfg, scfg, dp, dcfg, SpecConfig(k=3),
                     registry=Registry())
    reqs = [(prompts[1][:8], 8), (prompts[3][:8], 8), (prompts[4][:6], 6)]
    for i, (p, n) in enumerate(reqs):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
    out = eng.run()
    assert eng.metrics.counter("serve_preemptions_total").value == 1
    for i, (p, n) in enumerate(reqs):
        np.testing.assert_array_equal(
            out[f"r{i}"], _solo(params, cfg, p, n),
            err_msg=f"r{i} diverged from solo through preemption")
    assert eng.trace_counts["verify"] == 1
    assert eng.sched.allocator.live_count == 0


@pytest.mark.slow
def test_spec_kv8_matches_solo_and_baseline(setup):
    """Speculation under the int8 KV cache: the verify step's
    quantize-on-write/fused-dequant path produces greedy streams
    bitwise equal to solo ``generate(kv_dtype="int8")`` AND to the
    non-speculative int8 engine (speculation adds zero drift on top
    of the quantization regime)."""
    cfg, params, prompts = setup
    scfg = dataclasses.replace(SCFG, kv_dtype="int8")
    dp, dcfg = truncated_draft(params, cfg, cfg.num_layers - 1)
    eng = SpecEngine(params, cfg, scfg, dp, dcfg, SpecConfig(k=3),
                     registry=Registry())
    base = ServeEngine(params, cfg, scfg, registry=Registry())
    news = (6, 8, 5)
    for i, (p, n) in enumerate(zip(prompts[:3], news)):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
        base.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
    out, outb = eng.run(), base.run()
    for i, (p, n) in enumerate(zip(prompts[:3], news)):
        np.testing.assert_array_equal(
            out[f"r{i}"], _solo(params, cfg, p, n, kv_dtype="int8"),
            err_msg=f"r{i}: spec+kv8 diverged from solo int8")
        np.testing.assert_array_equal(
            out[f"r{i}"], outb[f"r{i}"],
            err_msg=f"r{i}: spec+kv8 diverged from the baseline "
                    f"int8 engine")
    assert eng.metrics.counter("serve_spec_accepted_total").value > 0


@pytest.mark.slow
def test_spec_sampled_streams_match_baseline_engine(setup):
    """Sampled slots: the verifier draws with the slot's key ladder
    through the same fused epilogue, so a spec-enabled sampled stream
    is bitwise the NON-spec engine's stream — the strong form of the
    distribution-exactness argument (the output IS the target's
    stream, not merely distributed like it)."""
    cfg, params, prompts = setup
    dp, dcfg = truncated_draft(params, cfg, cfg.num_layers - 1)
    eng = SpecEngine(params, cfg, SCFG, dp, dcfg, SpecConfig(k=3),
                     registry=Registry())
    base = ServeEngine(params, cfg, SCFG, registry=Registry())
    for e in (eng, base):
        e.submit(Request(uid="s", prompt=prompts[0], max_new_tokens=8,
                         temperature=0.8, top_k=12, seed=7))
        e.submit(Request(uid="g", prompt=prompts[2], max_new_tokens=6))
    out, outb = eng.run(), base.run()
    np.testing.assert_array_equal(out["s"], outb["s"])
    np.testing.assert_array_equal(out["g"], outb["g"])


@pytest.mark.slow
def test_advance_key_chain_identity_under_partial_accepts(setup):
    """Satellite: the draw-count chain under speculative drafts.  A
    spec round emits 1..k+1 tokens, but the slot's PRNG chain must
    advance EXACTLY one draw per emitted token — so after any prefix
    of the stream, ``advance_key(PRNGKey(seed), draws)`` (the
    router's replica-kill reconstruction,
    ``DisaggRouter.kill_replica``) equals the key the slot actually
    holds.  Checked at EVERY step boundary of a sampled stream whose
    rounds include partial accepts (0 < j < k) — the case where a
    mis-specified ladder index would silently skip or replay
    draws."""
    cfg, params, prompts = setup
    dp, dcfg = truncated_draft(params, cfg, cfg.num_layers - 1)
    eng = SpecEngine(params, cfg, SCFG, dp, dcfg, SpecConfig(k=3),
                     registry=Registry())
    eng.submit(Request(uid="s", prompt=prompts[1], max_new_tokens=12,
                       temperature=0.7, top_k=20, seed=11))
    eng._admit_and_evict()
    slot = next(i for i in range(eng.sched.num_slots)
                if eng.sched.slots[i] is not None)
    emit_counts = []
    while eng.sched.slots[slot] is not None:
        before = len(eng.sched.slots[slot].emitted)
        eng.step()
        s = eng.sched.slots[slot]
        if s is None:
            break
        emit_counts.append(len(s.emitted) - before)
        draws = len(s.request.prior_tokens) + len(s.emitted)
        want = np.asarray(advance_key(jax.random.PRNGKey(11), draws))
        got = np.asarray(eng.carry["keys"][slot])
        np.testing.assert_array_equal(
            got, want,
            err_msg=f"after {draws} draws (round emitted "
                    f"{emit_counts[-1]}): slot key is not the "
                    f"draw-count chain — kill_replica recovery would "
                    f"resume the wrong PRNG state")
    # the interesting regime actually happened: at least one round
    # emitted more than the baseline 1 token (an accept), and the
    # rounds were not uniformly full accepts either
    assert any(c > 1 for c in emit_counts), (
        f"no round accepted anything ({emit_counts}); the chain "
        f"identity was only checked at the trivial j=0 point")


@pytest.mark.slow
def test_full_reach_requests_do_not_wrap_writes(setup):
    """Review-found corruption class: a request whose footprint fills
    the ENTIRE slot reach (prompt + budget == max_blocks_per_slot x
    block_size) decodes to its very last token with the verify step's
    trailing rows at positions past the reach.  Unmasked, their
    clip+modulo write coordinates WRAP onto live early positions —
    silently corrupting history the emitted rows attend to in the
    same dispatch (writes land before the gather).  A low-acceptance
    draft maximizes the exposure (lengths advance by 1, so rounds
    straddle the boundary); outputs must stay bitwise solo.  The
    draft cache-fill step shares the same masking (it writes up to
    ``L + k``)."""
    cfg, params, prompts = setup
    # 16-token prompt + 8-token budget == 6 blocks x 4 exactly
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=13,
                       max_blocks_per_slot=6, prefill_chunk=4)
    # a deliberately WRONG draft (random init): acceptance ~0
    from apex_tpu.models import GPTModel
    import apex_tpu.amp as amp_mod
    bad = GPTModel(cfg).init(jax.random.PRNGKey(99),
                             jnp.zeros((1, 4), jnp.int32))["params"]
    bad = amp_mod.initialize(
        opt_level="O2", verbosity=0).model_params_from(bad)
    eng = SpecEngine(params, cfg, scfg, bad, cfg, SpecConfig(k=3),
                     registry=Registry())
    rng = np.random.RandomState(3)
    cases = [rng.randint(0, cfg.vocab_size, (16,)) for _ in range(4)]
    for i, p in enumerate(cases):
        eng.submit(Request(uid=f"w{i}", prompt=p, max_new_tokens=8))
    out = eng.run()
    for i, p in enumerate(cases):
        np.testing.assert_array_equal(
            out[f"w{i}"], _solo(params, cfg, p, 8),
            err_msg=f"w{i}: end-of-reach verify rows wrapped their "
                    f"writes onto live positions")


def test_spec_config_and_draft_validation(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="k="):
        SpecConfig(k=0)
    with pytest.raises(ValueError, match="num_layers"):
        truncated_draft(params, cfg, cfg.num_layers)
    with pytest.raises(ValueError, match="vocab"):
        bad_cfg = dataclasses.replace(cfg, vocab_size=cfg.vocab_size + 1)
        SpecEngine(params, cfg, SCFG, params, bad_cfg,
                   registry=Registry())


@pytest.mark.slow
def test_spec_profiler_partitions_latency_histograms(setup, engine):
    """The continuous-profiler contract holds on the SPECULATIVE
    engine too: attaching a profiler drives real capture windows, a
    captured round's latency lands in serve_profiled_step_seconds
    (never the gated histogram the SLO/latency gates judge), and the
    two partitions cover every dispatched round exactly.  The
    classifier builds from the VERIFY program — the target's
    per-round work."""
    from apex_tpu.obs import contprof

    cfg, params, prompts = setup
    eng = engine
    reg = eng.metrics
    gated_before = reg.histogram("serve_decode_step_seconds").count
    prof = contprof.serve_profiler(
        eng, config=contprof.ContProfConfig(
            capture_every=3, capture_steps=2, warmup_steps=1,
            max_windows=1, max_overhead_pct=None))
    try:
        rounds_before = eng._steps_dispatched
        for i, p in enumerate(prompts[:2]):
            eng.submit(Request(uid=f"prof{i}", prompt=p,
                               max_new_tokens=16))
        eng.run()
        rounds = eng._steps_dispatched - rounds_before
        gated = reg.histogram("serve_decode_step_seconds").count \
            - gated_before
        profiled = reg.histogram("serve_profiled_step_seconds").count
        captured = sum(w["steps"] for w in prof.windows) \
            + sum(w["steps"] for w in prof.discarded)
        assert len(prof.windows) + len(prof.discarded) == 1
        assert profiled == captured == 2
        assert gated + profiled == rounds
        for w in prof.windows:
            assert w["total_ps"] > 0
    finally:
        eng.profiler = None


def test_verify_step_has_no_host_sync_or_retrace_hazard(setup, engine):
    """The syncs pass over the ACTUAL lowered b×(k+1) verify step: no
    host callback, no statically-bound numeric scalar (the runtime
    half is the one-trace pin above; the full pass matrix runs in the
    graph-lint ``serve_verify`` lane)."""
    eng = engine
    s = eng.sched
    k = eng.spec.k
    lowered = eng._verify_step.lower(
        eng.top, eng.stacked, eng.carry,
        jnp.zeros((s.num_slots, k), jnp.int32),
        jnp.asarray(s.last_tok), jnp.asarray(s.lengths),
        jnp.asarray(s.active), jnp.asarray(s.page_table),
        jnp.asarray(s.temperature), jnp.asarray(s.top_k),
        jnp.asarray(s.top_p))
    ctx = analysis.build_context(lowered, compile=True)
    rep = analysis.run_passes(ctx, passes=("syncs", "donation"))
    assert rep.ok, rep.format()
    assert not [f for f in rep.by_pass("syncs")
                if f.op in ("host-callback", "static-scalar")], \
        rep.format()
