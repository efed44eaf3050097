"""Continuous-batching serve engine (:mod:`apex_tpu.serve`).

The acceptance contracts: (a) a mixed stream of short/long requests
completes through continuous batching with per-request outputs
bitwise-equal to solo :func:`apex_tpu.models.generate.generate` runs;
(b) admission/retirement/preemption across the whole stream never
changes a compiled-step shape — ONE trace and one executable serve
everything (the runtime side of the static-shape contract; the
graph-lint serve lane checks it statically); (c) the fused sampling
epilogue draws on device with per-slot knobs that never retrace.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, analysis
from apex_tpu.models import GPTModel, gpt_tiny
from apex_tpu.models.generate import generate
from apex_tpu.obs.metrics import Registry
from apex_tpu.serve import Request, ServeConfig, ServeEngine
from apex_tpu.serve.sampling import sample_tokens


@pytest.fixture(scope="module")
def setup():
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)      # bf16 serving layout
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,))
               for n in (5, 12, 3, 20, 9)]
    return cfg, params, prompts


@pytest.fixture(scope="module")
def engine(setup):
    """ONE engine shared by the stream tests (tier-1 budget: each
    ServeEngine re-jits its closures, so every extra instance is a
    fresh XLA compile) — sharing it also makes the one-trace
    assertions cover the whole module's request history."""
    cfg, params, _ = setup
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=17,
                       max_blocks_per_slot=8, prefill_chunk=4)
    # a private registry: the metric assertions below count THIS
    # engine's scripted history, not whatever else the process served
    return ServeEngine(params, cfg, scfg, registry=Registry())


def _solo(params, cfg, prompt, n):
    out = generate(params, cfg, jnp.asarray(prompt[None]), n)
    return np.asarray(out)[0, len(prompt):]


def test_mixed_stream_matches_solo_and_never_retraces(setup, engine):
    """The tier-1 serve smoke: admit 5 requests of mixed lengths into
    2 slots (continuous batching over a paged cache, greedy), outputs
    bitwise-equal to solo generate() per request, ONE decode trace and
    one compiled executable across every admit/retire boundary."""
    cfg, params, prompts = setup
    eng = engine
    news = (8, 6, 10, 4, 7)
    for i, (p, n) in enumerate(zip(prompts, news)):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
    out = eng.run()
    for i, (p, n) in enumerate(zip(prompts, news)):
        want = _solo(params, cfg, p, n)
        np.testing.assert_array_equal(out[f"r{i}"], want,
                                      err_msg=f"r{i} diverged from solo")
    # the static-shape contract, at runtime: one python-body execution
    # per program AND one compiled entry in the jit cache
    assert eng.trace_counts == {"decode": 1, "prefill": 1, "sample1": 1}
    assert eng._decode_step._cache_size() == 1
    assert eng._prefill_chunk._cache_size() == 1
    # telemetry (apex_tpu.obs): the counters match the scripted
    # stream — 5 admissions, 5 retirements, no preemption, every
    # generated token counted, and the decode-step histogram observed
    # every step (this is the histogram p50/p99 are read from)
    m = eng.metrics
    assert m.counter("serve_admissions_total").value == 5
    assert m.counter("serve_retirements_total").value == 5
    assert m.counter("serve_preemptions_total").value == 0
    assert m.counter("serve_tokens_total").value == sum(news)
    h = m.histogram("serve_decode_step_seconds")
    assert h.count > 0 and h.quantile(0.5) > 0
    # drained: gauges back to idle
    assert m.gauge("serve_queue_depth").value == 0
    assert m.gauge("serve_slot_occupancy").value == 0
    assert m.gauge("serve_block_utilization").value == 0


def test_decode_step_has_no_host_sync_or_retrace_hazard(setup):
    """The syncs pass (analysis/syncs.py retrace machinery) over the
    engine's ACTUAL lowered decode step: no host callback on the token
    loop, no statically-bound numeric scalar that would retrace."""
    cfg, params, prompts = setup
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=9,
                       max_blocks_per_slot=4, prefill_chunk=4)
    eng = ServeEngine(params, cfg, scfg)
    s = eng.sched
    lowered = eng._decode_step.lower(
        eng.top, eng.stacked, eng.carry,
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


def test_preemption_recompute_preserves_outputs(setup):
    """Block pressure with a free slot preempts the youngest request
    (recompute-on-resume); every request — including the evicted one —
    still matches its solo run, and eviction fires exactly once (a
    continuation never evicts its evictor back)."""
    cfg, params, prompts = setup
    scfg = ServeConfig(num_slots=3, block_size=4, num_blocks=9,
                       max_blocks_per_slot=8, prefill_chunk=4)
    eng = ServeEngine(params, cfg, scfg, registry=Registry())
    preempts = []
    orig = eng.sched.preempt
    eng.sched.preempt = lambda slot, key: (preempts.append(slot),
                                           orig(slot, key))[1]
    reqs = [(prompts[0][:8], 8), (prompts[1][:8], 8), (prompts[3][:6], 6)]
    for i, (p, n) in enumerate(reqs):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
    out = eng.run()
    assert len(preempts) == 1
    for i, (p, n) in enumerate(reqs):
        np.testing.assert_array_equal(out[f"r{i}"],
                                      _solo(params, cfg, p, n))
    # pool bookkeeping drained clean
    assert eng.sched.allocator.live_count == 0
    # telemetry: 3 fresh admissions + 1 continuation re-admission,
    # exactly one preemption, 3 retirements (the preempted request
    # retires once, under its own uid)
    m = eng.metrics
    assert m.counter("serve_admissions_total").value == 4
    assert m.counter("serve_preemptions_total").value == 1
    assert m.counter("serve_retirements_total").value == 3


def test_submit_validation():
    """Scheduler-level admission validation needs no engine (and no
    jax): context overflow, empty prompt, zero budget, over-pool
    footprint."""
    from apex_tpu.serve import SlotScheduler
    sched = SlotScheduler(num_slots=2, num_blocks=9, block_size=4,
                          max_blocks_per_slot=4)          # context 16
    with pytest.raises(ValueError, match="context"):
        sched.submit(Request(uid="big",
                             prompt=np.zeros(20, np.int32),
                             max_new_tokens=8))           # 20 + 8 > 16
    with pytest.raises(ValueError, match="non-empty"):
        sched.submit(Request(uid="empty",
                             prompt=np.zeros(0, np.int32),
                             max_new_tokens=4))
    with pytest.raises(ValueError, match="non-empty"):
        sched.submit(Request(uid="zero",
                             prompt=np.zeros(4, np.int32),
                             max_new_tokens=0))


def test_one_token_budget_finishes_on_prefill(setup, engine):
    cfg, params, prompts = setup
    engine.submit(Request(uid="one", prompt=prompts[0],
                          max_new_tokens=1))
    out = engine.run()
    np.testing.assert_array_equal(out["one"],
                                  _solo(params, cfg, prompts[0], 1))


def test_sampling_seeded_per_request_and_knobs_do_not_retrace(setup,
                                                              engine):
    """Per-request PRNG chains: same seed → identical stream even with
    different batch-mates; different seed → different stream; greedy
    and sampling slots share the one compiled step (trace count still
    1 across the whole module's greedy AND sampling history)."""
    cfg, params, prompts = setup
    for uid, seed, temp in (("a", 7, 1.0), ("b", 7, 1.0),
                            ("c", 8, 1.0), ("g", 0, 0.0)):
        engine.submit(Request(uid=uid, prompt=prompts[0],
                              max_new_tokens=8, temperature=temp,
                              top_k=50, top_p=0.9, seed=seed))
    out = engine.run()
    np.testing.assert_array_equal(out["a"], out["b"])
    assert not np.array_equal(out["a"], out["c"])
    np.testing.assert_array_equal(out["g"],
                                  _solo(params, cfg, prompts[0], 8))
    assert engine.trace_counts["decode"] == 1   # knob mix never retraced


# ---------------------------------------------------------------------------
# fused sampling epilogue as a pure function
# ---------------------------------------------------------------------------

def test_sample_tokens_greedy_and_topk1_agree():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((3, 32)), jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
    greedy, _ = sample_tokens(logits, keys,
                              jnp.zeros(3), jnp.zeros(3, jnp.int32),
                              jnp.ones(3))
    np.testing.assert_array_equal(np.asarray(greedy),
                                  np.argmax(np.asarray(logits), -1))
    # top_k=1 at any temperature can only emit the argmax
    k1, _ = sample_tokens(logits, keys, jnp.full(3, 2.0),
                          jnp.ones(3, jnp.int32), jnp.ones(3))
    np.testing.assert_array_equal(np.asarray(k1),
                                  np.argmax(np.asarray(logits), -1))


def test_sample_tokens_topk_topp_restrict_support():
    """With top_k=3 every draw lands in the 3 highest logits; with a
    tiny top_p only the head of the distribution survives."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((1, 64)), jnp.float32)
    top3 = set(np.argsort(-np.asarray(logits[0]))[:3].tolist())
    key = jax.random.PRNGKey(0)[None]
    seen = set()
    for i in range(50):
        tok, key = sample_tokens(logits, key, jnp.full(1, 1.5),
                                 jnp.full(1, 3, jnp.int32),
                                 jnp.ones(1))
        seen.add(int(tok[0]))
    assert seen <= top3 and len(seen) > 1
    # top_p ~ 0: only the single most-probable token survives
    tok, _ = sample_tokens(logits, jax.random.PRNGKey(9)[None],
                           jnp.full(1, 2.0), jnp.zeros(1, jnp.int32),
                           jnp.full(1, 1e-6))
    assert int(tok[0]) == int(np.argmax(np.asarray(logits)))


def test_sample_tokens_chains_keys():
    logits = jnp.zeros((2, 16), jnp.float32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2, dtype=jnp.uint32))
    _, k1 = sample_tokens(logits, keys, jnp.ones(2),
                          jnp.zeros(2, jnp.int32), jnp.ones(2))
    assert not np.array_equal(np.asarray(keys), np.asarray(k1))
