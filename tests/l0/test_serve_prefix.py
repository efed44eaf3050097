"""Cross-request prefix-cache KV sharing (serve/paged refcounted
content-addressed blocks + scheduler/engine/router admission).

The acceptance contracts: (a) allocator refcount/CoW invariants —
double free, share-then-evict, and fork-under-share all REFUSED, LRU
eviction order over refcount==0 only; (b) shared-prefix mixed streams
bitwise-equal to solo :func:`apex_tpu.models.generate.generate` —
greedy, sampled, and int8 KV, including through a preemption, a
copy-on-write fork of a fully-matched prompt, and a multi-turn
history reuse; (c) sharing actually SAVES work: fewer prefill chunks
dispatched than the sharing-off arm on the same stream; (d) the
disaggregated router admits prefix-hit requests straight to a decode
replica (no shipment) and the kill-busiest-replica chaos drill stays
bitwise under sharing; (e) the one-trace contract is untouched
(``trace_counts`` pins exactly as before; the CoW fork has its own
single-trace counter); (f) the V-side convert candidate from PR 6 is
resolved by a pin (structurally blocked at jax 0.4.37).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.models import GPTModel, gpt_tiny
from apex_tpu.models.generate import generate
from apex_tpu.obs.metrics import Registry
from apex_tpu.serve import (
    DisaggRouter,
    Request,
    RouterConfig,
    ServeConfig,
    ServeEngine,
)
from apex_tpu.serve.paged import (
    TRASH_BLOCK,
    BlockAllocator,
    PoolExhausted,
    chain_seed,
    chain_step,
    prefix_block_hashes,
)


# ---------------------------------------------------------------------------
# allocator property tests (no jax, no model)
# ---------------------------------------------------------------------------

def _h(i):
    return chain_step(chain_seed(4), [i, i, i, i])


def test_chain_hashes_cover_history_and_block_size():
    """Block identity is the CHAIN: equal token runs at different
    positions (or under different block sizes) never alias."""
    hs = prefix_block_hashes(list(range(8)), 4)
    assert len(hs) == 2                      # full blocks only
    assert prefix_block_hashes(list(range(7)), 4) == hs[:1]
    # same 4 tokens at positions 4..7 vs 0..3: different chain hash
    assert prefix_block_hashes([4, 5, 6, 7], 4)[0] != hs[1]
    # block-size is part of the seed
    assert prefix_block_hashes(list(range(8)), 8)[0] not in hs
    assert hs[0] == chain_step(chain_seed(4), [0, 1, 2, 3])
    assert hs[1] == chain_step(hs[0], [4, 5, 6, 7])


def test_allocator_refcount_share_free_invariants():
    a = BlockAllocator(8)                    # 7 usable
    b0 = a.alloc(3, "r0")
    assert TRASH_BLOCK not in b0
    a.register(b0[0], _h(0))
    a.register(b0[1], _h(1))
    # share increfs for another owner; refcount-1 private otherwise
    a.share(b0[0], "r1")
    assert a.refcount(b0[0]) == 2 and a.shared_count == 1
    with pytest.raises(ValueError, match="already held"):
        a.share(b0[0], "r1")                 # double-hold refused
    with pytest.raises(ValueError, match="not registered"):
        a.share(b0[2], "r1")                 # private blocks never share
    # r0's free decrefs; the block survives for r1
    a.free(b0, "r0")
    assert a.refcount(b0[0]) == 1
    with pytest.raises(ValueError, match="double free|not owned"):
        a.free([b0[0]], "r0")                # r0 no longer holds it
    # r1's free drops the last ref: registered -> cached, not free
    a.free([b0[0]], "r1")
    assert a.cached_count == 2 and a.refcount(b0[0]) == 0
    assert a.lookup(_h(0)) == b0[0]          # still matchable
    # the accounting invariant holds at every point
    assert a.free_count + a.live_count + a.cached_count == 7


def test_allocator_share_then_evict_refused():
    """A SHARED (live) block is never reclaimed: alloc raises
    PoolExhausted rather than stealing it — only refcount-0 cached
    blocks are eviction candidates."""
    a = BlockAllocator(4)                    # 3 usable
    blocks = a.alloc(3, "r0")
    for i, b in enumerate(blocks):
        a.register(b, _h(i))
    a.share(blocks[0], "r1")
    a.free(blocks, "r0")                     # b0 still live via r1
    assert a.cached_count == 2 and a.live_count == 1
    assert a.reclaimable_count == 2
    with pytest.raises(PoolExhausted):
        a.alloc(3, "r2")                     # would need the shared one
    # and the refusal reclaimed nothing
    assert a.cached_count == 2 and a.lookup(_h(0)) == blocks[0]


def test_allocator_fork_under_share_refused():
    """assert_writable refuses shared AND registered blocks — a write
    needs a private unregistered block (the copy-on-write rule)."""
    a = BlockAllocator(8)
    b = a.alloc(2, "r0")
    a.assert_writable(b[1], "r0")            # private: fine
    a.register(b[0], _h(0))
    with pytest.raises(ValueError, match="registered"):
        a.assert_writable(b[0], "r0")        # immutable once indexed
    a.share(b[0], "r1")
    with pytest.raises(ValueError, match="shared|registered"):
        a.assert_writable(b[0], "r1")
    with pytest.raises(ValueError, match="cannot write"):
        a.assert_writable(b[1], "r1")        # not the holder


def test_allocator_lru_reclaim_order_and_register_conflicts():
    a = BlockAllocator(5)                    # 4 usable
    blocks = a.alloc(4, "r0")
    for i, b in enumerate(blocks):
        a.register(b, _h(i))
    # free order defines LRU: blocks[2] parks first -> evicts first
    a.free([blocks[2]], "r0")
    a.free([blocks[0]], "r0")
    a.free([blocks[1]], "r0")
    got = a.alloc(2, "r1")
    assert got == [blocks[2], blocks[0]]     # least-recently-freed first
    assert a.cached_evictions == 2
    assert a.lookup(_h(2)) is None           # registration gone
    assert a.lookup(_h(1)) == blocks[1]      # survivor still indexed
    # register conflicts: same hash on another block -> False (first
    # registration canonical); same block, different hash -> raises
    assert a.register(got[0], _h(1)) is False
    assert not a.is_registered(got[0])
    assert a.register(got[0], _h(9)) is True
    with pytest.raises(ValueError, match="different chain hash"):
        a.register(got[0], _h(8))
    assert a.register(got[0], _h(9)) is True    # same-hash no-op
    with pytest.raises(ValueError, match="not live"):
        a.register(TRASH_BLOCK, _h(7))
    a.free([blocks[3]], "r0")
    with pytest.raises(ValueError, match="not live"):
        a.register(blocks[3], _h(7))         # register after free


# ---------------------------------------------------------------------------
# engine streams: bitwise parity under sharing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    cfg = gpt_tiny()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)      # bf16 serving layout
    rng = np.random.RandomState(42)
    system = rng.randint(0, cfg.vocab_size, (8,))   # 2 full blocks @ bs=4
    tails = [rng.randint(0, cfg.vocab_size, (n,)) for n in (3, 6, 1, 5)]
    return cfg, params, system, tails


SCFG = ServeConfig(num_slots=2, block_size=4, num_blocks=17,
                   max_blocks_per_slot=8, prefill_chunk=4)


def _solo(params, cfg, prompt, n, **kw):
    out = generate(params, cfg, jnp.asarray(np.asarray(prompt)[None]),
                   n, **kw)
    return np.asarray(out)[0, len(prompt):]


@pytest.mark.slow
def test_shared_system_prompt_stream_bitwise_and_saves_chunks(setup):
    """The tentpole gate: 4 requests sharing one 8-token system prompt
    through 2 slots — every output bitwise-equal to solo generate(),
    prefix hits recorded, STRICTLY fewer prefill chunks than the
    sharing-off arm on the identical stream, and the one-trace
    contract untouched in both arms."""
    cfg, params, system, tails = setup

    def run(prefix_cache):
        import dataclasses
        scfg = dataclasses.replace(SCFG, prefix_cache=prefix_cache)
        eng = ServeEngine(params, cfg, scfg, registry=Registry())
        for i, t in enumerate(tails):
            eng.submit(Request(uid=f"r{i}",
                               prompt=np.concatenate([system, t]),
                               max_new_tokens=6))
        out = eng.run()
        chunks = eng.metrics.counter("serve_prefill_chunks_total").value
        return eng, out, chunks

    eng_on, out_on, chunks_on = run(True)
    eng_off, out_off, chunks_off = run(False)
    for i, t in enumerate(tails):
        p = np.concatenate([system, t])
        want = _solo(params, cfg, p, 6)
        np.testing.assert_array_equal(
            out_on[f"r{i}"], want,
            err_msg=f"r{i} diverged from solo under sharing")
        np.testing.assert_array_equal(out_off[f"r{i}"], want)
    # the perf claim, on the engine's own counters: the shared spans'
    # chunks were never dispatched
    assert chunks_on < chunks_off
    s = eng_on.sched
    assert s.prefix_probes == 4
    assert s.prefix_hits >= 3                # first request seeds
    assert s.prefix_hit_tokens > 0
    eng_on.metrics.flush()
    assert eng_on.metrics.gauge("serve_prefix_hit_rate").value > 0.5
    # drained: nothing shared, nothing live; the hot prefix is CACHED
    # (refcount 0, still matchable), not leaked
    assert s.allocator.live_count == 0
    assert s.allocator.shared_count == 0
    assert s.allocator.cached_count > 0
    assert eng_on.metrics.gauge("serve_prefix_shared_blocks").value == 0
    # trace pins: sharing is host-side page-table construction only
    assert eng_on.trace_counts == {"decode": 1, "prefill": 1,
                                   "sample1": 1}
    assert eng_off.trace_counts == {"decode": 1, "prefill": 1,
                                    "sample1": 1}
    # sharing-off engine has no prefix machinery in its catalog
    assert eng_off.sched._m_hit_rate is None


def test_full_prompt_match_forks_copy_on_write(setup):
    """A FULLY-matched aligned prompt re-dispatches exactly one token:
    the last matched block forks copy-on-write (one device copy, its
    own single trace), the rewrite lands in the private fork, and the
    stream is bitwise-equal to solo — the fork source stays registered
    for the next hit."""
    cfg, params, system, _tails = setup
    eng = ServeEngine(params, cfg, SCFG, registry=Registry())
    # 8 tokens = 2 full blocks at bs=4: an aligned full-match prompt
    eng.submit(Request(uid="a", prompt=system, max_new_tokens=6))
    out_a = eng.run()["a"]
    chunks_before = eng.metrics.counter(
        "serve_prefill_chunks_total").value
    eng.submit(Request(uid="b", prompt=system, max_new_tokens=6))
    out_b = eng.run()["b"]
    want = _solo(params, cfg, system, 6)
    np.testing.assert_array_equal(out_a, want)
    np.testing.assert_array_equal(out_b, want,
                                  err_msg="CoW fork diverged")
    m = eng.metrics
    assert m.counter("serve_prefix_cow_copies_total").value == 1
    # the full match dispatched ONE chunk (the n-1 re-dispatch), not
    # the prompt's two
    assert m.counter("serve_prefill_chunks_total").value \
        == chunks_before + 1
    # the CoW copy is its own executable with its own ONE trace — the
    # pinned trace_counts dict is untouched
    assert eng.cow_trace_count == 1
    assert eng.trace_counts == {"decode": 1, "prefill": 1,
                                "sample1": 1}
    assert eng.sched.allocator.live_count == 0


@pytest.mark.slow
def test_sampled_and_multi_turn_reuse_bitwise(setup):
    """Sampling under sharing stays on the exact per-request PRNG
    chain (pinned against the sharing-off engine, the arm existing
    tests hold bitwise to solo), and a multi-turn follow-up (prompt =
    turn-1 prompt + its generated tokens + new user tokens) matches
    the DECODE-filled blocks the first turn registered at block
    boundaries — the greedy follow-up equals solo generate()."""
    import dataclasses
    cfg, params, system, tails = setup
    p1 = np.concatenate([system, tails[0]])          # 11 tokens

    def turn1(prefix_cache):
        scfg = dataclasses.replace(SCFG, prefix_cache=prefix_cache)
        eng = ServeEngine(params, cfg, scfg, registry=Registry())
        # two sampled same-prefix requests so the ON arm actually
        # shares (the second admission hits the first's blocks)
        eng.submit(Request(uid="s0", prompt=p1, max_new_tokens=8,
                           temperature=0.9, top_k=20, top_p=0.95,
                           seed=11))
        eng.submit(Request(uid="s1", prompt=np.concatenate(
            [system, tails[1]]), max_new_tokens=8, temperature=0.7,
            seed=3))
        return eng, eng.run()

    eng, out_on = turn1(True)
    _eng_off, out_off = turn1(False)
    for uid in ("s0", "s1"):
        np.testing.assert_array_equal(
            out_on[uid], out_off[uid],
            err_msg=f"{uid}: sampled stream diverged under sharing")
    # turn 2 reuses the whole turn-1 history + fresh tokens (greedy,
    # so solo generate() is the reference)
    p2 = np.concatenate([p1, out_on["s0"], tails[2], tails[2]])
    hits0 = eng.sched.prefix_hit_tokens
    eng.submit(Request(uid="t2", prompt=p2, max_new_tokens=5))
    out2 = eng.run()["t2"]
    np.testing.assert_array_equal(
        out2, _solo(params, cfg, p2, 5),
        err_msg="multi-turn reuse diverged from solo")
    # the follow-up matched PAST the prompt span of turn 1: generated
    # blocks registered at decode block boundaries are matchable too
    matched = eng.sched.prefix_hit_tokens - hits0
    assert matched >= 12                    # p1's 2 blocks + >=1 more


def test_preemption_under_sharing_stays_bitwise(setup):
    """The preemption drill replayed under sharing: block pressure
    evicts the youngest; its continuation re-probes the index (its own
    freed blocks are cached and matchable), and every request —
    evicted included — still equals its solo run."""
    cfg, params, system, tails = setup
    scfg = ServeConfig(num_slots=3, block_size=4, num_blocks=9,
                       max_blocks_per_slot=8, prefill_chunk=4)
    eng = ServeEngine(params, cfg, scfg, registry=Registry())
    reqs = [(system, 8), (np.concatenate([system[:4], tails[1]])[:8], 8),
            (np.concatenate([tails[1], tails[0]])[:6], 6)]
    for i, (p, n) in enumerate(reqs):
        eng.submit(Request(uid=f"r{i}", prompt=p, max_new_tokens=n))
    out = eng.run()
    assert eng.metrics.counter("serve_preemptions_total").value >= 1
    for i, (p, n) in enumerate(reqs):
        np.testing.assert_array_equal(
            out[f"r{i}"], _solo(params, cfg, p, n),
            err_msg=f"r{i} diverged through preemption under sharing")
    assert eng.sched.allocator.live_count == 0


@pytest.mark.slow
def test_int8_kv_scale_pools_share_bitwise(setup):
    """int8 KV under sharing: the scale pools ride the same refcounts
    (a shared block's scales are the registered content too), the CoW
    fork copies them with the values, and the stream equals solo int8
    generate() bitwise."""
    cfg, params, system, tails = setup
    scfg = ServeConfig(num_slots=2, block_size=4, num_blocks=17,
                       max_blocks_per_slot=8, prefill_chunk=4,
                       kv_dtype="int8")
    eng = ServeEngine(params, cfg, scfg, registry=Registry())
    p0 = np.concatenate([system, tails[0]])
    p1 = np.concatenate([system, tails[1]])
    eng.submit(Request(uid="a", prompt=p0, max_new_tokens=6))
    eng.submit(Request(uid="b", prompt=p1, max_new_tokens=6))
    out = eng.run()
    # b admitted the same boundary as a: no registration yet -> run a
    # third request AFTER the index is warm, plus a full-match CoW
    eng.submit(Request(uid="c", prompt=p1, max_new_tokens=6))
    eng.submit(Request(uid="d", prompt=system, max_new_tokens=6))
    out.update(eng.run())
    for uid, p in (("a", p0), ("b", p1), ("c", p1), ("d", system)):
        np.testing.assert_array_equal(
            out[uid], _solo(params, cfg, p, 6, kv_dtype="int8"),
            err_msg=f"{uid} diverged from solo int8 under sharing")
    assert eng.sched.prefix_hits >= 1
    assert eng.metrics.counter(
        "serve_prefix_cow_copies_total").value >= 1


# ---------------------------------------------------------------------------
# disaggregated fleet: straight-to-decode + chaos drill under sharing
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_straight_to_decode_and_kill_busiest_drill(setup):
    """Fleet sharing end-to-end: a warm replica's index admits a
    same-prefix request STRAIGHT to decode (no prefill slice, no
    shipment — the shipment counter does not move), per-replica hit
    gauges mirror at the fleet boundary, and the kill-busiest-replica
    chaos drill replayed under sharing stays bitwise (rerouted
    continuations re-probe the survivors' indexes)."""
    cfg, params, system, tails = setup
    router = DisaggRouter(
        params, cfg, SCFG,
        RouterConfig(n_decode_replicas=2, transfer="ship"),
        registry=Registry())
    p0 = np.concatenate([system, tails[0]])
    router.submit(Request(uid="w", prompt=p0, max_new_tokens=6))
    out = router.run()                       # warm a replica's index
    m = router.metrics
    assert m.counter("serve_kv_shipments_total").value == 1
    assert m.counter("serve_prefix_direct_admissions_total").value == 0
    # same system prompt again: a replica holds the match -> straight
    # to decode, no second shipment
    p1 = np.concatenate([system, tails[1]])
    router.submit(Request(uid="x", prompt=p1, max_new_tokens=6))
    out.update(router.run())
    assert m.counter("serve_kv_shipments_total").value == 1
    assert m.counter("serve_prefix_direct_admissions_total").value == 1
    hit_rates = [m.gauge(f"serve_replica{i}_prefix_hit_rate").value
                 for i in range(2)]
    assert max(hit_rates) > 0                # the mirrored fleet gauge
    # now the chaos drill under sharing: a burst of shared-prefix
    # requests, kill the busiest replica mid-flight, drain
    news = (8, 6, 7)
    for i, n in enumerate(news):
        router.submit(Request(uid=f"k{i}",
                              prompt=np.concatenate([system, tails[i]]),
                              max_new_tokens=n))
    for _ in range(3):
        router.step()
    victim = max(router.replicas,
                 key=lambda r: r.eng.sched.n_active()).index
    router.kill_replica(victim)
    out.update(router.run())
    np.testing.assert_array_equal(out["w"], _solo(params, cfg, p0, 6))
    np.testing.assert_array_equal(out["x"], _solo(params, cfg, p1, 6))
    for i, n in enumerate(news):
        p = np.concatenate([system, tails[i]])
        np.testing.assert_array_equal(
            out[f"k{i}"], _solo(params, cfg, p, n),
            err_msg=f"k{i} diverged after the kill under sharing")
    # the prefill worker never shares (transient single slot)
    assert router.prefill.eng.scfg.prefix_cache is False
    assert router.prefill.eng.sched.prefix_probes == 0


# ---------------------------------------------------------------------------
# satellite 5: the V-side convert pin (jax 0.4.37 structural block)
# ---------------------------------------------------------------------------

def test_v_side_convert_pin():
    """Pins the resolution of the PR-6 V-side convert candidate in
    ``_attn_cached``: at jax 0.4.37 every expressible form of the f32
    x bf16 V contraction lowers with a materialized cache convert
    (einsum AND raw mixed-dtype dot_general), the DotAlgorithm API
    that would express mixed-operand accumulation raises, and the
    direct dot_general form is BITWISE-equal to the shipped einsum —
    the ready replacement for a jax whose lowering honors it.  If
    this test fails on a future jax bump, the block lifted: move
    ``_attn_cached``'s V contraction to the direct form."""
    import re
    B, Q, H, D, M = 1, 2, 2, 4, 8
    rng = np.random.RandomState(0)
    p = jnp.asarray(rng.standard_normal((B, H, Q, M)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, M, H, D)), jnp.bfloat16)
    dn = (((3,), (1,)), ((0, 1), (0, 2)))

    def ein(p, v):
        return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                          preferred_element_type=jnp.float32)

    def direct(p, v):
        out = jax.lax.dot_general(p, v, dimension_numbers=dn,
                                  preferred_element_type=jnp.float32)
        return jnp.transpose(out, (0, 2, 1, 3))

    np.testing.assert_array_equal(
        np.asarray(jax.jit(ein)(p, v)),
        np.asarray(jax.jit(direct)(p, v)))
    pat = re.compile(r"convert.*tensor<1x8x2x4xf32>")
    for fn in (ein, direct):
        txt = jax.jit(fn).lower(p, v).as_text()
        assert pat.search(txt), (
            "the V-side cache convert vanished from the lowering — "
            "the jax upgrade unblocked preferred_element_type on the "
            "V contraction; move _attn_cached to the direct "
            "dot_general form and retire this pin")
    with pytest.raises(Exception):
        alg = jax.lax.DotAlgorithm(
            lhs_precision_type=jnp.float32,
            rhs_precision_type=jnp.bfloat16,
            accumulation_type=jnp.float32)
        jax.jit(lambda p, v: jax.lax.dot_general(
            p, v, dimension_numbers=dn, precision=alg)).lower(p, v)
