"""Headline benchmarks on one chip: ResNet-50 (amp O2 + FusedAdam, plus the
O3 "speed of light" config the reference documents in
``examples/imagenet/README.md``) and GPT-small causal-LM training.

Prints ONE JSON line.  Primary metric: best ResNet-50 img/s; ``mfu`` is
model-FLOPs utilisation for that config; the ``configs`` map carries every
measured config's throughput + MFU + HFU (incl. GPT tok/s) so
compute-efficiency regressions are visible, not just throughput ones.
``mfu`` counts MODEL FLOPs (6 attention passes, the PaLM convention);
``hfu`` counts EXECUTED FLOPs (7 passes where the fused one-pass
attention backward recomputes scores).

Regression gate: the output's ``regression_check`` compares every
config's throughput against the newest ``BENCH_r{N}.json`` next to this
script (or ``--compare PATH``); with ``--compare`` a >``--threshold``
(default 10%) per-config drop exits nonzero naming the configs.

Baseline derivation (BASELINE.json north star: "v5e-16 within 90% of
8xA100 images/sec"): 8xA100 ResNet-50 amp synthetic-data throughput
~2500 img/s/GPU => 20000 img/s; 90% over 16 v5e chips =>
1125 img/s/chip.  ``vs_baseline`` is measured img/s on this one chip
divided by that per-chip target (>1.0 beats the north star pro-rata).

MFU: FLOPs per step are taken from XLA's compiled cost analysis (the
compiler's own count for the whole train step: fwd + bwd + optimizer),
divided by wall time and chip peak.  Peaks come from the one table in
``apex_tpu.utils.chip_peaks``, keyed by ``device_kind``; a chip that is
not in it is an error.

No TPU is an error too: this script measures the chip and nothing else,
and a config that raises fails the run.
"""

import argparse
import glob
import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp

BASELINE_IMG_PER_SEC_PER_CHIP = 1125.0

def step_flops(compiled, fallback: float) -> float:
    """XLA's own FLOP count for one compiled step; ``fallback`` (an
    analytic estimate) covers backends whose cost analysis is missing."""
    try:
        f = float(compiled.cost_analysis().get("flops", 0.0))
        if f > 0:
            return f
    except Exception:
        pass
    return fallback


def _time_steps(step, state, args, warmup, iters, loss_key="loss"):
    # the scalar fetch of the last step's loss ends the timed region
    for _ in range(warmup):
        state, metrics = step(state, *args)
    if warmup:
        float(metrics[loss_key])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, *args)
    float(metrics[loss_key])
    return time.perf_counter() - t0


def bench_resnet(opt_level: str, batch: int, size: int, warmup: int,
                 iters: int, peak: float, s2d: bool = False):
    from apex_tpu import amp
    from apex_tpu.models.resnet import ResNet50, ResNet50S2D
    from apex_tpu.optimizers import FusedAdam

    # s2d: the TPU-native space-to-depth stem (MXU-friendly C_in)
    model = ResNet50S2D() if s2d else ResNet50()
    x = jax.random.normal(jax.random.PRNGKey(0), (batch, size, size, 3),
                          jnp.float32)
    y = jax.random.randint(jax.random.PRNGKey(1), (batch,), 0, 1000)
    variables = model.init(jax.random.PRNGKey(2), x[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # O3 speed-of-light per the reference README: pure half compute,
    # static scale, but --keep-batchnorm-fp32 True.
    kwargs = dict(keep_batchnorm_fp32=True, loss_scale=128.0) \
        if opt_level == "O3" else {}
    a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level=opt_level,
                       verbosity=0, **kwargs)
    state = a.init(params)

    def loss_fn(p, xb, yb):
        logits, _ = model.apply({"params": p, "batch_stats": batch_stats},
                                xb, train=True, mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    step = jax.jit(amp.make_train_step(a, loss_fn), donate_argnums=(0,))
    compiled = step.lower(state, x, y).compile()
    dt = _time_steps(compiled, state, (x, y), warmup, iters)

    img_per_sec = batch * iters / dt
    # analytic fallback: RN50 fwd ~4.09 GFLOP/img at 224px (scales with
    # spatial area), training ~3x fwd
    fwd = 4.09e9 * (size / 224.0) ** 2
    flops = step_flops(compiled, fallback=3.0 * fwd * batch)
    mfu = round(flops * iters / dt / peak, 4) if peak else None
    # no analytic-recompute correction on this path: XLA counts the
    # whole conv step itself, so model FLOPs == executed FLOPs
    return {"img_s": round(img_per_sec, 2), "mfu": mfu, "hfu": mfu,
            "batch": batch, "px": size}


def bench_gpt(batch: int, seq: int, warmup: int, iters: int, peak: float,
              tiny: bool, tpu_heads: "bool | str" = False,
              remat: bool = False):
    import dataclasses

    from apex_tpu import amp
    from apex_tpu.models.gpt import (
        GPTModel, gpt_medium_tpu, gpt_small, gpt_small_tpu, gpt_tiny,
        lm_loss)
    from apex_tpu.optimizers import FusedAdam

    # tpu_heads: same params/FLOPs with the TPU-native 6x128 head
    # geometry (full MXU lane width in the flash kernels); the string
    # "medium" selects gpt_medium_tpu (~368M, 8x128 heads) instead.
    if tiny:
        cfg = gpt_tiny()
    elif tpu_heads == "medium":
        cfg = gpt_medium_tpu()
    else:
        cfg = gpt_small_tpu() if tpu_heads else gpt_small()
    if remat:  # long-context configs recompute the layer body
        cfg = dataclasses.replace(cfg, remat=True)
    model = GPTModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(3), (batch, seq), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(4), ids[:, :16])["params"]
    a = amp.initialize(optimizer=FusedAdam(lr=1e-4), opt_level="O2",
                       verbosity=0)
    state = a.init(params)

    def loss_fn(p, xb):
        logits = model.apply({"params": p}, xb)
        return lm_loss(logits[:, :-1], xb[:, 1:])

    step = jax.jit(amp.make_train_step(a, loss_fn), donate_argnums=(0,))
    compiled = step.lower(state, ids).compile()
    dt = _time_steps(compiled, state, (ids,), warmup, iters)
    return _lm_result(compiled, cfg, params, batch, seq, dt, iters, peak,
                      "tok_s", batch * seq * iters / dt, causal=True,
                      remat=remat)


#: analytic attention matmul passes per layer.  MODEL passes (the PaLM
#: MFU convention): forward 2 (QK^T, PV) + backward 4 (dq, dk, dv, dp)
#: = 6.  EXECUTED passes on the fused one-pass Pallas backward: the bwd
#: additionally recomputes the score matrix = 7 total; that extra pass
#: is hardware work, not model work, so it books under HFU only.
ATTN_MODEL_PASSES = 6
ATTN_FUSED_EXEC_PASSES = 7


def attention_pass_flops(cfg, batch: int, seq: int, causal: bool) -> float:
    """Analytic FLOPs of ONE attention matmul pass (``2*B*H*L^2*D``),
    summed over layers.  Callers scale by ``ATTN_MODEL_PASSES`` (MFU) or
    ``ATTN_FUSED_EXEC_PASSES`` (HFU on the fused-backward kernel path).

    XLA's cost analysis reports (near-)ZERO flops for custom calls
    (measured: 0.003 GF vs 12.9 GF analytic for one L2048 forward), so
    without this term every transformer MFU undercounts by the
    attention fraction — ~1% at L2048 but ~40% at L8192.

    A remat'd layer body would re-run the forward's 2 passes, but
    remat=True measures identical step time to remat=False here (XLA
    CSEs the recompute), so no remat term is counted — conservative if
    a future config genuinely recomputes.  Causal halves every pass
    (the kernels skip dead blocks)."""
    head_dim = cfg.hidden_size // cfg.num_heads
    one_pass = 2.0 * batch * cfg.num_heads * float(seq) ** 2 * head_dim
    return cfg.num_layers * one_pass * (0.5 if causal else 1.0)


def _pallas_attn_compiled(compiled) -> "bool | None":
    """Whether the compiled step actually contains the flash-attention
    Pallas custom call — the analytic attention term must be gated on
    the path the executable TOOK, not on ``use_pallas()`` alone:
    flash_attention can still route to the jnp math under use_pallas
    (cross-attention shapes, interpret-mode under shard_map), where
    XLA's cost analysis already counts the einsums and adding the term
    would double count.  Only the *attention* kernels' names count:
    other Pallas kernels (fused optimizers, layer norm) are in the step
    too and must not vouch for the attention path.  Returns None when
    the HLO text is unavailable."""
    from apex_tpu.ops import mosaic_kernels
    try:
        txt = compiled.as_text()
    except Exception:
        return None
    return any(k.startswith("flash_") for k in mosaic_kernels(txt))


def _lm_result(compiled, cfg, params, batch, seq, dt, iters, peak,
               rate_key, rate, causal=True, remat=False):
    """Shared tail for the transformer benches: params count, FLOPs with
    the 6ND + attention analytic fallback, MFU + HFU.

    ``mfu`` counts model FLOPs (6 attention passes — the PaLM
    convention); ``hfu`` counts executed FLOPs (7 passes on the fused
    one-pass backward, which recomputes scores).  MFU is the headline
    number; HFU shows what the hardware actually ran."""
    del remat
    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    from apex_tpu.ops import use_pallas
    one_pass = attention_pass_flops(cfg, batch, seq, causal)
    dense_fb = 6.0 * n_params * batch * seq
    kernel_path = _pallas_attn_compiled(compiled)
    if kernel_path is None:
        kernel_path = use_pallas()
    if kernel_path:
        # step_flops covers everything XLA sees; the pallas attention
        # calls report ~0 there and are added analytically.
        base = step_flops(compiled, fallback=dense_fb)
        model_flops = base + ATTN_MODEL_PASSES * one_pass
        exec_flops = base + ATTN_FUSED_EXEC_PASSES * one_pass
    else:
        # jnp attention path: cost analysis counts the einsums itself
        # (and XLA's AD backward materializes rather than recomputes,
        # so model == executed); only the FALLBACK needs the term.
        model_flops = exec_flops = step_flops(
            compiled, fallback=dense_fb + ATTN_MODEL_PASSES * one_pass)
    mfu = round(model_flops * iters / dt / peak, 4) if peak else None
    hfu = round(exec_flops * iters / dt / peak, 4) if peak else None
    return {rate_key: round(rate, 2), "mfu": mfu, "hfu": hfu,
            "batch": batch, "seq": seq, "params": n_params}


def bench_bert(batch: int, seq: int, warmup: int, iters: int, peak: float,
               tiny: bool, tpu_heads: bool = False):
    """BASELINE config 4: BERT-large MLM+NSP pretraining step with
    FusedLAMB + FusedLayerNorm + flash attention (amp O2)."""
    import dataclasses

    from apex_tpu import amp
    from apex_tpu.models.bert import (
        BertForPreTraining, bert_large, bert_large_tpu, bert_tiny,
        pretraining_loss)
    from apex_tpu.optimizers import FusedLAMB

    base = bert_large_tpu() if tpu_heads else bert_large()
    cfg = bert_tiny() if tiny else dataclasses.replace(base, remat=True)
    model = BertForPreTraining(cfg)
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    ids = jax.random.randint(k[0], (batch, seq), 0, cfg.vocab_size)
    mlm_labels = jax.random.randint(k[1], (batch, seq), 0, cfg.vocab_size)
    mlm_mask = (jax.random.uniform(k[2], (batch, seq)) < 0.15)\
        .astype(jnp.float32)
    nsp_labels = jax.random.randint(k[3], (batch,), 0, 2)
    params = model.init(jax.random.PRNGKey(6), ids[:1, :8])["params"]

    a = amp.initialize(optimizer=FusedLAMB(lr=1e-4), opt_level="O2",
                       verbosity=0)
    state = a.init(params)

    def loss_fn(p, ids, mlm_labels, nsp_labels, mlm_mask):
        mlm_logits, nsp_logits = model.apply({"params": p}, ids)
        return pretraining_loss(mlm_logits, nsp_logits, mlm_labels,
                                nsp_labels, mlm_mask)

    step = jax.jit(amp.make_train_step(a, loss_fn), donate_argnums=(0,))
    args = (ids, mlm_labels, nsp_labels, mlm_mask)
    compiled = step.lower(state, *args).compile()
    dt = _time_steps(compiled, state, args, warmup, iters)

    return _lm_result(compiled, cfg, params, batch, seq, dt, iters, peak,
                      "seq_s", batch * iters / dt, causal=False)


def bench_generate(batch: int, prefill: int, new_tokens: int, warmup: int,
                   iters: int, peak: float, tiny: bool = False,
                   kv_dtype=None):
    """KV-cached decode throughput (``apex_tpu.models.generate``):
    greedy generation of ``new_tokens`` after a ``prefill``-token prompt
    on gpt-small (TPU head geometry), bf16 params.

    Decode is HBM-bandwidth-bound, not MXU-bound: every generated token
    re-reads the full parameter set plus both KV caches.  The ceiling
    is derived through the shared roofline machinery
    (:func:`apex_tpu.analysis.cost.roofline_expectation` — the same
    physics the lint calibration audit holds floors to): static
    flops/bytes per step in, binding resource and ceiling rate out,
    recorded as ``hbm_tok_s_ceiling`` + ``bound`` alongside the
    measured rate and its ``hbm_frac`` fraction-of-ceiling (gated by
    ``DECODE_FLOORS`` the way MFU floors gate the train configs; the
    MFU of a well-formed decode is intrinsically ~1-2% —
    ``docs/source/models.rst`` carries the framing).  CAVEAT the byte
    model is the roofline FLOOR (params + cache, ideal fusion):
    ``DECODE_DECOMPOSE_r01.json`` decomposes where the b8 step's real
    traffic goes and attributes the measured 0.43 — the fraction is a
    tracked efficiency metric against a fixed bar, not a claim that
    0.57 of the bandwidth is idle.  ``tok_s`` counts NEW tokens only;
    the one prefill forward per call is amortized into the measured
    window exactly as a serving loop would pay it.

    ``kv_dtype="int8"`` selects the int8 KV cache
    (:mod:`apex_tpu.quant.int8`: per-position absmax scales, dequant
    fused into the attention read) and the byte model follows — 1
    byte/element for both caches plus 4 bytes/position/layer for each
    scale array instead of 2 bytes/element, so the ceiling this config
    is gated against (``gpt_small_tpu_decode_kv8``) is DERIVED from
    the int8 byte model through the same
    :func:`~apex_tpu.analysis.cost.roofline_expectation` call, never
    hand-written: decode is HBM-bound with kv_read the dominant term
    (DECODE_DECOMPOSE_r01), so halving cache bytes is a ~2x ceiling
    lift at long context."""
    from apex_tpu import amp
    from apex_tpu.models.generate import generate
    from apex_tpu.models.gpt import GPTModel, gpt_small_tpu, gpt_tiny

    cfg = gpt_tiny() if tiny else gpt_small_tpu()
    model = GPTModel(cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(7), (batch, prefill),
                                0, cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(8), prompt[:1, :16])["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)  # bf16, the serving layout

    import numpy as np
    out = generate(params, cfg, prompt, new_tokens, kv_dtype=kv_dtype)
    np.asarray(out[:, -1])  # compile + drain (scalar fetch, not BUR)
    for _ in range(warmup):
        out = generate(params, cfg, prompt, new_tokens, kv_dtype=kv_dtype)
    np.asarray(out[:, -1])
    t0 = time.perf_counter()
    for _ in range(iters):
        out = generate(params, cfg, prompt, new_tokens, kv_dtype=kv_dtype)
    np.asarray(out[:, -1])
    dt = time.perf_counter() - t0

    from apex_tpu.analysis import cost as cost_mod

    n_params = sum(int(p.size) for p in jax.tree.leaves(params))
    head_dim = cfg.hidden_size // cfg.num_heads
    m = prefill + new_tokens
    if kv_dtype == "int8":
        # int8 KV byte model: 1 byte/element per cache + one f32 scale
        # per cached position per layer for each of K and V
        cache_b = (2 * cfg.num_layers * batch * m * cfg.num_heads
                   * head_dim * 1
                   + 2 * cfg.num_layers * batch * m * 4)
    else:
        cache_b = (2 * cfg.num_layers * batch * m * cfg.num_heads
                   * head_dim * 2)
    bytes_per_step = 2 * n_params + cache_b   # bf16 params + k&v caches
    # dense-matmul flops of one step (2 flops/param/token x batch):
    # the numerator of the shared roofline — decode intensity is ~0.01
    # flop/byte, so the expectation resolves bandwidth-bound and the
    # ceiling rate reduces to batch x bw / bytes; a future config that
    # tips compute-bound (huge batch, int8 KV) is handled by the same
    # formula instead of silently overstating the bar
    flops_per_step = 2.0 * n_params * batch
    from apex_tpu.utils.chip_peaks import CHIP_PEAKS, chip_peak
    # tiny is the unit tests' path: it checks the byte model against the
    # v5e entry, and nothing it times is reported
    bw = (CHIP_PEAKS["TPU v5 lite"] if tiny
          else chip_peak()).hbm_bytes_per_s
    exp = cost_mod.roofline_expectation(
        flops_per_step, bytes_per_step,
        peak_flops=peak or float("inf"), peak_hbm_bytes_per_s=bw)
    ceiling = batch * exp["ceiling_flops_per_s"] / flops_per_step
    rec = {"tok_s": round(batch * new_tokens * iters / dt, 2),
           "batch": batch, "prefill": prefill, "new_tokens": new_tokens,
           "params": n_params, "bound": exp["bound"],
           "hbm_tok_s_ceiling": round(ceiling, 2),
           "hbm_frac": round(batch * new_tokens * iters / dt / ceiling,
                             4)}
    if kv_dtype is not None:
        rec["kv_dtype"] = kv_dtype
        rec["cache_bytes_per_step"] = int(cache_b)
    return rec


def bench_serve(warmup: int, iters: int, peak: float,
                num_slots: int = 8, prefill: int = 512,
                new_tokens: int = 128, tiny: bool = False):
    """Continuous-batching serve throughput+latency
    (:class:`apex_tpu.serve.ServeEngine`): an offered-load sweep over
    concurrency levels — 1 in-flight request (pure latency), then
    ``num_slots`` mixed-length requests streaming through the fixed
    slots (continuous batching over the paged KV cache, fused sampling
    epilogue).

    Per level: ``tok_s`` (generated tokens / wall), per-DECODE-STEP
    wall latency ``p50_ms``/``p99_ms`` — read from the engine's own
    ``serve_decode_step_seconds`` histogram
    (:mod:`apex_tpu.obs.metrics`), NOT a private list sort, so bench
    and a production scrape can never disagree on percentile math (the
    quantiles are bucket-interpolated the Prometheus way).  The
    headline record carries the full-load numbers (``tok_s`` rides the
    existing delta/ladder gates).  ``ab_ok`` is the latency-tail gate:
    p99 under ``20 x p50`` — the tail a mid-serve retrace or host sync
    produces is 100-1000x (far beyond bucket-interpolation error), so
    this catches the static-shape contract breaking at runtime without
    guessing an absolute latency bar before a chip round records
    one."""
    del peak, warmup
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models.gpt import GPTModel, gpt_small_tpu, gpt_tiny
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import Request, ServeConfig, ServeEngine

    cfg = gpt_tiny() if tiny else gpt_small_tpu()
    if tiny:
        num_slots, prefill, new_tokens = 2, 16, 8
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)

    block = 16 if not tiny else 4
    mb = -(-(prefill + new_tokens) // block)
    scfg = ServeConfig(
        num_slots=num_slots, block_size=block,
        num_blocks=num_slots * mb + 1, max_blocks_per_slot=mb,
        prefill_chunk=min(prefill, 128 if not tiny else 8))
    rng = np.random.RandomState(11)

    def make_reqs(n, tag):
        reqs = []
        for i in range(n):
            plen = int(prefill * (0.5 + 0.5 * (i % 2)))  # mixed lengths
            reqs.append(Request(
                uid=f"{tag}{i}",
                prompt=rng.randint(0, cfg.vocab_size, (plen,)),
                max_new_tokens=new_tokens))
        return reqs

    # ONE engine serves every load level: the decode/prefill programs
    # compile once (each ServeEngine re-jits, and the compile dominates
    # setup on chip), and the retraces==1 gate then spans the sweep.
    # A PRIVATE registry isolates the histogram from any other serving
    # in this process; per-level windows come from histogram snapshots.
    eng = ServeEngine(params, cfg, scfg, registry=Registry())
    step_hist = eng.metrics.histogram("serve_decode_step_seconds")
    tok_counter = eng.metrics.counter("serve_tokens_total")

    def drive(n, tag):
        for r in make_reqs(n, tag):
            eng.submit(r)
        eng.step()                       # admission + compile + 1 step
        mark = step_hist.state()         # window: steady-state steps
        tok0 = tok_counter.value
        t0 = time.perf_counter()
        while not eng.sched.idle():
            # admission/prefill is driven OUTSIDE the decode-step
            # sample the engine histogram records: p50/p99 are
            # DECODE-step latency (the retrace/host-sync tail this
            # gate watches), while admission cost still lands in the
            # wall-clock tok_s
            eng._admit_and_evict()
            if not eng.sched.active.any():
                raise RuntimeError("serve bench admission stall: "
                                   "queued requests but no active slot")
            eng.step()
        wall = time.perf_counter() - t0
        produced = tok_counter.value - tok0
        steps = step_hist.count - mark[2]
        p50 = step_hist.quantile(0.5, since=mark) * 1e3 if steps else 0.0
        p99 = step_hist.quantile(0.99, since=mark) * 1e3 if steps else 0.0
        return {"tok_s": round(produced / wall, 2) if wall else 0.0,
                "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                "steps": int(steps), "retraces":
                    eng.trace_counts["decode"]}

    del iters  # the request stream sets the sample count
    solo = drive(1, "s")
    full = drive(num_slots, "f")
    tail_ok = full["p99_ms"] <= 20 * max(full["p50_ms"], 1e-6) \
        and full["retraces"] == 1
    return {"tok_s": full["tok_s"], "batch": num_slots,
            "prefill": prefill, "new_tokens": new_tokens,
            "p50_ms": full["p50_ms"], "p99_ms": full["p99_ms"],
            "offered_load": {"c1": solo, f"c{num_slots}": full},
            "ab_ok": bool(tail_ok)}


def bench_serve_spec(warmup: int, iters: int, peak: float,
                     num_slots: int = 8, prefill: int = 512,
                     new_tokens: int = 128, spec_k: int = 4,
                     draft_layers: int = 3, tiny: bool = False):
    """Speculative-vs-baseline serve A/B at EQUAL work
    (:class:`apex_tpu.serve.SpecEngine` vs
    :class:`~apex_tpu.serve.ServeEngine`): the SAME mixed-length
    greedy request stream served by the plain one-token-per-step
    engine and by the speculative engine (truncated layer-skip draft
    proposing ``spec_k`` tokens per round, the target verifying the
    whole block in one b×(k+1) step).

    The headline number is the speculative arm's ``tok_s``; the gate
    (``ab_ok``) is the latency win in machine-checked form —
    **tokens per decode dispatch strictly greater with speculation
    on** (every accepted token saves a full HBM sweep of params +
    KV, which is what converts the int8-KV bandwidth headroom into
    latency) — plus ``retraces == 1`` on BOTH arms (the speculation
    loop must not have broken the static-shape contract).  Latency
    percentiles come from each engine's own
    ``serve_decode_step_seconds`` histogram, like every serve
    config.

    Unlike the other serve configs, the model is BRIEFLY TRAINED
    (:func:`apex_tpu.models.gpt.train_toy_lm` — the ONE recipe the
    scenario tool and the spec tests share) and the prompts come
    from its training stream: acceptance rate — the entire
    speculative win — is a statement about how well the draft
    predicts the target, and a random-init model's near-uniform
    logits make it structurally ~1/vocab (the gate would fail by
    construction, measuring nothing).  The scenario-matrix artifact
    (``SCENARIO_r*.json``) carries the full per-scenario grid; this
    config is the chip-round headline cell."""
    del peak, warmup, iters
    import numpy as np

    from apex_tpu.models.gpt import gpt_small_tpu, gpt_tiny, \
        train_toy_lm
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import (Request, ServeConfig, ServeEngine,
                                SpecConfig, SpecEngine, truncated_draft)

    if tiny:
        num_slots, prefill, new_tokens, spec_k, draft_layers = \
            2, 16, 8, 2, 1
    cfg, params, ids = train_toy_lm(
        gpt_tiny() if tiny else gpt_small_tpu())
    draft_layers = min(draft_layers, cfg.num_layers - 1)
    dp, dcfg = truncated_draft(params, cfg, draft_layers)

    block = 16 if not tiny else 4
    mb = -(-(prefill + new_tokens) // block)
    scfg = ServeConfig(
        num_slots=num_slots, block_size=block,
        num_blocks=num_slots * mb + 1, max_blocks_per_slot=mb,
        prefill_chunk=min(prefill, 128 if not tiny else 8))
    ids_np = np.asarray(ids, np.int32)

    def make_reqs(tag):
        reqs = []
        for i in range(num_slots * 2):
            plen = max(2, int(prefill * (0.5 + 0.5 * (i % 2))))
            row = ids_np[i % ids_np.shape[0]]
            prompt = np.asarray(
                [row[j % row.shape[0]] for j in range(plen)], np.int32)
            reqs.append(Request(uid=f"{tag}{i}", prompt=prompt,
                                max_new_tokens=new_tokens))
        return reqs

    def drive(eng, tag):
        hist = eng.metrics.histogram("serve_decode_step_seconds")
        toks = eng.metrics.counter("serve_tokens_total")
        for r in make_reqs(tag):
            eng.submit(r)
        eng.step()                   # admission + compile + 1st step
        mark = hist.state()
        tok0 = toks.value
        t0 = time.perf_counter()
        while not eng.sched.idle():
            eng.step()
        wall = time.perf_counter() - t0
        steps = hist.count - mark[2]
        produced = toks.value - tok0
        p50 = hist.quantile(0.5, since=mark) * 1e3 if steps else 0.0
        p99 = hist.quantile(0.99, since=mark) * 1e3 if steps else 0.0
        return {"tok_s": round(produced / wall, 2) if wall else 0.0,
                "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                "steps": int(steps),
                "tokens_per_step": round(produced / max(steps, 1), 4),
                "retraces": max(eng.trace_counts.values())}

    base = drive(ServeEngine(params, cfg, scfg, registry=Registry()),
                 "b")
    eng = SpecEngine(params, cfg, scfg, dp, dcfg,
                     SpecConfig(k=spec_k), registry=Registry())
    spec = drive(eng, "s")
    spec["acceptance_rate"] = round(float(
        eng.metrics.gauge("serve_spec_acceptance_rate").value), 4)
    ab_ok = spec["tokens_per_step"] > base["tokens_per_step"] \
        and base["retraces"] == 1 and spec["retraces"] == 1
    return {"tok_s": spec["tok_s"], "batch": num_slots,
            "prefill": prefill, "new_tokens": new_tokens,
            "spec_k": spec_k, "draft_layers": draft_layers,
            "p50_ms": spec["p50_ms"], "p99_ms": spec["p99_ms"],
            "baseline": base, "spec": spec,
            "ab_ok": bool(ab_ok)}


def bench_serve_disagg(warmup: int, iters: int, peak: float,
                       n_replicas: int = 2, slots_per_replica: int = 8,
                       prefill: int = 512, new_tokens: int = 128,
                       tiny: bool = False):
    """Disaggregated-vs-monolithic serve A/B at EQUAL resources
    (:class:`apex_tpu.serve.DisaggRouter` vs one
    :class:`~apex_tpu.serve.ServeEngine`): the same offered load —
    ``c = n_replicas x slots_per_replica`` mixed-length requests, the
    same request stream, the same platform — served (a) by one
    monolithic engine with ``c`` slots interleaving prefill chunks and
    decode steps on one set of devices, and (b) by the disaggregated
    fleet: prefill on its own mesh slice, ``n_replicas`` decode
    replicas of ``slots_per_replica`` slots each on disjoint slices,
    KV shipped between them.

    Per arm: ``tok_s`` and decode-step ``p50_ms``/``p99_ms`` read from
    the engines' OWN ``serve_decode_step_seconds`` histograms (the
    disagg fleet's percentiles union the replicas' windows through the
    same Histogram math).  ``ab_ok`` is the DistServe/Splitwise claim
    as a gate: ``disagg p99 <= mono p99`` at equal device count —
    splitting bursty compute-bound prefill from steady HBM-bound
    decode must shorten the decode tail, not just move work around.
    The committed ``SERVE_DISAGG_r*.json`` artifact
    (``tools/serve_disagg.py``, schema
    ``apex_tpu/analysis/serve_disagg.py``) records the same sweep +
    the replica-kill chaos drill as gate memory."""
    del peak, warmup, iters
    import dataclasses

    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models.gpt import GPTModel, gpt_small_tpu, gpt_tiny
    from apex_tpu.obs import fleet as fleet_obs
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import (DisaggRouter, Request, RouterConfig,
                                ServeConfig, ServeEngine)

    need_devices = 1 + n_replicas
    if len(jax.devices()) < need_devices:
        return {"skipped": f"needs >= {need_devices} devices "
                           f"(1 prefill + {n_replicas} decode), have "
                           f"{len(jax.devices())}"}
    cfg = gpt_tiny() if tiny else gpt_small_tpu()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)

    concurrency = n_replicas * slots_per_replica
    block = 16 if not tiny else 4
    mb = -(-(prefill + new_tokens) // block)
    scfg_rep = ServeConfig(
        num_slots=slots_per_replica, block_size=block,
        num_blocks=slots_per_replica * mb + 1, max_blocks_per_slot=mb,
        prefill_chunk=min(prefill, 128 if not tiny else 8))
    scfg_mono = dataclasses.replace(
        scfg_rep, num_slots=concurrency,
        num_blocks=concurrency * mb + 1)
    rng = np.random.RandomState(11)

    def make_reqs(tag):
        # the SAME mixed-length stream hits both arms (same seed, same
        # budgets) — the A/B isolates the topology, nothing else
        reqs = []
        for i in range(concurrency):
            plen = int(prefill * (0.5 + 0.5 * (i % 2)))
            reqs.append(Request(
                uid=f"{tag}{i}",
                prompt=rng.randint(0, cfg.vocab_size, (plen,)),
                max_new_tokens=new_tokens))
        return reqs

    rng_state = rng.get_state()

    # -- monolithic arm: c slots, one engine, one device set ----------
    eng = ServeEngine(params, cfg, scfg_mono, registry=Registry())
    hist = eng.metrics.histogram("serve_decode_step_seconds")
    toks = eng.metrics.counter("serve_tokens_total")
    for r in make_reqs("m"):
        eng.submit(r)
    eng.step()                        # admission + compile + 1 step
    mark = hist.state()
    tok0 = toks.value
    t0 = time.perf_counter()
    while not eng.sched.idle():
        eng._admit_and_evict()
        eng.step()
    wall = time.perf_counter() - t0
    mono = {
        "num_slots": concurrency,
        "tok_s": round((toks.value - tok0) / wall, 2) if wall else 0.0,
        "p50_ms": round(hist.quantile(0.5, since=mark) * 1e3, 3),
        "p99_ms": round(hist.quantile(0.99, since=mark) * 1e3, 3),
        "steps": int(hist.count - mark[2]),
        "retraces": eng.trace_counts["decode"],
    }

    # -- disaggregated arm: same stream, same concurrency, the fleet --
    rng.set_state(rng_state)
    reg = Registry()
    router = DisaggRouter(
        params, cfg, scfg_rep,
        RouterConfig(n_decode_replicas=n_replicas, transfer="ship"),
        registry=reg)
    hists = [r.eng.metrics.histogram("serve_decode_step_seconds")
             for r in router.replicas]
    for r in make_reqs("d"):
        router.submit(r)
    router.step()                     # route + compile + 1 step each
    marks = [h.state() for h in hists]
    tok0 = [r.eng.metrics.counter("serve_tokens_total").value
            for r in router.replicas]
    t0 = time.perf_counter()
    router.run()
    wall = time.perf_counter() - t0
    produced = sum(
        r.eng.metrics.counter("serve_tokens_total").value - t
        for r, t in zip(router.replicas, tok0))
    per_replica = []
    for h, mark in zip(hists, marks):
        steps = int(h.count - mark[2])
        per_replica.append({
            "steps": steps,
            "p50_ms": round(h.quantile(0.5, since=mark) * 1e3, 3)
            if steps else 0.0,
            "p99_ms": round(h.quantile(0.99, since=mark) * 1e3, 3)
            if steps else 0.0,
        })
    disagg = {
        "slots_per_replica": slots_per_replica,
        "n_replicas": n_replicas,
        "tok_s": round(produced / wall, 2) if wall else 0.0,
        "p50_ms": round(fleet_obs.merged_quantile(
            list(zip(hists, marks)), 0.5) * 1e3, 3),
        "p99_ms": round(fleet_obs.merged_quantile(
            list(zip(hists, marks)), 0.99) * 1e3, 3),
        "per_replica": per_replica,
        "retraces": [r.eng.trace_counts["decode"]
                     for r in router.replicas],
        "kv_transfer_bytes": int(
            reg.counter("serve_kv_transfer_bytes").value),
        "shipments": int(reg.counter("serve_kv_shipments_total").value),
        "reroutes": int(reg.counter("serve_reroute_total").value),
    }

    ab_ok = disagg["p99_ms"] <= mono["p99_ms"] \
        and mono["retraces"] == 1 \
        and all(r == 1 for r in disagg["retraces"])
    return {"tok_s": disagg["tok_s"], "batch": concurrency,
            "prefill": prefill, "new_tokens": new_tokens,
            "p50_ms": disagg["p50_ms"], "p99_ms": disagg["p99_ms"],
            "mono": mono, "disagg": disagg,
            "topology": {"n_devices": len(jax.devices()),
                         **router.slices.describe()},
            "ab_ok": bool(ab_ok)}


def bench_serve_prefix(warmup: int, iters: int, peak: float,
                       num_slots: int = 16, prefill: int = 512,
                       new_tokens: int = 128, tiny: bool = False):
    """Cross-request prefix-sharing A/B at EQUAL work: the SAME
    shared-system-prompt c``num_slots`` mixed-length stream served
    with the prefix cache ON (``ServeConfig.prefix_cache=True``,
    content-addressed block sharing + CoW + prefill skip on hit) and
    OFF (every request prefills its full prompt).

    The gated numbers are DETERMINISTIC token/block counts, not wall
    time — CPU smoke and a chip round agree on them exactly:

    - ``prefill_tokens_dispatched`` — tokens-to-first-token in work
      terms: how many prompt tokens each arm actually pushed through
      the prefill program (the sharing arm skips the matched span);
    - ``admitted_requests_per_block`` — admitted requests / peak live
      blocks: the pool deduplication (same stream, same devices,
      smaller resident footprint with sharing on).

    ``ab_ok`` = sharing dispatched FEWER prefill tokens AND admitted
    MORE requests per resident block AND both arms stayed at ONE
    decode trace (sharing must not mint executables).  Wall-clock
    ``tok_s``/``p50_ms``/``p99_ms`` ride along per arm, read from each
    engine's own ``serve_decode_step_seconds`` histogram.  The
    committed ``PREFIXCACHE_r*.json`` artifact (``tools/
    serve_prefix.py``, schema ``apex_tpu/analysis/prefixcache.py``)
    records the same sweep plus the per-request spans and the bitwise
    drill as gate memory."""
    del peak, warmup, iters
    import dataclasses

    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models.gpt import GPTModel, gpt_small_tpu, gpt_tiny
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import Request, ServeConfig, ServeEngine

    cfg = gpt_tiny() if tiny else gpt_small_tpu()
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    a = amp.initialize(opt_level="O2", verbosity=0)
    params = a.model_params_from(params)

    block = 16 if not tiny else 4
    mb = -(-(prefill + new_tokens) // block)
    scfg_on = ServeConfig(
        num_slots=num_slots, block_size=block,
        num_blocks=num_slots * mb + 1, max_blocks_per_slot=mb,
        prefill_chunk=min(prefill, 128 if not tiny else 8),
        prefix_cache=True)
    scfg_off = dataclasses.replace(scfg_on, prefix_cache=False)
    rng = np.random.RandomState(11)

    # block-aligned shared system prompt (half the prefill budget) +
    # mixed-length per-request tails: the chat-service shape the
    # sharing claim is about
    sys_len = max((prefill // 2) // block * block, block)
    system = rng.randint(0, cfg.vocab_size, (sys_len,))
    tail_budget = max(prefill - sys_len, 1)
    prompts = []
    for i in range(num_slots):
        tlen = max(int(tail_budget * (0.5 + 0.5 * (i % 2))), 1)
        prompts.append(np.concatenate(
            [system, rng.randint(0, cfg.vocab_size, (tlen,))]))

    def drive(scfg, tag):
        eng = ServeEngine(params, cfg, scfg, registry=Registry())
        hist = eng.metrics.histogram("serve_decode_step_seconds")
        toks = eng.metrics.counter("serve_tokens_total")
        chunks = eng.metrics.counter("serve_prefill_chunks_total")
        for i, p in enumerate(prompts):
            eng.submit(Request(uid=f"{tag}{i}", prompt=p,
                               max_new_tokens=new_tokens))
        eng.step()                    # admission + compile + 1 step
        mark = hist.state()
        tok0 = toks.value
        peak_live = peak_shared = 0
        t0 = time.perf_counter()
        while not eng.sched.idle():
            eng._admit_and_evict()
            eng.step()
            peak_live = max(peak_live, eng.sched.allocator.live_count)
            peak_shared = max(peak_shared,
                              eng.sched.allocator.shared_count)
        wall = time.perf_counter() - t0
        sched = eng.sched
        if scfg.prefix_cache:
            # the scheduler's own spans are the ground truth the
            # artifact re-derives everything from
            dispatched = sum(e["dispatched"]
                             for e in sched.prefix_events)
        else:
            dispatched = sum(len(p) for p in prompts)
        arm = {
            "tok_s": round((toks.value - tok0) / wall, 2)
            if wall else 0.0,
            "p50_ms": round(hist.quantile(0.5, since=mark) * 1e3, 3),
            "p99_ms": round(hist.quantile(0.99, since=mark) * 1e3, 3),
            "prefill_chunks": int(chunks.value),
            "prefill_tokens_dispatched": int(dispatched),
            "admitted_requests": len(prompts),
            "peak_live_blocks": int(peak_live),
            "admitted_requests_per_block":
                round(len(prompts) / max(peak_live, 1), 6),
            "retraces": eng.trace_counts["decode"],
        }
        if scfg.prefix_cache:
            arm["prefix"] = {
                "probes": int(sched.prefix_probes),
                "hits": int(sched.prefix_hits),
                "hit_rate": round(
                    sched.prefix_hits / max(sched.prefix_probes, 1), 6),
                "hit_tokens": int(sched.prefix_hit_tokens),
                "cow_copies": int(eng.metrics.counter(
                    "serve_prefix_cow_copies_total").value),
                "shared_blocks_peak": int(peak_shared),
                "cached_evictions": int(
                    sched.allocator.cached_evictions),
                "requests": [dict(e) for e in sched.prefix_events],
            }
        return arm

    sharing = drive(scfg_on, "p")
    baseline = drive(scfg_off, "b")
    ab_ok = (sharing["prefill_tokens_dispatched"]
             < baseline["prefill_tokens_dispatched"]
             and sharing["admitted_requests_per_block"]
             > baseline["admitted_requests_per_block"]
             and sharing["retraces"] == 1 and baseline["retraces"] == 1)
    return {"tok_s": sharing["tok_s"], "batch": num_slots,
            "prefill": prefill, "new_tokens": new_tokens,
            "p50_ms": sharing["p50_ms"], "p99_ms": sharing["p99_ms"],
            "system_prompt_tokens": int(sys_len), "block_size": block,
            "sharing": sharing, "baseline": baseline,
            "ab_ok": bool(ab_ok)}


RATE_KEYS = ("img_s", "tok_s", "seq_s")

#: Published per-config MFU floors.  The RN50 floors are the
#: round-4 roofline audit's conclusions ("hold >=0.30 conv7 / >=0.32 s2d");
#: transformer floors are the round-4 measured values rounded to two
#: places.  The gate trips when measured MFU < floor * (1 - BAND): the
#: band is the re-statement VERDICT r4 weak #2 asked for — r4's
#: resnet50_o2 0.2983 sat 0.6% under the prose floor, inside the
#: documented ±2-4% chip-day variance, so a bandless floor misfires on
#: environment noise.  0.2983 passes the banded gate; a real >5%
#: efficiency loss does not.
MFU_VARIANCE_BAND = 0.05
MFU_FLOORS = {
    "resnet50_o2": 0.30,
    "resnet50_o3": 0.30,
    "resnet50_s2d_o2": 0.32,
    # r5 same-day spread on this config was 0.4032-0.4211 (-4.3% within
    # one day): the observed low cleared the former 0.42-floor gate
    # (0.399) by only 0.8%, thinner than the chip-day variance that
    # stacks ON TOP of same-day spread — floor widened one point so a
    # soft day cannot trip it; a real >7% loss still does
    "gpt_small_o2": 0.41,
    "bert_large_lamb_o2": 0.49,
    "gpt_small_tpu_heads_o2": 0.54,
    "bert_large_tpu_heads_lamb_o2": 0.59,
    "gpt_small_tpu_heads_L8192_o2": 0.55,
    "gpt_small_tpu_heads_L16384_o2": 0.51,
    "gpt_medium_tpu_o2": 0.58,
}

#: Published fraction-of-HBM-decode-ceiling floors for the decode
#: configs — the bandwidth analog of MFU_FLOORS, same band, gated by
#: :func:`check_decode_floors`.  Pinned at the r05 measured values
#: (ladder: b1 0.5433, b8 0.4346) now that DECODE_DECOMPOSE_r01.json
#: explains the b8 number (the ceiling byte model is the ideal-fusion
#: floor; the measured step carries ~1.5x that traffic, residual
#: attributed to the per-layer cache-slice materialization).  The
#: serve/preferred_element_type rewrites target exactly that residual:
#: the next on-chip round should ratchet b8 toward the >= 0.55 the
#: ROADMAP names, citing BENCH_VARIANCE like every floor raise.
DECODE_FLOORS = {
    "gpt_small_tpu_decode_b1": 0.54,
    "gpt_small_tpu_decode_b8": 0.43,
    # int8-KV b8 config: the ceiling itself is derived from the int8
    # byte model (cache term halves: ~1.6x the dense-config ceiling at
    # b8/2048+256, approaching 2x as context grows and kv_read
    # dominates), so the same hbm_frac would mean ~1.6x the tokens/s.
    # Floor seeded from the CPU-smoke measurement (hbm_frac 0.0011 vs
    # the TPU roofline — a catastrophic-regression guard only); the
    # first on-chip round ratchets it to the measured value per the
    # no-ratchet-down house rule (raising is always allowed).
    "gpt_small_tpu_decode_kv8": 0.001,
}


def check_decode_floors(configs: dict,
                        search_dir: "str | None" = None) -> dict:
    """Decode-bandwidth gate: every measured decode config with a
    published floor must hold ``hbm_frac >= floor * (1 - band)`` —
    same variance band as the MFU gate, same absolute (no-baseline)
    semantics through :func:`gate_exit_code`.  A floor above 1 is a
    calibration bug (nothing can beat the roofline) and fails
    loudly.

    With ``search_dir`` the floors consult the committed variance
    artifact (:func:`derive_floor_bands` — the MFU-gate contract on
    the ``hbm_frac`` statistic).  CPU-smoke-seeded floors
    (:data:`PROVISIONAL_FLOORS`, e.g. the kv8 0.001 guard) are marked
    ``provisional`` in the gate record: they still catch catastrophic
    regressions, but the record — and the timeline reading it — report
    them as unmeasured rather than as calibrated bars."""
    floors, bands = effective_floors(DECODE_FLOORS, search_dir,
                                     kind="config", stat="hbm_frac")
    checked, violations = {}, []
    for name, floor in floors.items():
        if floor > 1.0:
            checked[name] = {"floor": floor, "ok": False,
                             "error": "floor above the roofline "
                                      "ceiling (1.0) — impossible bar"}
            violations.append(name)
            continue
        cur = configs.get(name)
        # skip only configs with NO measurement (error/skipped records)
        # — an hbm_frac of exactly 0.0 is the catastrophic-regression
        # case the gate exists for, not a missing value (the falsy-zero
        # armed-gate class PR 4 fixed in the HFU audit)
        if not isinstance(cur, dict) or \
                not isinstance(cur.get("hbm_frac"), (int, float)):
            continue
        gate = floor * (1.0 - MFU_VARIANCE_BAND)
        ok = cur["hbm_frac"] >= gate
        checked[name] = {"hbm_frac": cur["hbm_frac"], "floor": floor,
                         "source": bands[name]["source"],
                         "gate": round(gate, 4), "ok": ok}
        if bands[name]["provisional"]:
            checked[name]["provisional"] = True
        if not ok:
            violations.append(name)
    return {"band": MFU_VARIANCE_BAND, "checked": checked,
            "provisional": sorted(n for n, b in bands.items()
                                  if b["provisional"]),
            "violations": violations, "ok": not violations}


LADDER_BASELINES = "BENCH_LADDER_BASELINES.json"

#: Recorded-variance artifact (tools/bench_variance.py) — the statistic
#: floor/band changes must cite.  Round-numbered committed artifacts
#: (``BENCH_VARIANCE_r*.json``, schema-validated by gate_hygiene) are
#: preferred; the un-numbered name stays accepted as the scratch
#: output.
VARIANCE_ARTIFACT = "BENCH_VARIANCE.json"


def _newest_round_artifact(search_dir: str,
                           prefix: str) -> "str | None":
    """Newest ``{prefix}_r{N}.json`` in ``search_dir`` by round
    number — the one lookup every round-numbered gate family shares."""
    rounds = []
    for path in glob.glob(os.path.join(search_dir,
                                       f"{prefix}_r*.json")):
        m = re.search(rf"{re.escape(prefix)}_r(\d+)\.json$", path)
        if m:
            rounds.append((int(m.group(1)), path))
    return max(rounds)[1] if rounds else None


def find_variance_artifact(search_dir: str) -> "str | None":
    """Newest committed ``BENCH_VARIANCE_r{N}.json`` next to this
    script, else the legacy un-numbered ``BENCH_VARIANCE.json``."""
    path = _newest_round_artifact(search_dir, "BENCH_VARIANCE")
    if path is not None:
        return path
    legacy = os.path.join(search_dir, VARIANCE_ARTIFACT)
    return legacy if os.path.exists(legacy) else None


def load_variance(search_dir: str) -> "dict | None":
    path = find_variance_artifact(search_dir)
    if path is None:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
        return doc if isinstance(doc, dict) else None
    except (OSError, ValueError):
        return None


def floor_change_allowed(name: str, old_floor: float, new_floor: float,
                         variance_doc: "dict | None",
                         kind: str = "config",
                         stat: "str | None" = None) -> bool:
    """The no-ratchet-down rule for the published floors (MFU_FLOORS
    here, KERNEL_FLOORS in tools/kernel_bench.py) — the floor analog of
    the ladder-baseline rule: RAISING a floor is always allowed
    (measured gains ratchet the bar up), LOWERING one requires a
    recorded-variance entry (``tools/bench_variance.py`` →
    BENCH_VARIANCE.json) for that config/kernel whose relative spread
    covers the drop.  Without the artifact — or with only a tiny-smoke
    one — no lowering: that is exactly the anecdote-calibrated erosion
    VERDICT r5 weak #1/#6 called out (a floor quietly lowered in the
    same commit that turns a gate green).  Enforced by
    tests/l1/test_bench_units.py against a frozen snapshot."""
    if new_floor >= old_floor:
        return True
    if not isinstance(variance_doc, dict) or variance_doc.get("tiny"):
        return False
    entry = (variance_doc.get("entries") or {}).get(f"{kind}:{name}")
    if not isinstance(entry, dict):
        return False
    spread = entry.get("rel_spread")
    if stat is not None:
        # the drop must be judged by the spread of the SAME statistic
        # the floor gates (hbm_frac for decode floors, roofline_frac
        # for kernel floors) — a wide spread on a different metric is
        # not evidence about this one
        sub = entry.get(stat)
        spread = sub.get("rel_spread") if isinstance(sub, dict) \
            else None
    elif kind == "config" and isinstance(entry.get("mfu"), dict):
        # MFU floors gate the mfu statistic when recorded; rate
        # otherwise (the legacy no-stat call path)
        spread = entry["mfu"].get("rel_spread", spread)
    if not spread:
        return False
    return (old_floor - new_floor) / old_floor <= spread


#: Floors seeded from CPU smokes rather than on-chip measurement —
#: catastrophic-regression guards, NOT calibrated bars.  The gate
#: records and the timeline report them as ``provisional`` (unmeasured)
#: instead of passing them off as floors; the first on-chip
#: bench_variance round with an entry for the config graduates them.
PROVISIONAL_FLOORS = frozenset({"gpt_small_tpu_decode_kv8"})

#: The derived-floor formula: ``floor = mean − FLOOR_BAND_K · std``
#: over at least FLOOR_MIN_SAMPLES recorded repeats of the GATED
#: statistic.  k = 2 puts the floor two sample standard deviations
#: under the recorded mean — on the documented same-day spreads
#: (±2-4%) that is a wider allowance than the hand 5% band only when
#: the recorded variance actually is wider, which is the point: band
#: width derives from measured spread, not anecdote.
FLOOR_BAND_K = 2.0
FLOOR_MIN_SAMPLES = 5

#: which variance-entry sub-statistic carries each floor table's unit
_FLOOR_STATS = {"mfu": "mfu", "hbm_frac": "hbm_frac",
                "roofline_frac": "roofline_frac"}


def derive_floor_bands(hand_floors: dict,
                       variance_doc: "dict | None",
                       kind: str = "config",
                       stat: "str | None" = None) -> dict:
    """Statistical floors from recorded variance, hand floors as the
    frozen fallback: for every published floor, when the newest
    committed variance artifact carries a qualifying entry (non-tiny
    document, ``n >= FLOOR_MIN_SAMPLES``, a ``std``-carrying stats
    block for the gated statistic), the derived candidate is
    ``mean − FLOOR_BAND_K · std``; otherwise the hand floor stands.

    The no-ratchet-down rule applies to DERIVED floors too: a
    candidate above the hand floor ratchets the bar up; a candidate
    below it is only accepted when :func:`floor_change_allowed` says
    the recorded spread covers the drop — so consulting the variance
    artifact can tighten gates but never silently loosen one
    (``tests/l1/test_bench_units.py`` pins the frozen-fallback
    behavior against the committed artifact).

    Returns ``{name: {"floor", "source": "derived"|"hand",
    "provisional": bool, ...evidence}}`` — ``provisional`` marks the
    CPU-smoke-seeded guards (:data:`PROVISIONAL_FLOORS`) that have no
    measurement behind them yet.

    Qualifying evidence must be ON-CHIP: the artifact must record
    ``platform == "tpu"`` as well as not-tiny — a full-size CPU run
    (interpret-mode timings, host noise) passes the schema but says
    nothing about the floors the TPU gates enforce, and must never
    loosen them."""
    usable = isinstance(variance_doc, dict) \
        and not variance_doc.get("tiny") \
        and variance_doc.get("platform") == "tpu"
    entries = (variance_doc or {}).get("entries") or {}
    out = {}
    for name, hand in hand_floors.items():
        rec = {"floor": hand, "source": "hand",
               "provisional": name in PROVISIONAL_FLOORS}
        out[name] = rec
        if not usable:
            continue
        e = entries.get(f"{kind}:{name}")
        if stat is not None and isinstance(e, dict):
            e = e.get(stat)
        if not isinstance(e, dict):
            continue
        n, mean, std = e.get("n"), e.get("mean"), e.get("std")
        if not (isinstance(n, int) and n >= FLOOR_MIN_SAMPLES
                and isinstance(mean, (int, float))
                and isinstance(std, (int, float))):
            rec["reason"] = (f"insufficient variance evidence "
                            f"(n={n!r} < {FLOOR_MIN_SAMPLES} or "
                            f"missing mean/std)")
            continue
        candidate = round(mean - FLOOR_BAND_K * std, 4)
        rec.update(mean=mean, std=std, n=n, k=FLOOR_BAND_K,
                   candidate=candidate)
        if candidate >= hand or floor_change_allowed(
                name, hand, candidate, variance_doc, kind=kind,
                stat=stat):
            rec.update(floor=candidate, source="derived",
                       provisional=False)
        else:
            rec["reason"] = ("derived candidate below the hand floor "
                             "beyond the recorded spread — hand floor "
                             "stands (no-ratchet-down)")
    return out


def effective_floors(hand_floors: dict, search_dir: "str | None",
                     kind: str = "config",
                     stat: "str | None" = None) -> "tuple[dict, dict]":
    """``({name: floor}, bands_record)`` — the floors a gate should
    apply: derived where the committed variance artifact qualifies,
    hand otherwise.  ``search_dir=None`` skips the artifact entirely
    (unit tests that pin the hand tables)."""
    doc = load_variance(search_dir) if search_dir else None
    bands = derive_floor_bands(hand_floors, doc, kind=kind, stat=stat)
    return {name: rec["floor"] for name, rec in bands.items()}, bands


def check_mfu_floors(configs: dict,
                     search_dir: "str | None" = None) -> dict:
    """Efficiency gate: every measured config with a published floor
    must hold ``MFU >= floor * (1 - MFU_VARIANCE_BAND)``.  Catches the
    regression class throughput deltas cannot: an OOM-laddered config
    whose batch changed (tok/s incomparable) still has comparable MFU,
    and a kernel regression on a chip-day when the baseline was fast
    shows up here before it survives two rounds of deltas.

    With ``search_dir``, the floors CONSULT the committed variance
    artifact through :func:`derive_floor_bands` (statistical floors
    where recorded evidence qualifies, the hand table as the frozen
    fallback — nothing loosens without a qualifying entry); each
    checked record names the floor's ``source``."""
    floors, bands = effective_floors(MFU_FLOORS, search_dir,
                                     kind="config", stat="mfu")
    checked, violations = {}, []
    for name, floor in floors.items():
        cur = configs.get(name)
        if not isinstance(cur, dict) or not cur.get("mfu"):
            continue
        gate = floor * (1.0 - MFU_VARIANCE_BAND)
        ok = cur["mfu"] >= gate
        checked[name] = {"mfu": cur["mfu"], "floor": floor,
                         "source": bands[name]["source"],
                         "gate": round(gate, 4), "ok": ok}
        if not ok:
            violations.append(name)
    return {"band": MFU_VARIANCE_BAND, "checked": checked,
            "violations": violations, "ok": not violations}


def load_ladder_baselines(search_dir: str) -> dict:
    try:
        with open(os.path.join(search_dir, LADDER_BASELINES)) as f:
            doc = json.load(f)
        return doc if isinstance(doc, dict) else {}
    except (OSError, ValueError):
        return {}


def update_ladder_baselines(search_dir: str, configs: dict) -> None:
    """Persist every successful result keyed ``(config, batch)`` so a
    future round whose config changed its batch still compares
    like-for-like instead of reporting "uncompared" (VERDICT r4 missing
    #3/next #4).
    Rungs never ratchet DOWNWARD: a slow chip-day may only add missing
    rungs, not overwrite a faster stored one — otherwise two soft days
    in a row would quietly lower the bar a real regression is gated
    against.  Best-effort: a read-only checkout must not fail the
    bench."""
    path = os.path.join(search_dir, LADDER_BASELINES)
    doc = load_ladder_baselines(search_dir)
    stamp = time.strftime("%Y-%m-%d")
    for name, cur in configs.items():
        if not isinstance(cur, dict) or cur.get("batch") is None:
            continue
        key = next((k for k in RATE_KEYS if cur.get(k)), None)
        if key is None:
            continue
        prev = doc.get(name, {}).get(str(cur["batch"]))
        if isinstance(prev, dict) and prev.get(key) and \
                prev[key] > cur[key]:
            continue
        entry = dict(cur)
        entry["recorded"] = stamp
        doc.setdefault(name, {})[str(cur["batch"])] = entry
    try:
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
    except OSError:
        pass


def find_kernel_bench_artifact(search_dir: str) -> "str | None":
    """Newest committed ``KERNELBENCH_r{N}.json`` next to this script —
    the kernel-level gate's memory (tools/kernel_bench.py writes it on
    chip; tools/gate_hygiene.py keeps it committed)."""
    return _newest_round_artifact(search_dir, "KERNELBENCH")


def check_kernel_floor_artifact(search_dir: str) -> "dict | None":
    """Surface the per-kernel roofline-fraction floors
    (``tools/kernel_bench.KERNEL_FLOORS``) in this gate record, checked
    against the newest KERNELBENCH_r*.json artifact — the kernel analog
    of the MFU floors, and an ABSOLUTE gate: a committed artifact that
    violates a floor fails the model bench too, so an optimizer-kernel
    bandwidth regression cannot hide behind a green model round (the
    2%-of-step problem the kernel bench exists for).  Best-effort like
    every artifact read here: no artifact → None, unreadable → recorded
    but never failing after the chip time is spent."""
    path = find_kernel_bench_artifact(search_dir)
    if path is None:
        return None
    name = os.path.basename(path)
    # THIS repo's floor table judges the artifact wherever it lives
    # (search_dir may be a scratch dir in tests); guard the insert so
    # repeated calls never grow sys.path.  An unimportable kernel_bench
    # is OUR bug, not a bad artifact: fail the gate loudly rather than
    # run with it silently off.
    tools_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    try:
        import kernel_bench
        check_fn = kernel_bench.check_kernel_floors
    except Exception as e:  # noqa: BLE001
        return {"artifact": name, "ok": False,
                "error": f"tools/kernel_bench unimportable: {e}"[:300]}
    try:
        with open(path) as f:
            doc = json.load(f)
        if not isinstance(doc, dict):
            raise ValueError(f"expected object, got {type(doc).__name__}")
        if doc.get("platform") != "tpu":
            return {"artifact": name, "ok": True,
                    "skipped": "non-TPU artifact: roofline fractions "
                               "only meaningful on chip"}
        # the kernel gate consults the committed variance artifact the
        # same way the MFU/decode gates do — through the ONE shared
        # wiring (statistical floors where a qualifying kernel entry
        # exists, the hand table otherwise), against the SAME
        # search_dir the artifact came from
        eff, bands = kernel_bench.effective_kernel_floors(search_dir)
        out = check_fn(doc.get("kernels") or {}, floors=eff)
        out["floor_sources"] = {n: b["source"]
                                for n, b in bands.items()}
        out["artifact"] = name
        return out
    except Exception as e:  # noqa: BLE001 - artifact reads never crash
        return {"artifact": name, "ok": True,
                "error": f"artifact unreadable: {e}"[:300]}


def find_export_artifact(search_dir: str) -> "str | None":
    """Newest committed ``EXPORT_r{N}.json`` next to this script — the
    AOT-export pipeline's round evidence (tools/aot_export.py writes
    it; tools/gate_hygiene.py keeps it committed and schema-valid)."""
    return _newest_round_artifact(search_dir, "EXPORT")


def check_export_cold_start(search_dir: str) -> "dict | None":
    """Serve cold-start gate, SOURCED from the newest committed
    EXPORT_r*.json (never re-measured here, so bench and the artifact
    can never disagree on the number): loading the serve lane's
    executable from the content-addressed AOT cache must cost at most
    ``budget`` (0.5) of compiling it on the recording host — the whole
    point of lint-then-serialize is that a scale-out replica stops
    paying XLA compilation; a cache slower than half a compile is
    decoration.  An ABSOLUTE gate like the MFU floors: no baseline
    needed, fails the run via :func:`gate_exit_code`.  No artifact →
    ``None`` (nothing to gate); unreadable → recorded but never
    failing after the chip time is spent (the best-effort artifact
    contract), while the verdict itself re-derives ``ok`` from the
    numbers rather than trusting the recorded flag."""
    path = find_export_artifact(search_dir)
    if path is None:
        return None
    name = os.path.basename(path)
    try:
        with open(path) as f:
            doc = json.load(f)
        cs = doc.get("cold_start") if isinstance(doc, dict) else None
        if not isinstance(cs, dict):
            raise ValueError("no cold_start block")
        ratio = cs["load_ratio"]
        budget = cs["budget"]
        return {"artifact": name, "lane": cs.get("lane"),
                "compile_s": cs.get("compile_s"),
                "load_s": cs.get("load_s"),
                "load_ratio": ratio, "budget": budget,
                "ok": bool(ratio <= budget)}
    except (OSError, ValueError, KeyError, TypeError) as e:
        return {"artifact": name, "ok": True,
                "error": f"artifact unreadable: {e}"[:300]}


def check_floor_calibration(search_dir: str) -> dict:
    """The static half of gate calibration (apex_tpu.analysis.cost):
    the published floors (MFU_FLOORS here, KERNEL_FLOORS in
    tools/kernel_bench.py) and the measurements in the newest committed
    KERNELBENCH/BENCH artifacts must all sit UNDER the cost-model
    ceilings — a floor above the roofline (fraction > 1, MFU > 1) or a
    measured bandwidth above the HBM peak means the gate was calibrated
    against impossible physics, and every later round inherits the
    miscalibration.  An unimportable audit is OUR bug: fail loudly
    rather than run with the check silently off (same contract as
    check_kernel_floor_artifact)."""
    try:
        from apex_tpu.analysis import cost as _cost
    except Exception as e:  # noqa: BLE001
        return {"ok": False,
                "error": f"apex_tpu.analysis.cost unimportable: {e}"[:300]}
    try:
        tools_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "tools")
        if tools_dir not in sys.path:
            sys.path.insert(0, tools_dir)
        import kernel_bench
        kernel_floors = kernel_bench.KERNEL_FLOORS
    except Exception as e:  # noqa: BLE001
        # same fail-loud contract as the analysis.cost import above:
        # an unimportable floor table means half the calibration gate
        # is off, which must never read as "calibrated clean"
        return {"ok": False,
                "error": f"tools/kernel_bench unimportable — "
                         f"KERNEL_FLOORS not audited: {e}"[:300]}
    findings = _cost.audit_floor_artifacts(
        search_dir, kernel_floors=kernel_floors, mfu_floors=MFU_FLOORS)
    errors = [f.message for f in findings if f.severity == "error"]
    # CPU-smoke-seeded floors are named as UNMEASURED (provisional):
    # they guard against catastrophe but calibrate nothing — the
    # timeline and the gate record must not pass them off as floors
    provisional = sorted(n for n in PROVISIONAL_FLOORS
                         if n in DECODE_FLOORS or n in MFU_FLOORS
                         or n in kernel_floors)
    return {"ok": not errors, "errors": errors,
            "provisional_floors": provisional}


def find_prior_bench(search_dir: str) -> "str | None":
    """Newest ``BENCH_r{N}.json`` next to this script (by round number) —
    the default regression baseline when ``--compare`` isn't given."""
    return _newest_round_artifact(search_dir, "BENCH")


def compare_configs(prior_path: str, configs: dict,
                    threshold: float = 0.10,
                    ladder: "dict | None" = None) -> dict:
    """Per-config throughput regression check against a prior round's
    ``BENCH_r{N}.json``.  A config counts as regressed when its rate
    metric drops by more than ``threshold`` (default 10%: documented
    chip-day variance is ±2-4%, so ≥8-10% same-config is signal, not
    noise — VERDICT r3 weak #6).  Configs present on only one side, or
    errored/skipped on either, are listed but never fail the gate.

    ``ladder``: persisted ``{config: {str(batch): result}}`` baselines
    (``BENCH_LADDER_BASELINES.json``).  When the round baseline's batch
    mismatches (an OOM-ladder rung change), the same-batch ladder entry
    substitutes so the config is still gated like-for-like; the
    substitution is recorded in ``ladder_compared``."""
    try:
        with open(prior_path) as f:
            doc = json.load(f)
        # the driver's BENCH_r{N}.json wraps the bench line under
        # "parsed" (raw stdout under "tail"); a tee'd run is the line
        # itself — accept both shapes.  Any OTHER shape (valid JSON
        # that isn't the expected dict-of-dicts) counts as unreadable:
        # a malformed artifact next to bench.py must never crash the
        # run after the chip time is already spent.
        if not isinstance(doc, dict):
            raise ValueError(f"expected object, got {type(doc).__name__}")
        if "configs" not in doc and isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        prior = doc.get("configs")
        if not isinstance(prior, dict):
            raise ValueError("no configs map")
    except (OSError, ValueError, TypeError) as e:
        return {"baseline": prior_path, "ok": True,
                "error": f"baseline unreadable: {e}"}
    deltas, regressions, uncompared = {}, [], []
    ladder_compared = {}
    for name, cur in configs.items():
        if not isinstance(cur, dict):
            uncompared.append(name)
            continue
        key = next((k for k in RATE_KEYS if cur.get(k)), None)
        if key is None:
            uncompared.append(name)
            continue
        old = prior.get(name)
        base = None
        if (isinstance(old, dict) and old.get(key)
                and (cur.get("batch") is None or old.get("batch") is None
                     or cur["batch"] == old["batch"])):
            base = old
        elif cur.get("batch") is not None:
            # the round baseline is batch-mismatched (an OOM-ladder rung
            # change reshapes the tok/s denominator), errored, or
            # missing — a persisted same-batch ladder rung still gates
            # like-for-like
            sub = (ladder or {}).get(name, {}).get(str(cur["batch"]))
            if isinstance(sub, dict) and sub.get(key):
                base = sub
                ladder_compared[name] = {"batch": cur["batch"],
                                         "recorded": sub.get("recorded")}
        if base is None:
            uncompared.append(name)
            continue
        delta = cur[key] / base[key] - 1.0
        deltas[name] = round(delta, 4)
        if delta < -threshold:
            regressions.append(name)
    # a config the BASELINE had but this run lost entirely must be
    # visible too — a silent disappearance is a 100% regression
    uncompared += [n for n in prior if n not in configs]
    return {"baseline": os.path.basename(prior_path),
            "threshold": threshold, "deltas": deltas,
            "regressions": regressions, "uncompared": uncompared,
            "ladder_compared": ladder_compared,
            "ok": not regressions}


def gate_exit_code(regression_check: dict, compare_given: bool) -> int:
    """2 when the run must fail, else 0.

    The MFU floors, the decode-bandwidth floors (DECODE_FLOORS on
    hbm_frac), the per-kernel roofline floors (from the newest
    KERNELBENCH artifact), and the A/B sign checks are ABSOLUTE gates —
    they need no baseline, so they fail the run with or without
    ``--compare`` (CI without a BENCH_r*.json must not silently pass an
    efficiency regression).  The throughput-delta gate stays opt-in via
    ``--compare``: without a chosen baseline the comparison is recorded
    in the output but informational."""
    mfu = regression_check.get("mfu_floors") or {}
    dec = regression_check.get("decode_floors") or {}
    kfl = regression_check.get("kernel_floors") or {}
    cal = regression_check.get("floor_calibration") or {}
    exp = regression_check.get("export_cold_start") or {}
    absolute_failed = bool(regression_check.get("ab_failures")) or \
        not mfu.get("ok", True) or not dec.get("ok", True) or \
        not kfl.get("ok", True) or not cal.get("ok", True) or \
        not exp.get("ok", True)
    if absolute_failed or (compare_given
                           and not regression_check.get("ok", True)):
        return 2
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--compare", metavar="BENCH_rN.json", default=None,
                    help="regression-gate against this prior bench "
                         "artifact: exit 2 (after printing the JSON "
                         "line) if any config's throughput dropped more "
                         "than --threshold.  Without this flag the "
                         "newest BENCH_r*.json next to the script is "
                         "still compared and the verdict recorded in "
                         "the output but the delta gate never fails the "
                         "run; the ABSOLUTE gates (MFU floors, A/B "
                         "sign) need no baseline and fail it either "
                         "way.")
    ap.add_argument("--threshold", type=float, default=0.10,
                    help="fractional per-config drop that counts as a "
                         "regression (default 0.10)")
    opts = ap.parse_args(argv)

    from apex_tpu.utils import compile_cache
    from apex_tpu.utils.chip_peaks import chip_peak

    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(
            f"bench: no TPU: jax.default_backend() is {platform!r}; this "
            "script measures the chip and nothing else")
    peak = chip_peak().bf16_flops_per_s
    compile_cache.enable()
    # iters sized so the full suite fits the time budget: measurement
    # noise at these counts is ~1%, and the budget headroom keeps the
    # optional long-context configs from being skipped
    rn_args = dict(batch=256, size=224, warmup=4, iters=20)
    gpt_args = dict(batch=8, seq=2048, warmup=3, iters=12, tiny=False)
    bert_args = dict(batch=16, seq=512, warmup=3, iters=10, tiny=False)

    configs = {}
    t_start = time.perf_counter()
    #: the one JSON line must print before any driver timeout: optional
    #: configs are skipped (recorded as such) once the suite has been
    #: running this long.  The required configs (RN50 O2/O3, gpt-small,
    #: bert-large = the BASELINE set) always run.
    try:
        optional_budget_s = float(
            os.environ.get("APEX_TPU_BENCH_BUDGET_S", 2100))
    except ValueError:  # malformed env must not cost the round's artifact
        optional_budget_s = 2100.0

    def record(name, fn, optional=False, fresh=False, **kw):
        """One config at its stated shape, in this process; a config
        that raises fails the run."""
        if optional and time.perf_counter() - t_start > optional_budget_s:
            configs[name] = {"skipped": "bench time budget"}
            return
        if fresh:
            # drop cached executables + their donated buffers first: HBM
            # fragmentation from earlier configs tanks very-long-context
            # allocations (round-2: L16384 measured 3x slower after an
            # L8192 model in the same process)
            import gc
            jax.clear_caches()
            gc.collect()
        configs[name] = fn(peak=peak, **kw)

    record("resnet50_o2", bench_resnet, opt_level="O2", **rn_args)
    record("resnet50_o3", bench_resnet, opt_level="O3", **rn_args)
    record("gpt_small_o2", bench_gpt, **gpt_args)
    record("bert_large_lamb_o2", bench_bert, **bert_args)
    record("gpt_small_tpu_heads_o2", bench_gpt, optional=True,
           tpu_heads=True, **gpt_args)
    record("bert_large_tpu_heads_lamb_o2", bench_bert, optional=True,
           tpu_heads=True, **bert_args)
    # long-context single-chip: flash + remat keep the (L, L) scores
    # and activations out of HBM at 8K tokens of context
    record("gpt_small_tpu_heads_L8192_o2", bench_gpt, optional=True,
           tpu_heads=True, remat=True, batch=2, seq=8192, warmup=3,
           iters=15, tiny=False)
    # TPU-native input stem (space-to-depth, +8% over conv7+maxpool)
    record("resnet50_s2d_o2", bench_resnet, optional=True,
           opt_level="O2", s2d=True, **rn_args)
    # KV-cached decode throughput (bandwidth-bound; see
    # docs/source/models.rst) — serving latency (b1) and a small
    # serving batch (b8).  Ordered before the very-long-context
    # configs: fresh round evidence must not be the first thing the
    # time budget sheds.
    record("gpt_small_tpu_decode_b1", bench_generate, optional=True,
           batch=1, prefill=2048, new_tokens=256, warmup=1, iters=4,
           tiny=False)
    record("gpt_small_tpu_decode_b8", bench_generate, optional=True,
           batch=8, prefill=2048, new_tokens=256, warmup=1, iters=4,
           tiny=False)
    # int8 KV cache variant of the b8 decode config: half the
    # cache bytes -> the ceiling (derived from the int8 byte model
    # via roofline_expectation inside bench_generate) nearly
    # doubles at this context length; hbm_frac is gated by its own
    # DECODE_FLOORS entry (CPU-smoke-seeded; on-chip ratchet next
    # driver round)
    record("gpt_small_tpu_decode_kv8", bench_generate, optional=True,
           batch=8, prefill=2048, new_tokens=256, warmup=1, iters=4,
           tiny=False, kv_dtype="int8")
    # continuous-batching serve engine (apex_tpu.serve): offered-
    # load sweep c1 -> c8 over the paged KV cache, decode-step
    # p50/p99 latency + tokens/s; the latency-tail ab gate catches
    # a mid-serve retrace/host-sync (static-shape contract at
    # runtime)
    record("gpt_small_tpu_serve_c8", bench_serve, optional=True,
           warmup=1, iters=1, num_slots=8, prefill=512,
           new_tokens=128, tiny=False)
    # speculative decoding vs the plain engine on the SAME c8
    # stream (truncated layer-skip draft, k=4): gated on tokens
    # per decode dispatch strictly greater with spec on +
    # retraces==1 both arms — the latency-win claim of
    # apex_tpu.serve.spec as a bench gate (the full scenario grid
    # is SCENARIO_r*.json via tools/serve_scenarios.py)
    record("gpt_small_tpu_serve_spec_c8", bench_serve_spec,
           optional=True, warmup=1, iters=1, num_slots=8,
           prefill=512, new_tokens=128, spec_k=4, draft_layers=3,
           tiny=False)
    # disaggregated prefill/decode fleet vs the monolithic engine
    # at EQUAL resources and the same c16 request stream: prefill
    # on its own mesh slice, 2 decode replicas on disjoint slices,
    # KV shipped between them; gated on the DistServe claim
    # (disagg decode p99 <= mono p99) via ab_ok.  Skips (recorded)
    # on hosts with fewer than 3 addressable devices.
    record("gpt_small_tpu_serve_disagg_c16", bench_serve_disagg,
           optional=True, warmup=1, iters=1, n_replicas=2,
           slots_per_replica=8, prefill=512, new_tokens=128,
           tiny=False)
    # cross-request prefix sharing vs no sharing on the SAME c16
    # shared-system-prompt stream at equal devices: gated on the
    # deterministic counts (sharing arm dispatches fewer prefill
    # tokens + admits more requests per resident block, retraces==1
    # both arms) via ab_ok; the committed PREFIXCACHE_r*.json
    # (tools/serve_prefix.py) carries the spans + bitwise drill
    record("gpt_small_tpu_serve_prefix_c16", bench_serve_prefix,
           optional=True, warmup=1, iters=1, num_slots=16,
           prefill=512, new_tokens=128, tiny=False)
    # 16K context (fresh: clearing caches avoids the HBM-
    # fragmentation slowdown of back-to-back long-context models in
    # one process); the fused one-pass attention backward still
    # runs (805 MB dq partials, under the 1 GiB budget)
    record("gpt_small_tpu_heads_L16384_o2", bench_gpt, optional=True,
           fresh=True, tpu_heads=True, remat=True, batch=1,
           seq=16384, warmup=2, iters=8, tiny=False)
    # bigger matmuls lift MFU: ~368M params, 8x128 heads, at its
    # stated batch of 8 or not at all
    record("gpt_medium_tpu_o2", bench_gpt, optional=True, fresh=True,
           tpu_heads="medium", batch=8, seq=2048, warmup=3, iters=12,
           tiny=False)

    # Headline = the parity configs only (the conv7-stem model the
    # BASELINE derivation refers to); the s2d variant stays a
    # configs-map entry like the TPU-heads transformers.
    best_lvl, best = max(
        ((k, configs[k]) for k in ("resnet50_o2", "resnet50_o3")),
        key=lambda kv: kv[1]["img_s"])

    here = os.path.dirname(os.path.abspath(__file__))
    prior = opts.compare or find_prior_bench(here)
    ladder = load_ladder_baselines(here)
    # The gate record ALWAYS exists: the MFU floors and A/B sign checks
    # are absolute (no baseline needed), so a missing BENCH_r*.json must
    # not silently discard them.
    regression_check = (compare_configs(prior, configs, opts.threshold,
                                        ladder=ladder)
                       if prior else {"baseline": None, "ok": True})
    # both floor gates consult the committed BENCH_VARIANCE_r*.json
    # through derive_floor_bands (hand tables as the frozen fallback)
    mfu_check = check_mfu_floors(configs, search_dir=here)
    # decode-bandwidth floors: absolute like the MFU floors (hbm_frac
    # against the roofline ceiling)
    decode_check = check_decode_floors(configs, search_dir=here)
    # the kernel-level floors ride the committed KERNELBENCH artifact
    # (checked regardless of this run's platform: the artifact carries
    # its own; a non-TPU artifact records skipped)
    kernel_floor_check = check_kernel_floor_artifact(here)
    # the serve configs' own A/B gates (ab_ok)
    ab_failures = [n for n, v in configs.items()
                   if isinstance(v, dict) and v.get("ab_ok") is False]
    # floors must sit under the cost-model ceiling (the lint analog:
    # apex_tpu.analysis.cost — a roofline fraction or MFU floor above 1,
    # or a committed measurement above physics, is a calibration bug)
    calibration_check = check_floor_calibration(here)
    # the serve cold-start gate rides the committed EXPORT artifact
    # (load <= 0.5x compile; platform-independent — the artifact
    # carries its own recording host), and the configs map records the
    # same numbers so the cold-start story shows up next to the
    # throughput it buys
    export_check = check_export_cold_start(here)
    if export_check is not None and "error" not in export_check:
        configs["serve_cold_start"] = {
            "source": export_check["artifact"],
            "lane": export_check["lane"],
            "compile_s": export_check["compile_s"],
            "load_s": export_check["load_s"],
            "load_ratio": export_check["load_ratio"],
            "budget": export_check["budget"]}
    regression_check["mfu_floors"] = mfu_check
    regression_check["decode_floors"] = decode_check
    regression_check["kernel_floors"] = kernel_floor_check
    regression_check["floor_calibration"] = calibration_check
    regression_check["export_cold_start"] = export_check
    regression_check["ab_failures"] = ab_failures
    regression_check["ok"] = bool(
        regression_check["ok"] and not ab_failures
        and mfu_check["ok"] and decode_check["ok"]
        and (kernel_floor_check is None or kernel_floor_check["ok"])
        and calibration_check["ok"]
        and (export_check is None or export_check["ok"]))
    if regression_check["ok"]:
        # a gate-failing run must not become the future like-for-like
        # baseline (a regressed rung would mask the loss once batches
        # churn) — persist rungs only from green runs
        update_ladder_baselines(here, configs)

    print(json.dumps({
        "metric": f"resnet50_amp_{best_lvl.split('_')[1]}_fused_adam_"
                  f"throughput_{platform}_b{best['batch']}_{best['px']}px",
        "value": best["img_s"],
        "unit": "img/s",
        "vs_baseline": round(best["img_s"] / BASELINE_IMG_PER_SEC_PER_CHIP,
                             4),
        "mfu": best["mfu"],
        "device": {"platform": platform,
                   "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())},
        "configs": configs,
        "regression_check": regression_check,
    }))
    rc = gate_exit_code(regression_check, bool(opts.compare))
    if rc:
        # an unreadable/missing baseline early-returns a dict WITHOUT
        # regressions/deltas — the absolute gates must still report
        # instead of dying on a KeyError after the chip time is spent;
        # with no baseline at all, name the absolute gates rather than
        # pointing the triage at a nonexistent comparison
        base = regression_check.get("baseline")
        vs = f"vs {base}" if base else "(absolute gates, no baseline)"
        print(f"bench: gate failed {vs}: throughput "
              f"regressions {regression_check.get('regressions', [])}, "
              f"MFU-floor violations "
              f"{mfu_check['violations']}, decode-floor "
              f"violations {decode_check['violations']}, "
              f"kernel-floor violations "
              f"{(kernel_floor_check or {}).get('violations', [])}, "
              f"A/B sign failures {ab_failures}, cold-start gate "
              f"{'FAILED' if export_check and not export_check['ok'] else 'ok'} "
              f"(deltas {regression_check.get('deltas', {})})",
              file=sys.stderr)
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
