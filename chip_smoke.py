"""chip_smoke.py — the quickest proof that apex_tpu still starts on the chip.

One process drives the system's main path once through the public entry
points at the full width of ``gpt_small_tpu`` (134M parameters, 6 heads
of 128) with weights made from a seed:

1. *train*: ``GPTModel`` + ``amp.initialize(FusedAdam, "O2")`` +
   ``jax.jit(amp.make_train_step(...), donate_argnums=(0,))`` at batch
   8 x sequence 2048 on a seeded periodic token stream, until the loss
   is under ``MAX_FINAL_LOSS``.  The compiled step's HLO must hold the
   Mosaic custom calls of the flash and LayerNorm kernels.
2. *serve*: the trained weights in ``ServeEngine`` (8 slots, block 16,
   prefill 512 in chunks of 128, 128 new tokens): one request alone,
   then eight of mixed length.  Every answer is complete, continues the
   period the trainer taught, took one decode trace, and one equals
   ``generate()`` on the same prompt and weights.
3. *kernels*: every production Pallas family is compiled by Mosaic at a
   production-sized shape and compared with its jnp reference within
   the unit tests' tolerances; among them the families of the
   DeepSeek-V3-shaped block: the LayerNorm kernels in their RMS mode,
   flash attention at 192/128, the held-experts layer on jax's megablox
   kernels, and one tiny block whole.

``--devices 4`` runs phase 1 alone, data-parallel under ``shard_map``
over a ``("data",)`` mesh of four chips at batch 8 per chip.

There is no fallback: no TPU, a jnp kernel selection, a missing kernel
or any failed check ends the run with a non-zero exit code and no
result line.  ``--cpu-dry-run`` checks the control flow at ``gpt_tiny``
size on the CPU (Pallas in interpret mode); it reports ``"platform":
"cpu"``, no timings, and is never taken by default or from the
environment.

The last two lines of standard output are one JSON object each: the
report (versions, compile-cache directory, native extension, and per
phase the compile and run seconds, losses, kernels found, peak bytes),
then the verdict, with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

import argparse
import contextlib
import dataclasses
import importlib.metadata
import itertools
import json
import os
import sys
import time
import traceback

import numpy as np

SEED = 21
PERIOD = 16           # tokens in the taught cycle
LR = 1e-3
#: steps taken, and the loss the last one must be under.  The first chip
#: run of PR 21 (one v5e) went 10.98 at step 0, 2.12 at 5, 0.018 at 10,
#: 0.004 at 30, 0.002 at 40: the threshold leaves a factor of 25.
TRAIN_STEPS = 40
MAX_FINAL_LOSS = 0.05
#: overflow-skipped steps are tolerated only while the dynamic loss
#: scale settles
OVERFLOW_GRACE_STEPS = 5


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Shape:
    """Sizes of one run: the full one, or the CPU dry run's."""
    batch: int
    seq: int
    steps: int
    max_final_loss: float
    slots: int
    block: int
    prefill: int
    prefill_chunk: int
    new_tokens: int
    optimizer_elems: int      # flat fp32 elements of the optimizer cases
    ln_rows: int
    ln_features: tuple
    long_seq: int             # rows of the long flash head


FULL = Shape(batch=8, seq=2048, steps=TRAIN_STEPS,
             max_final_loss=MAX_FINAL_LOSS, slots=8, block=16, prefill=512,
             prefill_chunk=128, new_tokens=128, optimizer_elems=1 << 24,
             ln_rows=8 * 2048, ln_features=(768, 1024, 4096), long_seq=8192)
DRY = Shape(batch=8, seq=64, steps=60, max_final_loss=1.0, slots=2,
            block=4, prefill=16, prefill_chunk=8, new_tokens=8,
            optimizer_elems=1 << 15, ln_rows=48, ln_features=(128, 256),
            long_seq=128)


def token_cycle(vocab_size: int) -> np.ndarray:
    """The ``PERIOD`` distinct token ids, drawn from the seed, that the
    stream repeats."""
    return np.random.RandomState(SEED).choice(
        vocab_size, PERIOD, replace=False).astype(np.int32)


def stream(cycle: np.ndarray, phase: int, length: int) -> np.ndarray:
    return cycle[(phase + np.arange(length)) % PERIOD]


def peak_bytes(device) -> "int | None":
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def timed(dry: bool, seconds: float) -> "float | None":
    """A wall time for the result line; the CPU dry run reports none."""
    return None if dry else round(seconds, 2)


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------

def phase_train(cfg, shape: Shape, n_devices: int, dry: bool):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import amp
    from apex_tpu.analysis import spmd
    from apex_tpu.models.gpt import GPTModel, lm_loss
    from apex_tpu.ops import mosaic_kernels
    from apex_tpu.ops.pallas.flash_attention import (GRID_SCOPE,
                                                     RESIDENT_SCOPE)
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel

    model = GPTModel(cfg)
    cycle = token_cycle(cfg.vocab_size)
    rows = shape.batch * n_devices
    ids = jnp.asarray(np.stack(
        [stream(cycle, 5 * r, shape.seq) for r in range(rows)]))
    params = model.init(jax.random.PRNGKey(SEED), ids[:1, :8])["params"]
    a = amp.initialize(optimizer=FusedAdam(lr=LR), opt_level="O2",
                       verbosity=0)
    state = a.init(params)

    def loss_fn(p, xb):
        logits = model.apply({"params": p}, xb)
        return lm_loss(logits[:, :-1], xb[:, 1:])

    if n_devices == 1:
        step = jax.jit(amp.make_train_step(a, loss_fn), donate_argnums=(0,))
    else:
        mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
        ddp = DistributedDataParallel(axis_name="data")
        inner = amp.make_train_step(a, loss_fn, axis_name="data",
                                    reduce_fn=ddp.reduce)

        def sharded(s, xb):
            s, m = inner(s, xb)
            return s, dict(m, loss=jax.lax.pmean(m["loss"], "data"))

        step = jax.jit(shard_map(sharded, mesh=mesh,
                                 in_specs=(P(), P("data")),
                                 out_specs=(P(), P())),
                       donate_argnums=(0,))

    t0 = time.perf_counter()
    compiled = step.lower(state, ids).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    kernels = mosaic_kernels(hlo)
    print(f"train: compiled ({timed(dry, compile_s)} s); Mosaic kernels "
          f"in the step: {kernels}", flush=True)
    if not dry:
        # 2048 causal rows of 128 are a resident head since PR 28 (under
        # a scoped-VMEM limit of their own): the one-pass backward under
        # the scope that says so, no dq / dkv pair and no grid walk
        missing = {"flash_fwd", "flash_bwd_fused", "layer_norm_fwd",
                   "layer_norm_bwd"} - set(kernels)
        walks = [s for s in (RESIDENT_SCOPE, GRID_SCOPE) if s in hlo]
        check(not missing and walks == [RESIDENT_SCOPE],
              f"train step HLO lacks Mosaic kernels: missing {missing}; "
              f"found {kernels}; its flash calls run under {walks}, not "
              f"{RESIDENT_SCOPE} alone")

    all_reduce_groups = None
    if n_devices > 1:
        all_reduce_groups = sorted({
            e["replica_groups"] for e in spmd.collective_schedule(hlo)
            if e["kind"] == "all-reduce"})
        whole_mesh = {"{{" + ",".join(map(str, range(n_devices))) + "}}",
                      f"[1,{n_devices}]<=[{n_devices}]"}
        check(whole_mesh & set(all_reduce_groups),
              f"no all-reduce over all {n_devices} replicas in the "
              f"compiled step; replica groups: {all_reduce_groups}")

    t0 = time.perf_counter()
    history = []
    for _ in range(shape.steps):
        state, metrics = compiled(state, ids)
        history.append(metrics)
    history = jax.device_get(history)       # one fetch, after the loop
    run_s = time.perf_counter() - t0

    losses = [float(m["loss"]) for m in history]
    skipped = [i for i, m in enumerate(history) if bool(m["overflow"])]
    print("train: loss " + " ".join(
        f"{i}:{losses[i]:.3f}" for i in range(0, shape.steps, 5))
        + f" final:{losses[-1]:.4f}", flush=True)
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < shape.max_final_loss,
          f"final loss {losses[-1]:.4f} is not under "
          f"{shape.max_final_loss} after {shape.steps} steps")
    check(all(i < OVERFLOW_GRACE_STEPS for i in skipped),
          f"overflow-skipped steps after the first "
          f"{OVERFLOW_GRACE_STEPS}: {skipped}")

    result = {
        "compile_s": timed(dry, compile_s), "run_s": timed(dry, run_s),
        "steps": shape.steps, "batch": rows, "seq": shape.seq,
        "first_loss": round(losses[0], 4),
        "final_loss": round(losses[-1], 4),
        "overflow_skipped_steps": skipped,
        "final_loss_scale": float(history[-1]["loss_scale"]),
        "kernels_found": kernels,
        "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
    }
    if n_devices > 1:
        result["all_reduce_replica_groups"] = all_reduce_groups
        result["per_device_memory"] = [
            {"id": d.id,
             "bytes_in_use": (d.memory_stats() or {}).get("bytes_in_use"),
             "peak_bytes_in_use": peak_bytes(d)}
            for d in jax.devices()[:n_devices]]
        if not dry:
            check(all(m["peak_bytes_in_use"]
                      for m in result["per_device_memory"]),
                  f"a device of the mesh holds no memory: "
                  f"{result['per_device_memory']}")
    return result, a.model_params(state), cycle


# ---------------------------------------------------------------------------
# phase 2: serve
# ---------------------------------------------------------------------------

def phase_serve(cfg, params, cycle, shape: Shape, dry: bool):
    import jax

    from apex_tpu.models.generate import generate
    from apex_tpu.obs.metrics import Registry
    from apex_tpu.serve import Request, ServeConfig, ServeEngine

    blocks_per_slot = -(-(shape.prefill + shape.new_tokens) // shape.block)
    scfg = ServeConfig(
        num_slots=shape.slots, block_size=shape.block,
        num_blocks=shape.slots * blocks_per_slot + 1,
        max_blocks_per_slot=blocks_per_slot,
        prefill_chunk=shape.prefill_chunk)
    engine = ServeEngine(params, cfg, scfg, registry=Registry())

    def request(uid, phase, prompt_len):
        return Request(uid=uid, prompt=stream(cycle, phase, prompt_len),
                       max_new_tokens=shape.new_tokens)

    # round 1: one request alone, at the full prefill length
    solo = request("solo", 0, shape.prefill)
    t0 = time.perf_counter()
    engine.submit(solo)
    engine.run()
    first_round_s = time.perf_counter() - t0

    # round 2: every slot busy, prompt lengths mixed, each request on
    # its own phase of the cycle
    mixed = [request(f"mixed{i}", 1 + i,
                     shape.prefill * (2 + (3 * i) % 7) // 8)
             for i in range(shape.slots)]
    t0 = time.perf_counter()
    for r in mixed:
        engine.submit(r)
    outputs = engine.run()
    second_round_s = time.perf_counter() - t0

    wrong = []
    for r in [solo] + mixed:
        out = np.asarray(outputs[r.uid])
        check(out.shape == (shape.new_tokens,),
              f"{r.uid}: {out.shape[0]} tokens of {shape.new_tokens}")
        phase = int(np.flatnonzero(cycle == r.prompt[-1])[0]) + 1
        if not np.array_equal(out, stream(cycle, phase, shape.new_tokens)):
            wrong.append(r.uid)
    check(not wrong, f"answers that leave the taught period: {wrong}")
    check(engine.trace_counts["decode"] == 1,
          f"decode step traced {engine.trace_counts['decode']} times")

    # the same prompt through generate(): a full-length prefill through
    # the flash kernel, then the monolithic-cache decode loop
    reference = np.asarray(generate(
        params, cfg, solo.prompt[None], shape.new_tokens))[0, shape.prefill:]
    check(np.array_equal(reference, np.asarray(outputs["solo"])),
          f"serve and generate() disagree on the solo request: "
          f"{np.asarray(outputs['solo']).tolist()} vs {reference.tolist()}")

    print(f"serve: {1 + len(mixed)} requests complete, "
          f"trace counts {engine.trace_counts}", flush=True)
    return {
        # the first round's wall time is dominated by compiling the
        # prefill, sampling and decode programs
        "compile_s": timed(dry, first_round_s),
        "run_s": timed(dry, second_round_s),
        "requests": 1 + len(mixed),
        "tokens": (1 + len(mixed)) * shape.new_tokens,
        "prompt_lengths": [len(r.prompt) for r in [solo] + mixed],
        "trace_counts": dict(engine.trace_counts),
        "matches_generate": True,
        "peak_bytes_in_use": peak_bytes(jax.devices()[0]),
    }


# ---------------------------------------------------------------------------
# phase 3: kernels
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def environ(**overrides):
    """Kernel selection is read from the environment at trace time
    (``apex_tpu.ops.use_pallas``), the axis the unit tests compare
    Pallas against jnp on."""
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def kernel_cases(shape: Shape):
    """``(name, expected kernels, fn, args, rtol, atol, env)`` per
    family: ``fn(*args)`` is traced once as the environment selects
    (Pallas) and once under ``APEX_TPU_KERNELS=jnp``.  Tolerances are
    the unit tests'.  Beside kernel names, ``expected`` may hold the
    scope a flash call has to run under (``flash_resident`` /
    ``flash_grid``)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.normalization import fused_layer_norm_affine
    from apex_tpu.ops import use_pallas
    from apex_tpu.ops.multi_tensor import (
        DEFAULT_CHUNK_SIZE, multi_tensor_axpby, multi_tensor_l2norm,
        multi_tensor_scale)
    from apex_tpu.ops.pallas.lamb_kernels import (
        grown_chunk, packed_lamb_stage2)
    from apex_tpu.optimizers import fused_adam, fused_lamb
    from apex_tpu.optimizers.fused_adam import adam_step

    n = shape.optimizer_elems
    draws = itertools.count()

    def key():
        return jax.random.fold_in(jax.random.PRNGKey(SEED), next(draws))

    def normal(*dims, dtype=jnp.float32):
        return jax.random.normal(key(), dims, dtype)

    def uniform(*dims):
        return jax.random.uniform(key(), dims)

    def tree(sizes):
        return {f"p{i}": normal(*s) for i, s in enumerate(sizes)}

    def optimizer_case(tx, sizes):
        params, grads = tree(sizes), tree(sizes)
        state = tx.init(params)
        # second moments off zero, so the update is a generic point
        state = state._replace(v=jax.tree.map(jnp.square, tree(sizes)))

        def fn(grads, state, params):
            updates, new = tx.update(grads, state, params)
            return updates, new.m, new.v
        return fn, (grads, state, params)

    # one transformer layer's worth of leaves, summing to about n
    side = max(128, int((n / 12) ** 0.5) // 128 * 128)
    layer = [(side, 4 * side), (4 * side,), (4 * side, side), (side,),
             (side, 3 * side), (3 * side,), (side, side), (side,)]

    def adam_leaf(p, m, v, g):
        return adam_step(p, m, v, g, lr=1e-3, beta1=0.9, beta2=0.999,
                         eps=1e-8, step=jnp.asarray(3, jnp.int32), scale=2.0,
                         weight_decay=0.01, p_copy_dtype=jnp.bfloat16)
    # moments drawn as tests/l0/test_fused_adam.py draws them: |m| stays
    # within a few sqrt(v), as Adam keeps it
    yield ("adam", {"adam"}, adam_leaf,
           (normal(n), uniform(n), uniform(n), normal(n)), 1e-5, 1e-6, {})
    adam = fused_adam(learning_rate=1e-3, weight_decay=0.01, scale=128.0)
    yield ("adam_tree", {"adam_tree"}, *optimizer_case(adam, layer),
           2e-7, 1.2e-7, {"APEX_TPU_ADAM_PACKED": "1"})
    lamb = fused_lamb(learning_rate=1e-3, weight_decay=0.01)
    yield ("lamb_stage1", {"lamb_stage1"}, *optimizer_case(lamb, layer),
           2e-5, 1e-7, {})

    chunk = grown_chunk(n)
    ratio = uniform(n // chunk) * 1e-2

    def stage2(p, u, ratio):
        if use_pallas():
            return packed_lamb_stage2(p, u, ratio, chunk_size=chunk)
        return p - jnp.repeat(ratio, chunk) * u
    yield ("lamb_stage2", {"lamb_stage2"}, stage2,
           (normal(n), normal(n), ratio), 1e-6, 1e-7, {})

    xs = [normal(n // 2), normal(n // 4, dtype=jnp.bfloat16), normal(1000)]
    ys = [normal(*x.shape) for x in xs]
    yield ("mt_scale", {"mt_scale"},
           lambda xs: multi_tensor_scale(DEFAULT_CHUNK_SIZE, [xs], 0.5),
           (xs,), 1e-2, 1e-6, {})
    yield ("mt_axpby", {"mt_axpby"},
           lambda xs, ys: multi_tensor_axpby(
               DEFAULT_CHUNK_SIZE, [xs, ys], 0.999, 0.001),
           (xs, ys), 1e-2, 1e-6, {})
    fp32 = [normal(n // 2), normal(n // 4), normal(n // 8)]
    yield ("mt_l2norm", {"mt_sumsq"},
           lambda xs: multi_tensor_l2norm(DEFAULT_CHUNK_SIZE, [xs])[0],
           (fp32,), 1e-5, 0.0, {})
    yield ("mt_l2norm_per_tensor", {"mt_sumsq_per_chunk"},
           lambda xs: multi_tensor_l2norm(DEFAULT_CHUNK_SIZE, [xs],
                                          per_tensor=True),
           (fp32,), 1e-5, 0.0, {})

    yield from latent_moe_cases(shape, normal, uniform)

    for features in shape.ln_features:
        for dtype, rtol, atol in ((jnp.bfloat16, 2e-2, 5e-2),
                                  (jnp.float32, 1e-3, 1e-4)):
            x = normal(shape.ln_rows, features, dtype=dtype)
            dy = normal(shape.ln_rows, features, dtype=dtype)
            w = 1.0 + 0.1 * normal(features)
            b = 0.1 * normal(features)

            def ln(x, w, b, dy, features=features):
                y, vjp = jax.vjp(
                    lambda x, w, b: fused_layer_norm_affine(
                        x, w, b, features), x, w, b)
                return (y,) + vjp(dy)
            yield (f"layer_norm_{features}_{jnp.dtype(dtype).name}",
                   {"layer_norm_fwd", "layer_norm_bwd"}, ln, (x, w, b, dy),
                   rtol, atol, {})


def latent_moe_cases(shape: Shape, normal, uniform):
    """The Pallas families of the DeepSeek-V3-shaped block
    (``apex_tpu.models.deepseek_v3``), each forward and backward: the
    LayerNorm kernels in their RMS mode, flash attention with q, k at
    192 lanes and v at 128, the held-experts layer on jax's megablox
    kernels under a fixed routing, and one tiny block whole (its loss
    and the norm of its gradient: a token that changes expert between
    the two kernel selections moves single entries, not these)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models.deepseek_v3 import (DeepseekV3Block,
                                             DeepseekV3Config)
    from apex_tpu.normalization import fused_rms_norm_affine
    from apex_tpu.attention import attention
    from apex_tpu.ops.pallas.flash_attention import RESIDENT_SCOPE
    from apex_tpu.ops.rope import rope_tables_interleaved
    from apex_tpu.parallel import moe

    rows, seq = shape.ln_rows, shape.seq
    features = shape.ln_features[-1]
    x, dy = (normal(rows, features, dtype=jnp.bfloat16) for _ in range(2))

    def rms(x, w, dy):
        y, vjp = jax.vjp(lambda x, w: fused_rms_norm_affine(
            x, w, features, 1e-6), x, w)
        return (y,) + vjp(dy)
    yield (f"rms_norm_{features}_bfloat16",
           {"layer_norm_fwd", "layer_norm_bwd"}, rms,
           (x, 1.0 + 0.1 * normal(features), dy), 2e-2, 5e-2, {})

    q, k = (normal(2, seq, 4, 192, dtype=jnp.bfloat16) for _ in range(2))
    v, do = (normal(2, seq, 4, 128, dtype=jnp.bfloat16) for _ in range(2))

    def flash(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: attention(
            q, k, v, causal=True, block_q=min(512, seq),
            block_k=min(512, seq)), q, k, v)
        return (out,) + vjp(do)
    yield ("flash_192_128", {"flash_fwd", "flash_bwd_fused"}, flash,
           (q, k, v, do), 3e-2, 5e-2, {})

    # the same widths at the length of the benchmark's kanana cell, blocks
    # left to the call: a head over Mosaic's default scoped-VMEM limit
    # stays resident under one of its own and walks q on the grid
    rows = shape.long_seq
    q, k = (normal(1, rows, 2, 192, dtype=jnp.bfloat16) for _ in range(2))
    v, do = (normal(1, rows, 2, 128, dtype=jnp.bfloat16) for _ in range(2))

    def flash_long(q, k, v, do):
        out, vjp = jax.vjp(lambda q, k, v: attention(
            q, k, v, causal=True), q, k, v)
        return (out,) + vjp(do)
    yield ("flash_192_128_long_head",
           {"flash_fwd", "flash_bwd_fused", RESIDENT_SCOPE}, flash_long,
           (q, k, v, do), 3e-2, 5e-2, {})

    tokens, d, f, experts, held = 2 * seq, 256, 128, 8, 4
    p = {"gate": 0.1 * normal(held, d, f), "up": 0.1 * normal(held, d, f),
         "down": 0.1 * normal(held, f, d)}
    routing = moe.route(normal(tokens, experts), 2, scoring="sigmoid",
                        renormalize=True)
    xt, dyt = normal(tokens, d), normal(tokens, d)

    def held_experts(p, x, weights, dy):
        y, vjp = jax.vjp(lambda p, x, w: moe.moe_apply(
            moe.gated_ffn, p, x, routing._replace(weights=w),
            n_experts=experts, first=2)[0], p, x, weights)
        return (y,) + vjp(dy)
    yield ("held_experts", {"gmm", "tgmm"}, held_experts,
           (p, xt, routing.weights, dyt), 2e-2, 2e-2, {})

    c = DeepseekV3Config(
        hidden_size=256, num_heads=2, moe_intermediate_size=128,
        n_routed_experts=experts, n_routed_experts_held=held,
        num_experts_per_tok=2, kv_lora_rank=128)
    block = DeepseekV3Block(c, dense=False)
    xb = normal(1, seq, 256)
    rope = rope_tables_interleaved(jnp.arange(seq)[None], c.qk_rope_head_dim,
                                   c.rope_theta)
    params = block.init(jax.random.PRNGKey(SEED), xb, rope)["params"]

    def one_block(params, x):
        loss, grads = jax.value_and_grad(lambda p: jnp.mean(jnp.square(
            block.apply({"params": p}, x, rope)[0])))(params)
        return loss, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                  for g in jax.tree.leaves(grads)))
    yield ("deepseek_v3_block",
           {"layer_norm_fwd", "layer_norm_bwd", "flash_fwd", "gmm", "tgmm"},
           one_block, (params, xb), 3e-2, 1e-6, {})


def phase_kernels(shape: Shape, dry: bool):
    import jax
    import jax.numpy as jnp

    from apex_tpu.ops import mosaic_kernels
    from apex_tpu.ops.pallas.flash_attention import (GRID_SCOPE,
                                                     RESIDENT_SCOPE)

    @jax.jit
    def worst(got, want, rtol, atol):
        """max over leaves of |got - want| / (atol + rtol |want|); a
        half-precision leaf (a bf16 parameter copy) is never held
        tighter than one unit in its last place."""
        def leaf(g, w):
            r = rtol
            if jnp.issubdtype(g.dtype, jnp.floating):
                r = jnp.maximum(rtol, float(jnp.finfo(g.dtype).eps))
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return jnp.max(jnp.abs(g - w) / (atol + r * jnp.abs(w)
                                             + 1e-30))
        return jnp.max(jnp.stack(jax.tree.leaves(
            jax.tree.map(leaf, got, want))))

    t0 = time.perf_counter()
    families, failures = {}, []
    for name, expected, fn, args, rtol, atol, env in kernel_cases(shape):
        try:
            with environ(**env):
                compiled = jax.jit(
                    lambda *a: fn(*a)).lower(*args).compile()
            text = compiled.as_text()
            found = mosaic_kernels(text) + [
                s for s in (RESIDENT_SCOPE, GRID_SCOPE) if s in text]
            got = compiled(*args)
            with environ(APEX_TPU_KERNELS="jnp"):
                want = jax.jit(lambda *a: fn(*a))(*args)
            err = float(worst(got, want, rtol, atol))
            finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                         for x in jax.tree.leaves(got))
            families[name] = {"kernels_found": found,
                              "err_over_tolerance": round(err, 4)}
            if not finite:
                failures.append(f"{name}: non-finite output")
            if not err <= 1.0:
                failures.append(f"{name}: {err:.3g}x its tolerance "
                                f"(rtol {rtol}, atol {atol})")
            if not dry and not expected <= set(found):
                failures.append(f"{name}: expected Mosaic kernels "
                                f"{sorted(expected)}, found {found}")
        except Exception as e:  # noqa: BLE001 - collected, then fatal
            traceback.print_exc()
            failures.append(f"{name}: {type(e).__name__}: "
                            f"{str(e)[:2000]}")
        print(f"kernels: {name}: {families.get(name, 'FAILED')}",
              flush=True)
    check(not failures,
          "kernel families failed:\n  " + "\n  ".join(failures))
    return {"run_s": timed(dry, time.perf_counter() - t0),
            "families": families,
            "peak_bytes_in_use": peak_bytes(jax.devices()[0])}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4),
                    help="4: phase 1 alone, data-parallel over four chips")
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="gpt_tiny on the CPU, control flow only")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run

    if dry:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["APEX_TPU_KERNELS"] = "pallas"      # interpret mode
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    import jax

    platform = jax.default_backend()
    if platform != ("cpu" if dry else "tpu"):
        print(f"chip_smoke: no TPU: jax.default_backend() is {platform!r} "
              f"(devices: {jax.devices()}); nothing was run",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.devices:
        print(f"chip_smoke: --devices {args.devices} needs that many "
              f"chips in this process; JAX sees {len(jax.devices())}",
              file=sys.stderr)
        return 1

    import apex_tpu._native as native
    from apex_tpu import ops
    from apex_tpu.models.gpt import gpt_small_tpu, gpt_tiny
    from apex_tpu.utils import compile_cache

    check(ops.use_pallas() and (dry or ops.on_tpu()),
          f"kernel selection is not Pallas-on-TPU: APEX_TPU_KERNELS="
          f"{ops.kernel_mode()!r}, on_tpu={ops.on_tpu()}")
    cache_dir = compile_cache.enable()
    cfg, shape = (gpt_tiny(), DRY) if dry else (gpt_small_tpu(), FULL)

    device = jax.devices()[0]
    verdict = {
        "ok": True,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
    }
    report = {
        "dry_run": dry,
        "model": "gpt_tiny" if dry else "gpt_small_tpu",
        "devices_used": args.devices,
        "versions": {"jax": jax.__version__,
                     "jaxlib": importlib.metadata.version("jaxlib"),
                     "libtpu": importlib.metadata.version("libtpu")},
        "compile_cache_dir": cache_dir,
        "native": {"available": native.available,
                   "import_err": None if native.import_err is None
                   else repr(native.import_err)},
        "phases": {},
    }
    print(f"chip_smoke: {verdict['device']} {report['versions']} "
          f"native={report['native']} cache={cache_dir}", flush=True)

    train, params, cycle = phase_train(cfg, shape, args.devices, dry)
    report["phases"]["train"] = train
    if args.devices == 1:
        report["phases"]["serve"] = phase_serve(cfg, params, cycle, shape,
                                                dry)
        report["phases"]["kernels"] = phase_kernels(shape, dry)
    print(json.dumps({"report": report}))
    # the last line: the verdict and nothing else
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
