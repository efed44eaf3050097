"""GPT causal-LM training demo — the long-context workload.

Beyond the reference (2019-era apex has no LM / long-context story):
trains :class:`apex_tpu.models.gpt.GPTModel` on synthetic token streams
under amp O2 with FusedAdam; ``--seq-parallel`` shards the sequence over a
mesh axis with ring attention (rope positions stay global), ``--remat``
rematerializes each block for HBM headroom at long L.

Run anywhere:
    python examples/gpt_lm.py --steps 20 --seq-len 256
    python examples/gpt_lm.py --seq-parallel --devices 4 --force-cpu
On a real TPU slice, drop --force-cpu and the mesh spans the chips.
"""

# Make the repo root importable when run as "python examples/<name>.py"
# without an install (the environment forbids pip install).
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="tiny",
                   choices=["tiny", "small", "small-tpu"],
                   help="small-tpu = gpt-small with the TPU-native 6x128 "
                        "head geometry (same params, ~30%% faster steps)")
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--remat", action="store_true")
    p.add_argument("--scan-layers", action="store_true")
    p.add_argument("--data", default="synthetic",
                   choices=["synthetic", "pysrc"],
                   help="pysrc = byte-level LM over the Python standard "
                        "library sources (real text, available offline); "
                        "fresh random windows every step, reports "
                        "bits-per-byte and a greedy sample")
    p.add_argument("--sample-bytes", type=int, default=96,
                   help="greedy continuation length printed after "
                        "--data pysrc training")
    p.add_argument("--seq-parallel", action="store_true",
                   help="shard the sequence over a mesh axis (ring "
                        "attention)")
    p.add_argument("--devices", type=int, default=4,
                   help="mesh size for --seq-parallel")
    p.add_argument("--force-cpu", action="store_true")
    p.add_argument("--print-freq", type=int, default=10)
    return p.parse_args()


def main():
    args = parse_args()
    if args.force_cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
    import dataclasses
    import jax
    from jax import shard_map
    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import amp
    from apex_tpu.models.gpt import (
        GPTModel, gpt_small, gpt_small_tpu, gpt_tiny, lm_loss)
    from apex_tpu.optimizers import FusedAdam

    cfg = {"tiny": gpt_tiny, "small": gpt_small,
           "small-tpu": gpt_small_tpu}[args.size]()
    cfg = dataclasses.replace(cfg, remat=args.remat,
                              scan_layers=args.scan_layers)

    b, l = args.batch_size, args.seq_len
    rng = np.random.RandomState(0)
    corpus = None
    if args.data == "pysrc":
        if args.seq_parallel:
            raise SystemExit("--data pysrc supports the local path only")
        # real text available in any environment: the stdlib's own source
        corpus = _load_pysrc_corpus()
        cfg = dataclasses.replace(cfg, vocab_size=256)  # byte-level
        print(f"pysrc corpus: {len(corpus) / 1e6:.1f}M bytes")
        ids = _sample_windows(corpus, rng, b, l)
    else:
        # synthetic structured stream: next token = (token + step) %
        # vocab, so the LM has signal to fit and the loss visibly descends
        base = rng.randint(0, cfg.vocab_size, (b, 1))
        ids = jnp.asarray((base + np.arange(l)[None, :]) % cfg.vocab_size)

    a = amp.initialize(optimizer=FusedAdam(lr=args.lr),
                       opt_level=args.opt_level, verbosity=0)

    if args.seq_parallel:
        from jax.sharding import Mesh, PartitionSpec as P
        n = args.devices
        if len(jax.devices()) < n:
            raise SystemExit(
                f"--seq-parallel --devices {n} needs {n} devices in this "
                f"process; JAX sees {len(jax.devices())} (pass "
                f"--force-cpu for a virtual CPU mesh)")
        if l % n != 0:
            raise SystemExit(
                f"--seq-parallel requires --seq-len divisible by the "
                f"device count: got seq_len={l}, devices={n}")
        mesh = Mesh(np.array(jax.devices()[:n]), ("seq",))
        cfg_sp = dataclasses.replace(cfg, seq_axis_name="seq")
        model = GPTModel(cfg_sp)
        init_model = GPTModel(cfg)   # init needs no bound mesh axis
        params = init_model.init(jax.random.PRNGKey(0), ids[:, :16])["params"]
        state = a.init(params)
        positions = jnp.broadcast_to(jnp.arange(l)[None, :], (b, l))
        targets = jnp.roll(ids, -1, axis=1)
        mask = jnp.ones((b, l), jnp.float32).at[:, -1].set(0.0)

        def loss_fn(p, ids_sh, tgt_sh, pos_sh, m_sh):
            logits = model.apply({"params": p}, ids_sh, positions=pos_sh)
            # global normalizer: shard grads sum to the global-mean grad
            return lm_loss(logits, tgt_sh, mask=m_sh, seq_axis_name="seq")

        train = amp.make_train_step(a, loss_fn)

        def train_step(state, ids_sh, tgt_sh, pos_sh, m_sh):
            new_state, metrics = train(state, ids_sh, tgt_sh, pos_sh, m_sh)
            # each shard holds local_sum/global_count: psum = global mean
            return new_state, jax.lax.psum(metrics["loss"], "seq")

        step = jax.jit(shard_map(
            train_step, mesh=mesh,
            in_specs=(P(), P(None, "seq"), P(None, "seq"),
                      P(None, "seq"), P(None, "seq")),
            out_specs=(P(), P())))
        batch = (ids, targets, positions, mask)
    else:
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0), ids[:, :16])["params"]
        state = a.init(params)

        def loss_fn(p, ids):
            logits = model.apply({"params": p}, ids)
            return lm_loss(logits[:, :-1], ids[:, 1:])

        step = jax.jit(amp.make_train_step(a, loss_fn))
        batch = (ids,)

    t0 = time.perf_counter()
    for i in range(args.steps):
        if corpus is not None and i > 0:
            batch = (_sample_windows(corpus, rng, b, l),)
        state, out = step(state, *batch)
        loss = out if args.seq_parallel else out["loss"]
        if i % args.print_freq == 0 or i == args.steps - 1:
            extra = (f"  ({float(loss) / np.log(2):.3f} bits/byte)"
                     if corpus is not None else "")
            print(f"step {i:4d}  loss {float(loss):.4f}{extra}")
    dt = time.perf_counter() - t0
    tok = b * l * args.steps / dt
    print(f"done: {tok / 1e3:.1f}K tokens/s "
          f"({jax.devices()[0].platform}, seq_parallel={args.seq_parallel})")

    if corpus is not None and args.sample_bytes > 0:
        text = _greedy_sample(model, state, corpus, l, args.sample_bytes)
        print("--- greedy sample (prompt|continuation) ---")
        print(text)


def _load_pysrc_corpus(max_bytes=8 << 20):
    """Concatenated Python standard-library sources as one byte stream —
    real, structured text present in every environment (no downloads)."""
    import sysconfig
    from pathlib import Path

    root = Path(sysconfig.get_paths()["stdlib"])
    chunks, total = [], 0
    for path in sorted(root.glob("*.py")):
        try:
            data = path.read_bytes()
        except OSError:
            continue
        chunks.append(data)
        total += len(data)
        if total >= max_bytes:
            break
    import numpy as np
    return np.frombuffer(b"".join(chunks), dtype=np.uint8)


def _sample_windows(corpus, rng, b, l):
    import jax.numpy as jnp
    import numpy as np
    if len(corpus) < l + 2:
        raise SystemExit(
            f"pysrc corpus has {len(corpus)} bytes, too small for "
            f"--seq-len {l} (zipped stdlib? try a smaller sequence)")
    starts = rng.randint(0, len(corpus) - l - 1, size=b)
    return jnp.asarray(np.stack([corpus[s:s + l] for s in starts])
                       .astype(np.int32))


def _greedy_sample(model, state, corpus, l, n_bytes):
    """Greedy continuation of a corpus prompt via the KV-cached decoder
    (:func:`apex_tpu.models.generate`): one compiled prefill + scan —
    the previous sliding-window loop re-ran a full forward AND paid one
    host round trip per generated byte."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models import generate

    window_len = l // 2
    prompt = jnp.asarray(corpus[:window_len].astype(np.int32))[None, :]
    out = generate(state.master_params, model.cfg, prompt, n_bytes)
    toks = np.asarray(out)[0].tolist()
    # decode prompt and continuation separately so the '|' separator
    # stays exact even when the byte boundary splits a UTF-8 sequence
    head = bytes(toks[:window_len]).decode("utf-8", errors="replace")
    tail = bytes(toks[window_len:]).decode("utf-8", errors="replace")
    return head + "|" + tail


if __name__ == "__main__":
    main()
