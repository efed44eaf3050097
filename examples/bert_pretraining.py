"""BASELINE config 4: BERT pretraining with FusedLAMB + FusedLayerNorm.

The reference ships the LAMB kernels with no driver (SURVEY.md §0); this is
the end-to-end pretraining loop those kernels exist for.  Synthetic masked-LM
data by default; ``--size large`` selects BERT-large (the v5e-16 config),
``--size large-tpu`` the same model with the TPU-native 8x128 head geometry
(same parameter count, ~20% faster steps), ``--size tiny`` runs anywhere.

Data-parallel over all devices with ``--dp`` (shard_map over ("data",)).
"""

# Make the repo root importable when run as "python examples/<name>.py"
# without an install (the environment forbids pip install).
import os
import sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models.bert import (
    BertForPreTraining,
    bert_base,
    bert_large,
    bert_large_tpu,
    bert_tiny,
    pretraining_loss,
)
from apex_tpu.optimizers import fused_lamb
from apex_tpu.parallel import DistributedDataParallel, data_parallel_mesh
from apex_tpu.utils import maybe_print
from jax import shard_map

# "large-tpu" = bert-large with the TPU-native 8x128 head geometry (same
# parameter count, ~20% faster pretraining steps on v5e)
CONFIGS = {"tiny": bert_tiny, "base": bert_base, "large": bert_large,
           "large-tpu": bert_large_tpu}


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="tiny", choices=list(CONFIGS))
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-device batch")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--dp", action="store_true")
    p.add_argument("--print-freq", type=int, default=10)
    return p.parse_args()


def synthetic_mlm_batch(key, cfg, batch, seq_len):
    ks = jax.random.split(key, 3)
    ids = jax.random.randint(ks[0], (batch, seq_len), 0, cfg.vocab_size)
    labels = ids  # predict the original token at masked positions
    mask_pos = (jax.random.uniform(ks[1], (batch, seq_len)) < 0.15)
    masked_ids = jnp.where(mask_pos, 103, ids)  # [MASK]-style id
    nsp = jax.random.randint(ks[2], (batch,), 0, 2)
    return (masked_ids, jnp.ones((batch, seq_len), jnp.int32), labels,
            mask_pos.astype(jnp.float32), nsp)


def main():
    args = parse_args()
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    cfg = CONFIGS[args.size]()
    seq_len = min(args.seq_len, cfg.max_position_embeddings)
    model = BertForPreTraining(cfg)

    batch0 = synthetic_mlm_batch(jax.random.PRNGKey(0), cfg, 2, seq_len)
    variables = model.init(jax.random.PRNGKey(1), batch0[0],
                           attention_mask=batch0[1])
    a = amp.initialize(optimizer=fused_lamb(learning_rate=args.lr),
                       opt_level=args.opt_level)
    state = a.init(variables["params"])

    def loss_fn(p, ids, mask, labels, mlm_mask, nsp):
        mlm, nspl = model.apply({"params": p}, ids, attention_mask=mask)
        return pretraining_loss(mlm, nspl, mlm_labels=labels,
                                nsp_labels=nsp, mlm_mask=mlm_mask)

    if args.dp:
        mesh = data_parallel_mesh()
        n_dev = len(jax.devices())
        ddp = DistributedDataParallel(axis_name="data")
        inner = amp.make_train_step(a, loss_fn, axis_name="data",
                                    reduce_fn=ddp.reduce)

        def sharded(s, *b):
            s2, m = inner(s, *b)
            return s2, jax.lax.pmean(m["loss"], "data")

        step = jax.jit(shard_map(
            sharded, mesh=mesh,
            in_specs=(P(),) + (P("data"),) * 5, out_specs=(P(), P())))
    else:
        n_dev = 1
        inner = amp.make_train_step(a, loss_fn)
        step = jax.jit(lambda s, *b: (lambda r: (r[0], r[1]["loss"]))(
            inner(s, *b)))

    global_batch = args.batch_size * n_dev
    t0 = None
    for i in range(args.steps):
        batch = synthetic_mlm_batch(jax.random.PRNGKey(i + 2), cfg,
                                    global_batch, seq_len)
        state, loss = step(state, *batch)
        if i == 0:
            jax.block_until_ready(loss)
            t0 = time.time()  # exclude compile
        if i % args.print_freq == 0 or i == args.steps - 1:
            maybe_print(f"step {i:4d}  loss {float(loss):.4f}")
    jax.block_until_ready(loss)
    if args.steps > 1:
        sps = (args.steps - 1) * global_batch / (time.time() - t0)
        maybe_print(f"Speed: {sps:.1f} sequences/s")


if __name__ == "__main__":
    main()
