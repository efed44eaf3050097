"""BASELINE configs 2 & 3: ImageNet ResNet-50 with amp O2 (+FusedAdam) and
data-parallel + SyncBatchNorm.

Port of ``examples/imagenet/main_amp.py`` / ``tests/L1/common/main_amp.py``:
the flag surface (``--opt-level``, ``--loss-scale``,
``--keep-batchnorm-fp32``, ``--fused-adam``, ``--sync-bn``, ``--prof``,
``--deterministic``) and the throughput/AverageMeter reporting carry over;
process-group DDP becomes a ``shard_map`` over the ``("data",)`` mesh with
:class:`apex_tpu.parallel.DistributedDataParallel` reduction.

The full train→validate epoch structure of the reference carries over:
``validate()`` with loss/prec@1/prec@5 AverageMeters
(``main_amp.py:439-460``), ``accuracy(output, target, topk)``
(``:475-489``), best-prec@1 tracking with an ``is_best`` checkpoint marker
(``:170-185, 244-254``), and the step-decay + warmup LR schedule
(``adjust_learning_rate``, ``:462-478``).

Data: ``--data synthetic`` (default; this environment has no ImageNet) or
``--data digits`` — the sklearn handwritten-digits set (1797 real 8x8
images, 10 classes), the real-data convergence path for this environment.
An ImageNet-layout directory can be wired the same way: implement
``load_xxx()`` returning ``(train_x, train_y, val_x, val_y)`` NHWC float32
arrays and register it in ``DATASETS``.
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
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from apex_tpu import amp
from apex_tpu.models.resnet import ARCHS
from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import (
    DistributedDataParallel,
    convert_syncbn_model,
    data_parallel_mesh,
)
from apex_tpu.utils import maybe_print
from jax import shard_map


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="resnet50",
               choices=sorted(ARCHS))
    p.add_argument("-b", "--batch-size", type=int, default=128,
                   help="per-device batch")
    p.add_argument("--lr", type=float, default=None,
                   help="default: 0.1 (SGD) or 1e-3 (FusedAdam)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--opt-level", default="O2")
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--keep-batchnorm-fp32", default=None)
    p.add_argument("--fused-adam", action="store_true")
    p.add_argument("--sync-bn", action="store_true")
    p.add_argument("--dp", action="store_true",
                   help="data-parallel over all visible devices")
    p.add_argument("--prof", type=int, default=0,
                   help="profile N steps then exit (reference --prof)")
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--print-freq", type=int, default=10)
    p.add_argument("--checkpoint-dir", default=None,
                   help="save a checkpoint every --checkpoint-freq steps "
                        "(reference epoch checkpointing, "
                        "main_amp.py:170-185)")
    p.add_argument("--checkpoint-freq", type=int, default=50)
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in "
                        "--checkpoint-dir (reference --resume)")
    p.add_argument("--data-pipeline", default="device",
                   choices=["device", "host"],
                   help="device: batches generated device-resident "
                        "(fastest); host: uint8 numpy batches streamed "
                        "through apex_tpu.data.prefetch_to_device with "
                        "on-device normalization — the reference "
                        "data_prefetcher pattern (main_amp.py:256-290)")
    p.add_argument("--data", default="synthetic",
                   choices=["synthetic", "digits"],
                   help="synthetic stream, or the sklearn digits set "
                        "(real data: 1797 8x8 images, 10 classes)")
    p.add_argument("--epochs", type=int, default=30,
                   help="epochs over real data (--data digits); synthetic "
                        "mode uses --steps instead")
    p.add_argument("--warmup-epochs", type=int, default=5,
                   help="linear LR warmup (reference adjust_learning_rate)")
    p.add_argument("--evaluate", action="store_true",
                   help="run validation only (reference --evaluate)")
    p.add_argument("--target-top1", type=float, default=None,
                   help="exit nonzero unless final best prec@1 reaches "
                        "this (convergence-proof runs)")
    return p.parse_args()


class AverageMeter:
    """(reference ``main_amp.py:336-372``)"""

    def __init__(self):
        self.reset()

    def reset(self):
        self.val = self.sum = self.count = self.avg = 0.0

    def update(self, val, n=1):
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / self.count


def synthetic_batch(key, batch, size):
    x = jax.random.normal(key, (batch, size, size, 3), jnp.float32)
    y = jax.random.randint(key, (batch,), 0, 1000)
    return x, y


def load_digits(image_size):
    """sklearn handwritten digits as NHWC float32: 1437 train / 360 val
    (deterministic split), grey replicated to 3 channels, resized to
    ``image_size`` — the smallest *real* image-classification set available
    in this environment."""
    from sklearn.datasets import load_digits as _ld
    d = _ld()
    x = d.images.astype(np.float32) / 16.0
    x = (x - 0.5) / 0.5
    x = np.repeat(x[..., None], 3, axis=-1)            # (N, 8, 8, 3)
    if image_size != 8:
        x = np.asarray(jax.image.resize(
            jnp.asarray(x), (x.shape[0], image_size, image_size, 3),
            "nearest"))
    y = d.target.astype(np.int32)
    perm = np.random.RandomState(0).permutation(len(y))
    x, y = x[perm], y[perm]
    n_val = 360
    return x[:-n_val], y[:-n_val], x[-n_val:], y[-n_val:], 10


DATASETS = {"digits": load_digits}


def accuracy(logits, target, topk=(1,)):
    """precision@k over a logits batch (reference ``main_amp.py:475-489``)."""
    maxk = max(topk)
    _, pred = jax.lax.top_k(logits, maxk)              # (B, maxk)
    correct = pred == target[:, None]
    return [100.0 * jnp.sum(correct[:, :k]) / target.shape[0] for k in topk]


def make_validate(model, a, eval_batch):
    """The reference ``validate()`` loop (``main_amp.py:439-460``): eval-mode
    forward over the val set, loss/prec@1/prec@5 AverageMeters, returns
    ``prec@1``."""

    @jax.jit
    def eval_step(p, stats, x, y):
        # O2/O3 policy input cast (training does this inside make_train_step)
        if a.properties.cast_model_dtype is not None:
            x = x.astype(a.properties.cast_model_dtype)
        logits = model.apply({"params": p, "batch_stats": stats}, x,
                             train=False).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
        prec1, prec5 = accuracy(logits, y, (1, 5))
        return loss, prec1, prec5

    def validate(state, batch_stats, val_x, val_y, print_freq=10):
        losses, top1, top5 = AverageMeter(), AverageMeter(), AverageMeter()
        p = a.model_params(state)
        n = (len(val_y) // eval_batch) * eval_batch
        t0 = time.time()
        for j, i in enumerate(range(0, n, eval_batch)):
            x = jnp.asarray(val_x[i:i + eval_batch])
            y = jnp.asarray(val_y[i:i + eval_batch])
            loss, p1, p5 = eval_step(p, batch_stats, x, y)
            losses.update(float(loss), eval_batch)
            top1.update(float(p1), eval_batch)
            top5.update(float(p5), eval_batch)
            if j % print_freq == 0:
                maybe_print(f"Test: [{j}/{n // eval_batch}]  "
                            f"loss {losses.val:.4f} ({losses.avg:.4f})  "
                            f"Prec@1 {top1.val:.3f} ({top1.avg:.3f})  "
                            f"Prec@5 {top5.val:.3f} ({top5.avg:.3f})")
        maybe_print(f" * Prec@1 {top1.avg:.3f} Prec@5 {top5.avg:.3f}  "
                    f"({n / max(time.time() - t0, 1e-9):.0f} img/s)")
        return top1.avg

    return validate


def make_lr_schedule(base_lr, len_epoch, epochs_warmup):
    """Reference ``adjust_learning_rate`` (``main_amp.py:462-478``): /10 at
    epochs 30/60/80 plus linear warmup over the first ``epochs_warmup``
    epochs, expressed as an optax-style ``step -> lr`` schedule."""

    def lr(global_step):
        e = global_step // len_epoch
        factor = e // 30 + jnp.where(e >= 80, 1, 0)
        out = base_lr * jnp.power(0.1, factor.astype(jnp.float32))
        warm = base_lr * (1.0 + global_step) / (epochs_warmup * len_epoch)
        return jnp.where(e < epochs_warmup, jnp.minimum(warm, out), out)

    return lr


def train_real(args, state, batch_stats, step, validate, mgr,
               train_x, train_y, val_x, val_y, global_batch,
               best_prec1, seed, start_step):
    """Epoch-structured train→validate loop over real data — the reference's
    ``for epoch: train(...); prec1 = validate(...); save_checkpoint(...,
    is_best)`` skeleton (``main_amp.py:170-185, 244-254``)."""
    import json

    len_epoch = max(len(train_y) // global_batch, 1)
    if args.prof:
        # reference --prof semantics (profile N steps, then exit) on the
        # real-data path: XProf capture of the first N steps of epoch 0
        from apex_tpu.utils import profiler_start, profiler_stop
        perm = np.random.RandomState(seed + 1000).permutation(len(train_y))
        profiler_start("/tmp/apex_tpu_trace")
        maybe_print(f"profiling {args.prof} steps -> /tmp/apex_tpu_trace")
        for b in range(args.prof):
            idx = perm[(b % len_epoch) * global_batch:][:global_batch]
            if len(idx) < global_batch:
                idx = np.concatenate([idx, perm[:global_batch - len(idx)]])
            state, batch_stats, loss, _ = step(
                state, batch_stats, jnp.asarray(train_x[idx]),
                jnp.asarray(train_y[idx]))
        float(loss)
        profiler_stop()
        return

    start_epoch = start_step // len_epoch
    for epoch in range(start_epoch, args.epochs):
        perm = np.random.RandomState(seed + 1000 + epoch).permutation(
            len(train_y))
        t0 = time.time()
        loss = scale = None
        for b in range(len_epoch):
            idx = perm[b * global_batch:(b + 1) * global_batch]
            if len(idx) < global_batch:   # static shapes: wrap the tail
                idx = np.concatenate([idx, perm[:global_batch - len(idx)]])
            x, y = jnp.asarray(train_x[idx]), jnp.asarray(train_y[idx])
            state, batch_stats, loss, scale = step(state, batch_stats, x, y)
        loss = float(loss)                # sync once per epoch
        speed = len_epoch * global_batch / max(time.time() - t0, 1e-9)
        maybe_print(f"Epoch {epoch:3d}  loss {loss:.4f}  "
                    f"scale {float(scale):.0f}  {speed:.0f} img/s")
        prec1 = validate(state, batch_stats, val_x, val_y)
        is_best = prec1 > best_prec1
        best_prec1 = max(prec1, best_prec1)
        if mgr is not None:
            mgr.save((epoch + 1) * len_epoch - 1, state,
                     extras={"batch_stats": batch_stats,
                             "best_prec1": jnp.asarray(best_prec1,
                                                       jnp.float32)})
            if is_best:
                # the reference copies checkpoint.pth.tar -> model_best;
                # the durable manager keeps whole step dirs, so record
                # WHICH step is best
                with open(os.path.join(args.checkpoint_dir,
                                       "best.json"), "w") as f:
                    json.dump({"step": (epoch + 1) * len_epoch - 1,
                               "epoch": epoch, "prec1": best_prec1}, f)
    if mgr is not None:
        mgr.wait()
    maybe_print(f"Best Prec@1 {best_prec1:.3f}")
    if args.target_top1 is not None and best_prec1 < args.target_top1:
        raise SystemExit(f"best prec@1 {best_prec1:.3f} below target "
                         f"{args.target_top1}")


def main():
    args = parse_args()
    from apex_tpu.utils import compile_cache
    compile_cache.enable()
    if args.deterministic:
        seed = 0
    else:
        seed = int(time.time())

    n_dev = len(jax.devices()) if args.dp else 1

    real_data = args.data != "synthetic"
    if real_data and args.data_pipeline == "host":
        # fail loudly rather than silently measuring the device path:
        # the digits set is staged once (it fits on chip), so there is
        # no host stream to exercise there
        raise SystemExit("--data-pipeline host applies to --data "
                         "synthetic only; digits is device-staged")
    num_classes = 1000
    if real_data:
        train_x, train_y, val_x, val_y, num_classes = \
            DATASETS[args.data](args.image_size)
        maybe_print(f"{args.data}: {len(train_y)} train / {len(val_y)} val "
                    f"images, {num_classes} classes")
    model = ARCHS[args.arch](num_classes=num_classes)
    if args.sync_bn:
        if not args.dp:
            raise SystemExit("--sync-bn requires --dp: the \"data\" mesh "
                             "axis SyncBatchNorm reduces over only exists "
                             "under the data-parallel shard_map")
        model = convert_syncbn_model(model, axis_name="data")
        maybe_print("using SyncBatchNorm over the data axis")

    x0, _ = synthetic_batch(jax.random.PRNGKey(0), 2, args.image_size)
    variables = model.init(jax.random.PRNGKey(seed), x0, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]

    global_batch = args.batch_size * n_dev
    base_lr = args.lr if args.lr is not None else \
        (1e-3 if args.fused_adam else 0.1)
    if real_data:
        len_epoch = max(len(train_y) // global_batch, 1)
        lr = make_lr_schedule(base_lr, len_epoch, args.warmup_epochs)
    else:
        lr = base_lr
    if args.fused_adam:
        tx = FusedAdam(lr=lr)
    else:
        tx = optax.sgd(lr, momentum=0.9)
    a = amp.initialize(optimizer=tx, opt_level=args.opt_level,
                       loss_scale=args.loss_scale,
                       keep_batchnorm_fp32=args.keep_batchnorm_fp32)
    state = a.init(params)

    def make_loss_fn(stats):
        def loss_fn(p, x, y):
            logits, mut = model.apply({"params": p, "batch_stats": stats},
                                      x, train=True, mutable=["batch_stats"])
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            loss = -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))
            return loss, mut["batch_stats"]
        return loss_fn

    if args.dp:
        mesh = data_parallel_mesh()
        ddp = DistributedDataParallel(axis_name="data")

        def sharded(s, stats, x, y):
            inner = amp.make_train_step(a, make_loss_fn(stats),
                                        axis_name="data",
                                        reduce_fn=ddp.reduce, has_aux=True)
            s2, m = inner(s, x, y)
            # SyncBN already produces identical stats on every device; for
            # local BN this averages the per-device running stats so one
            # replicated copy carries forward (the reference checkpoints
            # rank 0's copy instead).
            stats2 = jax.lax.pmean(m["aux"], "data")
            return (s2, stats2, jax.lax.pmean(m["loss"], "data"),
                    m["loss_scale"])

        step = jax.jit(shard_map(
            sharded, mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P())))
    else:
        def step(s, stats, x, y):
            inner = amp.make_train_step(a, make_loss_fn(stats),
                                        has_aux=True)
            s2, m = inner(s, x, y)
            return s2, m["aux"], m["loss"], m["loss_scale"]

        step = jax.jit(step)

    mgr = None
    start_step = 0
    best_prec1 = 0.0
    if args.checkpoint_dir:
        from apex_tpu.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.checkpoint_dir)
        if args.resume and mgr.latest_step() is not None:
            state, extras = mgr.restore(
                state, extras={"batch_stats": batch_stats,
                               "best_prec1": jnp.zeros((), jnp.float32)})
            batch_stats = extras["batch_stats"]
            best_prec1 = float(extras["best_prec1"])
            start_step = mgr.latest_step() + 1
            maybe_print(f"resumed from step {mgr.latest_step()} "
                        f"(best prec@1 {best_prec1:.3f})")

    if real_data:
        # largest eval batch that divides the val set: static shapes, no
        # dropped or padded samples
        eval_b = max(b for b in range(1, min(args.batch_size,
                                             len(val_y)) + 1)
                     if len(val_y) % b == 0)
        validate = make_validate(model, a, eval_b)

    if args.evaluate:
        if not real_data:
            raise SystemExit("--evaluate requires real data (--data digits)")
        validate(state, batch_stats, val_x, val_y)
        return

    if real_data:
        train_real(args, state, batch_stats, step, validate, mgr,
                   train_x, train_y, val_x, val_y, global_batch,
                   best_prec1, seed, start_step)
        return

    steps = args.prof or args.steps
    if args.prof:
        # reference --prof: nvtx ranges + early exit (main_amp.py:63-64);
        # here a full XProf capture of the profiled steps.
        from apex_tpu.utils import profiler_start
        profiler_start("/tmp/apex_tpu_trace")
        maybe_print(f"profiling {steps} steps -> /tmp/apex_tpu_trace")
    losses = AverageMeter()
    # Explicit span bookkeeping: the loss is fetched only at print
    # boundaries (a per-step device fetch would gate the async pipeline on
    # host round-trips; the reference synced per step because eager torch
    # already had).  The first span is compilation and stays out of the
    # averages.
    last_t = time.time()
    last_i = start_step - 1
    warm_t0 = warm_i0 = None
    inst = 0.0
    if args.data_pipeline == "host":
        from apex_tpu.data import (host_synthetic_loader, normalize_uint8,
                                   prefetch_to_device)
        sharding = None
        if args.dp:
            from jax.sharding import NamedSharding
            sharding = NamedSharding(mesh, P("data"))
        batches = prefetch_to_device(
            host_synthetic_loader(steps - start_step, global_batch,
                                  args.image_size, seed),
            lookahead=2, sharding=sharding, transform=normalize_uint8)
        maybe_print("host-streamed input pipeline: uint8 numpy batches, "
                    "H2D + on-device normalize overlapped (lookahead 2)")
    else:
        def _device_batches():
            for j in range(start_step, steps):
                kx = jax.random.PRNGKey(seed + j + 1)
                yield synthetic_batch(kx, global_batch, args.image_size)
        batches = _device_batches()
    for i, (x, y) in zip(range(start_step, steps), batches):
        state, batch_stats, loss, scale = step(state, batch_stats, x, y)
        if mgr is not None and (i + 1) % args.checkpoint_freq == 0:
            mgr.save(i, state,
                     extras={"batch_stats": batch_stats,
                             "best_prec1": jnp.zeros((), jnp.float32)})
        if i % args.print_freq == 0 or i == steps - 1:
            loss = float(loss)          # sync point
            now = time.time()
            span = i - last_i
            inst = global_batch * span / max(now - last_t, 1e-9)
            losses.update(loss, global_batch)
            if warm_t0 is None:
                warm_t0, warm_i0 = now, i
                avg = inst
            else:
                avg = (global_batch * (i - warm_i0)
                       / max(now - warm_t0, 1e-9))
            maybe_print(
                f"step {i:4d}  loss {losses.val:.4f} ({losses.avg:.4f})  "
                f"scale {float(scale):.0f}  "
                f"{inst:.0f} img/s ({avg:.0f} avg)")
            last_t, last_i = now, i
    if args.prof:
        from apex_tpu.utils import profiler_stop
        profiler_stop()
    if mgr is not None:
        mgr.wait()  # commit any in-flight async checkpoint
    if warm_t0 is not None and last_i > warm_i0:
        speed = global_batch * (last_i - warm_i0) / max(last_t - warm_t0,
                                                        1e-9)
    else:  # a single boundary (e.g. --steps 1): the compile-span rate
        speed = inst
    maybe_print(f"Speed: {speed:.1f} img/s total (post-warmup)")


if __name__ == "__main__":
    main()
