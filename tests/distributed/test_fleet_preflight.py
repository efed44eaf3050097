"""Cross-process SPMD preflight tests (real 2-process ``jax.distributed``).

The fleet failure mode this PR targets is a *hang*: one rank lowers a
different collective schedule (a sign-compressed bucket, a conditionally
skipped all-reduce) and the whole fleet wedges in the first mismatched
collective with no diagnosis.  Here two CPU-backend processes form a real
cluster and train a miniature DDP + amp-O2 step with the preflight barrier
enabled:

- the happy path proves the preflight passes AND the training itself is
  SPMD-consistent — reduced grads, agreeing scaler states, bit-identical
  final parameters across ranks (one digest covers all three);
- the seeded-divergence path gives rank 1 one extra collective and proves
  the fleet aborts before the first step with the differing op *named* in
  the error — instead of timing out.

Also here: the :func:`apex_tpu.parallel.multiproc.spawn` failure-surfacing
contract (a dying rank's stderr tail lands in the ``ClusterInitError``).
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[2])

#: the per-rank worker: build the DDP + amp-O2 train step, run the SPMD
#: preflight through ``initialize(preflight=...)``, then train 3 steps and
#: print a digest of the ENTIRE final state (params + masters + scaler) —
#: one line per rank the launcher can compare bit-for-bit.
WORKER = textwrap.dedent("""
    import hashlib
    import os
    import sys

    import jax
    jax.config.update("jax_platforms", "cpu")
    # the CPU backend only runs cross-process computations through the
    # gloo collectives implementation
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    import numpy as np

    from apex_tpu.parallel import multiproc

    _cache = {}

    def build():
        # runs AFTER cluster formation (initialize's preflight callable):
        # the global devices the mesh needs exist only now
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        from jax.sharding import Mesh, PartitionSpec as P

        from apex_tpu import amp
        from apex_tpu.optimizers import FusedAdam
        from apex_tpu.parallel import DistributedDataParallel
        from jax import shard_map

        mesh = Mesh(np.array(jax.devices()), ("data",))
        rank = jax.process_index()
        probe = os.environ.get("SEED_DIVERGENCE") == "1" and rank == 1
        params = {"w1": jax.random.normal(jax.random.PRNGKey(0), (8, 16)),
                  "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}

        def loss_fn(p, xb):
            h = jax.nn.relu(xb @ p["w1"])
            loss = jnp.mean(jnp.square(h @ p["w2"]))
            if probe:
                # the seeded divergence: rank 1 issues one extra
                # collective its peers never will (traced operand, so
                # nothing folds it away)
                extra = jax.lax.psum(jnp.sum(xb).astype(jnp.float32),
                                     "data")
                loss = loss + 0.0 * extra
            return loss

        ddp = DistributedDataParallel(axis_name="data")
        a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level="O2",
                           verbosity=0)
        state = a.init(params)
        step = amp.make_train_step(a, loss_fn, axis_name="data",
                                   reduce_fn=ddp.reduce)

        def inner(s, xb):
            s2, m = step(s, xb[0])
            return s2, jax.lax.pmean(m["loss"], "data")

        fn = jax.jit(shard_map(inner, mesh=mesh,
                               in_specs=(P(), P("data")),
                               out_specs=(P(), P())))
        n = jax.process_count()
        # every rank derives the same global batch, keeps its own shard
        xg = np.asarray(jax.random.normal(jax.random.PRNGKey(2),
                                          (n, 1, 4, 8)))
        state_g = multihost_utils.host_local_array_to_global_array(
            state, mesh, P())
        x_g = multihost_utils.host_local_array_to_global_array(
            xg[rank], mesh, P("data"))
        _cache.update(fn=fn, state=state_g, x=x_g, mesh=mesh)
        return fn.lower(state_g, x_g)

    try:
        rec = multiproc.initialize(preflight=build,
                                   preflight_label="ddp_o2_train")
    except multiproc.SpmdPreflightError as e:
        print("PREFLIGHT ABORT:", e, file=sys.stderr, flush=True)
        sys.exit(3)

    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    fn, state, x = _cache["fn"], _cache["state"], _cache["x"]
    for _ in range(3):
        state, loss = fn(state, x)
    state_l, loss_l = multihost_utils.global_array_to_host_local_array(
        (state, loss), _cache["mesh"], (P(), P()))
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(state_l):
        h.update(np.asarray(leaf).tobytes())
    scale = float(np.asarray(state_l.scaler_states[0].loss_scale))
    print("RANK", jax.process_index(),
          "SCHED", rec["schedule_hash"][:12],
          "NCOLL", rec["n_collectives"],
          "SCALE", scale,
          "LOSS", float(np.asarray(loss_l)),
          "DIGEST", h.hexdigest(), flush=True)
""")


def _launch(tmp_path, extra_env=None):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ, WORLD_SIZE="2",
               PYTHONPATH=REPO_ROOT + ":" + os.environ.get("PYTHONPATH", ""))
    # drop the single-process test config so workers form their own cluster
    env.pop("XLA_FLAGS", None)
    env.pop("SEED_DIVERGENCE", None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.multiproc", str(script)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.slow
@pytest.mark.skipif(
    os.environ.get("APEX_TPU_TEST_PLATFORM") not in (None, "cpu"),
    reason="local spawner test runs on the CPU backend")
def test_two_process_ddp_o2_trains_bit_identical_after_preflight(tmp_path):
    """Happy path: the preflight barrier passes, 3 real DDP + amp-O2
    steps run, and both ranks print the same schedule hash, scaler
    scale, loss, and full-state digest — grads were reduced and the
    replicas stayed bit-identical."""
    out = _launch(tmp_path)
    assert out.returncode == 0, (out.stdout, out.stderr)
    lines0 = [ln for ln in out.stdout.splitlines()
              if ln.startswith("RANK 0 ")]
    lines1 = [ln for ln in (tmp_path / "PROC_1.log").read_text().splitlines()
              if ln.startswith("RANK 1 ")]
    assert lines0 and lines1, (out.stdout, out.stderr)
    t0, t1 = lines0[0].split()[2:], lines1[0].split()[2:]
    # everything after "RANK <i>" must agree bit-for-bit across ranks:
    # schedule fingerprint, collective count, scaler state, loss, and the
    # sha256 over every leaf of the final AmpState
    assert t0 == t1, (lines0[0], lines1[0])
    # the preflight saw a real collective schedule (grad reduce + pmean)
    ncoll = int(t0[t0.index("NCOLL") + 1])
    assert ncoll >= 2, t0


@pytest.mark.slow
@pytest.mark.skipif(
    os.environ.get("APEX_TPU_TEST_PLATFORM") not in (None, "cpu"),
    reason="local spawner test runs on the CPU backend")
def test_two_process_seeded_divergence_aborts_with_named_diff(tmp_path):
    """Rank 1 lowers one extra all-reduce: the preflight must abort the
    fleet (exit, not hang) and the launcher error must carry the named
    schedule diff from the dying rank's stderr."""
    out = _launch(tmp_path, {"SEED_DIVERGENCE": "1"})
    assert out.returncode == 1, (out.stdout, out.stderr)
    # the worker caught SpmdPreflightError and exited 3; spawn surfaced
    # that rank's stderr tail, which names the diverging op
    assert "exited with code 3" in out.stderr, out.stderr
    assert "SPMD preflight failed" in out.stderr, out.stderr
    assert "all-reduce" in out.stderr, out.stderr
    assert "ddp_o2_train" in out.stderr, out.stderr


def test_spawn_surfaces_failing_rank_stderr_tail(tmp_path, monkeypatch):
    """A rank that dies pre-barrier must be diagnosable from the
    launcher's exception alone: first failing rank, exit code, and the
    tail of its captured stderr."""
    from apex_tpu.parallel import multiproc

    script = tmp_path / "boom.py"
    script.write_text(
        "import sys\n"
        "print('device mask mismatch: the diagnosis', file=sys.stderr)\n"
        "sys.exit(7)\n")
    monkeypatch.chdir(tmp_path)
    with pytest.raises(multiproc.ClusterInitError) as ei:
        multiproc.spawn([str(script)], world_size=1)
    msg = str(ei.value)
    assert "rank 0 exited with code 7" in msg
    assert "the diagnosis" in msg
    assert "PROC_0.err" in msg
