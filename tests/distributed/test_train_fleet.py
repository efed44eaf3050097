"""Elastic training fleet (ISSUE 18) — the unit bars under the chaos
drill: the ledger's atomic/exclusive coordination files, the heartbeat
lease, the membership gate's shrink/regrow/plan detection, the
absolute-step checkpoint adapter, the digest contract that makes the
drill's bitwise audit possible, the ``train_fleet_*`` metric family at
``run_resilient``'s lag-resolved boundary, and the 8→4→8 mesh-reshape
round-trip of full amp-O4 state (optimizer moments, scaler, fp8
delayed-scaling state) with a passing post-restore SPMD preflight.

The real 2-process SIGKILL drill itself (``tools/train_fleet.py``)
rides the ``slow`` marker; its committed artifact is re-validated every
tier-1 run through ``tools/gate_hygiene.py``.
"""

import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import amp
from apex_tpu.optimizers import FusedAdam
from apex_tpu.resilience import (DurableCheckpointManager, FleetConfig,
                                 FleetLedger, FleetMembershipChange,
                                 FleetMetrics, HeartbeatLease, RankKill,
                                 ResilienceConfig, latest_verified_step,
                                 membership_gate, run_resilient,
                                 snapshot_digest, state_digest)
from apex_tpu.resilience import fleet as fleet_mod

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


# ---------------------------------------------------------------------------
# ledger: atomic writes, exclusive plans, incarnations
# ---------------------------------------------------------------------------

def test_plan_write_is_exclusive_first_writer_wins(tmp_path):
    """Exactly one concurrent leader commits a generation plan: the
    O_EXCL link makes the second write a no-op returning False, and
    readers see the winner."""
    led = FleetLedger(str(tmp_path))
    won = led.write_plan({"gen": 1, "members": [0], "restore_step": 7})
    lost = led.write_plan({"gen": 1, "members": [0, 1], "restore_step": 3})
    assert won is True and lost is False
    assert led.read_plan(1)["members"] == [0]
    assert led.latest_plan()["gen"] == 1


def test_announce_increments_incarnation(tmp_path):
    """A relaunched supervisor re-announces with a bumped incarnation —
    the token that keeps it from adopting a plan written for its
    previous life."""
    led = FleetLedger(str(tmp_path))
    assert led.announce(0) == 0
    assert led.announce(1) == 0
    assert led.announce(1) == 1          # rank 1 came back
    assert led.incarnation(0) == 0
    assert led.incarnation(1) == 1
    assert sorted(led.announced()) == [0, 1]


def test_heartbeat_lease_fresh_then_stale(tmp_path):
    """The lease thread keeps the rank fresh while running; once
    stopped the lease ages past the TTL — liveness without ever
    touching a collective."""
    led = FleetLedger(str(tmp_path))
    led.announce(0)
    with HeartbeatLease(led, 0, interval_s=0.05,
                        info_fn=lambda: {"step": 3}):
        time.sleep(0.25)
        assert led.fresh(0, ttl_s=0.5)
        assert led.read_heartbeat(0)["step"] == 3
        assert led.live_ranks(ttl_s=0.5) == [0]
    time.sleep(0.3)
    assert not led.fresh(0, ttl_s=0.2)
    assert led.live_ranks(ttl_s=0.2) == []


def test_event_log_is_ordered_and_typed(tmp_path):
    led = FleetLedger(str(tmp_path))
    led.event(0, "kill", step=10)
    led.event(1, "restore", step=7)
    kinds = [e["kind"] for e in led.events()]
    assert kinds == ["kill", "restore"]
    assert all("utc" in e and "ts" in e for e in led.events())


# ---------------------------------------------------------------------------
# the membership gate
# ---------------------------------------------------------------------------

def _gate_cfg():
    # poll_s=0 disables throttling so every gate() call scans the ledger
    return FleetConfig(world_size=2, lease_ttl_s=0.2, poll_s=0.0)


def test_gate_raises_shrink_when_member_lease_stale(tmp_path):
    led = FleetLedger(str(tmp_path))
    led.announce(0), led.announce(1)
    led.heartbeat(0)                      # rank 1 never beats: dead
    seen = []
    gate = membership_gate(led, _gate_cfg(),
                           {"gen": 0, "members": [0, 1]}, rank=0,
                           on_change=lambda *a: seen.append(a))
    with pytest.raises(FleetMembershipChange) as ei:
        gate(11)
    assert ei.value.reason == "shrink"
    assert ei.value.ranks == [1] and ei.value.step == 11
    assert seen == [("shrink", [1], 11)]


def test_gate_raises_regrow_when_nonmember_lease_appears(tmp_path):
    led = FleetLedger(str(tmp_path))
    led.announce(0), led.heartbeat(0)
    gate = membership_gate(led, _gate_cfg(),
                           {"gen": 1, "members": [0]}, rank=0)
    gate(5)                               # alone: no change
    led.announce(1), led.heartbeat(1)     # the killed rank returns
    with pytest.raises(FleetMembershipChange) as ei:
        gate(6)
    assert ei.value.reason == "regrow" and ei.value.ranks == [1]


def test_gate_raises_on_newer_plan(tmp_path):
    led = FleetLedger(str(tmp_path))
    led.announce(0), led.heartbeat(0)
    gate = membership_gate(led, _gate_cfg(),
                           {"gen": 0, "members": [0]}, rank=0)
    led.write_plan({"gen": 1, "members": [0], "restore_step": 3})
    with pytest.raises(FleetMembershipChange) as ei:
        gate(4)
    assert ei.value.reason == "plan"


def test_gate_throttles_ledger_scans(tmp_path):
    """With a real poll interval the gate is nearly free: between polls
    it must not scan the ledger (a dead peer still raises at the NEXT
    poll — detection latency is lease_ttl + poll, not zero)."""
    led = FleetLedger(str(tmp_path))
    led.announce(0), led.heartbeat(0)
    cfg = FleetConfig(world_size=2, lease_ttl_s=0.2, poll_s=30.0)
    gate = membership_gate(led, cfg, {"gen": 0, "members": [0, 1]},
                           rank=0)
    with pytest.raises(FleetMembershipChange):
        gate(0)                           # first call always scans
    gate(1)                               # inside the poll window: silent


# ---------------------------------------------------------------------------
# absolute-step translation + fault parsing
# ---------------------------------------------------------------------------

class _FakeInner:
    def __init__(self):
        self.saved = []
        self.last_restore = None

    def save(self, step, state, extras=None):
        self.saved.append(step)

    def all_steps(self):
        return [3, 7, 11]

    def restore(self, template, step=None, extras=None):
        self.last_restore = {"step": 11 if step is None else step,
                             "skipped": []}
        return template, {}

    def wait(self):
        pass

    def close(self):
        pass


def test_step_offset_manager_translates_to_absolute_steps():
    inner = _FakeInner()
    mgr = fleet_mod._StepOffsetManager(inner, start=7)
    mgr.save(0, None)
    mgr.save(4, None)
    assert inner.saved == [7, 11]         # abs = start + local
    assert mgr.all_steps() == [0, 4]      # steps before start invisible
    mgr.restore(None, step=4)
    assert inner.last_restore["step"] == 11
    assert mgr.last_restore["step"] == 4  # translated back for the loop


def test_parse_fleet_faults_shift_and_vocabulary():
    out = fleet_mod._parse_fleet_faults(
        ["rank_kill@10:1", "rank_kill@3"], start=7)
    assert out == [RankKill(step=3, rank=1)]   # 10-7=3; step 3 < 7 dropped
    with pytest.raises(ValueError, match="not supported in the fleet"):
        fleet_mod._parse_fleet_faults(["nan_storm@5"], start=0)


# ---------------------------------------------------------------------------
# digest contract + pinned-step restore
# ---------------------------------------------------------------------------

def _tiny_state(steps=0, opt_level="O2"):
    params = {"w1": jax.random.normal(jax.random.PRNGKey(0), (4, 8)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (8, 4))}
    a = amp.initialize(optimizer=FusedAdam(lr=1e-2), opt_level=opt_level,
                       verbosity=0)
    step = jax.jit(amp.make_train_step(
        a, lambda p, xb: jnp.mean(jnp.square(
            jax.nn.relu(xb @ p["w1"]) @ p["w2"] - xb))))
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    state = a.init(params)
    for _ in range(steps):
        state, _ = step(state, x)
    return a, step, state, x


def test_state_digest_equals_snapshot_digest(tmp_path):
    """The drill's whole bitwise audit rides this: an in-memory state's
    digest equals the manifest-only digest of its committed snapshot,
    and a different state's does not."""
    _a, _step, state, _x = _tiny_state(steps=2)
    mgr = DurableCheckpointManager(str(tmp_path))
    mgr.save(3, state)
    mgr.wait()
    assert latest_verified_step(str(tmp_path)) == 3
    assert snapshot_digest(str(tmp_path), 3) == state_digest(state)
    _a2, step2, other, x2 = _tiny_state(steps=2)
    other, _ = step2(other, x2)           # one more step: different state
    assert state_digest(other) != state_digest(state)
    mgr.close()


def test_load_snapshot_state_restores_the_pinned_step(tmp_path):
    """Every member restores THE step its plan names — never "my
    newest", which async saves can skew across ranks."""
    a, step, state, x = _tiny_state(steps=1)
    mgr = DurableCheckpointManager(str(tmp_path), max_to_keep=4)
    mgr.save(1, state)
    later, _ = step(state, x)
    mgr.save(2, later)
    mgr.wait()
    got, _extras = fleet_mod.load_snapshot_state(
        str(tmp_path), 1, a.init({"w1": np.zeros((4, 8), np.float32),
                                  "w2": np.zeros((8, 4), np.float32)}))
    assert state_digest(got) == state_digest(state)
    assert state_digest(got) != state_digest(later)
    mgr.close()


def test_latest_verified_step_skips_corrupt_newest(tmp_path):
    a, step, state, x = _tiny_state(steps=1)
    mgr = DurableCheckpointManager(str(tmp_path), max_to_keep=4)
    mgr.save(1, state)
    later, _ = step(state, x)
    mgr.save(2, later)
    mgr.wait()
    mgr.close()
    # truncate a leaf of the newest snapshot: the plan must pin step 1
    from apex_tpu.resilience import durable
    step2_dir = tmp_path / durable._step_dirname(2)
    victim = next(p for p in step2_dir.iterdir()
                  if p.suffix == ".npy")
    victim.write_bytes(victim.read_bytes()[:10])
    assert latest_verified_step(str(tmp_path)) == 1


# ---------------------------------------------------------------------------
# the train_fleet_* metric family (satellite: run_resilient boundary)
# ---------------------------------------------------------------------------

def _metric(snap, name):
    return next(m for m in snap["metrics"] if m["name"] == name)


def test_fleet_metrics_family_shapes_and_counts():
    from apex_tpu.obs.metrics import Registry
    reg = Registry()
    fm = FleetMetrics(reg, active_ranks=2)
    fm.on_preemption()
    fm.on_recovery(1.5)
    fm.on_rewind()
    fm.set_active(1)
    fm.on_resolve()
    snap = reg.snapshot()
    assert _metric(snap, "train_fleet_active_ranks")["value"] == 1.0
    assert _metric(snap, "train_fleet_preemptions_total")["value"] == 1.0
    assert _metric(snap, "train_fleet_recoveries_total")["value"] == 1.0
    assert _metric(snap, "train_fleet_rewinds_total")["value"] == 1.0
    hist = _metric(snap, "train_fleet_recovery_seconds")
    assert hist["count"] == 1 and hist["sum"] == 1.5


def test_run_resilient_emits_fleet_metrics_at_resolve_boundary():
    """The loop re-asserts the active-ranks gauge at its existing
    lag-resolved boundary (a host int — no device read), and the
    instrumented step itself stays syncs-clean: fleet metrics ride the
    boundary the observability PR already paid for."""
    from apex_tpu import analysis
    from apex_tpu.obs.metrics import Registry

    a, step, state, x = _tiny_state()
    reg = Registry()
    fm = FleetMetrics(reg, active_ranks=2)
    result = run_resilient(
        step, state, lambda i: (x,), 4, amp_obj=a,
        config=ResilienceConfig(checkpoint_every=0,
                                watchdog_timeout_s=60.0),
        registry=reg, fleet_metrics=fm)
    assert result.steps_completed == 4
    snap = reg.snapshot()
    assert _metric(snap, "train_fleet_active_ranks")["value"] == 2.0
    assert _metric(snap, "train_fleet_rewinds_total")["value"] == 0.0
    # the step the loop dispatched carries no host callback / sync
    rep = analysis.analyze(step, state, x, passes=("syncs",))
    assert rep.ok, rep.format()


# ---------------------------------------------------------------------------
# satellite: shrink→regrow checkpoint round-trip across mesh sizes
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs 8 devices (virtual CPU mesh)")
def test_shrink_regrow_roundtrip_8_4_8_bitwise_with_preflight(tmp_path):
    """The fleet's storage story end-to-end on one host: train amp-O4
    (fp8 delayed-scaling state included) replicated over an 8-device
    mesh, checkpoint, "shrink" onto a 4-device mesh via the fleet's
    pinned-step restore with every leaf bitwise (masters, moments,
    scaler, fp8 amax history), train on, checkpoint, "regrow" back onto
    8 devices bitwise again — and the post-restore SPMD preflight
    passes on the regrown lowering."""
    from apex_tpu.parallel.multiproc import spmd_preflight

    a = amp.initialize(optimizer=FusedAdam(lr=1e-3), opt_level="O4",
                       verbosity=0)
    params = {"w1": jax.random.normal(jax.random.PRNGKey(0), (8, 16)),
              "w2": jax.random.normal(jax.random.PRNGKey(1), (16, 8))}
    step = jax.jit(amp.make_train_step(
        a, lambda p, xb: jnp.mean(jnp.square(
            jax.nn.relu(xb @ p["w1"]) @ p["w2"] - xb))))
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 8))

    def mesh(n):
        return Mesh(np.array(jax.devices()[:n]), ("data",))

    def replicated(tree, m):
        sh = NamedSharding(m, P())
        return jax.tree.map(lambda t: jax.device_put(t, sh), tree)

    def batch(m):
        return jax.device_put(x, NamedSharding(m, P("data")))

    def host(tree):
        return jax.tree.map(np.asarray, tree)

    def assert_bitwise(got, want, msg):
        for (pa, la), (_pb, lb) in zip(
                jax.tree_util.tree_leaves_with_path(host(got)),
                jax.tree_util.tree_leaves_with_path(host(want))):
            np.testing.assert_array_equal(
                la, lb, err_msg=f"{msg}: {jax.tree_util.keystr(pa)}")

    mesh8, mesh4 = mesh(8), mesh(4)
    state = replicated(a.init(params), mesh8)
    assert state.fp8_state is not None
    # drive one overflow so the scaler state moves off its init too
    x_bad = batch(mesh8).at[0, 0].set(jnp.inf)
    state, m = step(state, x_bad)
    assert bool(m["overflow"])
    for _ in range(2):
        state, _ = step(state, batch(mesh8))

    mgr = DurableCheckpointManager(str(tmp_path), max_to_keep=4)
    mgr.save(3, state)
    mgr.wait()
    assert latest_verified_step(str(tmp_path)) == 3
    assert snapshot_digest(str(tmp_path), 3) == state_digest(state)

    # -- shrink: restore the pinned step onto the 4-device mesh ---------
    tmpl4 = replicated(a.init(params), mesh4)
    state4, _ = fleet_mod.load_snapshot_state(str(tmp_path), 3, tmpl4)
    assert_bitwise(state4, state, "4-dev restore vs saved")
    w1 = state4.master_params["w1"]
    assert len(w1.sharding.device_set) == 4
    assert float(state4.scaler_states[0].loss_scale) == \
        float(state.scaler_states[0].loss_scale)
    for _ in range(2):
        state4, _ = step(state4, batch(mesh4))
    mgr.save(5, state4)
    mgr.wait()

    # -- regrow: restore the shrunken run's snapshot onto 8 devices -----
    tmpl8 = replicated(a.init(params), mesh8)
    state8, _ = fleet_mod.load_snapshot_state(str(tmp_path), 5, tmpl8)
    assert_bitwise(state8, state4, "8-dev regrow restore vs 4-dev state")
    assert len(state8.master_params["w1"].sharding.device_set) == 8
    assert state_digest(state8) == snapshot_digest(str(tmp_path), 5)

    # -- the post-restore preflight the fleet runs after every re-form --
    rec = spmd_preflight(step.lower(state8, batch(mesh8)),
                         label="fleet_regrow")
    assert rec["ok"] and rec["schedule_hash"]
    # ...and training actually continues on the regrown mesh
    state8, m8 = step(state8, batch(mesh8))
    assert np.isfinite(float(m8["loss"]))
    mgr.close()


# ---------------------------------------------------------------------------
# replan leadership (review round): a returning minimum rank must not
# deadlock the regrow, and a dead generation must not strand a joiner
# ---------------------------------------------------------------------------

def _plan(gen, members, **kw):
    return {"gen": gen, "members": members, "port": 1,
            "restore_step": None, "reason": "initial",
            "created_by": members[0], "created_ts": time.time(),
            "incarnations": {str(r): 0 for r in members}, **kw}


def test_replan_leader_is_surviving_member_not_returning_min_rank(tmp_path):
    """Kill rank 0 and let it return: the regrow replan must be led by
    the SURVIVING member (rank 1), not by bare min(live)=0 — the
    returning rank sits in supervise's joiner branch and never writes
    plans, so electing it would leave the survivor waiting
    replan_window_s for a plan that cannot appear."""
    led = FleetLedger(str(tmp_path))
    cfg = FleetConfig(world_size=2, lease_ttl_s=5.0, poll_s=0.01,
                      replan_window_s=10.0)
    assert led.write_plan(_plan(0, [0, 1]))
    assert led.write_plan(_plan(1, [1], reason="shrink"))
    led.announce(0), led.heartbeat(0)     # rank 0 is back: lease fresh
    led.announce(1), led.heartbeat(1)
    t0 = time.monotonic()
    plan = fleet_mod._await_next_plan(led, cfg, rank=1, gen=1)
    # member preference decided immediately — not via the grace fallback
    assert time.monotonic() - t0 < cfg.replan_window_s / 2
    assert plan["gen"] == 2
    assert plan["members"] == [0, 1]
    assert plan["reason"] == "regrow"
    assert plan["created_by"] == 1


def test_replan_grace_lets_waiting_member_pass_a_stalled_leader(tmp_path):
    """The elected member (min live member) can itself be wedged while
    its supervisor lease stays fresh: after half the replan window any
    waiting member commits the plan itself (O_EXCL arbitrates), so the
    fleet replans instead of timing out."""
    led = FleetLedger(str(tmp_path))
    cfg = FleetConfig(world_size=2, lease_ttl_s=10.0, poll_s=0.02,
                      replan_window_s=2.0)
    assert led.write_plan(_plan(0, [0, 1]))
    led.announce(0), led.heartbeat(0)     # leader rank 0: fresh, silent
    led.announce(1), led.heartbeat(1)
    t0 = time.monotonic()
    plan = fleet_mod._await_next_plan(led, cfg, rank=1, gen=0)
    assert time.monotonic() - t0 >= cfg.replan_window_s / 2 - 0.1
    assert plan["created_by"] == 1 and plan["reason"] == "reform"
    assert plan["members"] == [0, 1]


def test_joiner_takes_over_only_when_every_member_lease_is_stale(tmp_path):
    """A joiner polling a generation whose members ALL crashed (every
    lease stale, nobody left in _await_next_plan) commits the next
    plan itself instead of waiting forever; while any member is fresh
    it stays a polite joiner."""
    led = FleetLedger(str(tmp_path))
    cfg = FleetConfig(lease_ttl_s=0.2, poll_s=0.0)
    led.announce(0), led.heartbeat(0)
    led.announce(1)
    plan = _plan(0, [0])
    assert led.write_plan(plan)
    led.heartbeat(1)
    assert not fleet_mod._take_over_dead_generation(led, cfg, 1, plan)
    time.sleep(0.3)                       # member 0's lease goes stale
    led.heartbeat(1)                      # the joiner stays fresh
    assert fleet_mod._take_over_dead_generation(led, cfg, 1, plan)
    nxt = led.read_plan(1)
    assert nxt["members"] == [1] and nxt["created_by"] == 1
    assert "takeover" in [e["kind"] for e in led.events()]


@pytest.mark.slow
def test_formation_death_replans_instead_of_cascading_fatal(tmp_path):
    """A peer dying during cluster FORMATION must end in a replan, not
    total fleet death.  jax's distributed client LOG(FATAL)s the child
    (SIGABRT — no Python except path) when its peer never arrives, so
    the SUPERVISOR applies the lease classification to the hard exit:
    with the peer's lease stale it replans onto the smaller mesh and
    finishes, instead of recording rank_fatal and stopping its lease
    (which cascaded one rank's formation death into every rank's)."""
    from apex_tpu.parallel.multiproc import _free_port
    led = FleetLedger(str(tmp_path))
    cfg = FleetConfig(num_steps=3, checkpoint_every=2, world_size=2,
                      lease_ttl_s=0.5, heartbeat_s=0.1, poll_s=0.05,
                      init_timeout_s=2.0, init_retries=0,
                      replan_window_s=30.0)
    led.write_config(cfg)
    led.announce(0)
    led.heartbeat(0)      # stale long before init gives up: a dead peer
    # gen 0 plans ranks {0, 1}, but rank 0 is already gone and its
    # coordinator port has no listener: rank 1's child dies in
    # formation (SIGABRT from the distributed client)
    assert led.write_plan(_plan(0, [0, 1], port=_free_port()))
    env = dict(os.environ)
    for var in ("XLA_FLAGS", "COORDINATOR_ADDRESS", "WORLD_SIZE", "RANK"):
        env.pop(var, None)
    env["PYTHONPATH"] = str(REPO_ROOT) + os.pathsep + \
        env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "apex_tpu.resilience.fleet",
         "--role", "supervisor", "--ledger", str(tmp_path),
         "--rank", "1"],
        env=env, capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, (r.returncode, r.stdout[-2000:],
                               r.stderr[-2000:])
    events = led.events()
    kinds = [e["kind"] for e in events]
    assert "child_death_reclassified" in kinds      # not rank_fatal
    assert "rank_fatal" not in kinds
    hard = next(e for e in events
                if e["kind"] == "child_death_reclassified")
    assert hard["reason"] == "shrink" and hard["ranks"] == [0]
    # the supervisor speaks the child's vocabulary: canonical
    # shrink_detected event + schema-valid fleet-shrink incident with
    # a flight tail (the child died too hard to write its own)
    shr = next(e for e in events if e["kind"] == "shrink_detected")
    assert shr["via"] == "supervisor" and shr["ranks"] == [0]
    from apex_tpu.resilience.incidents import validate_incident_file
    inc_dir = led.path("incidents")
    shrink_incs = [os.path.join(inc_dir, n) for n in os.listdir(inc_dir)
                   if "fleet-shrink" in n]
    assert shrink_incs and all(
        validate_incident_file(p) == [] for p in shrink_incs)
    with open(shrink_incs[0]) as f:
        tail = {ev["kind"] for ev in json.load(f)["flight"]["events"]}
    assert {"kill", "shrink_detected"} <= tail
    plan1 = led.read_plan(1)
    assert plan1["members"] == [1] and plan1["reason"] == "shrink"
    finals = led.finals()
    assert sorted(finals) == [1]
    assert finals[1]["step"] == cfg.num_steps - 1


# ---------------------------------------------------------------------------
# the real 2-process SIGKILL drill (slow lane)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.skipif(
    os.environ.get("APEX_TPU_TEST_PLATFORM") not in (None, "cpu"),
    reason="the drill spawns its own CPU-backend cluster")
def test_real_fleet_drill_kill_shrink_regrow_bitwise(tmp_path):
    """The acceptance drill as a test: a real rank SIGKILLed
    mid-training, the fleet shrinks, regrows, and the artifact
    validates with all bitwise verdicts true."""
    out = tmp_path / "TRAINFLEET_r01.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "train_fleet.py"),
         "--root", str(tmp_path / "drill"), "--out", str(out)],
        env=env, capture_output=True, text=True, timeout=560)
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    doc = json.loads(out.read_text())
    from apex_tpu.analysis.trainfleet import validate_trainfleet
    assert validate_trainfleet(doc) == []
    assert doc["gate"]["ok"] and all(doc["bitwise"].values())
    assert len(doc["generations"]) >= 3
