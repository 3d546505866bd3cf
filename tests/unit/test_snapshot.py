"""Overlapped snapshots + grace-window budgeter (``checkpoint/snapshot.py``).

The overlap pin: arming snapshots must not touch the compiled step program
(same executable object, no recompile — donation/sanitizer budgets therefore
can't move) and the capture runs OUTSIDE the traced step span. The grace
pin: under the virtual clock, measured write+fsync time drives
``Elastic/grace_margin_ms``, an injected slow write fires a once-per-run
warning instead of tearing a checkpoint, and the budgeter stretches the
capture cadence when the writer can't keep up.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.checkpoint import atomic
from deepspeed_tpu.checkpoint.snapshot import GraceBudgeter, SnapshotManager
from deepspeed_tpu.models import get_model
from deepspeed_tpu.serving.clock import VirtualClock
from deepspeed_tpu.testing import FaultInjector

pytestmark = pytest.mark.faults

# jaxlib 0.4.x crash-class discipline (PR 3 root cause): engines here are
# deliberately LEAKED, never destroy()ed — freeing CPU-collective
# executables deserialized from the warm compile cache aborts the process,
# and toggling the compilation cache mid-suite is another trigger. (jaxlib
# 0.9.0 frees them cleanly: the engine-churning chaos_train tool runs in
# process below, warm cache or cold.)


def _engine(tmp_path=None, elastic=None, telemetry=False):
    model = get_model("gpt2", "tiny", vocab_size=128, max_seq_len=32,
                      compute_dtype=jnp.float32)
    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2}, "mesh": {"data": 8},
        "checkpoint": {"engine": "sharded"},
        "steps_per_print": 10 ** 9}
    if elastic is not None:
        config["elastic"] = elastic
    if telemetry:
        config["telemetry"] = {"enabled": True,
                               "output_path": str(tmp_path / "traces"),
                               "job_name": "snap"}
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    return eng


def _batch(step):
    rng = np.random.RandomState(9000 + step)
    return {"input_ids": rng.randint(0, 128, (8, 16)).astype(np.int32)}


# ---------------------------------------------------------------------------
# budgeter units (pure host logic — exact under injected durations)
# ---------------------------------------------------------------------------
def _cfg(**kw):
    from deepspeed_tpu.config.config import ElasticConfig

    base = {"enabled": True, "snapshot_interval": 1, "grace_period_s": 10.0,
            "safety_factor": 2.0, "max_interval": 16}
    base.update(kw)
    return ElasticConfig.from_dict(base)


def test_budgeter_margin_and_once_per_run_warning():
    b = GraceBudgeter(_cfg(grace_period_s=4.0, safety_factor=2.0))
    b.record_write(1.0)
    assert b.grace_margin_s() == pytest.approx(4.0 - 2.0)
    assert b.check(step=4) > 0 and b.warnings == 0  # healthy: no warning
    b.record_write(3.0)  # estimate = max of window = 3.0 -> 6.0 > 4.0
    assert b.grace_margin_s() == pytest.approx(-2.0)
    assert b.check(step=5) < 0
    assert b.check(step=6) < 0  # second breach: no second warning
    assert b.warnings == 1


def test_budgeter_stretches_cadence_to_writer_speed():
    b = GraceBudgeter(_cfg(snapshot_interval=1, max_interval=8))
    assert b.effective_interval() == 1  # no data yet: configured cadence
    b.record_step(0.5)
    b.record_write(2.0)  # writer needs 4 steps to drain
    assert b.effective_interval() == 4
    b.record_write(100.0)  # pathological writer: capped, never unbounded
    assert b.effective_interval() == 8


# ---------------------------------------------------------------------------
# the overlap pin
# ---------------------------------------------------------------------------
def test_snapshot_does_not_touch_the_step_program(tmp_path, devices8):
    eng = _engine(tmp_path, elastic={"enabled": True, "snapshot_interval": 1},
                  telemetry=True)
    mgr = SnapshotManager(eng, str(tmp_path / "ckpt"), cfg=eng.config.elastic)
    eng.train_batch(batch=_batch(0))
    fn = eng._train_step_fn
    assert fn is not None
    mgr.maybe_snapshot()
    eng.train_batch(batch=_batch(1))
    mgr.maybe_snapshot()
    # the compiled step is the SAME executable — no recompile, so the
    # donation (64 aliased inputs) and 0-transfer sanitizer budgets the
    # tier-1 audit enforces cannot have moved
    assert eng._train_step_fn is fn
    mgr.close()
    eng.tracer.flush()
    spans_path = os.path.join(str(tmp_path / "traces"), "snap", "spans.jsonl")
    spans = [json.loads(l) for l in open(spans_path) if l.strip()]
    names = [s.get("name") for s in spans]
    assert "checkpoint/snapshot" in names
    assert "checkpoint/snapshot_write" in names
    # capture happens OUTSIDE the step: no snapshot span nests inside a
    # train_batch span (depth 0 = top level in this harness)
    for s in spans:
        if s.get("name") == "checkpoint/snapshot":
            assert s.get("depth", 0) == 0


def test_snapshot_tags_are_valid_resume_candidates(tmp_path, devices8):
    """Every published snapshot is a complete COMMITTED checkpoint, and the
    background writer advances 'latest' as it goes (commit-per-write), so
    retention sees committed history immediately and the flush is a no-op
    pointer check when nothing is in flight."""
    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1})
    mgr = SnapshotManager(eng, str(tmp_path), cfg=eng.config.elastic)
    for s in range(2):
        eng.train_batch(batch=_batch(s))
        mgr.maybe_snapshot()
    mgr.close()
    assert atomic.read_latest(str(tmp_path)) == "elastic-step2"
    tags = atomic.list_tags(str(tmp_path))
    assert tags == ["elastic-step2", "elastic-step1"]
    for tag in tags:
        ok, reason = atomic.verify_checkpoint_dir(
            os.path.join(str(tmp_path), tag))
        assert ok, reason
    # flush confirms the freshest commit (everything already durable)
    tag, step = mgr.flush("test")
    assert (tag, step) == ("elastic-step2", 2)
    assert atomic.read_latest(str(tmp_path)) == "elastic-step2"


# ---------------------------------------------------------------------------
# the grace pin (virtual clock + injected slow writes)
# ---------------------------------------------------------------------------
def test_grace_margin_measured_under_virtual_clock(tmp_path, devices8):
    clock = VirtualClock()

    def slow_disk(event, path):
        if event == "write":
            clock.advance(3.0)  # every durable file write "takes" 3s

    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1,
                           "grace_period_s": 4.0, "safety_factor": 2.0})
    mgr = SnapshotManager(eng, str(tmp_path), cfg=eng.config.elastic,
                          clock=clock)
    atomic.register_fault_hook(slow_disk)
    try:
        for s in range(3):
            eng.train_batch(batch=_batch(s))
            clock.advance(1.0)  # 1s steps
            mgr.maybe_snapshot()
        result = mgr.flush("test")
    finally:
        atomic.unregister_fault_hook(slow_disk)
    # the injected slow write fired the once-per-run warning, NOT a torn
    # checkpoint: the flush still committed a verifiable tag
    assert result is not None
    tag, step = result
    ok, reason = atomic.verify_checkpoint_dir(os.path.join(str(tmp_path), tag))
    assert ok, reason
    assert atomic.read_latest(str(tmp_path)) == tag
    assert mgr.budget.warnings == 1  # the once-per-run slow-write warning
    assert mgr.budget.grace_margin_s() < 0
    # a snapshot write stages 3 durable files (shards/pieces/meta + marker):
    # measured, not assumed (cadence-stretch policy is pinned in the
    # budgeter unit test — under the SHARED virtual clock the step deltas
    # here include the writer's own advances)
    assert mgr.budget.flush_estimate_s() >= 9.0


# ---------------------------------------------------------------------------
# background-writer failure edges
# ---------------------------------------------------------------------------
def test_writer_failure_with_no_fresher_shadow_raises_at_flush(tmp_path,
                                                               devices8):
    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1})
    mgr = SnapshotManager(eng, str(tmp_path), cfg=eng.config.elastic)
    eng.train_batch(batch=_batch(0))
    with FaultInjector() as fi:
        fi.fail_async_write(match="shards-0")
        mgr.maybe_snapshot()
        mgr._drain()
        with pytest.raises(atomic.CheckpointError):
            mgr.flush("test")
    # nothing committed, nothing torn-published
    assert atomic.read_latest(str(tmp_path)) is None
    assert atomic.list_tags(str(tmp_path)) == []


def test_writer_failure_recovers_via_fresher_shadow(tmp_path, devices8):
    """A failed background write of snapshot N is healed by snapshot N+1:
    the flush writes the FRESHER remainder and commits it."""
    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1})
    mgr = SnapshotManager(eng, str(tmp_path), cfg=eng.config.elastic)
    with FaultInjector() as fi:
        fi.fail_async_write(match="shards-0", times=1)
        eng.train_batch(batch=_batch(0))
        mgr.maybe_snapshot()
        mgr._drain()  # background write of step 1 died
        eng.train_batch(batch=_batch(1))
        mgr.maybe_snapshot()
        tag, step = mgr.flush("test")
    assert (tag, step) == ("elastic-step2", 2)
    assert atomic.read_latest(str(tmp_path)) == "elastic-step2"


def test_agent_falls_back_to_sync_save_when_flush_fails(tmp_path, devices8):
    """The ordered teardown's safety net: a flush that raises falls back to
    a full synchronous save — the preemption still ends committed."""
    from deepspeed_tpu.elasticity import ElasticAgent
    from deepspeed_tpu.testing import sigterm_data_iter

    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1})
    agent = ElasticAgent(eng, str(tmp_path), save_interval=1000)

    real_flush = agent.snapshots.flush
    agent.snapshots.flush = lambda *a, **k: (_ for _ in ()).throw(
        atomic.CheckpointError("flush down"))
    status, steps = agent.run(sigterm_data_iter(
        (_batch(s) for s in range(50)), at_step=2), total_steps=50)
    agent.snapshots.flush = real_flush
    assert status == "preempted" and steps == 2
    latest = atomic.read_latest(str(tmp_path))
    assert latest == "elastic-step2"
    ok, reason = atomic.verify_checkpoint_dir(
        os.path.join(str(tmp_path), latest))
    assert ok, reason


def test_stale_pending_shadow_is_never_resurrected(tmp_path, devices8):
    """A shadow parked while a write was in flight is ORPHANED if that write
    fails; a later capture that starts its own write directly must drop the
    stale shadow — resurrecting it would regress the freshest published step
    and point 'latest' backwards at flush (review finding)."""
    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1})
    mgr = SnapshotManager(eng, str(tmp_path), cfg=eng.config.elastic)
    gate = threading.Event()
    state = {"fired": False}

    def stall_then_fail(event, path):
        # first background shards write: block until released, then die
        if event == "write" and "shards-0" in path and not state["fired"] \
                and threading.current_thread() is not threading.main_thread():
            state["fired"] = True
            gate.wait(timeout=30)
            raise OSError("injected: write died after stall")

    atomic.register_fault_hook(stall_then_fail)
    try:
        eng.train_batch(batch=_batch(0))
        mgr.maybe_snapshot()          # step-1 write stalls in background
        eng.train_batch(batch=_batch(1))
        mgr.maybe_snapshot()          # step-2 shadow parks as pending
        gate.set()                    # step-1 write now FAILS -> 2 orphaned
        mgr._drain()
        eng.train_batch(batch=_batch(2))
        # capture() directly: the budgeter may have stretched the cadence
        # (the stalled write inflated its estimate) and this scenario needs
        # the step-3 shadow to exist
        mgr.capture()                 # step-3: direct start, must drop 2
        tag, step = mgr.flush("test")
    finally:
        gate.set()
        atomic.unregister_fault_hook(stall_then_fail)
    assert (tag, step) == ("elastic-step3", 3)
    assert atomic.read_latest(str(tmp_path)) == "elastic-step3"
    assert mgr.stats["dropped_shadows"] >= 1
    # the orphaned step-2 shadow was never written behind step 3's back
    assert "elastic-step2" not in atomic.list_tags(str(tmp_path))


def test_chaos_train_tool_smoke(tmp_path):
    """tier-1 smoke of tools/chaos_train.py on the tiny preset: one seeded
    kill at equal scale, artifact stamped, exit 0 (survival + continuity +
    lost-steps gates)."""
    from tools import chaos_train

    out = str(tmp_path / "chaos.json")
    assert chaos_train.main(
        ["--steps", "6", "--kills", "1", "--seed", "1", "--meshes", "8",
         "--ckpt-dir", str(tmp_path / "ckpt"), "--out", out]) == 0
    report = json.loads(open(out).read())
    assert report["preemptions_survived"] == 1
    assert report["max_lost_steps"] <= 1  # the snapshot cadence
    assert report["loss_continuity"]["max_abs_delta"] == 0.0  # equal scale
    assert report["flush_fits_grace"]
    assert report["provenance"]["git_sha"]  # stamped


def test_freshest_wins_when_writer_is_busy(tmp_path, devices8):
    """Captures landing while the writer is busy replace each other — at
    most one write is queued, and the queued one is the freshest."""
    eng = _engine(elastic={"enabled": True, "snapshot_interval": 1})
    mgr = SnapshotManager(eng, str(tmp_path), cfg=eng.config.elastic)
    gate = threading.Event()

    def stall(event, path):
        if event == "write" and "shards-0" in path \
                and threading.current_thread() is not threading.main_thread():
            gate.wait(timeout=30)

    atomic.register_fault_hook(stall)
    try:
        for s in range(3):
            eng.train_batch(batch=_batch(s))
            mgr.maybe_snapshot()
        # writer stalled on step-1's write; steps 2 and 3 were captured:
        # 3 replaced 2 as the single pending shadow
        assert mgr.stats["dropped_shadows"] >= 1
    finally:
        gate.set()
        atomic.unregister_fault_hook(stall)
    tag, step = mgr.flush("test")
    assert step == 3  # the freshest shadow won
