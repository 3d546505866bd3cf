"""Fleet robustness tests (tier-1): live KV migration + failure recovery.

The acceptance invariants of the serving fleet's recovery primitive
(ROADMAP item: robustness), all assertable under the virtual clock:

- a request live-migrated mid-stream (drain-by-migration) continues on the
  target replica BITWISE-identically to a stay-put run — greedy AND seeded
  sampling, single-device and TP=2, fp32 and int8 pools — and the target's
  compile-once pins (decode==1, insert==1) hold across the splice;
- a seeded replica kill mid-stream loses ZERO committed tokens: every
  affected request completes on a surviving replica from its last periodic
  snapshot (splice + bounded tail replay) or a full resume replay, and the
  whole fleet trajectory is deterministic under the same chaos schedule;
- drain-by-migration empties the replica in one evacuation pass (restart
  loses nothing) and strictly beats wait-for-finish on fleet makespan and
  TTFT p99 when load keeps arriving, with zero recompute when fresh
  snapshots exist;
- migrated blocks dedupe against the target's prefix cache — a snapshot
  whose prefix the target already holds splices only the private tail, and
  a splice republishes the prefix for later same-prompt requests;
- an ``unhealthy_slot`` shed on a multi-replica fleet retries once on a
  DIFFERENT replica before shedding, bounded by ``serving.retry_limit``
  and counted distinctly from failovers; the terminal fallback is a
  shed-with-reason ``replica_failed``;
- ``ReplicaChaosSchedule`` is seeded/deterministic, respects min-gap, and
  never kills the same replica twice.
"""

import functools

import numpy as np
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import ServingConfig
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (REJECT_REPLICA_FAILED, Request,
                                   RequestState, Router, SamplingParams,
                                   ServingEngine, VirtualClock)
from deepspeed_tpu.testing.fault_injection import ReplicaChaosSchedule

from .conftest import make_full_replica, ref_tokens, tiny_cfg


make_replica = functools.partial(make_full_replica, job_name="chaos")


def make_router(engine, n=2, trace_dir=None, **kw):
    return Router([make_replica(engine, trace_dir=trace_dir, **kw)
                   for _ in range(n)])


def stay_put_tokens(engine, req, **kw):
    """The same request run to completion on one fresh replica — the
    stay-put reference for sampled streams (greedy also matches
    ``generate()``; sampled streams are pinned to the slot rng chain)."""
    r2 = Request(prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                 sampling=SamplingParams(**vars(req.sampling)))
    sv = make_replica(engine, **kw)
    fin, rej, _ = sv.run([r2])
    assert len(fin) == 1 and not rej
    return np.asarray(r2.tokens)


def mixed_requests(rng, n, max_new=8, plen=(9, 30), seed0=100):
    """Alternating greedy / seeded-sampled requests."""
    return [Request(
        prompt=rng.randint(0, 64, (int(rng.randint(*plen)),)).astype(np.int32),
        max_new_tokens=max_new,
        sampling=SamplingParams(temperature=0.8, top_k=8, seed=seed0 + i)
        if i % 2 else None)
        for i in range(n)]


# ---------------------------------------------------------------------------
# 1. the chaos schedule itself
# ---------------------------------------------------------------------------

def test_replica_chaos_schedule_seeded():
    a = ReplicaChaosSchedule(7, horizon=4.0, n_replicas=3, n_kills=2,
                             n_stalls=2, min_gap=0.1)
    b = ReplicaChaosSchedule(7, horizon=4.0, n_replicas=3, n_kills=2,
                             n_stalls=2, min_gap=0.1)
    assert a.events == b.events and len(a) == 4
    times = [e[0] for e in a.events]
    assert times == sorted(times)
    assert all(t2 - t1 >= 0.1 for t1, t2 in zip(times, times[1:]))
    assert all(0.1 <= t <= 3.9 for t in times)
    # kills never repeat a replica; every target is in range
    kills = [e[2] for e in a.events if e[1] == "kill"]
    assert len(set(kills)) == len(kills) == 2
    assert all(0 <= e[2] < 3 for e in a.events)
    assert all(e[3] > 0 for e in a.events if e[1] == "stall")
    # a different seed moves the instants
    c = ReplicaChaosSchedule(8, horizon=4.0, n_replicas=3, n_kills=2,
                             n_stalls=2, min_gap=0.1)
    assert c.events != a.events
    with pytest.raises(ValueError):
        ReplicaChaosSchedule(0, horizon=0.2, n_replicas=3, n_kills=2,
                             n_stalls=2, min_gap=0.1)
    with pytest.raises(ValueError):
        ReplicaChaosSchedule(0, horizon=10.0, n_replicas=2, n_kills=3)


# ---------------------------------------------------------------------------
# 2. migration bitwise parity (the tentpole pin)
# ---------------------------------------------------------------------------

def _drain_migrate_run(engine, trace_dir=None, **replica_kw):
    """Start a mixed workload on 2 replicas, drain replica 0 by migration
    mid-stream, finish on the peer. Returns (router, reqs, committed)."""
    router = make_router(engine, n=2, trace_dir=trace_dir, **replica_kw)
    rng = np.random.RandomState(0)
    reqs = mixed_requests(rng, 4)
    for r in reqs:
        router.submit(r)
    for _ in range(300):
        router.step()
        if all(len(r.tokens) >= 3 for r in reqs):
            break
    assert all(len(r.tokens) >= 3 for r in reqs)
    committed = {r.request_id: list(r.tokens) for r in reqs}
    shed = router.drain(0, migrate=True)
    assert not shed and router.drained(0)  # one evacuation pass, no losses
    while any(rep.busy for rep in router._replicas):
        router.step()
    return router, reqs, committed


def test_migration_bitwise_vs_stay_put(engine):
    """Drain-by-migration mid-stream: every moved stream (greedy AND seeded
    sampled) is bitwise-equal to a stay-put run and to sequential
    generate(); committed tokens never rewind; fresh snapshots splice with
    ZERO recompute; the target's compile-once pins hold."""
    router, reqs, committed = _drain_migrate_run(engine)
    mig = router.metrics.snapshot()["migration"]
    assert mig["migrations_out"] >= 2 and mig["migrations_in"] >= 2
    assert mig["kv_snapshots"] >= mig["migrations_out"]
    for r in reqs:
        assert r.state is RequestState.FINISHED
        assert r.tokens[:len(committed[r.request_id])] \
            == committed[r.request_id]
        np.testing.assert_array_equal(
            np.asarray(r.tokens), stay_put_tokens(engine, r))
        if r.sampling.temperature <= 0:
            np.testing.assert_array_equal(np.asarray(r.tokens),
                                          ref_tokens(engine, r))
    # fresh snapshots (captured at evacuation) splice, never replay
    assert router.metrics.fleet_goodput()["replay_tokens"] == 0
    migrated = [r for r in reqs if r.migrations]
    assert migrated and all(r.failovers == 0 for r in reqs)
    # the splice re-entered the compiled insert path: still one compile each
    for counts in router.compile_counts():
        assert counts["decode"] == 1 and counts["insert"] == 1


def test_migration_bitwise_int8_pool(engine):
    """Same pin on an int8-quantized pool: raw payload + scales move
    byte-for-byte (a dequant->requant round trip would perturb the scales'
    last ulp), so migrated int8 streams match stay-put int8 streams
    exactly — and the dedicated migrate-in program compiled once."""
    kw = dict(kv_pool={"block_size": 8,
                       "on_demand_growth": True, "kv_dtype": "int8"})
    router, reqs, committed = _drain_migrate_run(engine, **kw)
    assert router.metrics.snapshot()["migration"]["migrations_in"] >= 2
    for r in reqs:
        assert r.state is RequestState.FINISHED
        assert r.tokens[:len(committed[r.request_id])] \
            == committed[r.request_id]
        np.testing.assert_array_equal(
            np.asarray(r.tokens), stay_put_tokens(engine, r, **kw))
    for counts in router.compile_counts():
        assert counts["decode"] == 1 and counts.get("migrate_in", 0) <= 1


def test_migration_tp_mesh_parity(devices8):
    """TP=2 leg: migration moves sharded pool blocks between model-parallel
    replicas; greedy streams still match the single-device reference
    bitwise after a mid-stream drain-by-migration."""
    import jax

    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.parallel import build_mesh

    cfg = tiny_cfg(position_embedding="rope")
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(4)))
    mesh = build_mesh(MeshConfig(model=2, data=4), devices=devices8)
    eng = InferenceEngine(model, DeepSpeedInferenceConfig.from_dict(
        {"dtype": "float32", "max_tokens": 64,
         "tensor_parallel": {"tp_size": 2},
         "serving": {"n_slots": 2, "virtual_clock": True,
                     "chunked_prefill": {"enabled": True, "chunk_size": 8},
                     "kv_pool": {"block_size": 8,
                                 "on_demand_growth": True},
                     "migration": {"enabled": True,
                                   "snapshot_interval_tokens": 2}}}),
        mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)

    router = Router([ServingEngine(eng, clock=VirtualClock())
                     for _ in range(2)])
    rng = np.random.RandomState(9)
    reqs = [Request(
        prompt=rng.randint(0, 64, (int(rng.randint(10, 30)),)).astype(np.int32),
        max_new_tokens=6) for _ in range(4)]
    for r in reqs:
        router.submit(r)
    for _ in range(300):
        router.step()
        if all(len(r.tokens) >= 2 for r in reqs):
            break
    router.drain(0, migrate=True)
    while any(rep.busy for rep in router._replicas):
        router.step()
    assert router.metrics.snapshot()["migration"]["migrations_in"] > 0

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                       max_tokens=64)
    raw.params = values
    for r in reqs:
        assert r.state is RequestState.FINISHED
        ref = np.asarray(raw.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


# ---------------------------------------------------------------------------
# 3. kill-mid-stream failover
# ---------------------------------------------------------------------------

def test_kill_mid_stream_zero_lost_tokens(engine):
    """A replica crash mid-decode: every affected request completes on the
    survivor with its committed prefix intact (zero lost tokens), the tail
    replay is bounded by tokens-since-snapshot plus block-size slack, and
    the final streams stay bitwise-identical to stay-put runs."""
    router = make_router(engine, n=2)
    rng = np.random.RandomState(11)
    reqs = mixed_requests(rng, 4, max_new=10, plen=(12, 30), seed0=500)
    for r in reqs:
        router.submit(r)
    for _ in range(400):
        router.step()
        if all(len(r.tokens) >= 5 for r in reqs):
            break
    assert all(len(r.tokens) >= 5 for r in reqs)
    committed = {r.request_id: list(r.tokens) for r in reqs}
    # replay bound: tokens since the last periodic snapshot, plus at most
    # one partial block of KV the stale splice cannot carry
    bs = router._replicas[0].sv.pool_mgr.block_size
    bound = sum(
        len(r.tokens) - (len(r.migration.tokens) if r.migration else 0) + bs
        for r in reqs)
    shed = router.kill_replica(0)
    assert not shed  # retry budget covers one crash
    while any(rep.busy and not rep.dead for rep in router._replicas):
        router.step()
    mig = router.metrics.snapshot()["migration"]
    assert mig["replica_kills"] == 1 and mig["failovers"] >= 1
    gp = router.metrics.fleet_goodput()
    assert 0 <= gp["replay_tokens"] <= bound
    for r in reqs:
        assert r.state is RequestState.FINISHED
        assert r.tokens[:len(committed[r.request_id])] \
            == committed[r.request_id]
        np.testing.assert_array_equal(
            np.asarray(r.tokens), stay_put_tokens(engine, r))
    failed_over = [r for r in reqs if r.failovers]
    assert failed_over and all(r.failovers <= 1 for r in reqs)


def test_seeded_chaos_deterministic(engine):
    """The same ReplicaChaosSchedule over the same workload produces the
    same fleet trajectory twice: token streams, terminal states, recovery
    counters. Greedy survivors also match sequential generate()."""
    def run(seed):
        router = make_router(engine, n=3)
        rng = np.random.RandomState(7)
        reqs = [Request(
            prompt=rng.randint(0, 64, (int(rng.randint(9, 30)),))
            .astype(np.int32),
            max_new_tokens=8, arrival_time=i * 0.05,
            sampling=SamplingParams(temperature=0.8, top_k=8, seed=100 + i)
            if i % 2 else None)
            for i in range(8)]
        sched = ReplicaChaosSchedule(seed, horizon=2.0, n_replicas=3,
                                     n_kills=1, n_stalls=1)
        router.apply_chaos(sched)
        finished, rejected, snap = router.run(reqs)
        return reqs, finished, rejected, snap

    reqs1, fin1, rej1, snap1 = run(3)
    reqs2, fin2, rej2, snap2 = run(3)
    assert len(fin1) + len(rej1) == 8
    assert snap1["router"]["migration"]["replica_kills"] == 1
    assert snap1["router"]["migration"]["replica_stalls"] == 1
    assert "dead" in snap1["router"]["health"]
    for a, b in zip(reqs1, reqs2):
        assert a.state is b.state
        assert a.tokens == b.tokens
        assert a.failovers == b.failovers and a.migrations == b.migrations
    assert snap1["router"]["migration"] == snap2["router"]["migration"]
    assert snap1["goodput"]["replay_tokens"] == \
        snap2["goodput"]["replay_tokens"]
    for r in reqs1:
        if r.state is RequestState.FINISHED and r.sampling.temperature <= 0:
            np.testing.assert_array_equal(np.asarray(r.tokens),
                                          ref_tokens(engine, r))


def test_failover_retry_limit_sheds_replica_failed(engine):
    """With the retry budget exhausted (retry_limit=0), a crash sheds its
    started in-flight requests terminally with reason ``replica_failed`` —
    bounded failure, never a hang or a silent drop."""
    router = make_router(engine, n=2, retry_limit=0)
    rng = np.random.RandomState(2)
    reqs = mixed_requests(rng, 2, max_new=8)
    for r in reqs:
        router.submit(r)
    for _ in range(300):
        router.step()
        if all(len(r.tokens) >= 2 for r in reqs):
            break
    shed = router.kill_replica(0)
    victims = [r for r in reqs if r.state is RequestState.REJECTED]
    assert victims and len(shed) == len(victims)
    assert all(r.reject_reason == REJECT_REPLICA_FAILED for r in victims)
    assert all(e.done and e.finish_reason == "rejected:replica_failed"
               for e in shed)
    mig = router.metrics.snapshot()["migration"]
    assert mig["shed_replica_failed"] == len(victims)
    # survivors on the live replica keep decoding to completion
    while any(rep.busy and not rep.dead for rep in router._replicas):
        router.step()
    for r in reqs:
        if r.state is RequestState.FINISHED:
            np.testing.assert_array_equal(
                np.asarray(r.tokens), stay_put_tokens(engine, r))


# ---------------------------------------------------------------------------
# 4. drain-by-migration vs wait-for-finish
# ---------------------------------------------------------------------------

def _drain_scenario(engine, migrate):
    """Two long streams pin one replica; drain it for a restart while short
    requests keep arriving. Wait-for-finish holds the replica hostage for
    the long tails (new load single-files through the peer); migration
    moves the streams and restores fleet capacity immediately."""
    router = make_router(engine, n=2, n_slots=3)
    rng = np.random.RandomState(5)
    longs = [Request(prompt=rng.randint(0, 64, (12,)).astype(np.int32),
                     max_new_tokens=20, session_id="pin") for _ in range(2)]
    for r in longs:
        router.submit(r)
    idx = router._sessions["pin"]  # the replica both long streams stuck to
    for _ in range(300):
        router.step()
        if all(len(r.tokens) >= 3 for r in longs):
            break
    router.drain(idx, migrate=migrate)
    shorts = [Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                      max_new_tokens=6) for _ in range(16)]
    pending = list(shorts)
    while pending or any(rep.busy for rep in router._replicas):
        if router.drained(idx) and router._replicas[idx].draining:
            router.rejoin(idx)  # restart completes the moment it's empty
        if pending:
            router.submit(pending.pop(0))
        router.step()
    snap = router.snapshot()
    assert all(r.state is RequestState.FINISHED for r in longs + shorts)
    return router, longs, snap


def test_drain_migrate_beats_wait_for_finish(engine):
    """Same workload, same drain instant: drain-by-migration strictly beats
    wait-for-finish on fleet makespan AND TTFT p99, recomputes nothing
    (fresh snapshots), and the long streams stay bitwise-correct."""
    r_mig, longs_mig, snap_mig = _drain_scenario(engine, migrate=True)
    r_wait, longs_wait, snap_wait = _drain_scenario(engine, migrate=False)
    assert snap_mig["makespan"] < snap_wait["makespan"]
    assert snap_mig["ttft_ms"]["p99"] < snap_wait["ttft_ms"]["p99"]
    assert snap_mig["goodput"]["replay_tokens"] == 0
    assert snap_mig["router"]["migration"]["migrations_in"] >= 2
    assert snap_wait["router"]["migration"]["migrations_in"] == 0
    # identical math either way — only the schedule moved
    for a, b in zip(longs_mig, longs_wait):
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens))
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      ref_tokens(engine, a))


# ---------------------------------------------------------------------------
# 5. prefix-cache dedupe of migrated blocks
# ---------------------------------------------------------------------------

def test_migrated_blocks_dedupe_against_target_prefix_cache(engine):
    """Splicing rides the compiled insert path, so migrated blocks dedupe:
    (a) a snapshot whose prompt prefix the target already caches splices
    only the private tail (prefix_saved_tokens > 0 on the move), and
    (b) the splice republishes the prefix — a later same-prompt request on
    the target hits the cache without the migrated request ever having
    prefilled there."""
    router = make_router(engine, n=2)
    rng = np.random.RandomState(13)
    prompt = rng.randint(0, 64, (24,)).astype(np.int32)

    # (a) warm the future target with the same prompt (session-pinned)
    warm = Request(prompt=prompt.copy(), max_new_tokens=4, session_id="tgt")
    router.submit(warm)
    while warm.state is not RequestState.FINISHED:
        router.step()
    tgt = router._sessions["tgt"]
    src = 1 - tgt

    mover = Request(prompt=prompt.copy(), max_new_tokens=8, session_id="src")
    other = Request(prompt=rng.randint(0, 64, (10,)).astype(np.int32),
                    max_new_tokens=8, session_id="src2")
    # pin both to the source replica via session stickiness
    router._sessions["src"] = src
    router._sessions["src2"] = src
    router.submit(mover)
    router.submit(other)
    for _ in range(300):
        router.step()
        if len(mover.tokens) >= 3 and len(other.tokens) >= 3:
            break
    router.drain(src, migrate=True)
    while any(rep.busy for rep in router._replicas):
        router.step()
    assert mover.state is RequestState.FINISHED
    assert mover.migrations == 1
    # the warm prefix deduped the splice: shared blocks were NOT re-sent
    assert mover.prefix_saved_tokens > 0
    np.testing.assert_array_equal(np.asarray(mover.tokens),
                                  ref_tokens(engine, mover))

    # (b) the migrated request's blocks are published on the target: a new
    # same-prompt request there prefix-hits without any prior prefill
    late = Request(prompt=prompt.copy(), max_new_tokens=4, session_id="tgt")
    router.submit(late)
    while late.state is not RequestState.FINISHED:
        router.step()
    assert late.prefix_saved_tokens > 0
    np.testing.assert_array_equal(np.asarray(late.tokens),
                                  ref_tokens(engine, late))


# ---------------------------------------------------------------------------
# 6. unhealthy-slot cross-replica retry
# ---------------------------------------------------------------------------

def _poisoned_fleet(retry_limit):
    """Replica 0 over a model whose final layernorm is NaN (every decode
    sheds unhealthy), replica 1 over healthy weights."""
    import jax

    cfg = tiny_cfg()
    sick = deepspeed_tpu.init_inference(
        CausalLM(cfg), config={"dtype": "float32", "max_tokens": 64,
                               "health": {"enabled": True}})
    sick.params["ln_f"]["scale"] = sick.params["ln_f"]["scale"] * jnp.nan
    healthy = deepspeed_tpu.init_inference(
        CausalLM(cfg), config={"dtype": "float32", "max_tokens": 64,
                               "health": {"enabled": True}})
    mk = lambda eng: ServingEngine(
        eng, serving_config=ServingConfig(
            n_slots=2, virtual_clock=True, retry_limit=retry_limit,
            kv_pool={"block_size": 8,
                     "on_demand_growth": True}),
        clock=VirtualClock())
    return Router([mk(sick), mk(healthy)]), sick, healthy


def test_unhealthy_shed_retries_on_different_replica():
    """An unhealthy_slot shed before the first token retries ONCE on a
    different replica (bounded by serving.retry_limit) and completes there;
    the retry is counted distinctly from failovers."""
    router, sick, healthy = _poisoned_fleet(retry_limit=1)
    req = Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=4,
                  session_id="s0")
    router._sessions["s0"] = 0  # force the sick replica first
    router.submit(req)
    events = []
    for _ in range(300):
        events.extend(router.step())
        if req.state is RequestState.FINISHED:
            break
    assert req.state is RequestState.FINISHED
    assert req.finish_reason != "unhealthy_slot"
    assert req.retries == 1 and req.failovers == 0
    # the poisoned attempt never streamed: one clean final stream
    assert [e.token for e in events if e.request_id == req.request_id
            and not e.done] == req.tokens[:-1]
    mig = router.metrics.snapshot()["migration"]
    assert mig["retries"] == 1 and mig["failovers"] == 0
    sick.destroy(), healthy.destroy()


def test_unhealthy_shed_without_budget_stays_terminal():
    """retry_limit=0: the unhealthy shed keeps its original terminal
    semantics — no cross-replica retry, reason preserved."""
    router, sick, healthy = _poisoned_fleet(retry_limit=0)
    req = Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=4,
                  session_id="s0")
    router._sessions["s0"] = 0
    router.submit(req)
    for _ in range(300):
        router.step()
        if req.state is RequestState.FINISHED:
            break
    assert req.state is RequestState.FINISHED
    assert req.finish_reason == "unhealthy_slot"
    assert req.retries == 0
    assert router.metrics.snapshot()["migration"]["retries"] == 0
    sick.destroy(), healthy.destroy()


# ---------------------------------------------------------------------------
# 7. recovery accounting in the fleet wide events
# ---------------------------------------------------------------------------

def test_wide_events_carry_recovery_fields(engine, tmp_path):
    """The fleet merger surfaces migration/failover instants: wide events
    carry migrations/failovers/retries, the migrated stall lands in the
    breakdown like a preemption stall, and the latency rollup grows a
    ``migrated`` component."""
    from deepspeed_tpu.telemetry.fleet import (build_wide_events,
                                               latency_rollup,
                                               merge_fleet_events)

    router, reqs, _ = _drain_migrate_run(engine, trace_dir=tmp_path)
    sources = [("router", router.tracer.events)]
    sources += [(f"replica{i}", rep.sv.tracer.events)
                for i, rep in enumerate(router._replicas)]
    wide = build_wide_events(merge_fleet_events(sources))
    moved = [r for r in reqs if r.migrations]
    assert moved
    for r in moved:
        w = wide[r.request_id]
        assert w["state"] == "finished"
        assert w["migrations"] == r.migrations
        assert w["failovers"] == 0
        assert w["breakdown"] is not None
        assert w["breakdown"]["migrated"] >= 0.0
        assert w["migrated_saved_tokens"] > 0
    rollup = latency_rollup(wide)
    assert "migrated" in rollup and rollup["migrated"] >= 0.0


# ---------------------------------------------------------------------------
# 8. chaos_serve tool smoke
# ---------------------------------------------------------------------------

def test_chaos_serve_tool_smoke(tmp_path):
    """tier-1 smoke of tools/chaos_serve.py on the tiny preset: one seeded
    kill + one stall over a 3-replica fleet, artifact stamped, exit 0 (fault
    survival + bitwise continuity + determinism + shed gates). main() is
    called in process: the tool builds and destroys its own engine, and a
    child would spend its first seconds importing JAX to do the same."""
    import json

    from tools import chaos_serve

    out = str(tmp_path / "chaos_serve.json")
    assert chaos_serve.main(
        ["--replicas", "3", "--requests", "8", "--kills", "1",
         "--stalls", "1", "--seed", "1", "--out", out]) == 0
    report = json.loads(open(out).read())
    assert report["kills_fired"] == 1
    assert report["stalls_fired"] == 1
    assert report["nonterminal_requests"] == []
    assert report["bitwise_mismatches"] == []
    assert report["deterministic_rerun"] is True
    assert report["resilience"]["failovers"] >= 0
    assert report["provenance"]["git_sha"]  # stamped
