"""Multi-tenant QoS + SLO-driven control loop tests (tier-1).

The acceptance invariants of ``serving.tenants`` / ``serving.autoscaler`` /
``serving.degraded`` (ROADMAP item: close the control loop on the serving
fleet), all assertable under the virtual clock:

- weighted-fair admission (start-time fair queuing over tenant classes)
  converges to the configured weight share over a busy interval, is
  work-conserving (a lone tenant gets every slot), bounds batch starvation
  (the max interactive run between batch admissions is the weight ratio,
  not unbounded), and keeps within-tenant order strict FCFS;
- per-tenant token budgets gate admission EXACTLY under the virtual clock
  (admissions spaced cost/rate apart once the burst is spent) and defer —
  never shed — over-budget tenants;
- priority preemption (interactive evicts the newest batch stream through
  the rollback-safe preempt machinery) leaves every stream — evictor and
  evicted — bitwise-identical to its uncontended run, greedy and seeded
  sampled, single-device and TP=2;
- the degraded ladder sheds batch at rung 1 and interactive ONLY at the
  last rung (zero interactive sheds below it — the ordering pin), climbs
  and descends one rung at a time with hysteresis;
- the autoscaler, on a seeded three-phase workload (steady / burst /
  sparse tail), holds interactive p99 TTFT within the SLO with STRICTLY
  fewer cumulative replica-steps than a static max fleet AND strictly
  fewer SLO violations than a static min fleet; its scale decisions are
  deterministic across reruns and never ping-pong (monotone
  up-then-down profile on the single-burst workload).
"""

import functools

import numpy as np
import pytest

from deepspeed_tpu.config import (ConfigError, DegradedConfig, ServingConfig,
                                  SLOConfig, TenantsConfig)
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (CLASS_BATCH, CLASS_INTERACTIVE,
                                   DEGRADED_LADDER, DegradedModeController,
                                   REJECT_DEGRADED, Request, RequestQueue,
                                   RequestState, Router, SamplingParams,
                                   ServingEngine, ServingScheduler,
                                   VirtualClock)
from deepspeed_tpu.telemetry.digest import LatencyDigest

from .conftest import make_full_replica, ref_tokens, tiny_cfg


make_replica = functools.partial(make_full_replica, job_name="qos")


def qos_replica(engine, **kw):
    kw.setdefault("policy", "weighted_fair")
    kw.setdefault("tenants", {"enabled": True})
    return make_replica(engine, **kw)


def host_req(tid, cls, prompt_len=8, max_new=8):
    return Request(prompt=np.ones(prompt_len, np.int32), max_new_tokens=max_new,
                   tenant_id=tid, tenant_class=cls)


# --------------------------------------------------------------- config


def test_qos_config_validation():
    cfg = ServingConfig(policy="weighted_fair", tenants={"enabled": True})
    assert cfg.tenants.interactive.weight == 4.0      # defaults instantiated
    assert cfg.tenants.batch.weight == 1.0
    assert cfg.tenants.class_config(CLASS_BATCH) is cfg.tenants.batch
    with pytest.raises(ConfigError):
        ServingConfig(policy="priority")
    with pytest.raises(ConfigError):                  # autoscaler needs a sensor
        ServingConfig(autoscaler={"enabled": True})
    ServingConfig(autoscaler={"enabled": True, "scale_up_queue_depth": 4.0})
    ServingConfig(autoscaler={"enabled": True}, slo={"ttft_p99_ms": 100.0})
    with pytest.raises(ConfigError):                  # no dead band
        ServingConfig(autoscaler={"enabled": True, "scale_down_burn": 2.0},
                      slo={"ttft_p99_ms": 100.0})
    with pytest.raises(ConfigError):                  # ladder needs a burn input
        ServingConfig(degraded={"enabled": True})
    with pytest.raises(ConfigError):                  # no dead band
        ServingConfig(degraded={"enabled": True, "exit_burn": 1.5},
                      slo={"ttft_p99_ms": 100.0})
    with pytest.raises(ConfigError):
        ServingConfig(tenants={"enabled": True,
                               "interactive": {"weight": -1.0}})


def test_unknown_tenant_class_is_bad_request():
    q = RequestQueue(max_depth=8)
    req = Request(prompt=np.ones(4, np.int32), max_new_tokens=4,
                  tenant_class="premium")
    assert q.admit(req, 64) == "bad_request"
    assert req.state is RequestState.REJECTED


# ------------------------------------------------- weighted-fair admission


def fair_scheduler(**tenant_kw):
    q = RequestQueue(max_depth=4096)
    tenants = TenantsConfig(enabled=True, **tenant_kw)
    return q, ServingScheduler(q, n_slots=1, policy="weighted_fair",
                               tenants=tenants)


def test_weighted_fair_share_and_bounded_starvation():
    """Backlogged 4:1 tenants: admissions converge to the weight share,
    and the longest interactive run between batch admissions is bounded
    by the weight ratio (batch starvation is bounded by construction)."""
    q, sched = fair_scheduler()
    order = []
    now = 0.0
    for step in range(200):
        # keep both tenants continuously backlogged
        while sum(1 for i in range(len(q))
                  if q.peek_at(i).tenant_id == "ti") < 2:
            q.admit(host_req("ti", CLASS_INTERACTIVE), 64)
        while sum(1 for i in range(len(q))
                  if q.peek_at(i).tenant_id == "tb") < 2:
            q.admit(host_req("tb", CLASS_BATCH), 64)
        for r in sched.next_admissions(1, now):
            order.append(r.tenant_class)
        now += 1.0
    n_int = order.count(CLASS_INTERACTIVE)
    n_bat = order.count(CLASS_BATCH)
    assert n_bat > 0 and n_int > 0
    assert 3.0 <= n_int / n_bat <= 5.0          # 4:1 weights, SFQ-converged
    # bounded starvation: no interactive run longer than ~the weight ratio
    run = longest = 0
    for cls in order:
        run = run + 1 if cls == CLASS_INTERACTIVE else 0
        longest = max(longest, run)
    assert longest <= 6


def test_weighted_fair_work_conserving():
    """A lone batch tenant gets EVERY slot despite weight 1 — weights
    share busy intervals, they never idle capacity."""
    q, sched = fair_scheduler()
    for _ in range(10):
        q.admit(host_req("tb", CLASS_BATCH), 64)
    got = []
    for step in range(10):
        got.extend(sched.next_admissions(1, float(step)))
    assert len(got) == 10
    assert all(r.tenant_id == "tb" for r in got)


def test_weighted_fair_within_tenant_fcfs():
    q, sched = fair_scheduler()
    reqs = [host_req("ti", CLASS_INTERACTIVE) for _ in range(5)]
    for i, r in enumerate(reqs):
        r.request_id = i
        q.admit(r, 64)
    out = []
    for step in range(5):
        out.extend(sched.next_admissions(1, float(step)))
    assert [r.request_id for r in out] == [0, 1, 2, 3, 4]


def test_weighted_fair_returner_outranks_fresh():
    """A preemption returner (admit_time stamped, push_front'ed) wins the
    next slot ahead of any fresh arrival, and its re-admission is never
    re-charged (the SFQ tag and bucket moved at FIRST admission)."""
    q, sched = fair_scheduler()
    q.admit(host_req("ti", CLASS_INTERACTIVE), 64)
    returner = host_req("tb", CLASS_BATCH)
    returner.admit_time = 0.0                    # charged at first admission
    q.push_front(returner)
    vfinish_before = dict(sched._vfinish)
    out = sched.next_admissions(1, 1.0)
    assert out == [returner]
    assert sched._vfinish == vfinish_before      # no double-billing


def test_token_budget_exact_under_virtual_clock():
    """Token bucket arithmetic is exact: cost-16 requests against a
    rate-32/s, burst-16 bucket admit at t = 0, 0.5, 1.0, 1.5 — one
    bucket-refill period apart, deferred (never shed) in between."""
    q, sched = fair_scheduler(
        batch={"token_budget_per_s": 32.0, "token_budget_burst": 16.0})
    for _ in range(4):
        q.admit(host_req("tb", CLASS_BATCH, prompt_len=8, max_new=8), 64)
    times = []
    now = 0.0
    while len(q) and now < 10.0:
        if sched.next_admissions(1, now):
            times.append(now)
        now += 0.125
    assert times == [0.0, 0.5, 1.0, 1.5]
    assert not len(q)                            # deferred, all admitted
    assert q.shed_counts == {}                   # never shed


# ---------------------------------------------------- priority preemption


def test_priority_preemption_bitwise_greedy(engine):
    """Interactive arrival evicts the newest batch stream mid-decode; the
    evicted stream resumes and EVERY stream matches sequential greedy
    generate() bitwise — contention is invisible in the tokens."""
    rng = np.random.default_rng(7)
    batch = [Request(prompt=rng.integers(1, 64, size=10), max_new_tokens=16,
                     tenant_id=f"b{i}", tenant_class=CLASS_BATCH)
             for i in range(2)]
    inter = Request(prompt=rng.integers(1, 64, size=10), max_new_tokens=6,
                    tenant_id="vip", tenant_class=CLASS_INTERACTIVE,
                    arrival_time=3.0)
    sv = qos_replica(engine)
    fin, rej, snap = sv.run(batch + [inter])
    assert len(fin) == 3 and not rej
    assert sv.metrics.priority_evictions >= 1
    evicted = [r for r in batch if r.priority_evictions]
    assert evicted and all(r.preemptions >= 1 for r in evicted)
    for r in batch + [inter]:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))
    # the rollup reports the eviction + per-tenant accounting
    assert snap["priority_evictions"] == sv.metrics.priority_evictions
    assert snap["tenancy"]["vip"]["class"] == CLASS_INTERACTIVE
    assert snap["tenancy"]["vip"]["finished"] == 1
    sv.destroy()


def test_priority_preemption_bitwise_sampled(engine):
    """Seeded sampled streams: contended (evicted + resumed) tokens match
    the uncontended stay-put run bitwise — the rng chain survives the
    eviction (the PR 12/14 rollback-safe contract)."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 64, size=10) for _ in range(3)]

    def mk(i, cls, tid, arrival=None, seed=0):
        return Request(prompt=prompts[i], max_new_tokens=12 if cls ==
                       CLASS_BATCH else 6, tenant_id=tid, tenant_class=cls,
                       arrival_time=arrival,
                       sampling=SamplingParams(temperature=0.8, top_k=8,
                                               seed=seed))

    contended = [mk(0, CLASS_BATCH, "b0", seed=1),
                 mk(1, CLASS_BATCH, "b1", seed=2),
                 mk(2, CLASS_INTERACTIVE, "vip", arrival=3.0, seed=3)]
    sv = qos_replica(engine)
    fin, rej, _ = sv.run(contended)
    assert len(fin) == 3 and not rej
    assert sv.metrics.priority_evictions >= 1
    sv.destroy()
    for i, req in enumerate(contended):
        solo = Request(prompt=req.prompt, max_new_tokens=req.max_new_tokens,
                       sampling=SamplingParams(**vars(req.sampling)))
        ref = qos_replica(engine)
        fin2, _, _ = ref.run([solo])
        assert len(fin2) == 1
        np.testing.assert_array_equal(np.asarray(req.tokens),
                                      np.asarray(solo.tokens))
        ref.destroy()


def test_priority_preemption_bitwise_tp2(devices8):
    """TP=2 leg: the eviction/resume cycle moves sharded pool blocks;
    greedy streams under contention still match generate() bitwise."""
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
                     "policy": "weighted_fair",
                     "tenants": {"enabled": True},
                     "chunked_prefill": {"enabled": True, "chunk_size": 8},
                     "kv_pool": {"block_size": 8,
                                 "on_demand_growth": True},
                     "migration": {"enabled": True,
                                   "snapshot_interval_tokens": 2}}}),
        mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)
    rng = np.random.default_rng(13)
    batch = [Request(prompt=rng.integers(1, 64, size=10), max_new_tokens=14,
                     tenant_id=f"b{i}", tenant_class=CLASS_BATCH)
             for i in range(2)]
    inter = Request(prompt=rng.integers(1, 64, size=10), max_new_tokens=6,
                    tenant_id="vip", tenant_class=CLASS_INTERACTIVE,
                    arrival_time=3.0)
    sv = ServingEngine(eng, clock=VirtualClock())
    fin, rej, _ = sv.run(batch + [inter])
    assert len(fin) == 3 and not rej
    assert sv.metrics.priority_evictions >= 1
    for r in batch + [inter]:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(eng, r))
    sv.destroy()


# -------------------------------------------------------- degraded ladder


class _DigestProbe:
    """Minimal latency_digests() source the controller can sense."""

    def __init__(self):
        self.digests = {"ttft": LatencyDigest(), "tpot": LatencyDigest(),
                        "queue_wait": LatencyDigest()}

    def latency_digests(self):
        return self.digests


def test_degraded_ladder_climbs_and_recovers():
    """Unit ladder mechanics: sustained burn climbs exactly one rung per
    evaluation (never skips), the dead band holds the level, and a clean
    window descends back to healthy. Policy queries pin the rung order:
    batch sheds from rung 1, interactive only at the last rung."""
    probe = _DigestProbe()
    ctl = DegradedModeController(
        DegradedConfig(enabled=True, interval=1, enter_evals=1,
                       exit_evals=2, max_new_tokens_cap=4),
        SLOConfig(ttft_p99_ms=10.0), probe)
    seen = []
    for step in range(6):
        probe.digests["ttft"].add(0.05)          # 50ms >> 10ms target: burn
        seen.append(ctl.observe(float(step)))
    assert seen == [1, 2, 3, 4, 4, 4]            # one rung per eval, capped
    assert [lvl for _, lvl, _ in ctl.transitions] == [1, 2, 3, 4]
    assert ctl.sheds_class(CLASS_BATCH) and ctl.sheds_class(CLASS_INTERACTIVE)
    for step in range(6, 20):                    # no new samples: burn 0
        lvl = ctl.observe(float(step))
    assert lvl == 0                              # recovered, rung by rung
    assert ctl.snapshot()["ladder"] == list(DEGRADED_LADDER)
    # rung-order policy pins, per level
    for lvl, (shed_b, shed_i, cap, spec_off) in {
            0: (False, False, 0, False), 1: (True, False, 0, False),
            2: (True, False, 4, False), 3: (True, False, 4, True),
            4: (True, True, 4, True)}.items():
        ctl.level = lvl
        assert ctl.sheds_class(CLASS_BATCH) is shed_b
        assert ctl.sheds_class(CLASS_INTERACTIVE) is shed_i
        assert ctl.token_cap() == cap
        assert ctl.speculation_off() is spec_off


def test_degraded_ladder_hysteresis_dead_band():
    """Burn inside the dead band arms NEITHER direction: the level holds
    and both counters reset (sustained evidence cannot straddle it)."""
    probe = _DigestProbe()
    ctl = DegradedModeController(
        DegradedConfig(enabled=True, interval=1, enter_evals=2,
                       exit_evals=2, enter_burn=50.0, exit_burn=10.0),
        SLOConfig(ttft_p99_ms=10.0), probe)
    t = 0.0

    def eval_with(samples_over, samples_under):
        nonlocal t
        for _ in range(samples_over):
            probe.digests["ttft"].add(0.05)
        for _ in range(samples_under):
            probe.digests["ttft"].add(0.001)
        t += 1.0
        return ctl.observe(t)

    assert eval_with(1, 0) == 0                  # burn 100: hot 1/2
    assert eval_with(1, 3) == 0                  # burn 25, in band: reset
    assert eval_with(1, 0) == 0                  # hot 1/2 again — no climb
    assert eval_with(1, 0) == 1                  # hot 2/2: one rung


def test_degraded_sheds_batch_before_interactive(engine, tmp_path):
    """Integration ordering pin: under sustained burn the engine sheds
    batch from rung 1 while ZERO interactive requests are shed below the
    last rung — every interactive degraded-shed in the trace happened at
    level 4, and batch sheds strictly precede any interactive shed."""
    sv = qos_replica(
        engine, trace_dir=tmp_path,
        slo={"ttft_p99_ms": 1.0},                # everything burns
        degraded={"enabled": True, "interval": 2, "enter_evals": 1,
                  "exit_evals": 4, "max_new_tokens_cap": 4})
    rng = np.random.default_rng(3)
    reqs = []
    for i in range(16):
        cls = CLASS_BATCH if i % 2 else CLASS_INTERACTIVE
        reqs.append(Request(prompt=rng.integers(1, 64, size=8),
                            max_new_tokens=8, arrival_time=2.0 * i,
                            tenant_id="tb" if i % 2 else "ti",
                            tenant_class=cls))
    fin, rej, snap = sv.run(reqs)
    shed_batch = [r for r in rej if r.tenant_class == CLASS_BATCH
                  and r.reject_reason == REJECT_DEGRADED]
    assert shed_batch                            # rung 1 fired
    assert snap["degraded"]["level"] >= 1 or any(
        lvl >= 1 for _, lvl, _ in sv.degraded_ctl.transitions)
    # trace-ordered pin: level at each shed instant
    level_at = []                                # (ts, level)
    sheds = []                                   # (ts, tenant_class)
    for ev in sv.tracer.events:
        if ev.get("name") == "serving/degraded_level":
            level_at.append((ev["ts"], ev["args"]["level"]))
        elif ev.get("name") == "request/shed" \
                and ev["args"].get("reason") == REJECT_DEGRADED:
            sheds.append((ev["ts"], ev["args"]["tenant_class"]))

    def level_before(ts):
        lvl = 0
        for t, v in level_at:
            if t <= ts:
                lvl = v
        return lvl

    assert all(level_before(ts) >= 1 for ts, _ in sheds)
    for ts, cls in sheds:
        if cls == CLASS_INTERACTIVE:
            assert level_before(ts) == len(DEGRADED_LADDER) - 1
    first_batch = min(ts for ts, c in sheds if c == CLASS_BATCH)
    for ts, cls in sheds:
        if cls == CLASS_INTERACTIVE:
            assert ts > first_batch              # batch shed strictly first
    # rung 2+ capped the generation budget of what it still admitted
    capped = [r for r in fin if r.tenant_class == CLASS_INTERACTIVE
              and len(r.tokens) <= 4 and r.max_new_tokens == 4]
    assert capped
    sv.destroy()


def test_reset_window_preserves_tenant_counters(engine):
    """Satellite pin: reset_window() restarts the per-tenant latency
    digests (same epoch as the global ones) but the per-tenant COUNTERS
    survive — warmup exclusion must not erase who submitted what."""
    sv = qos_replica(engine)
    rng = np.random.default_rng(9)
    reqs = [Request(prompt=rng.integers(1, 64, size=8), max_new_tokens=4,
                    tenant_id="t0", tenant_class=CLASS_BATCH)
            for _ in range(2)]
    fin, _, _ = sv.run(reqs)
    assert len(fin) == 2
    m = sv.metrics
    t = m.tenants["t0"]
    assert t["submitted"] == 2 and t["ttft_digest"].count == 2
    resets = m.window_resets
    m.reset_window()
    assert m.window_resets == resets + 1
    assert t["submitted"] == 2 and t["finished"] == 2    # counters survive
    assert t["ttft_digest"].count == 0                   # samples restart
    snap = m.tenancy_snapshot()["t0"]
    assert snap["submitted"] == 2 and snap["ttft_p99_ms"] is None
    sv.destroy()


# ------------------------------------------------------------- autoscaler


QOS_SLO = {"ttft_p99_ms": 30000.0}
QOS_AUTO = {"enabled": True, "min_replicas": 1, "scale_up_burn": 1.0,
            "scale_down_burn": 0.25, "scale_up_queue_depth": 2.0,
            "sustain_evals": 2, "cooldown": 4.0, "interval": 2}


def phased_workload(seed=5):
    """Three phases: co-batchable steady pairs (fits one replica), a
    sustained burst past one replica's capacity, and a sparse tail that
    lets the fleet drain back to the floor."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(4):
        for _ in range(2):
            reqs.append(Request(prompt=rng.integers(1, 64, size=12),
                                max_new_tokens=8, arrival_time=16.0 * i,
                                tenant_id="steady",
                                tenant_class=CLASS_INTERACTIVE))
    for i in range(12):
        reqs.append(Request(prompt=rng.integers(1, 64, size=12),
                            max_new_tokens=8, arrival_time=70.0 + 2.0 * i,
                            tenant_id="burst",
                            tenant_class=CLASS_INTERACTIVE))
    for i in range(4):
        reqs.append(Request(prompt=rng.integers(1, 64, size=12),
                            max_new_tokens=8, arrival_time=160.0 + 40.0 * i,
                            tenant_id="tail",
                            tenant_class=CLASS_INTERACTIVE))
    return reqs


def run_fleet(engine, n, autoscale):
    kw = {"slo": QOS_SLO}
    if autoscale:
        kw["autoscaler"] = QOS_AUTO
    router = Router([make_replica(engine, **kw) for _ in range(n)])
    reqs = phased_workload()
    for _ in router.serve(reqs, yield_rejections=False):
        pass
    snap = router.snapshot()
    digest = router.metrics.fleet_digests()["ttft"]
    out = {
        "finished": sum(1 for r in reqs
                        if r.state is RequestState.FINISHED),
        "violations": digest.count_above(QOS_SLO["ttft_p99_ms"] / 1e3),
        "p99_ms": digest.quantile_ms(99),
        "replica_steps": snap["router"]["replica_steps"],
        "events": [(e["action"], e["replica"], e["group"])
                   for e in snap["autoscaler"].get("events", [])],
        "snapshot": snap,
    }
    router.destroy()
    return out


def test_autoscaler_beats_both_static_fleets(engine):
    """THE acceptance pin: on the seeded phased workload the autoscaled
    3-replica fleet (floor 1) holds interactive p99 TTFT within the SLO
    with strictly fewer cumulative replica-steps than the static max
    fleet AND strictly fewer SLO violations than the static min fleet."""
    auto = run_fleet(engine, 3, autoscale=True)
    static_min = run_fleet(engine, 1, autoscale=False)
    static_max = run_fleet(engine, 3, autoscale=False)
    assert auto["finished"] == static_min["finished"] \
        == static_max["finished"] == 24
    assert auto["p99_ms"] <= QOS_SLO["ttft_p99_ms"]      # SLO held
    assert static_min["violations"] > 0                  # min fleet drowns
    assert auto["violations"] < static_min["violations"]  # strictly fewer
    assert auto["replica_steps"] < static_max["replica_steps"]  # cheaper
    a = auto["snapshot"]["autoscaler"]
    assert a["enabled"] and a["scale_ups"] >= 1 and a["scale_downs"] >= 1
    # static fleets always report the (disabled) autoscaler block
    assert static_max["snapshot"]["autoscaler"] == {"enabled": False}


def test_autoscaler_deterministic_and_never_ping_pongs(engine):
    """Scale decisions are a pure function of the seeded workload: two
    runs produce the IDENTICAL event timeline. On the single-burst
    workload the profile is monotone — parks, then ups, then downs;
    no up ever follows a down (the no-thrash pin)."""
    a = run_fleet(engine, 3, autoscale=True)
    b = run_fleet(engine, 3, autoscale=True)
    assert a["events"] == b["events"]
    assert a["violations"] == b["violations"]
    assert a["replica_steps"] == b["replica_steps"]
    actions = [ev[0] for ev in a["events"]]
    assert actions.count("park") == 2            # 3-fleet parked to floor 1
    first_down = actions.index("down") if "down" in actions else len(actions)
    assert "up" not in actions[first_down:]      # monotone: never re-arms
    # the fleet ends back at the floor
    assert a["snapshot"]["autoscaler"]["active_replicas"] == 1


def test_pull_queued_moves_backlog(engine):
    """Router.pull_queued moves the TAIL of a hot queue to the target in
    order, re-homes the in-flight registry, and the moved requests finish
    on the new replica."""
    reps = [make_replica(engine) for _ in range(2)]
    router = Router(reps)
    router.drain(1)                              # force all routing to r0
    rng = np.random.default_rng(17)
    reqs = [Request(prompt=rng.integers(1, 64, size=8), max_new_tokens=4,
                    tenant_id="t", arrival_time=0.0)
            for _ in range(6)]
    for r in reqs:
        router.submit(r)
    assert reps[0].queue.depth == 6
    router.rejoin(1)
    moved = router.pull_queued(0, 1, 3)
    assert moved == 3
    assert reps[0].queue.depth == 3 and reps[1].queue.depth == 3
    # order preserved: the tail block lands in original relative order
    assert [r.request_id for i in range(reps[1].queue.depth)
            for r in [reps[1].queue.peek_at(i)]] \
        == [r.request_id for r in reqs[3:]]
    for r in reqs[3:]:
        assert router._requests[r.request_id][1] == 1    # re-homed
    for _ in router.serve([], yield_rejections=False):
        pass
    assert all(r.state is RequestState.FINISHED for r in reqs)
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))
    router.destroy()


def test_fleet_tenancy_merge(engine):
    """Router.snapshot()['tenancy'] is the exact merge of every replica's
    per-tenant counters and digests (associative bucket addition)."""
    reps = [qos_replica(engine) for _ in range(2)]
    router = Router(reps)
    rng = np.random.default_rng(23)
    reqs = []
    for i in range(8):
        cls = CLASS_BATCH if i % 2 else CLASS_INTERACTIVE
        reqs.append(Request(prompt=rng.integers(1, 64, size=8),
                            max_new_tokens=4, arrival_time=0.5 * i,
                            tenant_id="tb" if i % 2 else "ti",
                            tenant_class=cls))
    for _ in router.serve(reqs, yield_rejections=False):
        pass
    fleet = router.snapshot()["tenancy"]
    assert set(fleet) == {"ti", "tb"}
    for tid in ("ti", "tb"):
        per_rep = [r.sv.metrics.tenants.get(tid) for r in router._replicas]
        per_rep = [t for t in per_rep if t is not None]
        assert fleet[tid]["submitted"] == sum(t["submitted"] for t in per_rep)
        assert fleet[tid]["finished"] == sum(t["finished"] for t in per_rep)
        assert fleet[tid]["tokens"] == sum(t["tokens"] for t in per_rep)
        assert fleet[tid]["finished"] == 4
    router.destroy()
