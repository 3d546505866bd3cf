"""Router tier + chunked prefill + on-demand growth tests (tier-1).

The acceptance invariants of the millions-of-users serving topology
(ROADMAP item 2), all assertable under the virtual clock:

- greedy token streams THROUGH THE ROUTER (N>=2 replicas, chunked prefill
  on, paged pool with on-demand growth) are bitwise-equal to sequential
  single-replica ``generate()``, single-device and TP=2;
- least-loaded dispatch strictly beats round-robin (makespan) on skewed
  arrivals; prefix-affinity routing shows a strictly higher aggregate
  prefix hit rate than round-robin on repeated-system-prompt workloads;
- drain/rejoin completes every in-flight request with zero sheds;
- the chunked-prefill TPOT ceiling holds for a co-batched decoder while a
  max-length prompt prefills (vs an unbounded stall without chunking);
- on-demand growth admits strictly more concurrent requests than
  whole-footprint reservation at byte-identical pool sizes, preempting to
  the queue instead of OOM/shed on exhaustion — and a preempted request
  resumes bitwise-identically (greedy AND seeded sampling);
- FCFS head-of-line bypass admits a later fitting request past a blocked
  head only within the configured starvation window;
- Serving/router_* monitor events stay coherent with
  ``ServingMetrics.snapshot()["router"]`` (the PR 4 trace==metrics pin).
"""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (Request, RequestState, Router,
                                   SamplingParams, ServingEngine,
                                   VirtualClock)

from .conftest import make_replica, ref_tokens, tiny_cfg


def make_router(engine, n=2, router=None, **kw):
    replicas = [make_replica(engine, **kw) for _ in range(n)]
    cfg = replicas[0].cfg.router
    if router:
        cfg = cfg.replace(**router)
    return Router(replicas, config=cfg)


# ---------------------------------------------------------------------------
# 1. bitwise parity through the full topology
# ---------------------------------------------------------------------------

def test_router_greedy_parity_chunked_paged_growth(engine):
    """The acceptance pin: greedy streams through the router — 2 replicas,
    chunked prefill ON, paged pool with on-demand growth ON — are bitwise
    equal to sequential single-replica generate(). Chunking, routing, growth
    and preemption change the SCHEDULE, never the math."""
    rng = np.random.RandomState(0)
    router = make_router(
        engine, n=2,
        chunked_prefill={"enabled": True, "chunk_size": 8},
        kv_pool={"block_size": 8, "on_demand_growth": True})
    reqs = [Request(
        prompt=rng.randint(0, 64, (int(rng.randint(4, 40)),)).astype(np.int32),
        max_new_tokens=int(rng.randint(3, 9)), arrival_time=i * 0.5)
        for i in range(8)]
    finished, rejected, snap = router.run(reqs)
    assert len(finished) == 8 and not rejected
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(r.tokens), ref_tokens(engine, r))
    # both replicas actually served work, each compiling decode exactly once
    assert all(n > 0 for n in snap["router"]["per_replica_routed"])
    assert all(c["decode"] == 1 and c["insert"] == 1
               for c in router.compile_counts())


def test_router_tp_mesh_parity(devices8):
    """TP=2 fleet: two replicas over a model-sharded engine, chunked prefill
    + paged growth on — greedy streams still match the single-device
    reference bitwise (the acceptance pin's TP leg)."""
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
                                 "on_demand_growth": True}}}), mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)

    rng = np.random.RandomState(9)
    router = Router([ServingEngine(eng, clock=VirtualClock())
                     for _ in range(2)])
    reqs = [Request(
        prompt=rng.randint(0, 64, (int(rng.randint(4, 30)),)).astype(np.int32),
        max_new_tokens=int(rng.randint(3, 7)), arrival_time=i * 0.5)
        for i in range(4)]
    finished, rejected, _ = router.run(reqs)
    assert len(finished) == 4 and not rejected

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                       max_tokens=64)
    raw.params = values
    for r in reqs:
        ref = np.asarray(raw.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


# ---------------------------------------------------------------------------
# 2. routing policy under the virtual clock
# ---------------------------------------------------------------------------

def _skewed_workload(rng):
    """Long/short mix whose arrival order makes round-robin queue a long
    request behind another long one while the other replica sits idle."""
    long_p = rng.randint(0, 64, (8,)).astype(np.int32)
    short_p = rng.randint(0, 64, (8,)).astype(np.int32)
    return [
        Request(prompt=long_p.copy(), max_new_tokens=24, arrival_time=0.0),
        Request(prompt=short_p.copy(), max_new_tokens=3, arrival_time=0.1),
        Request(prompt=long_p.copy(), max_new_tokens=24, arrival_time=6.0),
        Request(prompt=short_p.copy(), max_new_tokens=3, arrival_time=6.1),
    ]


def test_least_loaded_beats_round_robin_on_skewed_arrivals(engine):
    """Deterministic makespan pin: round-robin sends the second long request
    to the replica still busy with the first (the other is idle); the
    least-loaded scorer sends it to the idle one. Same work, strictly
    smaller fleet makespan."""
    rng = np.random.RandomState(1)
    rr = make_router(engine, n=2, n_slots=1, router={"policy": "round_robin"})
    finished, rejected, rr_snap = rr.run(_skewed_workload(rng))
    assert len(finished) == 4 and not rejected

    ll = make_router(engine, n=2, n_slots=1,
                     router={"policy": "least_loaded"})
    finished, rejected, ll_snap = ll.run(_skewed_workload(rng))
    assert len(finished) == 4 and not rejected

    assert ll_snap["makespan"] < rr_snap["makespan"]
    # and the queues tell the story: round-robin queued work behind a busy
    # replica (depth observed > 0 on one side while the other idled)
    assert ll_snap["ttft_ms"]["p99"] < rr_snap["ttft_ms"]["p99"]


def test_prefix_affinity_beats_round_robin_hit_rate(engine):
    """Repeated system prompts: with prefix affinity the router keeps
    sending them to the replica already holding their blocks — the
    aggregate KV prefix hit rate is strictly higher than round-robin's
    (which spreads the same prompt over every replica's pool)."""
    def requests(seed):
        r = np.random.RandomState(seed)
        sys_prompt = r.randint(0, 64, (16,)).astype(np.int32)
        return [Request(
            prompt=np.concatenate(
                [sys_prompt, r.randint(0, 64, (6,)).astype(np.int32)]),
            max_new_tokens=4, arrival_time=i * 3.0) for i in range(6)]

    affin = make_router(engine, n=2,
                        kv_pool={"block_size": 8})
    _, _, affin_snap = affin.run(requests(2))

    rr = make_router(engine, n=2, router={"policy": "round_robin"},
                     kv_pool={"block_size": 8})
    _, _, rr_snap = rr.run(requests(2))

    def hit_rate(snap):
        hits = sum(r["kv_pool"]["prefix_hit_requests"]
                   for r in snap["replicas"])
        cands = sum(r["kv_pool"]["prefix_requests"]
                    for r in snap["replicas"])
        return hits / max(cands, 1)

    assert hit_rate(affin_snap) > hit_rate(rr_snap)
    assert affin_snap["router"]["affinity_hit_rate"] > 0
    # round-robin never consults the prefix index
    assert rr_snap["router"]["prefix_hits"] == 0


def test_rebalance_overrides_overloaded_affinity_target(engine):
    """An affinity target drowning in queue depth is overridden (counted as
    a rebalance) instead of piling more work onto it."""
    rng = np.random.RandomState(3)
    router = make_router(engine, n=2, n_slots=1, max_queue_depth=64,
                         router={"rebalance_margin": 0.05},
                         kv_pool={"block_size": 8})
    sys_prompt = rng.randint(0, 64, (16,)).astype(np.int32)
    mk = lambda t: Request(
        prompt=np.concatenate([sys_prompt,
                               rng.randint(0, 64, (6,)).astype(np.int32)]),
        max_new_tokens=8, arrival_time=t)
    # a burst that all wants replica 0 (prefix affinity) — load wins instead
    _, _, snap = router.run([mk(0.0), mk(0.1), mk(0.2), mk(0.3)])
    assert snap["router"]["rebalances"] > 0
    assert all(n > 0 for n in snap["router"]["per_replica_routed"])


# ---------------------------------------------------------------------------
# 3. drain / rejoin
# ---------------------------------------------------------------------------

def test_drain_rejoin_loses_zero_in_flight(engine):
    """Drain mid-flight: the draining replica takes no NEW work but finishes
    everything it owns (zero sheds); rejoin re-registers it for admissions
    — the PR 11 quiesce-then-teardown discipline at the router tier."""
    rng = np.random.RandomState(4)
    router = make_router(engine, n=2, n_slots=1)
    mk = lambda: Request(prompt=rng.randint(0, 64, (6,)).astype(np.int32),
                         max_new_tokens=8)
    a, b = router.submit(mk()), router.submit(mk())
    assert {a.state, b.state} <= {RequestState.QUEUED, RequestState.RUNNING}
    router.drain(0)
    # new work while draining routes AWAY from replica 0
    c, d = router.submit(mk()), router.submit(mk())
    while any(rep.busy for rep in router._replicas):
        router.step()
    for r in (a, b, c, d):
        assert r.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))
    assert router.drained(0)
    snap = router.snapshot()
    assert snap["router"]["drains"] == 1
    assert snap["router"]["shed_all_replicas_saturated"] == 0
    assert sum(sum(r["shed"].values()) for r in snap["replicas"]) == 0
    # while draining, replica 0 received at most its pre-drain share
    routed_while_draining = snap["router"]["per_replica_routed"]
    assert routed_while_draining[1] >= 2

    router.rejoin(0)
    e = router.submit(mk())
    while any(rep.busy for rep in router._replicas):
        router.step()
    assert e.state is RequestState.FINISHED
    assert router.snapshot()["router"]["rejoins"] == 1


def test_all_replicas_saturated_shed(engine):
    """Every replica at queue capacity (or draining) -> the router sheds
    with the cross-replica reason instead of dumping onto one queue."""
    rng = np.random.RandomState(5)
    router = make_router(engine, n=2, n_slots=1, max_queue_depth=1)
    mk = lambda: Request(prompt=rng.randint(0, 64, (5,)).astype(np.int32),
                         max_new_tokens=4)
    reqs = [router.submit(mk()) for _ in range(6)]
    shed = [r for r in reqs if r.state is RequestState.REJECTED]
    assert shed and all(r.reject_reason == "all_replicas_saturated"
                        for r in shed)
    assert router.metrics.shed_saturated == len(shed)
    while any(rep.busy for rep in router._replicas):
        router.step()
    done = [r for r in reqs if r.state is RequestState.FINISHED]
    assert len(done) + len(shed) == 6


# ---------------------------------------------------------------------------
# 4. chunked prefill: the bounded-TPOT guarantee
# ---------------------------------------------------------------------------

def _max_token_gap(events, request_id):
    times = [ev.time for ev in events if ev.request_id == request_id]
    return max(b - a for a, b in zip(times, times[1:]))


def test_chunked_prefill_bounds_cobatched_tpot(engine):
    """A max-length prompt prefills while a decoder streams: with chunked
    prefill the decoder's worst inter-token gap stays under the virtual-
    clock ceiling (chunk bucket * prefill cost + decode cost); without it,
    the whole-prompt prefill stalls the decoder past that ceiling."""
    rng = np.random.RandomState(6)
    dec_prompt = rng.randint(0, 64, (8,)).astype(np.int32)
    big_prompt = rng.randint(0, 64, (56,)).astype(np.int32)
    decoder = lambda: Request(prompt=dec_prompt.copy(), max_new_tokens=20,
                              arrival_time=0.0)
    # max-length prompt: 56 tokens prompt + 8 new fills the 64 window
    big = lambda: Request(prompt=big_prompt.copy(), max_new_tokens=4,
                          arrival_time=3.0)

    chunked = make_replica(
        engine, n_slots=2,
        chunked_prefill={"enabled": True, "chunk_size": 16,
                         "decode_steps_between_chunks": 1})
    d1, b1 = decoder(), big()
    ev_chunked = list(chunked.serve([d1, b1]))
    # ceiling: one 16-token chunk (0.0625/token) + one decode step
    ceiling = 16 * chunked.cfg.virtual_prefill_cost_per_token \
        + chunked.cfg.virtual_decode_step_cost
    gap_chunked = _max_token_gap(ev_chunked, d1.request_id)
    assert gap_chunked <= ceiling + 1e-9, (gap_chunked, ceiling)

    plain = make_replica(engine, n_slots=2)
    d2, b2 = decoder(), big()
    ev_plain = list(plain.serve([d2, b2]))
    gap_plain = _max_token_gap(ev_plain, d2.request_id)
    # the unbounded stall: the whole 56-token prompt (bucketed to 64)
    # lands between two of the decoder's tokens
    assert gap_plain > ceiling
    assert gap_plain >= 56 * plain.cfg.virtual_prefill_cost_per_token

    # chunking changed the schedule, not the tokens
    np.testing.assert_array_equal(np.asarray(d1.tokens), np.asarray(d2.tokens))
    np.testing.assert_array_equal(np.asarray(b1.tokens), np.asarray(b2.tokens))
    np.testing.assert_array_equal(np.asarray(d1.tokens),
                                  ref_tokens(engine, d1))
    np.testing.assert_array_equal(np.asarray(b1.tokens),
                                  ref_tokens(engine, b1))
    # all full chunks share ONE compiled suffix program
    assert chunked.compile_counts()["suffix_buckets"] <= 2


# ---------------------------------------------------------------------------
# 5. on-demand growth: capacity win + preempt/resume
# ---------------------------------------------------------------------------

def test_growth_admits_more_than_whole_footprint(engine):
    """Byte-identical pools: whole-footprint reservation pays for every
    not-yet-generated token at admission; reserve-as-you-decode admits
    strictly more concurrent requests (active_slots_peak), shedding nothing
    and preempting to the queue when the pool saturates mid-decode."""
    rng = np.random.RandomState(7)
    mk_reqs = lambda: [Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                               max_new_tokens=24, arrival_time=0.0)
                       for _ in range(6)]
    pool = {"block_size": 8, "n_blocks": 9,
            "prefix_cache": False}

    whole = make_replica(engine, n_slots=6, kv_pool=dict(pool))
    rng = np.random.RandomState(7)
    reqs_w = mk_reqs()
    list(whole.serve(reqs_w))
    snap_w = whole.metrics.snapshot()

    grow = make_replica(engine, n_slots=6,
                        kv_pool=dict(pool, on_demand_growth=True))
    rng = np.random.RandomState(7)
    reqs_g = mk_reqs()
    list(grow.serve(reqs_g))
    snap_g = grow.metrics.snapshot()

    # same pool bytes, strictly more concurrency
    assert snap_g["active_slots_peak"] > snap_w["active_slots_peak"]
    assert snap_g["kv_pool"]["grown_blocks"] > 0
    # exhaustion preempted instead of shedding/OOM
    assert snap_g["preempted"] > 0
    assert sum(snap_g["shed"].values()) == 0
    for a, b in zip(reqs_w, reqs_g):
        assert a.state is RequestState.FINISHED
        assert b.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens))


def test_preempted_request_resumes_bitwise_identical(engine):
    """The preempt-to-queue round trip replays prompt + generated tokens
    into fresh blocks and re-enters decode at the saved cursor AND rng —
    greedy streams match generate() and a seeded SAMPLED stream matches its
    un-preempted self token for token."""
    rng = np.random.RandomState(8)
    tight = {"block_size": 8, "n_blocks": 8,
             "prefix_cache": False, "on_demand_growth": True}
    sampled = lambda: Request(
        prompt=rng.randint(0, 64, (8,)).astype(np.int32), max_new_tokens=20,
        sampling=SamplingParams(temperature=0.8, top_k=8, seed=13),
        arrival_time=0.0)
    greedy = lambda: Request(
        prompt=rng.randint(0, 64, (8,)).astype(np.int32), max_new_tokens=20,
        arrival_time=0.0)

    rng = np.random.RandomState(8)
    sv = make_replica(engine, n_slots=3, kv_pool=dict(tight))
    s1, g1, g2 = sampled(), greedy(), greedy()
    list(sv.serve([s1, g1, g2]))
    assert sv.metrics.snapshot()["preempted"] > 0
    assert max(r.preemptions for r in (s1, g1, g2)) > 0
    # resume replays splice through the SAME compiled insert/decode programs
    counts = sv.compile_counts()
    assert counts["decode"] == 1 and counts["insert"] == 1

    # greedy legs: bitwise vs generate() regardless of preemption
    for g in (g1, g2):
        np.testing.assert_array_equal(np.asarray(g.tokens),
                                      ref_tokens(engine, g))
    # sampled leg: identical to the same seeded request served un-preempted
    rng = np.random.RandomState(8)
    roomy = make_replica(engine, n_slots=3,
                         kv_pool={"block_size": 8,
                                  "prefix_cache": False})
    s2 = sampled()
    list(roomy.serve([s2]))
    assert s2.preemptions == 0
    assert s1.tokens == s2.tokens


# ---------------------------------------------------------------------------
# 6. FCFS head-of-line bypass (bounded starvation)
# ---------------------------------------------------------------------------

def _hol_setup(engine, bypass):
    """1 running 2-block request + a 4-block head that can't fit + small
    requests behind it that could."""
    sv = make_replica(engine, n_slots=3, hol_bypass_limit=bypass,
                      kv_pool={"block_size": 8,
                               "n_blocks": 5, "prefix_cache": False})
    rng = np.random.RandomState(9)
    running = Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                      max_new_tokens=9)    # 2 blocks, 9 decode steps
    big = Request(prompt=rng.randint(0, 64, (16,)).astype(np.int32),
                  max_new_tokens=17)       # 4 blocks: can't fit while running
    small = Request(prompt=rng.randint(0, 64, (4,)).astype(np.int32),
                    max_new_tokens=4)      # 1 block: fits beside running
    small2 = Request(prompt=rng.randint(0, 64, (4,)).astype(np.int32),
                     max_new_tokens=4)
    for r in (running, big, small, small2):
        sv.submit(r)
    for _ in range(200):
        sv.step()
        if all(r.state is RequestState.FINISHED
               for r in (running, big, small, small2)):
            break
    return sv, running, big, small, small2


def test_hol_bypass_off_preserves_strict_fcfs(engine):
    sv, running, big, small, small2 = _hol_setup(engine, bypass=0)
    # strict FCFS: the small requests waited behind the blocked big head
    assert small.first_token_time > big.first_token_time
    assert small2.first_token_time > big.first_token_time
    assert sv.pool_mgr.stats()["reserved_blocks"] == 0


def test_hol_bypass_admits_fitting_request_within_window(engine):
    sv, running, big, small, small2 = _hol_setup(engine, bypass=1)
    # one bypass granted: small overtakes the stuck head...
    assert small.first_token_time < big.first_token_time
    # ...but the window is bounded: small2 (bypass #2) must wait for big
    assert small2.first_token_time > big.first_token_time
    # reservation counter consistent after the dust settles
    assert sv.pool_mgr.stats()["reserved_blocks"] == 0
    for r in (running, big, small, small2):
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))


# ---------------------------------------------------------------------------
# 7. router monitor events == snapshot (trace==metrics discipline)
# ---------------------------------------------------------------------------

def test_router_monitor_events_match_snapshot(engine, tmp_path):
    """Serving/router_* scalars through the CSV monitor backend carry
    exactly the numbers ``snapshot()['router']`` reports — and each
    replica's ServingMetrics.snapshot() exposes the same router block."""
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    mcfg = engine.config.replace(
        csv_monitor={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "router_test"})
    replicas = [make_replica(engine,
                             kv_pool={"block_size": 8})
                for _ in range(2)]
    router = Router(replicas, monitor=MonitorMaster(mcfg))
    rng = np.random.RandomState(10)
    sys_prompt = rng.randint(0, 64, (16,)).astype(np.int32)
    reqs = [Request(prompt=np.concatenate(
        [sys_prompt, rng.randint(0, 64, (5,)).astype(np.int32)]),
        max_new_tokens=3, arrival_time=i * 2.0) for i in range(5)]
    finished, rejected, snap = router.run(reqs)
    assert len(finished) == 5 and not rejected
    router.metrics.emit_events()

    outdir = tmp_path / "router_test"
    names = {p.name for p in outdir.iterdir()}
    for expected in ("Serving_router_routed.csv",
                     "Serving_router_affinity_hit_rate.csv",
                     "Serving_router_rebalances.csv",
                     "Serving_router_drains.csv",
                     "Serving_router_r0_queue_depth.csv",
                     "Serving_router_r1_occupancy.csv"):
        assert expected in names, names

    def last_value(name):
        rows = (outdir / name).read_text().strip().splitlines()
        return float(rows[-1].split(",")[-1])

    r = snap["router"]
    assert last_value("Serving_router_routed.csv") == float(r["routed"])
    assert last_value("Serving_router_affinity_hit_rate.csv") == \
        pytest.approx(r["affinity_hit_rate"])
    assert last_value("Serving_router_rebalances.csv") == \
        float(r["rebalances"])
    assert last_value("Serving_router_drains.csv") == float(r["drains"])
    # per-replica snapshot coherence: the same router block, same numbers
    for rep in replicas:
        assert rep.metrics.snapshot()["router"]["routed"] == r["routed"]
