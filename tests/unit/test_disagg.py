"""Disaggregated prefill/decode fleet tests (tier-1).

The acceptance invariants of ``serving.pools`` + ``serving.rebalance``
(ROADMAP item: disaggregated serving, DeepSpeed-Inference
arXiv:2207.00032), all assertable under the virtual clock:

- a stream routed through the full disaggregated topology (prefill pool ->
  first-token KV handoff -> decode pool) is BITWISE-identical to
  sequential ``generate()`` (greedy) and to a stay-put single-replica run
  (seeded sampling) — single-device and TP=2, fp32 and int8 pools, with
  speculation on the decode pool — and every handoff splices a FRESH
  snapshot (zero replay tokens, the PR 16 contract) while the
  compile-once pins (decode==1, insert==1) hold on BOTH sides;
- under a skewed long-prompt workload at EQUAL replica count, the
  disaggregated fleet's TTFT p99 STRICTLY beats the mixed fleet's
  (prefill slots recycle at first-token time instead of being held
  hostage by long decodes) — the acceptance pin, virtual-clock exact;
- live rebalancing settles: under a crafted hot/cold load the
  hysteresis + overshoot guard move streams hot -> cold until the gap
  sits inside the ``min_gain`` band and then STOP — no stream ever
  ping-pongs (each moves at most once), and moved streams stay bitwise;
- a prefill-replica kill mid-stream recovers through the normal
  failover path: every request finishes on survivors, bitwise;
- prefix affinity resolves against BOTH pools: a handed-off stream's
  blocks re-register to its decode replica (same-prompt requests route
  there directly, suffix-only prefill, no handoff needed) while fresh
  prompts still pull same-prompt followers into the prefill pool;
- ``Serving/handoffs`` / ``Serving/rebalances`` / ``Serving/pool_*``
  monitor events report the same numbers ``Router.snapshot()`` does
  (trace == metrics), and the merged fleet trace carries the handoff
  instant pair + the wide events' ``handoff`` latency component.
"""

import functools
import json
import os

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import ConfigError, ServingConfig
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (Request, RequestState, Router,
                                   SamplingParams, ServingEngine,
                                   VirtualClock)
from deepspeed_tpu.telemetry import load_jsonl

from .conftest import make_full_replica, ref_tokens, tiny_cfg


make_replica = functools.partial(make_full_replica, job_name="disagg")


def make_disagg(engine, n_prefill=1, n_decode=1, trace_dir=None,
                monitor=None, pools_extra=None, **kw):
    """A 1..N prefill + 1..M decode disaggregated fleet."""
    pools = {"enabled": True, "prefill_replicas": n_prefill,
             "decode_replicas": n_decode}
    pools.update(pools_extra or {})
    replicas = [make_replica(engine, trace_dir=trace_dir, pools=pools, **kw)
                for _ in range(n_prefill + n_decode)]
    return Router(replicas, monitor=monitor)


def stay_put_tokens(engine, req, **kw):
    """The same request run to completion on one fresh MIXED replica —
    the stay-put reference (greedy also matches ``generate()``; sampled
    streams are pinned to the slot rng chain, and a first-token handoff's
    capture delta is 0 so the chain passes through unchanged)."""
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


def skewed_requests(n=10, plen=40, max_new=16, gap=0.02):
    """The skewed long-prompt workload of the TTFT acceptance pin: long
    prompts + long decodes arriving faster than a mixed replica's slots
    free up, so mixed fleets queue prompts behind in-flight decodes."""
    rng = np.random.RandomState(0)
    return [Request(prompt=rng.randint(0, 64, (plen,)).astype(np.int32),
                    max_new_tokens=max_new, arrival_time=i * gap)
            for i in range(n)]


# ---------------------------------------------------------------------------
# 1. config surface
# ---------------------------------------------------------------------------

def test_pools_config_validation():
    ServingConfig(pools={"enabled": True},
                  kv_pool={"block_size": 8},
                  migration={"enabled": True})
    # no pool key at all: the default pool serves the handoff
    ServingConfig(pools={"enabled": True}, migration={"enabled": True})
    with pytest.raises(ConfigError):
        ServingConfig(pools={"enabled": True},          # no migration
                      kv_pool={"block_size": 8},
                      migration={"enabled": False})
    with pytest.raises(ConfigError):
        ServingConfig(rebalance={"enabled": True, "min_gain": -1.0},
                      kv_pool={"block_size": 8},
                      migration={"enabled": True})


def test_pool_sizes_must_match_fleet(engine):
    pools = {"enabled": True, "prefill_replicas": 2, "decode_replicas": 2}
    with pytest.raises(ValueError, match="must equal the fleet size"):
        Router([make_replica(engine, pools=pools) for _ in range(3)])


def test_pool_roles_and_overrides(engine):
    """Router construction assigns roles index-order (first
    ``prefill_replicas`` prefill, rest decode), applies the per-pool
    chunk-size override, and snapshot()/pool_rollup() report the roles."""
    router = make_disagg(engine, 1, 2,
                         pools_extra={"prefill_chunk_size": 16})
    roles = [r.role for r in router._replicas]
    assert roles == ["prefill", "decode", "decode"]
    assert [r.sv.pool_role for r in router._replicas] == roles
    assert router._replicas[0].sv.chunk_size == 16       # override
    assert router._replicas[1].sv.chunk_size == 8        # inherited
    snap = router.metrics.snapshot()
    assert snap["roles"] == roles
    assert snap["pools"]["enabled"] is True
    assert snap["pools"]["prefill"]["replicas"] == [0]
    assert snap["pools"]["decode"]["replicas"] == [1, 2]
    assert snap["handoffs"] == 0 and snap["pool_rebalances"] == 0


# ---------------------------------------------------------------------------
# 2. bitwise parity through the full disaggregated topology
# ---------------------------------------------------------------------------

def test_disagg_bitwise_parity_and_zero_replay(engine):
    """1 prefill + 2 decode: every stream hands off at its first token and
    continues on the decode pool BITWISE-identically to generate() (greedy)
    / a stay-put run (seeded sampling); fresh snapshots splice with ZERO
    replay tokens; the compile-once pins hold on both sides of the move."""
    router = make_disagg(engine, 1, 2)
    rng = np.random.RandomState(0)
    reqs = mixed_requests(rng, 6)
    fin, rej, snap = router.run(reqs)
    assert len(fin) == 6 and not rej

    # every multi-token stream handed off exactly once, first token on the
    # prefill side, remainder on the decode pool
    assert snap["router"]["handoffs"] == 6
    for r in reqs:
        assert r.state is RequestState.FINISHED
        assert r.handoffs == 1 and not r.handoff_pending
        # the handoff is the stream's ONLY splice, and not a failure
        assert r.migrations == 1 and r.failovers == 0 and r.retries == 0
        if r.sampling.temperature <= 0:
            np.testing.assert_array_equal(np.asarray(r.tokens),
                                          ref_tokens(engine, r))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      stay_put_tokens(engine, r))
    # the zero-recompute contract: first-token snapshots are FRESH
    assert router.metrics.fleet_goodput()["replay_tokens"] == 0
    # handoffs ride the compiled insert path: one compile per program
    for counts in router.compile_counts():
        assert counts["decode"] == 1 and counts["insert"] == 1


def test_disagg_parity_speculation_int8(engine):
    """Same pin with the decode pool speculating (ngram drafter) over an
    int8-quantized pool: greedy acceptance is lossless and int8 payloads
    move byte-for-byte, so handed-off streams still match a stay-put run
    with the identical serving config exactly."""
    kw = dict(kv_pool={"block_size": 8,
                       "on_demand_growth": True, "kv_dtype": "int8"},
              speculative={"enabled": True, "drafter": "ngram", "k": 4})
    router = make_disagg(
        engine, 1, 1,
        pools_extra={"prefill_speculation": "off",
                     "decode_speculation": "on"}, **kw)
    assert router._replicas[0].sv._spec_on is False
    assert router._replicas[1].sv._spec_on is True
    rng = np.random.RandomState(1)
    # repetitive prompts give the ngram drafter something to accept
    reqs = [Request(prompt=np.tile(rng.randint(0, 64, (4,)), 5)
                    .astype(np.int32), max_new_tokens=10)
            for _ in range(4)]
    fin, rej, snap = router.run(reqs)
    assert len(fin) == 4 and not rej
    assert snap["router"]["handoffs"] == 4
    for r in reqs:
        np.testing.assert_array_equal(
            np.asarray(r.tokens), stay_put_tokens(engine, r, **kw))
    assert router.metrics.fleet_goodput()["replay_tokens"] == 0


def test_disagg_tp2_parity(devices8):
    """TP=2 leg: the first-token handoff moves sharded pool blocks between
    model-parallel replicas; greedy streams through the disaggregated
    topology still match the single-device reference bitwise."""
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
                                   "snapshot_interval_tokens": 2},
                     "pools": {"enabled": True, "prefill_replicas": 1,
                               "decode_replicas": 1}}}),
        mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)

    router = Router([ServingEngine(eng, clock=VirtualClock())
                     for _ in range(2)])
    rng = np.random.RandomState(9)
    reqs = [Request(
        prompt=rng.randint(0, 64, (int(rng.randint(10, 30)),)).astype(np.int32),
        max_new_tokens=6) for _ in range(4)]
    fin, rej, snap = router.run(reqs)
    assert len(fin) == 4 and not rej
    assert snap["router"]["handoffs"] == 4

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                       max_tokens=64)
    raw.params = values
    for r in reqs:
        assert r.handoffs == 1
        ref = np.asarray(raw.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


# ---------------------------------------------------------------------------
# 3. the TTFT acceptance pin
# ---------------------------------------------------------------------------

def test_disagg_ttft_p99_strictly_beats_mixed(engine):
    """THE acceptance pin: under the skewed long-prompt workload at EQUAL
    replica count (2 vs 1+1), the disaggregated fleet's TTFT p99 is
    STRICTLY lower than the mixed fleet's, virtual-clock exact. Mechanism:
    a mixed replica's slots are held by long decodes, so later prompts
    queue behind token-by-token completion; a prefill replica's slots
    recycle the moment the first token hands off."""
    kw = dict(max_queue_depth=64)

    mixed = Router([make_replica(engine, **kw) for _ in range(2)])
    fin_m, rej_m, snap_m = mixed.run(skewed_requests())

    disagg = make_disagg(engine, 1, 1, **kw)
    fin_d, rej_d, snap_d = disagg.run(skewed_requests())

    # equal work completed — the comparison is apples-to-apples
    assert len(fin_m) == len(fin_d) == 10 and not rej_m and not rej_d
    assert snap_d["router"]["handoffs"] == 10
    p_mixed = snap_m["percentiles"]["ttft_ms"]
    p_disagg = snap_d["percentiles"]["ttft_ms"]
    assert p_disagg["p99"] < p_mixed["p99"]
    assert p_disagg["p50"] < p_mixed["p50"]
    # and the win costs nothing in correctness
    for r in fin_d:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))


# ---------------------------------------------------------------------------
# 4. live rebalancing hysteresis
# ---------------------------------------------------------------------------

def test_rebalance_hysteresis_no_ping_pong(engine):
    """Crafted hot/cold load: session affinity (with a huge override
    margin) piles four long decodes onto replica 0 while replica 1 idles.
    The rebalancer moves streams hot -> cold until the gap sits inside the
    ``min_gain`` band, then STOPS — even with cooldown/interval cranked to
    pathological values no stream moves twice (the overshoot guard keeps a
    move from arming the reverse trigger), and moved streams stay
    bitwise-identical to stay-put runs."""
    kw = dict(n_slots=4, router={"rebalance_margin": 100.0},
              rebalance={"enabled": True, "min_gain": 0.2, "cooldown": 0.05,
                         "max_concurrent": 1, "interval": 1})
    router = Router([make_replica(engine, **kw) for _ in range(2)])
    rng = np.random.RandomState(3)
    reqs = [Request(prompt=rng.randint(0, 64, (10,)).astype(np.int32),
                    max_new_tokens=16, session_id="hot") for _ in range(4)]
    fin, rej, snap = router.run(reqs)
    assert len(fin) == 4 and not rej
    # affinity really did pile everything onto replica 0
    assert snap["router"]["per_replica_routed"] == [4, 0]
    # the rebalancer split the load ...
    assert snap["router"]["pool_rebalances"] >= 1
    # ... and settled: nobody ping-pongs, moves stay bounded
    assert all(r.rebalances <= 1 for r in reqs)
    assert snap["router"]["pool_rebalances"] == \
        sum(r.rebalances for r in reqs) <= 3
    # voluntary moves burn no retry/failover budget and lose no tokens
    assert all(r.failovers == 0 and r.retries == 0 for r in reqs)
    assert router.metrics.fleet_goodput()["replay_tokens"] == 0
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      stay_put_tokens(engine, r))


# ---------------------------------------------------------------------------
# 5. prefill-replica kill mid-stream
# ---------------------------------------------------------------------------

def test_prefill_kill_recovers_via_failover(engine):
    """A prefill-replica kill mid-prefill rides the normal failover path
    while the SURVIVING prefill replica keeps handing off: the killed
    replica's stream re-dispatches and finishes on a survivor, nothing is
    shed, and every greedy stream stays bitwise-equal to generate()."""
    router = make_disagg(engine, 2, 1)
    rng = np.random.RandomState(7)
    reqs = [Request(prompt=rng.randint(0, 64, (40,)).astype(np.int32),
                    max_new_tokens=8, arrival_time=i * 0.4)
            for i in range(5)]
    router.apply_chaos([(1.0, "kill", 0, 0.0)])
    fin, rej, snap = router.run(reqs)
    assert len(fin) == 5 and not rej
    mig = snap["router"]["migration"]
    assert mig["replica_kills"] == 1 and mig["failovers"] >= 1
    assert mig["shed_replica_failed"] == 0
    # handoffs kept flowing through the surviving prefill replica
    assert snap["router"]["handoffs"] >= 3
    failed_over = [r for r in reqs if r.failovers]
    assert failed_over
    for r in reqs:
        assert r.state is RequestState.FINISHED
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))


# ---------------------------------------------------------------------------
# 6. prefix affinity across pools
# ---------------------------------------------------------------------------

def test_pool_prefix_affinity_both_directions(engine):
    """Cross-pool prefix dedupe: (a) a handed-off stream's blocks
    re-register to its DECODE replica, so a later same-prompt request
    routes straight there (suffix-only prefill — no handoff needed, the
    blocks never move twice); (b) a fresh prompt registers to its PREFILL
    replica at submit, so a same-prompt follower lands in the prefill
    pool with it."""
    router = make_disagg(engine, 1, 1)
    rng = np.random.RandomState(5)
    p_handed = rng.randint(0, 64, (24,)).astype(np.int32)

    first = Request(prompt=p_handed, max_new_tokens=6)
    router.submit(first)
    while first.state is not RequestState.FINISHED:
        router.step()
    assert first.handoffs == 1

    # (a) same prompt again: prefix affinity resolves to the DECODE
    # replica that now owns the blocks — routed there directly
    again = Request(prompt=p_handed.copy(), max_new_tokens=6)
    router.submit(again)
    assert router._requests[again.request_id][1] == 1
    assert router.metrics.prefix_hits >= 1
    while again.state is not RequestState.FINISHED:
        router.step()
    assert again.prefix_saved_tokens > 0       # suffix-only prefill
    assert again.handoffs == 0                 # already decode-side
    np.testing.assert_array_equal(np.asarray(again.tokens),
                                  ref_tokens(engine, again))

    # (b) a FRESH prompt registers prefill-side at submit: its follower
    # prefix-routes into the prefill pool before any token exists
    p_fresh = rng.randint(0, 64, (24,)).astype(np.int32)
    lead = Request(prompt=p_fresh, max_new_tokens=4)
    follow = Request(prompt=p_fresh.copy(), max_new_tokens=4)
    hits = router.metrics.prefix_hits
    router.submit(lead)
    assert router._requests[lead.request_id][1] == 0
    router.submit(follow)
    assert router._requests[follow.request_id][1] == 0
    assert router.metrics.prefix_hits == hits + 1
    while not (lead.state is RequestState.FINISHED
               and follow.state is RequestState.FINISHED):
        router.step()
    np.testing.assert_array_equal(np.asarray(follow.tokens),
                                  ref_tokens(engine, follow))


# ---------------------------------------------------------------------------
# 7. observability: events == snapshot, wide events carry the handoff
# ---------------------------------------------------------------------------

def csv_monitor(engine, tmp):
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    return MonitorMaster(engine.config.replace(
        csv_monitor={"enabled": True, "output_path": str(tmp),
                     "job_name": "mon"}))


def last_csv(tmp, name):
    rows = (tmp / "mon" / name).read_text().strip().splitlines()
    return float(rows[-1].split(",")[-1])


def test_handoff_events_snapshot_coherence(engine, tmp_path):
    """Traced + monitored disaggregated fleet: the Serving/handoffs /
    Serving/rebalances / Serving/pool_* monitor events carry exactly the
    numbers Router.snapshot() reports; the merged fleet trace has the
    request/handoff_out -> request/handoff_in instant pair; the wide
    events carry the per-request handoff count and a ``handoff`` latency
    component in the breakdown; fleet.json records the pool roles."""
    router = make_disagg(engine, 1, 1, trace_dir=tmp_path,
                         monitor=csv_monitor(engine, tmp_path))
    base = os.path.join(str(tmp_path), "disagg")
    rng = np.random.RandomState(2)
    reqs = mixed_requests(rng, 4)
    fin, rej, snap = router.run(reqs)
    assert len(fin) == 4 and not rej

    r_snap = snap["router"]
    assert r_snap["handoffs"] == 4
    # trace == metrics: monitor events report the snapshot's numbers
    assert last_csv(tmp_path, "Serving_handoffs.csv") == r_snap["handoffs"]
    assert last_csv(tmp_path, "Serving_rebalances.csv") \
        == r_snap["pool_rebalances"]
    assert last_csv(tmp_path, "Serving_pool_prefill_routed.csv") \
        == r_snap["pools"]["prefill"]["routed"] == 4
    assert (tmp_path / "mon" / "Serving_pool_decode_occupancy.csv").exists()

    # merged fleet trace: the handoff instant pair, once per request
    spans = load_jsonl(os.path.join(base, "spans.jsonl"))
    outs = [s for s in spans if s.get("name") == "request/handoff_out"]
    ins = [s for s in spans if s.get("name") == "request/handoff_in"]
    assert len(outs) == len(ins) == 4
    assert {s["args"]["request_id"] for s in outs} \
        == {r.request_id for r in reqs}
    assert all(s["args"]["saved_tokens"] > 0 for s in ins)
    routes = [s for s in spans if s.get("name") == "route/handoff"]
    assert len(routes) == 4 and all(s["args"]["target"] == 1
                                    for s in routes)

    # wide events: handoff count + latency component
    wide = {r["request_id"]: r
            for r in load_jsonl(os.path.join(base, "requests.jsonl"))}
    for r in reqs:
        row = wide[r.request_id]
        assert row["handoffs"] == 1 and row["rebalances"] == 0
        assert row["breakdown"]["handoff"] >= 0.0
        assert row["ttft"] is not None

    # fleet.json: roles + counters for the per-pool report tables
    fleet = json.load(open(os.path.join(base, "fleet.json")))
    assert fleet["router"]["roles"] == ["prefill", "decode"]
    assert fleet["router"]["handoffs"] == 4
    assert fleet["router"]["pools"]["enabled"] is True


# ---------------------------------------------------------------------------
# 8. chaos tool smoke through the disaggregated path
# ---------------------------------------------------------------------------

def test_chaos_serve_disagg_tool_smoke(tmp_path):
    """tier-1 smoke of tools/chaos_serve.py with pool flags: a seeded kill
    lands in the prefill pool and a stall in the decode pool, handoffs
    still flow (exit 2 guards against a silently-mixed run), artifact
    stamped with the topology block, exit 0."""
    from tools import chaos_serve

    out = str(tmp_path / "chaos_disagg.json")
    assert chaos_serve.main(
        ["--prefill-replicas", "2", "--decode-replicas", "2", "--rebalance",
         "--requests", "8", "--kills", "1", "--stalls", "1", "--seed", "0",
         "--out", out]) == 0
    report = json.loads(open(out).read())
    assert report["topology"]["roles"] == \
        ["prefill", "prefill", "decode", "decode"]
    assert report["topology"]["handoffs"] > 0
    assert report["nonterminal_requests"] == []
    assert report["bitwise_mismatches"] == []
    assert report["deterministic_rerun"] is True
    assert report["resilience"]["replay_tokens"] == 0
    assert report["provenance"]["git_sha"]
