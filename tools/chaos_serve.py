#!/usr/bin/env python
"""Serving chaos soak runner: seeded replica-kill/stall survival testing.

The serving-tier mirror of ``chaos_train.py``: drives a Router fleet of
ServingEngine replicas (virtual clock — deterministic DES) through a
:class:`ReplicaChaosSchedule` — seeded kills and stalls at arbitrary fleet
instants — with live KV migration armed, and measures what the recovery
layer actually delivers:

- ``kills_fired`` / ``stalls_fired``: every scheduled fault must fire;
- survival: every request ends FINISHED or terminally shed with a reason
  (``replica_failed`` after the bounded retry budget) — nothing hangs;
- bitwise continuity: every finished stream must equal an uninterrupted
  single-replica reference run of the same request (greedy AND seeded
  sampling) — failover replay and snapshot splicing may move work between
  replicas but may never change a committed token;
- determinism: the same chaos seed must reproduce the same per-request
  terminal states, token streams and recovery counters exactly;
- recovery economics: the fleet migration block (snapshots, migrations,
  failovers, retries, terminal sheds) and the goodput split (replay tokens
  burned re-computing work the dead replica had already done vs tokens
  the snapshots saved).

Emits a provenance-stamped JSON artifact (``tools/_common.run_stamp``).
Tier-1 smokes this on the tiny preset; real soaks raise ``--requests`` /
``--kills``.

Usage:
    python tools/chaos_serve.py --replicas 3 --requests 10 --kills 1 \
        --stalls 1 --seed 0 --out tools/artifacts/chaos_serve_tiny_cpu.json

Disaggregated mode (``--prefill-replicas/--decode-replicas``, optional
``--rebalance``): the fleet splits into a prefill and a decode pool
(first-token KV handoffs between them) and the seeded schedule becomes
POOL-AWARE — kills land on the PREFILL pool (a replica dies mid-prefill /
mid-handoff; recovery must re-dispatch through the surviving topology) and
stalls land on the DECODE pool (degraded health while rebalancing is live).
Same exit gates, plus the handoff machinery must actually have engaged.

Burst mode (``--burst-requests N``): on top of the staggered baseline the
schedule injects a DENSE arrival burst at ``--burst-at`` (gap
``--burst-gap``) followed by a sparse recovery tail (``--burst-tail``
requests, ``--burst-tail-gap`` apart) — and gains a RECOVERY exit gate:
every tail request's TTFT must come back under ``--recovery-ttft-ms``
(the uncontended bound), proving the fleet actually drained the burst
backlog instead of wedging. The artifact gains a ``burst`` block
(pre/burst/tail TTFT split, recovered flag).

Exit codes: 0 ok; 2 survival gate (fault did not fire / request neither
finished nor shed / disaggregated run with zero handoffs); 3 continuity
gate (bitwise mismatch vs reference or chaos-vs-chaos nondeterminism);
4 shed gate (shed rate above ``--max-shed``); 5 recovery gate (post-burst
tail TTFT never recovered to the uncontended bound).
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools._common import stamp_record  # noqa: E402


def build_engine(args):
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import get_model

    model = get_model("gpt2", "tiny", vocab_size=args.vocab,
                      max_seq_len=args.seq, compute_dtype=jnp.float32)
    return deepspeed_tpu.init_inference(
        model, dtype="float32", max_tokens=args.seq,
        prompt_bucket_size=16)


def make_replica(engine, args):
    from deepspeed_tpu.config import ServingConfig
    from deepspeed_tpu.serving import ServingEngine, VirtualClock

    kw = dict(
        virtual_clock=True,
        n_slots=args.slots,
        retry_limit=args.retry_limit,
        chunked_prefill={"enabled": True, "chunk_size": 8},
        kv_pool={"block_size": 8, "on_demand_growth": True},
        migration={"enabled": True,
                   "snapshot_interval_tokens": args.snapshot_interval})
    if args.prefill_replicas or args.decode_replicas:
        kw["pools"] = {"enabled": True,
                       "prefill_replicas": max(args.prefill_replicas, 1),
                       "decode_replicas": max(args.decode_replicas, 1)}
    if args.rebalance:
        kw["rebalance"] = {"enabled": True}
    cfg = ServingConfig(**kw)
    return ServingEngine(engine, serving_config=cfg, clock=VirtualClock())


def make_requests(args):
    """Seeded workload: alternating greedy / seeded-sampled requests with
    staggered arrivals — fresh Request objects per run (runs mutate them)."""
    import numpy as np

    from deepspeed_tpu.serving import Request, SamplingParams

    rng = np.random.RandomState(args.seed * 9973 + 17)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.randint(9, 30))
        prompt = rng.randint(0, args.vocab, (plen,)).astype(np.int32)
        sampling = SamplingParams(temperature=0.8, top_k=8,
                                  seed=1000 + i) if i % 2 else None
        reqs.append(Request(prompt=prompt, max_new_tokens=args.new_tokens,
                            arrival_time=i * args.arrival_gap,
                            sampling=sampling))
    if args.burst_requests:
        # dense burst at --burst-at, then a sparse recovery tail whose
        # arrivals are far enough apart that a healthy fleet serves each
        # one uncontended — the recovery gate measures THEIR TTFT
        for j in range(args.burst_requests):
            plen = int(rng.randint(9, 30))
            prompt = rng.randint(0, args.vocab, (plen,)).astype(np.int32)
            sampling = SamplingParams(temperature=0.8, top_k=8,
                                      seed=5000 + j) if j % 2 else None
            reqs.append(Request(
                prompt=prompt, max_new_tokens=args.new_tokens,
                arrival_time=args.burst_at + j * args.burst_gap,
                sampling=sampling))
        burst_end = args.burst_at + args.burst_requests * args.burst_gap
        for k in range(args.burst_tail):
            plen = int(rng.randint(9, 30))
            prompt = rng.randint(0, args.vocab, (plen,)).astype(np.int32)
            reqs.append(Request(
                prompt=prompt, max_new_tokens=args.new_tokens,
                arrival_time=burst_end + (k + 1) * args.burst_tail_gap))
    return reqs


def run_reference(engine, args):
    """Uninterrupted single-replica run of each request, one at a time:
    the bitwise-continuity baseline (no router, no chaos, no co-batching)."""
    sv = make_replica(engine, args)
    streams = []
    for req in make_requests(args):
        for _ in sv.run([req]):
            pass
        streams.append(list(req.tokens))
    return streams


def run_chaos(engine, args):
    """One seeded chaos pass over a fresh fleet; returns the terminal
    per-request states/streams plus the fleet snapshot."""
    from deepspeed_tpu.serving import Router
    from deepspeed_tpu.testing import ReplicaChaosSchedule

    replicas = [make_replica(engine, args) for _ in range(args.replicas)]
    router = Router(replicas)
    schedule = ReplicaChaosSchedule(
        args.seed, horizon=args.horizon, n_replicas=args.replicas,
        n_kills=args.kills, n_stalls=args.stalls,
        stall_duration=args.stall_duration)
    events = list(schedule.events)
    if args.prefill_replicas or args.decode_replicas:
        # pool-aware faults: deterministically remap the seeded schedule so
        # kills land on the PREFILL pool (mid-prefill / mid-handoff death)
        # and stalls on the DECODE pool (degraded health under rebalance)
        n_p = max(args.prefill_replicas, 1)
        n_d = max(args.decode_replicas, 1)
        events = [(t, kind,
                   idx % n_p if kind == "kill" else n_p + idx % n_d, dur)
                  for t, kind, idx, dur in events]
    router.apply_chaos(events)
    requests = make_requests(args)
    finished, rejected, snap = router.run(requests)
    return {
        "schedule": [[round(t, 6), kind, idx, dur]
                     for t, kind, idx, dur in events],
        "states": [r.state.value for r in requests],
        "streams": [list(r.tokens) for r in requests],
        "ttfts": [r.ttft for r in requests],
        "finish_reasons": [r.finish_reason or r.reject_reason
                           for r in requests],
        "failovers": [r.failovers for r in requests],
        "migrations": [r.migrations for r in requests],
        "n_finished": len(finished),
        "n_rejected": len(rejected),
        "snapshot": snap,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="disaggregated mode: dedicate this many replicas "
                         "to PREFILL (first-token KV handoff to the decode "
                         "pool); overrides --replicas to prefill+decode and "
                         "makes the chaos schedule pool-aware (kills target "
                         "the prefill pool, stalls the decode pool)")
    ap.add_argument("--decode-replicas", type=int, default=0,
                    help="disaggregated mode: decode-pool size")
    ap.add_argument("--rebalance", action="store_true",
                    help="arm live rebalancing (serving.rebalance) so decode "
                         "stalls exercise the hot->cold migration path")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--kills", type=int, default=1)
    ap.add_argument("--stalls", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--retry-limit", type=int, default=1)
    ap.add_argument("--snapshot-interval", type=int, default=2,
                    help="serving.migration.snapshot_interval_tokens — the "
                         "failover replay bound")
    ap.add_argument("--horizon", type=float, default=2.0,
                    help="chaos schedule horizon in fleet virtual seconds")
    ap.add_argument("--stall-duration", type=float, default=0.25)
    ap.add_argument("--arrival-gap", type=float, default=0.05)
    ap.add_argument("--burst-requests", type=int, default=0,
                    help="burst mode: inject this many DENSE arrivals at "
                         "--burst-at on top of the baseline, plus a sparse "
                         "recovery tail — arms the recovery exit gate")
    ap.add_argument("--burst-at", type=float, default=0.5,
                    help="burst start (fleet virtual seconds)")
    ap.add_argument("--burst-gap", type=float, default=0.01,
                    help="intra-burst arrival gap (virtual s)")
    ap.add_argument("--burst-tail", type=int, default=3,
                    help="sparse post-burst requests the recovery gate "
                         "measures")
    ap.add_argument("--burst-tail-gap", type=float, default=60.0,
                    help="tail arrival spacing (virtual s) — wide enough "
                         "that a DRAINED fleet serves each uncontended")
    ap.add_argument("--recovery-ttft-ms", type=float, default=5000.0,
                    help="recovery gate: every tail request's TTFT must be "
                         "under this bound (virtual ms) or exit 5")
    ap.add_argument("--max-shed", type=float, default=0.5,
                    help="max tolerated shed rate before exit 4 (kills with "
                         "retry_limit 0 legitimately shed their victims)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    pools_on = bool(args.prefill_replicas or args.decode_replicas)
    if pools_on:
        args.replicas = max(args.prefill_replicas, 1) \
            + max(args.decode_replicas, 1)
    if args.kills >= args.replicas:
        print(f"--kills {args.kills} must leave at least one survivor of "
              f"--replicas {args.replicas}", file=sys.stderr)
        return 1

    engine = build_engine(args)
    try:
        ref_streams = run_reference(engine, args)
        chaos = run_chaos(engine, args)
        rerun = run_chaos(engine, args)
    finally:
        engine.destroy()

    # ---- gates ----------------------------------------------------------
    mig = chaos["snapshot"]["router"]["migration"]
    goodput = chaos["snapshot"]["goodput"]
    kills_fired = mig["replica_kills"]
    stalls_fired = mig["replica_stalls"]
    nonterminal = [i for i, s in enumerate(chaos["states"])
                   if s not in ("finished", "rejected")]
    mismatches = [i for i, (s, ref) in
                  enumerate(zip(chaos["streams"], ref_streams))
                  if chaos["states"][i] == "finished" and s != ref]
    deterministic = all(
        chaos[k] == rerun[k]
        for k in ("states", "streams", "finish_reasons", "failovers",
                  "migrations", "schedule", "ttfts")) \
        and chaos["snapshot"]["router"]["migration"] == \
        rerun["snapshot"]["router"]["migration"] \
        and all(chaos["snapshot"]["router"][k] ==
                rerun["snapshot"]["router"][k]
                for k in ("handoffs", "pool_rebalances"))
    n_total = len(chaos["states"])
    shed_rate = chaos["n_rejected"] / max(n_total, 1)

    # ---- burst recovery split -------------------------------------------
    burst = None
    if args.burst_requests:
        pre = slice(0, args.requests)
        mid = slice(args.requests, args.requests + args.burst_requests)
        tail = slice(args.requests + args.burst_requests, n_total)
        p99 = lambda xs: None if not [x for x in xs if x is not None] \
            else round(max(x for x in xs if x is not None) * 1e3, 2)
        tail_ttfts = [t for t in chaos["ttfts"][tail] if t is not None]
        burst = {
            "burst_requests": args.burst_requests,
            "burst_at": args.burst_at,
            "pre_ttft_p99_ms": p99(chaos["ttfts"][pre]),
            "burst_ttft_p99_ms": p99(chaos["ttfts"][mid]),
            "tail_ttft_p99_ms": p99(chaos["ttfts"][tail]),
            "recovery_ttft_ms": args.recovery_ttft_ms,
            # every tail request finished AND came back under the
            # uncontended bound — the fleet drained the backlog
            "recovered": bool(
                tail_ttfts
                and len(tail_ttfts) == tail.stop - tail.start
                and all(t * 1e3 <= args.recovery_ttft_ms
                        for t in tail_ttfts)),
        }

    record = {
        "tool": "chaos_serve",
        "config": {k: getattr(args, k) for k in
                   ("replicas", "prefill_replicas", "decode_replicas",
                    "rebalance", "requests", "kills", "stalls", "seed",
                    "slots", "new_tokens", "vocab", "seq", "retry_limit",
                    "snapshot_interval", "horizon", "stall_duration",
                    "arrival_gap", "max_shed", "burst_requests", "burst_at",
                    "burst_gap", "burst_tail", "burst_tail_gap",
                    "recovery_ttft_ms")},
        "schedule": chaos["schedule"],
        "kills_fired": kills_fired,
        "stalls_fired": stalls_fired,
        "completed": chaos["n_finished"],
        "shed": chaos["n_rejected"],
        "shed_rate": round(shed_rate, 4),
        "shed_reasons": {r: chaos["finish_reasons"].count(r)
                         for i, r in enumerate(chaos["finish_reasons"])
                         if chaos["states"][i] == "rejected"},
        "nonterminal_requests": nonterminal,
        "bitwise_mismatches": mismatches,
        "deterministic_rerun": deterministic,
        "burst": burst,
        # the recovery economics: the resilience block bench artifacts carry
        "resilience": dict(mig, replay_tokens=goodput["replay_tokens"],
                           migrated_saved_tokens=mig["migrated_saved_tokens"]),
        "goodput": goodput,
        # the disaggregated-topology block: pool roles, per-pool rollup and
        # the handoff/rebalance counters (empty-by-default mixed fleets
        # carry enabled=false)
        "topology": dict(
            chaos["snapshot"]["router"]["pools"],
            roles=chaos["snapshot"]["router"]["roles"],
            handoffs=chaos["snapshot"]["router"]["handoffs"],
            rebalances=chaos["snapshot"]["router"]["pool_rebalances"]),
        "health": chaos["snapshot"]["router"]["health"],
        "makespan": chaos["snapshot"].get("makespan"),
        "per_request": [
            {"state": s, "reason": fr, "tokens": len(st),
             "failovers": f, "migrations": m}
            for s, fr, st, f, m in zip(
                chaos["states"], chaos["finish_reasons"], chaos["streams"],
                chaos["failovers"], chaos["migrations"])],
    }
    stamp_record(record, config=record["config"])
    out = json.dumps(record, indent=1, default=str)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            f.write(out + "\n")
    print(out)

    if kills_fired != args.kills or stalls_fired != args.stalls:
        print(f"FAIL: fired {kills_fired}/{args.kills} kills, "
              f"{stalls_fired}/{args.stalls} stalls", file=sys.stderr)
        return 2
    if pools_on and record["topology"]["handoffs"] == 0:
        print("FAIL: disaggregated run completed with zero prefill->decode "
              "handoffs — the pool machinery never engaged", file=sys.stderr)
        return 2
    if nonterminal:
        print(f"FAIL: requests {nonterminal} neither finished nor shed",
              file=sys.stderr)
        return 2
    if mismatches:
        print(f"FAIL: requests {mismatches} finished with streams that "
              f"differ from the uninterrupted reference", file=sys.stderr)
        return 3
    if not deterministic:
        print("FAIL: chaos rerun with the same seed diverged",
              file=sys.stderr)
        return 3
    if shed_rate > args.max_shed:
        print(f"FAIL: shed rate {shed_rate} > {args.max_shed}",
              file=sys.stderr)
        return 4
    if burst is not None and not burst["recovered"]:
        print(f"FAIL: post-burst tail TTFT p99 {burst['tail_ttft_p99_ms']} "
              f"ms never recovered under {args.recovery_ttft_ms} ms "
              f"(burst p99 {burst['burst_ttft_p99_ms']} ms)",
              file=sys.stderr)
        return 5
    return 0


if __name__ == "__main__":
    sys.exit(main())
