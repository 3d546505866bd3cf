"""Serving benchmark: p50 TTFT (prefill) + steady-state decode throughput,
plus an open-loop offered-load mode (``--qps``) for the continuous-batching
serving subsystem.

Matches the BASELINE.json serving metric ("init_inference p50 TTFT"; reference
flow ``inference/engine.py:560`` — model load, kernel inject, generate). Loads a
registry model via ``deepspeed_tpu.init_inference`` and measures, per
(model size x quant mode x prompt bucket):

- TTFT: wall time of ``generate(max_new_tokens=1)`` — prefill + first-token
  sample + host readback, i.e. what a serving frontend actually waits for.
  Reported as p50/p95 over ``--repeats``.
- decode tok/s: ``(b * D) / (t(generate(1 + D)) - t(generate(1)))`` —
  the compiled decode loop's steady-state rate, dispatch overhead excluded.

Usage (single chip):
    python tools/bench_serving.py --family gpt2 --sizes small,medium \
        --prompts 128,512,1000 --modes bf16,int8,int4 --new-tokens 64

Open-loop offered load (continuous batching; ``serving/engine.py``):
    python tools/bench_serving.py --qps 20 --num-requests 64 --family gpt2 \
        --sizes tiny --slots 4 --queue-depth 8 --output serving_load.json

``--qps`` drives seeded Poisson arrivals at the given rate through the
slot-pool scheduler and emits ONE throughput–latency JSON artifact: p50/p99
TTFT (queueing included), TPOT, aggregate tokens/s, and the shed rate —
under overload, admission control rejects with a reason instead of OOMing,
and the artifact records how much was shed. Tier-1 smokes this mode on the
tiny preset under JAX_PLATFORMS=cpu.

Emits one JSON line per row (machine-readable) then a summary table. Every
row carries the ``platform`` it ran on; the closed-loop mode divides by the
device's published HBM peak and so refuses a device kind that has none.
"""

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_engine(family, size, mode, max_tokens, **model_kw):
    """Returns (engine, n_params, weight_bytes) — n_params counted BEFORE
    quantization (int4 packs two weights per element; the packed tree
    undercounts), weight_bytes counted AFTER (the decode HBM-roofline
    numerator)."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.layers import split_params_axes
    from deepspeed_tpu.models.registry import get_model

    # max_seq_len must cover prompt + generation for the KV cache
    model = get_model(family, size, max_seq_len=max_tokens, **model_kw)
    shapes = split_params_axes(jax.eval_shape(model.init, jax.random.PRNGKey(0)))[0]
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    config = {
        "dtype": "bfloat16",
        "max_tokens": max_tokens,
        "prompt_bucket_size": 64,
    }
    if mode in ("int8", "int4"):
        config["quant"] = {"enabled": True, "bits": 8 if mode == "int8" else 4}
    elif mode != "bf16":
        raise ValueError(f"unknown mode {mode}")
    engine = deepspeed_tpu.init_inference(model=model, config=config)
    # resident weight bytes AFTER quantization (packed int4 counts real bytes,
    # groupwise scales included) — the decode roofline numerator: a batch-1
    # decode step reads every one of these bytes from HBM once
    weight_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(engine.params))
    return engine, n_params, weight_bytes


def bench_one(engine, prompt_len, new_tokens, batch, repeats, rng):
    """Returns (ttft_p50_ms, ttft_p95_ms, decode_tok_s)."""
    import jax

    vocab = engine.module.config.vocab_size
    ids = rng.randint(0, vocab, (batch, prompt_len)).astype(np.int32)

    def run(n):
        t0 = time.perf_counter()
        out = engine.generate(ids, max_new_tokens=n, greedy=True)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    run(1)            # compile prefill
    run(1 + new_tokens)  # compile decode loop

    ttfts = [run(1) for _ in range(repeats)]
    fulls = [run(1 + new_tokens) for _ in range(max(repeats // 2, 2))]
    ttft_p50 = statistics.median(ttfts)
    ttft_p95 = sorted(ttfts)[min(len(ttfts) - 1, int(0.95 * len(ttfts)))]
    decode_s = statistics.median(fulls) - ttft_p50
    decode_tok_s = (batch * new_tokens) / decode_s if decode_s > 0 else float("inf")
    return ttft_p50 * 1e3, ttft_p95 * 1e3, decode_tok_s


def project_bloom_7b1(measured_hbm_util, peak_bw_gbs, prompt=512,
                      mfu_prior=0.4157, dispatch_ms=8.0):
    """Analytic BLOOM-7B1 TP=8 v5e-8 TTFT from one chip's measured signals.

    Components (BLOOM-7B1: 7.07B params, 30 layers, d_model 14336/4... the
    public card: hidden 4096, 30 layers, 32 heads):
    - prefill compute: 2*P*prompt flops over 8 chips at the measured
      single-chip MFU prior (flash prefill, bf16);
    - prefill TP collectives: 2 all-reduces/layer of the [1, prompt, d]
      activation over ICI (ring, 2x(N-1)/N wire) at v5e's ~180 GB/s
      per-chip ICI (4 links x 45 GB/s);
    - first decode token: per-chip weight bytes / (measured HBM util x peak)
      + per-layer all-reduce latency floor (~20 us each);
    - dispatch floor: a serving-host estimate, stated as an assumption.
    """
    P = 7.07e9
    n_layers, d_model, n_chips = 30, 4096, 8
    peak_flops = 197e12
    ici_bw = 180e9

    prefill_flops = 2.0 * P * prompt
    t_prefill = prefill_flops / (n_chips * peak_flops * mfu_prior)
    ar_bytes = prompt * d_model * 2  # bf16 activation
    wire = 2 * ar_bytes * (n_chips - 1) / n_chips
    t_coll = n_layers * 2 * wire / ici_bw
    w_per_chip = P * 2 / n_chips
    t_decode1 = (w_per_chip / (measured_hbm_util * peak_bw_gbs * 1e9)
                 + n_layers * 2 * 20e-6)
    ttft_ms = (t_prefill + t_coll + t_decode1) * 1e3 + dispatch_ms
    print(json.dumps({
        "projection": "bloom-7b1-v5e-8-ttft",
        "prompt_len": prompt,
        "ttft_ms": round(ttft_ms, 1),
        "components_ms": {
            "prefill_compute": round(t_prefill * 1e3, 2),
            "prefill_collectives": round(t_coll * 1e3, 2),
            "first_decode_token": round(t_decode1 * 1e3, 2),
            "dispatch_floor_assumed": dispatch_ms,
        },
        "inputs": {
            "measured_hbm_util": round(measured_hbm_util, 3),
            "mfu_prior": mfu_prior,
            "ici_bw_gbs": ici_bw / 1e9,
        },
        "baseline_bar_ms": 55.0,
    }), flush=True)


def parse_tenant_mix(spec):
    """Parse ``--tenants`` mix specs like ``interactive:0.3:slo=300,batch:0.7``
    into ``[(class, fraction, ttft_slo_ms_or_None), ...]``. Fractions are
    normalised; ``slo=`` overrides that class's per-tenant TTFT P99 target."""
    mix = []
    for part in spec.split(","):
        fields = part.split(":")
        if len(fields) < 2:
            raise ValueError(f"--tenants entry {part!r}: want class:frac"
                             f"[:slo=ms]")
        cls, frac = fields[0].strip(), float(fields[1])
        if cls not in ("interactive", "batch"):
            raise ValueError(f"--tenants class {cls!r}: want interactive|batch")
        if frac <= 0:
            raise ValueError(f"--tenants fraction for {cls} must be > 0")
        slo_ms = None
        for extra in fields[2:]:
            k, _, v = extra.partition("=")
            if k.strip() != "slo":
                raise ValueError(f"--tenants option {extra!r}: want slo=ms")
            slo_ms = float(v)
        mix.append((cls, frac, slo_ms))
    total = sum(f for _, f, _ in mix)
    return [(c, f / total, s) for c, f, s in mix]


def run_open_loop(args):
    """Open-loop offered-load bench: seeded Poisson arrivals at ``--qps``
    through the continuous-batching serving engine; writes a throughput–
    latency JSON artifact (p50/p99 TTFT, TPOT, tokens/s, shed rate)."""
    import jax

    from deepspeed_tpu.serving import Request, Router, ServingEngine, percentile

    size = args.sizes.split(",")[0]
    mode = args.modes.split(",")[0]
    prompts = [int(p) for p in args.prompts.split(",")]
    max_tokens = ((max(prompts) + args.new_tokens + 63) // 64) * 64
    # which decode attention runs is the engine's choice (on a TPU: the
    # kernel where the compiler takes it); off a TPU the kernel only runs
    # under the interpreter, which is all this flag asks for
    model_kw = {"attention_interpret": True} \
        if (args.attention_interpret
            and jax.devices()[0].platform != "tpu") else {}
    engine, n_params, _ = build_engine(args.family, size, mode, max_tokens,
                                       **model_kw)
    serving_kw = dict(
        n_slots=args.slots, max_queue_depth=args.queue_depth,
        kv_pool={"block_size": args.kv_block_size,
                 "n_blocks": args.kv_blocks, "kv_dtype": args.kv_dtype,
                 "on_demand_growth": bool(args.kv_growth)})
    if args.chunk_size:
        serving_kw["chunked_prefill"] = {"enabled": True,
                                         "chunk_size": args.chunk_size}
    if args.spec_draft:
        serving_kw["speculative"] = {"enabled": True,
                                     "drafter": args.spec_draft,
                                     "k": args.spec_k}
    if args.slo_ttft_p99_ms or args.slo_tpot_p99_ms:
        serving_kw["slo"] = {"ttft_p99_ms": args.slo_ttft_p99_ms,
                             "tpot_p99_ms": args.slo_tpot_p99_ms}
    tenant_mix = parse_tenant_mix(args.tenants) if args.tenants else None
    if tenant_mix:
        # multi-tenant QoS: weighted-fair admission over the class mix;
        # slo= entries become per-class TTFT targets in the tenancy grades
        serving_kw["policy"] = "weighted_fair"
        tenants_cfg = {"enabled": True}
        for cls, _, slo_ms in tenant_mix:
            if slo_ms:
                tenants_cfg[cls] = {"ttft_p99_ms": slo_ms}
        serving_kw["tenants"] = tenants_cfg
    if args.autoscale:
        # queue-depth trigger keeps the autoscaler armed even without
        # --slo-* targets (config validation requires SOME sensor input)
        serving_kw["autoscaler"] = {
            "enabled": True,
            "scale_up_queue_depth": max(2.0, args.queue_depth / 2.0)}
    pools_on = bool(args.prefill_replicas or args.decode_replicas)
    if pools_on:
        # disaggregated topology: the pool split IS the replica count
        args.replicas = max(args.prefill_replicas, 1) \
            + max(args.decode_replicas, 1)
        serving_kw["pools"] = {
            "enabled": True,
            "prefill_replicas": max(args.prefill_replicas, 1),
            "decode_replicas": max(args.decode_replicas, 1)}
        # the handoff IS a live migration — arm fresh-snapshot capture
        serving_kw["migration"] = {
            "enabled": True,
            "snapshot_interval_tokens": args.chaos_snapshot_interval}
    if args.rebalance:
        serving_kw["rebalance"] = {"enabled": True}
    if args.chaos_kills or args.chaos_stalls:
        if args.chaos_kills >= max(args.replicas, 1):
            print(f"--chaos-kills {args.chaos_kills} must leave at least one "
                  f"survivor of --replicas {args.replicas}", file=sys.stderr)
            return 1
        # arm live migration so failover re-dispatches splice from the last
        # snapshot instead of replaying the whole committed stream
        serving_kw["migration"] = {
            "enabled": True,
            "snapshot_interval_tokens": args.chaos_snapshot_interval}
    engine._config.serving = engine._config.serving.replace(**serving_kw)

    rng = np.random.RandomState(args.seed)
    arrivals = np.cumsum(rng.exponential(1.0 / args.qps, args.num_requests))
    vocab = engine.module.config.vocab_size
    # --shared-prefix: every prompt opens with the SAME system-prompt tokens
    # (the pool's prefix cache turns the repeats into block hits)
    shared = rng.randint(0, vocab, (max(args.shared_prefix, 0),)) \
        .astype(np.int32)
    requests = []
    for i in range(args.num_requests):
        plen = int(rng.choice(prompts))
        new = int(rng.randint(max(args.new_tokens // 2, 1),
                              args.new_tokens + 1))
        tail = rng.randint(0, vocab,
                           (max(plen - len(shared), 1),)).astype(np.int32)
        tenant_kw = {}
        if tenant_mix:
            # seeded class draw against the normalised mix fractions; one
            # tenant per class so the tenancy block reads as the mix spec
            u, acc = rng.rand(), 0.0
            cls = tenant_mix[-1][0]
            for c, frac, _ in tenant_mix:
                acc += frac
                if u < acc:
                    cls = c
                    break
            tenant_kw = {"tenant_id": f"t-{cls}", "tenant_class": cls}
        requests.append(Request(
            prompt=np.concatenate([shared, tail])[:max(plen, 1)],
            max_new_tokens=new, arrival_time=float(arrivals[i]),
            # --session-affinity: a small pool of sticky sessions, so the
            # router's session map actually gets exercised under load
            session_id=f"sess{i % 4}" if args.session_affinity else None,
            **tenant_kw))

    # the router path is the production topology: N ServingEngine replicas
    # over ONE weight set behind the load-aware dispatcher (N=1 still goes
    # through the router, so the artifact always carries the router block)
    replicas = [ServingEngine(engine) for _ in range(max(args.replicas, 1))]
    router = Router(replicas)

    # compile outside the measured window (the reference's capture-at-init):
    # one prefill per prompt bucket + the decode/insert pool programs,
    # warmed PER REPLICA (each owns its own slot-pool programs)
    for rep in replicas:
        rep.run([Request(
            prompt=rng.randint(0, vocab, (p,)).astype(np.int32),
            max_new_tokens=2, tenant_id="warmup") for p in prompts])
        rep.metrics.reset_window()  # warmup out of the tokens/s window

    chaos_events = []
    if args.chaos_kills or args.chaos_stalls:
        from deepspeed_tpu.testing import ReplicaChaosSchedule

        # schedule instants are offsets into the offered-load window; shift
        # by the fleet frontier at arm time so the same seeded schedule
        # works on wall clocks (perf_counter zero is process start, not run
        # start) and virtual clocks (frontier 0 — identity shift) alike
        sched = ReplicaChaosSchedule(
            args.chaos_seed, horizon=max(float(arrivals[-1]), 1e-3) + 0.5,
            n_replicas=len(replicas), n_kills=args.chaos_kills,
            n_stalls=args.chaos_stalls)
        t_base = max(rep.clock.now() for rep in replicas)
        chaos_events = [[round(t, 4), kind, idx, dur]
                        for t, kind, idx, dur in sched.events]
        router.apply_chaos([(t_base + t, kind, idx, dur)
                            for t, kind, idx, dur in sched.events])

    t0 = time.perf_counter()
    finished, rejected, router_snap = router.run(requests)
    wall_s = time.perf_counter() - t0
    metrics_snap = replicas[0].metrics.snapshot()
    # fleet-aggregated health/shed blocks (the ServingMetrics partition,
    # summed over replicas)
    agg_health = {
        k: sum(r["health"][k] for r in router_snap["replicas"])
        for k in ("nonfinite_logit_steps", "unhealthy_slots")}
    agg_shed = {}
    for r in router_snap["replicas"]:
        for k, v in r["shed"].items():
            agg_shed[k] = agg_shed.get(k, 0) + v
    # router-level sheds never reach a replica's metrics — fold them in so
    # the shed histogram still partitions every turned-away request
    n_sat = router_snap["router"]["shed_all_replicas_saturated"]
    if n_sat:
        agg_shed["all_replicas_saturated"] = \
            agg_shed.get("all_replicas_saturated", 0) + n_sat

    # speculative block, fleet-aggregated: how many candidate tokens were
    # drafted, accepted and rolled back, and the effective decode tokens
    # per dispatch they bought (the multiplier headline)
    spec_keys = ("drafted_tokens", "accepted_tokens", "rolled_back_tokens",
                 "verify_steps", "decode_dispatches")
    agg_spec = {k: sum(r["speculative"][k]
                       for r in router_snap["replicas"]) for k in spec_keys}
    agg_dec = sum(r["goodput"]["decode_tokens"]
                  for r in router_snap["replicas"])
    speculative = {
        "drafter": args.spec_draft or "off",
        "spec_k": args.spec_k if args.spec_draft else 0,
        "drafts": agg_spec["drafted_tokens"],
        "accepted": agg_spec["accepted_tokens"],
        "rollbacks": agg_spec["rolled_back_tokens"],
        "verify_steps": agg_spec["verify_steps"],
        "accept_rate": round(agg_spec["accepted_tokens"]
                             / agg_spec["drafted_tokens"], 4)
        if agg_spec["drafted_tokens"] else 0.0,
        "accepted_tokens_per_step": round(
            agg_dec / agg_spec["decode_dispatches"], 4)
        if agg_spec["decode_dispatches"] else 0.0,
    }

    # unhealthy_slot sheds come back FINISHED too — keep their latencies
    # out of the artifact, same partition ServingMetrics enforces
    from deepspeed_tpu.serving import FINISH_UNHEALTHY
    healthy = [r for r in finished if r.finish_reason != FINISH_UNHEALTHY]
    ttfts = [r.ttft for r in healthy if r.ttft is not None]
    tpots = [r.tpot for r in healthy if r.tpot is not None]
    pct = lambda s, q: None if not s else round(percentile(s, q) * 1e3, 2)
    total_tokens = sum(len(r.tokens) for r in finished)
    artifact = {
        "bench": "serving_open_loop",
        "model": f"{args.family}-{size}", "mode": mode,
        "platform": jax.devices()[0].platform,
        "qps": args.qps, "num_requests": args.num_requests,
        "slots": args.slots, "queue_depth": args.queue_depth,
        "prompt_lens": prompts, "max_new_tokens": args.new_tokens,
        "seed": args.seed,
        # unhealthy-shed requests come back FINISHED but count as shed, not
        # completed — the headline counters keep the ServingMetrics partition
        "completed": len(healthy),
        "shed": len(rejected) + (len(finished) - len(healthy)),
        "shed_rate": round((len(rejected) + len(finished) - len(healthy))
                           / max(args.num_requests, 1), 4),
        "shed_reasons": dict(
            {r.reject_reason: sum(
                1 for x in rejected if x.reject_reason == r.reject_reason)
             for r in rejected},
            **({"unhealthy_slot": len(finished) - len(healthy)}
               if len(finished) > len(healthy) else {})),
        "total_tokens": total_tokens,
        "tokens_per_s": round(total_tokens / wall_s, 2) if wall_s else None,
        "wall_s": round(wall_s, 3),
        "ttft_ms": {"p50": pct(ttfts, 50), "p99": pct(ttfts, 99)},
        "tpot_ms": {"p50": pct(tpots, 50), "p99": pct(tpots, 99)},
        "replicas": len(replicas),
        "compile_counts": replicas[0].compile_counts(),
        # the router block: per-replica routing/occupancy, affinity hit
        # rates, rebalances and drain counts — how the fleet actually
        # balanced, next to the throughput it earned
        "router": router_snap["router"],
        # the disaggregated-topology block: pool roles, per-pool routed
        # counts / occupancy / TTFT split, and the first-token handoff +
        # live-rebalance counters (mirrors Serving/handoffs|rebalances)
        "topology": dict(
            router_snap["router"]["pools"],
            roles=router_snap["router"]["roles"],
            handoffs=router_snap["router"]["handoffs"],
            rebalances=router_snap["router"]["pool_rebalances"]),
        # streaming-digest percentiles (fleet-merged, EXACT across replica
        # count), the SLO grade against the --slo-* targets, and the
        # goodput accounting (useful vs replay/padding device tokens) —
        # the same numbers the Serving/*_p99_ms / goodput_frac events and
        # tools/fleet_report.py carry
        "percentiles": router_snap["percentiles"],
        "slo": router_snap["slo"],
        "goodput": router_snap["goodput"],
        # multi-tenant QoS rollup (always present): fleet-merged per-tenant
        # submitted/finished/shed/tokens + TTFT/TPOT digests and the
        # per-tenant SLO grade (class ttft targets from --tenants slo=),
        # plus the autoscaler's scale-event timeline and replica-step
        # economics ({"enabled": false} when --autoscale is off)
        "tenancy": router_snap["tenancy"],
        "autoscaler": router_snap["autoscaler"],
        # the resilience block: live-migration / failover economics next to
        # the throughput they protected — snapshots taken, streams migrated,
        # cross-replica failovers and retries, terminal replica_failed
        # sheds, and the replay tokens burned re-computing work a dead
        # replica had already committed (zero when every failover spliced a
        # fresh snapshot)
        "resilience": dict(
            router_snap["router"]["migration"],
            replay_tokens=router_snap["goodput"]["replay_tokens"],
            chaos={"kills": args.chaos_kills, "stalls": args.chaos_stalls,
                   "seed": args.chaos_seed,
                   "schedule": chaos_events} if chaos_events else None),
        "speculative": speculative,
        # numerics self-incrimination next to the run stamp: a throughput
        # number earned while slots were shedding non-finite logits (or
        # steps were silently unhealthy) carries its own evidence —
        # aggregated over the fleet
        "numerics": agg_health,
        "n_params_m": round(n_params / 1e6, 1),
    }
    if len(replicas) > 1:
        artifact["compile_counts_per_replica"] = router.compile_counts()
    # pool accounting next to the run stamp / numerics blocks: a tokens/s
    # number means something different at 30% vs 95% block occupancy, and
    # the shed histogram says WHY work was turned away (replica 0's pool;
    # per-replica occupancy lives in the router block)
    artifact["kv_pool"] = dict(
        metrics_snap["kv_pool"],
        kv_dtype=args.kv_dtype or "engine",
        shed_reasons=agg_shed)
    from _common import stamp_record

    stamp_record(artifact, config={
        "family": args.family, "size": size, "mode": mode, "qps": args.qps,
        "num_requests": args.num_requests, "slots": args.slots,
        "queue_depth": args.queue_depth, "prompts": prompts,
        "new_tokens": args.new_tokens, "seed": args.seed,
        "kv_block_size": args.kv_block_size,
        "kv_blocks": args.kv_blocks, "kv_dtype": args.kv_dtype,
        # the decode attention that ACTUALLY ran — must agree with the
        # kv_pool block's field
        "attention_backend": replicas[0].attn_backend,
        "shared_prefix": args.shared_prefix, "replicas": len(replicas),
        "chunk_size": args.chunk_size,
        "session_affinity": bool(args.session_affinity),
        "kv_growth": bool(args.kv_growth),
        "spec_draft": args.spec_draft, "spec_k": args.spec_k,
        "prefill_replicas": args.prefill_replicas,
        "decode_replicas": args.decode_replicas,
        "rebalance": bool(args.rebalance),
        "slo_ttft_p99_ms": args.slo_ttft_p99_ms,
        "slo_tpot_p99_ms": args.slo_tpot_p99_ms,
        "tenants": args.tenants, "autoscale": bool(args.autoscale),
        "chaos_kills": args.chaos_kills, "chaos_stalls": args.chaos_stalls,
        "chaos_seed": args.chaos_seed,
        "chaos_snapshot_interval": args.chaos_snapshot_interval})
    print(json.dumps(artifact), flush=True)
    if args.output:
        with open(args.output, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"artifact written to {args.output}", flush=True)
    router.destroy()
    engine.destroy()
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--family", default="gpt2")
    ap.add_argument("--sizes", default="small,medium")
    ap.add_argument("--prompts", default="128,512,1000")
    ap.add_argument("--modes", default="bf16,int8,int4")
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--repeats", type=int, default=10)
    ap.add_argument("--qps", type=float, default=None,
                    help="open-loop offered-load mode: Poisson arrival rate "
                         "through the continuous-batching serving engine")
    ap.add_argument("--num-requests", type=int, default=64)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--queue-depth", type=int, default=8)
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="open-loop mode: tokens per block of the KV pool "
                         "(serving.kv_pool); the artifact's kv_pool block "
                         "carries occupancy, fragmentation, "
                         "prefix_hit_rate and the shed histogram")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="0 = auto (slots x max_len tokens)")
    ap.add_argument("--kv-dtype", default="", choices=["", "int8"])
    ap.add_argument("--attention-interpret", action="store_true",
                    help="off a TPU only: run the flash-decode kernel under "
                         "the Pallas interpreter. On a TPU the engine "
                         "chooses the path; the artifact's kv_pool block "
                         "records which one produced the numbers, and why "
                         "where it is the view")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="open every prompt with this many IDENTICAL "
                         "system-prompt tokens (exercises the prefix cache)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="open-loop mode over N ServingEngine replicas "
                         "behind the load-aware Router (serving/router.py); "
                         "the artifact gains a router block (per-replica "
                         "occupancy, affinity hit rate, rebalances, drains)")
    ap.add_argument("--chunk-size", type=int, default=0,
                    help="chunked prefill: split prompt prefill into chunks "
                         "of this many tokens interleaved with decode steps "
                         "(0 = off) — bounds co-batched TPOT under long "
                         "prompts")
    ap.add_argument("--session-affinity", action="store_true",
                    help="tag requests with a small pool of session ids so "
                         "the router's sticky-session map is exercised")
    ap.add_argument("--kv-growth", action="store_true",
                    help="the pool reserves prompt blocks only and grows "
                         "decode blocks on demand (preempt-to-queue on "
                         "exhaustion)")
    ap.add_argument("--spec-draft", default="", choices=["", "ngram", "model"],
                    help="speculative decoding: drafter "
                         "proposing up to --spec-k tokens per greedy slot, "
                         "verified in ONE target forward; the artifact "
                         "gains a speculative block (accept_rate, "
                         "accepted_tokens_per_step, drafts, rollbacks)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens per verify step")
    ap.add_argument("--prefill-replicas", type=int, default=0,
                    help="disaggregated fleet: dedicate "
                         "this many replicas to PREFILL; at first token the "
                         "stream's KV hands off to the decode pool via a "
                         "fresh snapshot splice (zero recompute). Overrides "
                         "--replicas to prefill+decode; the artifact gains "
                         "a topology block (per-pool routed/occupancy, "
                         "handoffs, rebalances, TTFT split by pool)")
    ap.add_argument("--decode-replicas", type=int, default=0,
                    help="disaggregated fleet: dedicate this many replicas "
                         "to DECODE (receives first-token handoffs)")
    ap.add_argument("--rebalance", action="store_true",
                    help="live rebalancing (serving.rebalance): migrate "
                         "long-tail decode streams off hot replicas mid-"
                         "flight, with hysteresis (min_gain + cooldown) so "
                         "the fleet never thrashes")
    ap.add_argument("--slo-ttft-p99-ms", type=float, default=0.0,
                    help="open-loop mode: serving.slo TTFT P99 target (ms; "
                         "0 = no objective) — the artifact's slo block "
                         "grades the fleet digests against it")
    ap.add_argument("--slo-tpot-p99-ms", type=float, default=0.0,
                    help="open-loop mode: serving.slo TPOT P99 target (ms)")
    ap.add_argument("--tenants", default="",
                    help="open-loop mode: multi-tenant class mix, e.g. "
                         "'interactive:0.3:slo=300,batch:0.7' — requests "
                         "draw a class by the (normalised) fractions, "
                         "admission switches to weighted-fair (serving."
                         "tenants), and slo= sets that class's per-tenant "
                         "TTFT P99 target; the artifact's tenancy block "
                         "carries per-tenant counters, digests and grades")
    ap.add_argument("--autoscale", action="store_true",
                    help="open-loop mode: arm serving.autoscaler — parks "
                         "the fleet to the min-replica floor, scales up on "
                         "sustained SLO burn / queue depth, drains back on "
                         "idle; the artifact's autoscaler block records the "
                         "scale-event timeline and replica-step economics")
    ap.add_argument("--chaos-kills", type=int, default=0,
                    help="open-loop mode: kill this many "
                         "replicas at seeded instants during the offered-"
                         "load window (testing.ReplicaChaosSchedule); arms "
                         "live KV migration so failovers splice snapshots "
                         "instead of replaying streams, and the artifact "
                         "gains a resilience block (migrations, failovers, "
                         "retries, replay tokens)")
    ap.add_argument("--chaos-stalls", type=int, default=0,
                    help="stall this many replicas (transient degraded "
                         "health) at seeded instants")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="seed for the replica chaos schedule (independent "
                         "of --seed so the workload stays fixed across "
                         "chaos variations)")
    ap.add_argument("--chaos-snapshot-interval", type=int, default=4,
                    help="serving.migration.snapshot_interval_tokens under "
                         "--chaos-kills — the failover replay bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--output", default=None,
                    help="write the open-loop JSON artifact here")
    args = ap.parse_args()

    from _common import peak_hbm_gbs, setup_compile_cache

    setup_compile_cache()
    import jax

    if args.qps is not None:
        return run_open_loop(args)

    platform = jax.devices()[0].platform
    peak_bw = peak_hbm_gbs(jax.devices()[0].device_kind)
    prompts = [int(p) for p in args.prompts.split(",")]
    # +1: the decode-compile warmup generates 1 + new_tokens tokens
    max_tokens = ((max(prompts) + args.new_tokens + 1 + 63) // 64) * 64

    rng = np.random.RandomState(0)
    variants = [(size, mode, {}, mode)
                for size in args.sizes.split(",")
                for mode in args.modes.split(",")]
    # prefill_flash crossover: on TPU, one extra pass of
    # the first size in bf16 with the flash prefill forced OFF — the TTFT
    # delta per prompt bucket IS the crossover table for the serving path.
    # Skipped for alibi families (bloom): decoding.py never takes the flash
    # prefill there, so on/off would compare dense vs dense at real chip cost.
    if platform == "tpu" and "bf16" in args.modes.split(","):
        from deepspeed_tpu.models.registry import get_model as _gm

        size0 = args.sizes.split(",")[0]
        cfg0 = _gm(args.family, size0, max_seq_len=64).config
        if cfg0.position_embedding != "alibi":
            variants.append((size0, "bf16", {"prefill_flash": False},
                             "bf16-prefill_flash=off"))

    rows = []
    for size, mode, model_kw, label in variants:
        engine, n_params, weight_bytes = build_engine(
            args.family, size, mode, max_tokens, **model_kw)
        try:
            for p in prompts:
                ttft50, ttft95, dec = bench_one(
                    engine, p, args.new_tokens, args.batch, args.repeats, rng)
                # decode-bandwidth roofline: weight-only decode at small
                # batch reads every resident weight byte per step, so
                # achieved GB/s = weight_bytes x (decode steps/s)
                decode_steps_s = dec / args.batch
                gbs = weight_bytes * decode_steps_s / 1e9
                row = {
                    "model": f"{args.family}-{size}", "mode": label,
                    "prompt_len": p, "batch": args.batch,
                    "new_tokens": args.new_tokens,
                    "ttft_p50_ms": round(ttft50, 2),
                    "ttft_p95_ms": round(ttft95, 2),
                    "decode_tok_s": round(dec, 1),
                    "weight_gb": round(weight_bytes / 1e9, 3),
                    "achieved_gbs": round(gbs, 1),
                    "hbm_util": round(gbs / peak_bw, 3),
                    "n_params_m": round(n_params / 1e6, 1),
                    "platform": platform,
                }
                rows.append(row)
                print(json.dumps(row), flush=True)
        finally:
            # del alone leaves engine<->jit-closure cycles holding every
            # device buffer; destroy() is what actually frees HBM for the
            # next variant
            engine.destroy()
            del engine

    print(f"\n| model | mode | prompt | ttft p50 (ms) | ttft p95 (ms) "
          f"| decode tok/s | GB/s | %HBM peak ({peak_bw:.0f}) |")
    print("|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['model']} | {r['mode']} | {r['prompt_len']} "
              f"| {r['ttft_p50_ms']} | {r['ttft_p95_ms']} | {r['decode_tok_s']} "
              f"| {r['achieved_gbs']} | {100 * r['hbm_util']:.0f}% |")

    # BLOOM-7B1 v5e-8 TTFT projection: the BASELINE.md bar (~55 ms p50,
    # init_inference TP=8) restated from what one chip measures: decode HBM
    # utilization (bloom bf16 rows above) + an ICI collective model + an MFU
    # prior. v5e only: the model's constants are that chip's.
    if (args.family == "bloom"
            and jax.devices()[0].device_kind in ("TPU v5 lite", "TPU v5e")):
        bloom_bf16 = [r for r in rows if r["mode"] == "bf16"]
        if bloom_bf16:
            hbm_util = max(r["hbm_util"] for r in bloom_bf16)
            project_bloom_7b1(hbm_util, peak_bw)
    return 0


if __name__ == "__main__":
    sys.exit(main())
