"""Runner of the ``latent_serve_loop`` traffic kind: ``serve_loop``'s closed
or open loop over ``ServingEngine.submit()`` / ``step()`` for a model with a
latent cache and routed experts, served with chunked prefill.

What differs from ``serve_loop`` (whose ``Load``, bucket rule and timing it
imports):

- warm-up goes through every CHUNK program the traffic reaches (the full
  chunk and each padded last part-chunk), not every whole-prompt bucket;
- the reference check is ``reference/latent_moe_decoder.py`` in two
  comparisons, because with random weights a token's sixth and seventh
  router scores are often closer than bf16 activations resolve, one flipped
  choice moves that token's residual, and attention spreads it to every
  later token: (a) the reference is run with the expert choices the serving
  programs made FORCED, and every served token must be its maximum or
  within a few bf16 steps of it (``tie_bf16_steps``); (b) in that run the reference also makes its
  own choice at every expert layer and token, and where it differs from the
  served one its own margin in ``s + b`` between the two must be under the
  configuration's limit, with the share of differing choices capped; and
  the weights the served path gave its experts must agree with the weights
  the reference gives the same experts (a path that weighs by ``s + b``, or
  leaves the scaling out, moves no token at random weights' scale but moves
  every weight);
- a step is booked as prefill by the prompt tokens it really prefilled (the
  delta of ``goodput.prefill_device_tokens`` less padding): under chunked
  prefill the step in which a request's FIRST token came is only the last
  of its chunk-bearing steps;
- ``obs`` carries what the latent readers need: the routing counters'
  deltas, per traced step the live cache rows, the experts hit and the
  chunks dispatched (the engine sends a step's chunk behind the decode
  before it, so the device is let run out before the trace starts and
  before it stops: the trace then holds the programs of the traced steps,
  whole), and the device time of the named regions
  (``trace_reduce_latent``).
"""

import functools
import shutil
import tempfile
import time

import numpy as np

from . import harness, trace_reduce, trace_reduce_latent, traffic_gen
from .reference import latent_moe_decoder
from .serve_loop import ITEMSIZE, Load, prompt_buckets


def seed_selection_bias(params, seed, std):
    """The published model trains its selection bias ``b``
    (``e_score_correction_bias``); the program makes it zero, as the
    published init does, and with a zero ``b`` a path that ignores it, or
    weighs by ``s + b``, would pass. So the benchmark draws it from the seed
    at ``std``, about the spacing of a token's top scores: it changes the
    chosen set for a visible share of tokens. In place."""
    import jax

    router = params["blocks"]["mlp"]["router"]
    b = np.random.default_rng([seed, 6]).normal(0.0, std,
                                                router["bias"].shape)
    router["bias"] = jax.device_put(b.astype(router["bias"].dtype),
                                    router["bias"].sharding)


def compare_with_reference(params, arch, seq, first, tokens, served_ids,
                           served_weights, limits):
    """One forced reference forward over ``seq``; ``tokens[j]`` is the
    token the served path chose after position ``first + j``, ``served_ids``
    and ``served_weights`` [L_moe, len(seq), k] the experts it chose and the
    weights it gave them. Returns sums and counts: (a) tokens checked, off
    the maximum but tied, wrong; (b) expert choices that differ from the
    reference's own, the largest margin, those over the limit, and the
    squared relative error of the weights."""
    tokens = np.asarray(tokens, np.int32)
    logits, routing = latent_moe_decoder.logits_at(
        params, seq, arch, first, len(tokens), forced=served_ids,
        return_routing=True)
    logits = np.asarray(logits)
    top = logits.max(-1)
    step = 2.0 ** (np.floor(np.log2(np.abs(top))) - 7)   # of bf16 at the top
    tol = limits["tie_bf16_steps"] * step
    gap = top - logits[np.arange(len(tokens)), tokens]
    margins = latent_moe_decoder.routing_margins(routing, served_ids,
                                                 len(seq))
    w_err = latent_moe_decoder.weight_errors(routing, served_weights,
                                             len(seq))
    return {"tokens": len(tokens),
            "ties": int(((gap > 0) & (gap <= tol)).sum()),
            "wrong": int((gap > tol).sum()),
            "worst_gap_in_bf16_steps": float((gap / step).max()),
            "choices": int(margins.size), "differ": int((margins > 0).sum()),
            "max_margin": float(margins.max()),
            "over_margin": int((margins > limits["route_margin_limit"]).sum()),
            "weights": int(w_err.size),
            "weight_sq_error": float((w_err.astype(np.float64) ** 2).sum()),
            "max_weight_error": float(w_err.max())}


def passes(total, limits):
    """The comparisons' verdicts from summed ``compare_with_reference``
    results: (a) no served token off the forced reference's maximum beyond
    the tie rule; (b) no differing expert choice over the margin limit, the
    share of differing choices under its cap, and the root mean square
    relative error of the pair weights under its limit. Also returns the
    two statistics."""
    if not total:
        return {"served_tokens_match_forced_reference": False,
                "expert_choices_within_margin": False,
                "expert_weights_match": False}, {}
    stats = {"differ_share": total["differ"] / total["choices"],
             "weight_rms_error": (total["weight_sq_error"]
                                  / total["weights"]) ** 0.5}
    return {"served_tokens_match_forced_reference": total["wrong"] == 0,
            "expert_choices_within_margin":
            total["over_margin"] == 0 and stats["differ_share"]
            <= limits["route_differ_share_limit"],
            "expert_weights_match": stats["weight_rms_error"]
            <= limits["route_weight_rms_limit"]}, stats


def check_against_reference(params, arch, rec, limits):
    """A served request against the reference: the sequence is the prompt
    and every generated token that was fed back."""
    tokens = np.asarray(rec["tokens"], np.int32)
    req = rec["req"]
    return compare_with_reference(
        params, arch, np.concatenate([rec["prompt"], tokens[:-1]]),
        rec["prompt_len"] - 1, tokens, req.expert_ids(),
        req.expert_weights(), limits)


def run(cell, config, traffic, manifest, args, devices, peaks, cache_log):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, RequestState

    arch, inf = config["arch"], config["init_inference"]
    serving = inf["serving"]
    model = harness.build_model(config)
    engine = deepspeed_tpu.init_inference(model, seed=args.seed, **inf)
    seed_selection_bias(engine.params, args.seed,
                        config["selection_bias_std"])
    jax.block_until_ready(engine.params)
    sv = engine.serving
    max_len = serving["max_len"]
    chunk = serving["chunked_prefill"]["chunk_size"]
    harness.note("engine", attn_backend=sv.attn_backend,
                 n_slots=serving["n_slots"], max_len=max_len, chunk=chunk,
                 kv_pool=serving["kv_pool"],
                 pool_layout=sv.pool_layouts(),
                 setup_so_far_s=harness.process_age_s())

    # warm exactly the cell's shapes: every prompt is longer than a chunk,
    # so it runs full chunks and one last part-chunk, padded by the bucket
    # rule; one request through each part-chunk bucket reaches them all
    lo, _ = traffic["prompt_len"]["clip"]
    if lo <= chunk:
        raise SystemExit("benchmark: latent_serve_loop warms chunk programs "
                         "only; every prompt must be longer than a chunk")
    buckets = prompt_buckets(1, chunk, inf["prompt_bucket_size"],
                             inf["prompt_bucket_policy"], max_len)
    rng = np.random.default_rng([args.seed, 5])
    warm = [sv.submit(Request(prompt=rng.integers(
        0, arch["vocab_size"], chunk + n, dtype=np.int32), max_new_tokens=3))
        for n in buckets.values()]
    while any(r.state not in (RequestState.FINISHED, RequestState.REJECTED)
              for r in warm):
        sv.step()
    harness.note("warm", chunk_buckets=sorted(buckets),
                 compile_counts=sv.compile_counts(),
                 setup_so_far_s=harness.process_age_s())

    schedule = traffic_gen.serve_requests(
        traffic, args.seed, traffic["schedule_requests"], arch["vocab_size"])
    # requests sent during the ramp record their expert choices, for the
    # reference check; those of the window do not
    load = Load(sv, schedule, traffic["arrivals"],
                functools.partial(Request, record_routing=True),
                RequestState.REJECTED)
    # the trace is kept until the latent reduction has read it
    trace_dir = args.trace_dir or (
        tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None)
    traced = harness.TracedSlice(args.trace, args.seconds,
                                 traffic["trace_slice_s"], trace_dir)
    m = sv.metrics
    steps = []      # (seconds, prompt tokens prefilled, decoded, traced)
    traced_steps = []   # per traced step: live rows, experts hit, chunks
    freed_at = []

    def one_step():
        with harness.span("submit"):
            load.submit_due(freed_at)
        before = (m.prefill_device_tokens - m.padding_tokens,
                  m.moe_decode_experts_hit, m.moe_decode_pairs,
                  m.latent_kv_tokens_read, m.decode_dispatches,
                  m.prefill_chunks)
        t_before = load.now()
        with harness.span("step"):
            events = sv.step()
        now = load.now()
        with harness.span("read_tokens"):
            load.take(events, now, freed_at)
        if not events:
            load.wait_for_work()
        prefilled = m.prefill_device_tokens - m.padding_tokens - before[0]
        detail = {"live": m.latent_kv_tokens_read - before[3],
                  "experts_hit": m.moe_decode_experts_hit - before[1],
                  "pairs": m.moe_decode_pairs - before[2],
                  "decoded": m.decode_dispatches - before[4],
                  # the chunks DISPATCHED in this step (the engine
                  # dispatches a step's chunk behind the decode before it)
                  "chunks": list(m.recent_prefill_chunks)[
                      len(m.recent_prefill_chunks)
                      - (m.prefill_chunks - before[5]):]}
        return now, now - t_before, prefilled, detail

    # ramp: the load starts before the window and is not timed
    ramp = traffic["ramp"]
    while True:
        now, _, _, _ = one_step()
        finished = [r for r in load.records
                    if r["done"] is not None and not r["refused"]]
        if len(finished) >= ramp.get("finished_requests", 0) \
                and now - load.t_start >= ramp.get("seconds", 0.0):
            break
    harness.note("ramp", seconds=now - load.t_start, finished=len(finished),
                 submitted=len(load.records))

    # correctness, outside the window, with the load paused
    t_pause = time.perf_counter()
    limits = config["checks"]
    checked = finished[:limits["reference_requests"]]
    total = {}
    for rec in checked:
        one = check_against_reference(engine.params, arch, rec, limits)
        for k, v in one.items():
            total[k] = max(total.get(k, 0), v) if k.startswith(
                ("max_", "worst_")) else total.get(k, 0) + v
    checks, stats = passes(total, limits)
    harness.note("reference", requests=len(checked),
                 prompt_lens=[r["prompt_len"] for r in checked],
                 **stats, **total,
                 seconds=time.perf_counter() - t_pause)
    for rec in load.records:
        rec["req"].routing = []     # the record has served; free it
        rec["req"].record_routing = False
    load.request_cls = Request
    load.paused += time.perf_counter() - t_pause

    counts0, snap0, mark = sv.compile_counts(), sv.metrics.snapshot(), \
        cache_log.mark()
    setup_s = harness.process_age_s()
    t0 = load.now()
    while True:
        now, dt, prefilled, detail = one_step()
        on = traced.running
        steps.append((dt, prefilled, detail["decoded"], on))
        if on:
            traced_steps.append(detail)
        if now - t0 >= args.seconds:
            break
        if args.trace and not on and now - t0 >= traced.start_after:
            # the chunk dispatched ahead of the next step runs out first:
            # the trace holds whole programs, those the traced steps sent
            sv.block_until_idle()
        traced.maybe_start(now - t0)
    t_end = now
    snap1 = sv.metrics.snapshot()
    load.accepting = False
    for _ in range(traffic["drain_steps"]):
        _, _, _, detail = one_step()
        if traced.running:
            traced_steps.append(detail)
    if traced.running:
        sv.block_until_idle()
    traced.stop()
    regions = None
    if args.trace and trace_dir:
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            # the decode program's own text says which of its instructions
            # lie under which named scope (after the window: a compile-cache
            # hit, in no metric)
            text = sv.trace_decode()[0].compile().as_text()
            regions = trace_reduce_latent.reduce(
                trace_reduce_latent.load(path),
                {"jit_decode": trace_reduce_latent.scopes_in(text)})
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    compiled = cache_log.since(mark)
    counts1 = sv.compile_counts()

    window_s = t_end - t0
    in_window = [r for r in load.records if t0 <= r["submitted"] < t_end]
    done_in_window = [r for r in load.records
                      if r["done"] is not None and t0 <= r["done"] <= t_end]
    drain_end = load.now()
    ttft = [((r["times"][0] if r["times"] else drain_end) - r["due"]) * 1e3
            for r in in_window]
    no_first_token = sum(not r["times"] for r in in_window)
    gaps, tokens = [], 0
    for r in load.records:
        ts = r["times"]
        tokens += sum(t0 <= t <= t_end for t in ts)
        gaps += [(b - a) * 1e3 for a, b in zip(ts, ts[1:]) if t0 <= b <= t_end]
    refused = sum(r["refused"] for r in done_in_window)
    # the guarantee: every admitted request is answered in full, in order
    short = sum(not r["refused"] and (
        len(r["tokens"]) != r["max_new_tokens"]
        or r["tokens"] != [int(t) for t in r["req"].tokens])
        for r in done_in_window)
    checks.update(
        no_compile_in_window=not compiled and counts0 == counts1,
        answers_complete_and_in_order=short == 0,
        requests_timed=len(ttft) >= 10 and len(gaps) >= 20)
    harness.note(
        "window", window_s=window_s, steps=len(steps), tokens=tokens,
        # every run does the same work step for step, so a window that holds
        # fewer steps lost time somewhere: the largest steps say whether to
        # one host stall (the chunk staircase ends under 200 ms)
        step_ms_p50=harness.quantile([s[0] * 1e3 for s in steps], 50),
        step_ms_top3=sorted(s[0] * 1e3 for s in steps)[-3:],
        submitted=len(in_window), finished_or_refused=len(done_in_window),
        refused=refused, incomplete=short, without_first_token=no_first_token,
        gaps=len(gaps), in_flight_at_end=len(load.inflight),
        # first-token times are notes in a closed loop above capacity: they
        # are the queue's, and those of requests sent late in the window are
        # cut at the drain's end (``without_first_token``); so is the rate
        # wherever the manifest does not list it for the cell (one stalled
        # step of the host moves it, and not the median gap)
        tokens_per_s=tokens / window_s,
        ttft_ms_p50=harness.quantile(ttft, 50) if ttft else None,
        ttft_ms_p90=harness.quantile(ttft, 90) if ttft else None,
        itl_ms_p95=harness.quantile(gaps, 95) if gaps else None,
        completions_per_s=len(done_in_window) / window_s,
        generator_late_ms_p50=harness.quantile(load.lateness, 50) * 1e3,
        generator_late_ms_max=max(load.lateness) * 1e3,
        compiled_in_window=compiled, compile_counts=counts1,
        shed=snap1["shed"], preempted=snap1["preempted"],
        kv_pool=snap1.get("kv_pool"), moe=snap1.get("moe"),
        regions=regions)
    harness.note("checks", **checks)
    end_to_end = {
        "serve_tokens_per_s": tokens / window_s,
        "ttft_p50_ms": harness.quantile(ttft, 50) if ttft else float("nan"),
        "itl_p50_ms": harness.quantile(gaps, 50) if gaps else float("nan"),
        "setup_s": setup_s}
    delta = lambda group, key: snap1[group][key] - snap0[group][key]
    obs = {
        "samples": {
            "ttft_ms": ttft, "itl_ms": gaps,
            "decode_only_step_ms": [dt * 1e3 for dt, p, d, _ in steps
                                    if d and not p],
            "prefill_steps": [(dt * 1e3, p) for dt, p, _, _ in steps if p],
            "traced_steps": traced_steps},
        "counters": {
            "decode_tokens": delta("goodput", "decode_tokens"),
            "decode_dispatches": delta("speculative", "decode_dispatches"),
            "prefill_device_tokens": delta("goodput", "prefill_device_tokens"),
            "padding_tokens": delta("goodput", "padding_tokens"),
            "n_slots": serving["n_slots"],
            **({k: delta("moe", k) for k in (
                "dispatches", "moe_pairs", "moe_experts_hit",
                "max_expert_load_sum", "latent_kv_tokens_read",
                "prefill_chunks", "prefill_chunk_tokens")}
               if "moe" in snap1 else {})},
        "trace": traced.reduced, "regions": regions, "arch": arch,
        "work": {"chips": len(devices),
                 "kv_itemsize": ITEMSIZE[inf["dtype"]],
                 "weight_itemsize": ITEMSIZE[inf["dtype"]]},
        "peaks": peaks}
    result = harness.result_line(
        manifest, cell, args, correct=all(checks.values()),
        attempted=len(done_in_window), failed=refused + short,
        end_to_end=end_to_end, obs=obs, devices=devices, traced=traced)
    engine.destroy()
    return result
