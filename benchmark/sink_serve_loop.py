"""Runner of the ``sink_serve_loop`` traffic kind: ``window_serve_loop``'s
closed or open loop over ``ServingEngine.submit()`` / ``step()`` for a model of
sink-window and full attention layers whose expert layers hold a SHARE of
their experts (MiMo-V2), served with chunked prefill over a paged pool in two
block groups of different row widths.

It imports what the three other runners export (``serve_loop``'s ``Load``,
bucket rule and item sizes; ``latent_serve_loop``'s selection bias and
verdicts; ``window_serve_loop``'s summing and picking of the checked
requests) and differs from ``window_serve_loop`` in this (ROADMAP D15 merges
the four behind hooks):

- the reference is ``reference/sink_window_moe_decoder.py``, given the same
  share of the experts, run with the served expert choices forced;
- the sinks, like the selection bias, are trained in the published model and
  zero in the program: the runner draws them from ``--seed`` (``seed_sinks``)
  so that a sink takes a visible share of a full band's softmax mass, and a
  path that ignores it is seen;
- the regions of the device trace are this model's scopes
  (``sink_window_attn_decode``, ``sink_window_chunk_attn``);
- ``obs`` also carries the pairs the routers chose beside the pairs held
  (``moe_pairs_chosen`` / ``moe_pairs_held``); ``experts_hit`` and ``pairs``
  count the held experts only, as the program's counters do;
- the engine sends a decode-only step's NEXT decode behind its own, so the
  traced steps are entered by the decode programs they DISPATCHED
  (``traced_entries``), as chunks always were.
"""

import functools
import shutil
import statistics
import tempfile
import time

import numpy as np

from . import harness, trace_reduce, trace_reduce_latent, traffic_gen
from .latent_serve_loop import passes, seed_selection_bias
from .reference import sink_window_moe_decoder
from .serve_loop import ITEMSIZE, Load, prompt_buckets
from .window_serve_loop import pick_checked, sum_checks

# regions of the device trace: jax.named_scope names of the program
REGIONS = {
    "experts": ("moe_grouped_matmul",),
    "sink_window_attention": ("sink_window_attn_decode",),
    "full_attention": ("full_attn_decode",),
    "row_write": ("paged_row_write",),
    "sink_window_chunk_attention": ("sink_window_chunk_attn",),
    "full_chunk_attention": ("full_chunk_attn",),
}
# ``ragged_dot`` loses its scope on the TPU (``trace_reduce_latent``)
NAMED = {"experts": ("ragged-dot",)}


def scopes_in(compiled_text):
    """``{instruction name: region}`` of one compiled program, as
    ``trace_reduce_latent.scopes_in`` with this cell's regions."""
    out = {}
    for name, rest in trace_reduce_latent.INSTRUCTION.findall(compiled_text):
        found = trace_reduce_latent.OP_NAME.search(rest)
        op_name = found.group(1) if found else ""
        for region, scopes in REGIONS.items():
            if any(s in op_name for s in scopes) or any(
                    name.startswith(n) for n in NAMED.get(region, ())):
                out[name] = region
    return out


def traced_entries(detail):
    """What one traced step adds to ``obs``'s ``traced_steps``: an entry a
    DECODE PROGRAM it dispatched, since those are what the trace holds
    (``jit_decode`` runs) and the readers count their entries against them.
    A step dispatches one decode as a rule: its own, or, where its own was
    sent behind the step before (``ServingEngine._dispatch_decode_ahead``),
    the next step's. At the edges of a run of decode-only steps it sends two
    (its own and the next one's) or none (it read one sent earlier and a
    chunk goes ahead instead): then the step's rows and experts stand for
    each program it sent, or for none; its chunks are counted once."""
    sent = detail["programs"]
    if sent == 0:
        return [dict(detail, decoded=0)]
    return [dict(detail, decoded=1, chunks=detail["chunks"] if i == 0 else [])
            for i in range(sent)]


def seed_sinks(params, seed, mean, std):
    """The published model trains a sink a query head in its window layers;
    the program makes them zero, and a zero sink against a band whose
    scores' exponentials sum to hundreds is a share of the softmax no
    comparison would see. So the benchmark draws them from the seed, normal
    at ``mean`` with standard deviation ``std`` (the configuration file's
    ``sink_mean`` / ``sink_std``: between a tenth and a half of a full
    band's mass at the published widths). In place."""
    import jax

    kv = params["kv_window"]
    b = np.random.default_rng([seed, 7]).normal(mean, std, kv["sink"].shape)
    kv["sink"] = jax.device_put(b.astype(kv["sink"].dtype),
                                kv["sink"].sharding)


def compare_with_reference(params, arch, seq, first, tokens, served_ids,
                           served_weights, limits):
    """``latent_serve_loop.compare_with_reference`` against this cell's
    reference: one forced forward over ``seq``; ``tokens[j]`` is the token
    the served path chose after position ``first + j``."""
    tokens = np.asarray(tokens, np.int32)
    logits, routing = sink_window_moe_decoder.logits_at(
        params, seq, arch, first, len(tokens), forced=served_ids,
        return_routing=True)
    logits = np.asarray(logits)
    top = logits.max(-1)
    step = 2.0 ** (np.floor(np.log2(np.abs(top))) - 7)   # of bf16 at the top
    tol = limits["tie_bf16_steps"] * step
    gap = top - logits[np.arange(len(tokens)), tokens]
    margins = sink_window_moe_decoder.routing_margins(routing, served_ids,
                                                 len(seq))
    w_err = sink_window_moe_decoder.weight_errors(routing, served_weights,
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
    seed_sinks(engine.params, args.seed, config["sink_mean"],
               config["sink_std"])
    jax.block_until_ready(engine.params)
    sv = engine.serving
    max_len = serving["max_len"]
    chunk = serving["chunked_prefill"]["chunk_size"]
    harness.note("engine", attn_backend=sv.attn_backend,
                 attn_reason=sv.attn_reason,
                 n_slots=serving["n_slots"], max_len=max_len, chunk=chunk,
                 kv_pool=sv.metrics.snapshot()["kv_pool"],
                 pool_layout=sv.pool_layouts(),
                 setup_so_far_s=harness.process_age_s())

    # warm exactly the cell's shapes: every prompt is longer than a chunk,
    # so it runs full chunks and one last part-chunk, padded by the bucket
    # rule; one request through each part-chunk bucket reaches them all
    shortest = int(traffic_gen.lognormal_quantiles(
        traffic["prompt_len"], int(traffic["round_size"])).min())
    if shortest <= chunk:
        raise SystemExit("benchmark: sink_serve_loop warms chunk programs "
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
    # the trace is kept until the regions have been read from it
    trace_dir = args.trace_dir or (
        tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None)
    traced = harness.TracedSlice(args.trace, args.seconds,
                                 traffic["trace_slice_s"], trace_dir)
    m = sv.metrics
    steps = []      # (seconds, prompt tokens prefilled, decoded, traced,
    #                  chunks dispatched, slots that decoded)
    traced_steps = []   # per traced step: rows read, experts hit, chunks
    freed_at = []

    def one_step():
        with harness.span("submit"):
            load.submit_due(freed_at)
        before = (m.prefill_device_tokens - m.padding_tokens,
                  m.moe_decode_experts_hit, m.moe_decode_pairs,
                  m.latent_kv_tokens_read, m.kv_window_rows_read,
                  m.decode_dispatches, m.prefill_chunks, m.decode_tokens)
        # the share's counters: absent from a program that knows no share
        chosen_before = getattr(m, "moe_pairs_chosen", None)
        programs_before = getattr(m, "decode_programs", None)
        t_before = load.now()
        with harness.span("step"):
            events = sv.step()
        now = load.now()
        with harness.span("read_tokens"):
            load.take(events, now, freed_at)
        if not events:
            load.wait_for_work()
        prefilled = m.prefill_device_tokens - m.padding_tokens - before[0]
        n_chunks = m.prefill_chunks - before[6]
        detail = {"full_rows": m.latent_kv_tokens_read - before[3],
                  "window_rows": m.kv_window_rows_read - before[4],
                  "experts_hit": m.moe_decode_experts_hit - before[1],
                  "pairs": m.moe_decode_pairs - before[2],
                  "decoded": m.decode_dispatches - before[5],
                  "slots": m.decode_tokens - before[7],
                  "pairs_chosen": None if chosen_before is None
                  else m.moe_pairs_chosen - chosen_before,
                  # decode programs DISPATCHED in this step: the engine may
                  # send the next step's decode behind this one's
                  "programs": m.decode_dispatches - before[5]
                  if programs_before is None
                  else m.decode_programs - programs_before,
                  # the chunks DISPATCHED in this step (the engine
                  # dispatches a step's chunk behind the decode before it)
                  "chunks": list(m.recent_prefill_chunks)[
                      len(m.recent_prefill_chunks) - n_chunks:]}
        return now, now - t_before, prefilled, detail

    # ramp: the load starts before the window and is not timed; it lasts
    # until a request long enough for the band's check has finished
    ramp = traffic["ramp"]
    limits = config["checks"]
    while True:
        now, _, _, _ = one_step()
        finished = [r for r in load.records
                    if r["done"] is not None and not r["refused"]]
        if len(finished) >= ramp.get("finished_requests", 0) \
                and now - load.t_start >= ramp.get("seconds", 0.0) \
                and any(r["prompt_len"] + len(r["tokens"])
                        >= limits["band_request_min_tokens"]
                        for r in finished):
            break
    harness.note("ramp", seconds=now - load.t_start, finished=len(finished),
                 submitted=len(load.records))

    # correctness, outside the window, with the load paused
    t_pause = time.perf_counter()
    checked = pick_checked(finished, limits)
    total = sum_checks(check_against_reference(engine.params, arch, rec,
                                               limits) for rec in checked)
    checks, stats = passes(total, limits)
    checks["a_checked_request_crosses_the_band"] = any(
        r["prompt_len"] + len(r["tokens"])
        >= limits["band_request_min_tokens"] for r in checked)
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
        steps.append((dt, prefilled, detail["decoded"], on,
                      len(detail["chunks"]), detail["slots"]))
        if on:
            traced_steps += traced_entries(detail)
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
            traced_steps += traced_entries(detail)
    if traced.running:
        sv.block_until_idle()
    traced.stop()
    compiled = cache_log.since(mark)
    regions = None
    if args.trace and trace_dir:
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None:
            # each program's own text says which of its instructions lie
            # under which named scope (after the window: compile-cache hits,
            # in no metric)
            texts = {"jit_decode": sv.trace_decode()[0].compile().as_text(),
                     "jit_suffix_routed":
                     sv.trace_prefill_chunk()[0].compile().as_text()}
            regions = trace_reduce_latent.reduce(
                trace_reduce_latent.load(path),
                {prog: scopes_in(text) for prog, text in texts.items()})
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
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
    groups = (snap1.get("kv_pool") or {}).get("groups")
    harness.note(
        "window", window_s=window_s, steps=len(steps), tokens=tokens,
        # every run does the same work step for step, so a window that holds
        # fewer steps lost time somewhere: the largest steps say whether to
        # one host stall
        step_ms_p50=harness.quantile([s[0] * 1e3 for s in steps], 50),
        step_ms_top3=sorted(s[0] * 1e3 for s in steps)[-3:],
        steps_with_a_chunk_share=sum(s[4] > 0 for s in steps) / len(steps),
        decoding_slots_p50=statistics.median(s[5] for s in steps),
        submitted=len(in_window), finished_or_refused=len(done_in_window),
        refused=refused, incomplete=short, without_first_token=no_first_token,
        gaps=len(gaps), in_flight_at_end=len(load.inflight),
        # first-token times and the rate are notes in a closed loop above
        # capacity (they are the queue's, and one stalled step of the host
        # moves the rate and not the median gap)
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
            "decode_only_step_ms": [s[0] * 1e3 for s in steps
                                    if s[2] and not s[1]],
            "prefill_steps": [(s[0] * 1e3, s[1]) for s in steps if s[1]],
            "traced_steps": traced_steps},
        "counters": {
            "decode_tokens": delta("goodput", "decode_tokens"),
            "decode_dispatches": delta("speculative", "decode_dispatches"),
            "prefill_device_tokens": delta("goodput", "prefill_device_tokens"),
            "padding_tokens": delta("goodput", "padding_tokens"),
            "n_slots": serving["n_slots"],
            # blocks the live requests hold in each group at the window's
            # end
            **({"window_group_blocks": groups["window"]["allocated_blocks"],
                "full_group_blocks": groups["full"]["allocated_blocks"]}
               if groups else {}),
            **({k: delta("moe", k) for k in (
                "dispatches", "moe_pairs", "moe_experts_hit",
                "max_expert_load_sum", "prefill_chunks",
                "prefill_chunk_tokens", "moe_pairs_chosen",
                "moe_pairs_held") if k in snap1["moe"]}
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
