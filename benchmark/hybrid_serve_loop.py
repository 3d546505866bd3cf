"""Runner of the ``hybrid_serve_loop`` traffic kind: ``sink_serve_loop``'s
closed or open loop over ``ServingEngine.submit()`` / ``step()`` for a
Nemotron-H model (Mamba-2 mixers, LatentMoE expert layers of which a chip
holds a share, attention layers without positions), served with every
prefill as chunks over the slots' recurrent state and a paged K/V pool.

It imports what the other runners export (``serve_loop``'s ``Load``, bucket
rule and item sizes; ``latent_serve_loop``'s verdicts; ``window_serve_loop``'s
summing of checks; ``sink_serve_loop``'s entries of traced steps) and
differs from ``sink_serve_loop`` in this:

- the reference is ``reference/hybrid_moe_decoder.py`` (the recurrence
  token by token), given the same share of the experts, run with the served
  expert choices forced; one of the two checked requests has a prompt longer
  than two chunks and no multiple of one, so that its state crosses two
  chunk edges and a padded chunk: the ramp lasts until such a request has
  finished;
- the served requests' recurrent state is checked too: a checked request
  keeps its slot's state when it finishes (``Request.record_state``); each
  Mamba layer's S is compared with the reference's, token by token, after
  the same positions, and its entries must carry float32's mantissa;
- the weights come from one fixed key (``WEIGHTS_SEED``), whatever the
  seed: which experts a token's scores favour, and so the share of the
  pairs that falls on the experts held here, is the same work in every run;
  the seed draws the token ids and the selection bias, which lives in each
  expert layer of the model's list of layers (``seed_selection_bias``);
- prompts shorter than a chunk are prefilled as one part-chunk (the engine
  sends every prefill of a model with recurrent state as chunks), so the
  warm-up's buckets are all the part-chunks';
- the regions of the device trace are this model's scopes: the Mamba
  mixer in decode (``ssm_decode``), its recurrence (``ssm_state_update``),
  its conv, the chunked scan (``ssm_chunk_scan``), the latent projections,
  the grouped products and the attention;
- ``obs`` carries, per traced step, the slots that decoded (the state they
  moved), and the snapshot's ``ssm`` counters.
"""

import functools
import shutil
import statistics
import tempfile
import time

import numpy as np

from . import harness, trace_reduce, trace_reduce_latent, traffic_gen
from .latent_serve_loop import passes
from .reference import hybrid_moe_decoder
from .serve_loop import ITEMSIZE, Load, prompt_buckets
from .sink_serve_loop import traced_entries
from .window_serve_loop import sum_checks

# regions of the device trace: jax.named_scope names of the program, the
# inner scopes first (the decode's recurrence and conv lie inside
# ``ssm_decode``: an instruction is booked to the first region that names
# it, and the whole mixer's time is the sum of the three)
REGIONS = {
    "ssm_state_update": ("ssm_state_update",),
    "ssm_conv": ("ssm_conv",),
    "ssm_decode": ("ssm_decode",),
    "ssm_chunk_scan": ("ssm_chunk_scan",),
    "experts": ("moe_grouped_matmul",),
    "latent_proj": ("latent_moe_proj",),
    "full_attention": ("full_attn_decode",),
    "full_chunk_attention": ("full_chunk_attn",),
    "row_write": ("paged_row_write",),
}
# ``ragged_dot`` loses its scope on the TPU (``trace_reduce_latent``)
NAMED = {"experts": ("ragged-dot",)}


def scopes_in(compiled_text):
    """``{instruction name: region}`` of one compiled program, as
    ``trace_reduce_latent.scopes_in`` with this cell's regions, each
    instruction in the first region that names it."""
    out = {}
    for name, rest in trace_reduce_latent.INSTRUCTION.findall(compiled_text):
        found = trace_reduce_latent.OP_NAME.search(rest)
        op_name = found.group(1) if found else ""
        for region, scopes in REGIONS.items():
            if any(s in op_name for s in scopes) or any(
                    name.startswith(n) for n in NAMED.get(region, ())):
                out[name] = region
                break
    return out


# the key of the weights: the seed draws the token ids and the selection
# bias alone, so that a seed does not change the work
WEIGHTS_SEED = 0


def seed_selection_bias(params, seed, std):
    """The published model trains its selection bias ``b``; the program
    makes it zero. The benchmark draws it from the seed at ``std``, one
    draw for every expert layer (in the order of the layers). In place."""
    import jax

    routers = [layer["mixer"]["router"] for layer in params["layers"]
               if "router" in layer["mixer"]]
    rng = np.random.default_rng([seed, 6])
    for router in routers:
        b = rng.normal(0.0, std, router["bias"].shape)
        router["bias"] = jax.device_put(b.astype(router["bias"].dtype),
                                        router["bias"].sharding)


def crosses_chunks(rec, chunk):
    """A prompt longer than two chunks and no multiple of one."""
    return rec["prompt_len"] > 2 * chunk and rec["prompt_len"] % chunk


def pick_checked(finished, limits, chunk):
    """Of ``finished`` (in the order they finished), the first request whose
    state crossed two chunk edges and a padded chunk, then the first other
    ones. A request picked stays picked as more finish."""
    crossing = [r for r in finished if crosses_chunks(r, chunk)]
    rest = [r for r in finished if not crossing or r is not crossing[0]]
    return (crossing[:1] + rest)[:limits["reference_requests"]]


def state_error(served, want):
    """The largest over the Mamba layers of |S - S_ref| / |S_ref| (Frobenius
    norms): a served recurrent state ``served`` [L_mamba, H, P, N] against
    the reference's ``want`` (a list of [H, P, N])."""
    served = np.asarray(served, np.float64)
    want = np.stack([np.asarray(w, np.float64) for w in want])
    norm = lambda a: np.sqrt((a ** 2).sum((1, 2, 3)))
    return float((norm(served - want) / norm(want)).max())


def past_bf16(state):
    """(nonzero entries of a float32 ``state``, those of them that bfloat16
    cannot hold: a low half of their bits not zero). A state kept and updated
    in float32 has nearly all its entries so (one in 65,536 has a zero low
    half); a state rounded to bfloat16 anywhere on its way, none."""
    bits = np.asarray(state, np.float32).view(np.uint32)
    nonzero = (bits & 0x7FFFFFFF) != 0
    return int(nonzero.sum()), int(((bits & 0xFFFF) != 0).sum())


def compare_with_reference(params, arch, seq, first, tokens, served_ids,
                           served_weights, limits, served_state=None):
    """``latent_serve_loop.compare_with_reference`` against this cell's
    reference: one forced forward over ``seq``; ``tokens[j]`` is the token
    the served path chose after position ``first + j``; ``served_state``,
    where given: the served ``ssm`` leaf [L_mamba, H, P, N] after ``seq``."""
    tokens = np.asarray(tokens, np.int32)
    logits, routing, states = hybrid_moe_decoder.logits_at(
        params, seq, arch, first, len(tokens), forced=served_ids,
        return_routing=True, return_states=True)
    logits = np.asarray(logits)
    top = logits.max(-1)
    step = 2.0 ** (np.floor(np.log2(np.abs(top))) - 7)   # of bf16 at the top
    tol = limits["tie_bf16_steps"] * step
    gap = top - logits[np.arange(len(tokens)), tokens]
    margins = hybrid_moe_decoder.routing_margins(routing, served_ids,
                                                 len(seq))
    w_err = hybrid_moe_decoder.weight_errors(routing, served_weights,
                                             len(seq))
    state = {}
    if served_state is not None:
        entries, past = past_bf16(served_state)
        state = {"states": 1,
                 "max_state_rel_error": state_error(served_state, states),
                 "state_entries": entries, "state_entries_past_bf16": past}
    return {**state, "tokens": len(tokens),
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
    and every generated token that was fed back, after which the request's
    slot held the state it kept."""
    tokens = np.asarray(rec["tokens"], np.int32)
    req = rec["req"]
    return compare_with_reference(
        params, arch, np.concatenate([rec["prompt"], tokens[:-1]]),
        rec["prompt_len"] - 1, tokens, req.expert_ids(),
        req.expert_weights(), limits,
        served_state=(req.final_state or {}).get("ssm"))


def state_passes(total, limits):
    """The verdicts on the checked requests' recurrent state, every one
    compared: (c) each Mamba layer's S within its limit of the reference's;
    (d) the share of S's entries past bfloat16's mantissa at least its
    limit (the configuration holds the state in float32). Also returns the
    share."""
    if total.get("states", 0) != limits["reference_requests"]:
        return {"recurrent_state_matches_reference": False,
                "recurrent_state_holds_float32": False}, {}
    share = total["state_entries_past_bf16"] / total["state_entries"]
    return {"recurrent_state_matches_reference":
            total["max_state_rel_error"] <= limits["state_rel_error_limit"],
            "recurrent_state_holds_float32":
            share >= limits["state_float32_share_limit"]}, \
        {"state_float32_share": share}


def finished_in_order(records):
    """The records of the requests answered, in the order they finished."""
    return sorted((r for r in records
                   if r["done"] is not None and not r["refused"]),
                  key=lambda r: r["done"])


def run(cell, config, traffic, manifest, args, devices, peaks, cache_log):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, RequestState

    arch, inf = config["arch"], config["init_inference"]
    serving = inf["serving"]
    model = harness.build_model(config)
    engine = deepspeed_tpu.init_inference(model, seed=WEIGHTS_SEED, **inf)
    seed_selection_bias(engine.params, args.seed,
                        config["selection_bias_std"])
    jax.block_until_ready(engine.params)
    sv = engine.serving
    max_len = serving["max_len"]
    chunk = serving["chunked_prefill"]["chunk_size"]
    harness.note("engine", weights_seed=WEIGHTS_SEED,
                 attn_backend=sv.attn_backend,
                 attn_reason=sv.attn_reason,
                 n_slots=serving["n_slots"], max_len=max_len, chunk=chunk,
                 kv_pool=sv.metrics.snapshot()["kv_pool"],
                 pool_layout=sv.pool_layouts(),
                 setup_so_far_s=harness.process_age_s())

    # warm exactly the cell's shapes: every prompt runs full chunks and one
    # last part-chunk, padded by the bucket rule; one request through each
    # part-chunk bucket reaches them all
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
    # requests sent during the ramp record their expert choices and keep
    # their final state, for the reference check; those of the window do not
    load = Load(sv, schedule, traffic["arrivals"],
                functools.partial(Request, record_routing=True,
                                  record_state=True),
                RequestState.REJECTED)
    # the trace is kept until the regions have been read from it
    trace_dir = args.trace_dir or (
        tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None)
    traced = harness.TracedSlice(args.trace, args.seconds,
                                 traffic["trace_slice_s"], trace_dir)
    m = sv.metrics
    steps = []      # (seconds, prompt tokens prefilled, decoded, traced,
    #                  chunks dispatched, slots that decoded)
    traced_steps = []   # per traced decode program: rows, experts, slots
    freed_at = []

    def one_step():
        with harness.span("submit"):
            load.submit_due(freed_at)
        before = (m.prefill_device_tokens - m.padding_tokens,
                  m.moe_decode_experts_hit, m.moe_decode_pairs,
                  m.latent_kv_tokens_read, m.decode_dispatches,
                  m.prefill_chunks, m.decode_tokens, m.decode_programs)
        t_before = load.now()
        with harness.span("step"):
            events = sv.step()
        now = load.now()
        with harness.span("read_tokens"):
            load.take(events, now, freed_at)
        if not events:
            load.wait_for_work()
        prefilled = m.prefill_device_tokens - m.padding_tokens - before[0]
        n_chunks = m.prefill_chunks - before[5]
        detail = {"full_rows": m.latent_kv_tokens_read - before[3],
                  "experts_hit": m.moe_decode_experts_hit - before[1],
                  "pairs": m.moe_decode_pairs - before[2],
                  "decoded": m.decode_dispatches - before[4],
                  "slots": m.decode_tokens - before[6],
                  # decode programs DISPATCHED in this step: the engine may
                  # send the next step's decode behind this one's
                  "programs": m.decode_programs - before[7],
                  # the chunks DISPATCHED in this step (the engine
                  # dispatches a step's chunk behind the decode before it)
                  "chunks": list(m.recent_prefill_chunks)[
                      len(m.recent_prefill_chunks) - n_chunks:]}
        return now, now - t_before, prefilled, detail

    # ramp: the load starts before the window and is not timed; it lasts
    # until a request whose state crossed two chunk edges has finished
    ramp = traffic["ramp"]
    limits = config["checks"]
    while True:
        now, _, _, _ = one_step()
        finished = finished_in_order(load.records)
        checked = pick_checked(finished, limits, chunk)
        for r in finished:
            if not any(r is c for c in checked):
                r["req"].final_state = None     # not to be checked
        if len(finished) >= ramp.get("finished_requests", 0) \
                and now - load.t_start >= ramp.get("seconds", 0.0) \
                and any(crosses_chunks(r, chunk) for r in finished):
            break
    harness.note("ramp", seconds=now - load.t_start, finished=len(finished),
                 submitted=len(load.records))

    # correctness, outside the window, with the load paused
    t_pause = time.perf_counter()
    total = sum_checks(check_against_reference(engine.params, arch, rec,
                                               limits) for rec in checked)
    checks, stats = passes(total, limits)
    state_checks, state_stats = state_passes(total, limits)
    checks.update(state_checks)
    stats.update(state_stats)
    checks["a_checked_request_crosses_two_chunks"] = any(
        crosses_chunks(r, chunk) for r in checked)
    harness.note("reference", requests=len(checked),
                 prompt_lens=[r["prompt_len"] for r in checked],
                 answer_lens=[len(r["tokens"]) for r in checked],
                 **stats, **total,
                 seconds=time.perf_counter() - t_pause)
    for rec in load.records:
        rec["req"].routing = []     # the record has served; free it
        rec["req"].record_routing = False
        rec["req"].final_state = None
        rec["req"].record_state = False
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
    decode_only = [s[0] * 1e3 for s in steps if s[2] and not s[4]]
    harness.note(
        "window", window_s=window_s, steps=len(steps), tokens=tokens,
        step_ms_p50=harness.quantile([s[0] * 1e3 for s in steps], 50),
        step_ms_top3=sorted(s[0] * 1e3 for s in steps)[-3:],
        steps_with_a_chunk_share=sum(s[4] > 0 for s in steps) / len(steps),
        decode_only_step_ms_p50=harness.quantile(decode_only, 50)
        if decode_only else None,
        decoding_slots_p50=statistics.median(s[5] for s in steps),
        submitted=len(in_window), finished_or_refused=len(done_in_window),
        refused=refused, incomplete=short, without_first_token=no_first_token,
        gaps=len(gaps), in_flight_at_end=len(load.inflight),
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
        ssm=snap1.get("ssm"), regions=regions)
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
            "decode_only_step_ms": decode_only,
            "prefill_steps": [(s[0] * 1e3, s[1]) for s in steps if s[1]],
            "traced_steps": traced_steps},
        "counters": {
            "decode_tokens": delta("goodput", "decode_tokens"),
            "decode_dispatches": delta("speculative", "decode_dispatches"),
            "prefill_device_tokens": delta("goodput", "prefill_device_tokens"),
            "padding_tokens": delta("goodput", "padding_tokens"),
            "n_slots": serving["n_slots"],
            **({k: delta("moe", k) for k in (
                "dispatches", "moe_pairs", "moe_experts_hit",
                "max_expert_load_sum", "prefill_chunks",
                "prefill_chunk_tokens", "moe_pairs_chosen",
                "moe_pairs_held") if k in snap1["moe"]}
               if "moe" in snap1 else {}),
            **({"ssm_" + k: delta("ssm", k) for k in (
                "state_resets", "chunk_tokens_scanned", "pad_tokens_masked")}
               if "ssm" in snap1 else {})},
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
