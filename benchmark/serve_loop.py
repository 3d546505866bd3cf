"""Runner of the ``serve_loop`` traffic kind: requests drawn from the seed
are offered to ``ServingEngine`` through ``submit()`` and ``step()`` under
the arrival process the traffic file names, and every token is timed as it
reaches this harness (the return of the ``step()`` that produced it).

Arrivals are data: ``{"kind": "closed", "clients": n}`` sends a client's
next request the moment its previous answer ended; ``{"kind": "poisson" |
"gamma", "rate_rps": x}`` sends on a schedule whatever the server does, and
times each request from when it was due.

Set-up: build the engine (weights made on the device from ``--seed``), warm
one request through every prompt bucket the traffic's lengths reach, start
the load and let it ramp, check answers finished during the ramp against
the plain reference. Then the window, then a few steps of drain without new
submissions so that requests sent just before the end get their first token.
"""

import time

import numpy as np

from . import harness, traffic_gen
from .reference import dense_decoder


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def prompt_buckets(lo, hi, bucket, policy, ceiling):
    """{padded length: a prompt length that pads to it} for the prompt
    lengths lo..hi: the bucket rule of ``InferenceEngine`` (copied)."""
    out = {}
    for n in range(lo, hi + 1):
        if bucket > 1 and policy == "pow2":
            padded = bucket
            while padded < n:
                padded *= 2
        else:
            padded = -(-n // max(bucket, 1)) * max(bucket, 1)
        out.setdefault(max(min(padded, ceiling), n), n)
    return out


def check_against_reference(engine, arch, rec, max_len, width):
    """One reference forward over prompt plus answer: every served token is
    the reference's maximum, or within two bf16 steps of it (with random
    weights the top two of 50k logits are sometimes closer than bf16
    resolves, and the served path rounds in bf16: PR 21's tie rule).
    Returns (tokens off the maximum but tied, tokens wrong)."""
    ids = np.zeros((1, max_len), np.int32)
    seq = np.concatenate([rec["prompt"], np.asarray(rec["tokens"], np.int32)])
    ids[0, :len(seq)] = seq
    first = rec["prompt_len"] - 1          # this position predicts token 0
    start = min(first, max_len - width)
    logits = np.asarray(dense_decoder.logits_at(
        engine.params, ids, arch, start, width))
    ties = wrong = 0
    for j, tok in enumerate(rec["tokens"]):
        row = logits[first - start + j]
        top = float(row.max())
        tol = 2.0 * 2.0 ** (np.floor(np.log2(abs(top))) - 7)
        gap = top - float(row[tok])
        ties += 0 < gap <= tol
        wrong += gap > tol
    return int(ties), int(wrong)


class Load:
    """The clients: who is in flight, what was sent when, and what came
    back when. Times are on a clock that stands still while the harness
    pauses the load (the reference check), so a pause is not a stall."""

    def __init__(self, sv, schedule, arrivals, request_cls, rejected_state):
        self.sv, self.schedule, self.arrivals = sv, schedule, arrivals
        self.request_cls, self.rejected_state = request_cls, rejected_state
        self.paused = 0.0
        self.t_start = self.now()
        self.next_i = 0
        self.inflight, self.records = {}, []
        self.accepting = True
        self.lateness = []

    def now(self):
        return time.perf_counter() - self.paused

    def _submit(self, due, now):
        item = self.schedule[self.next_i]
        self.next_i += 1
        req = self.sv.submit(self.request_cls(
            prompt=item["prompt"], max_new_tokens=item["max_new_tokens"]))
        rec = {"due": due, "submitted": now, "prompt": item["prompt"],
               "prompt_len": len(item["prompt"]),
               "max_new_tokens": item["max_new_tokens"], "tokens": [],
               "times": [], "done": None, "req": req,
               "refused": req.state is self.rejected_state}
        self.records.append(rec)
        self.lateness.append(now - due)
        if rec["refused"]:
            rec["done"] = now
        else:
            self.inflight[req.request_id] = rec

    def submit_due(self, freed_at):
        """Closed loop: one request for each free client, due when the
        client's last answer ended. Open loop: every request whose time
        has come."""
        now = self.now()
        if not self.accepting:
            return
        if self.arrivals["kind"] == "closed":
            while len(self.inflight) < self.arrivals["clients"] \
                    and self.next_i < len(self.schedule):
                self._submit(freed_at.pop() if freed_at else now, now)
        else:
            while self.next_i < len(self.schedule) and self.t_start \
                    + self.schedule[self.next_i]["due_s"] <= now:
                self._submit(
                    self.t_start + self.schedule[self.next_i]["due_s"], now)

    def wait_for_work(self):
        """Open loop with nothing in flight: sleep until the next arrival."""
        if self.inflight or not self.accepting \
                or self.next_i >= len(self.schedule) \
                or self.arrivals["kind"] == "closed":
            return
        gap = self.t_start + self.schedule[self.next_i]["due_s"] - self.now()
        if gap > 0:
            time.sleep(min(gap, 0.05))

    def take(self, events, now, freed_at):
        """Book the tokens of one ``step()``. Returns the prompt tokens
        prefilled in it and whether it decoded."""
        prefilled, decoded = 0, False
        for ev in events:
            rec = self.inflight.get(ev.request_id)
            if rec is None:
                continue
            if ev.token >= 0:
                rec["tokens"].append(int(ev.token))
                rec["times"].append(now)
            if ev.index == 0:
                prefilled += rec["prompt_len"]
            else:
                decoded = True
            if ev.done:
                rec["done"] = now
                rec["finish_reason"] = ev.finish_reason
                del self.inflight[ev.request_id]
                freed_at.append(now)
        return prefilled, decoded

    def live_kv_tokens(self):
        return sum(r["prompt_len"] + len(r["tokens"])
                   for r in self.inflight.values())


def run(cell, config, traffic, manifest, args, devices, peaks, cache_log):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, RequestState

    arch, inf = config["arch"], config["init_inference"]
    serving = inf["serving"]
    model = harness.build_model(config)
    engine = deepspeed_tpu.init_inference(model, seed=args.seed, **inf)
    jax.block_until_ready(engine.params)
    sv = engine.serving
    max_len = serving["max_len"]
    harness.note("engine", attn_backend=sv.attn_backend,
                 n_slots=serving["n_slots"], max_len=max_len,
                 kv_pool=serving["kv_pool"], setup_so_far_s=harness.process_age_s())

    # warm exactly the cell's shapes: one request through each prompt bucket
    lo, hi = traffic["prompt_len"]["clip"]
    buckets = prompt_buckets(lo, hi, inf["prompt_bucket_size"],
                             inf["prompt_bucket_policy"], max_len)
    rng = np.random.default_rng([args.seed, 5])
    warm = [sv.submit(Request(prompt=rng.integers(
        0, arch["vocab_size"], n, dtype=np.int32), max_new_tokens=3))
        for n in buckets.values()]
    while any(r.state not in (RequestState.FINISHED, RequestState.REJECTED)
              for r in warm):
        sv.step()
    harness.note("warm", buckets=sorted(buckets),
                 compile_counts=sv.compile_counts(),
                 setup_so_far_s=harness.process_age_s())

    schedule = traffic_gen.serve_requests(
        traffic, args.seed, traffic["schedule_requests"], arch["vocab_size"])
    load = Load(sv, schedule, traffic["arrivals"], Request,
                RequestState.REJECTED)
    traced = harness.TracedSlice(args.trace, args.seconds,
                                 traffic["trace_slice_s"], args.trace_dir)
    steps = []          # (seconds, prompt tokens prefilled, decoded, traced)
    live_traced = []    # live KV tokens at each traced step
    freed_at = []

    def one_step():
        with harness.span("submit"):
            load.submit_due(freed_at)
        t_before = load.now()
        with harness.span("step"):
            events = sv.step()
        now = load.now()
        with harness.span("read_tokens"):
            prefilled, decoded = load.take(events, now, freed_at)
        if not events:
            load.wait_for_work()
        return now, now - t_before, prefilled, decoded

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
    checked = finished[:config["checks"]["reference_requests"]]
    ties = wrong = 0
    for rec in checked:
        t, w = check_against_reference(engine, arch, rec, max_len,
                                       traffic["output_len"]["clip"][1])
        ties, wrong = ties + t, wrong + w
    checks = {"served_tokens_match_reference": wrong == 0 and bool(checked)}
    harness.note("reference", requests=len(checked),
                 tokens=sum(len(r["tokens"]) for r in checked),
                 off_maximum_but_tied=ties, wrong=wrong,
                 seconds=time.perf_counter() - t_pause)
    load.paused += time.perf_counter() - t_pause

    counts0, snap0, mark = sv.compile_counts(), sv.metrics.snapshot(), \
        cache_log.mark()
    setup_s = harness.process_age_s()
    t0 = load.now()
    while True:
        now, dt, prefilled, decoded = one_step()
        on = traced.running
        steps.append((dt, prefilled, decoded, on))
        if on:
            live_traced.append(load.live_kv_tokens())
        if now - t0 >= args.seconds:
            break
        traced.maybe_start(now - t0)
    t_end = now
    snap1 = sv.metrics.snapshot()
    load.accepting = False
    for _ in range(traffic["drain_steps"]):
        one_step()
    traced.stop()
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
        submitted=len(in_window), finished_or_refused=len(done_in_window),
        refused=refused, incomplete=short, without_first_token=no_first_token,
        gaps=len(gaps), in_flight_at_end=len(load.inflight),
        ttft_ms_p90=harness.quantile(ttft, 90) if ttft else None,
        itl_ms_p95=harness.quantile(gaps, 95) if gaps else None,
        completions_per_s=len(done_in_window) / window_s,
        generator_late_ms_p50=harness.quantile(load.lateness, 50) * 1e3,
        generator_late_ms_max=max(load.lateness) * 1e3,
        compiled_in_window=compiled, compile_counts=counts1,
        shed=snap1["shed"], preempted=snap1["preempted"],
        kv_pool=snap1.get("kv_pool"))
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
            "live_kv_tokens_traced": live_traced},
        "counters": {
            "decode_tokens": delta("goodput", "decode_tokens"),
            "decode_dispatches": delta("speculative", "decode_dispatches"),
            "prefill_device_tokens": delta("goodput", "prefill_device_tokens"),
            "padding_tokens": delta("goodput", "padding_tokens"),
            "n_slots": serving["n_slots"]},
        "trace": traced.reduced, "arch": arch,
        "work": {"chips": len(devices),
                 "kv_itemsize": 1 if serving["kv_pool"].get("kv_dtype")
                 == "int8" else ITEMSIZE[inf["dtype"]],
                 "weight_itemsize": ITEMSIZE[inf["dtype"]]},
        "peaks": peaks}
    result = harness.result_line(
        manifest, cell, args, correct=all(checks.values()),
        attempted=len(done_in_window), failed=refused + short,
        end_to_end=end_to_end, obs=obs, devices=devices, traced=traced)
    engine.destroy()
    return result
