"""Continuous-batching serving tests (tier-1).

The acceptance invariants of the serving subsystem:

- greedy token streams are BITWISE identical to sequential ``generate()``
  under staggered arrivals and mixed prompt/output lengths;
- the decode step compiles exactly once per (model, slot-pool) configuration
  — requests joining/leaving mid-flight never recompile;
- slot reuse after EOS/finish cannot leak stale KV rows into the next
  request's attention window;
- on a mixed-length workload the continuous scheduler's aggregate tokens/s
  strictly beats static whole-batch batching under the shared virtual cost
  model;
- admission control sheds with a reason under overload instead of growing
  until OOM.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import ServingConfig
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (Request, RequestState, SamplingParams,
                                   ServingEngine, VirtualClock,
                                   simulate_static_batching)

from .conftest import make_replica, staggered_requests, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def test_greedy_parity_staggered_and_compiles_once(engine):
    """Continuous batching == sequential generate(), token for token, under
    staggered arrivals and mixed prompt/output lengths — and the decode/insert
    programs compile exactly once while requests join and leave mid-flight."""
    rng = np.random.RandomState(0)
    reqs = staggered_requests(rng, 6)
    sv = make_replica(engine, n_slots=2)
    events = list(sv.serve(reqs))

    assert all(r.state is RequestState.FINISHED for r in reqs)
    for r in reqs:
        ref = np.asarray(engine.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])

    # 6 requests through 2 slots = slots freed and re-filled mid-flight;
    # exactly one compiled decode step + one insert + one prompt bucket
    counts = sv.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["insert"] == 1, counts
    assert counts["prefill_buckets"] == 1, counts

    # the event stream is complete and ordered per request
    by_req = {}
    for ev in events:
        assert ev.index == len(by_req.setdefault(ev.request_id, []))
        by_req[ev.request_id].append(ev.token)
    for r in reqs:
        assert by_req[r.request_id] == r.tokens


def test_slot_reuse_cannot_leak_stale_kv(engine):
    """A long request fills a slot's KV rows; the short request that reuses
    the slot must produce BITWISE the same tokens as on a never-used pool —
    stale rows sit behind the whole-row insert + causal mask."""
    rng = np.random.RandomState(1)
    long_req = Request(prompt=rng.randint(0, 64, (12,)).astype(np.int32),
                       max_new_tokens=20)
    short_prompt = rng.randint(0, 64, (5,)).astype(np.int32)

    sv = make_replica(engine, n_slots=1)
    list(sv.serve([long_req]))
    assert long_req.state is RequestState.FINISHED
    reused = Request(prompt=short_prompt, max_new_tokens=6)
    list(sv.serve([reused]))

    fresh = make_replica(engine, n_slots=1)
    pristine = Request(prompt=short_prompt, max_new_tokens=6)
    list(fresh.serve([pristine]))

    np.testing.assert_array_equal(np.asarray(reused.tokens),
                                  np.asarray(pristine.tokens))
    # and the same again with the hygiene scrub on (the block scrub)
    sv2 = make_replica(engine, n_slots=1, scrub_freed_slots=True)
    list(sv2.serve([Request(prompt=long_req.prompt, max_new_tokens=20)]))
    scrubbed = Request(prompt=short_prompt, max_new_tokens=6)
    list(sv2.serve([scrubbed]))
    np.testing.assert_array_equal(np.asarray(scrubbed.tokens),
                                  np.asarray(pristine.tokens))


def test_continuous_beats_static_batching(engine):
    """Deterministic virtual-clock throughput: on a mixed-length workload the
    slot scheduler's aggregate tokens/s strictly exceeds static whole-batch
    batching (which decodes every batch until its LONGEST member finishes),
    under the SAME cost model."""
    rng = np.random.RandomState(2)
    reqs = []
    for i in range(6):
        # alternating short/long outputs — the static baseline's worst case
        # and the realistic serving mix
        reqs.append(Request(
            prompt=rng.randint(0, 64, (int(rng.randint(4, 14)),)).astype(np.int32),
            max_new_tokens=3 if i % 2 == 0 else 16))
    sv = make_replica(engine, n_slots=2)
    finished, rejected, snap = sv.run([Request(prompt=r.prompt,
                                               max_new_tokens=r.max_new_tokens)
                                       for r in reqs])
    assert len(finished) == 6 and not rejected
    cont_tokens = sum(len(r.tokens) for r in finished)
    cont_time = sv.clock.now()

    static_tokens, static_time = simulate_static_batching(
        reqs, sv.n_slots,
        prefill_cost_per_token=sv.cfg.virtual_prefill_cost_per_token,
        decode_step_cost=sv.cfg.virtual_decode_step_cost,
        bucket_len=lambda p: engine._bucket_prompt_len(p, sv.max_len))
    assert cont_tokens == static_tokens  # same work...
    assert cont_tokens / cont_time > static_tokens / static_time  # ...faster
    assert snap["tokens_per_s"] > 0


def test_admission_control_sheds_with_reason(engine):
    """Overload: bounded queue sheds queue_full; an oversized request sheds
    prompt_too_long; nothing crashes and accepted work completes."""
    rng = np.random.RandomState(3)
    sv = make_replica(engine, n_slots=1, max_queue_depth=2)
    reqs = [Request(prompt=rng.randint(0, 64, (6,)).astype(np.int32),
                    max_new_tokens=4) for _ in range(8)]
    # all arrive at t=0: 1 slot + 2 queue spots -> some must shed
    events = list(sv.serve(reqs))
    finished = [r for r in reqs if r.state is RequestState.FINISHED]
    rejected = [r for r in reqs if r.state is RequestState.REJECTED]
    assert finished and rejected
    assert all(r.reject_reason == "queue_full" for r in rejected)
    shed_events = [e for e in events
                   if e.finish_reason == "rejected:queue_full"]
    assert len(shed_events) == len(rejected)
    assert sv.metrics.shed_rate > 0

    too_long = sv.submit(rng.randint(0, 64, (40,)).astype(np.int32),
                         max_new_tokens=40)  # 40 + 40 > 64-token window
    assert too_long.state is RequestState.REJECTED
    assert too_long.reject_reason == "prompt_too_long"
    snap = sv.metrics.snapshot()
    assert snap["shed"]["prompt_too_long"] == 1


def test_per_request_rng_and_sampling_isolation(engine):
    """Co-batched sampled requests never share an rng stream: a seeded
    request's sampled tokens are identical whether it runs alone or
    co-batched with different neighbours; co-batched same-prompt requests
    with different seeds diverge; per-request temperature 0 stays greedy
    next to a sampled neighbour."""
    rng = np.random.RandomState(4)
    prompt = rng.randint(0, 64, (6,)).astype(np.int32)
    other = rng.randint(0, 64, (9,)).astype(np.int32)

    def seeded(seed, temp=1.0):
        return Request(prompt=prompt, max_new_tokens=8,
                       sampling=SamplingParams(temperature=temp, top_k=8,
                                               seed=seed))

    sv = make_replica(engine, n_slots=2)
    alone = seeded(7)
    list(sv.serve([alone]))

    sv2 = make_replica(engine, n_slots=2)
    cobatched = seeded(7)
    neighbour = Request(prompt=other, max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.7, seed=123))
    list(sv2.serve([cobatched, neighbour]))
    assert cobatched.tokens == alone.tokens  # own stream, neighbours ignored

    sv3 = make_replica(engine, n_slots=2)
    a, b = seeded(7), seeded(8)
    list(sv3.serve([a, b]))
    assert a.tokens == alone.tokens
    assert a.tokens != b.tokens  # different seeds, different streams

    # greedy row next to a sampled row stays exact argmax
    sv4 = make_replica(engine, n_slots=2)
    greedy_req = Request(prompt=prompt, max_new_tokens=6)
    list(sv4.serve([greedy_req, seeded(9)]))
    ref = np.asarray(engine.generate(prompt[None, :], max_new_tokens=6,
                                     greedy=True))
    np.testing.assert_array_equal(np.asarray(greedy_req.tokens),
                                  ref[0, len(prompt):])


@pytest.mark.parametrize("case", ["greedy_only", "temperature_only",
                                  "top_k", "top_p", "verify_steps"])
def test_sampler_step_counters(engine, case):
    """``snapshot()["sampler"]`` books every decode (or verify) dispatch by
    the sampler arm its LIVE rows selected: a sampled request pins the sampled
    arm for the steps it is live and no longer; its freed slot still carries
    its knobs on the device, and the steps are greedy again."""
    rng = np.random.RandomState(11)
    prompt = lambda n: rng.randint(0, 64, (n,)).astype(np.int32)
    kw = {}
    if case == "verify_steps":
        kw = dict(kv_pool={"block_size": 16},
                  speculative={"enabled": True, "drafter": "ngram", "k": 4})
    sv = make_replica(engine, n_slots=2, **kw)
    sampler = lambda: sv.metrics.snapshot()["sampler"]
    dispatches = lambda: sum(
        sv.metrics.snapshot()["kv_pool"]["decode_dispatches"].values())
    assert sampler() == {"greedy_steps": 0, "sampled_steps": 0}

    if case in ("greedy_only", "verify_steps"):
        # (a periodic prompt, so that the n-gram drafter drafts)
        reqs = [Request(prompt=np.tile(prompt(4), 4), max_new_tokens=12),
                Request(prompt=prompt(9), max_new_tokens=5),
                Request(prompt=prompt(5), max_new_tokens=7)]
        list(sv.serve(reqs))
        if case == "verify_steps":
            assert sv.metrics.snapshot()["speculative"]["verify_steps"] > 0
        assert sampler() == {"greedy_steps": dispatches(),
                             "sampled_steps": 0}
        return

    knobs = {"temperature_only": dict(temperature=0.8),
             "top_k": dict(temperature=0.8, top_k=8),
             "top_p": dict(temperature=0.8, top_p=0.7)}[case]
    sampled = Request(prompt=prompt(6), max_new_tokens=4,
                      sampling=SamplingParams(seed=5, **knobs))
    greedy_req = Request(prompt=prompt(7), max_new_tokens=14)
    list(sv.serve([sampled, greedy_req]))
    # its first token left the prefill; every later one a decode step
    live_steps = len(sampled.tokens) - 1
    assert live_steps == 3
    after_first = sampler()
    assert after_first["sampled_steps"] == live_steps
    # the greedy request outlived it beside its freed slot: greedy steps
    assert after_first["greedy_steps"] == dispatches() - live_steps
    assert after_first["greedy_steps"] >= 8
    ref = np.asarray(engine.generate(greedy_req.prompt[None, :],
                                     max_new_tokens=14, greedy=True))
    np.testing.assert_array_equal(np.asarray(greedy_req.tokens),
                                  ref[0, greedy_req.prompt_len:])

    # the slot reused by a greedy request: still greedy steps only
    list(sv.serve([Request(prompt=prompt(5), max_new_tokens=6),
                   Request(prompt=prompt(8), max_new_tokens=6)]))
    after_reuse = sampler()
    assert after_reuse["sampled_steps"] == live_steps
    assert after_reuse["greedy_steps"] == dispatches() - live_steps
    assert after_reuse["greedy_steps"] > after_first["greedy_steps"]
    assert sv.compile_counts()["decode"] == 1


def test_eos_stops_slot_early(engine):
    """Per-request EOS frees the slot mid-flight; the stream ends with the
    eos token and finish_reason 'eos', matching generate()'s truncation."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 64, (6,)).astype(np.int32)
    ref = np.asarray(engine.generate(prompt[None, :], max_new_tokens=10,
                                     greedy=True))[0, len(prompt):]
    # the premise, checked: the stream turns to a token it has not produced
    # yet at least twice after its first, so both stops below cut MID-stream
    # (the seed is picked for it: a tiny random model mostly repeats itself)
    fresh = [i for i in range(1, len(ref)) if ref[i] not in ref[:i]]
    assert len(fresh) >= 2, ref
    eos = int(ref[fresh[-1]])

    sv = make_replica(engine, n_slots=2)
    req = Request(prompt=prompt, max_new_tokens=10, eos_token_id=eos)
    filler = Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                     max_new_tokens=12)
    list(sv.serve([req, filler]))
    assert req.finish_reason == "eos"
    assert req.tokens[-1] == eos
    np.testing.assert_array_equal(np.asarray(req.tokens),
                                  ref[:fresh[-1] + 1])
    assert filler.finish_reason == "length"
    assert len(filler.tokens) == 12

    # host-side stop sequences: a set of ids, distinct from the device eos
    stop_tok = int(ref[fresh[0]])
    sv2 = make_replica(engine, n_slots=2)
    stopped = Request(prompt=prompt, max_new_tokens=10,
                      stop_token_ids=(stop_tok,))
    neighbour = Request(prompt=prompt, max_new_tokens=8)
    list(sv2.serve([stopped, neighbour]))
    assert stopped.finish_reason == "stop"
    np.testing.assert_array_equal(np.asarray(stopped.tokens),
                                  ref[:fresh[0] + 1])
    # the neighbour keeps decoding correctly after the mid-flight release
    np.testing.assert_array_equal(np.asarray(neighbour.tokens), ref[:8])


def test_serving_monitor_events(engine, tmp_path):
    """Serving/* scalars flow through the existing monitor config (CSV
    backend), mirroring the Comm/*_gb pattern."""
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    mcfg = engine.config.replace(
        csv_monitor={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "serving_test"})
    sv = ServingEngine(
        engine, serving_config=ServingConfig(n_slots=2, virtual_clock=True,
                                             monitor_interval=1),
        clock=VirtualClock(), monitor=MonitorMaster(mcfg))
    rng = np.random.RandomState(6)
    list(sv.serve(staggered_requests(rng, 3, arrival_gap=0.0)))
    sv.metrics.emit_events()

    outdir = tmp_path / "serving_test"
    names = {p.name for p in outdir.iterdir()}
    for expected in ("Serving_queue_depth.csv", "Serving_slot_occupancy.csv",
                     "Serving_tokens_per_s.csv", "Serving_ttft_ms.csv"):
        assert expected in names, names
    rows = (outdir / "Serving_tokens_per_s.csv").read_text().strip().splitlines()
    assert len(rows) >= 2  # header + at least one sample


def test_engine_serve_frontend_and_streaming_order(engine):
    """engine.serve() streams TokenEvents incrementally (a generator, not a
    batch): events for a long request interleave with a later-arriving short
    one instead of waiting for the batch to drain."""
    rng = np.random.RandomState(7)
    eng = deepspeed_tpu.init_inference(
        CausalLM(tiny_cfg()), dtype="float32", max_tokens=64,
        prompt_bucket_size=16,
        serving={"n_slots": 2, "virtual_clock": True})
    long_req = Request(prompt=rng.randint(0, 64, (6,)).astype(np.int32),
                       max_new_tokens=12, arrival_time=0.0)
    late_req = Request(prompt=rng.randint(0, 64, (5,)).astype(np.int32),
                       max_new_tokens=3, arrival_time=2.0)
    seen = []
    for ev in eng.serve([long_req, late_req]):
        seen.append(ev.request_id)
    # the late request's events are sandwiched inside the long one's
    first_late = seen.index(late_req.request_id)
    assert any(rid == long_req.request_id for rid in seen[first_late:])
    assert late_req.state is RequestState.FINISHED
    eng.destroy()
    assert eng._serving is None


@pytest.mark.parametrize("kw", [dict(position_embedding="rope", n_kv_heads=2),
                                dict(position_embedding="alibi")],
                         ids=["rope-gqa", "alibi"])
def test_greedy_parity_model_variants(kw):
    """The per-slot decode path stays bitwise-exact for GQA/rope and alibi
    position handling (per-row cursors exercise their own mask/bias code)."""
    model = CausalLM(tiny_cfg(**kw))
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", max_tokens=32, prompt_bucket_size=8,
        serving={"n_slots": 2, "virtual_clock": True})
    rng = np.random.RandomState(8)
    reqs = staggered_requests(rng, 3, max_new=(3, 6))
    list(eng.serve(reqs))
    for r in reqs:
        ref = np.asarray(eng.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


def test_direct_submit_future_arrival_no_livelock(engine):
    """Manual submit()/step() driving with an arrival OFFSET: the offset
    resolves against the clock (ttft stays sane) and an idle virtual-clock
    step() loop advances to the arrival instead of spinning forever."""
    sv = make_replica(engine, n_slots=1)
    rng = np.random.RandomState(10)
    req = sv.submit(Request(prompt=rng.randint(0, 64, (5,)).astype(np.int32),
                            max_new_tokens=3, arrival_time=4.0))
    assert req.state is RequestState.QUEUED
    for _ in range(50):
        sv.step()
        if req.state is RequestState.FINISHED:
            break
    assert req.state is RequestState.FINISHED
    assert req.ttft is not None and 0.0 <= req.ttft < 10.0


def test_serving_tp_mesh_parity(devices8):
    """TP=2 slot pool: the KV pool shards its kv-head axis over the model
    mesh axis (pinned out_shardings), decode still compiles once, and greedy
    streams match the single-device reference bitwise."""
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
         "serving": {"n_slots": 2, "virtual_clock": True}}), mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)

    rng = np.random.RandomState(9)
    reqs = staggered_requests(rng, 3, max_new=(3, 6))
    list(eng.serve(reqs))
    assert eng.serving.compile_counts()["decode"] == 1

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                       max_tokens=64)
    raw.params = values
    for r in reqs:
        ref = np.asarray(raw.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


@pytest.mark.parametrize("fleet", [True, False],
                         ids=["blocks8-shared-prefix", "blocks16"])
def test_bench_serving_qps_smoke(tmp_path, fleet):
    """tools/bench_serving.py --qps emits the throughput–latency artifact on
    the tiny preset under JAX_PLATFORMS=cpu (tier-1 smoke, incl. overload
    shed accounting), over two pool geometries. Both rows run THROUGH THE
    ROUTER and carry the kv_pool block; the first (blocks of 8, prompts
    opening with a shared prefix) additionally exercises --replicas 2 +
    --chunk-size + --session-affinity + speculation, the second is the
    pool no flag describes (blocks of 16, slots x max_len tokens)."""
    out = tmp_path / "serving_load.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(REPO, "tools", "bench_serving.py"),
           "--qps", "200", "--num-requests", "10", "--family", "gpt2",
           "--sizes", "tiny", "--modes", "bf16", "--prompts", "8,16",
           "--new-tokens", "6", "--slots", "2", "--queue-depth", "3",
           "--seed", "0", "--output", str(out)]
    if fleet:
        cmd += ["--kv-block-size", "8", "--shared-prefix", "8",
                "--replicas", "2", "--chunk-size", "8",
                "--session-affinity", "--spec-draft", "ngram",
                "--spec-k", "4"]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    art = json.loads(out.read_text())
    assert art["bench"] == "serving_open_loop"
    assert art["completed"] >= 1
    assert art["completed"] + art["shed"] == 10
    assert art["shed"] >= 1 and art["shed_rate"] > 0  # overload engaged
    assert art["ttft_ms"]["p50"] is not None
    assert art["tokens_per_s"] > 0
    assert art["compile_counts"]["decode"] == 1
    assert art["numerics"]["nonfinite_logit_steps"] == 0
    # the router block is always present: per-replica routing/occupancy,
    # affinity hit rates, rebalances + drain counts
    router = art["router"]
    assert router["replicas"] == (2 if fleet else 1)
    assert sum(router["per_replica_routed"]) == router["routed"]
    assert router["routed"] == art["completed"]
    assert "affinity_hit_rate" in router and "rebalances" in router
    assert "drains" in router and router["drains"] == 0
    # fleet digest / SLO / goodput blocks ride every open-loop artifact
    assert art["percentiles"]["ttft_ms"]["p99"] is not None
    assert art["slo"]["configured"] is False and art["slo"]["pass"] is True
    assert 0.0 < art["goodput"]["goodput_frac"] <= 1.0
    assert art["goodput"]["replay_tokens"] == 0
    kv = art["kv_pool"]
    assert kv["n_blocks"] > 1 and kv["block_size"] == (8 if fleet else 16)
    assert 0.0 <= kv["occupancy"] <= 1.0
    assert 0.0 <= kv["fragmentation"] <= 1.0
    assert "prefix_hit_rate" in kv and "shed_reasons" in kv
    assert sum(kv["shed_reasons"].values()) == art["shed"]
    if fleet:
        assert art["replicas"] == 2
        assert router["session_hits"] > 0  # sticky sessions engaged
        assert len(art["compile_counts_per_replica"]) == 2
        # speculative block next to percentiles/slo/goodput: the ngram
        # drafter ran, acceptance reconciles, and the verify program is in
        # the per-replica compile census
        spec = art["speculative"]
        assert spec["drafter"] == "ngram" and spec["spec_k"] == 4
        assert spec["drafts"] == spec["accepted"] + spec["rollbacks"]
        assert 0.0 <= spec["accept_rate"] <= 1.0
        assert art["compile_counts"].get("verify", 0) <= 1
    else:
        # no flag: slots x max_len tokens of blocks + the garbage block
        assert kv["capacity_tokens"] == 2 * 64
        assert art["speculative"]["drafter"] == "off"
        assert art["speculative"]["drafts"] == 0
