"""Speculative decoding subsystem tests (tier-1).

The acceptance invariants (ISSUE 14 / ROADMAP item 3):

- greedy streams with speculation enabled (n-gram drafter, k >= 4) are
  BITWISE equal to sequential ``generate()`` and to the non-speculative
  paged serving path — staggered arrivals, mixed lengths, single device and
  TP=2, including a FORCED rollback (a drafter that is always wrong) and a
  forced preemption mid-speculation;
- the draft and verify programs each compile exactly once; verify costs ONE
  decode step, so the virtual-clock accepted-tokens-per-step is strictly
  > 1 on a repetitive workload and the chunked-prefill worst inter-token
  gap bound (PR 12) is unchanged;
- per-slot rng streams are provably unperturbed by speculation: a seeded
  sampled request co-batched with speculating slots emits the identical
  stream with speculation on, off, or toggled off mid-run;
- rollback is stale-KV safe at block granularity: rejected candidate rows
  never become visible, fully-stale blocks are released/scrubbed (counted),
  and a stream decoded after a rollback on a REUSED pool is bitwise equal
  to a pristine pool;
- Serving/spec_* monitor events are coherent with
  ``snapshot()["speculative"]`` and the per-request wide-event counts
  reconcile with the fleet counters.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.config import ServingConfig
from deepspeed_tpu.config.base import ConfigError
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (NgramDrafter, Request, RequestState,
                                   SamplingParams, ServingEngine,
                                   VirtualClock)

from .conftest import (make_paged, ref_tokens, staggered_requests,
                       tiny_cfg)


def make_spec(engine, drafter="ngram", k=4, kv_pool=None, speculative=None,
              **kw):
    spec = dict(enabled=True, drafter=drafter, k=k)
    spec.update(speculative or {})
    return make_paged(engine, kv_pool, speculative=spec, **kw)


def repetitive_prompt(period=4, repeats=5, seed=0):
    """A periodic prompt: exactly where prompt-lookup drafting pays."""
    base = np.random.RandomState(seed).randint(0, 64, (period,))
    return np.tile(base, repeats).astype(np.int32)


class WrongDrafter:
    """Always proposes token 63 — (almost) always rejected: the forced-
    rollback fixture. Parity must hold for ANY drafter, because accepted
    output is the target's own argmax by construction."""

    name = "wrong"

    def propose(self, wanted):
        return {s: np.full((cap,), 63, np.int32)
                for s, (_h, cap) in wanted.items()}

    def release(self, slot):
        pass

    def compile_counts(self):
        return {}


# ---------------------------------------------------------------------------
# config surface + the host-side drafter
# ---------------------------------------------------------------------------

def test_config_validation():
    # speculation needs no pool key: the one KV store has the blocks
    # rollback rides on
    assert ServingConfig(speculative={"enabled": True}).speculative.enabled
    with pytest.raises(ConfigError):
        ServingConfig(speculative={"enabled": True, "drafter": "oracle"})
    with pytest.raises(ConfigError):
        ServingConfig(speculative={"enabled": True, "k": 0})


def test_ngram_drafter_prompt_lookup():
    from deepspeed_tpu.config import SpeculativeConfig

    d = NgramDrafter(SpeculativeConfig(enabled=True, k=4, ngram=2))
    hist = np.array([1, 2, 3, 4, 9, 9, 1, 2], np.int32)
    # last 2 tokens [1, 2] match at position 0 -> propose [3, 4, 9, 9]
    out = d.propose({0: (hist, 4)})
    np.testing.assert_array_equal(out[0], [3, 4, 9, 9])
    # cap truncates
    out = d.propose({0: (hist, 2)})
    np.testing.assert_array_equal(out[0], [3, 4])
    # no earlier occurrence -> nothing proposed
    assert d.propose({0: (np.arange(8, dtype=np.int32), 4)}) == {}
    # the MOST RECENT earlier occurrence wins
    hist2 = np.array([1, 2, 7, 5, 1, 2, 8, 1, 2], np.int32)
    np.testing.assert_array_equal(d.propose({0: (hist2, 3)})[0], [8, 1, 2])


# ---------------------------------------------------------------------------
# bitwise parity (the acceptance gate)
# ---------------------------------------------------------------------------

def test_spec_greedy_parity_and_compiles_once(engine):
    """Speculative serving == non-speculative paged serving == sequential
    generate(), token for token, under staggered arrivals and mixed
    lengths — and the verify program compiles exactly once while drafts of
    every length (including none) dispatch."""
    mk = lambda: staggered_requests(np.random.RandomState(0), 6)
    spec_reqs, plain_reqs = mk(), mk()

    sv = make_spec(engine, n_slots=2)
    list(sv.serve(spec_reqs))
    pv = make_paged(engine, n_slots=2)
    list(pv.serve(plain_reqs))

    assert all(r.state is RequestState.FINISHED for r in spec_reqs)
    for sr, pr in zip(spec_reqs, plain_reqs):
        assert sr.tokens == pr.tokens          # spec == non-spec, bitwise
        np.testing.assert_array_equal(np.asarray(sr.tokens),
                                      ref_tokens(engine, sr))

    counts = sv.compile_counts()
    assert counts["verify"] == 1, counts
    assert counts["decode"] == 1, counts
    assert counts["insert"] == 1, counts
    # speculation actually engaged (generated cycles give the n-gram
    # drafter material even on random prompts) and the books balance
    m = sv.metrics
    assert m.drafted_tokens > 0
    assert m.drafted_tokens == m.accepted_tokens + m.rolled_back_tokens
    assert sum(r.drafted_tokens for r in spec_reqs) == m.drafted_tokens


def test_spec_accepted_tokens_per_step_strictly_gt_1(engine):
    """THE virtual-clock win: on a repetitive workload the accepted drafts
    make effective decode tokens per dispatched step strictly > 1 (each
    verify costs ONE decode step), and the stream is still bitwise
    generate()'s."""
    req = Request(prompt=repetitive_prompt(), max_new_tokens=24)
    sv = make_spec(engine, n_slots=2)
    list(sv.serve([req]))
    np.testing.assert_array_equal(np.asarray(req.tokens),
                                  ref_tokens(engine, req))
    m = sv.metrics
    assert m.accepted_tokens_per_step > 1.0, m.speculative_snapshot()
    snap = sv.metrics.snapshot()["speculative"]
    assert snap["accepted_tokens_per_step"] == round(
        m.accepted_tokens_per_step, 4)
    # fewer dispatches than tokens: the whole point
    assert m.decode_dispatches < len(req.tokens)


def test_spec_forced_rollback_bitwise_on_reused_pool(engine):
    """Forced rollback (a drafter that is always wrong): every draft is
    rejected, the stream stays bitwise generate()'s, the rejected suffix
    rows are scrubbed at block granularity (scrubbed_blocks counts), and a
    stream decoded AFTER the rollbacks on the reused pool equals a
    pristine pool — the PR 7 stale-KV-leak pin extended to the speculative
    rollback path."""
    pool_cfg = {"n_blocks": 4, "prefix_cache": False}
    short = np.random.RandomState(1).randint(0, 64, (5,)).astype(np.int32)

    fresh = make_spec(engine, n_slots=1, kv_pool=pool_cfg,
                      scrub_freed_slots=True)
    fresh._drafter = WrongDrafter()
    pristine = Request(prompt=short, max_new_tokens=6)
    list(fresh.serve([pristine]))

    sv = make_spec(engine, n_slots=1, kv_pool=pool_cfg,
                   scrub_freed_slots=True)
    sv._drafter = WrongDrafter()
    long_req = Request(
        prompt=np.random.RandomState(1).randint(0, 64, (20,)).astype(np.int32),
        max_new_tokens=20)
    list(sv.serve([long_req]))
    np.testing.assert_array_equal(np.asarray(long_req.tokens),
                                  ref_tokens(engine, long_req))
    assert sv.metrics.rolled_back_tokens > 0
    assert sv.metrics.accepted_tokens == 0   # token 63 never the argmax here
    assert sv.pool_mgr.scrubbed_blocks > 0

    reused = Request(prompt=short, max_new_tokens=6)
    list(sv.serve([reused]))
    assert reused.tokens == pristine.tokens
    np.testing.assert_array_equal(np.asarray(reused.tokens),
                                  ref_tokens(engine, reused))


def test_spec_rollback_releases_grown_blocks(engine):
    """Under on-demand growth a block grown to cover candidate rows that
    all get rejected lies entirely past the rolled-back cursor: it is
    RELEASED back to the pool (rolled_back_blocks counts, the scrub rides
    the last-ref drop) instead of sitting stale until the request ends."""
    sv = make_spec(engine, n_slots=1,
                   kv_pool={"n_blocks": 6, "on_demand_growth": True,
                            "prefix_cache": False},
                   scrub_freed_slots=True)
    sv._drafter = WrongDrafter()
    req = Request(
        prompt=np.random.RandomState(2).randint(0, 64, (14,)).astype(np.int32),
        max_new_tokens=24)
    list(sv.serve([req]))
    np.testing.assert_array_equal(np.asarray(req.tokens),
                                  ref_tokens(engine, req))
    stats = sv.pool_mgr.stats()
    assert stats["rolled_back_blocks"] > 0
    assert stats["scrubbed_blocks"] > 0
    assert stats["free_blocks"] == sv.pool_mgr.allocatable  # all came back


def test_spec_eos_mid_speculation(engine):
    """An EOS inside an accepted draft run stops the stream AT the eos
    token, exactly like generate()'s truncation — the in-graph acceptance
    caps emission at the first eos."""
    prompt = repetitive_prompt(period=3, repeats=5, seed=3)
    ref = ref_tokens(engine, Request(prompt=prompt, max_new_tokens=12))
    eos = int(ref[5])
    sv = make_spec(engine, n_slots=2)
    req = Request(prompt=prompt, max_new_tokens=12, eos_token_id=eos)
    list(sv.serve([req]))
    assert req.finish_reason == "eos"
    cut = list(ref).index(eos) + 1
    np.testing.assert_array_equal(np.asarray(req.tokens), ref[:cut])


def test_spec_int8_pool_serves_end_to_end(engine):
    """int8 blocks + speculation: the quantizing writeback handles the k+1
    candidate rows (garbage-redirect included) and streams complete with
    finite logits. The bitwise pin does not apply here — the verify reads
    its fresh rows at full precision where sequential decode reads them
    through the int8 round trip, the pool's own ~2e-4 tolerance story."""
    sv = make_spec(engine, n_slots=2, kv_pool={"kv_dtype": "int8"})
    reqs = [Request(prompt=repetitive_prompt(), max_new_tokens=16),
            Request(prompt=np.random.RandomState(3).randint(
                0, 64, (9,)).astype(np.int32), max_new_tokens=8,
                arrival_time=1.0)]
    list(sv.serve(reqs))
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert all(len(r.tokens) >= 1 for r in reqs)
    assert sv.metrics.nonfinite_logit_steps == 0
    assert sv._state["k"].dtype == jnp.int8


def test_spec_unhealthy_shed_keeps_draft_books():
    """A verify step whose logits go non-finite sheds the slot with reason
    unhealthy_slot (never streaming the poisoned run) — and the draft
    accounting still balances: drafted == accepted + rolled_back on every
    exit path, including the shed (regression: the shed used to skip the
    acceptance bookkeeping)."""
    import jax.numpy as jnp2

    from deepspeed_tpu.serving import FINISH_UNHEALTHY

    eng = deepspeed_tpu.init_inference(
        CausalLM(tiny_cfg()), dtype="float32", max_tokens=64,
        prompt_bucket_size=16, health={"enabled": True})
    sv = make_spec(eng, n_slots=1)
    req = Request(prompt=repetitive_prompt(), max_new_tokens=24)
    sv.submit(req)
    steps = 0
    # run healthy until speculation has engaged at least once
    while sv.metrics.drafted_tokens == 0 \
            and req.state is not RequestState.FINISHED and steps < 50:
        sv.step()
        steps += 1
    assert sv.metrics.drafted_tokens > 0
    assert req.state is RequestState.RUNNING
    # poison the final layernorm: the next verify's logits go NaN while
    # its drafts were already collected and counted
    eng.params["ln_f"]["scale"] = eng.params["ln_f"]["scale"] * jnp2.nan
    while req.state is not RequestState.FINISHED and steps < 100:
        sv.step()
        steps += 1
    assert req.finish_reason == FINISH_UNHEALTHY
    m = sv.metrics
    assert m.unhealthy_slots == 1
    assert m.drafted_tokens == m.accepted_tokens + m.rolled_back_tokens
    assert req.drafted_tokens == req.accepted_tokens + req.rolled_back_tokens
    eng.destroy()


# ---------------------------------------------------------------------------
# rng isolation: sampled streams cannot tell verify from decode
# ---------------------------------------------------------------------------

def test_spec_sampled_streams_unperturbed(engine):
    """A seeded sampled request co-batched with speculating greedy slots
    emits the IDENTICAL stream with speculation on, off, or disabled
    mid-run: both the decode and verify programs split each slot's rng
    exactly once per dispatch, and sampled slots never carry drafts."""
    def run(spec, toggle_at=None):
        sv = make_spec(engine, n_slots=2) if spec \
            else make_paged(engine, n_slots=2)
        s_req = Request(prompt=repetitive_prompt(seed=4)[:10],
                        max_new_tokens=8,
                        sampling=SamplingParams(temperature=1.0, top_k=8,
                                                seed=7))
        g_req = Request(prompt=repetitive_prompt(seed=4), max_new_tokens=20)
        if toggle_at is None:
            list(sv.serve([s_req, g_req]))
        else:
            sv.submit(s_req)
            sv.submit(g_req)
            steps = 0
            while (sv._slots or sv.queue.depth or sv._prefill_jobs) \
                    and steps < 200:
                sv.step()
                steps += 1
                if steps == toggle_at:
                    sv.set_speculation(False)
        return s_req, g_req, sv

    s_on, g_on, sv_on = run(True)
    s_off, g_off, _ = run(False)
    s_mid, g_mid, _ = run(True, toggle_at=4)
    assert sv_on.metrics.accepted_tokens > 0     # speculation engaged
    assert s_on.tokens == s_off.tokens == s_mid.tokens
    assert g_on.tokens == g_off.tokens == g_mid.tokens
    np.testing.assert_array_equal(np.asarray(g_on.tokens),
                                  ref_tokens(engine, g_on))
    # the sampled stream actually sampled (not a greedy collapse)
    assert s_on.tokens != g_on.tokens[:len(s_on.tokens)]


# ---------------------------------------------------------------------------
# draft model sharing the mesh
# ---------------------------------------------------------------------------

def test_spec_model_drafter_parity_and_compiles_once(engine):
    """The draft-model drafter (separate params, own tiny dense cache,
    same mesh): greedy parity holds regardless of what it proposes, its
    extend/propose programs each compile exactly once, and on a workload
    its 1-layer twin predicts well it multiplies tokens per dispatch."""
    reqs = [Request(prompt=repetitive_prompt(seed=5), max_new_tokens=20),
            Request(prompt=repetitive_prompt(seed=6)[:14],
                    max_new_tokens=8, arrival_time=1.0)]
    sv = make_spec(engine, drafter="model", n_slots=2)
    list(sv.serve(reqs))
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))
    counts = sv.compile_counts()
    assert counts["verify"] == 1, counts
    assert counts["draft_ingest"] == 1, counts
    assert counts["draft_propose"] == 1, counts
    assert sv.metrics.drafted_tokens > 0


# ---------------------------------------------------------------------------
# scheduler coexistence: growth/preemption + chunked prefill
# ---------------------------------------------------------------------------

def test_spec_preempt_mid_speculation_resume_bitwise(engine):
    """Pool exhaustion preempts a speculating request back to the queue;
    the resume replay + re-splice continues the stream bitwise (greedy
    acceptance is position-exact, so speculation before, during and after
    the round trip changes nothing)."""
    def run(spec):
        sv = (make_spec if spec else make_paged)(
            engine, n_slots=2, max_prefills_per_step=2,
            kv_pool={"n_blocks": 6, "on_demand_growth": True,
                     "prefix_cache": False})
        reqs = [Request(prompt=np.tile(
            np.array([3 + i, 11, 6], np.int32), 4), max_new_tokens=30)
            for i in range(2)]
        list(sv.serve(reqs))
        return reqs, sv

    spec_reqs, sv = run(True)
    plain_reqs, pv = run(False)
    assert sv.metrics.preempted >= 1          # forced mid-speculation
    assert sv.metrics.accepted_tokens > 0
    for sr, pr in zip(spec_reqs, plain_reqs):
        assert sr.tokens == pr.tokens
        np.testing.assert_array_equal(np.asarray(sr.tokens),
                                      ref_tokens(engine, sr))


def test_spec_inter_token_gap_bound_unchanged(engine):
    """Speculation never worsens the PR 12 worst inter-token gap bound:
    with chunked prefill interleaving a max-length prompt, a speculating
    decoder's gaps stay under chunk_bucket * prefill_cost + decode_cost —
    a verify is ONE decode-priced dispatch that emits >= 1 token."""
    def max_gap(events, rid):
        ts = [e.time for e in events if e.request_id == rid]
        return max(b - a for a, b in zip(ts, ts[1:]))

    rng = np.random.RandomState(6)
    decoder = Request(prompt=repetitive_prompt(seed=7)[:8],
                      max_new_tokens=20, arrival_time=0.0)
    big = Request(prompt=rng.randint(0, 64, (40,)).astype(np.int32),
                  max_new_tokens=4, arrival_time=3.0)
    sv = make_spec(engine, n_slots=2,
                   chunked_prefill={"enabled": True, "chunk_size": 16,
                                    "decode_steps_between_chunks": 1})
    events = list(sv.serve([decoder, big]))
    ceiling = 16 * sv.cfg.virtual_prefill_cost_per_token \
        + sv.cfg.virtual_decode_step_cost
    assert sv.metrics.accepted_tokens > 0
    assert max_gap(events, decoder.request_id) <= ceiling + 1e-9
    np.testing.assert_array_equal(np.asarray(decoder.tokens),
                                  ref_tokens(engine, decoder))
    np.testing.assert_array_equal(np.asarray(big.tokens),
                                  ref_tokens(engine, big))


# ---------------------------------------------------------------------------
# observability: events == snapshot == per-request wide-event counts
# ---------------------------------------------------------------------------

def test_spec_monitor_events_coherent_with_snapshot(engine, tmp_path):
    """Serving/spec_accept_rate + Serving/spec_accepted_tokens_per_step
    flow through the monitor fan-out and equal snapshot()["speculative"]
    exactly (the PR 4 trace==metrics pin)."""
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    mcfg = engine.config.replace(
        csv_monitor={"enabled": True, "output_path": str(tmp_path),
                     "job_name": "spec_test"})
    sv = ServingEngine(
        engine,
        serving_config=ServingConfig(
            n_slots=2, virtual_clock=True, monitor_interval=1,
            kv_pool={"block_size": 16},
            speculative={"enabled": True, "drafter": "ngram", "k": 4}),
        clock=VirtualClock(), monitor=MonitorMaster(mcfg))
    req = Request(prompt=repetitive_prompt(), max_new_tokens=20)
    list(sv.serve([req]))
    sv.metrics.emit_events()
    snap = sv.metrics.snapshot()["speculative"]
    outdir = tmp_path / "spec_test"
    rows = (outdir / "Serving_spec_accept_rate.csv") \
        .read_text().strip().splitlines()
    assert float(rows[-1].split(",")[-1]) == pytest.approx(
        snap["accept_rate"], abs=1e-9)
    rows = (outdir / "Serving_spec_accepted_tokens_per_step.csv") \
        .read_text().strip().splitlines()
    assert float(rows[-1].split(",")[-1]) == pytest.approx(
        sv.metrics.accepted_tokens_per_step, abs=1e-9)
    assert snap["accepted_tokens_per_step"] > 1.0


def test_spec_wide_event_counts_reconcile(engine):
    """The request/finish instant carries drafted/accepted/rolled_back
    verbatim; summed over requests they reconcile with the fleet counters
    (so the PR 13 wide events attribute the speculative win per request
    without re-deriving engine state)."""
    from deepspeed_tpu.telemetry import SpanTracer
    from deepspeed_tpu.telemetry.fleet import build_wide_events

    rng = np.random.RandomState(8)
    reqs = [Request(prompt=repetitive_prompt(seed=9 + i),
                    max_new_tokens=int(rng.randint(8, 20)),
                    arrival_time=i * 0.5) for i in range(4)]
    clock = VirtualClock()
    sv = ServingEngine(
        engine,
        serving_config=ServingConfig(
            n_slots=2, virtual_clock=True,
            kv_pool={"block_size": 16},
            speculative={"enabled": True, "drafter": "ngram", "k": 4}),
        clock=clock, tracer=SpanTracer(enabled=True, clock=clock.now))
    list(sv.serve(reqs))
    m = sv.metrics
    assert m.drafted_tokens > 0
    assert sum(r.drafted_tokens for r in reqs) == m.drafted_tokens
    assert sum(r.accepted_tokens for r in reqs) == m.accepted_tokens
    assert sum(r.rolled_back_tokens for r in reqs) == m.rolled_back_tokens
    wide = build_wide_events(sv.tracer.events)
    assert sum(w["drafted_tokens"] for w in wide.values()) \
        == m.drafted_tokens
    assert sum(w["accepted_tokens"] for w in wide.values()) \
        == m.accepted_tokens
    assert sum(w["rolled_back_tokens"] for w in wide.values()) \
        == m.rolled_back_tokens
    for r in reqs:
        assert wide[r.request_id]["accepted_tokens"] == r.accepted_tokens


# ---------------------------------------------------------------------------
# TP=2 mesh (incl. forced rollback + forced preemption mid-speculation)
# ---------------------------------------------------------------------------

def test_spec_tp_mesh_parity(devices8):
    """TP=2 slot pool with speculation: the verify program shards its kv
    heads over the model axis like decode, compiles once, and greedy
    streams — through growth, a forced preemption and natural rollbacks —
    match the single-device reference bitwise."""
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
                     "max_prefills_per_step": 2,
                     "kv_pool": {"block_size": 16,
                                 "n_blocks": 6, "prefix_cache": False,
                                 "on_demand_growth": True},
                     "speculative": {"enabled": True, "drafter": "ngram",
                                     "k": 4}}}), mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)

    reqs = [Request(prompt=np.tile(np.array([3 + i, 11, 6], np.int32), 4),
                    max_new_tokens=30) for i in range(2)]
    list(eng.serve(reqs))
    sv = eng.serving
    assert sv.compile_counts()["verify"] == 1
    assert sv.metrics.accepted_tokens > 0
    assert sv.metrics.preempted >= 1       # forced preemption mid-spec

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                       max_tokens=64)
    raw.params = values
    for r in reqs:
        ref = np.asarray(raw.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()
