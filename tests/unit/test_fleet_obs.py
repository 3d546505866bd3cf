"""Fleet-level request observability tests (tier-1).

The measuring-instrument invariants for the serving fleet:

- the mergeable fixed-bucket latency digest tracks exact nearest-rank
  percentiles within its bucket resolution and merges EXACTLY
  associatively (fleet percentiles independent of sharding/merge order);
- fleet P99 TTFT derived from the MERGED trace's wide events equals the
  live fleet digest equals the ``Serving/ttft_p99_ms`` monitor event,
  bit for bit under the virtual clock — 2 replicas, chunked prefill, and
  a forced preemption in the workload (and again on a TP=2 mesh);
- a preempted request's wide event records its replay tokens, and they
  reconcile with the fleet goodput accounting behind
  ``Serving/goodput_frac``;
- ``serving.slo`` targets grade the digests: violations emit the
  structured ``slo/violation`` event + ``Serving/slo_*`` scalars;
- ``Router.serve()`` completing flushes every replica tracer and forces a
  terminal metrics interval (short runs lose no tail spans/events);
- ``tools/fleet_report.py``: the planted/clean ``--selftest`` pair is the
  tier-1 exit-code gate (the health_report idiom), and the committed
  bench artifact's ``slo.pass`` field stays green;
- ``tools/trace_summary.py`` understands the merged fleet dir and flags
  ``--max-ttft-p99-ms`` regressions.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import Request, Router, VirtualClock
from deepspeed_tpu.telemetry import (LatencyDigest, digest_from_wide_events,
                                     evaluate_slo, load_jsonl)

from .conftest import make_replica, ref_tokens, tiny_cfg

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# 1. the digest itself: accuracy + exact merge algebra
# ---------------------------------------------------------------------------

def _exact_percentile(samples, q):
    s = sorted(samples)
    import math

    rank = max(1, int(math.ceil(q / 100.0 * len(s))))
    return s[rank - 1]


def test_digest_quantiles_track_exact_percentiles():
    """Seeded lognormal latencies: every digest quantile sits within one
    bucket (growth factor ~7.8%) of the exact nearest-rank percentile, and
    quantiles are monotone in q."""
    from deepspeed_tpu.telemetry.digest import DIGEST_GROWTH

    rng = np.random.RandomState(0)
    samples = np.exp(rng.normal(-1.0, 1.2, size=5000)).tolist()
    d = LatencyDigest()
    for s in samples:
        d.add(s)
    assert d.count == len(samples)
    last = 0.0
    for q in (10, 50, 90, 99, 99.9):
        got, exact = d.quantile(q), _exact_percentile(samples, q)
        # upper-edge representative: exact <= got <= exact * growth
        assert exact <= got <= exact * DIGEST_GROWTH * (1 + 1e-12), (q, got,
                                                                     exact)
        assert got >= last
        last = got


def test_digest_merge_is_exactly_associative():
    """Shard the same samples three ways: every merge order yields
    bucket-identical counts and BIT-identical quantiles — the property
    that makes fleet percentiles well-defined."""
    rng = np.random.RandomState(1)
    shards = [np.exp(rng.normal(0, 1, size=n)).tolist()
              for n in (400, 37, 1201)]

    def digest(samples):
        d = LatencyDigest()
        for s in samples:
            d.add(s)
        return d

    a, b, c = (digest(s) for s in shards)
    ab_c = LatencyDigest.merged([LatencyDigest.merged([a, b]), c])
    a_bc = LatencyDigest.merged([a, LatencyDigest.merged([b, c])])
    flat = digest([s for sh in shards for s in sh])
    assert ab_c.counts == a_bc.counts == flat.counts
    for q in (50, 90, 99):
        assert ab_c.quantile(q) == a_bc.quantile(q) == flat.quantile(q)
    # snapshot round-trip is exact too (fleet.json -> fleet_report)
    rt = LatencyDigest.from_snapshot(flat.snapshot())
    assert rt.counts == flat.counts and rt.count == flat.count


def test_digest_remove_and_count_above():
    d = LatencyDigest()
    for v in (0.1, 0.2, 0.4, 3.0):
        d.add(v)
    assert d.count_above(1.0) == 1      # only 3.0 sits above 1.0's bucket
    d.remove(3.0)
    assert d.count == 3 and d.count_above(1.0) == 0
    d.remove(99.0)  # never added: same bucket empty, no-op
    assert d.count == 3


def test_evaluate_slo_burn_rate_and_pass():
    """90 fast + 10 slow samples against a target between them: P99 over
    target -> violated, burn rate = 10% over / 1% budget = 10x."""
    d = LatencyDigest()
    for _ in range(90):
        d.add(0.010)           # 10 ms
    for _ in range(10):
        d.add(1.0)             # 1000 ms
    grade = evaluate_slo({"ttft_p99_ms": 500.0}, {"ttft": d})
    assert grade["configured"] and grade["violated"]["ttft"]
    assert not grade["pass"]
    assert grade["burn_rate"]["ttft"] == pytest.approx(10.0)
    ok = evaluate_slo({"ttft_p99_ms": 5000.0}, {"ttft": d})
    assert ok["pass"] and not ok["violated"]["ttft"]
    off = evaluate_slo({"ttft_p99_ms": 0.0}, {"ttft": d})
    assert not off["configured"] and off["pass"]


def test_evaluate_slo_not_fooled_by_bucket_quantization():
    """Every sample UNDER target, but the bucket upper edge (the reported
    quantile) lands above it: violation is judged at bucket granularity, so
    this must grade pass — no self-contradictory 'VIOLATED, burn rate 0'."""
    from deepspeed_tpu.telemetry.digest import (DIGEST_GROWTH, DIGEST_LO)

    i = LatencyDigest.bucket_index(0.240)
    v = DIGEST_LO * DIGEST_GROWTH ** (i + 0.2)       # low in bucket i
    target_s = DIGEST_LO * DIGEST_GROWTH ** (i + 0.6)  # same bucket, above v
    assert LatencyDigest.bucket_index(v) == \
        LatencyDigest.bucket_index(target_s) == i
    d = LatencyDigest()
    for _ in range(100):
        d.add(v)
    assert d.quantile(99) > target_s        # the upper edge IS over target
    grade = evaluate_slo({"ttft_p99_ms": target_s * 1e3}, {"ttft": d})
    assert not grade["violated"]["ttft"] and grade["pass"]
    assert grade["burn_rate"]["ttft"] == 0.0
    # one bucket higher IS a real violation
    d.add(DIGEST_LO * DIGEST_GROWTH ** (i + 1.5))
    worse = LatencyDigest()
    for _ in range(100):
        worse.add(DIGEST_LO * DIGEST_GROWTH ** (i + 1.5))
    bad = evaluate_slo({"ttft_p99_ms": target_s * 1e3}, {"ttft": worse})
    assert bad["violated"]["ttft"] and not bad["pass"]


def test_unhealthy_finish_retracts_queue_wait_digest():
    """The wide-event partition drops unhealthy requests from EVERY latency
    field; the live digests must retract the same samples or the
    trace==digest coherence gate false-alarms on any unhealthy shed."""
    from deepspeed_tpu.serving import Request, ServingMetrics, VirtualClock
    from deepspeed_tpu.serving.request import FINISH_UNHEALTHY

    clock = VirtualClock()
    m = ServingMetrics(2, clock)
    req = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=4)
    req.submit_time, req.prefill_start_time = 0.0, 2.0
    req.first_token_time = 3.0
    m.record_queue_wait(req)
    m.record_first_token(req)
    assert m.queue_wait_digest.count == 1 and m.ttft_digest.count == 1
    req.finish_reason = FINISH_UNHEALTHY
    m.record_finish(req)
    assert m.ttft_digest.count == 0
    assert m.queue_wait_digest.count == 0

    # epoch guard: a PRE-reset sample must not be retracted from the fresh
    # digest (it would decrement a different healthy request's bucket)
    stale = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=4)
    stale.submit_time, stale.prefill_start_time = 0.0, 2.0
    stale.first_token_time = 3.0
    m.record_queue_wait(stale)
    m.record_first_token(stale)
    m.reset_window()
    healthy = Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=4)
    healthy.submit_time, healthy.prefill_start_time = 0.0, 2.0
    healthy.first_token_time = 3.0       # same buckets as stale
    m.record_queue_wait(healthy)
    m.record_first_token(healthy)
    stale.finish_reason = FINISH_UNHEALTHY
    m.record_finish(stale)
    assert m.ttft_digest.count == 1      # healthy's sample survived
    assert m.queue_wait_digest.count == 1


# ---------------------------------------------------------------------------
# fleet fixtures
# ---------------------------------------------------------------------------

def make_fleet(engine, tmp, n=2, monitor=None, **kw):
    """N traced replicas (virtual clocks) behind a Router; the Router
    re-homes the per-replica trace dirs under <tmp>/fleet and writes the
    merged fleet files there at the end of serve()."""
    replicas = [make_replica(engine, tmp, "fleet", **kw) for _ in range(n)]
    return Router(replicas, monitor=monitor), os.path.join(str(tmp), "fleet")


def csv_monitor(engine, tmp):
    from deepspeed_tpu.monitor.monitor import MonitorMaster

    return MonitorMaster(engine.config.replace(
        csv_monitor={"enabled": True, "output_path": str(tmp),
                     "job_name": "mon"}))


def last_csv(tmp, name):
    rows = (tmp / "mon" / name).read_text().strip().splitlines()
    return float(rows[-1].split(",")[-1])


def load_wide(base):
    return {r["request_id"]: r
            for r in load_jsonl(os.path.join(base, "requests.jsonl"))}


PREEMPT_KW = dict(
    chunked_prefill={"enabled": True, "chunk_size": 8},
    kv_pool={"block_size": 8, "n_blocks": 6,
             "prefix_cache": False, "on_demand_growth": True})


# ---------------------------------------------------------------------------
# 2. the acceptance pin: trace == digest == monitor event
# ---------------------------------------------------------------------------

def test_fleet_trace_digest_monitor_coherence(engine, tmp_path):
    """2 replicas, chunked prefill, tight paged pool forcing >=1 preemption:
    fleet P99 TTFT from the merged trace's wide events == the live fleet
    digest == the Serving/ttft_p99_ms monitor event, EXACTLY; the preempted
    request's wide event carries its replay tokens and they reconcile with
    the goodput accounting behind Serving/goodput_frac. Greedy streams stay
    bitwise-equal to generate() with the whole instrument armed."""
    router, base = make_fleet(
        engine, tmp_path, n=2, monitor=csv_monitor(engine, tmp_path),
        slo={"ttft_p99_ms": 60000.0}, **PREEMPT_KW)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                    max_new_tokens=18, arrival_time=i * 0.25)
            for i in range(4)]
    finished, rejected, snap = router.run(reqs)
    assert len(finished) == 4 and not rejected
    preempted = sum(r["preempted"] for r in snap["replicas"])
    assert preempted > 0, "workload must force a preemption"

    # merged fleet dir written by serve()'s terminal edge
    assert sorted(f for f in os.listdir(base) if f.endswith(".json")
                  or f.endswith(".jsonl")) >= ["fleet.json"]
    wide = load_wide(base)
    assert set(wide) == {r.request_id for r in reqs}

    # --- the three-way P99 pin (exact) ----------------------------------
    d_trace = digest_from_wide_events(wide, "ttft")
    d_live = LatencyDigest.from_snapshot(snap["digests"]["ttft"])
    assert d_trace.counts == d_live.counts
    p99_trace = d_trace.quantile_ms(99)
    p99_live = snap["percentiles"]["ttft_ms"]["p99"]
    p99_event = last_csv(tmp_path, "Serving_ttft_p99_ms.csv")
    assert p99_trace == p99_live == p99_event
    # tpot leg of the same pin
    assert digest_from_wide_events(wide, "tpot").counts == \
        LatencyDigest.from_snapshot(snap["digests"]["tpot"]).counts

    # --- wide events: routing + lifecycle + goodput fields --------------
    for r in wide.values():
        assert r["state"] == "finished"
        assert r["routing"]["replica"] in (0, 1)
        assert set(r["routing"]["scores"]) <= {"0", "1"}
        assert r["breakdown"] is not None and r["ttft"] is not None
    pre = [r for r in wide.values() if r["preemptions"] > 0]
    assert pre and all(r["replay_tokens"] > 0 for r in pre)

    # --- replay tokens reconcile with goodput ---------------------------
    gp = snap["goodput"]
    assert sum(r["replay_tokens"] for r in wide.values()) \
        == gp["replay_tokens"] > 0
    assert sum(r["padding_tokens"] for r in wide.values()) \
        == gp["padding_tokens"]
    useful = gp["prefill_device_tokens"] + gp["decode_tokens"] \
        - gp["wasted_tokens"]
    assert gp["goodput_frac"] == pytest.approx(
        useful / (gp["prefill_device_tokens"] + gp["decode_tokens"]),
        abs=1e-4)
    # the monitor event carries the same (rounded) fleet goodput fraction
    assert last_csv(tmp_path, "Serving_goodput_frac.csv") == \
        snap["goodput"]["goodput_frac"]

    # --- the instrument never changed the math --------------------------
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref_tokens(engine, r))
    # fleet chrome trace has one process lane per source
    trace = json.load(open(os.path.join(base, "trace.json")))
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "process_name"}
    assert {"router", "replica0", "replica1"} <= names


def test_fleet_coherence_tp2_mesh(devices8, tmp_path):
    """The acceptance pin's TP=2 leg: two replicas over a model-sharded
    engine, chunked + paged growth on — coherence and parity hold on the
    sharded decode program too."""
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

    router, base = make_fleet(eng, tmp_path, n=2, **PREEMPT_KW)
    rng = np.random.RandomState(9)
    reqs = [Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                    max_new_tokens=14, arrival_time=i * 0.25)
            for i in range(3)]
    finished, rejected, snap = router.run(reqs)
    assert len(finished) == 3 and not rejected

    wide = load_wide(base)
    d_trace = digest_from_wide_events(wide, "ttft")
    assert d_trace.counts == LatencyDigest.from_snapshot(
        snap["digests"]["ttft"]).counts
    assert d_trace.quantile_ms(99) == snap["percentiles"]["ttft_ms"]["p99"]

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
# 3. SLO violation events + queue-wait breakdown + terminal flush
# ---------------------------------------------------------------------------

def test_slo_violation_emits_structured_event(engine, tmp_path):
    """An impossible TTFT target: the grade fails, Serving/slo_* scalars
    land in the monitor, and the router tracer carries the structured
    slo/violation instant with observed/target/burn-rate args."""
    router, base = make_fleet(engine, tmp_path, n=1,
                              monitor=csv_monitor(engine, tmp_path),
                              slo={"ttft_p99_ms": 0.001})
    rng = np.random.RandomState(2)
    reqs = [Request(prompt=rng.randint(0, 64, (6,)).astype(np.int32),
                    max_new_tokens=4, arrival_time=i * 1.0)
            for i in range(3)]
    _, _, snap = router.run(reqs)
    assert snap["slo"]["configured"] and not snap["slo"]["pass"]
    assert snap["slo"]["violated"]["ttft"]
    assert snap["slo"]["burn_rate"]["ttft"] > 1.0
    assert router.metrics.slo_violations >= 1
    assert last_csv(tmp_path, "Serving_slo_violations.csv") >= 1.0
    assert last_csv(tmp_path, "Serving_slo_burn_rate.csv") > 1.0
    viol = [e for e in router.tracer.events if e["name"] == "slo/violation"]
    assert viol and viol[-1]["args"]["metric"] == "ttft"
    assert viol[-1]["args"]["observed_p99_ms"] > \
        viol[-1]["args"]["target_ms"]


def test_queue_wait_breakdown_is_exact_under_virtual_clock(engine, tmp_path):
    """No chunking/preemption: a wide event's TTFT decomposes EXACTLY as
    queue_wait + prefill span time (virtual clock, single-shot prefill) —
    the breakdown is attribution, not estimation."""
    router, base = make_fleet(engine, tmp_path, n=1, n_slots=1)
    rng = np.random.RandomState(3)
    reqs = [Request(prompt=rng.randint(0, 64, (6,)).astype(np.int32),
                    max_new_tokens=5, arrival_time=0.0)
            for _ in range(3)]     # burst: later ones queue behind slot 0
    _, _, snap = router.run(reqs)
    wide = load_wide(base)
    waits = []
    for r in wide.values():
        b = r["breakdown"]
        assert abs(r["ttft"] - (b["queue_wait"] + b["prefill"])) < 1e-9
        waits.append(r["queue_wait"])
    assert max(waits) > 0      # the burst actually queued someone
    # queue-wait digest saw the same samples (fleet percentile leg)
    d = digest_from_wide_events(wide, "queue_wait")
    assert d.counts == LatencyDigest.from_snapshot(
        snap["digests"]["queue_wait"]).counts


def test_short_run_loses_no_tail_events(engine, tmp_path):
    """ONE request, fewer scheduler steps than monitor_interval: without
    the terminal edge the rate-limited cadence would swallow every event
    and the replica tracer would never flush. serve() must land both."""
    router, base = make_fleet(engine, tmp_path, n=1,
                              monitor=csv_monitor(engine, tmp_path),
                              monitor_interval=1000)
    req = Request(prompt=np.arange(6, dtype=np.int32), max_new_tokens=3)
    finished, _, _ = router.run([req])
    assert len(finished) == 1
    # replica tracer flushed to its re-homed dir
    spans = load_jsonl(os.path.join(base, "replica0", "spans.jsonl"))
    assert any(e["name"] == "request/finish" for e in spans)
    # terminal metrics interval reached the monitor despite interval=1000
    assert (tmp_path / "mon" / "Serving_router_routed.csv").exists()
    assert last_csv(tmp_path, "Serving_router_routed.csv") == 1.0
    assert (tmp_path / "mon" / "Serving_ttft_p99_ms.csv").exists()
    # and the merged wide event exists
    assert load_wide(base)[req.request_id]["state"] == "finished"


# ---------------------------------------------------------------------------
# 4. the CLIs: fleet_report gate + trace_summary fleet mode
# ---------------------------------------------------------------------------

def test_fleet_report_selftest_pair():
    """The tier-1 exit-code gate (health_report's planted/clean idiom):
    the planted fleet violates its TTFT SLO -> exit 3; clean -> exit 0."""
    cli = os.path.join(REPO, "tools", "fleet_report.py")
    p = subprocess.run(
        [sys.executable, cli, "--selftest", "planted", "--fail-on", "slo"],
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 3, p.stdout + p.stderr
    assert "VIOLATED" in p.stdout and "replay" in p.stdout
    c = subprocess.run(
        [sys.executable, cli, "--selftest", "clean", "--fail-on", "slo"],
        capture_output=True, text=True, timeout=120)
    assert c.returncode == 0, c.stdout + c.stderr


def test_fleet_report_and_trace_summary_on_real_run(engine, tmp_path,
                                                    capsys):
    """Both CLIs read a real merged fleet dir: fleet_report grades the SLO
    (exit 3 on an impossible read-time target, 0 on a generous one, digest
    coherence verified against fleet.json) and trace_summary's fleet mode
    flags --max-ttft-p99-ms."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import fleet_report
    import trace_summary

    router, base = make_fleet(engine, tmp_path, n=2, **PREEMPT_KW)
    rng = np.random.RandomState(5)
    reqs = [Request(prompt=rng.randint(0, 64, (8,)).astype(np.int32),
                    max_new_tokens=12, arrival_time=i * 0.5)
            for i in range(4)]
    finished, _, _ = router.run(reqs)
    assert len(finished) == 4

    out_json = tmp_path / "fleet_report.json"
    rc = fleet_report.main([base, "--ttft-p99-ms", "1e9", "--fail-on",
                            "slo", "--json", str(out_json)])
    assert rc == 0
    report = json.loads(out_json.read_text())
    assert report["fleet"]["finished"] == 4
    assert report["critical_paths"] and report["provenance"]["git_sha"]
    assert all(v is True for v in report["digest_coherence"].values())
    # re-grade with an impossible target: the gate bites
    assert fleet_report.main([base, "--ttft-p99-ms", "0.001",
                              "--fail-on", "slo"]) == 3

    assert trace_summary.main([base]) == 0
    cap = capsys.readouterr().out
    assert "fleet trace: 4 requests" in cap
    assert "latency attribution" in cap
    assert trace_summary.main(
        [base, "--max-ttft-p99-ms", "0.001", "--fail-on-flag"]) == 3


def test_committed_artifact_slo_pass_gate():
    """CI wiring: the committed bench artifact went through the digest/SLO
    path and its slo.pass field is green (regressing the serving tier past
    its targets shows up as a diff in a committed file)."""
    art = json.load(open(os.path.join(
        REPO, "tools", "artifacts", "serving_open_loop_tiny_cpu.json")))
    assert art["slo"]["configured"] is True
    assert art["slo"]["pass"] is True
    assert art["percentiles"]["ttft_ms"]["p99"] is not None
    assert art["goodput"]["goodput_frac"] > 0
    assert art["goodput"]["replay_tokens"] == 0
    assert "burn_rate" in art["slo"]
