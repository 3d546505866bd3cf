"""Unified step-trace layer: span tracer, engine/serving wiring, summary CLI.

Tier-1 coverage for the telemetry substrate every ROADMAP perf item is
judged against: span nesting/ordering semantics, Chrome-trace schema
validity (the file must load in Perfetto), device-fence plumbing, the
one-time unsynced-monitor warning, engine step-phase spans + checkpoint
spans + trace files on disk, serving TTFT/TPOT reproduced FROM THE TRACE
bit-identically to ``ServingMetrics`` under the virtual clock (the
acceptance bar), and ``tools/trace_summary.py``'s table + budget flagging.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.telemetry import (SpanTracer, counters_by_step, load_jsonl,
                                     phase_table, request_metrics)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))


class FakeClock:
    """Deterministic clock: each call advances by ``tick``."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------

def test_span_nesting_ordering_and_depth():
    tr = SpanTracer(clock=FakeClock())
    with tr.span("outer", cat="t", step=1):
        with tr.span("inner_a", cat="t"):
            pass
        with tr.span("inner_b", cat="t"):
            tr.instant("mark", note="x")
    # events append at span END: children before parents
    names = [e["name"] for e in tr.events]
    assert names == ["inner_a", "mark", "inner_b", "outer"]
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["inner_a"]["parent"] == "outer"
    assert by_name["inner_b"]["parent"] == "outer"
    assert by_name["outer"]["parent"] is None
    assert by_name["inner_a"]["depth"] == 1
    assert by_name["outer"]["depth"] == 0
    # spans nest in time: child windows inside the parent window
    o, a = by_name["outer"], by_name["inner_a"]
    assert o["ts"] < a["ts"]
    assert a["ts"] + a["dur"] <= o["ts"] + o["dur"]
    # seq strictly increases in emission order
    assert [e["seq"] for e in tr.events] == sorted(e["seq"] for e in tr.events)


def test_disabled_tracer_records_nothing():
    tr = SpanTracer(enabled=False)
    with tr.span("x") as sp:
        sp.fence(None)
        tr.instant("y")
    assert tr.events == []
    assert tr.flush() is None


def test_max_events_drops_and_counts():
    tr = SpanTracer(clock=FakeClock(), max_events=2)
    for i in range(5):
        tr.instant(f"e{i}")
    assert len(tr.events) == 2
    assert tr.dropped == 3
    assert tr.to_chrome_trace()["otherData"]["dropped_events"] == 3


def test_chrome_trace_schema_valid(tmp_path):
    tr = SpanTracer(clock=FakeClock())
    with tr.span("phase", cat="train", step=3):
        tr.instant("tick")
    path = tr.write_chrome_trace(str(tmp_path / "trace.json"))
    blob = json.load(open(path))  # must round-trip as plain JSON
    evs = blob["traceEvents"]
    assert evs[0]["ph"] == "M"  # process_name metadata
    complete = [e for e in evs if e["ph"] == "X"]
    assert len(complete) == 1
    for e in complete:
        # the Trace Event Format required keys for complete events
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid"} <= set(e)
        assert e["dur"] >= 0
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and inst[0]["s"] == "t"
    # span ts/dur are microseconds of the 1-tick clock
    assert complete[0]["dur"] == pytest.approx(2e6)


def test_jsonl_incremental_flush(tmp_path):
    tr = SpanTracer(clock=FakeClock(), output_path=str(tmp_path), job_name="j")
    with tr.span("a"):
        pass
    tr.flush()
    with tr.span("b"):
        pass
    tr.flush()
    events = load_jsonl(str(tmp_path / "j" / "spans.jsonl"))
    assert [e["name"] for e in events] == ["a", "b"]  # appended, not doubled
    # the chrome trace is rewritten whole and stays complete
    blob = json.load(open(tmp_path / "j" / "trace.json"))
    assert len([e for e in blob["traceEvents"] if e["ph"] == "X"]) == 2


def test_sync_span_runs_fence_and_marks_event():
    calls = []
    tr = SpanTracer(clock=FakeClock(), sync_fn=lambda: calls.append("fn"))
    with tr.span("synced", sync=True):
        pass
    with tr.span("fenced", sync=True) as sp:
        sp.fence(jnp.ones((2,)))
    with tr.span("unsynced"):
        pass
    assert calls == ["fn"]  # explicit fence value bypasses sync_fn
    by_name = {e["name"]: e for e in tr.events}
    assert by_name["synced"]["args"].get("synced") is True
    assert by_name["fenced"]["args"].get("synced") is True
    assert "synced" not in by_name["unsynced"]["args"]


# ---------------------------------------------------------------------------
# timers: opt-in device sync + the one-time unsynced-monitor warning
# ---------------------------------------------------------------------------

def test_timer_sync_fn_called_on_stop():
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer

    calls = []
    timers = SynchronizedWallClockTimer(sync_fn=lambda: calls.append(1))
    timers("fwd").start()
    timers("fwd").stop()
    assert len(calls) == 1
    tput = ThroughputTimer(batch_size=8, start_step=0,
                           sync_fn=lambda: calls.append(2))
    tput.start()
    tput.stop(global_step=True, report_speed=False)
    assert calls[-1] == 2


def test_unsynced_monitor_warning_fires_once(monkeypatch):
    from deepspeed_tpu.utils import timer as timer_mod

    warnings = []
    monkeypatch.setattr(timer_mod, "_UNSYNCED_MONITOR_WARNED", False)
    monkeypatch.setattr(timer_mod.logger, "warning",
                        lambda msg, *a: warnings.append(msg % a if a else msg))

    written = []

    class Sink:
        def write_events(self, events):
            written.extend(events)

    timers = timer_mod.SynchronizedWallClockTimer()  # no sync_fn
    timers("fwd").start(); timers("fwd").stop()
    timers.write_events(Sink(), ["fwd"], step=1)
    timers("fwd").start(); timers("fwd").stop()
    timers.write_events(Sink(), ["fwd"], step=2)
    assert len([w for w in warnings if "UNSYNCED" in w]) == 1
    assert [n for n, _, _ in written] == ["Time/fwd_ms", "Time/fwd_ms"]

    # synced timers never warn
    warnings.clear()
    monkeypatch.setattr(timer_mod, "_UNSYNCED_MONITOR_WARNED", False)
    synced = timer_mod.SynchronizedWallClockTimer(sync_fn=lambda: None)
    synced("fwd").start(); synced("fwd").stop()
    synced.write_events(Sink(), ["fwd"], step=1)
    assert not warnings


# ---------------------------------------------------------------------------
# engine wiring: step phases, checkpoint spans, trace files
# ---------------------------------------------------------------------------

def _tiny_engine(tmp_path, devices8, **cfg_extra):
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig(
        vocab_size=64, max_seq_len=32, n_layers=2, n_heads=2, d_model=32,
        d_ff=64, compute_dtype=jnp.float32))
    cfg = {
        "train_batch_size": 8,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "steps_per_print": 10 ** 9,
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "job_name": "t", "device_sync": True},
    }
    cfg.update(cfg_extra)
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
    return eng


def test_engine_step_phases_and_checkpoint_spans(tmp_path, devices8):
    eng = _tiny_engine(tmp_path, devices8)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)}
    for _ in range(2):
        eng.train_batch(batch=batch)     # fused: data + step under train_batch
    eng.forward(batch)                   # unfused: fwd/bwd/step
    eng.backward()
    eng.step()
    eng.save_checkpoint(str(tmp_path / "ck"))
    eng.load_checkpoint(str(tmp_path / "ck"))
    eng.destroy()

    names = {e["name"] for e in eng.tracer.events}
    assert {"train_batch", "data", "step", "fwd", "bwd",
            "checkpoint/save", "checkpoint/write", "checkpoint/commit",
            "checkpoint/resume"} <= names
    # device_sync marked the fenced spans
    tb = [e for e in eng.tracer.events if e["name"] == "train_batch"]
    assert all(e["args"].get("synced") for e in tb)
    # phase attribution: each train_batch span carries its step number
    steps, phases = phase_table(eng.tracer.events)
    assert set(steps) >= {1, 2, 3}
    assert "train_batch" in phases and "step" in phases
    # per-step: fused steps contain data+step, the unfused one fwd+bwd+step
    assert {"data", "step", "train_batch"} <= set(steps[1])
    assert {"fwd", "bwd", "step"} <= set(steps[3])
    # trace files on disk (flushed at checkpoint save + destroy)
    d = tmp_path / "t"
    assert (d / "trace.json").exists() and (d / "spans.jsonl").exists()
    blob = json.load(open(d / "trace.json"))
    assert any(e["ph"] == "X" for e in blob["traceEvents"])
    disk = load_jsonl(str(d / "spans.jsonl"))
    assert {e["name"] for e in disk} == names


def test_trace_monitor_backend_writes_scalars(tmp_path, devices8):
    eng = _tiny_engine(tmp_path, devices8, steps_per_print=1,
                       wall_clock_breakdown=True)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)}
    eng.forward(batch)
    eng.backward()
    eng.step()   # wall_clock_breakdown -> Time/* events through the monitor
    eng.destroy()
    rows = load_jsonl(str(tmp_path / "t" / "scalars.jsonl"))
    names = {r["name"] for r in rows}
    assert "Train/lr" in names
    assert "Time/fwd_ms" in names and "Time/step_ms" in names
    by_step = counters_by_step(rows, "Train/lr")
    assert by_step.get(1) == pytest.approx(1e-3)


# ---------------------------------------------------------------------------
# serving: trace-derived TTFT/TPOT == ServingMetrics (the acceptance bar)
# ---------------------------------------------------------------------------

def _serving_engine(tmp_path, n_slots=2, max_queue_depth=8):
    import deepspeed_tpu
    from deepspeed_tpu.models.registry import get_model
    from deepspeed_tpu.serving import ServingEngine

    model = get_model("gpt2", "tiny", max_seq_len=64)
    eng = deepspeed_tpu.init_inference(model=model, config={
        "dtype": "float32", "max_tokens": 64,
        "serving": {"n_slots": n_slots, "virtual_clock": True,
                    "max_queue_depth": max_queue_depth},
        "telemetry": {"enabled": True, "output_path": str(tmp_path),
                      "job_name": "srv"},
    })
    return eng, ServingEngine(eng)


def test_serving_trace_ttft_tpot_matches_metrics(tmp_path, devices8):
    """Staggered arrivals under the virtual clock: TTFT/TPOT recomputed
    from the trace JSONL must equal the ServingMetrics samples (and each
    Request's own ttft/tpot) EXACTLY — both read the same scheduler clock,
    so the trace is a faithful attribution of queueing + prefill + decode,
    not a parallel bookkeeping that can drift."""
    from deepspeed_tpu.serving import Request

    eng, srv = _serving_engine(tmp_path)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, 50, (4 + 3 * i,)).astype(np.int32),
                    max_new_tokens=3 + i, arrival_time=float(i) * 1.5)
            for i in range(5)]
    finished, rejected, snap = srv.run(reqs)
    assert len(finished) == 5 and not rejected

    # recompute from the JSONL on disk — the full emission path, not the
    # in-memory event list
    events = load_jsonl(str(tmp_path / "srv" / "spans.jsonl"))
    from_trace = request_metrics(events)
    for r in finished:
        t = from_trace[r.request_id]
        assert t["ttft"] == r.ttft              # virtual clock: exact
        assert t["tpot"] == r.tpot
        assert t["n_tokens"] == len(r.tokens)
        assert t["finish_reason"] == r.finish_reason
    # and the metrics histograms are the same samples
    assert sorted(t["ttft"] for t in from_trace.values()) == \
        sorted(srv.metrics.ttft_samples)
    assert sorted(t["tpot"] for t in from_trace.values()
                  if t["tpot"] is not None) == sorted(srv.metrics.tpot_samples)
    srv.destroy()
    eng.destroy()


def test_serving_trace_records_shed_and_decode_spans(tmp_path, devices8):
    from deepspeed_tpu.serving import Request

    eng, srv = _serving_engine(tmp_path, n_slots=1, max_queue_depth=1)
    rng = np.random.RandomState(1)
    reqs = [Request(prompt=rng.randint(0, 50, (4,)).astype(np.int32),
                    max_new_tokens=4) for _ in range(4)]
    finished, rejected, _ = srv.run(reqs)
    assert rejected, "queue_depth=1 under a 4-burst must shed"
    metrics = request_metrics(srv.tracer.events)
    shed_ids = {r.request_id for r in rejected}
    for rid in shed_ids:
        assert metrics[rid]["shed_reason"] == "queue_full"
    assert any(e["name"] == "decode_step" for e in srv.tracer.events)
    assert any(e["name"] == "prefill" for e in srv.tracer.events)
    srv.destroy()
    eng.destroy()


# ---------------------------------------------------------------------------
# tools/trace_summary.py
# ---------------------------------------------------------------------------

def test_trace_summary_table_and_budget_flagging(tmp_path, capsys):
    import trace_summary

    d = tmp_path / "tr"
    os.makedirs(d)
    with open(d / "spans.jsonl", "w") as f:
        for step in (1, 2):
            for name, dur in (("data", 0.002), ("step", 0.06),
                              ("train_batch", 0.063)):
                f.write(json.dumps(
                    {"ph": "X", "name": name, "cat": "train", "ts": 1.0 * step,
                     "dur": dur, "depth": 0, "parent": None,
                     "args": {"step": step}, "tid": 0, "seq": 0}) + "\n")
    with open(d / "scalars.jsonl", "w") as f:
        for step, frac in ((1, 0.05), (2, 0.61)):
            f.write(json.dumps({"name": "Comm/exposed_frac", "value": frac,
                                "step": step, "time": 0.0}) + "\n")

    out_json = str(tmp_path / "summary.json")
    rc = trace_summary.main([str(d), "--max-exposed-frac", "0.5",
                             "--fail-on-flag", "--json", out_json])
    assert rc == 3  # step 2 over budget
    out = capsys.readouterr().out
    assert "OVER BUDGET" in out and "| step |" in out
    summary = json.load(open(out_json))
    assert summary["flagged_steps"] == [2]
    assert summary["p50_ms"]["step"] == pytest.approx(60.0)
    assert "provenance" in summary and "git_sha" in summary["provenance"]

    # --budget pulls exposed_fraction_max from collective_budgets.json
    rc = trace_summary.main([str(d), "--budget", "tiny-test/8/bf16"])
    assert rc == 0  # no --fail-on-flag: report only


def test_trace_summary_on_real_engine_trace(tmp_path, devices8):
    """End-to-end smoke: a real engine trace dir summarizes without error
    and contains the train phases."""
    import trace_summary

    eng = _tiny_engine(tmp_path, devices8)
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)}
    eng.train_batch(batch=batch)
    eng.destroy()  # flush
    events, scalars = trace_summary.load_trace(str(tmp_path / "t"))
    summary = trace_summary.summarize(events, scalars)
    assert 1 in {r["step"] for r in summary["steps"]}
    assert "train_batch" in summary["phases"]
    assert summary["flagged_steps"] == []
