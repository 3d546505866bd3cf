"""The serving step's phases as spans on the profiler's clock.

Every ``SpanTracer`` span is also a ``jax.profiler.TraceAnnotation`` named
``<cat>/<name>``, whether or not the tracer records; ``ServingEngine.step()``
is ``serving/step`` with its phases inside it; and
``benchmark/trace_reduce_spans.py`` puts each idle gap of the device down to
the innermost of them. The enabled tracer's own record is what it was, the
new spans aside.
"""

import json
import os

import numpy as np
import pytest

import jax

from benchmark import trace_reduce
from benchmark import trace_reduce_spans as spans
from deepspeed_tpu.telemetry import SpanTracer, load_jsonl
from deepspeed_tpu.telemetry.tracer import profiler_name

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = {"step", "admit", "insert", "ahead", "read_back", "book", "upkeep"}
US = 1000  # ns


def host_names(trace_dir):
    """Every event name on the host plane of the trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(trace_reduce.find_xplane(str(trace_dir)))
    return [ev.name for plane in data.planes
            if plane.name == trace_reduce.HOST_PLANE
            for line in plane.lines for ev in line.events]


def program_spans(trace_dir):
    return spans.load(trace_reduce.find_xplane(str(trace_dir)))[
        "program_spans"]


@pytest.mark.parametrize("name,cat,expected", [
    ("decode_step", "serving", "serving/decode_step"),
    ("step", "train", "train/step"),
    ("checkpoint/save", "checkpoint", "checkpoint/save"),
])
def test_profiler_name(name, cat, expected):
    assert profiler_name(name, cat) == expected


def test_spans_reach_the_profiler_enabled_or_not(tmp_path):
    """A disabled tracer records nothing and still annotates; an enabled
    one does both; keyword arguments never enter the annotation's name."""
    off = SpanTracer(enabled=False)
    on = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    with off.span("step", cat="serving", n=3) as sp:
        sp.fence(None)
        sp.set(k=1)
        with on.span("step", cat="train", step=7):
            pass
    with on.span("checkpoint/save", cat="checkpoint"):
        pass
    jax.profiler.stop_trace()
    names = host_names(tmp_path)
    assert {"serving/step", "train/step", "checkpoint/save"} <= set(names)
    assert not [n for n in names if n.startswith(("serving/", "train/"))
                and n not in ("serving/step", "train/step")]
    assert sorted(n for n, _, _ in program_spans(tmp_path)) == [
        "serving/step", "train/step"]
    assert off.events == []
    assert [e["name"] for e in on.events] == ["step", "checkpoint/save"]
    assert on.events[0]["args"] == {"step": 7}


def _engine(serving_extra=None, telemetry=None):
    import deepspeed_tpu
    from deepspeed_tpu.models.registry import get_model
    from deepspeed_tpu.serving import ServingEngine

    model = get_model("gpt2", "tiny", max_seq_len=64)
    config = {"dtype": "float32", "max_tokens": 64,
              "serving": {"n_slots": 2, "max_queue_depth": 8,
                          **(serving_extra or {})}}
    if telemetry is not None:
        config["telemetry"] = telemetry
    eng = deepspeed_tpu.init_inference(model=model, config=config)
    return eng, ServingEngine(eng)


def _requests(arrivals=False):
    from deepspeed_tpu.serving import Request

    rng = np.random.RandomState(0)
    return [Request(prompt=rng.randint(0, 50, (4 + 7 * i,)).astype(np.int32),
                    max_new_tokens=3 + i,
                    arrival_time=float(i) * 1.5 if arrivals else None)
            for i in range(5)]


CHUNKED = {"chunked_prefill": {"enabled": True, "chunk_size": 8}}


@pytest.mark.parametrize("serving_extra,also", [
    ({}, set()),
    (CHUNKED, {"serving/ahead", "serving/prefill_chunk"}),
], ids=["dense", "chunked"])
def test_step_phases_in_a_profiler_trace(tmp_path, devices8, serving_extra,
                                         also):
    """Telemetry not configured: the step's phases are in the profiler's
    trace all the same, nested in ``serving/step``."""
    from deepspeed_tpu.serving import RequestState

    eng, srv = _engine(serving_extra)
    warm = [srv.submit(r) for r in _requests()]
    while any(r.state is not RequestState.FINISHED for r in warm):
        srv.step()
    srv.block_until_idle()
    jax.profiler.start_trace(str(tmp_path))
    reqs = [srv.submit(r) for r in _requests()]
    n_steps = 0
    while any(r.state is not RequestState.FINISHED for r in reqs):
        srv.step()
        n_steps += 1
    srv.block_until_idle()
    jax.profiler.stop_trace()
    srv.destroy()
    eng.destroy()

    found = program_spans(tmp_path)
    steps = [(s, s + d) for n, s, d in found if n == "serving/step"]
    assert len(steps) == n_steps
    inside = {n for n, s, d in found if n != "serving/step" and any(
        lo <= s and s + d <= hi for lo, hi in steps)}
    assert {"serving/admit", "serving/prefill", "serving/insert",
            "serving/decode_step", "serving/read_back", "serving/book",
            "serving/upkeep"} | also <= inside
    assert inside == {n for n, _, _ in found} - {"serving/step"}
    # one span a phase a step, never one a slot or a token: only the
    # admissions add spans (a prefill, its first token, its insert)
    assert len(found) <= 12 * n_steps


def test_enabled_tracer_record_is_what_it_was(tmp_path, devices8):
    """Under the virtual clock the enabled tracer's JSONL, with the new
    phase spans left out, is event for event what the tracer wrote before
    they existed (``data/serving_spans_virtual.jsonl``): names, categories,
    times, durations and arguments, in order."""
    with open(os.path.join(HERE, "data", "serving_spans_virtual.jsonl")) as f:
        before = [json.loads(line) for line in f]
    for run, extra in (("dense", {}), ("chunked", CHUNKED)):
        out = tmp_path / run
        eng, srv = _engine({"virtual_clock": True, **extra},
                           {"enabled": True, "output_path": str(out),
                            "job_name": "srv"})
        finished, rejected, _ = srv.run(_requests(arrivals=True))
        assert len(finished) == 5 and not rejected
        srv.destroy()
        eng.destroy()
        events = load_jsonl(str(out / "srv" / "spans.jsonl"))
        assert {e["name"] for e in events} >= {"step", "admit", "read_back",
                                               "book", "upkeep"}
        kept = [{k: e[k] for k in ("ph", "name", "cat", "ts", "dur", "args")}
                for e in events if e["name"] not in PHASES]
        want = [{k: v for k, v in e.items() if k != "run"}
                for e in before if e["run"] == run]
        assert kept == want, run


# ------------------------------------------------------ the trace reduction
def device(ops):
    return {"ops": [(n, s * US, d * US) for n, s, d in ops], "modules": []}


# two steps of 1000 us; the device idles 100 us in the first step's
# read-back, 50 us in its booking, 200 us between the two steps' spans
# (inside the harness's bench/step only), and 100 us in the second's
# admission
TRACE = {
    "devices": {"/device:TPU:0": device([
        ("fusion.1", 0, 400), ("fusion.2", 500, 300), ("fusion.3", 850, 150),
        ("fusion.4", 1200, 300), ("fusion.5", 1600, 600)])},
    "host_spans": [("bench/step", 0, 2200 * US)],
    "program_spans": [
        ("serving/step", 0, 1000 * US),
        ("serving/admit", 0, 100 * US),
        ("serving/decode_step", 100 * US, 100 * US),
        ("serving/read_back", 300 * US, 300 * US),
        ("serving/book", 780 * US, 200 * US),
        ("serving/step", 1200 * US, 1000 * US),
        ("serving/admit", 1200 * US, 500 * US),
        ("serving/read_back", 1700 * US, 400 * US),
        # a span that is not the serving step's: counted, never a step
        ("train/step", 1250 * US, 10 * US),
    ],
}


def test_idle_gaps_go_to_the_innermost_harness_or_program_span():
    r = spans.reduce(TRACE)
    assert r["idle_gaps"] == [
        ["bench/step", pytest.approx(200e-6)],
        ["serving/read_back", pytest.approx(100e-6)],
        ["serving/admit", pytest.approx(100e-6)],
        ["serving/book", pytest.approx(50e-6)]]
    assert r["idle_s"] == pytest.approx(450e-6)
    assert r["idle_to_serving_s"] == pytest.approx(250e-6)


def test_program_spans_count_seconds_and_idle():
    ps = spans.reduce(TRACE)["program_spans"]
    assert ps["serving/step"] == {"count": 2, "seconds": pytest.approx(2e-3),
                                  "idle_s": 0.0}
    assert ps["serving/read_back"] == {
        "count": 2, "seconds": pytest.approx(700e-6),
        "idle_s": pytest.approx(100e-6)}
    assert ps["serving/admit"]["idle_s"] == pytest.approx(100e-6)
    assert ps["train/step"]["count"] == 1


def test_steps_and_what_no_phase_covers():
    r = spans.reduce(TRACE)
    assert r["steps"] == [
        [pytest.approx(1e-3), pytest.approx(300e-6), pytest.approx(150e-6)],
        [pytest.approx(1e-3), pytest.approx(400e-6), pytest.approx(100e-6)]]
    # the first step's phases leave 200..300 and 600..780 and 980..1000 us
    # bare; the second's 2100..2200
    assert r["steps_uncovered_s"] == pytest.approx(400e-6)


def test_a_gap_across_phases_is_cut_at_their_edges():
    """The end of a decode to the next dispatch crosses several phases: the
    spans' idle is cut at their edges, ``idle_gaps`` gives the whole gap to
    the span at its midpoint."""
    trace = {"devices": {"/device:TPU:0": device([("fusion.1", 0, 5),
                                                  ("fusion.2", 80, 20)])},
             "host_spans": [],
             "program_spans": [("serving/step", 0, 100 * US),
                               ("serving/read_back", 0, 40 * US),
                               ("serving/book", 40 * US, 30 * US)]}
    r = spans.reduce(trace)
    idle = {k: v["idle_s"] for k, v in r["program_spans"].items()}
    assert idle == {"serving/step": pytest.approx(10e-6),
                    "serving/read_back": pytest.approx(35e-6),
                    "serving/book": pytest.approx(30e-6)}
    assert r["idle_gaps"] == [["serving/book", pytest.approx(75e-6)]]
    assert r["steps"] == [[pytest.approx(100e-6), pytest.approx(40e-6),
                           pytest.approx(75e-6)]]


def test_the_device_clock_is_moved_onto_the_host_clock():
    """A device plane 100 us behind the host's: each program starts 100 us
    "before" its launch. The offset is found from the launches, and the
    gaps go where they go on a trace without it."""
    lag = 100
    shifted = {**TRACE, "devices": {"/device:TPU:0": {
        "ops": [(n, s - lag * US, d) for n, s, d in
                TRACE["devices"]["/device:TPU:0"]["ops"]],
        "modules": [(f"jit_step({i})", s - lag * US, d) for i, (_, s, d)
                    in enumerate(TRACE["devices"]["/device:TPU:0"]["ops"])]}},
        "launches": [("jit_step", s) for _, s, _ in
                     TRACE["devices"]["/device:TPU:0"]["ops"]]}
    r, want = spans.reduce(shifted), spans.reduce(TRACE)
    assert r["clock_offset_s"] == pytest.approx(lag * 1e-6)
    assert want["clock_offset_s"] == 0
    for key in ("idle_gaps", "idle_under", "steps"):
        assert r[key] == [[x if isinstance(x, str) else pytest.approx(x)
                           for x in row] for row in want[key]]
    assert {k: v["idle_s"] for k, v in r["program_spans"].items()} == \
        pytest.approx({k: v["idle_s"]
                       for k, v in want["program_spans"].items()})


def test_clock_offset_reads_programs_launched_into_an_idle_device():
    """jit_a runs every 10 ms on an idle device, 50 us after each launch on
    the host's clock, the device's clock 1 ms behind; jit_b queues behind
    each jit_a, launched long before it starts, and says nothing."""
    lag, latency = 1_000 * US, 50 * US
    modules, launches = [], []
    for i in range(6):
        start = i * 10_000 * US
        modules += [(f"jit_a({i})", start, 2_000 * US),
                    (f"jit_b({i})", start + 2_000 * US, 1_000 * US)]
        launches += [("jit_a", start + lag - latency),
                     ("jit_b", start + lag - 5_000 * US)]
    assert spans.clock_offset(modules, launches) == lag - latency
    assert spans.clock_offset(modules, []) == 0


def test_load_finds_the_launch_of_each_jitted_call(tmp_path):
    import jax.numpy as jnp

    double = jax.jit(lambda x: x * 2)
    double(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        double(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    launches = spans.load(trace_reduce.find_xplane(str(tmp_path)))[
        "launches"]
    assert [n for n, _ in launches].count("jit_<lambda>") == 3


def test_per_step_numbers():
    r = spans.reduce(TRACE)
    assert spans.step_host_ms(r) == pytest.approx(0.65)
    assert spans.step_device_wait_ms(r) == pytest.approx(0.125)


@pytest.mark.parametrize("reduced", [
    None, {"steps": []},
    spans.reduce({**TRACE, "program_spans": []})], ids=["none", "empty",
                                                         "no_spans"])
def test_per_step_numbers_are_none_without_steps(reduced):
    assert spans.step_host_ms(reduced) is None
    assert spans.step_device_wait_ms(reduced) is None


def test_without_program_spans_the_harness_names_every_gap():
    """A trace of a program older than its spans: the idle gaps are
    ``trace_reduce``'s, nothing else is found."""
    old = {k: v for k, v in TRACE.items() if k != "program_spans"}
    r = spans.reduce(old)
    assert r["idle_gaps"] == trace_reduce.reduce(old)["idle_gaps"]
    assert r["program_spans"] == {} and r["steps"] == []
    assert spans.reduce({"devices": {}, "host_spans": []}) is None


def test_the_command_line_reduces_a_kept_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: "trace.pb")
    monkeypatch.setattr(spans, "load", lambda path: TRACE)
    out = tmp_path / "spans.json"
    assert spans.main([str(tmp_path), "--json", str(out)]) == 0
    got = json.loads(out.read_text())
    assert got["steps"] == 2
    assert got["step_device_wait_ms"] == pytest.approx(0.125)
    assert got["steps_idle_s"] == pytest.approx(250e-6)
    assert got["idle_to_serving_share"] == pytest.approx(250 / 450)
    assert got["steps_uncovered_share"] == pytest.approx(0.2)
    assert got["idle_pct"] == pytest.approx(100 * 450 / 2200)
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: None)
    assert spans.main([str(tmp_path)]) == 2
