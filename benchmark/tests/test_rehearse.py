"""``--rehearse-cpu`` runs every cell of the manifest end to end on the
CPU (on 4 virtual devices for a four-chip cell), and the open-loop arrival
kinds run through the same serving runner."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def rehearse(cell, trace):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", cell,
         "--seed", str(2 ** 31 + 17), "--seconds", "1.5", "--trace",
         str(trace), "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    return out.stdout, lines


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_rehearses_on_the_cpu(cell):
    stdout, lines = rehearse(cell, trace=0)
    assert "REHEARSAL" in stdout
    last = lines[-1]
    # labelled, and no result line: a CPU number is never a device metric
    assert last["note"] == "rehearsal" and last["passed"]
    assert not any("correct" in ln for ln in lines)
    chips = next(w["chips"] for w in MANIFEST["workloads"]
                 if w["name"] == cell)
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0}
    want = {m["name"] for m in MANIFEST["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(last["metric_names"]) == want
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert all(v for k, v in checks.items() if k != "note")


def test_a_traced_rehearsal_reads_the_host_clock_metrics():
    cell = next(w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1)
    _, lines = rehearse(cell, trace=1)
    assert lines[-1]["passed"] and lines[-1]["metric_names"]


def test_without_a_tpu_the_benchmark_refuses():
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout == ""
    assert "not 'tpu'" in out.stderr


@pytest.mark.parametrize("arrivals", [
    {"kind": "poisson", "rate_rps": 40.0},
    {"kind": "gamma", "rate_rps": 40.0, "cv": 2.5}], ids=lambda a: a["kind"])
def test_open_loop_arrivals_run_through_the_serving_runner(arrivals, capsys):
    """The cells Open questions keeps for later differ from the closed
    loop in their traffic file's ``arrivals`` alone."""
    import argparse

    import jax

    from benchmark import harness, serve_loop

    serve = [w for w in MANIFEST["workloads"]
             if harness.load_json(harness.traffic_path(w["traffic"]))["kind"]
             == "serve_loop"]
    if not serve:
        pytest.skip("the manifest has no serving cell")
    cell = serve[0]
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    config = harness.load_sized(os.path.join(ROOT, entry["file"]), True)
    traffic = harness.load_sized(harness.traffic_path(cell["traffic"]), True)
    traffic["arrivals"] = arrivals
    traffic["ramp"] = {"seconds": 0.5, "finished_requests": 4}
    args = argparse.Namespace(seed=5, seconds=1.5, trace=0, trace_dir=None,
                              rehearse_cpu=True)
    rc = serve_loop.run(cell["name"], config, traffic, MANIFEST, args,
                        jax.devices()[:1], None, harness.CompileCacheLog())
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    window = next(ln for ln in lines if ln.get("note") == "window")
    assert rc == 0 and lines[-1]["passed"]
    # offered 40 a second for 1.5 s, whatever the server did
    assert 40 <= window["submitted"] <= 80
    assert window["generator_late_ms_max"] >= 0
