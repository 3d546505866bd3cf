"""The trace reduction on a small synthetic trace with known answers."""

import pytest

from benchmark import trace_reduce as tr

US = 1000  # ns


def device(ops, modules=()):
    return {"ops": [(n, s * US, d * US) for n, s, d in ops],
            "modules": [(n, s * US, d * US) for n, s, d in modules]}


TRACE = {
    "devices": {"/device:TPU:0": device(
        ops=[
            ("fusion.1", 0, 100),
            # a call-like operation that contains its body's operations
            ("checkpoint.2", 100, 300),
            ("fusion.3", 120, 80),
            ("all-gather.4", 200, 100),      # exposed: innermost for 100 us
            ("all-reduce-start.5", 400, 10),
            ("fusion.6", 410, 90),
            ("all-reduce-done.5", 500, 50),  # the core waits: exposed
            # idle 550..800 while the host makes a batch
            ("fusion.1", 800, 200),
        ],
        modules=[("jit_train_step(123)", 0, 550),
                 ("jit_train_step(123)", 800, 200)])},
    "host_spans": [("bench/train_batch", 0, 560 * US),
                   ("bench/make_batch", 560 * US, 230 * US),
                   ("bench/step", 500 * US, 400 * US)],
}


def test_busy_idle_and_window():
    r = tr.reduce(TRACE)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(750e-6)


def test_top_operations_by_self_time():
    ops = dict(tr.reduce(TRACE)["device_ops"])
    assert ops["fusion.1"] == pytest.approx(300e-6)
    # 300 us long, 80 + 100 of them inside its body's operations
    assert ops["checkpoint.2"] == pytest.approx(120e-6)
    assert ops["all-gather.4"] == pytest.approx(100e-6)
    assert tr.reduce(TRACE)["device_ops"][0][0] == "fusion.1"


def test_exposed_collective_time():
    r = tr.reduce(TRACE)
    assert r["has_collectives"]
    assert r["collective_exposed_s"] == pytest.approx(160e-6)


def test_idle_gap_gets_the_innermost_harness_span():
    assert tr.reduce(TRACE)["idle_gaps"] == [
        ["bench/make_batch", pytest.approx(250e-6)]]


def test_modules_are_summed_by_name_and_one_run_is_the_median():
    m = tr.reduce(TRACE)["modules"]["jit_train_step"]
    assert m["runs"] == 2 and m["seconds"] == pytest.approx(750e-6)
    assert m["median_s"] == pytest.approx(375e-6)


def test_chips_are_averaged():
    two = {"devices": {
        "/device:TPU:0": device([("fusion.1", 0, 100)]),
        "/device:TPU:1": device([("fusion.1", 0, 50), ("fusion.2", 150, 50)])},
        "host_spans": []}
    r = tr.reduce(two)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx(100e-6)
    assert r["window_s"] == pytest.approx(150e-6)
    assert not r["has_collectives"]


def test_no_device_operation_gives_nothing():
    assert tr.reduce({"devices": {}, "host_spans": []}) is None


def test_load_reads_harness_spans_from_a_recorded_trace(tmp_path):
    """A trace recorded here has no TPU plane; the harness's spans are
    found on the host plane all the same."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench/step"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    loaded = tr.load(tr.find_xplane(str(tmp_path)))
    assert [n for n, _, _ in loaded["host_spans"]] == ["bench/step"]
    assert tr.reduce(loaded) is None
