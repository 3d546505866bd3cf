"""Test harness configuration.

The reference simulates multi-node as multi-process on localhost
(``tests/unit/common.py:86`` DistributedExec). On TPU we instead virtualize: force the
CPU platform with 8 XLA host devices, so every test sees an 8-device mesh and the same
SPMD programs that run on a TPU slice compile and execute here. This must run before
jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never touch an accelerator

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "xla_cpu_collective_call_terminate_timeout_seconds" not in _flags:
    # 8 emulated devices share this box's cores; under load the default 40s
    # collective rendezvous can fire spuriously and SIGABRT the worker. Kept
    # under TEST_LIMIT_S so that XLA's account of WHICH collective is stuck
    # comes before the per-test limit's stack dump.
    _flags += (" --xla_cpu_collective_call_terminate_timeout_seconds=180"
               " --xla_cpu_collective_timeout_seconds=180")
os.environ["XLA_FLAGS"] = _flags.strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Persistent compilation cache: the suite compiles hundreds of small SPMD
# programs; identical programs across runs hit the disk cache instead of
# recompiling, cutting repeat wall-clock by minutes. The package helper leaves
# the directory alone when JAX_COMPILATION_CACHE_DIR placed it.
from deepspeed_tpu.utils.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache(os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


# No test may hold its worker longer than this: eight to eleven times the
# slowest tier-1 test (22-29 s over two runs on the 8-core box of PR 26), and
# over the XLA collective timeouts above.
TEST_LIMIT_S = 240
_WATCHDOG_FD = pytest.StashKey[int]()


def pytest_configure(config):
    # fd 2 while no test's output is being captured: where the watchdog writes
    config.stash[_WATCHDOG_FD] = os.dup(2)


@pytest.fixture(autouse=True)
def _bounded_and_collected(request):
    """Bound the test, then collect cycles after it.

    The bound is faulthandler's watchdog thread, not a signal: a thread stuck
    inside XLA never returns to the interpreter to run a handler. On expiry
    every thread's stack is printed and the process exits; xdist reports the
    test as failed with that output and hands the queue to a new worker.

    Engines captured in jit closures die by CYCLE collection, not refcount;
    collecting between tests keeps live-buffer accounting (e.g.
    test_destroy_releases_device_buffers) independent of test order."""
    import faulthandler
    import gc

    faulthandler.dump_traceback_later(TEST_LIMIT_S, exit=True,
                                      file=request.config.stash[_WATCHDOG_FD])
    yield
    faulthandler.cancel_dump_traceback_later()
    gc.collect()


@pytest.fixture(scope="session")
def devices8():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture
def mesh8(devices8):
    """Canonical 8-device mesh: pure data-parallel by default."""
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import MeshConfig

    return build_mesh(MeshConfig(), devices=devices8)


@pytest.fixture
def mesh_2d(devices8):
    """data=4 x model=2 mesh for TP tests."""
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import MeshConfig

    return build_mesh(MeshConfig(data=4, model=2), devices=devices8)
