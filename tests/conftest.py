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
    # collective rendezvous can fire spuriously and SIGABRT the whole suite
    _flags += (" --xla_cpu_collective_call_terminate_timeout_seconds=600"
               " --xla_cpu_collective_timeout_seconds=600")
os.environ["XLA_FLAGS"] = _flags.strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# Persistent compilation cache: the suite compiles hundreds of small SPMD
# programs; identical programs across runs hit the disk cache instead of
# recompiling, cutting repeat wall-clock by minutes. The package helper leaves
# the directory alone when JAX_COMPILATION_CACHE_DIR placed it.
from deepspeed_tpu.utils.compile_cache import setup_compile_cache  # noqa: E402

setup_compile_cache(os.path.join(os.path.dirname(__file__), ".jax_cache"))
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _collect_cycles():
    """Engines captured in jit closures die by CYCLE collection, not refcount;
    collecting between tests keeps live-buffer accounting (e.g.
    test_destroy_releases_device_buffers) independent of test order."""
    yield
    import gc

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
