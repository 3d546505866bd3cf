"""Resume-at-any-scale + trajectory continuity (ROADMAP item 5 acceptance).

The chaos contract: a seeded SIGTERM at an arbitrary step loses at most the
snapshot cadence and resumes with a BITWISE-identical trajectory at equal
scale (loss-scale, rng stream, and skipped-step counters included); a resume
onto a different mesh (8 -> 4x2 -> 8) reshards params AND ZeRO optimizer
state automatically from the universal sharded layout and tracks the
uninterrupted run within 2e-5 per step.

Root-cause note: ``test_agent_resumes_at_different_scale`` (quarantined
known-failing since PR 1) is folded in here. The failure was never the
checkpoint — the fused-qkv ``jnp.concatenate`` along a model-sharded axis is
miscompiled by the jaxlib 0.4.x SPMD partitioner (a pure sharded concat
returns wrong bytes), so EVERY tensor-parallel forward was wrong. The
engines now force ``fused_qkv=False`` whenever the model axis is >1.

Process-isolation note: the tensor-parallel step programs sit in the jaxlib
0.4.x warm-compile-cache crash class (PR 3 root cause: deserialized
CPU-collective executables segfault on execute/free; toggling the
compilation cache mid-suite is ALSO a trigger), so the TP-touching bodies
run as world_size=1 subprocess workers via the mp harness — fresh cache-less
process, crash fails one test. Empirically the dp-only resume-then-train
sequence is ALSO in the crash class when the suite's earlier collective
modules warmed the cache (train_batch on reshard-loaded arrays under a
deserialized executable segfaults), so every engine-driving chaos body lives
in a worker; only the pure-filesystem prune test stays in-process.
"""

import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.checkpoint import atomic
from deepspeed_tpu.elasticity import ElasticAgent

from tests.mp_harness import run_distributed

pytestmark = pytest.mark.faults


def _run_body(name):
    """One world_size=1 worker on the suite's compile cache: each body builds
    the same engine several times over and compiles it once."""
    run_distributed(
        f"tests.mp_targets:{name}", world_size=1, local_devices=8, timeout=200,
        env={"JAX_COMPILATION_CACHE_DIR": jax.config.jax_compilation_cache_dir})


# ---------------------------------------------------------------------------
# the formerly-quarantined rescale test + 8->4x2->8 chaos (subprocess workers:
# tensor-parallel programs — see the module docstring)
# ---------------------------------------------------------------------------
def test_agent_resumes_at_different_scale():
    """dp8 -> dp4 x tp2 rescale resume + the sharded-concat miscompile
    guard. Body: tests/mp_targets.py elastic_rescale_and_concat_guard."""
    _run_body("elastic_rescale_and_concat_guard")


def test_chaos_resize_8_4_8_continuity():
    """Seeded kills at steps 2 and 5; resume 8 -> 4x2 -> 8 with overlapped
    snapshots; per-step losses within 2e-5 of the uninterrupted run; ZeRO
    state resharded automatically both ways. Body: tests/mp_targets.py
    elastic_chaos_resize_8_4_8."""
    _run_body("elastic_chaos_resize_8_4_8")


def test_chaos_equal_scale_bitwise_and_cadence_bound():
    """Seeded SIGTERM, equal scale, bitwise trajectory continuity (losses +
    rng + loss-scale + counters), then the cadence bound (snapshot_interval=2
    loses at most 2 steps) — chained in ONE worker to keep the tier-1 window
    lean. Bodies: tests/mp_targets.py elastic_chaos_equal_scale_bitwise ->
    elastic_chaos_cadence_bounds_lost_steps."""
    _run_body("elastic_chaos_equal_scale_bitwise")


# ---------------------------------------------------------------------------
# retention vs the live writer (the prune race fix)
# ---------------------------------------------------------------------------
def test_prune_never_touches_tags_newer_than_committed(tmp_path, devices8):
    """A snapshot tag PUBLISHED by the background writer (no latest swap
    yet) must never be counted toward keep_last — pruning the last
    committed tag under it would leave 'latest' dangling if the fresh
    commit then fails."""
    from deepspeed_tpu.checkpoint.sharded import ShardedCheckpointEngine
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.parallel import build_mesh

    mesh = build_mesh(MeshConfig(data=8), devices=devices8)
    sh = NamedSharding(mesh, P("data", None))
    io = ShardedCheckpointEngine()

    def publish(step, commit):
        state = {"w": jax.device_put(
            jnp.arange(64.0).reshape(8, 8) + step, sh)}
        io.save(state, str(tmp_path / f"elastic-step{step}"),
                meta={"global_steps": step})
        if commit:
            io.commit(f"elastic-step{step}")
        else:
            io._last_path = None  # published tag, pointer untouched

    publish(1, commit=True)
    publish(2, commit=True)   # latest -> elastic-step2 (the committed line)
    publish(4, commit=False)  # live writer's output, commit still pending

    agent = ElasticAgent(None, str(tmp_path), keep_last=1)
    agent._prune()
    tags = atomic.list_tags(str(tmp_path))
    assert "elastic-step4" in tags   # newer than committed: protected
    assert "elastic-step2" in tags   # the committed tag itself: kept
    assert "elastic-step1" not in tags  # committed history beyond keep_last
    assert atomic.read_latest(str(tmp_path)) == "elastic-step2"
