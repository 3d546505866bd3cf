"""Compressed (1-bit / int8) collectives + 1-bit optimizers.

Reference test analog: ``tests/onebit/test_nccl_backend.py`` — numerical
closeness of the compressed allreduce vs the exact one, error-feedback
correctness, and convergence of OnebitAdam after the freeze step.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.comm.compressed import (
    compressed_allreduce_local,
    make_compressed_allreduce,
)
from deepspeed_tpu.ops.onebit import OnebitAdam
from tests.mp_harness import run_distributed


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_compile_cache():
    """jaxlib 0.4.x segfaults/aborts freeing CPU-collective executables that
    were DESERIALIZED from the persistent compilation cache (conftest enables
    it suite-wide): once another run has warmed the cache for this module's
    shard_map programs, every later run dies in the post-test gc — taking the
    whole tier-1 suite with it. Compiling fresh is ~free for these tiny
    programs and sidesteps the bad deserialize path entirely (the two
    engine-level tests that intermittently failed/crashed here pass reliably
    without the cache)."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _mesh(devices8):
    return Mesh(np.array(devices8), ("data",))


@pytest.mark.parametrize("bits", [1, 8])
def test_compressed_allreduce_close_to_exact(devices8, bits):
    mesh = _mesh(devices8)
    world = 8
    n_local = 256
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(world * n_local), jnp.float32)
    we = jnp.zeros_like(x)
    se = jnp.zeros(world * (n_local // world), jnp.float32)

    sm = make_compressed_allreduce(mesh, "data", bits=bits)
    out, we2, se2 = sm(x, we, se)
    # every device ends with the same (approximately exact-mean) vector
    exact = np.mean(np.asarray(x).reshape(world, n_local), axis=0)
    got = np.asarray(out).reshape(world, n_local)
    for r in range(world):
        np.testing.assert_array_equal(got[r], got[0])
    # single-shot 1-bit is crude by design (~0.8 rel err on gaussian data);
    # the error-feedback test below shows it averages out to exact. int8 is
    # already tight in one shot.
    tol = 1.0 if bits == 1 else 0.02
    assert np.abs(got[0] - exact).mean() < tol * np.abs(exact).mean() + 1e-3


@pytest.mark.parametrize("bits", [1, 8])
def test_error_feedback_is_unbiased_over_steps(devices8, bits):
    """Repeatedly reducing the SAME tensor with error feedback must converge
    to the exact mean (the compensation property)."""
    mesh = _mesh(devices8)
    world, n_local = 8, 64
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(world * n_local), jnp.float32)
    exact = np.mean(np.asarray(x).reshape(world, n_local), axis=0)

    sm = make_compressed_allreduce(mesh, "data", bits=bits)
    steps = 64

    # the whole 64-step accumulation as ONE scanned program: per-dispatch
    # overhead on the emulated 8-device CPU backend dominated the old
    # python-loop version (~90s -> ~2s)
    @jax.jit
    def run(x, we, se):
        def body(carry, _):
            we, se, acc = carry
            out, we, se = sm(x, we, se)
            return (we, se, acc + out), None

        acc0 = jnp.zeros_like(x)
        (we, se, acc), _ = jax.lax.scan(body, (we, se, acc0), None, length=steps)
        return acc

    acc = run(x, jnp.zeros_like(x),
              jnp.zeros((world * (n_local // world),), jnp.float32))
    acc = np.asarray(acc).reshape(world, n_local)[0]
    # time-average of compensated quantized reductions -> exact mean
    err = np.abs(acc / steps - exact).mean() / (np.abs(exact).mean() + 1e-9)
    assert err < 0.05, err


def test_compressed_allreduce_hlo_has_all_to_all(devices8):
    mesh = _mesh(devices8)
    sm = make_compressed_allreduce(mesh, "data", bits=1)
    x = jnp.zeros((8 * 64,), jnp.float32)
    we = jnp.zeros_like(x)
    se = jnp.zeros((64,), jnp.float32)
    txt = jax.jit(sm).lower(x, we, se).compile().as_text()
    assert "all-to-all" in txt
    assert "all-gather" in txt


def test_onebit_adam_converges_after_freeze(devices8):
    """Data-parallel quadratic: warmup with exact reduction, then compressed
    momentum; the loss must keep decreasing in the compressed stage."""
    mesh = _mesh(devices8)
    world = 8
    dim = 64
    rng = np.random.RandomState(2)
    target = jnp.asarray(rng.randn(dim), jnp.float32)
    # per-device data shards
    data = jnp.asarray(rng.randn(world * 16, dim), jnp.float32)

    opt = OnebitAdam(lr=0.05, freeze_step=10)
    params = {"w": jnp.zeros((dim,), jnp.float32)}
    state = opt.init(params)

    def local_grads(w, shard):
        # grad of mean || shard @ diag? simple: mean over rows of (w - target)
        # weighted by per-row data norm, deterministic per shard
        err = w - target
        weight = 1.0 + 0.1 * jnp.mean(jnp.abs(shard), axis=(0, 1))
        return err * weight

    sm = make_compressed_allreduce(mesh, "data", bits=1)
    we = jnp.zeros((world * dim,), jnp.float32)
    se = jnp.zeros((dim,), jnp.float32)
    shards = data.reshape(world, 16, dim)
    grads_all = jax.vmap(local_grads, in_axes=(None, 0))

    # one program per stage: called eagerly, the shard_map and the optimizer
    # dispatch op by op, and 40 such steps were 135 s of this file's 197
    @jax.jit
    def exact_step(params, state):
        g_mean = {"w": jnp.mean(grads_all(params["w"], shards), axis=0)}
        return opt.update(g_mean, state, params)

    @jax.jit
    def compressed_step(params, state, we, se):
        # compressed momentum path: each device folds ITS local grad
        m_locals = jax.vmap(
            lambda g: opt.local_momentum({"w": g}, state)["w"])(
                grads_all(params["w"], shards))
        m_red, we, se = sm(m_locals.reshape(-1), we, se)
        m_tree = {"w": m_red.reshape(world, dim)[0]}
        return opt.apply_compressed(m_tree, state, params) + (we, se)

    def loss(w):
        return float(jnp.mean((w - target) ** 2))

    losses = [loss(params["w"])]
    for step in range(40):
        if step < opt.freeze_step:
            params, state = exact_step(params, state)
        else:
            params, state, we, se = compressed_step(params, state, we, se)
        losses.append(loss(params["w"]))

    assert losses[10] < losses[0]          # warmup learns
    assert losses[-1] < 0.5 * losses[10]   # compressed stage keeps learning


def test_engine_onebit_adam_end_to_end():
    """Engine-integrated 1-bit Adam, isolated in a world_size=1 subprocess
    (the mp_harness pattern). Rationale: the two engine-level onebit tests
    were the suite's residual warm-compile-cache segfault exposure — jaxlib
    0.4.x can abort freeing CPU-collective executables deserialized from the
    persistent cache (PR 3 root cause), and an in-process crash killed the
    whole tier-1 run. The worker compiles fresh (no conftest = no persistent
    cache) and a crash fails ONE test. Body: tests/mp_targets.py
    onebit_engine_end_to_end (moved verbatim)."""
    run_distributed("tests.mp_targets:onebit_engine_end_to_end",
                    world_size=1, local_devices=8, timeout=600)


def test_engine_onebit_falls_back_on_tp_mesh(devices8):
    """Non-pure-dp meshes keep exact numerics with a warning."""
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig(
        vocab_size=64, max_seq_len=32, n_layers=2, n_heads=2, d_model=32,
        d_ff=64, compute_dtype=jnp.float32))
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "onebit_adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "mesh": {"data": 4, "model": 2},
        "steps_per_print": 10 ** 9,
    })
    assert not eng._onebit_active
    rng = np.random.RandomState(1)
    batch = {"input_ids": rng.randint(0, 64, (8, 16)).astype(np.int32)}
    losses = [float(eng.train_batch(batch=batch)) for _ in range(3)]
    assert losses[-1] < losses[0]


def test_zero_one_adam_variance_refresh():
    """0/1 Adam engine test, isolated in a world_size=1 subprocess (same
    residual-segfault rationale as test_engine_onebit_adam_end_to_end).
    Body: tests/mp_targets.py zero_one_adam_variance_refresh (moved
    verbatim)."""
    run_distributed("tests.mp_targets:zero_one_adam_variance_refresh",
                    world_size=1, local_devices=8, timeout=600)


def test_zero_one_adam_growing_refresh_schedule():
    """The variance-refresh interval follows the reference's exponential rule
    (zoadam.py:267): starts at 1, doubles after every var_update_scaler
    refreshes, freezes past var_freeze_step. Deterministic and replayable."""
    from deepspeed_tpu.ops.onebit import ZeroOneAdam

    opt = ZeroOneAdam(freeze_step=0, var_update_scaler=2, var_freeze_step=40)
    refreshes = [s for s in range(40) if opt.wants_exact_step(s)]
    # interval 1 for 2 refreshes (0,1), then 2 for two (2,4), then 4 (8,12),
    # then 8 (16,24), then 16 (32)
    assert refreshes == [0, 1, 2, 4, 8, 12, 16, 24, 32], refreshes
    # frozen past var_freeze_step
    assert not any(opt.wants_exact_step(s) for s in range(40, 120))
    # a FRESH object (checkpoint resume) replays to the same answers
    opt2 = ZeroOneAdam(freeze_step=0, var_update_scaler=2, var_freeze_step=40)
    assert opt2.wants_exact_step(24) and not opt2.wants_exact_step(20)
    # non-monotone queries replay consistently
    assert opt2.wants_exact_step(4) and not opt2.wants_exact_step(3)
    # legacy fixed interval still honored
    opt3 = ZeroOneAdam(freeze_step=0, var_update_interval=8)
    assert [s for s in range(17) if opt3.wants_exact_step(s)] == [0, 8, 16]
