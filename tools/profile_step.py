"""Micro-profile of one training step on the chip (one process; refuses any
other platform).

Times, separately: a reference GEMM at model shapes (achievable peak), model
forward, forward+backward, optimizer apply, and the full engine step — so MFU
losses can be attributed to a phase instead of guessed at. Profiles the base
bench config AND (when bench_defaults.json records a different sweep winner)
the winning config, so the remaining gap is attributed for the config the
headline bench actually runs.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timeit(fn, *args, n=5, warmup=2):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def profile_config(label, model_over, cfg_over, b, seq, layers):
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    cfg = TransformerConfig(**{**dict(
        vocab_size=50304, max_seq_len=seq, n_layers=layers, n_heads=16,
        d_model=1024, d_ff=4096, compute_dtype=jnp.bfloat16,
        attention_impl=os.environ.get("BENCH_ATTN", "xla"),
        remat=os.environ.get("BENCH_NOREMAT", "") != "1",
        remat_policy=os.environ.get("BENCH_REMAT", "minimal"),
    ), **model_over})
    model = CausalLM(cfg)
    config = {
        "train_batch_size": b,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
        **cfg_over,
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    try:
        rng = np.random.RandomState(0)
        batch = {"input_ids": rng.randint(
            0, cfg.vocab_size, (b, seq)).astype(np.int32)}
        sharded = engine._shard_batch(batch)

        step_rng = jax.random.PRNGKey(0)
        with engine.mesh:
            fwd = jax.jit(lambda p, bt: model.loss(
                p, bt, deterministic=False, dropout_rng=step_rng))
        t_fwd = timeit(fwd, engine.params, sharded)
        print(f"[{label}] forward:  {t_fwd*1e3:.1f} ms", flush=True)

        if engine._fwd_bwd_fn is None:
            engine._build_fwd_bwd()
        t_fb = timeit(lambda: engine._fwd_bwd_fn(
            engine.params, sharded, engine._scale, step_rng))
        print(f"[{label}] fwd+bwd:  {t_fb*1e3:.1f} ms "
              f"(bwd+remat ~ {(t_fb-t_fwd)*1e3:.1f} ms)", flush=True)

        # apply (can't donate repeatedly -> time via full step minus fwd_bwd)
        def full_step():
            loss = engine.forward(batch)
            engine.backward(loss)
            engine.step()
            return engine.params

        t_step = timeit(full_step, n=5)
        print(f"[{label}] full step: {t_step*1e3:.1f} ms "
              f"(apply+overhead ~ {(t_step-t_fb)*1e3:.1f} ms)", flush=True)

        from deepspeed_tpu.accelerator.peaks import device_peaks

        peak = device_peaks(jax.devices()[0].device_kind).bf16_tflops
        mfu = 6.0 * engine.num_parameters * b * seq / t_step / 1e12 / peak
        print(f"[{label}] MFU: {mfu:.4f} (of {peak} TFLOP/s)", flush=True)
    finally:
        # free HBM before the next profiled config (engine<->jit-closure gc
        # cycles otherwise pin every device buffer)
        engine.destroy()


def main():
    from _common import require_tpu, setup_compile_cache

    require_tpu("profile_step")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    layers = int(os.environ.get("BENCH_LAYERS", "24"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    b = int(os.environ.get("BENCH_BATCH", "12"))

    # reference GEMM: same M as the model's token dim, K=N=4096 (mlp shape).
    # The loop runs INSIDE one jit dispatch (fori_loop with a data dependency)
    # so per-dispatch host overhead cannot pollute a ~1 ms GEMM's number.
    M = b * seq
    REPS = 50
    x = jnp.zeros((M, 1024), jnp.bfloat16)
    w1 = jnp.zeros((1024, 4096), jnp.bfloat16)
    w2 = jnp.zeros((4096, 1024), jnp.bfloat16)

    @jax.jit
    def gemm_loop(x, w1, w2):
        def body(_, acc):
            return ((acc @ w1) @ w2) * jnp.bfloat16(1e-3)
        return jax.lax.fori_loop(0, REPS, body, x)

    t = timeit(gemm_loop, x, w1, w2, n=3) / REPS
    gemm_fl = 2 * M * 1024 * 4096 * 2
    print(f"ref gemm pair (in-jit x{REPS}): {t*1e3:.2f} ms -> "
          f"{gemm_fl/t/1e12:.1f} TFLOP/s achievable", flush=True)

    profile_config("base", {}, {}, b, seq, layers)

    # winner attribution: profile the sweep-chosen config too, so the
    # remaining MFU gap is explained for what bench.py actually runs
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, "bench_defaults.json")
    if os.path.isfile(path):
        try:
            rec = json.load(open(path))
        except (ValueError, OSError):
            rec = None
        if not isinstance(rec, dict):
            rec = None  # hand-edited file may be valid-JSON-but-not-object
        if rec and (rec.get("model_overrides") or rec.get("config_overrides")
                    or rec.get("batch", b) != b):
            profile_config(
                f"winner:{rec.get('variant')}",
                dict(rec.get("model_overrides", {})),
                dict(rec.get("config_overrides", {})),
                int(rec.get("batch", b)), seq, layers)


if __name__ == "__main__":
    main()
