"""ZeRO-Offload throughput check on the chip (one process; refuses any other
platform).

Measures tokens/s of the same model with (a) standard on-device optimizer and
(b) host-offloaded optimizer (the CPUAdam path), reporting the offload tax.
Run:

    python tools/bench_offload.py            # ~2 min
    BENCH_LAYERS=48 python tools/bench_offload.py   # heavier model
"""

import os
import sys
import time

import numpy as np


def run(config_extra, model, batch, steps=6):
    import jax

    import deepspeed_tpu

    config = {
        "train_batch_size": batch["input_ids"].shape[0],
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    config.update(config_extra)
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=config)
    try:
        engine.train_batch(batch=batch)  # compile + warm
        jax.block_until_ready(engine.params)
        t0 = time.perf_counter()
        for _ in range(steps):
            engine.train_batch(batch=batch)
        jax.block_until_ready(engine.params)
        dt = (time.perf_counter() - t0) / steps
        tokens = batch["input_ids"].size
        return tokens / dt
    finally:
        # free HBM before the next engine: del alone leaves engine<->jit
        # closure gc cycles pinning every device buffer, and ~5 GB of pinned
        # optimizer state would fail the second engine's compile on a 16 GB
        # chip
        engine.destroy()


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from _common import require_tpu, setup_compile_cache

    require_tpu("bench_offload")
    setup_compile_cache()
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    n_layers = int(os.environ.get("BENCH_LAYERS", "24"))
    cfg = dict(vocab_size=50304, max_seq_len=1024, n_layers=n_layers,
               n_heads=16, d_model=1024, d_ff=4096,
               compute_dtype=jnp.bfloat16, remat=True, remat_policy="minimal")
    rng = np.random.RandomState(0)
    b = int(os.environ.get("BENCH_BATCH", "8"))
    batch = {"input_ids": rng.randint(0, 50304, (b, 1024)).astype(np.int32)}

    base = run({"zero_optimization": {"stage": 2}},
               CausalLM(TransformerConfig(**cfg)), batch)
    print(f"on-device optimizer : {base:10.0f} tok/s")

    off = run({"zero_optimization": {"stage": 2,
                                     "offload_optimizer": {"device": "cpu"}}},
              CausalLM(TransformerConfig(**cfg)), batch)
    print(f"cpu-offload optimizer: {off:10.0f} tok/s "
          f"({off / base * 100:.0f}% of on-device)")


if __name__ == "__main__":
    main()
