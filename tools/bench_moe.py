"""MoE training overhead on chip: dense vs k-expert at EQUAL active params.

The reference's claim is "5x cheaper MoE training at same quality"
(``/root/reference/docs/_posts/2021-12-09-deepspeed-moe-nlg.md``) — the
question one chip can answer is the cost side: with top-1 gating and the
same per-token FLOPs as dense, how much throughput does the gating + dispatch
machinery (router softmax, capacity sort, one-hot combine — all local on a
single chip; the a2a is degenerate at ep=1) actually cost?

Shape is reduced from the headline (12 layers, d_ff 2048) so the 8-expert
tree + AdamW state fits the 16 GB v5e: expert mlp params = 8x dense mlp, and
optimizer state is fp32 m/v over all of it.

    python tools/bench_moe.py          # dense, 4-expert, 8-expert
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main():
    from _common import require_tpu, setup_compile_cache

    require_tpu("bench_moe")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from sweep_bench import compile_step, measure, HBM_BUDGET

    seq = int(os.environ.get("BENCH_MOE_SEQ", "1024"))
    b = int(os.environ.get("BENCH_MOE_BATCH", "8"))
    base = dict(
        vocab_size=50304, max_seq_len=seq, n_layers=12, n_heads=16,
        d_model=1024, d_ff=2048, compute_dtype=jnp.bfloat16,
        remat=True, remat_policy="minimal", scan_layers=True, fused_ce=True,
        attention_impl="xla")
    cfg_base = {
        "train_batch_size": b,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    # top-1 gating keeps per-token mlp FLOPs equal to dense — the measured
    # delta IS the gating+dispatch overhead (plus the capacity-padding waste)
    variants = [
        ("dense", {}),
        ("moe4-top1", {"n_experts": 4, "moe_top_k": 1}),
        ("moe8-top1", {"n_experts": 8, "moe_top_k": 1}),
        ("moe8-top2", {"n_experts": 8, "moe_top_k": 2}),
    ]

    rng = np.random.RandomState(0)
    rows = []
    dense_tps = None
    failed = 0
    for name, over in variants:
        engine = None
        try:
            model = CausalLM(TransformerConfig(**{**base, **over}))
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, config=dict(cfg_base))
            batch = {"input_ids": rng.randint(
                0, 50304, (b, seq)).astype(np.int32)}
            compiled, sharded, need = compile_step(engine, batch)
            if need > HBM_BUDGET:
                print(f"{name:<12} SKIPPED: projected {need/1e9:.1f} GB "
                      f"> budget", flush=True)
                continue
            tps = measure(engine, compiled, sharded, steps=8)
            n_params = engine.num_parameters
            if name == "dense":
                dense_tps = tps
            rel = tps / dense_tps if dense_tps else float("nan")
            rows.append((name, tps, n_params, rel))
            print(f"{name:<12} {tps:>9.0f} tok/s  {n_params/1e6:>7.1f}M params  "
                  f"{rel:>6.3f}x dense", flush=True)
        except Exception as e:  # a failed variant is a row, not the end
            failed += 1
            print(f"{name:<12} FAILED: {type(e).__name__}: {str(e)[:250]}",
                  flush=True)
        finally:
            if engine is not None:
                engine.destroy()
            engine = None

    print("\n| variant | tok/s | params (M) | vs dense |")
    print("|---|---|---|---|")
    for name, tps, n, rel in rows:
        print(f"| {name} | {tps:.0f} | {n/1e6:.1f} | {rel:.3f}x |")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
