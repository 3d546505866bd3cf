"""One-process sweep of the headline bench shape (GPT-2 350M, seq 1024) on
the chip: every configuration variant, one jax runtime, a table at the end —
use this to pick ``bench_defaults.json``:

    python tools/sweep_bench.py
    BENCH_SWEEP="batch,attn" python tools/sweep_bench.py   # subset by name

Refuses any platform but a TPU. A variant that fails to compile or run is a
FAILED row (and a non-zero exit code at the end), not a reason to stop.
"""

import json
import os
import sys
import time

import numpy as np


# Budget over the memory_analysis PROJECTION (temp+args+out-alias), which
# over-counts the true post-buffer-assignment peak by ~3 GB (donated-buffer
# double count): programs projected past it are skipped, not attempted.
HBM_BUDGET = float(os.environ.get("BENCH_HBM_BUDGET", "19.0e9"))


def compile_step(engine, batch):
    """AOT-compile the exact fused train-step program (one compile total) and
    return (compiled, sharded batch, projected peak HBM bytes) WITHOUT
    executing anything."""
    if engine.gradient_accumulation_steps_ != 1 \
            or not engine._can_fuse_train_step():
        raise ValueError(
            "sweep drives the gas==1 fused step; this variant would run a "
            "different program through engine.train_batch")
    compiled = engine.lower_train_step(batch).compile()
    mem = compiled.memory_analysis()
    # donated params/opt-state alias input->output; without subtracting the
    # alias bytes the projection double-counts ~5 GB and mis-skips exactly
    # the large-micro-batch variants this sweep exists to measure
    peak = (mem.temp_size_in_bytes + mem.argument_size_in_bytes +
            mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return compiled, engine._shard_batch(batch), peak


def measure(engine, compiled, sharded, steps=8):
    """Drive the AOT-compiled fused step directly (params/opt-state donated
    through, like engine.train_batch's hot loop). Returns tokens/s."""
    import jax
    import jax.numpy as jnp

    lr = jnp.asarray(1e-4, jnp.float32)
    theta = jnp.asarray(1.0, jnp.float32)

    def step():
        (engine.params, engine.optimizer_state, engine._scale,
         engine._good_steps, _, _, loss, engine._rng, _) = compiled(
            engine.params, engine.optimizer_state, sharded, engine._scale,
            engine._good_steps, engine._rng, lr, theta)
        return loss

    step()  # warm (first run may still page in the executable)
    jax.block_until_ready(step())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step()
    jax.block_until_ready((loss, engine.params))
    dt = (time.perf_counter() - t0) / steps
    return sharded["input_ids"].size / dt


def main():
    from _common import require_tpu, setup_compile_cache

    require_tpu("sweep_bench")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import deepspeed_tpu
    from deepspeed_tpu.accelerator.peaks import device_peaks
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    peak = device_peaks(jax.devices()[0].device_kind).bf16_tflops * 1e12

    layers = int(os.environ.get("BENCH_LAYERS", "24"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    base_model = dict(
        vocab_size=50304, max_seq_len=seq, n_layers=layers, n_heads=16,
        d_model=1024, d_ff=4096, compute_dtype=jnp.bfloat16,
        remat=True, remat_policy="minimal", scan_layers=True, fused_ce=True,
        attention_impl="xla")
    base_cfg = {
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }

    # (name, model overrides, micro-batch). "huge" = single-kv-block flash
    # tiles 512x1024 fwd+bwd, "maxq" = whole-sequence q tile, "nomlp" = the
    # lean remat policy (no mlp_hidden save), "noscan" = unrolled layers.
    variants = [
        ("noscan-flash-huge-noremat-b14", {"scan_layers": False,
                                           "attention_impl": "flash",
                                           "flash_block_q": 512,
                                           "flash_block_kv": 1024,
                                           "flash_block_q_bwd": 512,
                                           "flash_block_kv_bwd": 1024,
                                           "remat": False}, 14),
        ("noscan-flash-huge-noremat-ce4-b12", {"scan_layers": False,
                                               "attention_impl": "flash",
                                               "flash_block_q": 512,
                                               "flash_block_kv": 1024,
                                               "flash_block_q_bwd": 512,
                                               "flash_block_kv_bwd": 1024,
                                               "remat": False,
                                               "fused_ce_chunks": 4}, 12),
        ("noscan-flash-maxq-b12", {"scan_layers": False,
                                   "attention_impl": "flash",
                                   "flash_block_q": 1024,
                                   "flash_block_kv": 1024,
                                   "flash_block_q_bwd": 1024,
                                   "flash_block_kv_bwd": 1024}, 12),
        ("noscan-flash-maxq-noremat-b12", {"scan_layers": False,
                                           "attention_impl": "flash",
                                           "flash_block_q": 1024,
                                           "flash_block_kv": 1024,
                                           "flash_block_q_bwd": 1024,
                                           "flash_block_kv_bwd": 1024,
                                           "remat": False}, 12),
        ("noscan-flash-huge-b16", {"scan_layers": False,
                                   "attention_impl": "flash",
                                   "flash_block_q": 512,
                                   "flash_block_kv": 1024,
                                   "flash_block_q_bwd": 512,
                                   "flash_block_kv_bwd": 1024}, 16),
        ("noscan-flash-huge-noremat-b16", {"scan_layers": False,
                                           "attention_impl": "flash",
                                           "flash_block_q": 512,
                                           "flash_block_kv": 1024,
                                           "flash_block_q_bwd": 512,
                                           "flash_block_kv_bwd": 1024,
                                           "remat": False}, 16),
        ("noscan-ce-pallas-flash-huge-noremat-b12", {
            "scan_layers": False, "attention_impl": "flash",
            "flash_block_q": 512, "flash_block_kv": 1024,
            "flash_block_q_bwd": 512, "flash_block_kv_bwd": 1024,
            "remat": False, "fused_ce_impl": "pallas"}, 12),
        ("noscan-flash-huge-b12", {"scan_layers": False,
                                   "attention_impl": "flash",
                                   "flash_block_q": 512,
                                   "flash_block_kv": 1024,
                                   "flash_block_q_bwd": 512,
                                   "flash_block_kv_bwd": 1024}, 12),
        ("flash-huge-b16", {"attention_impl": "flash", "flash_block_q": 512,
                            "flash_block_kv": 1024, "flash_block_q_bwd": 512,
                            "flash_block_kv_bwd": 1024}, 16),
        ("flash-huge-noremat-b12", {"attention_impl": "flash",
                                    "flash_block_q": 512,
                                    "flash_block_kv": 1024,
                                    "flash_block_q_bwd": 512,
                                    "flash_block_kv_bwd": 1024,
                                    "remat": False}, 12),
        ("noscan-flash-huge-noremat-b12", {"scan_layers": False,
                                           "attention_impl": "flash",
                                           "flash_block_q": 512,
                                           "flash_block_kv": 1024,
                                           "flash_block_q_bwd": 512,
                                           "flash_block_kv_bwd": 1024,
                                           "remat": False}, 12),
        ("flash-maxq-b12", {"attention_impl": "flash", "flash_block_q": 1024,
                            "flash_block_kv": 1024, "flash_block_q_bwd": 1024,
                            "flash_block_kv_bwd": 1024}, 12),
        ("flash-huge-b24-nomlp", {"attention_impl": "flash",
                                  "flash_block_q": 512,
                                  "flash_block_kv": 1024,
                                  "flash_block_q_bwd": 512,
                                  "flash_block_kv_bwd": 1024,
                                  "remat_policy": "minimal_nomlp"}, 24),
        ("ce-pallas-flash-huge-b12", {"attention_impl": "flash",
                                      "flash_block_q": 512,
                                      "flash_block_kv": 1024,
                                      "flash_block_q_bwd": 512,
                                      "flash_block_kv_bwd": 1024,
                                      "fused_ce_impl": "pallas"}, 12),
        ("base-b12", {}, 12),
        ("flash-b12", {"attention_impl": "flash"}, 12),
        ("bf16-logits-b12", {"attention_logits_dtype": "bf16"}, 12),
        ("ce-pallas-b12", {"fused_ce_impl": "pallas"}, 12),
        ("b16", {}, 16),
        ("bf16-logits-b16", {"attention_logits_dtype": "bf16"}, 16),
        ("flash-b16", {"attention_impl": "flash"}, 16),
        ("b24-nomlp", {"remat_policy": "minimal_nomlp"}, 24),
        ("bf16-logits-b24-nomlp", {"attention_logits_dtype": "bf16",
                                   "remat_policy": "minimal_nomlp"}, 24),
        ("flash-b24-nomlp", {"attention_impl": "flash",
                             "remat_policy": "minimal_nomlp"}, 24),
        ("bf16-logits-b32-nomlp", {"attention_logits_dtype": "bf16",
                                   "remat_policy": "minimal_nomlp"}, 32),
        ("flash-b32-nomlp", {"attention_impl": "flash",
                             "remat_policy": "minimal_nomlp"}, 32),
        ("flash-big-b12", {"attention_impl": "flash", "flash_block_q": 512,
                           "flash_block_kv": 1024, "flash_block_q_bwd": 256,
                           "flash_block_kv_bwd": 512}, 12),
        ("flash-huge-b12", {"attention_impl": "flash", "flash_block_q": 512,
                            "flash_block_kv": 1024, "flash_block_q_bwd": 512,
                            "flash_block_kv_bwd": 1024}, 12),
        ("b8", {}, 8),
        ("noscan-b12", {"scan_layers": False}, 12),
        ("noscan-bf16-logits-b12", {"scan_layers": False,
                                    "attention_logits_dtype": "bf16"}, 12),
        ("noscan-b16", {"scan_layers": False}, 16),
        ("noscan-bf16-logits-b16", {"scan_layers": False,
                                    "attention_logits_dtype": "bf16"}, 16),
        ("noscan-flash-b12", {"scan_layers": False,
                              "attention_impl": "flash"}, 12),
        ("noscan-b24-nomlp", {"scan_layers": False,
                              "remat_policy": "minimal_nomlp"}, 24),
        ("noscan-bf16-b24-nomlp", {"scan_layers": False,
                                   "attention_logits_dtype": "bf16",
                                   "remat_policy": "minimal_nomlp"}, 24),
        ("jaxflash-b12", {"attention_impl": "jax_flash"}, 12),
        ("noscan-jaxflash-b12", {"scan_layers": False,
                                 "attention_impl": "jax_flash"}, 12),
        ("densece-b12", {"fused_ce": False}, 12),
        ("noclip-b12", {}, 12),  # gradient_clipping removed below
        ("ce4-b12", {"fused_ce_chunks": 4}, 12),
        ("ce16-b12", {"fused_ce_chunks": 16}, 12),
    ]
    sel = os.environ.get("BENCH_SWEEP")
    if sel:
        keys = sel.split(",")
        variants = [v for v in variants if any(k in v[0] for k in keys)]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rng = np.random.RandomState(0)
    print(f"{'variant':<16} {'tok/s':>10} {'MFU':>7}")
    best = (None, 0.0)
    best_spec = None
    failed = 0
    for name, m_over, b in variants:
        engine = None
        try:
            # ONE computation of the engine-config delta, shared by the run
            # and the persisted winner record — substring match so compound
            # variants ("noscan-noclip-b12") can't run with clipping while
            # their name claims otherwise
            cfg_over = {"gradient_clipping": 0.0} if "noclip" in name else {}
            cfg = dict(base_cfg, train_batch_size=b, **cfg_over)
            model = CausalLM(TransformerConfig(**{**base_model, **m_over}))
            engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=cfg)
            batch = {"input_ids": rng.randint(
                0, 50304, (b, seq)).astype(np.int32)}
            compiled, sharded, need = compile_step(engine, batch)
            if need > HBM_BUDGET:
                print(f"{name:<16} SKIPPED: projected {need/1e9:.1f} GB "
                      f"> {HBM_BUDGET/1e9:.1f} GB budget", flush=True)
                continue
            tps = measure(engine, compiled, sharded, steps=8)
            mfu = tps * 6 * engine.num_parameters / peak
            print(f"{name:<16} {tps:>10.0f} {mfu:>7.4f}", flush=True)
            if tps > best[1]:
                best = (name, tps)
                # engine-config deltas travel too (noclip lives in cfg, not
                # the model) — otherwise the persisted "winner" is
                # unreproducible by bench.py
                best_spec = (dict(m_over), b, dict(cfg_over))
        except Exception as e:  # a failed variant is a row, not the end
            failed += 1
            print(f"{name:<16} FAILED: {type(e).__name__}: {str(e)[:300]}",
                  flush=True)
        finally:
            # free HBM before the next variant: del alone leaves
            # engine<->jit-closure gc cycles pinning every device buffer
            if engine is not None:
                engine.destroy()
    print(f"\nbest: {best[0]} at {best[1]:.0f} tok/s")

    # Persist the winner for bench.py (which reads bench_defaults.json; env
    # vars still win). Only from an UNFILTERED sweep at the headline shape: a
    # BENCH_SWEEP subset or a reduced BENCH_SEQ/BENCH_LAYERS run must not
    # steer the headline config.
    if best_spec is not None and not sel and layers == 24 and seq == 1024:
        m_over, b, cfg_over = best_spec
        with open(os.path.join(repo, "bench_defaults.json"), "w") as f:
            json.dump({"variant": best[0], "tokens_per_s": round(best[1], 1),
                       "batch": b, "model_overrides": m_over,
                       "config_overrides": cfg_over,
                       "device_kind": jax.devices()[0].device_kind,
                       "measured_utc": time.strftime(
                           "%Y-%m-%d %H:%M:%S", time.gmtime())}, f, indent=1)
        print(f"bench_defaults.json <- {best[0]} (b={b}, {m_over}, {cfg_over})")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
