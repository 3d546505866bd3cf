"""Headline benchmark: GPT-2 (350M-class) training throughput on the local
TPU chip(s), one cell, one process.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
vs_baseline = achieved MFU / 0.40 (the north star is ZeRO-3 OPT-13B at >40%
MFU on v4-256; this cell is the dense-LM single-host stand-in).

The process that runs this owns the chip: it needs a TPU (no CPU stand-in —
a host number is never written under a device metric's name), the device
kind must have published peaks (``deepspeed_tpu/accelerator/peaks.py``), and
any failure is a traceback and a non-zero exit, never a zero-valued record.
Its rebuild into a table of cells is ROADMAP S1.

Configuration: the r01 shape (24 x 1024, 16 heads, ffn 4096, vocab 50304,
seq 1024, bf16, micro-batch 12 a chip), with the sweep-measured overrides in
``bench_defaults.json`` applied on top; ``BENCH_*`` environment variables
override both (see ``opt`` below).
"""

import dataclasses
import json
import os
import sys
import time

import numpy as np

METRIC = "gpt2_350m_train_tokens_per_sec_per_chip"
UNIT = "tokens/s/chip"
REPO = os.path.dirname(os.path.abspath(__file__))
DEFAULTS_PATH = os.path.join(REPO, "bench_defaults.json")


def load_tuned():
    """(model overrides, engine-config overrides, batch) from the sweep
    winner ``tools/sweep_bench.py`` persisted, or empties."""
    if not os.path.isfile(DEFAULTS_PATH):
        return {}, {}, None
    with open(DEFAULTS_PATH) as f:
        rec = json.load(f)
    print(f"# bench_defaults.json: {rec.get('variant')} "
          f"({rec.get('tokens_per_s')} tok/s when swept)", file=sys.stderr)
    return (dict(rec.get("model_overrides", {})),
            dict(rec.get("config_overrides", {})), rec.get("batch"))


def build_config(tuned):
    """TransformerConfig for the cell. Priority per knob: explicit BENCH_*
    env var > sweep-tuned default > built-in (the r01 configuration)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import TransformerConfig
    from deepspeed_tpu.ops.flash_attention import parse_block_spec

    def opt(env_name, key, default, parse=str):
        if os.environ.get(env_name):
            return parse(os.environ[env_name])
        return tuned[key] if key in tuned else parse(default)

    on = lambda v: v == "1"
    explicit = dict(
        vocab_size=50304,  # padded to a multiple of 128 for the head matmul
        max_seq_len=1024, n_layers=24, n_heads=16, d_model=1024, d_ff=4096,
        compute_dtype=jnp.bfloat16,
        attention_impl=opt("BENCH_ATTN", "attention_impl", "xla"),
        attention_logits_dtype=opt(
            "BENCH_ATTN_LOGITS", "attention_logits_dtype", "fp32"),
        remat=(os.environ["BENCH_NOREMAT"] != "1")
        if os.environ.get("BENCH_NOREMAT") else bool(tuned.get("remat", True)),
        remat_policy=opt("BENCH_REMAT", "remat_policy", "minimal"),
        scan_layers=bool(opt("BENCH_SCAN", "scan_layers", "1", on)),
        fused_ce=bool(opt("BENCH_FUSED_CE", "fused_ce", "1", on)),
    )
    # every other tuned key the config knows flows through, so a sweep
    # variant's winning override is fully applied; unknown keys are an error
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    unknown = set(tuned) - fields
    if unknown:
        raise ValueError(f"bench_defaults.json model_overrides not in "
                         f"TransformerConfig: {sorted(unknown)}")
    passthrough = {k: v for k, v in tuned.items() if k not in explicit}
    if os.environ.get("BENCH_FLASH_BLOCKS"):  # "bqxbkv[:bq_bwd x bkv_bwd]"
        bq, bkv, bqb, bkvb = parse_block_spec(os.environ["BENCH_FLASH_BLOCKS"])
        passthrough.update(flash_block_q=bq, flash_block_kv=bkv,
                           flash_block_q_bwd=bqb, flash_block_kv_bwd=bkvb)
    return TransformerConfig(**explicit, **passthrough)


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; platform is "
                         f"{dev.platform!r} — refusing to run")

    import deepspeed_tpu
    from deepspeed_tpu.accelerator.peaks import device_peaks
    from deepspeed_tpu.models import CausalLM
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache

    peak_tflops = device_peaks(dev.device_kind).bf16_tflops
    setup_compile_cache()
    n_chips = len(jax.devices())
    tuned, tuned_cfg, tuned_batch = load_tuned()
    cfg = build_config(tuned)
    batch_size = int(os.environ.get("BENCH_BATCH", "")
                     or tuned_batch or 12) * n_chips
    seq_len = int(os.environ.get("BENCH_SEQ", "1024"))
    config = {
        "train_batch_size": batch_size,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1 if n_chips > 1 else 0},
        "gradient_clipping": 1.0,
        "steps_per_print": 1000000,
        **tuned_cfg,  # sweep-measured engine-config deltas
    }
    engine, _, _, _ = deepspeed_tpu.initialize(model=CausalLM(cfg),
                                               config=config)
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch_size, seq_len)).astype(np.int32)}

    t0 = time.perf_counter()
    for _ in range(2):  # compile + warm
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready((loss, engine.params))
    warm_s = time.perf_counter() - t0

    n_steps = int(os.environ.get("BENCH_STEPS", "10"))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = engine.train_batch(batch=batch)
    jax.block_until_ready((loss, engine.params))
    dt = time.perf_counter() - t0

    tokens_per_sec_per_chip = batch_size * seq_len * n_steps / dt / n_chips
    # model flops ~= 6 * n_params * tokens (fwd 2x + bwd 4x); recompute and
    # attention's O(s^2) term are not counted
    achieved_tflops = tokens_per_sec_per_chip * 6.0 * engine.num_parameters \
        / 1e12
    mfu = achieved_tflops / peak_tflops
    if not (np.isfinite(float(loss)) and 0.0 < mfu < 1.0):
        raise SystemExit(f"implausible result: loss={float(loss)} mfu={mfu} "
                         "(a fence that does not block reads as > peak)")

    sys.path.insert(0, os.path.join(REPO, "tools"))
    from _common import stamp_record

    print(json.dumps(stamp_record({
        "metric": METRIC,
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": UNIT,
        "vs_baseline": round(mfu / 0.40, 4),
        "numerics": {
            "skipped_steps": engine.skipped_steps,
            "final_loss_scale": float(engine.loss_scale),
            "health_anomalies": engine.health.anomaly_count,
        },
        "extra": {
            "mfu": round(mfu, 4),
            "achieved_tflops": round(achieved_tflops, 2),
            "peak_tflops": peak_tflops,
            "n_params_m": round(engine.num_parameters / 1e6, 1),
            "batch": batch_size, "seq": seq_len, "steps": n_steps,
            "compile_and_warm_s": round(warm_s, 1),
            "final_loss": round(float(loss), 4),
            "n_chips": n_chips,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
        },
    }, config=dict(config, batch=batch_size, seq=seq_len))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
