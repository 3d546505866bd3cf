"""Autotuner roofline validation: predicted vs measured ordering on the chip.

The autotuner's compile-prune stage is exact (XLA memory_analysis), but its
est_time roofline ranking had never been checked against a single on-chip
measurement — "measured top-k" may measure the wrong k. This tool runs the
tuner on the headline bench model with a compact, fully-measured space and
reports:

- per-candidate predicted vs measured global-batch time,
- the rank correlation between the two orderings,
- recalibrated roofline constants (the single scale factor that best maps
  est -> measured; peak_flops/hbm_bw are scaled by its inverse).

Results land in autotuning_results_r04/ (ledger.jsonl + validation.json).

    python tools/validate_autotuner.py       # on the chip, one process
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _ranks(x):
    """Average-tie ranks (scipy-free): tied values share the mean of their
    positions, so the correlation doesn't depend on enumeration order."""
    x = np.asarray(x, np.float64)
    order = np.argsort(x, kind="stable")
    ranks = np.empty(len(x), np.float64)
    i = 0
    while i < len(x):
        j = i
        while j + 1 < len(x) and x[order[j + 1]] == x[order[i]]:
            j += 1
        ranks[order[i:j + 1]] = (i + j) / 2.0
        i = j + 1
    return ranks


def rank_correlation(a, b):
    """Spearman rho without scipy: Pearson correlation of the rank vectors."""
    ra, rb = _ranks(a), _ranks(b)
    if ra.std() == 0 or rb.std() == 0:
        return float("nan")
    return float(np.corrcoef(ra, rb)[0, 1])


def main():
    from _common import require_tpu, setup_compile_cache

    require_tpu("validate_autotuner")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.accelerator.peaks import device_peaks

    from deepspeed_tpu.autotuning.autotuner import Autotuner
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    layers = int(os.environ.get("BENCH_LAYERS", "24"))
    seq = int(os.environ.get("BENCH_SEQ", "1024"))
    global_batch = int(os.environ.get("AUTOTUNE_BATCH", "16"))

    def factory():
        return CausalLM(TransformerConfig(
            vocab_size=50304, max_seq_len=seq, n_layers=layers, n_heads=16,
            d_model=1024, d_ff=4096, compute_dtype=jnp.bfloat16,
            scan_layers=True, fused_ce=True, attention_impl="xla"))

    base = {
        "train_batch_size": global_batch,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    results_dir = os.environ.get("AUTOTUNE_DIR", "autotuning_results_r04")
    peaks = device_peaks(jax.devices()[0].device_kind)
    # compact single-chip space: on one device ZeRO stages shard nothing, so
    # the informative axes are remat x micro (plus the offload tax model);
    # measured_topk covers the WHOLE space so every estimate gets a check
    tuner = Autotuner(
        factory, base, results_dir=results_dir,
        peak_flops=peaks.bf16_tflops * 1e12 * 0.5,  # prior: ~0.5 efficiency
        hbm_bw=peaks.hbm_gbs * 1e9,
        zero_stages=[0], offloads=[None],
        # compact: 8 candidates = ~16 chip compiles; minimal_nomlp and the
        # batch extremes are already covered by the sweep itself
        remats=["minimal", None],
        micros=[2, 4, 8, 16],
    )
    rng = np.random.RandomState(0)
    batch = {"input_ids": rng.randint(
        0, 50304, (global_batch, seq)).astype(np.int32)}
    best, results = tuner.tune(batch, measured_topk=99, measure_steps=5)

    rows, pred, meas = [], [], []
    for r in results:
        row = r.row()
        if r.status == "measured" and r.measured_tokens_per_s > 0:
            gas = max(r.config.get("gradient_accumulation_steps", 1), 1)
            predicted = r.est_time * gas
            measured = global_batch * seq / r.measured_tokens_per_s
            row["pred_ms_global"] = round(predicted * 1e3, 1)
            row["meas_ms_global"] = round(measured * 1e3, 1)
            pred.append(predicted)
            meas.append(measured)
        rows.append(row)
        print(json.dumps(row), flush=True)

    out = {"best": best, "rows": rows}
    if pred:
        rho = rank_correlation(pred, meas)
        # one multiplicative recalibration: median measured/predicted ratio —
        # scaling both roofline constants by 1/ratio makes est_time land on
        # the measured magnitude while preserving the ordering
        ratio = float(np.median(np.asarray(meas) / np.asarray(pred)))
        out["rank_correlation"] = round(rho, 4)
        out["measured_over_predicted_median"] = round(ratio, 4)
        out["recalibrated"] = {
            "peak_flops": tuner.peak_flops / ratio,
            "hbm_bw": tuner.hbm_bw / ratio,
        }
        print(f"autotune validation: rank_corr={rho:.3f} "
              f"measured/predicted={ratio:.3f} over {len(pred)} candidates",
              flush=True)
    os.makedirs(results_dir, exist_ok=True)
    with open(os.path.join(results_dir, "validation.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)


if __name__ == "__main__":
    main()
