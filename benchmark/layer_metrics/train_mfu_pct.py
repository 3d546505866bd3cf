"""Model FLOP/s utilisation of training: the operations a token requires
(6 per parameter plus causal attention, from shapes, recompute not counted;
``benchmark/shapes.py``) times the tokens a chip trains per second at the
median step, over the chip's published bf16 peak."""

import statistics

from benchmark import shapes

NAME = "train_mfu_pct"
UNIT = "%"
LAYER = "training engine (runtime/engine.py)"
MOVES = "train_tokens_per_s_per_chip"


def read(obs):
    work = obs["work"]
    step_s = statistics.median(obs["samples"]["train_step_ms"]) * 1e-3
    tokens_per_s_per_chip = work["tokens_per_step"] / step_s / work["chips"]
    flops = shapes.train_flops_per_token(obs["arch"], work["seq_len"])
    return 100.0 * flops * tokens_per_s_per_chip \
        / (obs["peaks"]["bf16_tflops"] * 1e12)
