"""Median wall time of a training step, each taken at ``block_until_ready``
of its loss with the next step already dispatched (host clock)."""

import statistics

NAME = "train_step_ms"
UNIT = "ms"
LAYER = "training engine (runtime/engine.py)"
MOVES = "train_tokens_per_s_per_chip"


def read(obs):
    return statistics.median(obs["samples"]["train_step_ms"])
