"""Median wall time of a ``step()`` that ran decode only, from its call to
its return with the step's tokens read (host clock)."""

import statistics

NAME = "decode_step_ms"
UNIT = "ms"
LAYER = "model step (inference/engine.py, models/decoding.py)"
MOVES = "itl_p50_ms"


def read(obs):
    samples = obs["samples"]["decode_only_step_ms"]
    return statistics.median(samples) if samples else None
