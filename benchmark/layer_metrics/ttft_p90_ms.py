"""90th percentile, over requests submitted in the window, of first token at
the harness minus the moment the request was due. A tail of a few dozen
requests (three samples beyond it at today's 33 a window): it jumps when
the window holds one request more or less, so it carries no bound until a
window holds a hundred requests; ``ttft_p50_ms`` is the end-to-end metric
(host clock)."""

import numpy as np

NAME = "ttft_p90_ms"
UNIT = "ms"
LAYER = "serving engine host loop (serving/engine.py, scheduler.py, kv_pool.py)"
MOVES = "ttft_p50_ms"


def read(obs):
    samples = obs["samples"]["ttft_ms"]
    return float(np.percentile(samples, 90)) if samples else None
