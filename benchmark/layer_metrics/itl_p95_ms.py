"""95th percentile over ALL gaps between consecutive output tokens of one
request whose later token arrived in the window, so a decode step stalled
by a co-scheduled prefill shows. With a prefill in two steps of five it is
the fourth-longest prefill step of the window, and moved by 2 percent when
the window held one step less: no bound; ``itl_p50_ms`` is the end-to-end
metric (host clock)."""

import numpy as np

NAME = "itl_p95_ms"
UNIT = "ms"
LAYER = "serving engine host loop (serving/engine.py, scheduler.py, kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(obs):
    samples = obs["samples"]["itl_ms"]
    return float(np.percentile(samples, 95)) if samples else None
