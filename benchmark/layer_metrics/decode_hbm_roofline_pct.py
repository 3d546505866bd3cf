"""Share of its memory roofline the decode program reaches. Decode is
memory-bound: the least time a step can take is the bytes it must read
(every weight once, plus the cached K and V of the tokens its active slots
hold, from shapes: ``benchmark/shapes.py``) over the chip's published HBM
bandwidth. That, over the median device time of a ``jit_decode`` run in
the trace."""

import statistics

from benchmark import shapes

NAME = "decode_hbm_roofline_pct"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "serve_tokens_per_s"


def read(obs):
    runs = [m for name, m in obs["trace"]["modules"].items()
            if name.startswith("jit_decode")]
    live = obs["samples"]["live_kv_tokens_traced"]
    if not runs or not live:
        return None
    step_s = runs[0]["median_s"]
    work = obs["work"]
    least_s = shapes.decode_step_bytes(
        obs["arch"], statistics.mean(live), work["weight_itemsize"],
        work["kv_itemsize"]) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / step_s
