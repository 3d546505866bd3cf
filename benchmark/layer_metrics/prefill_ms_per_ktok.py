"""Wall time the ``step()`` calls with a prefill took beyond the median
decode-only step, summed over the window, per thousand prompt tokens they
prefilled (padding not counted; host clock)."""

import statistics

NAME = "prefill_ms_per_ktok"
UNIT = "ms/ktok"
LAYER = "model step (inference/engine.py, models/decoding.py)"
MOVES = "ttft_p50_ms"


def read(obs):
    prefills = obs["samples"]["prefill_steps"]
    decodes = obs["samples"]["decode_only_step_ms"]
    if not prefills or not decodes:
        return None
    base = statistics.median(decodes)
    extra_ms = sum(ms - base for ms, _ in prefills)
    return extra_ms / (sum(n for _, n in prefills) / 1000.0)
