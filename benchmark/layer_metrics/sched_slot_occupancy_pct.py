"""Mean active slots over the slot count, per decode dispatch of the
window: the program's own counters (``goodput.decode_tokens`` over
``speculative.decode_dispatches`` of ``metrics.snapshot()``)."""

NAME = "sched_slot_occupancy_pct"
UNIT = "%"
LAYER = "serving engine host loop (serving/engine.py, scheduler.py, kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(obs):
    c = obs["counters"]
    if not c["decode_dispatches"]:
        return None
    return 100.0 * c["decode_tokens"] / c["decode_dispatches"] / c["n_slots"]
