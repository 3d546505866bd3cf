"""Blocks the live requests hold in the window layers' group (a block there
holds its tokens' rows in every window layer) over the blocks they hold in
the full layers' group, at the window's end, in percent: what the band saves
a window layer. 100 is a uniform pool, every layer keeping every token. From
the program's counters (``metrics.snapshot()["kv_pool"]["groups"]``)."""

NAME = "kv_window_blocks_pct"
UNIT = "%"
LAYER = "serving engine host loop (serving/engine.py, scheduler.py, kv_pool.py)"
MOVES = "itl_p50_ms"


def read(obs):
    c = obs["counters"]
    if not c.get("full_group_blocks"):
        return None
    return 100.0 * c["window_group_blocks"] / c["full_group_blocks"]
