"""Share of its memory roofline the decode-attention kernel reaches in the
WINDOW layers (a band of 128 rows, a sink a head, a ring of two blocks a
slot), over the traced steps: of each slot the rows in its band, min(live,
window), in every window layer, a row its K (8 x 192) and its V (8 x 128),
5,120 B at the published widths, over the published HBM bandwidth, over the
device time of the kernel's calls in ``jit_decode`` under the scope
``sink_window_attn_decode``. These calls read some 21 MB each and are bound
by their latency, not by bytes: a low share here is the expected reading."""

from benchmark import shapes_sink_moe as shapes

NAME = "sink_window_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps):
        return None
    seconds = decode["regions"].get("sink_window_attention", 0.0)
    if not seconds:
        return None
    least_s = sum(shapes.window_attention_bytes(
        obs["arch"], s["window_rows"], obs["work"]["kv_itemsize"])
        for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / seconds
