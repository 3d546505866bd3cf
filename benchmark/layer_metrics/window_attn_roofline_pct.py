"""Share of its memory roofline the banded decode-attention kernel reaches,
over the traced steps: the K/V rows it must read (the live rows of the active
slots in every full layer, of each slot the rows in its band, min(live,
window), in every window layer; a row is its K and its V, 2,048 B at the
published widths) over the published HBM bandwidth, over the device time of
the kernel's calls in ``jit_decode`` under the scopes ``window_attn_decode``
and ``full_attn_decode``."""

from benchmark import shapes_window_moe as shapes

NAME = "window_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps):
        return None
    seconds = decode["regions"].get("window_attention", 0.0) \
        + decode["regions"].get("full_attention", 0.0)
    if not seconds:
        return None
    least_s = sum(shapes.attention_bytes(
        obs["arch"], s["full_rows"], s["window_rows"],
        obs["work"]["kv_itemsize"]) for s in steps) \
        / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / seconds
