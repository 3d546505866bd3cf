"""Share of its memory roofline the decode-attention kernel reaches in the
FULL layers, whose K rows (4 x 192) are wider than their V rows (4 x 128),
over the traced steps: the live rows of the active slots in every full layer,
a row 2,560 B at the published widths, over the published HBM bandwidth, over
the device time of the kernel's calls in ``jit_decode`` under the scope
``full_attn_decode``."""

from benchmark import shapes_sink_moe as shapes

NAME = "asym_full_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps):
        return None
    seconds = decode["regions"].get("full_attention", 0.0)
    if not seconds:
        return None
    least_s = sum(shapes.full_attention_bytes(
        obs["arch"], s["full_rows"], obs["work"]["kv_itemsize"])
        for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / seconds
