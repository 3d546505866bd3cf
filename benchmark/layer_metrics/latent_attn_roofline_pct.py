"""Share of its memory roofline the absorbed decode attention reaches, over
the traced steps: the latent rows of the live tokens (one 576-wide row a
token a layer, read once for all 32 heads) over the published HBM bandwidth,
over the device time of the operations that read and write the latent in
``jit_decode`` (the scopes ``latent_view_gather``, ``latent_attn_absorbed``
and ``latent_row_write``: the gather of the slots' view is part of how this
path reads the cache)."""

from benchmark import shapes_latent_moe as shapes

NAME = "latent_attn_roofline_pct"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps) \
            or not decode["regions"].get("latent_attention"):
        return None
    least_s = sum(shapes.absorbed_attention_bytes(
        obs["arch"], s["live"], obs["work"]["kv_itemsize"])
        for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / decode["regions"]["latent_attention"]
