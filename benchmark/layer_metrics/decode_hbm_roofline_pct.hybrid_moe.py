"""Share of its memory roofline the WHOLE decode program of the Nemotron-H
model (Mamba-2 mixers, latent experts of which a chip holds a share,
attention without positions) reaches, over the traced steps. The least time
a step can take is the bytes it must move over the chip's published HBM
bandwidth: every weight outside the routed experts once (the head too), the
HELD routed experts that were hit in that step (the program's counter), the
recurrent state of the slots decoded, read and written (float32 S and the
bf16 conv tail), and the live K/V rows of those slots in every attention
layer (``benchmark/shapes_hybrid_moe.py``). That, summed over the traced
decode steps, over the device time of the ``jit_decode`` runs in the
trace."""

from benchmark import shapes_hybrid_moe as shapes

NAME = "decode_hbm_roofline_pct.hybrid_moe"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps):
        return None
    itemsize = obs["work"]["weight_itemsize"]
    least_s = sum(shapes.decode_step_bytes(
        obs["arch"], s["full_rows"], s["experts_hit"], s["slots"], itemsize)
        for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / decode["seconds"]
