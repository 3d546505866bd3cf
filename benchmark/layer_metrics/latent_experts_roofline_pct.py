"""Share of its roofline the grouped products of the latent experts (up,
then down, 1024 -> 2688 -> 1024, not gated) reach in decode, over the traced
steps: the larger of the bytes of the HELD experts hit (2 x latent x width
x 2 B each: ``shapes_hybrid_moe.grouped_product_bytes``) over the published
HBM bandwidth and the operations of the pairs computed over the published
bf16 peak, over the device time of the operations under the
``moe_grouped_matmul`` scope inside the ``jit_decode`` runs of the trace.
(``moe_experts_roofline_pct`` counts gated experts at d_model and is not
this cell's.)"""

from benchmark import shapes_hybrid_moe as shapes

NAME = "latent_experts_roofline_pct"
UNIT = "%"
LAYER = "expert layer (moe/dropfree.py)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps) \
            or not decode["regions"].get("experts") \
            or "moe_latent_size" not in obs["arch"]:
        return None
    peaks, arch = obs["peaks"], obs["arch"]
    least_s = sum(max(
        shapes.grouped_product_bytes(arch, s["experts_hit"],
                                     obs["work"]["weight_itemsize"])
        / (peaks["hbm_gbs"] * 1e9),
        shapes.grouped_product_flops(arch, s["pairs"])
        / (peaks["bf16_tflops"] * 1e12)) for s in steps)
    return 100.0 * least_s / decode["regions"]["experts"]
