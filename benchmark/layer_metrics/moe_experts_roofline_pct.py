"""Share of its roofline the grouped expert product (gate/up, then down)
reaches in decode, over the traced steps. The least time is the larger of
the bytes of the experts HIT over the published HBM bandwidth and the
operations of the pairs computed over the published bf16 peak (in decode the
bytes bound it: 192 pairs a layer over about 100 experts hit). That, over
the device time of the operations under the ``moe_grouped_matmul`` scope
inside the ``jit_decode`` runs of the trace."""

from benchmark import shapes_latent_moe as shapes

NAME = "moe_experts_roofline_pct"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps) \
            or not decode["regions"].get("experts"):
        return None
    peaks = obs["peaks"]
    least_s = sum(max(
        shapes.grouped_product_bytes(obs["arch"], s["experts_hit"],
                                     obs["work"]["weight_itemsize"])
        / (peaks["hbm_gbs"] * 1e9),
        shapes.grouped_product_flops(obs["arch"], s["pairs"])
        / (peaks["bf16_tflops"] * 1e12)) for s in steps)
    return 100.0 * least_s / decode["regions"]["experts"]
