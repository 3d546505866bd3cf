"""Share of its memory roofline the WHOLE decode program of the latent-
attention expert model reaches, over the traced steps. The least time a step
can take is the bytes it must read over the chip's published HBM bandwidth:
every weight outside the routed experts and the head once, the routed
experts that were HIT in that step (the program's counter: distinct experts
with work, summed over the expert layers), and the latent rows of the tokens
its active slots hold (``benchmark/shapes_latent_moe.py``). That, summed
over the traced decode steps, over the device time of the ``jit_decode``
runs in the trace."""

from benchmark import shapes_latent_moe as shapes

NAME = "decode_hbm_roofline_pct.latent_moe"
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
        obs["arch"], s["live"], s["experts_hit"], itemsize)
        for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / decode["seconds"]
