"""Share of its memory roofline the WHOLE decode program of the sink-window /
full attention expert model reaches, over the traced steps. The least time a
step can take is the bytes it must read over the chip's published HBM
bandwidth: every weight outside the routed experts once, the HELD routed
experts that were hit in that step (the program's counter), the live K/V rows
of its active slots in every full layer and min(live, window) rows of each in
every window layer, each kind's rows at their own widths
(``benchmark/shapes_sink_moe.py``). That, summed over the traced decode steps,
over the device time of the ``jit_decode`` runs in the trace."""

from benchmark import shapes_sink_moe as shapes

NAME = "decode_hbm_roofline_pct.sink_moe"
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
        obs["arch"], s["full_rows"], s["window_rows"], s["experts_hit"],
        itemsize) for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / decode["seconds"]
