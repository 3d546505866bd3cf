"""Share of its memory roofline the decode step's recurrence reaches: the
recurrent state of the slots decoded in every Mamba layer, read and written
(float32 S ``[heads, head_dim, state]`` and the bf16 conv tail:
``shapes_hybrid_moe.state_bytes``), over the chip's published HBM
bandwidth, summed over the traced decode steps, over the device time of the
operations under the ``ssm_state_update`` scope inside the ``jit_decode``
runs of the trace. A program without that scope (one that serves no
recurrent state) leaves the metric out."""

from benchmark import shapes_hybrid_moe as shapes

NAME = "ssm_state_roofline_pct"
UNIT = "%"
LAYER = "Mamba-2 mixer, decode recurrence (ops/pallas/ssm_state_update.py)"
MOVES = "itl_p50_ms"


def read(obs):
    steps = [s for s in obs["samples"]["traced_steps"] if s["decoded"]]
    decode = (obs["regions"] or {}).get("jit_decode")
    if not steps or not decode or decode["runs"] != len(steps) \
            or not decode["regions"].get("ssm_state_update"):
        return None
    least_s = sum(shapes.state_bytes(obs["arch"], s["slots"],
                                     obs["work"]["kv_itemsize"])
                  for s in steps) / (obs["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / decode["regions"]["ssm_state_update"]
