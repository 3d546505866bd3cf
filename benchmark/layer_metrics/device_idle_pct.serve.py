"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window,
averaged over the chips (device trace)."""

from benchmark import trace_reduce

NAME = "device_idle_pct.serve"
UNIT = "%"
LAYER = "device"
MOVES = "serve_tokens_per_s"


def read(obs):
    return trace_reduce.idle_pct(obs["trace"])
