"""Share of the traced window in which no operation ran on the device:
1 minus the union of the device's operation intervals over the window,
averaged over the chips (device trace)."""

from benchmark import trace_reduce

NAME = "device_idle_pct.train"
UNIT = "%"
LAYER = "device"
MOVES = "train_tokens_per_s_per_chip"


def read(obs):
    return trace_reduce.idle_pct(obs["trace"])
