"""Share of the traced window in which a collective (all-gather,
all-reduce, reduce-scatter, ...) is the innermost operation on the device's
instruction stream: the core waits for the wire and computes nothing.
Left out where the trace names no collective (device trace)."""

NAME = "collective_exposed_pct"
UNIT = "%"
LAYER = "parallel layout (parallel/, comm/, ZeRO specs)"
MOVES = "train_tokens_per_s_per_chip"


def read(obs):
    trace = obs["trace"]
    if not trace["has_collectives"]:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
