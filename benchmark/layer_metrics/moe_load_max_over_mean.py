"""How uneven the routing was over the window: the mean over dispatches (one
expert layer in one program run) of the most pairs any expert got, over the
mean pairs of an expert with work. 1 is an even spread; a grouped product's
longest group sets how long its rows wait. From the program's counters
(``metrics.snapshot()["moe"]``: ``max_expert_load_sum``, ``dispatches``,
``moe_pairs``, ``moe_experts_hit``)."""

NAME = "moe_load_max_over_mean"
UNIT = "ratio"
LAYER = "expert layer (moe/dropfree.py)"
MOVES = "itl_p50_ms"


def read(obs):
    c = obs["counters"]
    if not c.get("dispatches") or not c.get("moe_experts_hit"):
        return None
    return (c["max_expert_load_sum"] / c["dispatches"]) \
        / (c["moe_pairs"] / c["moe_experts_hit"])
