"""Of the token-expert pairs the routers chose over the window, the share
that fell on experts this chip HOLDS and so was computed here, in percent:
the share of a deployment at work (16 of 256 experts a layer: 6.25 where the
routing is even). From the program's counters (``metrics.snapshot()["moe"]``:
``moe_pairs_held``, ``moe_pairs_chosen``); a program that knows no share has
neither, and the metric is left out."""

NAME = "moe_pairs_held_pct"
UNIT = "%"
LAYER = "expert layer (moe/dropfree.py)"
MOVES = "itl_p50_ms"


def read(obs):
    c = obs["counters"]
    if not c.get("moe_pairs_chosen") or "moe_pairs_held" not in c:
        return None
    return 100.0 * c["moe_pairs_held"] / c["moe_pairs_chosen"]
