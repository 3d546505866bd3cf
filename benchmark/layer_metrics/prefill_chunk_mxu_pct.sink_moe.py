"""Share of the chip's published bf16 peak the prefill chunk program of the
sink-window / full attention expert model reaches, over the traced steps: the
useful operations of each chunk that ran (its tokens and the position it was
written at, so the rows each query sees, the band counted in the window
layers and not the blocks visited; of a token's chosen experts the share held
here: ``shapes_sink_moe.chunk_flops``; padding rows of a last part-chunk are
not counted) over the device time of the chunk program's runs in the trace
(``jit_suffix_routed``)."""

from benchmark import shapes_sink_moe as shapes

NAME = "prefill_chunk_mxu_pct.sink_moe"
UNIT = "%"
LAYER = "kernels (ops/pallas, decode attention path)"
MOVES = "itl_p50_ms"


def read(obs):
    chunks = [c for s in obs["samples"]["traced_steps"]
              for c in s["chunks"]]
    prog = (obs["regions"] or {}).get("jit_suffix_routed")
    if not chunks or not prog or prog["runs"] != len(chunks):
        return None
    flops = sum(shapes.chunk_flops(obs["arch"], n, start)
                for start, n in chunks)
    return 100.0 * flops / (obs["peaks"]["bf16_tflops"] * 1e12) \
        / prog["seconds"]
