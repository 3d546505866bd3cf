"""Share of its roofline the chunked scan (SSD) of the prefill chunks
reaches: for the chunk tokens the traced steps dispatched (padding not
counted), the larger of the scan's useful operations over the published
bf16 peak and the bytes it must move over the published HBM bandwidth
(``shapes_hybrid_moe.ssd_flops`` / ``ssd_bytes``: every Mamba layer), over
the device time of the operations under the ``ssm_chunk_scan`` scope inside
the chunk program's runs (``jit_suffix_routed``) of the trace. The median
gap of the cell is a decode-only step, which the scan does not enter: it is
booked against ``itl_p50_ms``, the cell's one latency, so that a later
kernel for the scan is decided on a measured share."""

from benchmark import shapes_hybrid_moe as shapes

NAME = "ssm_chunk_scan_roofline_pct"
UNIT = "%"
LAYER = "Mamba-2 mixer, chunk scan (models/hybrid.py)"
MOVES = "itl_p50_ms"


def read(obs):
    chunks = [c for s in obs["samples"]["traced_steps"]
              for c in s["chunks"]]
    prog = (obs["regions"] or {}).get("jit_suffix_routed")
    if not chunks or not prog or prog["runs"] != len(chunks) \
            or not prog["regions"].get("ssm_chunk_scan"):
        return None
    peaks, arch = obs["peaks"], obs["arch"]
    least_s = sum(max(shapes.ssd_flops(arch, n)
                      / (peaks["bf16_tflops"] * 1e12),
                      shapes.ssd_bytes(arch, n) / (peaks["hbm_gbs"] * 1e9))
                  for _, n in chunks)
    return 100.0 * least_s / prog["regions"]["ssm_chunk_scan"]
