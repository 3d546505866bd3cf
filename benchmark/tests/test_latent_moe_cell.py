"""The latent-attention expert cell's own arithmetic: the shapes against a
hand count at the cell's sizes, every new reader against a hand count (and
silent where the program has no such span or counter, as the parent has
not), and the trace reduction on synthetic events."""

import os

import pytest

from benchmark import harness, shapes_latent_moe as shapes
from benchmark import trace_reduce_latent

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = harness.load_json(
    os.path.join(HERE, "configs", "kanana-2-30b-a3b-serve.json"))
ARCH = CONFIG["arch"]
PEAKS = {"bf16_tflops": 197.0, "hbm_gbs": 819.0}


def reader(name):
    return harness.load_module(
        os.path.join(HERE, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def test_shapes_against_a_hand_count():
    # ISSUE 29's table: attention 2048x6144 + 2048x576 + 512x8192 +
    # 4096x2048 (+ the latent's norm, 512)
    attn = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    assert shapes.attention_params(ARCH) == attn == 26_345_984
    expert = 3 * 2048 * 768
    assert shapes.expert_params(ARCH) == expert == 4_718_592
    fixed = attn + 2 * expert + 2048 * 128 + 128 + 2 * 2048
    dense = attn + 3 * 2048 * 6144 + 2 * 2048
    total = dense + 6 * (fixed + 128 * expert) + 2 * 128256 * 2048 + 2048
    assert shapes.param_count(ARCH) == total == 4_429_613_312
    assert shapes.latent_bytes_per_token(ARCH) == 7 * 576 * 2 == 8064
    # a decode step with 240,000 live rows and 600 experts hit
    want = (dense + 6 * fixed + 128256 * 2048 + 2048 + 600 * expert) * 2 \
        + 240_000 * 8064
    assert shapes.decode_step_bytes(ARCH, 240_000, 600) == want
    assert 10.0 < want / 819e9 * 1e3 < 11.0     # the floor: 10.6 ms
    assert shapes.grouped_product_bytes(ARCH, 100) == 100 * expert * 2
    assert shapes.grouped_product_flops(ARCH, 192) == 2 * 192 * expert


def test_chunk_flops_against_a_hand_count():
    n, start = 1024, 3072
    proj = 2048 * 6144 + 2048 * 576 + 4096 * 2048
    per_token = 7 * proj + 3 * 2048 * 6144 \
        + 6 * (8 * 3 * 2048 * 768 + 2048 * 128)
    expand = 7 * (start + n) * 512 * 32 * 256
    attend = 7 * (n * start + n * (n + 1) // 2) * 32 * (192 + 128)
    want = 2 * (n * per_token + expand + attend + 2048 * 128256)
    assert shapes.chunk_flops(ARCH, n, start) == want
    assert 1.5e12 < want < 1.8e12


def obs(regions, steps):
    return {"samples": {"traced_steps": steps}, "regions": regions,
            "arch": ARCH, "peaks": PEAKS,
            "work": {"weight_itemsize": 2, "kv_itemsize": 2}}


STEPS = [{"decoded": 1, "live": 200_000, "experts_hit": 600, "pairs": 1152,
          "chunks": [(2048, 1024)]},
         {"decoded": 1, "live": 100_000, "experts_hit": 500, "pairs": 1152,
          "chunks": []}]
REGIONS = {"jit_decode": {"runs": 2, "seconds": 0.1, "regions": {
    "experts": 0.02, "latent_attention": 0.05}},
    "jit_suffix_routed": {"runs": 1, "seconds": 0.08, "regions": {}}}


def test_readers_against_a_hand_count():
    o = obs(REGIONS, STEPS)
    least = (shapes.decode_step_bytes(ARCH, 200_000, 600)
             + shapes.decode_step_bytes(ARCH, 100_000, 500)) / 819e9
    assert reader("decode_hbm_roofline_pct.latent_moe").read(o) \
        == pytest.approx(100 * least / 0.1)
    experts = 1100 * 4_718_592 * 2 / 819e9      # bytes bound it in decode
    assert reader("moe_experts_roofline_pct").read(o) \
        == pytest.approx(100 * experts / 0.02)
    rows = 300_000 * 8064 / 819e9
    assert reader("latent_attn_roofline_pct").read(o) \
        == pytest.approx(100 * rows / 0.05)
    assert reader("prefill_chunk_mxu_pct").read(o) == pytest.approx(
        100 * shapes.chunk_flops(ARCH, 1024, 2048) / 197e12 / 0.08)
    # every share is one: at the cell's sizes none can pass 100
    for name in ("decode_hbm_roofline_pct.latent_moe",
                 "moe_experts_roofline_pct", "latent_attn_roofline_pct",
                 "prefill_chunk_mxu_pct"):
        assert 0 < reader(name).read(o) < 100
    counters = {"dispatches": 12, "max_expert_load_sum": 60,
                "moe_pairs": 2304, "moe_experts_hit": 1100}
    assert reader("moe_load_max_over_mean").read({"counters": counters}) \
        == pytest.approx((60 / 12) / (2304 / 1100))


@pytest.mark.parametrize("name", [
    "decode_hbm_roofline_pct.latent_moe", "moe_experts_roofline_pct",
    "latent_attn_roofline_pct", "prefill_chunk_mxu_pct"])
def test_a_program_without_the_scopes_leaves_the_metric_out(name):
    """The parent commit has no such program, region or counter: a reader
    returns None (or raises what ``read_layer_metrics`` suppresses)."""
    for regions in (None, {}, {"jit_decode": {"runs": 2, "seconds": 0.1,
                                              "regions": {}}}):
        try:
            value = reader(name).read(obs(regions, STEPS))
        except (KeyError, TypeError, ZeroDivisionError):
            value = None
        if name.startswith("decode_hbm") and regions and "jit_decode" in regions:
            assert value is not None    # the whole step needs no scope
        else:
            assert value is None
    assert reader("moe_load_max_over_mean").read(
        {"counters": {"n_slots": 32}}) is None


COMPILED = """
HloModule jit_decode
%body (p: bf16[8]) -> bf16[8] {
  %fusion.7 = bf16[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(decode)/while/body/closed_call/moe_grouped_matmul/ragged_dot" stack_frame_id=5}
  %ragged-dot-none.1 = f32[8]{0} custom-call(%fusion.7), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %fusion.8 = bf16[8]{0} fusion(%fusion.7), kind=kCustom, metadata={op_name="jit(decode)/while/body/latent_view_gather/gather"}
}
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(), metadata={op_name="jit(decode)/add"}
  %copy.3 = bf16[8]{0} copy(%fusion.1)
}
"""


def test_trace_reduction_of_named_regions():
    regions = trace_reduce_latent.scopes_in(COMPILED)
    assert regions == {"fusion.7": "experts", "ragged-dot-none.1": "experts",
                       "fusion.8": "latent_attention"}
    ms = 1_000_000
    loaded = {
        "modules": [("jit_decode", 0, 10 * ms), ("jit_suffix_routed",
                                                 10 * ms, 20 * ms),
                    ("jit_decode", 30 * ms, 10 * ms)],
        "ops": [("while.1", 0, 9 * ms), ("fusion.7", 1 * ms, 2 * ms),
                ("fusion.8", 3 * ms, 3 * ms),
                # the same instruction name in another program is not it
                ("fusion.7", 12 * ms, 5 * ms),
                ("fusion.7", 31 * ms, 1 * ms), ("fusion.1", 33 * ms, 1 * ms)]}
    out = trace_reduce_latent.reduce(loaded, {"jit_decode": regions})
    assert out["jit_decode"]["runs"] == 2
    assert out["jit_decode"]["seconds"] == pytest.approx(0.020)
    assert out["jit_decode"]["regions"] == pytest.approx(
        {"experts": 0.003, "latent_attention": 0.003})
    assert out["jit_suffix_routed"]["regions"] == {}
    assert trace_reduce_latent.reduce(loaded)["jit_decode"]["regions"] == {}
    assert trace_reduce_latent.reduce(None) is None
