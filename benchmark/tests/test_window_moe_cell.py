"""The window / full attention expert cell's own arithmetic: the
configuration file against the catalog's published keys, the shapes against a
hand count and against the parameters the program makes, every new reader
against a hand count (and silent where the program has no such span or
counter, as the parent has not), the regions of a compiled program's text, and
the reference against itself in blocks."""

import os

import numpy as np
import pytest

from benchmark import harness, shapes_window_moe as shapes
from benchmark import traffic_gen, window_serve_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = harness.load_json(
    os.path.join(HERE, "configs", "trinity-mini-serve.json"))
TRAFFIC = harness.load_json(
    os.path.join(HERE, "traffic", "longmix-closed.json"))
ARCH = CONFIG["arch"]
PEAKS = {"bf16_tflops": 197.0, "hbm_gbs": 819.0}
CELL = "trinity-mini-serve.longmix-closed"


def reader(name):
    return harness.load_module(
        os.path.join(HERE, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    period = ["sliding_attention"] * 3 + ["full_attention"]
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "layer_types": period * 8, "load_balance_coeff": 0.001,
        "max_position_embeddings": 131072, "model_type": "afmoe",
        "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1,
        "num_attention_heads": 32, "num_dense_layers": 2,
        "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
        "sliding_window": 2048, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["n_layers"] and CONFIG["n_layers"] == 5
    assert len(CONFIG["source"]) <= 200 and CONFIG["chips"] == 1
    # published layer 1 (dense, window) and layers 4-7 (one whole period)
    assert ARCH["layer_kinds"] == [published["layer_types"][i]
                                   for i in (1, 4, 5, 6, 7)]
    assert ARCH["first_k_dense"] == 1 and ARCH["n_experts"] == 128
    assert ARCH["moe_top_k"] == 8 and ARCH["vocab_size"] == 200192
    assert ARCH["embed_scale"] == 2048 ** 0.5
    for item in ("embed_scale", "rope", "output_gate", "norms", "dtype",
                 "selection_bias", "block_size", "n_blocks"):
        assert item in CONFIG["assumed"]
    manifest = harness.load_json(harness.MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "trinity-mini-serve")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == ["n_layers"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "longmix-closed"
    assert {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")} == {"itl_p50_ms", "setup_s"}


def test_the_traffic_is_the_issues_letter_for_letter():
    assert TRAFFIC["kind"] == "window_serve_loop"
    assert TRAFFIC["arrivals"] == {"kind": "closed", "clients": 32}
    assert TRAFFIC["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                     "sigma": 0.9, "clip": [512, 30720]}
    assert TRAFFIC["output_len"] == {"dist": "lognormal", "median": 256,
                                     "sigma": 0.5, "clip": [64, 768]}
    assert TRAFFIC["round_size"] == 32 and TRAFFIC["schedule_seed"] == 0
    prompts = traffic_gen.lognormal_quantiles(TRAFFIC["prompt_len"], 32)
    answers = traffic_gen.lognormal_quantiles(TRAFFIC["output_len"], 32)
    assert (prompts.min(), prompts.max()) == (1179, 30720)
    assert round(prompts.mean()) == 10965 and round(answers.mean()) == 288
    serving = CONFIG["init_inference"]["serving"]
    assert prompts.min() > serving["chunked_prefill"]["chunk_size"]
    assert prompts.max() + answers.max() <= serving["max_len"]
    # one checked request is longer than two windows and a chunk
    assert CONFIG["checks"]["band_request_min_tokens"] == 2 * 2048 + 1024 + 1


def test_shapes_against_a_hand_count():
    # ISSUE 34: q 2048x4096, k and v 2048x512, o 4096x2048, the output gate
    # 2048x4096 (+ the q and k norms' 128 each)
    attn = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2048 * 4096 + 256
    assert shapes.attention_params(ARCH) == attn == 27_263_232
    expert = 3 * 2048 * 1024
    assert shapes.expert_params(ARCH) == expert == 6_291_456
    fixed = attn + expert + 2048 * 128 + 128 + 4 * 2048
    dense = attn + 3 * 2048 * 6144 + 4 * 2048
    total = dense + 4 * (fixed + 128 * expert) + 2 * 200192 * 2048 + 2048
    assert shapes.param_count(ARCH) == total == 4_241_534_720
    assert (shapes.window_layers(ARCH), shapes.full_layers(ARCH)) == (4, 1)
    assert shapes.kv_row_bytes(ARCH) == 2048
    # a decode step of 26 slots over 290,000 live rows, 412 experts hit
    rows = (1 * 290_000 + 4 * 26 * 2048) * 2048
    assert shapes.attention_bytes(ARCH, 290_000, 26 * 2048) == rows
    want = (dense + 4 * fixed + 200192 * 2048 + 2048 + 412 * expert) * 2 \
        + rows
    assert shapes.decode_step_bytes(ARCH, 290_000, 26 * 2048, 412) == want
    assert 9.0 < want / 819e9 * 1e3 < 9.2        # the floor: 9.1 ms
    # through a uniform pool the four window layers would read every live
    # row: five times what their bands hold
    assert 4 * 290_000 * 2048 > 5 * 4 * 26 * 2048 * 2048


def test_chunk_flops_count_the_band():
    n, start = 1024, 8192
    proj = 2048 * 4096 * 2 + 2 * 2048 * 512 + 4096 * 2048
    per_token = 5 * proj + 3 * 2048 * 6144 \
        + 4 * (9 * 3 * 2048 * 1024 + 2048 * 128)
    full = n * start + n * (n + 1) // 2
    window = n * 2048                      # every query sees a whole window
    attend = (full + 4 * window) * 32 * 256
    want = 2 * (n * per_token + attend + 2048 * 200192)
    assert shapes.chunk_flops(ARCH, n, start) == want
    # the first chunk: a query sees the rows before and at it, under a window
    assert shapes.chunk_flops(ARCH, 1024, 0) == 2 * (
        n * per_token + 5 * (n * (n + 1) // 2) * 32 * 256 + 2048 * 200192)
    assert 1.0e12 < want < 1.2e12


def test_the_shapes_count_the_parameters_the_program_makes():
    import jax

    from deepspeed_tpu.models.layers import Param

    model = harness.build_model(CONFIG)
    made = jax.eval_shape(lambda r: jax.tree_util.tree_map(
        lambda p: p.value, model.init(r),
        is_leaf=lambda x: isinstance(x, Param)), jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(made))
    assert count == shapes.param_count(ARCH) == 4_241_534_720


def obs(regions, steps, counters=None):
    return {"samples": {"traced_steps": steps}, "regions": regions,
            "arch": ARCH, "peaks": PEAKS, "counters": counters or {},
            "work": {"weight_itemsize": 2, "kv_itemsize": 2}}


STEPS = [{"decoded": 1, "full_rows": 290_000, "window_rows": 50_000,
          "experts_hit": 412, "pairs": 208 * 4, "slots": 26,
          "chunks": [(8192, 1024)]},
         {"decoded": 1, "full_rows": 150_000, "window_rows": 40_000,
          "experts_hit": 380, "pairs": 200 * 4, "slots": 25, "chunks": []}]
REGIONS = {"jit_decode": {"runs": 2, "seconds": 0.034, "regions": {
    "experts": 0.02, "window_attention": 0.002, "full_attention": 0.002,
    "row_write": 0.0004}},
    "jit_suffix_routed": {"runs": 1, "seconds": 0.05, "regions": {
        "window_chunk_attention": 0.008, "full_chunk_attention": 0.004}}}
NEW = ("decode_hbm_roofline_pct.window_moe", "window_attn_roofline_pct",
       "prefill_chunk_mxu_pct.window_moe")


def test_readers_against_a_hand_count():
    o = obs(REGIONS, STEPS, {"window_group_blocks": 480,
                             "full_group_blocks": 2400})
    least = (shapes.decode_step_bytes(ARCH, 290_000, 50_000, 412)
             + shapes.decode_step_bytes(ARCH, 150_000, 40_000, 380)) / 819e9
    assert reader(NEW[0]).read(o) == pytest.approx(100 * least / 0.034)
    rows = (440_000 + 4 * 90_000) * 2048 / 819e9
    assert reader(NEW[1]).read(o) == pytest.approx(100 * rows / 0.004)
    assert reader(NEW[2]).read(o) == pytest.approx(
        100 * shapes.chunk_flops(ARCH, 1024, 8192) / 197e12 / 0.05)
    assert reader("kv_window_blocks_pct").read(o) == pytest.approx(20.0)
    # the accepted expert readers take this runner's obs as it is
    experts = (412 + 380) * 6_291_456 * 2 / 819e9
    assert reader("moe_experts_roofline_pct").read(o) \
        == pytest.approx(100 * experts / 0.02)
    for name in NEW + ("moe_experts_roofline_pct",):
        assert 0 < reader(name).read(o) < 100
    manifest = harness.load_json(harness.MANIFEST)
    listed = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                      "per_layer")}
    assert listed == set(NEW) | {"kv_window_blocks_pct",
                                 "moe_experts_roofline_pct",
                                 "moe_load_max_over_mean"}
    for m in manifest["per_layer"]:
        if m["name"] in listed:
            mod = reader(m["name"])
            assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
                m["name"], m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("name", NEW + ("kv_window_blocks_pct",))
def test_a_program_without_the_scopes_leaves_the_metric_out(name):
    """The parent commit has no such program, region or counter: a reader
    returns None (or raises what ``read_layer_metrics`` suppresses)."""
    for regions in (None, {}, {"jit_decode": {"runs": 2, "seconds": 0.1,
                                              "regions": {}}}):
        try:
            value = reader(name).read(obs(regions, STEPS))
        except (KeyError, TypeError, ZeroDivisionError):
            value = None
        if name.startswith("decode_hbm") and regions:
            assert value is not None    # the whole step needs no scope
        else:
            assert value is None


COMPILED = """
HloModule jit_decode
ENTRY %main {
  %paged_flash_decode.8 = f32[32,8,512]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/window_attn_decode/paged_flash_decode/pallas_call"}
  %paged_flash_decode.12 = f32[32,8,512]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/while/body/closed_call/full_attn_decode/paged_flash_decode/pallas_call"}
  %fusion.9 = bf16[4,545,128,512]{3,2,1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(decode)/paged_row_write/scatter"}
  %ragged-dot-none.3 = f32[256,2048]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.20 = f32[1,4,8,1024,1024]{4,3,2,1,0} fusion(%d), kind=kOutput, metadata={op_name="jit(chunk)/window_chunk_attn/while/body/mul"}
  %fusion.21 = f32[1,4,8,1024,1024]{4,3,2,1,0} fusion(%d), kind=kOutput, metadata={op_name="jit(chunk)/full_chunk_attn/while/body/mul"}
  %fusion.1 = bf16[8]{0} fusion(), metadata={op_name="jit(decode)/add"}
}
"""


def test_regions_of_a_compiled_programs_text():
    assert window_serve_loop.scopes_in(COMPILED) == {
        "paged_flash_decode.8": "window_attention",
        "paged_flash_decode.12": "full_attention",
        "fusion.9": "row_write", "ragged-dot-none.3": "experts",
        "fusion.20": "window_chunk_attention",
        "fusion.21": "full_chunk_attention"}


def test_the_reference_in_blocks_equals_itself_and_picks_its_requests(
        monkeypatch):
    """The reference's attention over blocks of 32 queries gives what blocks
    of 128 give; the ramp's choice of checked requests puts one that crosses
    the band first."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import window_moe_decoder as ref
    from deepspeed_tpu.models import split_params_axes

    small = harness.load_sized(os.path.join(
        HERE, "configs", "trinity-mini-serve.json"), True)
    model = harness.build_model(small)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    ids = np.random.default_rng(0).integers(0, 512, 150).astype(np.int32)
    whole = np.asarray(ref.logits_at(params, ids, small["arch"], 100, 50))
    monkeypatch.setattr(ref, "Q_BLOCK", 32)
    jax.clear_caches()         # the jitted block closed over the old size
    blocks = np.asarray(ref.logits_at(params, ids, small["arch"], 100, 50))
    np.testing.assert_allclose(blocks, whole, atol=2e-6)
    rec = lambda p, t: {"prompt_len": p, "tokens": [0] * t}
    finished = [rec(1200, 90), rec(3000, 200), rec(5000, 121), rec(9000, 64)]
    limits = {"reference_requests": 2, "band_request_min_tokens": 5121}
    assert window_serve_loop.pick_checked(finished, limits) \
        == [finished[2], finished[0]]
    assert window_serve_loop.pick_checked(finished[:2], limits) \
        == finished[:2]
