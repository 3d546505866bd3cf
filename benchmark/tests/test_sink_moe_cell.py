"""The sink-window / share-of-experts cell's own arithmetic: the
configuration file against the catalog's published keys and its three cuts,
the traffic against the issue's letter, the shapes against a hand count and
against the parameters the program makes, every new reader against a hand
count (and silent where the program has no such span or counter, as the
parent has not), the regions of a compiled program's text, and the reference
against itself in blocks."""

import os

import numpy as np
import pytest

from benchmark import harness, shapes_sink_moe as shapes
from benchmark import sink_serve_loop, traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = harness.load_json(
    os.path.join(HERE, "configs", "mimo-v2-flash-serve.json"))
TRAFFIC = harness.load_json(
    os.path.join(HERE, "traffic", "agent8k-closed.json"))
ARCH = CONFIG["arch"]
PEAKS = {"bf16_tflops": 197.0, "hbm_gbs": 819.0}
CELL = "mimo-v2-flash-serve.agent8k-closed"
W, F = "sliding_attention", "full_attention"


def reader(name):
    return harness.load_module(
        os.path.join(HERE, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def test_the_configuration_is_the_published_one_with_its_three_cuts():
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    published = {
        "attention_value_scale": 0.707, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384,
        "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
        "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
        "rope_theta": 5000000, "tie_word_embeddings": False,
        "partial_rotary_factor": 0.334, "sliding_window": 128,
        "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 128,
        "hybrid_layer_pattern": pattern, "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "sliding_window_size": 128,
        "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
        "moe_intermediate_size": 2048, "n_routed_experts": 256,
        "n_shared_experts": None, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
        "topk_group": 1, "topk_method": "noaux_tc",
        "routed_scaling_factor": None, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 8, "swa_head_dim": 192,
        "swa_v_head_dim": 128}
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["n_layers", "n_experts", "vocab_size"]
    assert (CONFIG["n_layers"], CONFIG["n_experts"], CONFIG["vocab_size"]) \
        == (7, 16, 19072)
    assert CONFIG["published_vocab_size"] == 152576 == 8 * 19072
    assert len(CONFIG["source"]) <= 200 and CONFIG["chips"] == 1
    assert "16 chips share each layer" in CONFIG["deployment"]
    # published layer 0 (dense, full) and layers 6-11 (one whole period)
    assert ARCH["layer_kinds"] == [F if pattern[i] == 0 else W
                                   for i in (0, 6, 7, 8, 9, 10, 11)]
    assert ARCH["first_k_dense"] == 1 and ARCH["n_experts"] == 256
    assert (ARCH["moe_local_experts"], ARCH["moe_expert_offset"]) == (16, 0)
    assert ARCH["moe_top_k"] == 8 and ARCH["vocab_size"] == 19072
    assert ARCH["rotary_dim"] == int(192 * 0.334) == 64
    for item in ("rope", "value_scale", "sink", "norms", "router",
                 "selection_bias", "mtp", "dtype", "block_size", "n_blocks"):
        assert item in CONFIG["assumed"]
    manifest = harness.load_json(harness.MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "mimo-v2-flash-serve")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "agent8k-closed"
    assert {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")} == {"itl_p50_ms", "setup_s"}


def test_the_traffic_is_the_issues_letter_for_letter():
    assert TRAFFIC["kind"] == "sink_serve_loop"
    assert TRAFFIC["arrivals"] == {"kind": "closed", "clients": 32}
    assert TRAFFIC["prompt_len"] == {"dist": "lognormal", "median": 8192,
                                     "sigma": 0.8, "clip": [1024, 28672]}
    assert TRAFFIC["output_len"] == {"dist": "lognormal", "median": 1536,
                                     "sigma": 0.4, "clip": [768, 3072]}
    assert TRAFFIC["round_size"] == 32 and TRAFFIC["schedule_seed"] == 0
    assert TRAFFIC["ramp"] == {"finished_requests": 8}
    prompts = traffic_gen.lognormal_quantiles(TRAFFIC["prompt_len"], 32)
    answers = traffic_gen.lognormal_quantiles(TRAFFIC["output_len"], 32)
    assert (prompts.min(), prompts.max()) == (1462, 28672)
    assert round(prompts.mean()) == 10458 and round(answers.mean()) == 1644
    serving = CONFIG["init_inference"]["serving"]
    assert prompts.min() > serving["chunked_prefill"]["chunk_size"]
    assert prompts.max() + answers.max() <= serving["max_len"]
    # every request is longer than the band, the ring and a chunk together
    assert CONFIG["checks"]["band_request_min_tokens"] \
        == 128 + 2 * 128 + 1024 + 1 < prompts.min() + answers.min()
    # all 32 sizes of a round fit the full group at once
    blocks = sum(-(-(p + a) // 128) for p, a in zip(prompts, np.sort(answers)))
    assert blocks < serving["kv_pool"]["n_blocks"] - 1


def test_shapes_against_a_hand_count():
    # ISSUE 37: q 4096 x 12288, o 8192 x 4096; a full layer's k 4096 x 768
    # and v 4096 x 512; a window layer's k 4096 x 1536, v 4096 x 1024, sinks
    qo = 4096 * 12288 + 8192 * 4096
    full, window = qo + 4096 * (768 + 512), qo + 4096 * (1536 + 1024) + 64
    assert shapes.attention_params(ARCH, False) == full == 89_128_960
    assert shapes.attention_params(ARCH, True) == window == 94_371_904
    expert = 3 * 4096 * 2048
    assert shapes.expert_params(ARCH) == expert == 25_165_824
    router = 4096 * 256 + 256
    fixed = (2 * full + 5 * window + 7 * 2 * 4096 + 3 * 4096 * 16384
             + 6 * router + 19072 * 4096 + 4096)
    assert shapes.fixed_params(ARCH) == fixed
    total = fixed + 19072 * 4096 + 6 * 16 * expert
    assert shapes.param_count(ARCH) == total == 3_429_955_392
    assert (shapes.window_layers(ARCH), shapes.full_layers(ARCH)) == (5, 2)
    assert shapes.kv_row_bytes(ARCH, False) == 2560
    assert shapes.kv_row_bytes(ARCH, True) == 5120
    # a decode step of 32 slots over 360,000 live rows, 60 held experts hit
    assert shapes.full_attention_bytes(ARCH, 360_000) == 2 * 360_000 * 2560
    assert shapes.window_attention_bytes(ARCH, 32 * 128) \
        == 5 * 32 * 128 * 5120
    want = (fixed + 60 * expert) * 2 + 2 * 360_000 * 2560 \
        + 5 * 32 * 128 * 5120
    assert shapes.decode_step_bytes(ARCH, 360_000, 32 * 128, 60) == want
    assert 8.2 < want / 819e9 * 1e3 < 8.6        # the floor: 8.4 ms


def test_chunk_flops_count_the_band_and_the_share():
    n, start = 1024, 8192
    qo = 4096 * 12288 + 8192 * 4096
    proj = 2 * (qo + 4096 * 1280) + 5 * (qo + 4096 * 2560)
    # of a token's 8 chosen experts, 8 * 16 / 256 = half a pair is held
    per_token = proj + 3 * 4096 * 16384 \
        + 6 * (0.5 * 3 * 4096 * 2048 + 4096 * 256)
    seen_full = n * start + n * (n + 1) // 2
    seen_window = n * 128                  # every query sees a whole window
    attend = (2 * seen_full + 5 * seen_window) * 64 * (192 + 128)
    want = 2 * (n * per_token + attend + 4096 * 19072)
    assert shapes.chunk_flops(ARCH, n, start) == pytest.approx(want)
    assert 2.5e12 < want < 2.8e12


def test_the_shapes_count_the_parameters_the_program_makes():
    import jax

    from deepspeed_tpu.models.layers import Param

    model = harness.build_model(CONFIG)
    made = jax.eval_shape(lambda r: jax.tree_util.tree_map(
        lambda p: p.value, model.init(r),
        is_leaf=lambda x: isinstance(x, Param)), jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(made))
    assert count == shapes.param_count(ARCH) == model.config.num_params()


def obs(regions, steps, counters=None):
    return {"samples": {"traced_steps": steps}, "regions": regions,
            "arch": ARCH, "peaks": PEAKS, "counters": counters or {},
            "work": {"weight_itemsize": 2, "kv_itemsize": 2}}


STEPS = [{"decoded": 1, "full_rows": 360_000, "window_rows": 4096,
          "experts_hit": 60, "pairs": 98, "slots": 32,
          "chunks": [(8192, 1024)]},
         {"decoded": 1, "full_rows": 300_000, "window_rows": 4096,
          "experts_hit": 64, "pairs": 104, "slots": 32, "chunks": []}]
REGIONS = {"jit_decode": {"runs": 2, "seconds": 0.024, "regions": {
    "experts": 0.009, "sink_window_attention": 0.001,
    "full_attention": 0.005, "row_write": 0.0004}},
    "jit_suffix_routed": {"runs": 1, "seconds": 0.2, "regions": {
        "sink_window_chunk_attention": 0.03,
        "full_chunk_attention": 0.02}}}
NEW = ("decode_hbm_roofline_pct.sink_moe", "sink_window_attn_roofline_pct",
       "asym_full_attn_roofline_pct", "prefill_chunk_mxu_pct.sink_moe",
       "moe_pairs_held_pct")


def test_readers_against_a_hand_count():
    o = obs(REGIONS, STEPS, {"window_group_blocks": 64,
                             "full_group_blocks": 2900,
                             "moe_pairs_chosen": 1_000_000,
                             "moe_pairs_held": 62_400})
    least = (shapes.decode_step_bytes(ARCH, 360_000, 4096, 60)
             + shapes.decode_step_bytes(ARCH, 300_000, 4096, 64)) / 819e9
    assert reader(NEW[0]).read(o) == pytest.approx(100 * least / 0.024)
    assert reader(NEW[1]).read(o) == pytest.approx(
        100 * (2 * 5 * 4096 * 5120 / 819e9) / 0.001)
    assert reader(NEW[2]).read(o) == pytest.approx(
        100 * (2 * 660_000 * 2560 / 819e9) / 0.005)
    assert reader(NEW[3]).read(o) == pytest.approx(
        100 * shapes.chunk_flops(ARCH, 1024, 8192) / 197e12 / 0.2)
    assert reader(NEW[4]).read(o) == pytest.approx(6.24)
    assert reader("kv_window_blocks_pct").read(o) == pytest.approx(
        100 * 64 / 2900)
    # the accepted expert readers take this runner's obs as it is: the bytes
    # are those of the HELD experts hit
    experts = (60 + 64) * 25_165_824 * 2 / 819e9
    assert reader("moe_experts_roofline_pct").read(o) \
        == pytest.approx(100 * experts / 0.009)
    for name in NEW + ("moe_experts_roofline_pct",):
        assert 0 < reader(name).read(o) < 100
    manifest = harness.load_json(harness.MANIFEST)
    listed = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                      "per_layer")}
    assert listed == set(NEW) | {"kv_window_blocks_pct",
                                 "moe_experts_roofline_pct",
                                 "moe_load_max_over_mean"}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p50_ms"
        if m["name"] in listed:
            mod = reader(m["name"])
            assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
                m["name"], m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("name", NEW)
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
  %paged_flash_decode.8 = f32[32,8,1024]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/sink_window_attn_decode/paged_flash_decode/pallas_call"}
  %paged_flash_decode.12 = f32[32,16,512]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/while/body/closed_call/full_attn_decode/paged_flash_decode/pallas_call"}
  %fusion.9 = bf16[5,65,128,1536]{3,2,1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(decode)/paged_row_write/scatter"}
  %grouped_matmul.3 = bf16[256,4096]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/moe_grouped_matmul/grouped_matmul/pallas_call"}
  %fusion.20 = f32[1,8,8,1024,1024]{4,3,2,1,0} fusion(%d), kind=kOutput, metadata={op_name="jit(chunk)/sink_window_chunk_attn/while/body/mul"}
  %fusion.21 = f32[1,4,16,1024,1024]{4,3,2,1,0} fusion(%d), kind=kOutput, metadata={op_name="jit(chunk)/full_chunk_attn/while/body/mul"}
  %fusion.1 = bf16[8]{0} fusion(), metadata={op_name="jit(decode)/add"}
}
"""


def test_regions_of_a_compiled_programs_text():
    assert sink_serve_loop.scopes_in(COMPILED) == {
        "paged_flash_decode.8": "sink_window_attention",
        "paged_flash_decode.12": "full_attention",
        "fusion.9": "row_write", "grouped_matmul.3": "experts",
        "fusion.20": "sink_window_chunk_attention",
        "fusion.21": "full_chunk_attention"}


def test_the_reference_in_blocks_equals_itself_and_the_sinks_are_seeded(
        monkeypatch):
    """The reference's attention over blocks of 16 queries gives what blocks
    of 64 give; the runner's sinks come from the seed at the file's mean and
    spread, the same for the same seed."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import sink_window_moe_decoder as ref
    from deepspeed_tpu.models import split_params_axes

    small = harness.load_sized(os.path.join(
        HERE, "configs", "mimo-v2-flash-serve.json"), True)
    model = harness.build_model(small)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    assert float(jnp.abs(params["kv_window"]["sink"]).max()) == 0.0
    sink_serve_loop.seed_sinks(params, 2 ** 31 + 5, small["sink_mean"],
                               small["sink_std"])
    sinks = np.asarray(params["kv_window"]["sink"])
    assert sinks.shape == (5, 8) and abs(sinks.mean() - 1.0) < 0.3
    again = dict(params, kv_window=dict(params["kv_window"]))
    sink_serve_loop.seed_sinks(again, 2 ** 31 + 5, small["sink_mean"],
                               small["sink_std"])
    np.testing.assert_array_equal(np.asarray(again["kv_window"]["sink"]),
                                  sinks)
    ids = np.random.default_rng(0).integers(0, 512, 150).astype(np.int32)
    whole = np.asarray(ref.logits_at(params, ids, small["arch"], 100, 50))
    monkeypatch.setattr(ref, "Q_BLOCK", 16)
    jax.clear_caches()         # the jitted block closed over the old size
    blocks = np.asarray(ref.logits_at(params, ids, small["arch"], 100, 50))
    np.testing.assert_allclose(blocks, whole, atol=2e-6)


def test_traced_steps_are_entered_by_the_decode_programs_they_dispatched():
    """The engine sends a decode-only step's next decode behind its own: a
    traced step stands for as many ``jit_decode`` runs as it dispatched, and
    its chunks are counted once."""
    step = {"decoded": 1, "full_rows": 10, "experts_hit": 3,
            "chunks": [(0, 64)]}
    one = sink_serve_loop.traced_entries(dict(step, programs=1))
    assert one == [dict(step, programs=1)]
    two = sink_serve_loop.traced_entries(dict(step, programs=2))
    assert [e["decoded"] for e in two] == [1, 1]
    assert [e["chunks"] for e in two] == [[(0, 64)], []]
    none = sink_serve_loop.traced_entries(dict(step, programs=0))
    assert [e["decoded"] for e in none] == [0]
    assert none[0]["chunks"] == [(0, 64)]
