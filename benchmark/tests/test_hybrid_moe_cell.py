"""The Nemotron-H cell's own arithmetic: the configuration file against the
catalog's published keys and its three cuts, the traffic against the cell's
letter, the shapes against a hand count and against the parameters the
program makes (at the tiny widths, and at the published ones by
``jax.eval_shape``), every new reader against a hand count (and silent where
the program has no such scope or counter, as an older program has not), the
regions of a compiled program's text, and the cell rehearsed on the CPU."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark import harness, hybrid_serve_loop, shapes_hybrid_moe as shapes
from benchmark import traffic_gen

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = harness.load_json(
    os.path.join(HERE, "configs", "nemotron-3-super-serve.json"))
TRAFFIC = harness.load_json(
    os.path.join(HERE, "traffic", "chat128-closed.json"))
ARCH = CONFIG["arch"]
PEAKS = {"bf16_tflops": 197.0, "hbm_gbs": 819.0}
CELL = "nemotron-3-super-serve.chat128-closed"
NEW = ("decode_hbm_roofline_pct.hybrid_moe", "ssm_state_roofline_pct",
       "ssm_chunk_scan_roofline_pct", "latent_experts_roofline_pct")


def reader(name):
    return harness.load_module(
        os.path.join(HERE, "layer_metrics", name + ".py"),
        "reader_" + name.replace(".", "_"))


def test_the_configuration_is_the_published_one_with_its_three_cuts():
    # the published catalog (a JSON line per model), where one is given
    catalog = os.environ.get("MODEL_CATALOG_JSONL", "")
    pattern = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
               "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
    assert CONFIG["hybrid_override_pattern"] == pattern
    if os.path.isfile(catalog):
        with open(catalog) as f:
            entry = next(json.loads(line) for line in f
                         if "Nemotron-3-Super" in line)
        published = entry["config"]
        assert CONFIG["source_url"] == entry["source_url"]
        for k, v in published.items():
            if k not in CONFIG["reduced"]:
                assert CONFIG[k] == v, k
    assert CONFIG["reduced"] == ["n_layers", "n_experts", "vocab_size"]
    assert (CONFIG["n_layers"], CONFIG["n_experts"], CONFIG["vocab_size"]) \
        == (11, 128, 32768)
    assert CONFIG["published_vocab_size"] == 131072 == 4 * 32768
    assert CONFIG["num_hidden_layers"] == 88
    assert CONFIG["n_routed_experts"] == 512
    assert len(CONFIG["source"]) <= 200 and CONFIG["chips"] == 1
    assert "4 chips share each layer" in CONFIG["deployment"]
    assert "No MTP" in CONFIG["deployment"]
    # published layers 0-10: one whole period, 5 : 5 : 1
    assert ARCH["layer_kinds"] == pattern[:11] == "MEMEMEM*EME"
    assert ARCH["n_experts"] == 512 and ARCH["moe_top_k"] == 22
    assert (ARCH["moe_local_experts"], ARCH["moe_expert_offset"]) == (128, 0)
    for item in ("router_input", "gated_norm", "no_rotation", "state_dtype",
                 "selection_bias", "mtp"):
        assert item in CONFIG["assumed"]
    serving = CONFIG["init_inference"]["serving"]
    assert (serving["n_slots"], serving["max_len"]) == (128, 10240)
    assert serving["kv_pool"]["n_blocks"] - 1 \
        == 128 * 10240 // serving["kv_pool"]["block_size"]
    assert not serving["kv_pool"]["prefix_cache"]
    manifest = harness.load_json(harness.MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "nemotron-3-super-serve")
    assert entry["source"] == CONFIG["source"]
    assert entry["reduced"] == CONFIG["reduced"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "chat128-closed"
    assert {m["name"] for m in harness.cell_metrics(
        manifest, CELL, "end_to_end")} == {"itl_p50_ms", "setup_s"}


def test_the_traffic_is_the_cells_letter_for_letter():
    assert TRAFFIC["kind"] == "hybrid_serve_loop"
    assert TRAFFIC["arrivals"] == {"kind": "closed", "clients": 128}
    assert TRAFFIC["prompt_len"] == {"dist": "lognormal", "median": 1024,
                                     "sigma": 0.8, "clip": [128, 8192]}
    assert TRAFFIC["output_len"] == {"dist": "lognormal", "median": 768,
                                     "sigma": 0.5, "clip": [256, 2048]}
    assert TRAFFIC["round_size"] == 32 and TRAFFIC["schedule_seed"] == 0
    prompts = traffic_gen.lognormal_quantiles(TRAFFIC["prompt_len"], 32)
    answers = traffic_gen.lognormal_quantiles(TRAFFIC["output_len"], 32)
    assert (prompts.min(), prompts.max()) == (183, 5736)
    assert round(prompts.mean()) == 1385 and round(answers.mean()) == 859
    serving = CONFIG["init_inference"]["serving"]
    chunk = serving["chunked_prefill"]["chunk_size"]
    assert prompts.max() + answers.max() <= serving["max_len"]
    # the checked request: a prompt over two chunks and no multiple of one
    assert any(p > 2 * chunk and p % chunk for p in prompts)
    assert serving["max_queue_depth"] >= TRAFFIC["arrivals"]["clients"]


def test_shapes_against_a_hand_count():
    # published widths: in-proj 4096 x 18,560, out-proj 8192 x 4096, conv, norm and
    # A/D/dt 0.06 M; 128 experts of 2 x 1024 x 2688 held, a shared expert of
    # 2 x 4096 x 5376, the latent projections, the router
    mamba = 4096 * 18560 + 8192 * 4096 + 4 * 10240 + 10240 + 3 * 128 + 8192
    assert shapes.mamba_params(ARCH) == mamba == 109_635_968
    assert shapes.attention_params(ARCH) == 2 * 4096 * 4096 \
        + 2 * 4096 * 256 == 35_651_584
    assert shapes.expert_params(ARCH) == 2 * 1024 * 2688
    fixed_e = 4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376
    assert shapes.expert_layer_fixed_params(ARCH) == fixed_e
    assert round((128 * 2 * 1024 * 2688 + fixed_e) / 1e6, 1) == 759.2
    fixed = 5 * mamba + 5 * fixed_e + 35_651_584 + 12 * 4096 \
        + 32768 * 4096
    assert shapes.fixed_params(ARCH) == fixed
    total = fixed + 32768 * 4096 + 5 * 128 * 2 * 1024 * 2688
    assert shapes.param_count(ARCH) == total == 4_648_163_712
    # the state of 128 slots, read and written: 5 x (4.19 MB + 0.06 MB)
    one = 128 * 64 * 128 * 4 + 3 * 10240 * 2
    assert shapes.state_bytes(ARCH, 128) == 2 * 5 * 128 * one
    assert round(shapes.state_bytes(ARCH, 128) / 1e9, 2) == 5.45
    # a decode step of 128 slots over 240,000 live rows, 640 experts hit
    want = (fixed + 640 * 2 * 1024 * 2688) * 2 + 2 * 5 * 128 * one \
        + 240_000 * 2 * 256 * 2
    assert shapes.decode_step_bytes(ARCH, 240_000, 640, 128) == want
    assert 17.5 < want / 819e9 * 1e3 < 18.5      # the floor: about 18 ms


def test_scan_arithmetic():
    n = 1024
    per_layer = 2 * n * (128 * 8 * 128 + 128 * 8192 + 2 * 8192 * 128)
    assert shapes.ssd_flops(ARCH, n) == 5 * per_layer
    assert shapes.ssd_bytes(ARCH, n) == 5 * (
        n * (2 * 8192 + 2 * 1024 + 128) * 4 + 2 * 8192 * 128 * 4)


def test_the_shapes_count_the_parameters_the_program_makes():
    """At the published widths by ``jax.eval_shape`` (nothing allocated), and
    at the tiny widths the rehearsal runs."""
    import jax

    from deepspeed_tpu.models.layers import Param

    for rehearse in (False, True):
        config = harness.load_sized(os.path.join(
            HERE, "configs", "nemotron-3-super-serve.json"), rehearse)
        model = harness.build_model(config)
        made = jax.eval_shape(lambda r: jax.tree_util.tree_map(
            lambda p: p.value, model.init(r),
            is_leaf=lambda x: isinstance(x, Param)), jax.random.PRNGKey(0))
        count = sum(int(np.prod(a.shape))
                    for a in jax.tree_util.tree_leaves(made))
        assert count == shapes.param_count(config["arch"]) \
            == model.config.num_params()


def obs(regions, steps, counters=None):
    return {"samples": {"traced_steps": steps}, "regions": regions,
            "arch": ARCH, "peaks": PEAKS, "counters": counters or {},
            "work": {"weight_itemsize": 2, "kv_itemsize": 2}}


STEPS = [{"decoded": 1, "full_rows": 240_000, "experts_hit": 640,
          "pairs": 700, "slots": 128, "chunks": [(2048, 1024)]},
         {"decoded": 1, "full_rows": 230_000, "experts_hit": 630,
          "pairs": 704, "slots": 127, "chunks": []}]
REGIONS = {"jit_decode": {"runs": 2, "seconds": 0.050, "regions": {
    "experts": 0.020, "ssm_state_update": 0.015, "full_attention": 0.002}},
    "jit_suffix_routed": {"runs": 1, "seconds": 0.04, "regions": {
        "ssm_chunk_scan": 0.004}}}


def test_readers_against_a_hand_count():
    o = obs(REGIONS, STEPS, {"moe_pairs_chosen": 1_000_000,
                             "moe_pairs_held": 250_000})
    least = (shapes.decode_step_bytes(ARCH, 240_000, 640, 128)
             + shapes.decode_step_bytes(ARCH, 230_000, 630, 127)) / 819e9
    assert reader(NEW[0]).read(o) == pytest.approx(100 * least / 0.05)
    state = (shapes.state_bytes(ARCH, 128) + shapes.state_bytes(ARCH, 127))
    assert reader(NEW[1]).read(o) == pytest.approx(
        100 * state / 819e9 / 0.015)
    scan = max(shapes.ssd_flops(ARCH, 1024) / 197e12,
               shapes.ssd_bytes(ARCH, 1024) / 819e9)
    assert reader(NEW[2]).read(o) == pytest.approx(100 * scan / 0.004)
    experts = (640 + 630) * 2 * 1024 * 2688 * 2 / 819e9
    assert reader(NEW[3]).read(o) == pytest.approx(100 * experts / 0.020)
    assert reader("moe_pairs_held_pct").read(o) == pytest.approx(25.0)
    for name in NEW:
        assert 0 < reader(name).read(o) < 100
    manifest = harness.load_json(harness.MANIFEST)
    listed = {m["name"] for m in harness.cell_metrics(manifest, CELL,
                                                      "per_layer")}
    # the held share is read by its reader in this cell too, but listed for
    # the mimo cell alone (its test holds the list to that cell)
    assert listed == set(NEW) | {"moe_load_max_over_mean"}
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "itl_p50_ms"
        if m["name"] in listed:
            mod = reader(m["name"])
            assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == (
                m["name"], m["unit"], m["layer"], m["moves"])


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_leaves_the_metric_out(name):
    """An older program has no such program, region or counter: a reader
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
  %ssm_state_update.3 = f32[5,128,128,64,128]{4,3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/ssm_decode/ssm_state_update/ssm_state_update/pallas_call"}
  %fusion.7 = bf16[128,3,10240]{2,1,0} fusion(%b), kind=kLoop, metadata={op_name="jit(decode)/ssm_decode/ssm_conv/add"}
  %fusion.8 = f32[128,18560]{1,0} fusion(%b), kind=kOutput, metadata={op_name="jit(decode)/ssm_decode/dot_general"}
  %grouped_matmul.3 = bf16[2816,1024]{1,0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/moe_grouped_matmul/grouped_matmul/pallas_call"}
  %fusion.9 = bf16[128,4096]{1,0} fusion(%c), kind=kOutput, metadata={op_name="jit(decode)/latent_moe_proj/dot_general"}
  %paged_flash_decode.2 = f32[128,2,16,128]{3,2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode)/full_attn_decode/paged_flash_decode/pallas_call"}
  %fusion.20 = f32[1,8,8,16,128,128]{5,4,3,2,1,0} fusion(%d), kind=kOutput, metadata={op_name="jit(chunk)/ssm_chunk_scan/exp"}
  %fusion.1 = bf16[8]{0} fusion(), metadata={op_name="jit(decode)/add"}
}
"""


def test_regions_of_a_compiled_programs_text():
    assert hybrid_serve_loop.scopes_in(COMPILED) == {
        "ssm_state_update.3": "ssm_state_update", "fusion.7": "ssm_conv",
        "fusion.8": "ssm_decode", "grouped_matmul.3": "experts",
        "fusion.9": "latent_proj", "paged_flash_decode.2": "full_attention",
        "fusion.20": "ssm_chunk_scan"}


def test_the_selection_bias_comes_from_the_seed_and_the_checked_request():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import split_params_axes

    small = harness.load_sized(os.path.join(
        HERE, "configs", "nemotron-3-super-serve.json"), True)
    model = harness.build_model(small)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    hybrid_serve_loop.seed_selection_bias(params, 2 ** 31 + 5, 0.005)
    biases = [np.asarray(layer["mixer"]["router"]["bias"])
              for layer in params["layers"] if "router" in layer["mixer"]]
    assert len(biases) == 5 and 0.003 < np.std(biases) < 0.007
    assert len({b.tobytes() for b in biases}) == 5
    recs = [{"prompt_len": n} for n in (64, 100, 16, 96)]
    # 100 is over two chunks of 32 and no multiple of one; 64 and 96 are
    assert hybrid_serve_loop.pick_checked(recs, {"reference_requests": 2},
                                          32) == [recs[1], recs[0]]


def test_the_checked_requests_are_the_first_to_finish_and_keep_their_pick():
    recs = [{"prompt_len": n, "done": t, "refused": False}
            for n, t in ((100, 5.0), (40, 2.0), (30, None), (60, 1.0),
                         (20, 3.0))]
    done = hybrid_serve_loop.finished_in_order(recs)
    assert [r["done"] for r in done] == [1.0, 2.0, 3.0, 5.0]
    limits = {"reference_requests": 2}
    # before the crossing request finishes, the first two; after, it and
    # the first: a request picked at the end was picked since it finished
    assert hybrid_serve_loop.pick_checked(done[:3], limits, 32) \
        == [recs[3], recs[1]]
    assert hybrid_serve_loop.pick_checked(done, limits, 32) \
        == [recs[0], recs[3]]


def test_the_state_checks_by_layer_and_by_mantissa():
    rng = np.random.default_rng(0)
    want = [rng.normal(size=(4, 8, 16)).astype(np.float32)
            for _ in range(3)]
    served = np.stack(want)
    served[1, :2] *= 1.1         # half the rows of one layer 10% off
    assert hybrid_serve_loop.state_error(served, want) == pytest.approx(
        0.1 / 2 ** 0.5, rel=0.05)
    # float32 values: nearly every entry past bf16; rounded to bf16: none;
    # zeros are not counted
    served[0, 0, 0, :4] = 0
    entries, past = hybrid_serve_loop.past_bf16(served)
    assert entries == served.size - 4 and past >= entries - 2
    rounded = served.view(np.uint32) & np.uint32(0xFFFF0000)
    assert hybrid_serve_loop.past_bf16(rounded.view(np.float32)) == (
        entries, 0)
    limits = {"reference_requests": 2, "state_rel_error_limit": 0.05,
              "state_float32_share_limit": 0.5}
    total = {"states": 2, "max_state_rel_error": 0.07, "state_entries": 100,
             "state_entries_past_bf16": 100}
    checks, stats = hybrid_serve_loop.state_passes(total, limits)
    assert checks == {"recurrent_state_matches_reference": False,
                      "recurrent_state_holds_float32": True}
    assert stats == {"state_float32_share": 1.0}
    checks, _ = hybrid_serve_loop.state_passes(
        dict(total, max_state_rel_error=0.01, state_entries_past_bf16=0),
        limits)
    assert checks == {"recurrent_state_matches_reference": True,
                      "recurrent_state_holds_float32": False}
    # a checked request that kept no state fails both
    checks, _ = hybrid_serve_loop.state_passes(
        dict(total, states=1, max_state_rel_error=0.01), limits)
    assert not any(checks.values())


def test_the_cell_rehearses_on_the_cpu():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELL,
         "--seed", str(2 ** 31 + 17), "--seconds", "1.5", "--trace", "1",
         "--rehearse-cpu"],
        cwd=os.path.dirname(HERE), env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert last["note"] == "rehearsal" and last["passed"]
    checks = next(ln for ln in lines if ln.get("note") == "checks")
    assert all(v for k, v in checks.items() if k != "note")
    # the weights come from one fixed key, whatever the seed
    engine = next(ln for ln in lines if ln.get("note") == "engine")
    assert engine["weights_seed"] == hybrid_serve_loop.WEIGHTS_SEED
    window = next(ln for ln in lines if ln.get("note") == "window")
    assert window["ssm"]["state_resets"] > 0
    assert window["kv_pool"]["groups"]["state"]["layers"] == 5
    result = subprocess.run(
        [sys.executable, "-m", "benchmark.check_manifest"],
        cwd=os.path.dirname(HERE), capture_output=True, text=True)
    assert result.returncode == 0, result.stdout
