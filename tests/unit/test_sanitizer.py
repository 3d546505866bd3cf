"""Program-sanitizer tests: fixture HLO per rule, planted-defect REAL
programs, and the serving-decode tier-1 gate.

Three layers, mirroring test_collective_audit.py's structure:

1. Hand-built HLO fixtures, one planted defect per rule — pins each rule's
   detection, severity, and byte attribution without compiling anything.
2. REAL planted-defect programs (``tools/program_lint.py``'s self-test
   pair): the defective twin must light up every rule through an actual
   lower+compile; the clean twin must produce nothing above info.
3. The serving decode program, audited end to end and held to the
   checked-in ``serving-decode-paged/8/bf16`` (the view) and
   ``serving-decode-fused/8/bf16`` (the kernel) budgets. (The tiny
   TRAINING preset's sanitizer gate lives in test_collective_audit.py,
   riding the cached tiny-test audit.)
"""

import json
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "tools"))

from deepspeed_tpu.profiling.sanitizer import (  # noqa: E402
    check_sanitizer_budgets,
    count_at_or_above,
    estimate_peak_hbm,
    merge_reports,
    parse_entry_outputs,
    parse_entry_params,
    parse_input_output_alias,
    rule_recompile_hazard,
    sanitize_hlo,
    sanitize_jaxpr,
)

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BUDGETS = json.load(open(os.path.join(REPO, "tools", "collective_budgets.json")))


# ---------------------------------------------------------------------------
# 1. fixture HLO, one planted defect per rule
# ---------------------------------------------------------------------------

HLO_DTYPE_LEAK = """
HloModule jit_step, entry_computation_layout={(bf16[64,64]{1,0})->bf16[64,64]{1,0}}

body.1 {
  p.1 = f32[8]{0} parameter(0)
  x.1 = f32[64,64]{1,0} broadcast(p.1), dimensions={0}
  y.1 = f32[64,64]{1,0} broadcast(p.1), dimensions={0}
  w.1 = bf16[64,64]{1,0} all-gather(q.1), channel_id=1, dimensions={0}
  d.1 = f32[64,64]{1,0} dot(x.1, y.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/leaky/dot_general"}
  g.1 = f32[64,64]{1,0} all-gather(s.1), channel_id=2, dimensions={0}
  d.2 = bf16[64,64]{1,0} dot(w.1, w.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT r.1 = f32[8]{0} add(p.1, p.1)
}

ENTRY main.9_spmd {
  a.1 = bf16[64,64]{1,0} parameter(0)
  wl.1 = f32[8]{0} while(init.1), condition=cond.9, body=body.1
  ROOT out.1 = bf16[64,64]{1,0} copy(a.1)
}
"""


def test_dtype_leak_attribution_and_trip():
    r = sanitize_hlo(HLO_DTYPE_LEAK, {"compute_dtype": "bf16"},
                     n_devices=8, loop_trip_count=24)
    leaks = [f for f in r["findings"] if f["rule"] == "dtype-leak"]
    # the f32 dot AND the f32 all-gather, not the bf16 dot/gather
    assert {f["instruction"] for f in leaks} == {"d.1", "g.1"}
    s = r["summary"]
    # both dots are 64x64x64 matmuls in the x24 while body; half the flops f32
    assert s["f32_dot_flops_frac"] == pytest.approx(0.5)
    assert s["total_dot_flops"] == pytest.approx(2 * 2 * 64 ** 3 * 24)
    # one f32 dot = 50% of dot flops >= the 1% warn threshold -> escalated
    d = next(f for f in leaks if f["instruction"] == "d.1")
    assert d["severity"] == "warning"
    assert d["op_name"] == "jit(f)/leaky/dot_general"
    # collective wire: all-gather in-body, ring accounting x24 (no groups ->
    # single-participant fallback frac=1.0 is not used: default_n=1 -> frac 1)
    assert s["f32_collective_wire_bytes"] > 0


def test_dtype_leak_allowlist_demotes():
    r = sanitize_hlo(HLO_DTYPE_LEAK,
                     {"compute_dtype": "bf16", "allow": ["dtype-leak:leaky"]},
                     n_devices=8, loop_trip_count=1)
    d = next(f for f in r["findings"] if f["instruction"] == "d.1")
    assert d["allowed"] and d["severity"] == "info"
    # allowed findings drop out of the summary counters
    assert all(f["severity"] != "warning" or f["instruction"] != "d.1"
               for f in r["findings"])
    # fp32-configured program: f32 compute is not a leak at all
    r32 = sanitize_hlo(HLO_DTYPE_LEAK, {"compute_dtype": "f32"}, 8)
    assert not [f for f in r32["findings"] if f["rule"] == "dtype-leak"]


HLO_DONATION = """
HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[100]{0}, f32[200]{0}, f32[50]{0})->(f32[100]{0}, f32[200]{0})}

ENTRY main.5_spmd {
  p0.1 = f32[100]{0} parameter(0), metadata={op_name="params"}
  p1.1 = f32[200]{0} parameter(1), metadata={op_name="opt_state"}
  p2.1 = f32[50]{0} parameter(2), metadata={op_name="batch"}
  a.1 = f32[100]{0} add(p0.1, p0.1)
  b.1 = f32[200]{0} multiply(p1.1, p1.1)
  ROOT t.1 = (f32[100]{0}, f32[200]{0}) tuple(a.1, b.1)
}
"""


def test_donation_rule_flags_matching_unaliased_input():
    assert parse_input_output_alias(HLO_DONATION) == {0: 0}
    assert [p["op_name"] for p in parse_entry_params(HLO_DONATION)] == \
        ["params", "opt_state", "batch"]
    assert parse_entry_outputs(HLO_DONATION) == \
        [("f32", "100"), ("f32", "200")]
    r = sanitize_hlo(HLO_DONATION, {"compute_dtype": "f32",
                                    "donation_bytes_threshold": 100})
    d = [f for f in r["findings"] if f["rule"] == "donation"]
    # opt_state (f32[200], un-aliased, matches un-aliased output #1) fires;
    # params is aliased, batch (f32[50]) matches no output shape
    assert len(d) == 1
    assert d[0]["op_name"] == "opt_state"
    assert d[0]["bytes"] == 800 and d[0]["output_index"] == 1
    assert d[0]["severity"] == "warning"
    assert r["summary"]["undonated_candidate_bytes"] == 800
    assert r["summary"]["n_aliased_params"] == 1
    # above the error threshold the severity escalates
    r2 = sanitize_hlo(HLO_DONATION, {"compute_dtype": "f32",
                                     "donation_bytes_threshold": 100,
                                     "donation_error_bytes": 500})
    d2 = [f for f in r2["findings"] if f["rule"] == "donation"]
    assert d2[0]["severity"] == "error"


HLO_TRANSFER = """
HloModule jit_step, entry_computation_layout={(f32[10]{0})->f32[10]{0}}

ENTRY main.7_spmd {
  p0.1 = f32[10]{0} parameter(0)
  tk.1 = token[] after-all()
  of.1 = token[] outfeed(p0.1, tk.1), outfeed_config="x"
  cc.1 = (f32[1]{0}) custom-call(p0.1), custom_call_target="xla_python_cpu_callback", custom_call_has_side_effect=true
  h.1 = f32[10]{0:S(5)} copy(p0.1)
  ROOT r.1 = f32[10]{0} add(p0.1, p0.1)
}
"""


def test_transfer_rule_fires_on_every_host_path():
    r = sanitize_hlo(HLO_TRANSFER, {"compute_dtype": "f32"})
    t = [f for f in r["findings"] if f["rule"] == "transfer"]
    assert {f["instruction"] for f in t} == {"of.1", "cc.1", "h.1"}
    assert all(f["severity"] == "error" for f in t)
    assert r["summary"]["transfer_count"] == 3
    assert r["summary"]["max_severity"] == "error"


HLO_SHARDING = """
HloModule jit_step, entry_computation_layout={(f32[300000]{0})->f32[300000]{0}}

body.2 {
  p.1 = f32[8]{0} parameter(0)
  ag.1 = bf16[1048576]{0} all-gather(q.1), channel_id=1, dimensions={0}
  ROOT r.1 = f32[8]{0} add(p.1, p.1)
}

ENTRY main.11_spmd {
  big.1 = f32[300000]{0} parameter(0), sharding={replicated}, metadata={op_name="frozen_table"}
  small.1 = f32[10]{0} parameter(1), sharding={replicated}
  sharded.1 = f32[4096]{0} parameter(2), sharding={devices=[8]<=[8]}
  wl.1 = f32[8]{0} while(init.1), condition=cond.11, body=body.2
  eg.1 = f32[1048576]{0} all-gather(sharded.1), channel_id=2, dimensions={0}
  ROOT out.1 = f32[300000]{0} copy(big.1)
}
"""


def test_sharding_rule_replicated_and_entry_gathers():
    r = sanitize_hlo(HLO_SHARDING, {"compute_dtype": "f32"}, n_devices=8)
    s = [f for f in r["findings"] if f["rule"] == "sharding"]
    # the 1.2 MB replicated table fires; the 40 B replicated scalar and the
    # properly sharded param do not
    rep = [f for f in s if "replicated" in f["message"]]
    assert len(rep) == 1 and rep[0]["op_name"] == "frozen_table"
    assert rep[0]["bytes"] == 300000 * 4
    # the 4 MB ENTRY-scope gather fires; the while-body (gather island) one
    # does not
    eg = [f for f in s if "ENTRY scope" in f["message"]]
    assert len(eg) == 1 and eg[0]["instruction"] == "eg.1"
    assert r["summary"]["replicated_bytes"] == 300000 * 4
    assert r["summary"]["entry_gather_bytes"] == 1048576 * 4


HLO_PEAK = """
HloModule jit_step, entry_computation_layout={(f32[100]{0}, f32[200]{0})->(f32[100]{0}, f32[200]{0})}

ENTRY main.3_spmd {
  p0.1 = f32[100]{0} parameter(0)
  p1.1 = f32[200]{0} parameter(1)
  a.1 = f32[100]{0} add(p0.1, p0.1)
  b.1 = f32[200]{0} multiply(p1.1, p1.1)
  c.1 = f32[100]{0} add(a.1, a.1)
  ROOT t.1 = (f32[100]{0}, f32[200]{0}) tuple(c.1, b.1)
}
"""


def test_peak_hbm_liveness_walk_exact():
    p = estimate_peak_hbm(HLO_PEAK)
    # args: 400 + 800; intermediates peak at c.1: a(400)+b(800)+c(400)
    assert p["argument_bytes"] == 1200
    assert p["transient_peak_bytes"] == 1600
    assert p["estimate_bytes"] == 2800
    assert p["peak_instruction"] == "c.1"


def test_peak_hbm_charges_callee_as_transient():
    hlo = """
HloModule jit_step, entry_computation_layout={(f32[100]{0})->f32[100]{0}}

body.3 {
  bp.1 = f32[100]{0} parameter(0)
  big.1 = f32[1000]{0} broadcast(bp.1), dimensions={0}
  red.1 = f32[100]{0} slice(big.1), slice={[0:100]}
  ROOT br.1 = f32[100]{0} add(red.1, red.1)
}

ENTRY main.4_spmd {
  p0.1 = f32[100]{0} parameter(0)
  wl.1 = f32[100]{0} while(p0.1), condition=cond.4, body=body.3
  ROOT o.1 = f32[100]{0} copy(wl.1)
}
"""
    p = estimate_peak_hbm(hlo)
    # while result is a view, but its body's own peak (big 4000 live
    # together with red 400; big frees before br allocates) lands as a
    # transient at the call site
    assert p["argument_bytes"] == 400
    assert p["transient_peak_bytes"] == 4400
    assert p["peak_instruction"] == "wl.1"


def test_recompile_hazard_consts_and_scalar_args():
    jaxpr = types.SimpleNamespace(
        consts=[np.zeros((600, 600), np.float32),   # 1.44 MB: fires
                np.zeros((4,), np.float32)])        # 16 B: quiet
    fs, stats = rule_recompile_hazard(jaxpr, example_args=None)
    assert len(fs) == 1 and fs[0]["severity"] == "warning"
    assert stats["baked_const_bytes"] == 600 * 600 * 4
    import jax.numpy as jnp

    r = sanitize_jaxpr(jaxpr, example_args=(jnp.ones((2,)), 0.5, {"t": 3}))
    scal = [f for f in r["findings"] if "scalar" in f["message"]]
    assert len(scal) == 2  # the float AND the int leaf, not the array
    assert r["summary"]["python_scalar_args"] == 2


def test_budget_checks_and_fail_on():
    r = sanitize_hlo(HLO_TRANSFER, {"compute_dtype": "f32"})
    v = check_sanitizer_budgets(r, {"transfer_count_max": 0})
    assert len(v) == 1 and "host transfers" in v[0]
    assert not check_sanitizer_budgets(r, {"transfer_count_max": 3})
    v = check_sanitizer_budgets(r, {"errors_max": 0})
    assert len(v) == 1 and "error-severity" in v[0]
    assert count_at_or_above(r["findings"], "error") == 3
    assert count_at_or_above(r["findings"], "info") >= 3
    # and through the top-level check_budgets() seam, as the tier-1 gate
    # consumes it (a report with a sanitizer section + a budget with a
    # sanitizer sub-dict)
    from deepspeed_tpu.profiling.collectives import check_budgets

    report = {"collectives": {"all-gather": {"wire_bytes": 0.0,
                                             "by_dtype": {}}},
              "total_wire_bytes": 0.0, "fp32_param_bytes_per_chip": 0.0,
              "sanitizer": r}
    v = check_budgets(report, {"sanitizer": {"transfer_count_max": 0}})
    assert len(v) == 1 and "host transfers" in v[0]
    # reports predating the sanitizer stay checkable
    del report["sanitizer"]
    assert not check_budgets(report, {"sanitizer": {"transfer_count_max": 0}})


def test_merge_reports_combines_views():
    hlo_r = sanitize_hlo(HLO_TRANSFER, {"compute_dtype": "f32"})
    jax_r = sanitize_jaxpr(
        types.SimpleNamespace(consts=[np.zeros((600, 600), np.float32)]))
    m = merge_reports(hlo_r, jax_r)
    assert m["summary"]["transfer_count"] == 3
    assert m["summary"]["baked_const_bytes"] == 600 * 600 * 4
    assert m["summary"]["counts"]["error"] == 3
    assert m["summary"]["counts"]["warning"] == 1
    assert "peak_hbm" in m


# ---------------------------------------------------------------------------
# 2. REAL planted-defect programs (program_lint's self-test pair)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def planted(devices8):
    from program_lint import _planted_program

    return _planted_program(clean=False)


def test_planted_program_lights_up_every_rule(devices8, planted):
    """The acceptance pin: all five defect classes detected on a real
    compiled program (dtype leak, missing donation, host transfer,
    replicated tensor, recompile hazard) — plus the entry-scope gather."""
    san = planted["sanitizer"]
    fired = {f["rule"] for f in san["findings"] if not f.get("allowed")}
    assert {"dtype-leak", "donation", "transfer", "sharding",
            "recompile-hazard"} <= fired
    assert san["summary"]["counts"]["error"] >= 1          # the transfer
    assert san["summary"]["transfer_count"] == 1
    assert san["summary"]["f32_dot_flops_frac"] == pytest.approx(1.0)
    # the undonated 512 KiB weight is attributed with its bytes
    d = next(f for f in san["findings"] if f["rule"] == "donation")
    assert d["bytes"] * 8 == 512 * 512 * 2  # per-chip local shard
    assert san["summary"]["replicated_bytes"] == 512 * 512 * 4
    assert san["summary"]["baked_const_bytes"] == 512 * 512 * 4
    assert san["summary"]["python_scalar_args"] == 1
    assert count_at_or_above(san["findings"], "error") >= 1


def test_clean_program_zero_findings_above_info(devices8):
    from program_lint import _planted_program

    report = _planted_program(clean=True)
    san = report["sanitizer"]
    assert count_at_or_above(san["findings"], "warning") == 0
    assert san["summary"]["transfer_count"] == 0
    assert san["summary"]["undonated_candidate_bytes"] == 0
    assert san["summary"]["f32_dot_flops_frac"] == 0.0


# ---------------------------------------------------------------------------
# 3. the serving decode program, held to the checked-in budget (tier-1)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=[None, {"block_size": 16}],
                ids=["no-kv_pool-key", "block16"])
def decode_report(request, devices8):
    """Same geometry as tools/program_lint.py --program decode defaults
    (tiny-test dims, 4 slots, 64-token windows in blocks of 16) so the
    committed serving-decode-paged/8/bf16 budget's observed values are THIS
    program's: the decode program over the gather view (block-table gathers
    + pool writeback), once from a ``serving`` block that names no pool and
    once from one that spells the same pool out."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig(
        vocab_size=512, max_seq_len=64, n_layers=4, n_heads=4,
        d_model=128, d_ff=256, compute_dtype=jnp.bfloat16))
    serving = {"n_slots": 4, "max_len": 64, "virtual_clock": True}
    if request.param is not None:
        serving["kv_pool"] = request.param
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": 64, "serving": serving})
    assert engine.serving.attn_backend == "view"
    report = engine.decode_program_report()
    yield report
    engine.destroy()


def test_serving_decode_within_sanitizer_budget(decode_report):
    from deepspeed_tpu.profiling.collectives import check_budgets

    v = check_budgets(decode_report, BUDGETS["serving-decode-paged/8/bf16"])
    assert not v, v
    san = decode_report["sanitizer"]
    # nothing above info once the QK f32 einsum is allowlisted
    assert count_at_or_above(san["findings"], "warning") == 0
    # full donation of the pool state: k/v pool + block table + per-slot
    # cursors/rng/knobs all alias outputs, zero host transfers
    assert san["summary"]["n_aliased_params"] == 12
    assert san["summary"]["undonated_candidate_bytes"] == 0
    assert san["summary"]["transfer_count"] == 0


@pytest.fixture(scope="module")
def decode_report_fused(devices8):
    """tools/program_lint.py --program decode-fused geometry: the decode
    program through the flash-decode kernel
    (block-table walk IN-KERNEL, no dense per-slot view; the engine chooses
    it where the kernel can run) held to the checked-in
    serving-decode-fused/8/bf16 budget, enforced tier-1 alongside the view
    path's gate."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig(
        vocab_size=512, max_seq_len=64, n_layers=4, n_heads=4,
        d_model=128, d_ff=256, compute_dtype=jnp.bfloat16,
        attention_interpret=True))
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": 64,
                "serving": {"n_slots": 4, "max_len": 64,
                            "virtual_clock": True,
                            "kv_pool": {"block_size": 16}}})
    assert engine.serving.attn_backend == "kernel"
    report = engine.decode_program_report()
    yield report
    engine.destroy()


def test_serving_decode_fused_within_sanitizer_budget(decode_report_fused):
    from deepspeed_tpu.profiling.collectives import check_budgets

    v = check_budgets(decode_report_fused,
                      BUDGETS["serving-decode-fused/8/bf16"])
    assert not v, v
    san = decode_report_fused["sanitizer"]
    assert count_at_or_above(san["findings"], "warning") == 0
    # the fused program is held to the SAME donation/transfer fence as the
    # gather path (pool k/v + block table + cursors/rng/knobs all aliased)
    assert san["summary"]["n_aliased_params"] == 12
    assert san["summary"]["undonated_candidate_bytes"] == 0
    assert san["summary"]["transfer_count"] == 0
    # the table/cursors ride into the kernel as scalar-prefetch operands,
    # never as Python scalars: compiles once per (model, pool) config
    assert san["summary"].get("python_scalar_args", 0) == 0


def test_fused_peak_hbm_ceiling_below_gather_budget(decode_report_fused):
    """The whole point of the kernel is DELETING the dense-view transient:
    the fused budget's peak-HBM ceiling sits strictly below the gather
    budget's, and the fused program's liveness estimate fits it. (The
    view's absence itself — 0 view-shaped gathers in the lowered program —
    is pinned in test_paged_attention.py.)"""
    fused_cap = BUDGETS["serving-decode-fused/8/bf16"]["sanitizer"][
        "peak_hbm_gb_max"]
    gather_cap = BUDGETS["serving-decode-paged/8/bf16"]["sanitizer"][
        "peak_hbm_gb_max"]
    assert fused_cap < gather_cap
    est = decode_report_fused["sanitizer"]["peak_hbm"]["estimate_bytes"]
    assert est / 1e9 <= fused_cap


@pytest.fixture(scope="module")
def prefill_chunked_report(devices8):
    """tools/program_lint.py --program prefill-chunked geometry: the chunked
    suffix-prefill program (one full chunk's bucket at a traced start
    position against a donated partial cache) held to the checked-in
    serving-prefill-chunked/8/bf16 budget — the fence for the chunked-
    prefill path, enforced tier-1 alongside the decode gates."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig(
        vocab_size=512, max_seq_len=64, n_layers=4, n_heads=4,
        d_model=128, d_ff=256, compute_dtype=jnp.bfloat16))
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": 64,
                "serving": {"n_slots": 4, "max_len": 64,
                            "virtual_clock": True,
                            "chunked_prefill": {"enabled": True,
                                                "chunk_size": 16}}})
    report = engine.prefill_chunk_report()
    yield report
    engine.destroy()


def test_serving_prefill_chunked_within_sanitizer_budget(
        prefill_chunked_report):
    from deepspeed_tpu.profiling.collectives import check_budgets

    v = check_budgets(prefill_chunked_report,
                      BUDGETS["serving-prefill-chunked/8/bf16"])
    assert not v, v
    san = prefill_chunked_report["sanitizer"]
    assert count_at_or_above(san["findings"], "warning") == 0
    # the donation pin chunked prefill depends on: the partial b=1 cache
    # (k + v) aliases the output, so chunk N+1 reuses chunk N's buffers —
    # a chunked prefill never holds two copies of the request's cache
    assert san["summary"]["n_aliased_params"] == 2
    assert san["summary"]["undonated_candidate_bytes"] == 0
    assert san["summary"]["transfer_count"] == 0
    # start_pos / true_len are TRACED: one compiled program per chunk
    # bucket no matter where in the prompt the chunk starts
    assert san["summary"].get("python_scalar_args", 0) == 0
    assert san["summary"].get("baked_const_bytes", 0) == 0


@pytest.fixture(scope="module")
def verify_report(devices8):
    """tools/program_lint.py --program verify geometry: the speculative
    one-forward verify program (k+1 positions per slot against the paged
    pool, drafts/draft_len traced) held to the checked-in
    serving-verify/8/bf16 budget — the fence for the speculative-decoding
    subsystem, enforced tier-1 alongside the decode/prefill gates."""
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    model = CausalLM(TransformerConfig(
        vocab_size=512, max_seq_len=64, n_layers=4, n_heads=4,
        d_model=128, d_ff=256, compute_dtype=jnp.bfloat16))
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": 64,
                "serving": {"n_slots": 4, "max_len": 64,
                            "virtual_clock": True,
                            "kv_pool": {"block_size": 16},
                            "speculative": {"enabled": True, "k": 4}}})
    report = engine.verify_program_report()
    yield report
    engine.destroy()


def test_serving_verify_within_sanitizer_budget(verify_report):
    from deepspeed_tpu.profiling.collectives import check_budgets

    v = check_budgets(verify_report, BUDGETS["serving-verify/8/bf16"])
    assert not v, v
    san = verify_report["sanitizer"]
    assert count_at_or_above(san["findings"], "warning") == 0
    # the donation pin speculation depends on: the verify step holds ONE
    # copy of the paged pool state (same 12-leaf census as the paged
    # decode program — pool k/v + block table + cursors/rng/knobs), with
    # zero host transfers and the drafts/draft_len TRACED (one compiled
    # program per k, no recompile per draft mix)
    assert san["summary"]["n_aliased_params"] == 12
    assert san["summary"]["undonated_candidate_bytes"] == 0
    assert san["summary"]["transfer_count"] == 0
    assert san["summary"].get("python_scalar_args", 0) == 0
    assert san["summary"].get("baked_const_bytes", 0) == 0


def test_serving_decode_slot_state_fully_donated(decode_report):
    """The donation discipline the slot pool depends on: every state leaf
    (KV pool, block table, cursors, rng, sampling knobs — 12 arrays)
    aliases an output,
    so decode-in-a-loop holds ONE copy of the pool, not two. The only
    un-aliased outputs are the 2 that ran out of same-shape input buffers
    (nxt/done_now duplicates); weights are read-only by design."""
    san = decode_report["sanitizer"]
    assert san["summary"]["n_aliased_params"] == 12
    assert san["summary"]["undonated_candidate_bytes"] == 0
    assert not [f for f in san["findings"]
                if f["rule"] == "donation" and not f.get("allowed")]


def test_serving_decode_no_transfers_or_hazards(decode_report):
    san = decode_report["sanitizer"]
    assert san["summary"]["transfer_count"] == 0
    assert san["summary"].get("baked_const_bytes", 0) == 0
    assert san["summary"].get("python_scalar_args", 0) == 0
    p = san["peak_hbm"]
    assert 0 < p["estimate_bytes"] < \
        BUDGETS["serving-decode-paged/8/bf16"]["sanitizer"][
            "peak_hbm_gb_max"] * 1e9
