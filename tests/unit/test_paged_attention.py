"""Paged flash-decode kernel subsystem tests (tier-1, interpret mode on CPU).

The acceptance invariants of the fused attention backend (ROADMAP item 1):

- the split-KV kernel (``ops/pallas/paged_attention.py``) matches a dense
  gather-and-softmax reference through the block table: ragged per-slot
  cursors (mid-block included), GQA grouping, alibi bias, split-count
  sweeps, and garbage-block rows EXCLUDED (the pool's reserved block is
  poisoned with huge values — any unmasked read explodes the output);
- the int8 variant dequantizes in-kernel to the same values the gather
  path's dequantized view holds, within the pinned 2e-4 logits tolerance;
- ``forward_with_paged_cache(attention_backend="fused")`` tracks the
  gather path's logits at fp tolerance across rope/alibi/GQA/parallel-attn
  model variants, and the fused program MATERIALIZES NO dense per-slot
  view (no view-shaped gather in the lowered program — the transient the
  kernel exists to delete);
- greedy serving streams are BITWISE equal fused-vs-gather-vs-sequential
  ``generate()`` under staggered arrivals (single-device and TP=2), decode
  compiles exactly once, and unsupported shapes warn-and-fall-back to the
  gather path instead of failing.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.ops.pallas.paged_attention import (fused_decode_supported,
                                                      paged_flash_decode)
from deepspeed_tpu.serving import Request, RequestState, SamplingParams

from . import conftest
from .conftest import staggered_requests


# attention_interpret: the fused kernel runs under the Pallas interpreter
# here — the explicit switch every kernel test sets
tiny_cfg = functools.partial(conftest.tiny_cfg, attention_interpret=True)


@pytest.fixture(scope="module")
def engine():
    return conftest.tiny_engine(attention_interpret=True)


def make_serving(engine, backend, kv_pool=None, **kw):
    return conftest.make_paged(
        engine, {"attention_backend": backend, **(kv_pool or {})}, **kw)


# ---------------------------------------------------------------------------
# 1. the kernel itself vs a dense reference (interpret mode)
# ---------------------------------------------------------------------------

def _dense_reference(q, k_new, v_new, kc, vc, table, pos, scale, slopes=None,
                     ks=None, vs=None):
    """Gather a dense view through the table and run exact softmax over the
    valid window [0, pos) + the fresh row — what the kernel must match."""
    S, nh, dh = q.shape
    nb, bs, kvh, _ = kc.shape
    NB = table.shape[1]
    hq = nh // kvh
    kc = np.asarray(kc, np.float32)
    vc = np.asarray(vc, np.float32)
    if ks is not None:
        kc = kc * np.asarray(ks)
        vc = vc * np.asarray(vs)
    vk = kc[np.asarray(table)].reshape(S, NB * bs, kvh, dh)
    vv = vc[np.asarray(table)].reshape(S, NB * bs, kvh, dh)
    out = np.zeros((S, nh, dh), np.float32)
    for s in range(S):
        p_ = int(pos[s])
        for h in range(nh):
            g = h // hq
            keys = np.concatenate(
                [vk[s, :p_, g], np.asarray(k_new)[s, g][None]], 0)
            vals = np.concatenate(
                [vv[s, :p_, g], np.asarray(v_new)[s, g][None]], 0)
            sc = (np.asarray(q)[s, h] @ keys.T) * scale
            if slopes is not None:
                sc = sc + np.asarray(slopes)[h] * (np.arange(p_ + 1) - p_)
            e = np.exp(sc - sc.max())
            out[s, h] = (e / e.sum()) @ vals
    return out


def _kernel_fixture(kvh=2, hq=2, dh=16, int8=False):
    rng = np.random.RandomState(0)
    S, NB, bs, n_blocks = 4, 4, 8, 9
    nh = kvh * hq
    if int8:
        kc = rng.randint(-127, 127, (n_blocks, bs, kvh, dh)).astype(np.int8)
        vc = rng.randint(-127, 127, (n_blocks, bs, kvh, dh)).astype(np.int8)
        ks = np.abs(rng.randn(n_blocks, bs, kvh, 1)).astype(np.float32) * .01
        vs = np.abs(rng.randn(n_blocks, bs, kvh, 1)).astype(np.float32) * .01
    else:
        kc = rng.randn(n_blocks, bs, kvh, dh).astype(np.float32)
        vc = rng.randn(n_blocks, bs, kvh, dh).astype(np.float32)
        ks = vs = None
        # poison the GARBAGE block: the kernel must never read an unbound
        # column or a past-cursor row, or the softmax visibly explodes
        kc[0] = 1e4
        vc[0] = 1e4
    table = np.zeros((S, NB), np.int32)
    table[0, :2] = [3, 5]
    table[1] = [1, 2, 4, 6]
    table[2, :1] = [7]
    table[3, :3] = [8, 3, 1]
    # ragged cursors: mid-block (9, 31), inside the first block (1), and a
    # block-boundary tail (24) — unbound columns stay on the garbage block
    pos = np.asarray([9, 31, 1, 24], np.int32)
    q = rng.randn(S, nh, dh).astype(np.float32)
    k_new = rng.randn(S, kvh, dh).astype(np.float32)
    v_new = rng.randn(S, kvh, dh).astype(np.float32)
    return q, k_new, v_new, kc, vc, ks, vs, table, pos


@pytest.mark.parametrize("kv_splits", [1, 2, 4])
def test_kernel_matches_dense_reference(kv_splits):
    q, k_new, v_new, kc, vc, _, _, table, pos = _kernel_fixture()
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = paged_flash_decode(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table),
        jnp.asarray(pos), kv_splits=kv_splits, interpret=True)
    ref = _dense_reference(q, k_new, v_new, kc, vc, table, pos, scale)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-6)


def test_kernel_gqa_and_alibi():
    q, k_new, v_new, kc, vc, _, _, table, pos = _kernel_fixture(
        kvh=2, hq=3, dh=8)
    scale = 1.0 / np.sqrt(q.shape[-1])
    slopes = (0.5 ** np.arange(1, q.shape[1] + 1)).astype(np.float32)
    out = paged_flash_decode(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table),
        jnp.asarray(pos), alibi_slopes=jnp.asarray(slopes), kv_splits=2,
        interpret=True)
    ref = _dense_reference(q, k_new, v_new, kc, vc, table, pos, scale,
                           slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-6)


def test_kernel_int8_dequant_in_kernel():
    q, k_new, v_new, kc, vc, ks, vs, table, pos = _kernel_fixture(int8=True)
    scale = 1.0 / np.sqrt(q.shape[-1])
    out = paged_flash_decode(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table),
        jnp.asarray(pos), k_scale=jnp.asarray(ks), v_scale=jnp.asarray(vs),
        kv_splits=2, interpret=True)
    ref = _dense_reference(q, k_new, v_new, kc, vc, table, pos, scale,
                           ks=ks, vs=vs)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-6)


def test_kernel_survives_cursor_zero():
    """pos == 0 never happens in serving (the cursor starts at prompt_len
    >= 1) but the kernel must not NaN on an all-empty pool window: the
    fresh row alone defines the softmax."""
    q, k_new, v_new, kc, vc, _, _, table, _ = _kernel_fixture()
    out = paged_flash_decode(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table),
        jnp.zeros((q.shape[0],), jnp.int32), kv_splits=2, interpret=True)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(
        np.asarray(out),
        np.repeat(np.asarray(v_new), q.shape[1] // k_new.shape[1], axis=1),
        atol=2e-6)


# ---------------------------------------------------------------------------
# 2. forward_with_paged_cache: fused vs gather across model variants
# ---------------------------------------------------------------------------

def _forward_parity(cfg_kw, kv_dtype=None, tol=1e-5, steps=5):
    cfg = tiny_cfg(**cfg_kw)
    model = CausalLM(cfg)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    from deepspeed_tpu.models.decoding import (forward_with_cache,
                                               forward_with_paged_cache,
                                               init_cache, init_paged_cache,
                                               insert_block_kv)

    rng = np.random.RandomState(2)
    plen, bs, max_len = 10, 16, 64
    ids = rng.randint(0, 64, (2, plen)).astype(np.int32)
    cache = init_cache(cfg, 2, max_len, jnp.float32)
    logits, cache = forward_with_cache(model, params, jnp.asarray(ids),
                                       cache, 0, max_len)

    def mkpool():
        pool = init_paged_cache(cfg, 9, bs, jnp.float32, kv_dtype)
        for s in range(2):
            c1 = {k: v[:, s:s + 1] for k, v in cache.items()}
            pool = insert_block_kv(pool, c1, 1 + s * 4 + jnp.arange(4),
                                   jnp.arange(4), bs)
        return pool

    pg, pf = mkpool(), mkpool()
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    tok = jnp.argmax(logits[:, plen - 1], -1).astype(jnp.int32)
    pos = jnp.asarray([plen, plen], jnp.int32)
    worst = 0.0
    for _ in range(steps):
        lg, pg = forward_with_paged_cache(model, params, tok[:, None], pg,
                                          table, pos, bs)
        lf, pf = forward_with_paged_cache(model, params, tok[:, None], pf,
                                          table, pos, bs,
                                          attention_backend="fused")
        worst = max(worst, float(jnp.max(jnp.abs(lg - lf))))
        # greedy decisions identical -> bitwise streams downstream
        assert bool((jnp.argmax(lg[:, 0], -1)
                     == jnp.argmax(lf[:, 0], -1)).all())
        tok = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
        pos = pos + 1
    assert worst < tol, (cfg_kw, kv_dtype, worst)


def test_forward_parity_plain():
    _forward_parity({})


def test_forward_parity_rope_gqa():
    _forward_parity({"position_embedding": "rope", "n_kv_heads": 2})


def test_forward_parity_alibi():
    _forward_parity({"position_embedding": "alibi"})


def test_forward_parity_parallel_attn():
    _forward_parity({"parallel_attn_mlp": True})


def test_forward_parity_int8_within_pinned_tolerance():
    # the existing paged-int8 logits pin (2e-4, observed ~1e-7 here: the
    # in-kernel dequant reads bit-identical values to the gathered view)
    _forward_parity({}, kv_dtype="int8", tol=2e-4)


def test_fused_is_decode_only():
    cfg = tiny_cfg()
    model = CausalLM(cfg)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    from deepspeed_tpu.models.decoding import (forward_with_paged_cache,
                                               init_paged_cache)

    pool = init_paged_cache(cfg, 5, 16, jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    with pytest.raises(ValueError, match="decode-only"):
        forward_with_paged_cache(
            model, params, jnp.zeros((1, 3), jnp.int32), pool, table,
            jnp.asarray([4], jnp.int32), 16,
            draft_len=jnp.asarray([2], jnp.int32),
            attention_backend="fused")


# ---------------------------------------------------------------------------
# 3. serving: bitwise streams, compile census, no dense view, fallback
# ---------------------------------------------------------------------------

def test_serving_streams_bitwise_fused_vs_gather_vs_generate(engine):
    """THE acceptance pin: greedy streams through the fused backend are
    bitwise-equal to the gather path AND sequential generate() under
    staggered arrivals/mixed lengths, the decode program compiles exactly
    once, and the snapshot records which backend produced the streams."""
    mk = lambda: staggered_requests(np.random.RandomState(0), 6)
    fused_reqs, gather_reqs = mk(), mk()

    sf = make_serving(engine, "fused")
    assert sf.attn_backend == "fused"
    list(sf.serve(fused_reqs))
    sg = make_serving(engine, "gather")
    list(sg.serve(gather_reqs))

    assert all(r.state is RequestState.FINISHED for r in fused_reqs)
    for fr, gr in zip(fused_reqs, gather_reqs):
        assert fr.tokens == gr.tokens          # fused == gather, bitwise
        ref = np.asarray(engine.generate(
            fr.prompt[None, :], max_new_tokens=fr.max_new_tokens,
            greedy=True))
        np.testing.assert_array_equal(np.asarray(fr.tokens),
                                      ref[0, fr.prompt_len:])

    counts = sf.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["insert"] == 1, counts
    snap = sf.metrics.snapshot()
    assert snap["kv_pool"]["attention_backend"] == "fused"
    assert sg.metrics.snapshot()["kv_pool"]["attention_backend"] == "gather"


def test_serving_seeded_sampling_unchanged_by_backend(engine):
    """Sampled streams are byte-identical across backends: the backend
    moves attention reads around, never the rng chain (the rng splits once
    per dispatched step either way)."""
    def mk():
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, 64, (6,)).astype(np.int32)
        return [Request(prompt=prompt, max_new_tokens=8,
                        sampling=SamplingParams(temperature=1.0, top_k=8,
                                                seed=7))]

    fused, gather = mk(), mk()
    list(make_serving(engine, "fused").serve(fused))
    list(make_serving(engine, "gather").serve(gather))
    assert fused[0].tokens == gather[0].tokens


def test_serving_int8_fused_matches_gather(engine):
    rng = np.random.RandomState(3)
    mk = lambda: staggered_requests(np.random.RandomState(3), 4)
    fused, gather = mk(), mk()
    list(make_serving(engine, "fused",
                      kv_pool={"kv_dtype": "int8"}).serve(fused))
    list(make_serving(engine, "gather",
                      kv_pool={"kv_dtype": "int8"}).serve(gather))
    assert all(r.state is RequestState.FINISHED for r in fused)
    for f, g in zip(fused, gather):
        assert f.tokens == g.tokens


def test_serving_fused_with_growth_and_garbage_columns(engine):
    """On-demand growth leaves unbound table columns on the garbage block
    mid-stream — exactly the rows the kernel's cursor mask must exclude.
    Streams stay bitwise-equal to generate() through grows."""
    mk = lambda: [Request(
        prompt=np.random.RandomState(50 + i).randint(
            0, 64, (6,)).astype(np.int32), max_new_tokens=20)
        for i in range(2)]
    fused = mk()
    sv = make_serving(engine, "fused", n_slots=2,
                      kv_pool={"on_demand_growth": True})
    list(sv.serve(fused))
    assert sv.pool_mgr.grown_blocks > 0
    for r in fused:
        ref = np.asarray(engine.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])


def test_fused_program_materializes_no_dense_view(engine):
    """The transient this kernel deletes: the gather path's lowered decode
    program contains the [S, NB, bs, kvh, dh] view-shaped gathers (k and
    v, one per layer scan); the fused program contains NONE — the block
    table walks inside the kernel's index map instead."""
    def view_gathers(sv):
        text = sv.trace_decode()[0].as_text()
        # S=2 slots, NB=4 table columns, bs=16, kvh=4, dh=4 on the tiny cfg
        return sum(1 for line in text.splitlines()
                   if "gather" in line and "2x4x16x4x4" in line)

    assert view_gathers(make_serving(engine, "gather")) > 0
    assert view_gathers(make_serving(engine, "fused")) == 0


def test_unsupported_shape_falls_back_to_gather(engine):
    """Banded local-attention layers aren't implemented in-kernel: a
    requested fused backend warns ONCE and serves through the gather path
    — never a hard failure — with streams still bitwise-greedy-equal to
    generate()."""
    cfg = tiny_cfg(local_attention_window=8, n_layers=2)
    ok, reason = fused_decode_supported(cfg, 16)
    assert not ok and "local_attention_window" in reason

    model = CausalLM(cfg)
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", max_tokens=64, prompt_bucket_size=16)
    sv = make_serving(eng, "fused")
    assert sv.attn_backend == "gather"         # fell back
    assert sv.metrics.snapshot()["kv_pool"]["attention_backend"] == "gather"
    reqs = staggered_requests(np.random.RandomState(6), 3)
    list(sv.serve(reqs))
    for r in reqs:
        ref = np.asarray(eng.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


def test_probe_asks_the_compiler():
    """The probe's TPU answer is the compiler's: with attention_interpret
    off the kernel is lowered for the TPU at the engine's geometry and a
    refusal carries the compiler's words. Off a TPU target with interpret
    off there is no way to run a kernel at all."""
    from deepspeed_tpu.ops.pallas import lowering_target

    cpu = tiny_cfg(attention_interpret=False)
    ok, reason = fused_decode_supported(cpu, 16)
    assert not ok and "interpret mode was not requested" in reason
    assert fused_decode_supported(tiny_cfg(), 16)[0]      # interpret: any shape
    assert fused_decode_supported(tiny_cfg(), 16, kv_dtype="int8")[0]
    with lowering_target("tpu"):
        # the production geometries lower for the TPU: many kv heads x 128
        # (BLOOM class), x 64 (OPT class), GQA, MQA, int8 pools. The pool
        # block is fetched whole; the per-head (1, bs, 1, dh) blocking this
        # kernel shipped with was refused by Mosaic for every kvh > 1 while
        # a hand-written rule list approved it (tests/unit/
        # test_tpu_lowering.py keeps that refusal as compiler_verdict's case)
        for kw in (dict(d_model=512), dict(d_model=256),
                   dict(d_model=512, n_kv_heads=2),
                   dict(d_model=512, n_kv_heads=1),
                   dict(d_model=512, position_embedding="alibi")):
            cfg = tiny_cfg(attention_interpret=False,
                           compute_dtype=jnp.bfloat16, **kw)
            for kv_dtype in ("", "int8"):
                assert fused_decode_supported(
                    cfg, 16, kv_dtype=kv_dtype) == (True, ""), (kw, kv_dtype)


# ---------------------------------------------------------------------------
# 4. TP=2 mesh
# ---------------------------------------------------------------------------

def test_fused_tp_mesh_parity(devices8):
    """TP=2: the fused decode program (the kernel inside a shard_map over
    the mesh, kv heads split over ``model`` — the layout a Mosaic call
    needs, run here under the interpreter) still compiles once and
    produces greedy streams bitwise-equal to the gather path and the
    single-device generate() reference."""
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.parallel import build_mesh

    cfg = tiny_cfg(position_embedding="rope")
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(4)))

    def run(backend):
        mesh = build_mesh(MeshConfig(model=2, data=4), devices=devices8)
        eng = InferenceEngine(model, DeepSpeedInferenceConfig.from_dict(
            {"dtype": "float32", "max_tokens": 64,
             "tensor_parallel": {"tp_size": 2},
             "serving": {"n_slots": 2, "virtual_clock": True,
                         "kv_pool": {"enabled": True, "block_size": 16,
                                     "attention_backend": backend}}}),
            mesh=mesh)
        eng.params = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), values, eng.param_shardings)
        reqs = staggered_requests(np.random.RandomState(9), 3,
                                  max_new=(3, 6))
        list(eng.serve(reqs))
        assert eng.serving.attn_backend == backend
        assert eng.serving.compile_counts()["decode"] == 1
        toks = [r.tokens for r in reqs]
        prompts = [r.prompt for r in reqs]
        lens = [r.max_new_tokens for r in reqs]
        eng.destroy()
        return toks, prompts, lens

    fused_toks, prompts, lens = run("fused")
    gather_toks, _, _ = run("gather")
    assert fused_toks == gather_toks

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                      max_tokens=64)
    raw.params = values
    for toks, prompt, n in zip(fused_toks, prompts, lens):
        ref = np.asarray(raw.generate(prompt[None, :], max_new_tokens=n,
                                      greedy=True))
        np.testing.assert_array_equal(np.asarray(toks),
                                      ref[0, len(prompt):])
    raw.destroy()
