"""trinity (window and full attention layers mixed, q/k norms, an output
gate, four norms a layer, drop-free experts beside a shared one) against
``benchmark/reference/window_moe_decoder.py``: the uncached forward, the
dense-cache path, chunked prefill then decode through the two pool groups
(kernel and view), the published 32-layer pattern, the comparison that
decides the benchmark cell's ``correct`` (each broken-once variant must fail
it), the serving engine end to end, admission by group and the refusals.
Tiny sizes, CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark import latent_serve_loop, window_serve_loop
from benchmark.reference import window_moe_decoder as ref
from deepspeed_tpu.models import decoding as D
from deepspeed_tpu.models import get_model, split_params_axes, window_moe
from deepspeed_tpu.serving import Request, RequestState

ARCH_KEYS = ("n_layers", "first_k_dense", "d_model", "n_heads", "n_kv_heads",
             "head_dim", "d_ff", "moe_d_ff", "n_experts", "moe_top_k",
             "n_shared_experts", "sliding_window", "vocab_size", "rope_base",
             "layernorm_eps", "moe_routed_scale", "embed_scale")
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)
# float32 served against the float32 reference: rounding alone
LIMITS = {"tie_bf16_steps": 2, "route_margin_limit": 1e-3,
          "route_differ_share_limit": 0.005, "route_weight_rms_limit": 1e-4,
          "reference_requests": 2, "band_request_min_tokens": 81}


def arch_of(cfg):
    arch = {k: getattr(cfg, k) for k in ARCH_KEYS}
    arch["layer_kinds"] = list(cfg.layer_types)
    return arch


def build(dtype=jnp.float32, seed=0, **overrides):
    model = get_model("trinity", "tiny", compute_dtype=dtype, **overrides)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(seed)))
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    # the program's selection bias is zero, as the published init is: drawn
    # here, as the benchmark does, so that a path that ignores it is seen
    latent_serve_loop.seed_selection_bias(params, seed, 0.02)
    return model, params, arch_of(model.config)


@pytest.fixture(scope="module")
def tiny():
    return build()


def token_ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_full_forward_matches_the_reference(tiny):
    model, params, arch = tiny
    ids = token_ids(200)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(ids[None])))
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    np.testing.assert_allclose(got[0], want, atol=3e-6)
    assert model.config.num_params() == pytest.approx(
        sum(a.size for a in jax.tree_util.tree_leaves(params)), rel=1e-3)
    loss = jax.jit(model.loss)(params, {"input_ids": jnp.asarray(
        token_ids(64).reshape(2, 32))})
    assert np.isfinite(float(loss)) and 5.0 < float(loss) < 8.0


def test_the_published_32_layer_pattern_builds_with_its_kinds():
    """2 dense layers, then 30 expert layers whose kinds are the published
    ``layer_types[2:]``: they do not start on a period's edge, so two run
    unrolled and seven whole periods are scanned, a period's four layers
    written out (a layer's kind and pool group are static)."""
    model, params, arch = build(n_layers=32)
    cfg = model.config
    assert cfg.layer_types == PERIOD * 8 and cfg.first_k_dense == 2
    assert cfg.embed_scale == 8.0
    assert window_moe.layer_plan(cfg) == ([0, 1, 2, 3], PERIOD, 7)
    window, full = window_moe.layer_groups(cfg)
    assert full == list(range(3, 32, 4)) and len(window) == 24
    assert params["dense_blocks"]["mlp"]["gate"]["kernel"].shape[0] == 2
    assert params["blocks"]["mlp"]["gate_up"].shape[:2] == (30, 8)
    ids = token_ids(70, seed=2)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(ids[None])))
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    np.testing.assert_allclose(got[0], want, atol=2e-5)


def chunked_prefill(model, params, ids, max_len, chunk):
    """The chunk program's math: [logits of every position], the cache and
    what the expert layers chose."""
    @jax.jit
    def one(params, ids, cache, start):
        return D.forward_with_cache(model, params, ids, cache, start, max_len,
                                    return_routing=True)

    cache = D.init_cache(model.config, 1, max_len, params["wte"][
        "weight"].dtype)
    logits, routed = [], []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(ids), chunk):
            lg, cache, r = one(params, jnp.asarray(ids[None, s:s + chunk]),
                               cache, s)
            logits.append(np.asarray(lg[0], np.float32))
            routed.append(np.asarray(r[:, 0]))
    return np.concatenate(logits), cache, np.concatenate(routed, axis=1)


def test_chunked_prefill_then_decode_through_the_dense_cache(tiny):
    """A prompt of nearly four windows in chunks that straddle the band's
    edge, then ``generate()``'s decode over the dense cache."""
    model, params, arch = tiny
    P, steps, max_len = 90, 8, 128
    ids = token_ids(P + steps, seed=3)
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    got, cache, _ = chunked_prefill(model, params, ids[:P], max_len, 32)
    np.testing.assert_allclose(got, want[:P], atol=3e-6)
    decode = jax.jit(lambda p, t, c, pos: D.forward_with_cache(
        model, p, t, c, pos, max_len))
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            lg, cache = decode(params, jnp.asarray(ids[None, P + t:P + t + 1]),
                               cache, P + t)
            np.testing.assert_allclose(np.asarray(lg[0, 0]), want[P + t],
                                       atol=3e-6)
    with pytest.raises(ValueError, match="per-row cursors"):
        D.forward_with_cache(model, params, jnp.zeros((2, 1), jnp.int32),
                             D.init_cache(model.config, 2, 32, jnp.float32),
                             jnp.asarray([3, 4]), 32)


def test_attention_in_blocks_equals_one_block(tiny, monkeypatch):
    """The context visited in blocks of 16 positions (a context that is no
    multiple of the block, a band that starts inside one) gives what one
    block gives."""
    model, params, arch = tiny
    ids = token_ids(75, seed=4)
    whole, _, _ = chunked_prefill(model, params, ids, 75, 75)
    monkeypatch.setattr(window_moe, "KV_BLOCK", 16)
    blocks, _, _ = chunked_prefill(model, params, ids, 75, 32)
    np.testing.assert_allclose(blocks, whole, atol=3e-6)
    np.testing.assert_allclose(
        whole, np.asarray(ref.logits_at(params, ids, arch, 0, 75)),
        atol=3e-6)


def pool_of(cfg, cache, table_row, ring_row, prefill_len, bs, n_slots,
            n_blocks, dtype):
    """Two pool groups with slot 1 holding the prefilled request: what the
    engine's insert does, by hand."""
    window, full = window_moe.layer_groups(cfg)
    ring = len(ring_row)
    width = cfg.kv_heads * cfg.head_dim
    per = cache["k"].shape[2] // bs
    pool, tables = {}, []
    for names, layers, n in ((("k", "v"), full, n_blocks),
                             (("wk", "wv"), window, n_slots * ring + 1)):
        for name, leaf in zip(names, ("k", "v")):
            a = np.zeros((len(layers), n, bs, width), np.float32)
            src = np.asarray(cache[leaf], np.float32).reshape(
                cfg.n_layers, per, bs, width)[layers]
            if name in ("k", "v"):
                for j, b in enumerate(table_row):
                    if b:
                        a[:, b] = src[:, j]
            else:
                last = (prefill_len - 1) // bs
                for j in range(max(last - ring + 1, 0), last + 1):
                    a[:, ring_row[j % ring]] = src[:, j]
            pool[name] = jnp.asarray(a, dtype)
    table = np.zeros((n_slots, per), np.int32)
    table[1] = table_row
    wtable = np.zeros((n_slots, ring), np.int32)
    wtable[1] = ring_row
    return pool, (jnp.asarray(table), jnp.asarray(wtable))


@pytest.mark.parametrize("kernel", [False, True], ids=["view", "kernel"])
@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-6),
                                        (jnp.bfloat16, 8e-2)])
def test_chunked_prefill_then_decode_through_the_two_pool_groups(
        kernel, dtype, atol):
    """Prefill 90 tokens in chunks of 32 (window 24, blocks of 8, a ring of
    4 blocks), insert the full layers' blocks and the window layers' band,
    then decode across ring laps: every step's logits against the
    reference's full forward over the same tokens."""
    model, params, arch = build(dtype)
    cfg = dataclasses.replace(model.config, attention_interpret=kernel)
    model = type(model)(cfg)
    P, steps, bs, max_len = 90, 20, 8, 128
    ids = token_ids(P + steps, seed=3)
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    _, cache, _ = chunked_prefill(model, params, ids[:P], max_len, 32)
    table_row = np.zeros((max_len // bs,), np.int32)
    table_row[:-(-(P + steps) // bs)] = 5 + np.arange(-(-(P + steps) // bs))
    pool, tables = pool_of(cfg, cache, table_row, [3, 1, 4, 2], P, bs, 3, 40,
                           dtype)

    @jax.jit
    def decode(params, tok, pool, pos):
        return D.forward_with_paged_cache(model, params, tok, pool, tables,
                                          pos, bs, kernel=kernel,
                                          return_routing=True)

    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            tok = jnp.asarray([[0], [ids[P + t]], [0]], jnp.int32)
            lg, pool, routed = decode(params, tok, pool,
                                      jnp.asarray([0, P + t, 0], jnp.int32))
            np.testing.assert_allclose(
                np.asarray(lg[1, 0], np.float32), want[P + t], atol=atol)
    assert routed.shape == (4, 3, 1, 4)
    with pytest.raises(ValueError, match="speculative verify"):
        D.forward_with_paged_cache(model, params, jnp.zeros((3, 2), jnp.int32),
                                   pool, tables, jnp.zeros((3,), jnp.int32),
                                   bs)


@pytest.mark.parametrize("variant", ["sound", "band", "rope", "gate",
                                     "embed_scale", "routed_scale", "float8"])
def test_the_cells_comparison_catches_each_broken_variant(tiny, variant):
    """The comparison that decides the cell's ``correct`` passes the sound
    path and fails, by at least one limit, a path broken once: the band left
    out of the window layers, rotation applied in the full layers, the
    output gate, the embedding's scale or the routed factor left out, the
    reference rounded to float8_e4m3fn."""
    model, params, arch = tiny
    ids = token_ids(100, seed=5)
    logits, _, routed = chunked_prefill(model, params, ids, 128, 32)
    first = 60
    tokens = logits[first:].argmax(-1)
    from deepspeed_tpu.moe.dropfree import routed_ids, routed_weights

    broken = dict(arch, **{
        "sound": {}, "band": {"break": "band"}, "rope": {"break": "rope"},
        "gate": {"break": "gate"}, "embed_scale": {"embed_scale": 1.0},
        "routed_scale": {"moe_routed_scale": 1.0},
        "float8": {"round_to": "float8_e4m3fn"}}[variant])
    total = window_serve_loop.compare_with_reference(
        params, broken, ids, first, tokens, routed_ids(routed),
        routed_weights(routed), LIMITS)
    verdicts, _ = latent_serve_loop.passes(total, LIMITS)
    assert all(verdicts.values()) == (variant == "sound"), (verdicts, total)


SERVING = {"n_slots": 4, "max_len": 256, "max_prefills_per_step": 1,
           "chunked_prefill": {"enabled": True, "chunk_size": 32,
                               "decode_steps_between_chunks": 1},
           "kv_pool": {"block_size": 8, "n_blocks": 65,
                       "prefix_cache": False}}


def engine(serving=None, interpret=False, **kw):
    model = get_model("trinity", "tiny", attention_interpret=interpret)
    return deepspeed_tpu.init_inference(
        model, dtype="float32", seed=3, max_tokens=256, prompt_bucket_size=8,
        prompt_bucket_policy="pow2", serving=serving or SERVING, **kw)


@pytest.mark.parametrize("interpret", [False, True], ids=["view", "kernel"])
def test_serving_engine_end_to_end(interpret):
    """submit, stream, finish through the normal path: chunked prefill,
    the pool by group, the ring written over, the routing record and the
    counters; the decode path is the engine's choice from what it observes."""
    eng = engine(interpret=interpret)
    sv = eng.serving
    assert sv.attn_backend == ("kernel" if interpret else "view")
    ring = sv.window_mgr.ring
    assert ring == 4 and sv.window_mgr.n_blocks == 4 * ring + 1
    state = sv._state
    assert state["k"].shape == (1, 65, 8, 32)
    assert state["wk"].shape == (5, 17, 8, 32)
    assert state["table"].shape == (4, 32) and state["wtable"].shape == (4, 4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n, dtype=np.int32)
               for n in (70, 20, 100, 150)]
    reqs = [sv.submit(Request(prompt=p, max_new_tokens=m,
                              record_routing=True))
            for p, m in zip(prompts, (9, 5, 40, 6))]
    streamed, most = {}, 0
    while any(r.state is not RequestState.FINISHED for r in reqs):
        for ev in sv.step():
            streamed.setdefault(ev.request_id, []).append(ev.token)
        # the window group never holds more than a ring a slot
        held = [sv.window_mgr.slot_block_count(s) for s in sv._slots]
        assert all(h <= ring for h in held)
        most = max(most, sum(held))
    assert most > ring
    kv = sv.metrics.snapshot()["kv_pool"]
    groups = kv["groups"]
    assert groups["full"]["layers"] == 1 and groups["window"]["layers"] == 5
    # everything went back; a 20-token request took 3 ring blocks, not 4
    assert groups["window"]["allocated_blocks"] == 0
    assert groups["window"]["free_blocks"] == 4 * ring
    assert groups["window"]["recycled_blocks"] > 0
    assert 0 < groups["window"]["rows_read_per_layer"] \
        < groups["full"]["rows_read_per_layer"]
    assert kv["decode_dispatches"]["kernel" if interpret else "view"] > 0
    assert sv.compile_counts()["decode"] == 1
    assert sv.compile_counts()["insert_block"] == 1
    apply = jax.jit(eng.module.apply)
    for r in reqs:
        assert streamed[r.request_id] == r.tokens
        assert len(r.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(apply(eng.params, jnp.asarray(seq[None])))[
            0, r.prompt_len - 1:].argmax(-1)
        assert (want == np.asarray(r.tokens)).all()
        ids = r.expert_ids()
        assert ids.shape == (4, len(seq), 2)
        assert ids.min() >= 0 and ids.max() < 8
    eng.destroy()


def test_admission_is_by_group():
    """The full layers' group is what ``kv_pool.n_blocks`` sizes and what
    admission reserves in: a request it can never hold is shed, one it
    cannot hold YET waits at the head while the window group, whose size
    follows from slots, window and block, has a ring free for it."""
    serving = dict(SERVING, kv_pool=dict(SERVING["kv_pool"], n_blocks=31))
    eng = engine(serving)
    sv = eng.serving
    assert sv.pool_mgr.allocatable == 30 and sv.window_mgr.allocatable == 16
    never = sv.submit(Request(prompt=token_ids(245), max_new_tokens=4))
    assert never.state is RequestState.REJECTED \
        and never.reject_reason == "no_free_blocks"
    a = sv.submit(Request(prompt=token_ids(150, 1), max_new_tokens=30))
    b = sv.submit(Request(prompt=token_ids(100, 2), max_new_tokens=12))
    waited = 0
    while a.state is not RequestState.FINISHED:
        sv.step()
        if b.state is RequestState.QUEUED and a.state is RequestState.RUNNING:
            waited += 1
            # 23 of 30 full-group blocks are a's, b needs 14; the window
            # group has three rings free
            assert sv.pool_mgr.stats()["allocated_blocks"] == 23
            assert sv.window_mgr.stats()["free_blocks"] == 12
            assert not sv.pool_mgr.can_allocate(14)
            assert sv.window_mgr.can_allocate(4)
    assert waited > 0
    while b.state is not RequestState.FINISHED:
        sv.step()
    assert len(b.tokens) == 12
    eng.destroy()


def refused(**changes):
    serving = {**SERVING, **{k: v for k, v in changes.items()
                             if k != "kv_pool"}}
    serving["kv_pool"] = {**SERVING["kv_pool"], **changes.get("kv_pool", {})}
    return serving


@pytest.mark.parametrize("what,serving,kw", [
    ("prefix cache", refused(kv_pool={"prefix_cache": True}), {}),
    ("on-demand block growth", refused(kv_pool={"on_demand_growth": True}),
     {}),
    ("freed-block scrub", refused(scrub_freed_slots=True), {}),
    ("int8 pool", refused(kv_pool={"kv_dtype": "int8"}), {}),
    ("speculative verify", refused(speculative={"enabled": True, "k": 2}),
     {}),
    ("live KV migration", refused(migration={
        "enabled": True, "snapshot_interval_tokens": 4}), {}),
    ("tensor parallel", SERVING,
     {"tensor_parallel": {"enabled": True, "tp_size": 2}}),
])
def test_what_the_engine_cannot_do_refuses_by_name(what, serving, kw):
    eng = engine(serving, **kw)
    with pytest.raises(ValueError, match="window and full attention layers "
                                         "does not implement.*" + what):
        eng.serving
    eng.destroy()


def test_handoff_snapshot_and_bad_layer_types_refuse_by_name():
    eng = engine()
    sv = eng.serving
    with pytest.raises(ValueError, match="disaggregated hand-off"):
        sv.set_pool_role("prefill")
    req = sv.submit(Request(prompt=token_ids(40), max_new_tokens=4))
    while req.state is not RequestState.RUNNING:
        sv.step()
    with pytest.raises(ValueError, match="live KV migration"):
        sv.capture_snapshot(req)
    eng.destroy()
    with pytest.raises(ValueError, match="layer_types must name"):
        get_model("trinity", "tiny", layer_types=PERIOD)
    with pytest.raises(ValueError, match="layers of both kinds"):
        get_model("trinity", "tiny",
                  layer_types=("sliding_attention",) * 6)
    with pytest.raises(NotImplementedError, match="plain causal forward"):
        model = get_model("trinity", "tiny")
        model.apply(None, jnp.zeros((1, 4), jnp.int32),
                    attention_mask=jnp.ones((1, 4)))
