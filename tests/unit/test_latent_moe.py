"""kanana2 (latent attention, drop-free sigmoid-routed experts beside shared
ones, unlike layers) against ``benchmark/reference/latent_moe_decoder.py``:
the full forward, the cache paths, the two attention forms, chunking, the
expert layer, the comparison that decides the benchmark cell's ``correct``
(each deliberately broken variant must fail it), the serving engine end to
end and its refusals. Tiny sizes, CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark import latent_serve_loop
from benchmark.reference import latent_moe_decoder as ref
from deepspeed_tpu.models import decoding as D
from deepspeed_tpu.models import get_model, latent, split_params_axes
from deepspeed_tpu.moe import dropfree
from deepspeed_tpu.serving import Request, RequestState

ARCH_KEYS = ("n_layers", "first_k_dense", "d_model", "n_heads", "d_ff",
             "moe_d_ff", "n_experts", "moe_top_k", "n_shared_experts",
             "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
             "v_head_dim", "vocab_size", "rope_base", "layernorm_eps",
             "moe_routed_scale")
# the float32 served path parts from the float32 reference by rounding
# alone: a differing expert choice has a margin near 1e-6 there, so the
# limits can be far tighter than the bf16 cell's (its configuration file
# reckons those from bf16's step)
LIMITS = {"tie_bf16_steps": 2, "route_margin_limit": 1e-3,
          "route_differ_share_limit": 0.005, "route_weight_rms_limit": 1e-4}


def build(dtype=jnp.float32, seed=0, **overrides):
    model = get_model("kanana2", "tiny", compute_dtype=dtype, **overrides)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(seed)))
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    # the program's selection bias is zero, as the published init is: the
    # tests draw one, as the benchmark does, so that a path that ignores it
    # is seen
    latent_serve_loop.seed_selection_bias(params, seed, 0.02)
    arch = {k: getattr(model.config, k) for k in ARCH_KEYS}
    return model, params, arch


@pytest.fixture(scope="module")
def tiny():
    return build()


def token_ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def served_prefill(model, params, ids):
    """The serving prefill program's math over a whole sequence: logits of
    every position and what its expert layers chose (ids and weights)."""
    def f(params, ids):
        cache = D.init_cache(model.config, 1, len(ids), params["wte"][
            "weight"].dtype)
        logits, _, routed = D.forward_with_cache(
            model, params, ids[None], cache, 0, len(ids), prefill=True,
            return_routing=True)
        return logits[0], routed[:, 0]

    with jax.default_matmul_precision("highest"):
        logits, routed = jax.jit(f)(params, jnp.asarray(ids))
    return np.asarray(logits, np.float32), np.asarray(routed)


def test_full_forward_matches_the_reference(tiny):
    model, params, arch = tiny
    ids = token_ids(200)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(ids[None])))
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    np.testing.assert_allclose(got[0], want, atol=2e-6)
    assert model.config.num_params() == pytest.approx(
        sum(a.size for a in jax.tree_util.tree_leaves(params)), rel=1e-3)


def test_loss_runs_and_is_finite(tiny):
    model, params, _ = tiny
    loss = jax.jit(model.loss)(params, {"input_ids": jnp.asarray(
        token_ids(64).reshape(2, 32))})
    assert np.isfinite(float(loss)) and 5.0 < float(loss) < 8.0


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 5e-6),
                                        (jnp.bfloat16, 6e-2)])
def test_prefill_then_decode_through_the_latent_pool(dtype, atol):
    """Prefill into the dense cache, insert its blocks into the paged pool
    of latent rows, then ten decode steps in the absorbed form: every step's
    logits against the reference's full forward over the same tokens."""
    model, params, arch = build(dtype)
    cfg = model.config
    P, steps, bs, n_blocks, max_len = 37, 10, 16, 9, 64
    ids = token_ids(P + steps, seed=3)
    pool = D.init_paged_cache(cfg, n_blocks, bs, dtype)
    assert pool["k"].shape == (3, n_blocks, bs, 1, 32)
    assert pool["v"].shape == (3, n_blocks, bs, 1, 8)
    table = jnp.asarray([[3, 5, 1, 7]], jnp.int32)     # max_len / bs blocks

    @jax.jit
    def prefill(params, ids):
        cache = D.init_cache(cfg, 1, max_len, dtype)
        return D.forward_with_cache(model, params, ids, cache, 0, max_len,
                                    prefill=True)

    @jax.jit
    def decode(params, tok, pool, pos):
        return D.forward_with_paged_cache(model, params, tok, pool, table,
                                          pos, bs)

    with jax.default_matmul_precision("highest"):
        logits, cache = prefill(params, jnp.asarray(ids[None, :P]))
        pool = D.insert_block_kv(pool, cache, table[0],
                                 jnp.arange(4, dtype=jnp.int32), bs)
        got = [np.asarray(logits[0, -1], np.float32)]
        for i in range(steps):
            step_logits, pool = decode(
                params, jnp.asarray(ids[None, P + i:P + i + 1]), pool,
                jnp.asarray([P + i], jnp.int32))
            got.append(np.asarray(step_logits[0, 0], np.float32))
    want = np.asarray(ref.logits_at(params, ids, arch, P - 1, steps + 1))
    np.testing.assert_allclose(np.stack(got), want, atol=atol)
    if dtype == jnp.float32:
        assert (np.stack(got).argmax(-1) == want.argmax(-1)).all()


def test_absorbed_equals_expanded_attention(tiny):
    """The same mathematics in two forms: one query row a sequence against
    its latent rows, absorbed (decode) and expanded (prefill)."""
    model, params, _ = tiny
    cfg = latent.dense_cfg(model.config)
    p = jax.tree_util.tree_map(lambda a: a[0], params["dense_blocks"]["attn"])
    rng = np.random.default_rng(1)
    S, kv = 3, 40
    h = jnp.asarray(rng.normal(size=(S, 1, cfg.d_model)), jnp.float32)
    pos = jnp.asarray([39, 17, 0], jnp.int32)
    c_ctx = jnp.asarray(rng.normal(size=(S, kv, cfg.kv_lora_rank)),
                        jnp.float32)
    kr_ctx = jnp.asarray(rng.normal(size=(S, kv, cfg.qk_rope_head_dim)),
                         jnp.float32)
    q_nope, q_rope, _, _ = latent.project(
        cfg, p, h, latent.rope_tables(cfg, pos[:, None]))
    absorbed = latent.absorbed_attention(cfg, p, q_nope[:, 0], q_rope[:, 0],
                                         c_ctx, kr_ctx, pos)
    for s in range(S):
        expanded = latent.expanded_attention(
            cfg, p, q_nope[s:s + 1], q_rope[s:s + 1], c_ctx[s:s + 1],
            kr_ctx[s:s + 1], pos[s])
        np.testing.assert_allclose(absorbed[s], expanded[0, 0], atol=2e-5)


def test_expanded_attention_in_blocks_equals_one_block(tiny, monkeypatch):
    model, params, _ = tiny
    ids = token_ids(100, seed=5)
    whole, _ = served_prefill(model, params, ids)
    monkeypatch.setattr(latent, "KV_BLOCK", 32)     # 4 blocks, one padded
    blocked, _ = served_prefill(model, params, ids)
    np.testing.assert_allclose(blocked, whole, atol=2e-6)


def test_chunked_prefill_equals_whole_prefill(tiny, monkeypatch):
    """Three chunks written at their cursors against the cache the earlier
    ones left (each expands the prefix it attends to from the latent rows)
    give the cache and the last row's logits of one whole prefill."""
    monkeypatch.setattr(latent, "KV_BLOCK", 32)
    model, params, _ = tiny
    cfg = model.config
    ids = token_ids(80, seed=7)
    max_len = 96

    def chunk(params, ids, cache, start, last):
        return D.forward_with_cache(model, params, ids, cache, start, max_len,
                                    last_index=last, return_routing=True)

    whole = jax.jit(lambda p, i: D.forward_with_cache(
        model, p, i, D.init_cache(cfg, 1, max_len, jnp.float32), 0, max_len,
        prefill=True, return_routing=True))
    with jax.default_matmul_precision("highest"):
        w_logits, w_cache, w_ids = whole(params, jnp.asarray(ids[None]))
        cache = D.init_cache(cfg, 1, max_len, jnp.float32)
        routed = []
        for start, n in ((0, 32), (32, 32), (64, 16)):
            logits, cache, r = jax.jit(chunk)(
                params, jnp.asarray(ids[None, start:start + n]), cache,
                np.int32(start), np.int32(n - 1))
            routed.append(np.asarray(r))
    np.testing.assert_allclose(logits[0, 0], w_logits[0, -1], atol=2e-6)
    for name in ("k", "v"):
        np.testing.assert_allclose(cache[name][:, :, :80],
                                   w_cache[name][:, :, :80], atol=2e-6)
    assert (dropfree.routed_ids(np.concatenate(routed, axis=2))
            == dropfree.routed_ids(np.asarray(w_ids))).all()


def every_expert_ffn(cfg, p, x, ids):
    """The expert layer as the reference computes it: every expert for
    every token, weighed by 0 where it was not chosen."""
    f = cfg.expert_d_ff
    scores = jax.nn.sigmoid(x @ p["router"]["kernel"])
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = cfg.moe_routed_scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    y = jnp.zeros_like(x)
    for e in range(cfg.n_experts):
        gu = x @ p["gate_up"][e]
        out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ p["down"][e]
        y = y + out * jnp.where(ids == e, w, 0.0).sum(-1, keepdims=True)
    sh = p["shared"]
    return y + (jax.nn.silu(x @ sh["gate"]["kernel"])
                * (x @ sh["up"]["kernel"])) @ sh["down"]["kernel"]


@pytest.mark.parametrize("routing", ["own", "empty_groups", "one_expert"])
def test_dropfree_layer_against_every_expert(tiny, routing):
    model, params, _ = tiny
    cfg = model.config
    p = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["mlp"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 24, cfg.d_model)),
                    jnp.float32)
    forced = None
    if routing == "empty_groups":      # experts 0, 2, 4..7 get no token
        forced = jnp.tile(jnp.asarray([[1, 3]], jnp.int32), (24, 1))
    elif routing == "one_expert":      # every token's first choice is 5
        forced = jnp.stack([jnp.full((24,), 5, jnp.int32),
                            jnp.arange(24, dtype=jnp.int32) % 5], axis=-1)
    with jax.default_matmul_precision("highest"):
        y, routed = jax.jit(lambda p, x: dropfree.dropfree_moe_apply(
            cfg, p, x, ids=forced))(p, x)
        ids = dropfree.routed_ids(routed)
        want = every_expert_ffn(cfg, p, x[0], ids[0])
    if forced is not None:
        assert (np.asarray(ids[0]) == np.asarray(forced)).all()
    else:       # b picks: top-2 of s + b
        sel = jax.nn.sigmoid(x[0] @ p["router"]["kernel"]) \
            + p["router"]["bias"]
        assert (np.sort(np.asarray(ids[0])) == np.sort(np.asarray(
            jax.lax.top_k(sel, 2)[1]))).all()
    np.testing.assert_allclose(y[0], want, atol=2e-6)


def broken(name, monkeypatch, model, params):
    """The served path with one piece of the routing's mathematics left out
    or done wrong; returns what it is served with."""
    blocks = params["blocks"]
    mlp = blocks["mlp"]
    if name == "bf16_router":
        bf = jnp.bfloat16

        def scores_bf16(p_router, x):
            return jax.nn.sigmoid(
                x.astype(bf) @ p_router["kernel"].astype(bf)).astype(
                    jnp.float32)

        def choose_bf16(cfg, p_router, scores):
            sel = scores.astype(bf) + p_router["bias"].astype(bf)
            return jax.lax.top_k(sel, cfg.moe_top_k)[1].astype(jnp.int32)

        monkeypatch.setattr(dropfree, "scores_of", scores_bf16)
        monkeypatch.setattr(dropfree, "choose", choose_bf16)
    elif name == "no_bias":
        mlp = dict(mlp, router=dict(mlp["router"], bias=jnp.zeros_like(
            mlp["router"]["bias"])))
    elif name == "weights_from_s_plus_b":
        seen = {}
        choose, weigh = dropfree.choose, dropfree.pair_weights

        def choose_and_keep(cfg, p_router, scores):
            seen["select"] = scores + p_router["bias"]
            return choose(cfg, p_router, scores)

        monkeypatch.setattr(dropfree, "choose", choose_and_keep)
        monkeypatch.setattr(dropfree, "pair_weights",
                            lambda cfg, scores, ids: weigh(
                                cfg, seen["select"], ids))
    elif name == "no_routed_scale":
        model = type(model)(dataclasses.replace(model.config,
                                                moe_routed_scale=1.0))
    elif name == "no_shared_expert":
        mlp = {k: v for k, v in mlp.items() if k != "shared"}
    return model, dict(params, blocks=dict(blocks, mlp=mlp))


@pytest.mark.parametrize("variant", [
    "sound", "bf16_router", "no_bias", "weights_from_s_plus_b",
    "no_routed_scale", "no_shared_expert"])
def test_the_cells_comparison_catches_each_broken_variant(
        tiny, variant, monkeypatch):
    """The comparisons that decide the cell's ``correct`` (forced-routing
    logits under the tie rule; the reference's own margin where choices
    differ; the pair weights), over every position of a 256-token prefill:
    the sound path passes all, each broken one fails at least one."""
    model, params, arch = tiny
    ids = token_ids(256, seed=11)
    served_model, served_params = broken(variant, monkeypatch, model, params)
    logits, routed = served_prefill(served_model, served_params, ids)
    found = latent_serve_loop.compare_with_reference(
        params, arch, ids, 0, logits.argmax(-1), dropfree.routed_ids(routed),
        dropfree.routed_weights(routed), LIMITS)
    verdicts, stats = latent_serve_loop.passes(found, LIMITS)
    if variant == "sound":
        assert all(verdicts.values()), (found, stats)
        assert found["max_margin"] < 1e-5
        assert found["max_weight_error"] < 1e-5
    else:
        assert not all(verdicts.values()), (found, stats)


SERVING = {"n_slots": 4, "max_len": 256, "max_prefills_per_step": 1,
           "chunked_prefill": {"enabled": True, "chunk_size": 32,
                               "decode_steps_between_chunks": 1},
           "kv_pool": {"block_size": 16, "n_blocks": 49,
                       "prefix_cache": True, "on_demand_growth": False}}


def engine(serving=None, interpret=False, **kw):
    """``interpret``: the model's kernels run under the Pallas interpreter,
    so that the engine's probe answers ``kernel`` here."""
    kw.setdefault("dtype", "float32")
    eng = deepspeed_tpu.init_inference(
        get_model("kanana2", "tiny", attention_interpret=interpret),
        max_tokens=256, seed=3,
        prompt_bucket_size=16, prompt_bucket_policy="pow2",
        serving=serving or SERVING, **kw)
    latent_serve_loop.seed_selection_bias(eng.params, 3, 0.02)
    return eng


def test_serving_engine_end_to_end():
    """submit, stream, finish through the normal path: chunked prefill,
    paged latent pool, a prefix-cache hit on latent blocks, the routing
    record and the counters."""
    eng = engine()
    sv = eng.serving
    model = eng.module
    rng = np.random.default_rng(0)
    shared = rng.integers(0, 512, 48, dtype=np.int32)
    prompts = [rng.integers(0, 512, n, dtype=np.int32) for n in (70, 20, 100)]
    prompts += [np.concatenate([shared, rng.integers(0, 512, 9, dtype=np.int32)])
                for _ in range(2)]
    reqs = [sv.submit(Request(prompt=p, max_new_tokens=m, record_routing=True))
            for p, m in zip(prompts[:4], (9, 5, 12, 6))]
    streamed = {}
    while any(r.state is not RequestState.FINISHED for r in reqs):
        for ev in sv.step():
            streamed.setdefault(ev.request_id, []).append(ev.token)
    # the fifth shares its first 48 tokens (3 blocks) with the fourth
    reqs.append(sv.submit(Request(prompt=prompts[4], max_new_tokens=6,
                                  record_routing=True)))
    while reqs[-1].state is not RequestState.FINISHED:
        for ev in sv.step():
            streamed.setdefault(ev.request_id, []).append(ev.token)
    snap = sv.metrics.snapshot()
    assert snap["kv_pool"]["prefix_hit_requests"] == 1
    assert reqs[-1].prefix_saved_tokens == 48
    moe = snap["moe"]
    assert moe["prefill_chunks"] >= 8 and moe["moe_pairs"] > 0
    assert 1.0 <= moe["moe_mean_expert_load"] <= moe["moe_max_expert_load"]
    assert moe["decode_pairs"] == moe["decode_dispatches"] * 4 * 2
    assert moe["latent_kv_tokens_read"] > 0
    assert sv.compile_counts()["decode"] == 1
    apply = jax.jit(model.apply)
    for r in reqs:
        assert streamed[r.request_id] == r.tokens
        assert len(r.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(apply(eng.params, jnp.asarray(seq[None])))[
            0, r.prompt_len - 1:].argmax(-1)
        assert (want == np.asarray(r.tokens)).all()
        ids = r.expert_ids()
        assert ids.shape == (2, len(seq), 2)
        fresh = ids[:, r.prefix_saved_tokens:]
        assert fresh.min() >= 0 and fresh.max() < 8
        assert (ids[:, :r.prefix_saved_tokens] == -1).all()
    eng.destroy()


@pytest.mark.parametrize("between", [1, 2])
def test_the_next_chunk_is_dispatched_behind_the_decode_before_it(between):
    """With a request decoding and a prompt still in chunks, a step leaves
    the next step's chunk dispatched (the device runs it while the host
    reads tokens and admits) and books it only where the next step takes
    it; the pacing between chunks holds, and the stream is the model's."""
    eng = engine({**SERVING, "chunked_prefill": {
        "enabled": True, "chunk_size": 32,
        "decode_steps_between_chunks": between}})
    sv, m = eng.serving, eng.serving.metrics
    rng = np.random.default_rng(1)
    first = sv.submit(Request(prompt=rng.integers(0, 512, 20, dtype=np.int32),
                              max_new_tokens=40))
    while not first.tokens:
        sv.step()
    late = sv.submit(Request(prompt=rng.integers(0, 512, 150, dtype=np.int32),
                             max_new_tokens=4))
    ahead, booked_at = [], []
    while late.state is not RequestState.FINISHED:
        before = late.chunks
        sv.step()
        if late.chunks > before:
            booked_at.append(len(ahead))
        job = sv._prefill_jobs[0] if sv._prefill_jobs else None
        ahead.append(job is not None and job.ahead is not None)
        if job is not None:
            assert m.prefill_chunks == first.chunks + late.chunks + ahead[-1]
    assert late.chunks == 5 and booked_at == [between * i for i in range(5)]
    # a chunk is ahead exactly where the next step is due one
    assert ahead[:booked_at[-1]] == [
        (i + 1) % between == 0 for i in range(booked_at[-1])]
    seq = np.concatenate([late.prompt, np.asarray(late.tokens[:-1], np.int32)])
    want = np.asarray(jax.jit(eng.module.apply)(
        eng.params, jnp.asarray(seq[None])))[0, late.prompt_len - 1:].argmax(-1)
    assert (want == np.asarray(late.tokens)).all()
    eng.destroy()


def refused(**changes):
    serving = {**SERVING, **{k: v for k, v in changes.items()
                             if k != "kv_pool"}}
    serving["kv_pool"] = {**SERVING["kv_pool"], **changes.get("kv_pool", {})}
    return serving


@pytest.mark.parametrize("what,serving,kw", [
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
    with pytest.raises(ValueError, match=what):
        eng.serving
    eng.destroy()


@pytest.mark.parametrize("stale", [
    {"enabled": True, "attention_backend": "gather"},
    {"enabled": True, "attention_backend": "fused"},
    # what asked for the dense slot pool and was refused by name: ignored
    # now, the engine has one KV store
    {"enabled": False, "attention_backend": "gather"},
], ids=["gather", "fused", "enabled-false"])
def test_configuration_written_before_pr31_still_loads_for_a_latent_model(
        stale):
    """A ``kv_pool`` block with the two keys the program dropped (as
    ``benchmark/configs/kanana-2-30b-a3b-serve.json`` carries them) loads
    through ``init_inference`` and warns once a key; the latent model's
    decode then takes the kernel, as without them, the snapshot says so and
    counts every decode under it, and ``kernel=True`` at the call is served,
    equal to the view."""
    from .conftest import STALE_KV_KEYS, unknown_key_warnings

    with unknown_key_warnings() as seen:
        eng = engine(refused(kv_pool=stale), interpret=True)
    assert sorted(seen) == STALE_KV_KEYS
    sv = eng.serving
    assert (sv.attn_backend, sv.attn_reason) == ("kernel", "")
    req = sv.submit(Request(prompt=token_ids(40), max_new_tokens=4))
    while req.state is not RequestState.FINISHED:
        sv.step()
    kv = sv.metrics.snapshot()["kv_pool"]
    assert kv["attention_backend"] == "kernel"
    assert kv["decode_dispatches"] == {"kernel": sv.metrics.decode_dispatches,
                                       "view": 0}
    assert sv.metrics.decode_dispatches > 0
    model = eng.module
    pool = D.init_paged_cache(model.config, 3, 16, jnp.float32)
    step = lambda kernel: D.forward_with_paged_cache(
        model, eng.params, jnp.zeros((1, 1), jnp.int32), pool,
        jnp.asarray([[1, 2]], jnp.int32), jnp.asarray([3], jnp.int32), 16,
        kernel=kernel)
    (lk, pk), (lv, pv) = step(True), step(False)
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lv), atol=1e-5)
    for name in pk:
        # a layer's rows follow the attention of the layer below
        np.testing.assert_allclose(np.asarray(pk[name]), np.asarray(pv[name]),
                                   atol=1e-5)
    eng.destroy()


def test_greedy_streams_are_equal_through_the_kernel_and_the_view():
    """The tiny kanana2 in float32: prefill two prompts, insert their blocks,
    then greedy decode steps through the decode kernel's latent form and
    through the view (``kernel=False`` at the call), each fed its own
    choices; beside them a slot parked on the garbage block at cursor 0.
    The streams are equal, the logits and the pools within rounding."""
    model, params, _ = build(jnp.float32, attention_interpret=True)
    cfg = model.config
    bs, n_blocks, max_len, steps = 16, 17, 64, 12
    prompts = [token_ids(37, seed=5), token_ids(16, seed=6)]
    table = jnp.asarray([[3, 5, 1, 7], [2, 9, 11, 4], [0, 0, 0, 0]],
                        jnp.int32)

    @jax.jit
    def prefill(params, ids):
        cache = D.init_cache(cfg, 1, max_len, jnp.float32)
        return D.forward_with_cache(model, params, ids, cache, 0, max_len,
                                    prefill=True)

    def decode(kernel):
        return jax.jit(lambda params, tok, pool, pos: D.forward_with_paged_cache(
            model, params, tok, pool, table, pos, bs, kernel=kernel))

    pool = D.init_paged_cache(cfg, n_blocks, bs, jnp.float32)
    first = []
    with jax.default_matmul_precision("highest"):
        for s, ids in enumerate(prompts):
            logits, cache = prefill(params, jnp.asarray(ids[None]))
            pool = D.insert_block_kv(pool, cache, table[s],
                                     jnp.arange(4, dtype=jnp.int32), bs)
            first.append(int(logits[0, -1].argmax()))
        streams = {}
        for kernel in (True, False):
            run, p = decode(kernel), pool
            tok = jnp.asarray(first + [0], jnp.int32)[:, None]
            pos = jnp.asarray([len(q) for q in prompts] + [0], jnp.int32)
            out, seen = [], []
            for _ in range(steps):
                logits, p = run(params, tok, p, pos)
                tok = jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None]
                out.append(np.asarray(tok[:2, 0]))
                seen.append(np.asarray(logits[:2, 0]))
                pos = pos + jnp.asarray([1, 1, 0], jnp.int32)
            streams[kernel] = (np.stack(out), np.stack(seen), p)
    (tk, lk, pk), (tv, lv, pv) = streams[True], streams[False]
    np.testing.assert_array_equal(tk, tv)
    np.testing.assert_allclose(lk, lv, atol=1e-5)
    for name in pk:
        # the live slots' blocks; the garbage block holds whatever was last
        # written to it
        np.testing.assert_allclose(np.asarray(pk[name])[:, 1:],
                                   np.asarray(pv[name])[:, 1:], atol=1e-5)


def test_a_refusal_by_the_probe_leaves_the_view_with_its_reason(monkeypatch):
    """Where the compiler refuses the latent form, the engine serves
    through the view and says why, in the compiler's words; the streams are
    those of an engine that could only take the view."""
    from deepspeed_tpu.ops import pallas as plx

    monkeypatch.setattr(plx, "unavailable_reason", lambda *a: None)
    monkeypatch.setattr(plx, "compiler_verdict", lambda fn, *args: (
        False, "Mosaic failed to compile the latent form."))
    eng = engine()
    sv = eng.serving
    assert sv.attn_backend == "view"
    assert sv.attn_reason == ("TPU compiler: Mosaic failed to compile the "
                              "latent form.")
    monkeypatch.undo()
    plain = engine()
    assert plain.serving.attn_backend == "view"
    reqs = [[s.submit(Request(prompt=token_ids(n, seed=n), max_new_tokens=5))
             for n in (40, 23)] for s in (sv, plain.serving)]
    for s, rs in zip((sv, plain.serving), reqs):
        while any(r.state is not RequestState.FINISHED for r in rs):
            s.step()
    assert [r.tokens for r in reqs[0]] == [r.tokens for r in reqs[1]]
    kv = sv.metrics.snapshot()["kv_pool"]
    assert kv["attention_reason"] == sv.attn_reason
    assert kv["decode_dispatches"]["kernel"] == 0
    eng.destroy()
    plain.destroy()


def test_a_slot_between_its_chunks_walks_nothing():
    """A request still in chunks holds its slot, but the slot's cursor is 0
    and its table row the garbage block until the request is inserted: the
    kernel reads only what decoding slots hold. A slot freed by a finished
    request is back at 0 too."""
    eng = engine(interpret=True)
    sv = eng.serving
    assert sv.attn_backend == "kernel"
    rng = np.random.default_rng(2)
    first = sv.submit(Request(prompt=rng.integers(0, 512, 20, dtype=np.int32),
                              max_new_tokens=30))
    while not first.tokens:
        sv.step()
    late = sv.submit(Request(prompt=rng.integers(0, 512, 130, dtype=np.int32),
                             max_new_tokens=3))
    between = 0
    while late.state is not RequestState.FINISHED:
        sv.step()
        job = sv._prefill_jobs[0] if sv._prefill_jobs else None
        if job is not None and job.req is late:
            between += 1
            assert int(np.asarray(sv._state["pos"])[job.slot]) == 0
            assert not np.asarray(sv._state["table"])[job.slot].any()
    assert between >= 2
    while first.state is not RequestState.FINISHED:
        sv.step()
    assert not sv._slots and not np.asarray(sv._state["pos"]).any()
    eng.destroy()


def test_dropfree_without_latent_attention_refuses_by_name():
    """The cache paths that carry the routing are the latent model's: a
    drop-free model on the dense cache path is refused when it is built."""
    with pytest.raises(ValueError, match="beside latent attention"):
        get_model("kanana2", "tiny", kv_lora_rank=0)


def test_handoff_and_snapshot_refuse_by_name():
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
