"""mimo_v2 (window layers with a learned sink beside full layers of another
K/V head count, K heads wider than V heads, partial rotation with a base per
kind, a value scale, drop-free experts of which the program holds a share)
against ``benchmark/reference/sink_window_moe_decoder.py``: the uncached
forward, the dense-cache path, chunked prefill then decode through the two
pool groups of different row widths (kernel and view), the comparison that
decides the benchmark cell's ``correct`` (each broken-once variant must fail
it), the shares of an expert layer adding up, the other families' expert
layers unchanged, the kernel's two new operands alone, the serving engine end
to end and its refusals. Tiny sizes, CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark import latent_serve_loop, sink_serve_loop
from benchmark.reference import sink_window_moe_decoder as ref
from deepspeed_tpu.models import decoding as D
from deepspeed_tpu.models import get_model, split_params_axes, window_moe
from deepspeed_tpu.moe import dropfree
from deepspeed_tpu.serving import Request, RequestState

ARCH_KEYS = ("n_layers", "first_k_dense", "d_model", "n_heads", "n_kv_heads",
             "n_kv_heads_window", "head_dim", "v_head_dim", "rotary_dim",
             "d_ff", "moe_d_ff", "n_experts", "moe_local_experts",
             "moe_expert_offset", "moe_top_k", "sliding_window", "vocab_size",
             "rope_base", "rope_base_window", "attn_value_scale",
             "layernorm_eps")
# the tiny preset holds experts 4-7 of 16 unless a test says otherwise
SHARE = {"moe_local_experts": 4, "moe_expert_offset": 4}
# float32 served against the float32 reference: rounding alone
# the kernel runs under the interpreter, slowly: its tests take four layers
# (dense and full, two window layers, a full one), not the preset's seven
FOUR = {"n_layers": 4, "layer_types": (
    "full_attention", "sliding_attention", "sliding_attention",
    "full_attention")}
LIMITS = {"tie_bf16_steps": 2, "route_margin_limit": 1e-3,
          "route_differ_share_limit": 0.005, "route_weight_rms_limit": 1e-4,
          "reference_requests": 2, "band_request_min_tokens": 57}


def arch_of(cfg):
    arch = {k: getattr(cfg, k) for k in ARCH_KEYS}
    arch["layer_kinds"] = list(cfg.layer_types)
    return arch


def build(dtype=jnp.float32, seed=0, **overrides):
    model = get_model("mimo_v2", "tiny", compute_dtype=dtype,
                      **{**SHARE, **overrides})
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(seed)))
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    # zero in the program, as fresh ones are: drawn here, as the benchmark
    # does, so that a path that ignores either is seen
    latent_serve_loop.seed_selection_bias(params, seed, 0.02)
    sink_serve_loop.seed_sinks(params, seed, 1.0, 0.5)
    return model, params, arch_of(model.config)


@pytest.fixture(scope="module")
def tiny():
    return build()


@pytest.fixture(scope="module")
def sharp():
    """Weights five times as large: scores far enough apart that where a
    position's rotation or its band lies shows in the logits (at 0.02 a tiny
    model's attention is nearly uniform)."""
    return build(initializer_range=0.1)


def token_ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_full_forward_matches_the_reference(tiny):
    model, params, arch = tiny
    cfg = model.config
    assert cfg.layer_types == ("full_attention",) \
        + ("sliding_attention",) * 5 + ("full_attention",)
    # the shortest period that tiles the END of the list: three layers run
    # one by one, then one period of four (every kind and group index static)
    assert window_moe.layer_plan(cfg) == (
        [0, 1, 2], ("sliding_attention",) * 3 + ("full_attention",), 1)
    # K and V by kind: 2 K/V heads in the two full layers, 4 in the five
    # window layers, K heads of 24 over V heads of 16, a sink a query head
    assert params["kv_full"]["k"]["kernel"].shape == (2, 64, 2 * 24)
    assert params["kv_full"]["v"]["kernel"].shape == (2, 64, 2 * 16)
    assert params["kv_window"]["k"]["kernel"].shape == (5, 64, 4 * 24)
    assert params["kv_window"]["sink"].shape == (5, 8)
    # the share: 4 of 16 experts a layer, the router whole
    assert params["blocks"]["mlp"]["gate_up"].shape == (6, 4, 64, 64)
    assert params["blocks"]["mlp"]["router"]["kernel"].shape == (6, 64, 16)
    assert cfg.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    ids = token_ids(200)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(ids[None])))
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    np.testing.assert_allclose(got[0], want, atol=3e-6)
    loss = jax.jit(model.loss)(params, {"input_ids": jnp.asarray(
        token_ids(64).reshape(2, 32))})
    assert np.isfinite(float(loss)) and 5.0 < float(loss) < 8.0


def test_the_published_48_layers_build_with_their_kinds():
    model = get_model("mimo_v2", "flash")
    cfg = model.config
    # hybrid_layer_pattern: 0 = full, 1 = window; 9 full layers of 48
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    assert [k == "sliding_attention" for k in cfg.layer_types] \
        == [bool(p) for p in pattern]
    assert cfg.kv_geometry(False) == {"k": (4, 192), "v": (4, 128)}
    assert cfg.kv_geometry(True) == {"k": (8, 192), "v": (8, 128)}
    assert cfg.group_pool_geometry(True) == {"k": (1536,), "v": (1024,)}
    assert cfg.cache_geometry == {"k": (8, 192), "v": (8, 128)}
    assert cfg.rotary_dim == int(192 * 0.334) and cfg.first_k_dense == 1
    assert cfg.held_experts == (0, 256)


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


def test_chunked_prefill_then_decode_through_the_dense_cache(tiny,
                                                             monkeypatch):
    """A prompt of eleven windows (longer than window + ring + chunk) in
    chunks of four windows, the context visited in blocks of 16 positions,
    then ``generate()``'s decode over the dense cache, which holds both
    kinds' layers at the wider kind's head count."""
    model, params, arch = tiny
    P, steps, max_len = 90, 8, 128
    ids = token_ids(P + steps, seed=3)
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    monkeypatch.setattr(window_moe, "KV_BLOCK", 16)
    got, cache, _ = chunked_prefill(model, params, ids[:P], max_len, 32)
    assert cache["k"].shape == (7, 1, 128, 4, 24)
    assert cache["v"].shape == (7, 1, 128, 4, 16)
    np.testing.assert_allclose(got, want[:P], atol=3e-6)
    decode = jax.jit(lambda p, t, c, pos: D.forward_with_cache(
        model, p, t, c, pos, max_len))
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            lg, cache = decode(params, jnp.asarray(ids[None, P + t:P + t + 1]),
                               cache, P + t)
            np.testing.assert_allclose(np.asarray(lg[0, 0]), want[P + t],
                                       atol=3e-6)


def pool_of(cfg, cache, table_row, ring_row, prefill_len, bs, n_slots,
            n_blocks, dtype):
    """Two pool groups, each at its own row widths, with slot 1 holding the
    prefilled request: what the engine's insert does, by hand."""
    groups = window_moe.layer_groups(cfg)
    ring = len(ring_row)
    per = cache["k"].shape[2] // bs
    pool = {}
    for names, window, layers, n in (
            (("k", "v"), False, groups[1], n_blocks),
            (("wk", "wv"), True, groups[0], n_slots * ring + 1)):
        geometry = cfg.kv_geometry(window)
        for name, leaf in zip(names, ("k", "v")):
            heads, width = geometry[leaf]
            a = np.zeros((len(layers), n, bs, heads * width), np.float32)
            src = np.asarray(cache[leaf], np.float32)[layers][
                :, 0, :, :heads].reshape(len(layers), per, bs, heads * width)
            if not window:
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


@pytest.mark.parametrize("kernel,dtype,atol,steps", [
    (False, jnp.float32, 3e-6, 20), (False, jnp.bfloat16, 8e-2, 20),
    (True, jnp.float32, 3e-6, 10)], ids=["view", "view-bf16", "kernel"])
def test_chunked_prefill_then_decode_through_the_two_pool_groups(
        kernel, dtype, atol, steps):
    """Prefill 90 tokens in chunks of 32 (window 8, blocks of 8: a ring of 2
    blocks, so a chunk is twice the ring), insert the full layers' blocks
    and the window layers' band, then decode across ring laps: every step's
    logits against the reference's full forward over the same tokens."""
    model, params, arch = build(dtype, **(FOUR if kernel else {}))
    cfg = dataclasses.replace(model.config, attention_interpret=kernel)
    model = type(model)(cfg)
    P, bs, max_len = 90, 8, 128     # the kernel's steps: under the interpreter
    ids = token_ids(P + steps, seed=3)
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    _, cache, _ = chunked_prefill(model, params, ids[:P], max_len, 32)
    table_row = np.zeros((max_len // bs,), np.int32)
    table_row[:-(-(P + steps) // bs)] = 5 + np.arange(-(-(P + steps) // bs))
    pool, tables = pool_of(cfg, cache, table_row, [3, 1], P, bs, 3, 40, dtype)
    assert pool["k"].shape[-1] == 48 and pool["v"].shape[-1] == 32
    assert pool["wk"].shape[-1] == 96 and pool["wv"].shape[-1] == 64

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
    assert routed.shape == (3 if kernel else 6, 3, 1, 8)


@pytest.mark.parametrize("variant", [
    "sound", "sink", "value_scale", "rope_whole", "bases", "band",
    "window_heads", "share_norm", "float8"])
def test_the_cells_comparison_catches_each_broken_variant(sharp, variant):
    """The comparison that decides the cell's ``correct`` passes the sound
    path and fails, by at least one limit, a path broken once: the sink or
    the value scale left out, rotation over the whole head, the two kinds'
    bases swapped, the band left out, the window layers read with the full
    layers' K/V head count, the weights normalised over the held experts
    only, the reference rounded to float8_e4m3fn."""
    model, params, arch = sharp
    ids = token_ids(100, seed=5)
    logits, _, routed = chunked_prefill(model, params, ids, 128, 32)
    first = 60
    tokens = logits[first:].argmax(-1)
    broken = dict(arch, **{
        "sound": {}, "value_scale": {"attn_value_scale": 1.0},
        "float8": {"round_to": "float8_e4m3fn"}}.get(
            variant, {"break": variant}))
    total = sink_serve_loop.compare_with_reference(
        params, broken, ids, first, tokens, dropfree.routed_ids(routed),
        dropfree.routed_weights(routed), LIMITS)
    verdicts, _ = latent_serve_loop.passes(total, LIMITS)
    assert all(verdicts.values()) == (variant == "sound"), (verdicts, total)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11 and 12-15 of a layer of 16:
    what each computes for the same tokens (the same router, the weights
    normalised over all the chosen) sums to what the uncut reference gives
    for the whole layer; the program's own uncut layer gives that too."""
    cfg = get_model("mimo_v2", "tiny", compute_dtype=jnp.float32,
                    initializer_range=0.2).config
    whole = jax.tree_util.tree_map(
        lambda p: p.value, dropfree.dropfree_moe_init(
            jax.random.PRNGKey(1), cfg),
        is_leaf=lambda x: hasattr(x, "axes"))
    whole["router"]["bias"] = jnp.asarray(
        np.random.default_rng(2).normal(0, 0.02, (16,)), jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (2, 24, 64)),
                    jnp.float32)
    arch = dict(arch_of(cfg), moe_local_experts=0, moe_expert_offset=0)
    with jax.default_matmul_precision("highest"):
        want, own, _, w = ref.expert_ffn(whole, x.reshape(-1, 64), arch, None)
        uncut, routed = dropfree.dropfree_moe_apply(cfg, whole, x)
        parts = []
        for lo in range(0, 16, 4):
            share = dataclasses.replace(cfg, moe_local_experts=4,
                                        moe_expert_offset=lo)
            p = dict(whole, gate_up=whole["gate_up"][lo:lo + 4],
                     down=whole["down"][lo:lo + 4])
            y, r = dropfree.dropfree_moe_apply(share, p, x)
            # every share routes alike: the same ids, the same weights
            np.testing.assert_array_equal(np.asarray(r), np.asarray(routed))
            parts.append(np.asarray(y))
            # and is what the reference gives for that share
            part, *_ = ref.expert_ffn(p, x.reshape(-1, 64), dict(
                arch, moe_local_experts=4, moe_expert_offset=lo), None)
            np.testing.assert_allclose(parts[-1].reshape(-1, 64),
                                       np.asarray(part), atol=3e-6)
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(sum(parts).reshape(-1, 64), np.asarray(want),
                               atol=3e-6)
    np.testing.assert_allclose(np.asarray(uncut).reshape(-1, 64),
                               np.asarray(want), atol=3e-6)
    np.testing.assert_array_equal(
        np.sort(dropfree.routed_ids(np.asarray(routed)).reshape(-1, 4), -1),
        np.sort(np.asarray(own), -1))
    with pytest.raises(ValueError, match="must name a range"):
        get_model("mimo_v2", "tiny", moe_local_experts=8,
                  moe_expert_offset=12)


def _layer_before_the_share(cfg, p, x):
    """``dropfree_moe_apply`` as it was before a layer could hold a share
    (PR 36), kept here to hold the other families' layers to its bits."""
    b, s, d = x.shape
    E, k, f = cfg.n_experts, cfg.moe_top_k, cfg.expert_d_ff
    flat = x.reshape(b * s, d)
    scores = dropfree.scores_of(p["router"], flat)
    ids = dropfree.choose(cfg, p["router"], scores)
    weights = dropfree.pair_weights(cfg, scores, ids)
    pair_expert = ids.reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    group_sizes = jnp.zeros((E,), jnp.int32).at[pair_expert].add(1)
    rows = flat[order // k]
    how = (cfg.attention_interpret, cfg.mesh)
    h = dropfree.grouped_product(rows, p["gate_up"], group_sizes, *how)
    h = jax.nn.silu(h[:, :f]) * h[:, f:]
    out = dropfree.grouped_product(h, p["down"], group_sizes, *how)
    out = out.astype(jnp.float32) * weights.reshape(-1)[order][:, None]
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    y = out[inverse].reshape(b * s, k, d).sum(axis=1)
    sp = jax.tree_util.tree_map(lambda a: a.astype(x.dtype), p["shared"])
    from deepspeed_tpu.models.layers import linear_apply
    y = y + linear_apply(sp["down"], jax.nn.silu(linear_apply(
        sp["gate"], flat)) * linear_apply(sp["up"], flat)).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d)


@pytest.mark.parametrize("family,size,interpret", [
    ("kanana2", "tiny", False), ("trinity", "tiny", False),
    ("trinity", "tiny", True)], ids=["kanana2", "trinity", "trinity-kernel"])
def test_the_other_families_layers_are_bit_equal_with_the_default_share(
        family, size, interpret):
    cfg = get_model(family, size, compute_dtype=jnp.float32,
                    attention_interpret=interpret).config
    assert cfg.held_experts == (0, cfg.n_experts)
    p = jax.tree_util.tree_map(
        lambda q: q.value, dropfree.dropfree_moe_init(
            jax.random.PRNGKey(4), cfg),
        is_leaf=lambda x: hasattr(x, "axes"))
    x = jnp.asarray(np.random.default_rng(5).normal(0, 1, (1, 32, 64)),
                    jnp.float32)
    got, _ = jax.jit(lambda p, x: dropfree.dropfree_moe_apply(cfg, p, x))(
        p, x)
    want = jax.jit(lambda p, x: _layer_before_the_share(cfg, p, x))(p, x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sink,window", [(False, 0), (True, 0), (True, 8)],
                         ids=["asymmetric", "sink", "sink-band-ring"])
def test_the_kernel_takes_v_rows_narrower_than_k_rows_and_a_sink(sink,
                                                                 window):
    """The decode kernel alone (under the interpreter) against the plain
    softmax: K heads of 24 over V heads of 16, a sink a query head that
    joins the sum and adds nothing, cursors from 0 (a slot that attends its
    own row and the sink alone) across ring laps."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    rng = np.random.default_rng(0)
    S, H, G, dk, dv, bs = 5, 8, 4, 24, 16, 8
    cols = 2 if window else 6
    n_blocks = S * cols + 1
    kc = jnp.asarray(rng.normal(0, 1, (2, n_blocks, bs, G * dk)), jnp.float32)
    vc = jnp.asarray(rng.normal(0, 1, (2, n_blocks, bs, G * dv)), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(S * cols).reshape(S, cols),
                        jnp.int32)
    pos = jnp.asarray([0, 3, 8, 21, 47] if not window
                      else [0, 3, 8, 21, 95], jnp.int32)
    q = jnp.asarray(rng.normal(0, 0.5, (S, H, dk)), jnp.float32)
    kn = jnp.asarray(rng.normal(0, 1, (S, G, dk)), jnp.float32)
    vn = jnp.asarray(rng.normal(0, 1, (S, G, dv)), jnp.float32)
    sinks = jnp.asarray(rng.normal(1.0, 0.5, (H,)), jnp.float32) \
        if sink else None
    got = paged_flash_decode(q, kn, vn, kc, vc, table, pos, layer=1,
                             window=window, ring=bool(window), sink=sinks,
                             interpret=True)
    assert got.shape == (S, H, dv)
    view = lambda c: np.asarray(c[1])[np.asarray(table)].reshape(
        S, cols * bs, G, -1)
    if window:
        k_pos = np.asarray(window_moe.ring_positions(pos, cols, bs))
    else:
        k_pos = np.broadcast_to(np.arange(cols * bs), (S, cols * bs))
    p_ = np.asarray(pos)[:, None]
    seen = (k_pos >= 0) & (k_pos < p_)
    if window:
        seen &= p_ - k_pos < window
    qg = np.asarray(q).reshape(S, G, H // G, dk)
    sc = np.einsum("sgrd,stgd->sgrt", qg, view(kc)) / np.sqrt(dk)
    sc = np.where(seen[:, None, None], sc, -np.inf)
    columns = [sc, (np.einsum("sgrd,sgd->sgr", qg, np.asarray(kn))
                    / np.sqrt(dk))[..., None]]
    if sink:
        columns.append(np.broadcast_to(
            np.asarray(sinks).reshape(1, G, H // G, 1), sc.shape[:3] + (1,)))
    sc = np.concatenate(columns, -1)
    prob = np.exp(sc - sc.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    n = cols * bs
    want = np.einsum("sgrt,stgd->sgrd", prob[..., :n], view(vc)) \
        + prob[..., n:n + 1] * np.asarray(vn)[:, :, None]
    np.testing.assert_allclose(np.asarray(got), want.reshape(S, H, dv),
                               atol=2e-5)


SERVING = {"n_slots": 4, "max_len": 256, "max_prefills_per_step": 1,
           "chunked_prefill": {"enabled": True, "chunk_size": 32,
                               "decode_steps_between_chunks": 1},
           "kv_pool": {"block_size": 8, "n_blocks": 65,
                       "prefix_cache": False}}


def engine(serving=None, interpret=False, **kw):
    model = get_model("mimo_v2", "tiny", attention_interpret=interpret,
                      **SHARE, **(FOUR if interpret else {}))
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", seed=3, max_tokens=256, prompt_bucket_size=8,
        prompt_bucket_policy="pow2", serving=serving or SERVING, **kw)
    sink_serve_loop.seed_sinks(eng.params, 3, 1.0, 0.5)
    return eng


@pytest.mark.parametrize("interpret", [False, True], ids=["view", "kernel"])
def test_serving_engine_end_to_end(interpret):
    """submit, stream, finish through the normal path: chunked prefill (a
    chunk of 4 blocks over a ring of 2), the two groups at their own row
    widths, the ring written over, the share's counters; the decode path is
    the engine's choice from what it observes."""
    eng = engine(interpret=interpret)
    sv = eng.serving
    assert sv.attn_backend == ("kernel" if interpret else "view")
    ring = sv.window_mgr.ring
    assert ring == 2 and sv.window_mgr.n_blocks == 4 * ring + 1
    state = sv._state
    n_window = 2 if interpret else 5
    assert state["k"].shape == (2, 65, 8, 48)
    assert state["v"].shape == (2, 65, 8, 32)
    assert state["wk"].shape == (n_window, 9, 8, 96)
    assert state["wv"].shape == (n_window, 9, 8, 64)
    assert state["table"].shape == (4, 32) and state["wtable"].shape == (4, 2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 512, n, dtype=np.int32)
               for n in (70, 20, 100)]
    reqs = [sv.submit(Request(prompt=p, max_new_tokens=m,
                              record_routing=True))
            for p, m in zip(prompts, (9, 5, 20))]
    streamed = {}
    while any(r.state is not RequestState.FINISHED for r in reqs):
        for ev in sv.step():
            streamed.setdefault(ev.request_id, []).append(ev.token)
        assert all(sv.window_mgr.slot_block_count(s) <= ring
                   for s in sv._slots)
    snap = sv.metrics.snapshot()
    groups = snap["kv_pool"]["groups"]
    assert groups["full"]["layers"] == 2
    assert groups["window"]["layers"] == n_window
    assert groups["window"]["allocated_blocks"] == 0
    assert groups["window"]["recycled_blocks"] > 0
    assert 0 < groups["window"]["rows_read_per_layer"] \
        < groups["full"]["rows_read_per_layer"]
    moe = snap["moe"]
    # 4 of 16 experts held: about a quarter of the chosen pairs are computed
    assert moe["moe_pairs_held"] == moe["moe_pairs"]
    assert 0.15 < moe["moe_pairs_held"] / moe["moe_pairs_chosen"] < 0.35
    assert moe["product_dispatches"][
        "kernel" if interpret else "ragged_dot"] > 0
    assert sv.compile_counts()["decode"] == 1
    assert sv.compile_counts()["insert_block"] == 1
    # once the last prompt is in, a run of decode-only steps: each decode
    # dispatched behind the one before it (the one program, and the tokens
    # below are still the uncached forward's)
    kv = snap["kv_pool"]
    assert 0 < kv["decode_ahead_dispatches"] < sum(
        kv["decode_dispatches"].values())
    apply = jax.jit(eng.module.apply)
    for r in reqs:
        assert streamed[r.request_id] == r.tokens
        assert len(r.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(apply(eng.params, jnp.asarray(seq[None])))[
            0, r.prompt_len - 1:].argmax(-1)
        assert (want == np.asarray(r.tokens)).all()
        ids = r.expert_ids()
        assert ids.shape == (3 if interpret else 6, len(seq), 4)
        assert ids.min() >= 0 and ids.max() < 16
    eng.destroy()


def test_a_request_bound_while_a_decode_is_ahead_joins_the_next_decode():
    """A lone request decodes with each step's decode dispatched behind the
    one before it; a short prompt (one prefill, bound at once) and a long one
    (chunks) arrive while such a decode is out: each is bound on the state
    that decode leaves, is skipped by its read-back, and streams the model's
    own tokens from the decode after; the first stream is undisturbed."""
    eng = engine()
    sv = eng.serving
    first = sv.submit(Request(prompt=token_ids(40, 1), max_new_tokens=30))
    while len(first.tokens) < 4:
        sv.step()
    assert sv._decode_ahead is not None
    late = [sv.submit(Request(prompt=token_ids(n, n), max_new_tokens=m))
            for n, m in ((20, 6), (90, 5))]
    while any(r.state is not RequestState.FINISHED for r in [first] + late):
        sv.step()
    assert sv._decode_ahead is None     # the last step freed a slot
    kv = sv.metrics.snapshot()["kv_pool"]
    assert kv["decode_ahead_dispatches"] > 10
    apply = jax.jit(eng.module.apply)
    for r in [first] + late:
        assert len(r.tokens) == r.max_new_tokens
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        want = np.asarray(apply(eng.params, jnp.asarray(seq[None])))[
            0, r.prompt_len - 1:].argmax(-1)
        assert (want == np.asarray(r.tokens)).all()
    eng.destroy()


@pytest.mark.parametrize("what,serving,kw", [
    ("prefix cache", dict(SERVING, kv_pool=dict(
        SERVING["kv_pool"], prefix_cache=True)), {}),
    ("int8 pool", dict(SERVING, kv_pool=dict(
        SERVING["kv_pool"], kv_dtype="int8")), {}),
    ("speculative verify", dict(SERVING, speculative={
        "enabled": True, "k": 2}), {}),
    ("tensor parallel", SERVING,
     {"tensor_parallel": {"enabled": True, "tp_size": 2}}),
])
def test_what_the_engine_cannot_do_refuses_by_name(what, serving, kw):
    eng = engine(serving, **kw)
    with pytest.raises(ValueError, match="window and full attention layers "
                                         "does not implement.*" + what):
        eng.serving
    eng.destroy()
