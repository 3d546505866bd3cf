"""nemotron_h (Mamba-2 mixers, LatentMoE expert layers of which the program
holds a share, attention layers without positions; ``models/hybrid.py``)
against ``benchmark/reference/hybrid_moe_decoder.py``, whose recurrence runs
token by token: the uncached forward, chunked prefill over the dense cache
(prompts that straddle a scan block and a chunk, a padded last chunk) then
decode through the pool and the slots' state (view and kernel), the
recurrence kernel alone, the shares of a latent expert layer adding up, the
serving engine end to end (a freed slot re-admitted starts from a zeroed
state, a preempted request replays its state), and what the engine refuses
by name. Tiny sizes, CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from benchmark import hybrid_serve_loop
from benchmark.reference import hybrid_moe_decoder as ref
from deepspeed_tpu.models import decoding as D
from deepspeed_tpu.models import get_model, hybrid, split_params_axes
from deepspeed_tpu.moe import dropfree
from deepspeed_tpu.ops.pallas import ssm_state_update as kernel
from deepspeed_tpu.serving import Request, RequestState

# the tiny preset holds experts 4-7 of 16 unless a test says otherwise
SHARE = {"moe_local_experts": 4, "moe_expert_offset": 4}
# float32 served against the float32 reference: rounding alone. The served
# path scans in blocks (SSD) and the reference token by token, so they agree
# to about 1e-6 on logits near 1; a state rounded to bf16 after every token
# moves them by 5e-5 and more (test_the_tolerance_catches_a_bf16_state)
ATOL = 5e-6


def arch_of(cfg):
    return {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.kv_heads, "head_dim": cfg.head_dim,
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
            "ssm_conv": cfg.ssm_conv, "layer_kinds": cfg.hybrid_pattern,
            "n_experts": cfg.n_experts,
            "moe_local_experts": cfg.held_experts[1],
            "moe_expert_offset": cfg.held_experts[0],
            "moe_top_k": cfg.moe_top_k, "moe_d_ff": cfg.expert_d_ff,
            "moe_latent_size": cfg.moe_latent_size,
            "moe_shared_d_ff": cfg.moe_shared_d_ff,
            "moe_routed_scale": cfg.moe_routed_scale,
            "layernorm_eps": cfg.layernorm_eps, "vocab_size": cfg.vocab_size,
            "n_layers": cfg.n_layers}


def build(seed=0, **overrides):
    model = get_model("nemotron_h", "tiny", compute_dtype=jnp.float32,
                      **{**SHARE, **overrides})
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(seed)))
    hybrid_serve_loop.seed_selection_bias(params, seed, 0.02)
    return model, params, arch_of(model.config)


@pytest.fixture(scope="module")
def tiny():
    return build()


def token_ids(n, seed=0, vocab=512):
    return np.random.default_rng(seed).integers(0, vocab, n).astype(np.int32)


def test_uncached_forward_matches_the_reference(tiny):
    model, params, arch = tiny
    cfg = model.config
    assert cfg.hybrid_pattern == "MEM*E"
    assert hybrid.layer_groups(cfg) == {"M": [0, 2], "E": [1, 4], "*": [3]}
    # the share: 4 of 16 relu2 experts in a 32-wide latent, the router whole
    mixer = params["layers"][1]["mixer"]
    assert mixer["up"].shape == (4, 32, 32) and "gate_up" not in mixer
    assert mixer["down"].shape == (4, 32, 32)
    assert mixer["router"]["kernel"].shape == (64, 16)
    assert mixer["latent_in"]["kernel"].shape == (64, 32)
    assert mixer["shared"]["up"]["kernel"].shape == (64, 48)
    assert params["layers"][0]["mixer"]["in_proj"]["kernel"].shape \
        == (64, 2 * 64 + 2 * 2 * 16 + 4)
    assert cfg.num_params() == sum(
        a.size for a in jax.tree_util.tree_leaves(params))
    ids = token_ids(70)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(ids[None])))
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got[0], want, atol=ATOL)


def test_the_published_configuration_builds_with_its_pattern():
    cfg = get_model("nemotron_h", "3-super").config
    assert len(cfg.hybrid_pattern) == 88
    counts = {k: cfg.hybrid_pattern.count(k) for k in "ME*"}
    assert counts == {"M": 40, "E": 40, "*": 8}
    assert hybrid.ssm_widths(cfg) == (8192, 10240, 18560)
    assert cfg.kv_heads == 2 and cfg.head_dim == 128
    assert cfg.cache_geometry == {"k": (2, 128), "v": (2, 128)}
    # one slot's state in one layer: S 128 x 64 x 128 float32, the tail 3 x
    # 10,240 bf16
    assert hybrid.state_bytes_per_slot(cfg, jnp.bfloat16) \
        == 40 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    cut = get_model("nemotron_h", "3-super", n_layers=11, vocab_size=32768,
                    moe_local_experts=128).config
    assert cut.hybrid_pattern == "MEMEMEM*EME"
    assert cut.num_params() == 4_648_163_712


def chunked_prefill(model, params, ids, max_len, chunk):
    """The chunk program's math, its last chunk padded to a multiple of 8:
    [logits of every real position], the cache and what the expert layers
    chose."""
    @jax.jit
    def one(params, ids, cache, start, last):
        return D.forward_with_cache(model, params, ids, cache, start, max_len,
                                    return_routing=True)

    @jax.jit
    def padded(params, ids, cache, start, last):
        return D.forward_with_cache(model, params, ids, cache, start, max_len,
                                    last_index=last, return_routing=True)

    cache = D.init_cache(model.config, 1, max_len, jnp.float32)
    logits, routed = [], []
    with jax.default_matmul_precision("highest"):
        for s in range(0, len(ids), chunk):
            part = ids[s:s + chunk]
            if len(part) == chunk:
                lg, cache, r = one(params, jnp.asarray(part[None]), cache, s,
                                   None)
                logits.append(np.asarray(lg[0]))
                routed.append(np.asarray(r[:, 0]))
            else:
                # the part-chunk: padding to a multiple of 8 that must leave
                # the state and the conv tail alone
                n = len(part)
                pad = np.zeros((1, -(-n // 8) * 8), np.int32)
                pad[0, :n] = part
                for j in range(n):
                    lg, c2, r = padded(params, jnp.asarray(pad), cache, s, j)
                    logits.append(np.asarray(lg[0, 0]))
                cache = c2
                routed.append(np.asarray(r[:, 0, :n]))
    logits = np.concatenate([lg.reshape(-1, lg.shape[-1]) for lg in logits])
    return logits, cache, np.concatenate(routed, axis=1)


@pytest.mark.parametrize("interpret", [False, True], ids=["view", "kernel"])
def test_chunked_prefill_then_decode_matches_the_reference(tiny, interpret):
    """A prompt of 45 in chunks of 20 (the scan's blocks of 8 straddled
    inside every chunk, the last part-chunk of 5 padded to 8), then 6
    decode steps through the pool and the slot's state: every position's
    logits are the token-by-token reference's."""
    model, params, arch = tiny
    if interpret:
        model = get_model("nemotron_h", "tiny", compute_dtype=jnp.float32,
                          attention_interpret=True, **SHARE)
    cfg = model.config
    max_len, bs, S, slot = 64, 8, 2, 1
    ids = token_ids(51, 3)
    prompt, steps = ids[:45], ids[45:]
    logits, cache, routed = chunked_prefill(model, params, prompt, max_len,
                                            20)
    assert routed.shape == (2, 45, 8)
    # the pool of the one attention layer, and the slot's state from the
    # dense cache; the other slot is garbage that must not leak
    n_blocks = S * max_len // bs + 1
    pool = {n: jnp.zeros((1, n_blocks, bs, 32), jnp.float32) for n in "kv"}
    for n in "kv":
        rows = cache[n][0, 0].reshape(max_len // bs, bs, 32)
        pool[n] = pool[n].at[0, 1:1 + max_len // bs].set(rows)
    state = hybrid.init_state(cfg, S, jnp.float32)
    state = {n: state[n].at[:, slot].set(cache[n][:, 0]) for n in state}
    state["ssm"] = state["ssm"].at[:, 0].set(7.0)
    pool.update(state)
    table = np.zeros((S, max_len // bs), np.int32)
    table[slot] = np.arange(1, 1 + max_len // bs)

    @jax.jit
    def decode(params, tok, pool, pos):
        return D.forward_with_paged_cache(
            model, params, tok, pool, jnp.asarray(table), pos, bs,
            kernel=interpret, return_routing=True)

    got = [logits]
    with jax.default_matmul_precision("highest"):
        for j, t in enumerate(steps):
            tok = np.zeros((S, 1), np.int32)
            tok[slot] = t
            pos = np.full((S,), 45 + j, np.int32)
            lg, pool, _ = decode(params, jnp.asarray(tok), pool,
                                 jnp.asarray(pos))
            got.append(np.asarray(lg[slot]))
    got = np.concatenate(got)
    want = np.asarray(ref.logits_at(params, ids, arch, 0, len(ids)))
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("broken", ["state_bf16", "norm_whole", "no_d"])
def test_the_tolerance_catches_a_bf16_state_and_each_broken_equation(
        tiny, broken):
    """The reference with its state rounded to bf16 after every token (or
    the gated norm over the whole width, or no D term) is off the served
    float32 path by more than ``ATOL``: the check is tight enough to see a
    lower precision of the state."""
    model, params, arch = tiny
    ids = token_ids(70)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(model.apply)(params, jnp.asarray(ids[None])))
    off = np.asarray(ref.logits_at(params, ids, dict(arch, **{
        "break": broken}), 0, len(ids)))
    assert np.abs(got[0] - off).max() > 5 * ATOL


def test_a_finished_request_keeps_the_state_the_reference_reaches():
    """``Request.record_state``: the slot's state as the request finishes,
    read before the slot is released, with the engine sending decodes ahead
    of its steps, is the reference's S after the prompt and every token but
    the last, token by token, to float32 rounding, and its entries carry
    float32's mantissa; the reference with its state rounded to bf16 is off
    it by far more than that, and none of its entries does (the benchmark's
    state checks rest on these). A request that did not ask keeps none."""
    eng = engine()
    sv = eng.serving
    reqs = [sv.submit(Request(prompt=token_ids(n, n), max_new_tokens=m,
                              record_state=True))
            for n, m in ((20, 9), (45, 5), (70, 12))]
    other = sv.submit(Request(prompt=token_ids(30, 1), max_new_tokens=4))
    while any(r.state is not RequestState.FINISHED for r in reqs + [other]):
        sv.step()
    assert sv.metrics.snapshot()["kv_pool"]["decode_ahead_dispatches"] > 0
    assert other.final_state is None
    arch = arch_of(eng.module.config)
    for r in reqs:
        assert r.final_state["ssm"].shape == (2, 4, 16, 16)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        errors, past = {}, {}
        for broken in (None, "state_bf16"):
            _, states = ref.logits_at(
                eng.params, seq, dict(arch, **({"break": broken} if broken
                                               else {})),
                0, 1, return_states=True)
            errors[broken] = hybrid_serve_loop.state_error(
                r.final_state["ssm"], states)
            entries, n = hybrid_serve_loop.past_bf16(np.stack(states))
            past[broken] = n / entries
        # the blocked scan against the one-token steps: about 2e-7
        assert errors[None] < 2e-6 and errors["state_bf16"] > 1e-3
        entries, n = hybrid_serve_loop.past_bf16(r.final_state["ssm"])
        assert entries > 0.99 * r.final_state["ssm"].size
        assert n / entries > 0.99 and past[None] > 0.99
        assert past["state_bf16"] == 0
    eng.destroy()


def test_the_recurrence_kernel_is_the_xla_form_and_keeps_the_other_layers():
    rng = np.random.default_rng(0)
    L, S, H, P, N, G = 3, 6, 8, 16, 128, 2
    states = jnp.asarray(rng.normal(size=(L, S, H, P, N)), jnp.float32)
    da = jnp.asarray(rng.uniform(0.5, 1, (S, H)), jnp.float32)
    dtx = jnp.asarray(rng.normal(size=(S, H, P)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(S, G, N)), jnp.float32)
            for _ in range(2))
    y, out = kernel.ssm_state_update(states, 1, da, dtx, b, c,
                                     interpret=True)
    want_y, want = hybrid.state_update(states[1], da, dtx, b, c)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    # y sums 128 products in another order: float32 rounding of a sum of
    # about 10
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=2e-5)
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(out[layer]),
                                      np.asarray(states[layer]))
    assert kernel.slot_block(6) == 2 and kernel.slot_block(128) == 4
    assert kernel.update_path(False) == "xla"
    assert kernel.update_path(True) == "kernel"


def test_the_shares_of_a_latent_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold experts 0-3, 4-7, 8-11 and 12-15 of a layer of 16,
    each with the router, the latent projections and the shared expert
    whole: their outputs, the shared expert counted once, sum to the uncut
    layer's, and each is what the reference gives for its share."""
    cfg = get_model("nemotron_h", "tiny", compute_dtype=jnp.float32,
                    initializer_range=0.2).config
    whole = jax.tree_util.tree_map(
        lambda p: p.value, dropfree.dropfree_moe_init(
            jax.random.PRNGKey(1), cfg),
        is_leaf=lambda x: hasattr(x, "axes"))
    whole["router"]["bias"] = jnp.asarray(
        np.random.default_rng(2).normal(0, 0.02, (16,)), jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (2, 24, 64)),
                    jnp.float32)
    arch = dict(arch_of(cfg), moe_local_experts=16, moe_expert_offset=0)
    flat = x.reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        want, own, _, _ = ref.expert_ffn(whole, flat, arch, None)
        uncut, routed = dropfree.dropfree_moe_apply(cfg, whole, x)
        sh = whole["shared"]
        shared = np.asarray(ref.relu2(sh["up"]["kernel"],
                                      sh["down"]["kernel"], flat))
        parts = []
        for lo in range(0, 16, 4):
            share = dataclasses.replace(cfg, moe_local_experts=4,
                                        moe_expert_offset=lo)
            p = dict(whole, up=whole["up"][lo:lo + 4],
                     down=whole["down"][lo:lo + 4])
            y, r = dropfree.dropfree_moe_apply(share, p, x)
            np.testing.assert_array_equal(np.asarray(r), np.asarray(routed))
            parts.append(np.asarray(y).reshape(-1, 64))
            part, *_ = ref.expert_ffn(p, flat, dict(
                arch, moe_local_experts=4, moe_expert_offset=lo), None)
            np.testing.assert_allclose(parts[-1], np.asarray(part),
                                       atol=3e-5)
    assert np.abs(np.asarray(want) - shared).max() > 0.1
    np.testing.assert_allclose(sum(parts) - 3 * shared, np.asarray(want),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(uncut).reshape(-1, 64),
                               np.asarray(want), atol=3e-5)
    np.testing.assert_array_equal(
        np.sort(dropfree.routed_ids(np.asarray(routed)).reshape(-1, 4), -1),
        np.sort(np.asarray(own), -1))


SERVING = {"n_slots": 2, "max_len": 128, "max_prefills_per_step": 1,
           "chunked_prefill": {"enabled": True, "chunk_size": 32,
                               "decode_steps_between_chunks": 1},
           "kv_pool": {"block_size": 8, "n_blocks": 33,
                       "prefix_cache": False}}


def engine(serving=None, interpret=False, **kw):
    model = get_model("nemotron_h", "tiny", attention_interpret=interpret,
                      **SHARE)
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", seed=3, max_tokens=128, prompt_bucket_size=8,
        prompt_bucket_policy="pow2", serving=serving or SERVING, **kw)
    hybrid_serve_loop.seed_selection_bias(eng.params, 3, 0.02)
    return eng


def own_tokens(apply, params, r):
    seq = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
    return np.asarray(apply(params, jnp.asarray(seq[None])))[
        0, r.prompt_len - 1:].argmax(-1)


@pytest.mark.parametrize("interpret", [False, True], ids=["view", "kernel"])
def test_serving_engine_end_to_end(interpret):
    """submit, stream, finish through the normal path: five requests over
    two slots (each slot freed and re-admitted), prompts of one part-chunk,
    of a chunk and a padded part, and of three chunks; the state in the
    engine's leaves, the counters, one decode program; every stream is the
    uncached forward's own greedy tokens."""
    eng = engine(interpret=interpret)
    sv = eng.serving
    assert sv.attn_backend == ("kernel" if interpret else "view")
    state = sv._state
    assert state["k"].shape == (1, 33, 8, 32)
    assert state["ssm"].shape == (2, 2, 4, 16, 16)
    assert state["conv"].shape == (2, 2, 3, 128)
    prompts = [token_ids(n, n) for n in (20, 45, 70, 9, 33)]
    reqs = [sv.submit(Request(prompt=p, max_new_tokens=m,
                              record_routing=True))
            for p, m in zip(prompts, (9, 5, 12, 7, 6))]
    streamed = {}
    while any(r.state is not RequestState.FINISHED for r in reqs):
        for ev in sv.step():
            streamed.setdefault(ev.request_id, []).append(ev.token)
    snap = sv.metrics.snapshot()
    ssm = snap["ssm"]
    assert ssm["state_resets"] == 5 and ssm["slots_with_state"] == 0
    assert ssm["chunk_tokens_scanned"] == sum(len(p) for p in prompts)
    # part-chunks pad to powers of two from 8: 20 -> 32, 13 -> 16, 6 -> 8,
    # 9 -> 16, 1 -> 8
    assert ssm["pad_tokens_masked"] == (32 - 20) + (16 - 13) + (8 - 6) \
        + (16 - 9) + (8 - 1)
    assert ssm["state_bytes_per_slot"] == 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    groups = snap["kv_pool"]["groups"]
    assert groups["full"]["layers"] == 1 and groups["state"]["layers"] == 2
    assert groups["state"]["bytes"] == 2 * ssm["state_bytes_per_slot"]
    assert snap["moe"]["moe_pairs_held"] == snap["moe"]["moe_pairs"]
    assert sv.compile_counts()["decode"] == 1
    assert sv.compile_counts()["insert_block"] == 1
    assert sv.compile_counts()["prefill_buckets"] == 0
    assert snap["kv_pool"]["decode_ahead_dispatches"] > 0
    apply = jax.jit(eng.module.apply)
    for r in reqs:
        assert streamed[r.request_id] == r.tokens
        assert len(r.tokens) == r.max_new_tokens
        assert (own_tokens(apply, eng.params, r) == np.asarray(r.tokens)).all()
        assert r.expert_ids().shape == (2, r.prompt_len + len(r.tokens) - 1,
                                        4)
    eng.destroy()


def test_a_freed_slot_starts_from_a_zeroed_state_and_a_preemption_replays():
    """One slot: a request that runs alone gives the same tokens after
    another request held the slot (its state is not read again); a request
    preempted mid-decode replays its prompt and tokens into a fresh state
    and streams the same tokens as one that never was."""
    one = dict(SERVING, n_slots=1)
    prompt = token_ids(40, 7)
    alone = engine(one)
    want = alone.serving.run([Request(prompt=prompt, max_new_tokens=10)])
    alone.destroy()
    eng = engine(one)
    sv = eng.serving
    first = sv.submit(Request(prompt=token_ids(50, 8), max_new_tokens=6))
    second = sv.submit(Request(prompt=prompt, max_new_tokens=10))
    while len(second.tokens) < 4:
        sv.step()
    assert first.state is RequestState.FINISHED
    sv.block_until_idle()
    sv._decode_ahead = None     # the test edits a running slot
    sv._preempt(second.slot)
    while second.state is not RequestState.FINISHED:
        sv.step()
    assert second.preemptions == 1 and second.replay_tokens > 0
    assert second.tokens == want[0][0].tokens
    eng.destroy()


@pytest.mark.parametrize("what,serving,kw", [
    ("prefix cache", dict(SERVING, kv_pool=dict(
        SERVING["kv_pool"], prefix_cache=True)), {}),
    ("int8 pool", dict(SERVING, kv_pool=dict(
        SERVING["kv_pool"], kv_dtype="int8")), {}),
    ("speculative verify", dict(SERVING, speculative={
        "enabled": True, "k": 2}), {}),
    ("live migration", dict(SERVING, migration={
        "snapshot_interval_tokens": 8}), {}),
    ("on-demand block growth", dict(SERVING, kv_pool=dict(
        SERVING["kv_pool"], on_demand_growth=True)), {}),
    ("tensor parallel", SERVING,
     {"tensor_parallel": {"enabled": True, "tp_size": 2}}),
])
def test_what_the_engine_cannot_do_refuses_by_name(what, serving, kw):
    eng = engine(serving, **kw)
    with pytest.raises(ValueError, match="recurrent state .* does not "
                                         "implement .*" + what):
        eng.serving
    eng.destroy()


def test_the_hand_off_and_a_snapshot_refuse_by_name():
    eng = engine()
    sv = eng.serving
    with pytest.raises(ValueError, match="recurrent state .* does not "
                       "implement the disaggregated hand-off"):
        sv.set_pool_role("decode")
    req = sv.submit(Request(prompt=token_ids(12), max_new_tokens=4))
    sv.step()
    with pytest.raises(ValueError, match="recurrent state .* does not "
                       "implement live KV migration"):
        sv.capture_snapshot(req)
    eng.destroy()
