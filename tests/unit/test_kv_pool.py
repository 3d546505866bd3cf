"""Paged + quantized KV-cache subsystem tests (tier-1).

The acceptance invariants of the block pool (ROADMAP item 1):

- paged greedy decode is BITWISE equal to sequential ``generate()`` (the
  dense ``[B, max_len]`` cache), under staggered arrivals and mixed
  lengths, single device and TP=2; seeded sampling streams are unchanged by
  the pool's geometry;
- for a KV HBM budget of ``n`` worst-case (max_len) windows the pool admits
  strictly more concurrent requests (>= 2x ``n``), because requests reserve
  their actual block footprint instead of a max_len window;
- a freed block re-allocated to a different request cannot leak the old
  occupant's tokens (whole-block insert + garbage-block parking), with and
  without the block-granularity scrub;
- int8 KV blocks (per-(token, head) fp32 scales via the ZeRO++ blockwise
  kernels) stay within a pinned logits tolerance of the dense path;
- identical prompt prefixes map to the SAME physical blocks (copy-on-write,
  refcounted) — the suffix-only prefill is cheaper and still bitwise-exact;
- a request whose footprint can never fit sheds ``no_free_blocks``; one
  that merely has to wait holds the queue head (FCFS) until blocks free.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, split_params_axes
from deepspeed_tpu.serving import (GARBAGE_BLOCK, KVPoolManager, Request,
                                   RequestState, SamplingParams)
from deepspeed_tpu.serving.kv_pool import KVPoolManager as _Mgr  # noqa: F401

from .conftest import (make_paged, make_replica, staggered_requests,
                       tiny_cfg)


# ---------------------------------------------------------------------------
# host-side allocator + prefix cache (no device work)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,block,ring", [(2048, 128, 17), (24, 8, 4),
                                               (100, 16, 8), (16, 16, 2)])
def test_window_group_is_a_ring_a_slot(window, block, ring):
    """The window layers' group (``models/window_moe.py``): its size follows
    from slots, window and block; a request takes its footprint capped at
    the ring, the band behind a prefill is what the insert copies, a cursor
    that enters a block past the first lap writes over one that left the
    band, and a finished slot gives everything back."""
    from deepspeed_tpu.config import KVPoolConfig
    from deepspeed_tpu.serving.kv_pool import (WindowGroupManager,
                                               window_ring_blocks)

    assert window_ring_blocks(window, block) == ring
    mgr = WindowGroupManager(
        KVPoolConfig(block_size=block, n_blocks=7, prefix_cache=True),
        n_slots=3, window=window)
    assert mgr.ring == ring and mgr.blocks_per_slot == ring
    assert mgr.n_blocks == 3 * ring + 1 and not mgr.cfg.prefix_cache
    assert mgr.blocks_for(block, 1) == 1
    assert mgr.blocks_for(50 * window, 100) == ring
    # the band behind any cursor lies inside the blocks the ring keeps
    for prefill in (1, block, window, window + 1, 7 * window + 3):
        held = mgr.ring_columns(prefill)
        assert len(held) <= ring
        assert len({c for _, c in held}) == len(held)
        assert held[-1][0] == (prefill - 1) // block
        assert held[0][0] * block <= max(prefill - window, 0)
        assert all(c == j % ring for j, c in held)
    for slot in range(3):
        mgr.bind_slot(slot, mgr.alloc(ring), 10 * window)
        assert mgr.slot_block_count(slot) == ring
    assert not mgr.can_allocate(1)
    for pos in range(2 * ring * block):
        mgr.book_cursor(pos)
    assert mgr.recycled_blocks == ring           # the second lap's blocks
    mgr.free_slot(1)
    assert mgr.can_allocate(ring) and not mgr.can_allocate(ring + 1)
    st = mgr.stats()
    assert st["ring_blocks"] == ring and st["window"] == window
    assert st["allocated_blocks"] == 2 * ring and st["free_blocks"] == ring


def test_allocator_refcount_and_eviction():
    from deepspeed_tpu.config import KVPoolConfig

    mgr = KVPoolManager(KVPoolConfig(block_size=4, n_blocks=6),
                        n_slots=4, max_len=16)
    assert mgr.allocatable == 5          # block 0 reserved (garbage)
    assert mgr.blocks_for(4, 5) == 2     # positions [0, 8) -> 2 blocks of 4
    assert mgr.blocks_for(1, 1) == 1
    assert not mgr.fits_ever(16, 9)      # 24 tokens = 6 blocks > 5

    a = mgr.alloc(3)
    assert GARBAGE_BLOCK not in a and len(set(a)) == 3
    mgr.bind_slot(0, a, footprint_tokens=10)
    assert not mgr.can_allocate(3) and mgr.can_allocate(2)

    # register a prefix over the first block: the cache takes its own ref,
    # so the block survives the slot's release...
    prompt = np.arange(8, dtype=np.int32)
    mgr.register_prefix(prompt, a)       # blocks 0..1 of the prompt are full
    mgr.free_slot(0)
    assert mgr.stats()["cached_prefix_blocks"] == 2
    shared_len, blocks = mgr.acquire_prefix(
        np.concatenate([prompt, np.int32([9, 9, 9])]))
    assert shared_len == 8 and blocks == a[:2]
    mgr.release_blocks(blocks)

    # ...and is evicted LRU when allocation needs the space
    b = mgr.alloc(5)
    assert len(set(b)) == 5
    assert mgr.stats()["cached_prefix_blocks"] == 0
    mgr.release_blocks(b)
    assert mgr.stats()["free_blocks"] == 5

    # matching is capped at prompt_len - 1: a prompt that IS the cached
    # prefix must still leave one suffix token to prefill
    mgr.register_prefix(prompt, mgr.alloc(2))
    shared_len, blocks = mgr.acquire_prefix(prompt)
    assert shared_len == 4               # not 8: block 2 ends at len(prompt)
    mgr.release_blocks(blocks)


def test_allocator_rejects_bad_geometry():
    from deepspeed_tpu.config import KVPoolConfig
    from deepspeed_tpu.config.base import ConfigError

    with pytest.raises(ConfigError):
        KVPoolManager(KVPoolConfig(block_size=6), 2, 16)
    with pytest.raises(ConfigError):
        KVPoolConfig(kv_dtype="int4")


@pytest.mark.parametrize("name,n_blocks,block_size", [
    ("opt-1.3b-serve", 1537, 16),
    ("kanana-2-30b-a3b-serve", 2561, 128),
])
def test_benchmark_serve_configurations_load_as_they_are(name, n_blocks,
                                                         block_size):
    """Both serve cells' files still carry the two ``kv_pool`` keys the
    program dropped in PR 31 (``enabled``, ``attention_backend``; a
    ``benchmark`` issue removes them): the ``init_inference`` block loads
    as ``init_inference`` loads it, warns once a key and nothing else, and
    the pool it describes is the cell's."""
    import json
    import os

    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    from .conftest import STALE_KV_KEYS, unknown_key_warnings

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(repo, "benchmark", "configs",
                           name + ".json")) as f:
        inf = json.load(f)["init_inference"]
    assert {"enabled", "attention_backend"} <= set(inf["serving"]["kv_pool"])
    with unknown_key_warnings() as seen:
        cfg = DeepSpeedInferenceConfig.from_dict(inf)
    assert sorted(seen) == STALE_KV_KEYS
    sv = cfg.serving
    mgr = KVPoolManager(sv.kv_pool, sv.n_slots, sv.max_len)
    assert (mgr.n_blocks, mgr.block_size) == (n_blocks, block_size)
    assert sv.kv_pool.prefix_cache and not sv.kv_pool.on_demand_growth


def test_no_kv_pool_key_is_slots_times_max_len_of_blocks(engine):
    """A ``serving`` block that names no pool gets the one KV store at the
    capacity of every slot at ``max_len``: blocks of 16, ``n_slots *
    max_len / 16`` of them + the garbage block, prefix cache on."""
    from deepspeed_tpu.config import ServingConfig

    assert ServingConfig(n_slots=3).kv_pool.to_dict() == {
        "block_size": 16, "n_blocks": 0, "kv_dtype": "",
        "prefix_cache": True, "on_demand_growth": False}
    sv = make_replica(engine, n_slots=3)
    kv = sv.metrics.snapshot()["kv_pool"]
    assert kv["capacity_tokens"] == 3 * sv.max_len == 192
    assert (kv["n_blocks"], kv["block_size"]) == (3 * 64 // 16 + 1, 16)
    assert sv._state["k"].shape[1:3] == (13, 16)


def test_max_len_must_divide_by_block_size_and_says_what_to_set(engine):
    """Input validation every engine now meets: the error names both
    numbers and the keys to set."""
    from deepspeed_tpu.config.base import ConfigError

    with pytest.raises(ConfigError) as e:
        make_replica(engine, n_slots=2, max_len=40)
    msg = str(e.value)
    assert "40" in msg and "16" in msg
    assert "serving.kv_pool.block_size" in msg and "serving.max_len" in msg
    make_replica(engine, n_slots=2, max_len=40, kv_pool={"block_size": 8})


# ---------------------------------------------------------------------------
# bitwise parity + capacity (the subsystem acceptance pins)
# ---------------------------------------------------------------------------

def test_paged_greedy_parity_vs_generate_and_dense(engine):
    """Paged continuous batching == sequential generate() over the dense
    ``[B, max_len]`` cache, token for token, under staggered arrivals and
    mixed prompt/output lengths — and the decode program still compiles
    exactly once while requests join and leave mid-flight."""
    paged_reqs = staggered_requests(np.random.RandomState(0), 6)

    sv = make_paged(engine, n_slots=2)
    list(sv.serve(paged_reqs))

    assert all(r.state is RequestState.FINISHED for r in paged_reqs)
    for pr in paged_reqs:
        ref = np.asarray(engine.generate(
            pr.prompt[None, :], max_new_tokens=pr.max_new_tokens,
            greedy=True))
        np.testing.assert_array_equal(np.asarray(pr.tokens),
                                      ref[0, pr.prompt_len:])

    counts = sv.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["insert"] == 1, counts
    assert counts["insert_block"] == 1, counts


def test_paged_seeded_sampling_streams_unchanged(engine):
    """Seeded per-request sampling streams are byte-identical whatever
    the pool's geometry (blocks of 4 in a tight pool against blocks of 16
    in the auto-sized one): paging moves KV memory around, never the rng
    chain or the logits it samples from."""
    def mk():
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, 64, (6,)).astype(np.int32)
        other = rng.randint(0, 64, (9,)).astype(np.int32)
        return [
            Request(prompt=prompt, max_new_tokens=8,
                    sampling=SamplingParams(temperature=1.0, top_k=8, seed=7)),
            Request(prompt=other, max_new_tokens=8,
                    sampling=SamplingParams(temperature=0.7, seed=123)),
        ]

    small, auto = mk(), mk()
    # 9 blocks of 4: the two requests' footprints (4 + 4 blocks) and the
    # garbage block, nothing to spare
    list(make_paged(engine, n_slots=2,
                    kv_pool={"block_size": 4, "n_blocks": 9}).serve(small))
    list(make_paged(engine, n_slots=2).serve(auto))
    for p, d in zip(small, auto):
        assert len(p.tokens) == 8 and p.tokens == d.tokens
    # and the sampled stream actually sampled (not greedy collapse)
    assert len(set(map(tuple, [small[0].tokens, small[1].tokens]))) == 2


def test_paged_admits_2x_slots_for_same_kv_hbm(engine):
    """THE acceptance criterion: a KV HBM budget that holds 2 worst-case
    requests (2 x 64-token windows, what one dense region of those bytes
    could ever serve at once) split into 8 blocks of 16 tokens serves 7
    one-block requests CONCURRENTLY (block 0 is the garbage block) —
    >= 2x the worst-case count — with every stream still bitwise-greedy
    equal to generate()."""
    mk = lambda: [Request(
        prompt=np.random.RandomState(100 + i).randint(
            0, 64, (8,)).astype(np.int32), max_new_tokens=8)
        for i in range(7)]

    from deepspeed_tpu.models.decoding import init_cache

    worst_case = 2
    paged = make_paged(engine, n_slots=8, max_prefills_per_step=8,
                       kv_pool={"block_size": 16, "n_blocks": 8})
    # the budget, by the arithmetic: the pool's k array is byte-for-byte a
    # dense [L, 2, max_len, ...] cache's (8 * 16 == 2 * 64 token rows)
    assert paged.max_len == 64
    dense = init_cache(engine.module.config, worst_case, paged.max_len,
                       engine.dtype)
    assert paged._state["k"].nbytes == dense["k"].nbytes
    assert paged._state["v"].nbytes == dense["v"].nbytes

    paged_reqs = mk()
    list(paged.serve(paged_reqs))
    assert all(r.state is RequestState.FINISHED for r in paged_reqs)

    paged_peak = paged.metrics.active_slots_peak
    assert paged_peak >= 2 * worst_case, (paged_peak, worst_case)
    assert paged_peak == 7  # every allocatable block serving a request

    for r in paged_reqs:
        ref = np.asarray(engine.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    snap = paged.metrics.snapshot()
    assert snap["kv_pool"]["n_blocks"] == 8
    assert 0.0 <= snap["kv_pool"]["fragmentation"] <= 1.0


def test_block_reuse_cannot_leak_stale_kv(engine):
    """A long request fills pool blocks with real KV; the short request
    whose blocks REUSE that freed memory must produce bitwise the same
    tokens as on a never-used pool — whole-block insert overwrites every
    row, and freed slots park on the garbage block. Same again with the
    block-granularity scrub on, which must also actually zero the blocks."""
    rng = np.random.RandomState(1)
    long_prompt = rng.randint(0, 64, (20,)).astype(np.int32)
    short_prompt = rng.randint(0, 64, (5,)).astype(np.int32)
    pool_cfg = {"block_size": 16, "n_blocks": 4, "prefix_cache": False}

    fresh = make_paged(engine, n_slots=1, kv_pool=pool_cfg)
    pristine = Request(prompt=short_prompt, max_new_tokens=6)
    list(fresh.serve([pristine]))

    sv = make_paged(engine, n_slots=1, kv_pool=pool_cfg)
    long_req = Request(prompt=long_prompt, max_new_tokens=20)
    list(sv.serve([long_req]))
    assert long_req.state is RequestState.FINISHED
    assert sv.pool_mgr.stats()["free_blocks"] == 3  # everything came back
    reused = Request(prompt=short_prompt, max_new_tokens=6)
    list(sv.serve([reused]))
    np.testing.assert_array_equal(np.asarray(reused.tokens),
                                  np.asarray(pristine.tokens))

    # with the hygiene scrub: freed physical blocks are ZEROED in the pool
    sv2 = make_paged(engine, n_slots=1, scrub_freed_slots=True,
                     kv_pool=pool_cfg)
    list(sv2.serve([Request(prompt=long_prompt, max_new_tokens=20)]))
    assert sv2.pool_mgr.scrubbed_blocks >= 2
    k = np.asarray(sv2._state["k"])
    assert np.all(k[:, 1:] == 0)  # every allocatable block scrubbed to zero
    scrubbed = Request(prompt=short_prompt, max_new_tokens=6)
    list(sv2.serve([scrubbed]))
    np.testing.assert_array_equal(np.asarray(scrubbed.tokens),
                                  np.asarray(pristine.tokens))


# ---------------------------------------------------------------------------
# int8 KV blocks (pinned tolerance)
# ---------------------------------------------------------------------------

def test_int8_kv_within_pinned_tolerance(engine):
    """int8 pool blocks (per-(token, head) fp32 scales, the ZeRO++
    blockwise kernels) track the dense-path decode logits within a pinned
    tolerance — measured ~2.3e-5 max-abs on this model, pinned at 10x."""
    from deepspeed_tpu.models.decoding import (forward_with_cache,
                                               forward_with_paged_cache,
                                               init_cache, init_paged_cache,
                                               insert_block_kv)

    TOL = 2e-4
    model, params = engine.module, engine.params
    cfg = model.config
    rng = np.random.RandomState(2)
    plen, bs, max_len = 10, 16, 64
    ids = rng.randint(0, 64, (1, plen)).astype(np.int32)
    cache = init_cache(cfg, 1, max_len, engine.dtype)
    logits, cache = forward_with_cache(model, params, jnp.asarray(ids),
                                       cache, 0, max_len)
    pool = init_paged_cache(cfg, 5, bs, engine.dtype, "int8")
    pool = insert_block_kv(pool, cache, 1 + jnp.arange(4), jnp.arange(4), bs)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)

    tok = jnp.argmax(logits[:, plen - 1], -1).astype(jnp.int32)
    pos = jnp.asarray([plen], jnp.int32)
    for _ in range(5):
        ld, cache = forward_with_cache(model, params, tok[:, None], cache,
                                       pos, max_len)
        l8, pool = forward_with_paged_cache(model, params, tok[:, None],
                                            pool, table, pos, bs)
        assert float(jnp.max(jnp.abs(ld[:, 0] - l8[:, 0]))) < TOL
        tok = jnp.argmax(ld[:, 0], -1).astype(jnp.int32)
        pos = pos + 1


def test_int8_serving_end_to_end(engine):
    """The int8 pool serves real traffic: streams complete, and on this
    tiny model the greedy tokens happen to match the fp reference (the
    quantization error is far below the argmax margins)."""
    rng = np.random.RandomState(3)
    reqs = staggered_requests(rng, 4)
    sv = make_paged(engine, n_slots=2, kv_pool={"kv_dtype": "int8"})
    list(sv.serve(reqs))
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert all(len(r.tokens) >= 1 for r in reqs)
    # int8 pool ~quarter the fp32 payload bytes (scales extra)
    assert sv._state["k"].dtype == jnp.int8
    assert "k_scale" in sv._state


# ---------------------------------------------------------------------------
# shared-prefix cache (copy-on-write)
# ---------------------------------------------------------------------------

def test_prefix_cache_shares_blocks_bitwise_and_cheaper(engine):
    """Identical prompt prefixes map to the SAME physical blocks: the
    second request's prefill only pays for the suffix (smaller TTFT under
    the virtual cost model), the shared blocks are refcounted not copied,
    and the streams stay bitwise-greedy-equal to generate()."""
    rng = np.random.RandomState(5)
    sys_prompt = rng.randint(0, 64, (20,)).astype(np.int32)  # > 1 block
    tail_a = rng.randint(0, 64, (4,)).astype(np.int32)
    tail_b = rng.randint(0, 64, (7,)).astype(np.int32)

    sv = make_paged(engine, n_slots=2)
    cold = Request(prompt=np.concatenate([sys_prompt, tail_a]),
                   max_new_tokens=6)
    list(sv.serve([cold]))
    assert sv.pool_mgr.stats()["cached_prefix_blocks"] == 1
    canonical = list(sv.pool_mgr._prefix.values())

    warm = Request(prompt=np.concatenate([sys_prompt, tail_b]),
                   max_new_tokens=6)
    rerun = Request(prompt=np.concatenate([sys_prompt, tail_a]),
                    max_new_tokens=6)
    list(sv.serve([warm]))
    list(sv.serve([rerun]))   # alone, so its ttft is pure prefill cost
    stats = sv.pool_mgr.stats()
    assert stats["prefix_hit_requests"] == 2
    assert stats["prefix_hit_rate"] > 0
    # COW: the canonical physical block survived and was shared, not copied
    assert list(sv.pool_mgr._prefix.values()) == canonical

    for r in (cold, warm, rerun):
        ref = np.asarray(engine.generate(r.prompt[None, :], max_new_tokens=6,
                                         greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    # the identical rerun is cheaper end-to-end: only the suffix prefilled
    assert rerun.ttft < cold.ttft
    # the hit path went through the suffix program, not a full prefill
    assert sv.compile_counts()["suffix_buckets"] >= 1


def test_prefix_hit_with_large_prompt_bucket_stays_exact():
    """Regression: the suffix prefill pads to a PROMPT bucket, and with
    prompt_bucket_size == max_len the padded q-block written at
    pos=shared_len used to overrun the KV window — XLA clamps the update
    start, silently clobbering the prefix rows (caught as non-finite
    logits / token-0 streams on bf16). The suffix bucket ceiling must
    shrink by shared_len."""
    eng = deepspeed_tpu.init_inference(
        CausalLM(tiny_cfg()), dtype="float32", max_tokens=64,
        prompt_bucket_size=64)
    rng = np.random.RandomState(11)
    shared = rng.randint(0, 64, (16,)).astype(np.int32)
    mk = lambda seed: Request(prompt=np.concatenate(
        [shared, np.random.RandomState(seed).randint(
            0, 64, (8,)).astype(np.int32)]), max_new_tokens=6)
    sv = make_paged(eng, n_slots=2)
    cold, warm = mk(1), mk(2)
    list(sv.serve([cold]))
    list(sv.serve([warm]))
    assert sv.pool_mgr.stats()["prefix_hit_requests"] == 1
    assert sv.metrics.nonfinite_logit_steps == 0
    for r in (cold, warm):
        ref = np.asarray(eng.generate(r.prompt[None, :], max_new_tokens=6,
                                      greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()


def test_prefix_cache_off_means_no_sharing(engine):
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 64, (20,)).astype(np.int32)
    sv = make_paged(engine, n_slots=2, kv_pool={"prefix_cache": False})
    list(sv.serve([Request(prompt=prompt, max_new_tokens=4),
                   Request(prompt=prompt, max_new_tokens=4)]))
    stats = sv.pool_mgr.stats()
    assert stats["cached_prefix_blocks"] == 0
    assert stats["prefix_hit_requests"] == 0


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_no_free_blocks_shed_and_fcfs_wait(engine):
    """A request whose block footprint exceeds the whole pool sheds
    ``no_free_blocks`` at submit; one that merely has to WAIT holds the
    queue head until the running request frees its blocks, then completes
    (FCFS, no overtaking, no livelock)."""
    rng = np.random.RandomState(7)
    sv = make_paged(engine, n_slots=2,
                    kv_pool={"block_size": 16, "n_blocks": 3})
    # footprint 40 + 10 - 1 = 49 tokens = 4 blocks > 2 allocatable
    big = sv.submit(Request(
        prompt=rng.randint(0, 64, (40,)).astype(np.int32),
        max_new_tokens=10))
    assert big.state is RequestState.REJECTED
    assert big.reject_reason == "no_free_blocks"
    assert sv.metrics.snapshot()["shed"]["no_free_blocks"] == 1

    # two 2-block requests through a 2-block pool: strictly serialized
    # (the second waits for blocks, not a slot — both slots are free)
    r1 = Request(prompt=rng.randint(0, 64, (16,)).astype(np.int32),
                 max_new_tokens=10)
    r2 = Request(prompt=rng.randint(0, 64, (16,)).astype(np.int32),
                 max_new_tokens=10)
    list(sv.serve([r1, r2]))
    assert r1.state is RequestState.FINISHED
    assert r2.state is RequestState.FINISHED
    assert sv.metrics.active_slots_peak == 1
    for r in (r1, r2):
        ref = np.asarray(engine.generate(r.prompt[None, :],
                                         max_new_tokens=10, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])


# ---------------------------------------------------------------------------
# TP=2 mesh
# ---------------------------------------------------------------------------

def test_paged_tp_mesh_parity(devices8):
    """TP=2 paged pool: the block pool shards its kv-head axis over the
    model mesh axis, the paged decode still compiles once, and greedy
    streams match the single-device reference bitwise."""
    import jax

    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.parallel import build_mesh

    cfg = tiny_cfg(position_embedding="rope")
    model = CausalLM(cfg)
    values, _ = split_params_axes(model.init(jax.random.PRNGKey(4)))
    mesh = build_mesh(MeshConfig(model=2, data=4), devices=devices8)
    eng = InferenceEngine(model, DeepSpeedInferenceConfig.from_dict(
        {"dtype": "float32", "max_tokens": 64,
         "tensor_parallel": {"tp_size": 2},
         "serving": {"n_slots": 2, "virtual_clock": True,
                     "kv_pool": {"block_size": 16}}}),
        mesh=mesh)
    eng.params = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), values, eng.param_shardings)

    rng = np.random.RandomState(9)
    reqs = staggered_requests(rng, 3, max_new=(3, 6))
    list(eng.serve(reqs))
    assert eng.serving._state["k"].sharding.spec[3] == "model"
    assert eng.serving.compile_counts()["decode"] == 1

    raw = deepspeed_tpu.init_inference(CausalLM(cfg), dtype="float32",
                                       max_tokens=64)
    raw.params = values
    for r in reqs:
        ref = np.asarray(raw.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])
    eng.destroy()
