"""The paged pool's ONE block writer (``models/decoding.write_pool_blocks``).

- It leaves the pool bit for bit what the parent's per-block loop left (kept
  here as the reference), on both of its paths: the XLA scatter and the
  column kernel that runs where the device keeps the block axis in the
  lanes (under the Pallas interpreter here).
- Padded entries write nothing, sources may start past a shared prefix, a
  request may own one block or ``blocks_per_slot`` of them, and a block the
  request does not own keeps its bytes.
- The engine's compiled insert program aliases the pool in and out, holds no
  loop, compiles once whatever the request sizes, and counts the blocks it
  really writes.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.comm.collectives import quantize_blockwise
from deepspeed_tpu.models.decoding import insert_block_kv, write_pool_blocks
from deepspeed_tpu.profiling.sanitizer import parse_input_output_alias
from deepspeed_tpu.serving import Request, RequestState

from .conftest import make_paged

L, N_BLOCKS, BS, KVH, DH, MAX_LEN = 2, 300, 8, 2, 16, 64
NB = MAX_LEN // BS                       # blocks_per_slot


def loop_insert_one(pool, dense_cache, block_id, src_start, block_size):
    """The parent's ``insert_block_kv``: ONE block, ``dynamic_update_slice``d
    into the pool (quantized per block when the pool is int8)."""
    out = dict(pool)
    for name in ("k", "v"):
        rows = jax.lax.dynamic_slice_in_dim(
            dense_cache[name], src_start, block_size, axis=2)
        rows = jnp.swapaxes(rows, 1, 2)[:, :, 0]
        if name + "_scale" in pool:
            q, scale = quantize_blockwise(rows, block=rows.shape[-1])
            out[name] = jax.lax.dynamic_update_slice(
                pool[name], q[:, None], (0, block_id, 0, 0, 0))
            out[name + "_scale"] = jax.lax.dynamic_update_slice(
                pool[name + "_scale"], scale[:, None],
                (0, block_id, 0, 0, 0))
        else:
            out[name] = jax.lax.dynamic_update_slice(
                pool[name], rows[:, None].astype(pool[name].dtype),
                (0, block_id, 0, 0, 0))
    return out


def random_pool(rng, int8):
    shape = (L, N_BLOCKS, BS, KVH, DH)
    if int8:
        return {
            "k": jnp.asarray(rng.randint(-127, 128, shape), jnp.int8),
            "v": jnp.asarray(rng.randint(-127, 128, shape), jnp.int8),
            "k_scale": jnp.asarray(rng.rand(*shape[:-1], 1), jnp.float32),
            "v_scale": jnp.asarray(rng.rand(*shape[:-1], 1), jnp.float32)}
    return {n: jnp.asarray(rng.randn(*shape), jnp.bfloat16)
            for n in ("k", "v")}


def padded(targets, sources):
    ids = N_BLOCKS + np.arange(NB, dtype=np.int32)
    srcs = np.zeros((NB,), np.int32)
    ids[:len(targets)] = targets
    srcs[:len(targets)] = sources
    return jnp.asarray(ids), jnp.asarray(srcs)


CASES = {
    # targets (pool blocks), sources (blocks of the dense cache)
    "padded": ([7, 131, 2], [0, 1, 2]),
    "past_a_shared_prefix": ([260, 5, 129], [2, 3, 4]),
    "one_block": ([299], [0]),
    "blocks_per_slot": ([5, 130, 131, 260, 299, 7, 128, 1], list(range(NB))),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("lanes", [False, True], ids=["scatter", "kernel"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_writer_equals_the_per_block_loop(int8, lanes, case):
    rng = np.random.RandomState(sum(map(ord, case)))
    pool = random_pool(rng, int8)
    cache = {n: jnp.asarray(rng.randn(L, 1, MAX_LEN, KVH, DH), jnp.bfloat16)
             for n in ("k", "v")}
    targets, sources = CASES[case]

    one = jax.jit(loop_insert_one, static_argnums=4)   # compiled, as it ran
    want = pool
    for b, s in zip(targets, sources):
        want = one(want, cache, b, s * BS, BS)
    got = jax.jit(lambda p, c, i, s: insert_block_kv(
        p, c, i, s, BS, lanes=lanes, interpret=True))(
            pool, cache, *padded(targets, sources))

    others = np.setdiff1d(np.arange(N_BLOCKS), targets)
    assert set(got) == set(pool)
    for name in pool:
        g, w = np.asarray(got[name]), np.asarray(want[name])
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8))
        np.testing.assert_array_equal(          # not the request's: untouched
            g[:, others].view(np.uint8),
            np.asarray(pool[name])[:, others].view(np.uint8))


@pytest.mark.parametrize("lanes", [False, True], ids=["scatter", "kernel"])
def test_raw_blocks_move_verbatim(lanes):
    """The migration splice: raw int8 payloads AND scales, no requantizing,
    source block i of the snapshot onto the i-th target, some shared blocks
    (entries with no target) skipped."""
    rng = np.random.RandomState(3)
    pool = random_pool(rng, int8=True)
    raw = {n: jnp.asarray(np.asarray(a)[:, rng.permutation(N_BLOCKS)[:NB]])
           for n, a in random_pool(rng, int8=True).items()}
    targets, sources = [140, 9, 270], [2, 3, 4]
    got = jax.jit(lambda p, r, i, s: write_pool_blocks(
        p, r, i, s, lanes=lanes, interpret=True))(
            pool, raw, *padded(targets, sources))
    for name in pool:
        want = np.asarray(pool[name]).copy()
        want[:, targets] = np.asarray(raw[name])[:, sources]
        np.testing.assert_array_equal(np.asarray(got[name]), want)


def test_insert_program_aliases_the_pool_and_holds_no_loop(engine):
    sv = make_paged(engine, n_slots=2)
    sv._build_pool_programs()
    cache = sv._fresh_cache_jit()
    ids, srcs = sv._writer_ids([3, 4], [0, 1])
    text = sv._insert_block_jit.lower(
        sv._state, cache["k"], cache["v"], ids, srcs).compile().as_text()
    alias = parse_input_output_alias(text)
    # the state dict flattens in key order, inputs and outputs alike
    for name in ("k", "v"):
        leaf = sorted(sv._state).index(name)
        assert alias.get(leaf) == leaf, (name, alias)
    assert "while(" not in text


def test_one_insert_program_and_honest_block_counts(engine):
    """Requests of four different footprints: one compiled insert program,
    one dispatch a request, and ``kv_insert_blocks`` is the private blocks
    the allocator handed out, padding not counted."""
    sv = make_paged(engine, n_slots=2)
    handed = []
    alloc = sv.pool_mgr.alloc
    sv.pool_mgr.alloc = lambda n: handed.append(n) or alloc(n)
    rng = np.random.RandomState(5)
    reqs = [Request(prompt=rng.randint(0, 64, (plen,)).astype(np.int32),
                    max_new_tokens=new)
            for plen, new in ((3, 2), (14, 9), (30, 20), (40, 24))]
    list(sv.serve(reqs))
    assert all(r.state is RequestState.FINISHED for r in reqs)
    assert len(set(handed)) > 2, handed       # the sizes did differ
    assert sv.compile_counts()["insert_block"] == 1
    snap = sv.metrics.snapshot()
    assert snap["kv_insert_dispatches"] == len(reqs)
    assert snap["kv_insert_blocks"] == sum(handed)
    assert sum(handed) < len(reqs) * sv.pool_mgr.blocks_per_slot
