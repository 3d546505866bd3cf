"""Paged flash-decode kernel subsystem tests (tier-1, interpret mode on CPU).

The acceptance invariants of the decode-attention kernel (ROADMAP S5a):

- the kernel (``ops/pallas/paged_attention.py``) matches a dense
  gather-and-softmax reference through the block table over the pool's
  merged-row leaves ``[n_blocks, bs, kvh * dh]``: ragged per-slot cursors
  (0, mid-block, a full block, the last position), GQA grouping, alibi
  bias, every chunking of a slot's window, and garbage-block rows EXCLUDED
  (the pool's reserved block is poisoned with huge values: any unmasked
  read explodes the output);
- ``forward_with_paged_cache(kernel=True)`` tracks the view path's logits
  at fp tolerance across rope/alibi/GQA/parallel-attn model variants, and
  the kernel program MATERIALIZES NO dense per-slot view and no copy of a
  pool leaf (the compiled decode program is read);
- which path runs is the engine's choice from what it can observe:
  a configuration that still carries ``kv_pool.enabled`` and
  ``kv_pool.attention_backend`` loads, warns once a key and changes no
  token; an int8 pool, banded local layers and speculative verify take the
  view, with the reason in the snapshot;
- greedy serving streams are BITWISE equal kernel-vs-view-vs-sequential
  ``generate()`` under staggered arrivals (single-device and TP=2), decode
  compiles exactly once, and the snapshot counts the dispatches by path.
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


# attention_interpret: the kernel runs under the Pallas interpreter here (the
# explicit switch every kernel test sets), and the probe then answers
# "kernel"; without it, off a TPU, the probe answers "view"
tiny_cfg = functools.partial(conftest.tiny_cfg, attention_interpret=True)


@pytest.fixture(scope="module")
def kernel_engine():
    return conftest.tiny_engine(attention_interpret=True)


def make_serving(engine, kv_pool=None, **kw):
    return conftest.make_paged(engine, kv_pool, **kw)


# ---------------------------------------------------------------------------
# 1. the kernel itself vs a dense reference (interpret mode)
# ---------------------------------------------------------------------------

def _dense_reference(q, k_new, v_new, kc, vc, table, pos, scale, slopes=None):
    """Gather a dense view through the table and run exact softmax over the
    valid window [0, pos) + the fresh row: what the kernel must match."""
    S, nh, dh = q.shape
    kvh = k_new.shape[1]
    nb, bs, _ = kc.shape
    NB = table.shape[1]
    hq = nh // kvh
    kc = np.asarray(kc, np.float32).reshape(nb, bs, kvh, dh)
    vc = np.asarray(vc, np.float32).reshape(nb, bs, kvh, dh)
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


# ragged cursors over a 4-column table of 8-token blocks: mid-block (9, 31:
# also the LAST position of the window), inside the first block (1), a
# block boundary (24); all 0; whole blocks exactly (8, 16, 32 would leave
# the fresh row no room: 24 is the fullest whole-block cursor), with empty
# slots between live ones (the kernel's prefetch chain skips them)
CURSORS = {"ragged": [9, 31, 1, 24], "zero": [0, 0, 0, 0],
           "full_blocks": [8, 16, 0, 24], "gaps": [0, 31, 0, 5]}


def _kernel_fixture(kvh=2, hq=2, dh=16, cursors="ragged"):
    rng = np.random.RandomState(0)
    S, NB, bs, n_blocks = 4, 4, 8, 9
    nh = kvh * hq
    kc = rng.randn(n_blocks, bs, kvh * dh).astype(np.float32)
    vc = rng.randn(n_blocks, bs, kvh * dh).astype(np.float32)
    # poison the GARBAGE block: the kernel must never read an unbound
    # column or a past-cursor row, or the softmax visibly explodes
    kc[0] = 1e4
    vc[0] = 1e4
    pos = np.asarray(CURSORS[cursors], np.int32)
    # each slot binds exactly the blocks its cursor needs (plus the one the
    # fresh row lands in); every other column stays on the garbage block
    free = list(range(1, n_blocks))
    table = np.zeros((S, NB), np.int32)
    for s in range(S):
        for j in range(min(NB, pos[s] // bs + 1) if pos[s] else 0):
            table[s, j] = free[(3 * s + 5 * j) % len(free)]
    q = rng.randn(S, nh, dh).astype(np.float32)
    k_new = rng.randn(S, kvh, dh).astype(np.float32)
    v_new = rng.randn(S, kvh, dh).astype(np.float32)
    return q, k_new, v_new, kc, vc, table, pos


def _run_kernel(q, k_new, v_new, kc, vc, table, pos, **kw):
    return paged_flash_decode(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(kc), jnp.asarray(vc), jnp.asarray(table),
        jnp.asarray(pos), interpret=True, **kw)


@pytest.mark.parametrize("chunk_tokens", [8, 16, 256])
@pytest.mark.parametrize("cursors", sorted(CURSORS))
def test_kernel_matches_dense_reference(cursors, chunk_tokens):
    """Every chunking of a slot's window (one block a step, two, the whole
    table: what split counts were to the old grid) at every kind of cursor."""
    q, k_new, v_new, kc, vc, table, pos = _kernel_fixture(cursors=cursors)
    out = _run_kernel(q, k_new, v_new, kc, vc, table, pos,
                      chunk_tokens=chunk_tokens)
    ref = _dense_reference(q, k_new, v_new, kc, vc, table, pos,
                           1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-6)


@pytest.mark.parametrize("cursors", sorted(CURSORS))
@pytest.mark.parametrize("kvh,hq,dh", [(2, 3, 8), (1, 4, 8), (4, 1, 16)])
def test_kernel_gqa_and_alibi(kvh, hq, dh, cursors):
    q, k_new, v_new, kc, vc, table, pos = _kernel_fixture(
        kvh=kvh, hq=hq, dh=dh, cursors=cursors)
    slopes = (0.5 ** np.arange(1, q.shape[1] + 1)).astype(np.float32)
    out = _run_kernel(q, k_new, v_new, kc, vc, table, pos,
                      alibi_slopes=jnp.asarray(slopes), chunk_tokens=16)
    ref = _dense_reference(q, k_new, v_new, kc, vc, table, pos,
                           1.0 / np.sqrt(q.shape[-1]), slopes=slopes)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-6)


def _band_reference(q, k_new, v_new, kc, vc, table, pos, window, ring):
    """The view with the mask: each slot's blocks gathered through the
    table (a ring: column ``c`` holds the newest block ``j <= pos // bs``
    with ``j % n_cols == c``), exact softmax over the positions ``[pos -
    window + 1, pos)`` and the fresh row."""
    S, nh, dh = q.shape
    kvh = k_new.shape[1]
    nb, bs, _ = kc.shape
    NB = table.shape[1]
    kc = np.asarray(kc, np.float32).reshape(nb, bs, kvh, dh)
    vc = np.asarray(vc, np.float32).reshape(nb, bs, kvh, dh)
    out = np.zeros((S, nh, dh), np.float32)
    for s in range(S):
        p_ = int(pos[s])
        seen = range(max(p_ - window + 1, 0), p_)
        cols = [(t // bs) % NB if ring else t // bs for t in seen]
        rows = [(table[s, c], t % bs) for c, t in zip(cols, seen)]
        for h in range(nh):
            g = h // (nh // kvh)
            keys = np.stack([kc[b, o, g] for b, o in rows]
                            + [np.asarray(k_new)[s, g]])
            vals = np.stack([vc[b, o, g] for b, o in rows]
                            + [np.asarray(v_new)[s, g]])
            sc = (np.asarray(q)[s, h] @ keys.T) / np.sqrt(dh)
            e = np.exp(sc - sc.max())
            out[s, h] = (e / e.sum()) @ vals
    return out


# window 20 over blocks of 8: before the band fills (5), at its edge (19,
# 20, 21), a block boundary inside a lap (24), several laps of a 4-block
# ring on (67, 96, 127), and dead slots between
BAND_CURSORS = {"filling": [5, 19, 0, 20], "edge": [21, 24, 23, 1],
                "laps": [67, 0, 96, 127]}


@pytest.mark.parametrize("chunk_tokens", [8, 16, 256])
@pytest.mark.parametrize("kvh,hq", [(2, 2), (1, 4), (2, 4)])
@pytest.mark.parametrize("cursors", sorted(BAND_CURSORS))
def test_kernel_band_over_a_ring_matches_the_masked_view(cursors, kvh, hq,
                                                         chunk_tokens):
    """A window layer's calls: the walk starts at the chunk that holds
    ``pos - window + 1``, masks inside it, and reads a slot's blocks through
    a ring as wide as the band; every cursor x chunking x GQA grouping
    against the view with the mask. Blocks the band has left are POISONED:
    any read of them explodes the output."""
    rng = np.random.RandomState(1)
    S, bs, window, dh = 4, 8, 20, 16
    NB = -(-window // bs) + 1                    # the ring: 4 blocks
    nh = kvh * hq
    n_blocks = S * NB + 1
    kc = rng.randn(n_blocks, bs, kvh * dh).astype(np.float32)
    vc = rng.randn(n_blocks, bs, kvh * dh).astype(np.float32)
    pos = np.asarray(BAND_CURSORS[cursors], np.int32)
    table = (1 + rng.permutation(S * NB).reshape(S, NB)).astype(np.int32)
    for s in range(S):
        # rows of the ring that hold nothing the band sees: poison
        first = max(pos[s] - window + 1, 0)
        live = {((t // bs) % NB, t % bs) for t in range(first, pos[s])}
        for c in range(NB):
            for o in range(bs):
                if (c, o) not in live:
                    kc[table[s, c], o] = 1e4
                    vc[table[s, c], o] = 1e4
    q = rng.randn(S, nh, dh).astype(np.float32)
    k_new = rng.randn(S, kvh, dh).astype(np.float32)
    v_new = rng.randn(S, kvh, dh).astype(np.float32)
    out = _run_kernel(q, k_new, v_new, kc, vc, table, pos, window=window,
                      ring=True, chunk_tokens=chunk_tokens)
    want = _band_reference(q, k_new, v_new, kc, vc, table, pos, window, True)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-6)


def test_kernel_band_over_an_ordinary_table_and_no_band_is_the_old_program():
    """The band without the ring (every block kept, the walk still starts
    at the band); and ``window=0`` traces the program the kernel had before
    the band existed, whatever ``ring`` says about a table it never wraps."""
    q, k_new, v_new, kc, vc, table, pos = _kernel_fixture()
    out = _run_kernel(q, k_new, v_new, kc, vc, table, pos, window=6,
                      chunk_tokens=8)
    want = _band_reference(q, k_new, v_new, kc, vc, table, pos, 6, False)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-6)
    args = [jnp.asarray(a) for a in (q, k_new, v_new, kc, vc, table, pos)]
    plain = jax.make_jaxpr(lambda *a: paged_flash_decode(
        *a, interpret=True))(*args)
    no_band = jax.make_jaxpr(lambda *a: paged_flash_decode(
        *a, window=0, interpret=True))(*args)
    banded = jax.make_jaxpr(lambda *a: paged_flash_decode(
        *a, window=6, interpret=True))(*args)
    assert str(plain) == str(no_band) != str(banded)


def test_kernel_reads_one_layer_of_the_whole_pool():
    """The decode program hands the kernel the pool leaves WHOLE and a
    traced layer index (no slice of a leaf is made): every layer reads its
    own blocks, in bf16 through the two-part probability product too."""
    q, k_new, v_new, kc, vc, table, pos = _kernel_fixture()
    rng = np.random.RandomState(1)
    kcs = np.stack([kc, rng.randn(*kc.shape).astype(np.float32)])
    vcs = np.stack([vc, rng.randn(*vc.shape).astype(np.float32)])
    scale = 1.0 / np.sqrt(q.shape[-1])
    for dtype, atol in ((jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)):
        cast = lambda a: np.asarray(jnp.asarray(a, dtype).astype(jnp.float32))
        run = jax.jit(lambda layer: paged_flash_decode(
            jnp.asarray(q, dtype), jnp.asarray(k_new, dtype),
            jnp.asarray(v_new, dtype), jnp.asarray(kcs, dtype),
            jnp.asarray(vcs, dtype), jnp.asarray(table), jnp.asarray(pos),
            layer=layer, interpret=True))
        for layer in (0, 1):
            ref = _dense_reference(cast(q), cast(k_new), cast(v_new),
                                   cast(kcs[layer]), cast(vcs[layer]), table,
                                   pos, scale)
            np.testing.assert_allclose(
                np.asarray(run(layer).astype(jnp.float32)), ref, atol=atol)


def test_kernel_survives_cursor_zero():
    """pos == 0 never happens in serving (the cursor starts at prompt_len
    >= 1) but the kernel must not NaN on an all-empty pool window: the
    fresh row alone defines the softmax."""
    q, k_new, v_new, kc, vc, table, _ = _kernel_fixture()
    out = _run_kernel(q, k_new, v_new, kc, vc, table,
                      np.zeros((q.shape[0],), np.int32))
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(
        np.asarray(out),
        np.repeat(np.asarray(v_new), q.shape[1] // k_new.shape[1], axis=1),
        atol=2e-6)


# ---------------------------------------------------------------------------
# 2. forward_with_paged_cache: kernel vs view across model variants
# ---------------------------------------------------------------------------

def _paged_setup(cfg_kw, kv_dtype=None):
    cfg = tiny_cfg(**cfg_kw)
    model = CausalLM(cfg)
    params, _ = split_params_axes(model.init(jax.random.PRNGKey(0)))
    from deepspeed_tpu.models.decoding import (forward_with_cache, init_cache,
                                               init_paged_cache,
                                               insert_block_kv)

    rng = np.random.RandomState(2)
    plen, bs, max_len = 10, 16, 64
    ids = rng.randint(0, 64, (2, plen)).astype(np.int32)
    cache = init_cache(cfg, 2, max_len, jnp.float32)
    logits, cache = forward_with_cache(model, params, jnp.asarray(ids),
                                       cache, 0, max_len)

    def mkpool(kv_dtype=kv_dtype):
        pool = init_paged_cache(cfg, 9, bs, jnp.float32, kv_dtype)
        for s in range(2):
            c1 = {k: v[:, s:s + 1] for k, v in cache.items()}
            pool = insert_block_kv(pool, c1, 1 + s * 4 + jnp.arange(4),
                                   jnp.arange(4), bs)
        return pool

    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    tok = jnp.argmax(logits[:, plen - 1], -1).astype(jnp.int32)
    pos = jnp.asarray([plen, plen], jnp.int32)
    return model, params, mkpool, table, tok, pos, bs


def _forward_parity(cfg_kw, tol=1e-5, steps=5):
    from deepspeed_tpu.models.decoding import forward_with_paged_cache

    model, params, mkpool, table, tok, pos, bs = _paged_setup(cfg_kw)
    pv, pk = mkpool(), mkpool()
    worst = 0.0
    for _ in range(steps):
        lv, pv = forward_with_paged_cache(model, params, tok[:, None], pv,
                                          table, pos, bs)
        lk, pk = forward_with_paged_cache(model, params, tok[:, None], pk,
                                          table, pos, bs, kernel=True)
        worst = max(worst, float(jnp.max(jnp.abs(lv - lk))))
        # greedy decisions identical -> bitwise streams downstream
        assert bool((jnp.argmax(lv[:, 0], -1)
                     == jnp.argmax(lk[:, 0], -1)).all())
        tok = jnp.argmax(lv[:, 0], -1).astype(jnp.int32)
        pos = pos + 1
    assert worst < tol, (cfg_kw, worst)
    # both paths wrote the same rows into the pool (a layer's rows follow
    # the layer below's attention: float order, not bits)
    for name in pv:
        np.testing.assert_allclose(np.asarray(pv[name]),
                                   np.asarray(pk[name]), atol=tol)


def test_forward_parity_plain():
    _forward_parity({})


def test_forward_parity_rope_gqa():
    _forward_parity({"position_embedding": "rope", "n_kv_heads": 2})


def test_forward_parity_alibi():
    _forward_parity({"position_embedding": "alibi"})


def test_forward_parity_parallel_attn():
    _forward_parity({"parallel_attn_mlp": True})


def test_forward_int8_pool_takes_the_view_within_pinned_tolerance():
    """An int8 pool is the view's (the kernel reads a pool in the engine's
    dtype): asked for by name it refuses, and the view over the merged
    int8 rows and their per-head scales tracks the float pool within what
    8 bits a value allow."""
    from deepspeed_tpu.models.decoding import forward_with_paged_cache

    model, params, mkpool, table, tok, pos, bs = _paged_setup({})
    pool8, poolf = mkpool("int8"), mkpool(None)
    cfg = model.config
    assert pool8["k"].shape == (2, 9, 16, cfg.kv_heads * cfg.head_dim)
    assert pool8["k_scale"].shape == (2, 9, 16, cfg.kv_heads)
    with pytest.raises(ValueError, match="int8 pool"):
        forward_with_paged_cache(model, params, tok[:, None], pool8, table,
                                 pos, bs, kernel=True)
    l8, pool8 = forward_with_paged_cache(model, params, tok[:, None], pool8,
                                         table, pos, bs)
    lf, _ = forward_with_paged_cache(model, params, tok[:, None], poolf,
                                     table, pos, bs, kernel=True)
    assert float(jnp.max(jnp.abs(l8 - lf))) < 2e-2
    assert pool8["k"].dtype == jnp.int8


def test_kernel_is_decode_only():
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
            draft_len=jnp.asarray([2], jnp.int32), kernel=True)


# ---------------------------------------------------------------------------
# 3. serving: bitwise streams, compile census, no dense view, who chooses
# ---------------------------------------------------------------------------

def test_serving_streams_bitwise_kernel_vs_view_vs_generate(kernel_engine,
                                                            engine):
    """THE acceptance pin: greedy streams through the kernel are
    bitwise-equal to the view path AND sequential generate() under
    staggered arrivals/mixed lengths, the decode program compiles exactly
    once, and the snapshot records which path produced the streams and
    counts its dispatches."""
    mk = lambda: staggered_requests(np.random.RandomState(0), 6)
    kernel_reqs, view_reqs = mk(), mk()

    sk = make_serving(kernel_engine)
    assert (sk.attn_backend, sk.attn_reason) == ("kernel", "")
    list(sk.serve(kernel_reqs))
    sv = make_serving(engine)
    assert sv.attn_backend == "view"
    assert "interpret mode was not requested" in sv.attn_reason
    list(sv.serve(view_reqs))

    assert all(r.state is RequestState.FINISHED for r in kernel_reqs)
    for kr, vr in zip(kernel_reqs, view_reqs):
        assert kr.tokens == vr.tokens          # kernel == view, bitwise
        ref = np.asarray(engine.generate(
            kr.prompt[None, :], max_new_tokens=kr.max_new_tokens,
            greedy=True))
        np.testing.assert_array_equal(np.asarray(kr.tokens),
                                      ref[0, kr.prompt_len:])

    counts = sk.compile_counts()
    assert counts["decode"] == 1, counts
    assert counts["insert"] == 1, counts
    for s, path, other in ((sk, "kernel", "view"), (sv, "view", "kernel")):
        kv = s.metrics.snapshot()["kv_pool"]
        assert kv["attention_backend"] == path
        assert kv["attention_reason"] == s.attn_reason
        assert kv["decode_dispatches"][path] \
            == s.metrics.decode_dispatches > 0
        assert kv["decode_dispatches"][other] == 0


@pytest.mark.parametrize("value,enabled", [("gather", True),
                                           ("fused", False)],
                         ids=["gather", "fused"])
def test_configuration_written_before_pr31_still_loads(kernel_engine, engine,
                                                       value, enabled):
    """A ``kv_pool`` block that still carries the two keys the program
    dropped (``enabled``, ``attention_backend``) loads: each warns once as
    an unknown key, and neither the path the engine chose nor a token
    changes. The typed constructor knows neither field."""
    stale = {"enabled": enabled, "attention_backend": value}
    for eng, path in ((kernel_engine, "kernel"), (engine, "view")):
        mk = lambda: staggered_requests(np.random.RandomState(1), 3)
        plain, old = mk(), mk()
        with conftest.unknown_key_warnings() as seen:
            list(make_serving(eng).serve(plain))
        assert not seen
        with conftest.unknown_key_warnings() as seen:
            sv = make_serving(eng, stale)
        assert sorted(seen) == conftest.STALE_KV_KEYS
        list(sv.serve(old))
        assert sv.attn_backend == path
        assert [r.tokens for r in plain] == [r.tokens for r in old]
    from deepspeed_tpu.config import ConfigError
    from deepspeed_tpu.config.config import KVPoolConfig

    for field in stale:
        with pytest.raises(ConfigError, match="unexpected fields"):
            KVPoolConfig(**{field: stale[field]})


def test_serving_seeded_sampling_unchanged_by_path(kernel_engine, engine):
    """Sampled streams are byte-identical across paths: the path moves
    attention reads around, never the rng chain (the rng splits once per
    dispatched step either way)."""
    def mk():
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, 64, (6,)).astype(np.int32)
        return [Request(prompt=prompt, max_new_tokens=8,
                        sampling=SamplingParams(temperature=1.0, top_k=8,
                                                seed=7))]

    kernel, view = mk(), mk()
    list(make_serving(kernel_engine).serve(kernel))
    list(make_serving(engine).serve(view))
    assert kernel[0].tokens == view[0].tokens


def test_serving_int8_pool_takes_the_view(kernel_engine, engine):
    """An int8 pool: the engine chooses the view even where the kernel could
    run, names the reason, and streams equal the engine's that could only
    ever take the view."""
    mk = lambda: staggered_requests(np.random.RandomState(3), 4)
    a, b = mk(), mk()
    sa = make_serving(kernel_engine, kv_pool={"kv_dtype": "int8"})
    assert sa.attn_backend == "view" and "int8 pool" in sa.attn_reason
    list(sa.serve(a))
    list(make_serving(engine, kv_pool={"kv_dtype": "int8"}).serve(b))
    assert all(r.state is RequestState.FINISHED for r in a)
    for x, y in zip(a, b):
        assert x.tokens == y.tokens
    kv = sa.metrics.snapshot()["kv_pool"]
    assert kv["decode_dispatches"]["kernel"] == 0 \
        and kv["decode_dispatches"]["view"] > 0


def test_serving_kernel_with_growth_and_garbage_columns(kernel_engine):
    """On-demand growth leaves unbound table columns on the garbage block
    mid-stream: exactly the blocks the kernel must never copy. Streams stay
    bitwise-equal to generate() through grows."""
    mk = lambda: [Request(
        prompt=np.random.RandomState(50 + i).randint(
            0, 64, (6,)).astype(np.int32), max_new_tokens=20)
        for i in range(2)]
    reqs = mk()
    sv = make_serving(kernel_engine, n_slots=2,
                      kv_pool={"on_demand_growth": True})
    assert sv.attn_backend == "kernel"
    list(sv.serve(reqs))
    assert sv.pool_mgr.grown_blocks > 0
    for r in reqs:
        ref = np.asarray(kernel_engine.generate(
            r.prompt[None, :], max_new_tokens=r.max_new_tokens, greedy=True))
        np.testing.assert_array_equal(np.asarray(r.tokens),
                                      ref[0, r.prompt_len:])


def test_kernel_program_materializes_no_view_and_copies_no_leaf(
        kernel_engine, engine):
    """What the kernel path exists to delete: the view path's lowered decode
    program holds the [S, NB, bs, kvh * dh] view-shaped gathers (K and V,
    a layer at a time); the kernel's holds NONE (the block table is walked
    inside the kernel). And the pool is the layer loop's CARRY, a new row a
    row-sized update: XLA's compiled view program (the one whose every
    operation is XLA's own here; tests/unit/test_tpu_lowering.py compiles
    the kernel's for the TPU) copies no pool leaf and slices no layer out."""
    sk, sv = make_serving(kernel_engine), make_serving(engine)
    # S=2 slots, NB=4 table columns, bs=16, kvh * dh = 16 on the tiny cfg;
    # the pool leaf is [2 layers, 9 blocks, 16, 16]
    assert tuple(sk._state["k"].shape) == (2, 9, 16, 16)

    def view_gathers(s):
        text = s.trace_decode()[0].as_text()
        return sum(1 for line in text.splitlines()
                   if "gather" in line and "2x4x16x16" in line)

    assert view_gathers(sv) > 0
    assert view_gathers(sk) == 0
    text = sv.trace_decode()[0].compile().as_text()
    made = [line.split(" = ")[1] for line in text.splitlines()
            if " = " in line]
    leaf_sized = [m for m in made if m.startswith(("f32[2,9,16,16]",
                                                   "f32[1,9,16,16]",
                                                   "f32[9,16,16]"))]
    assert leaf_sized                      # the carry, its row writes
    offenders = [m for m in leaf_sized
                 if m.split("(")[0].split()[-1].startswith(
                     ("copy", "dynamic-slice", "slice", "concatenate"))]
    assert not offenders, offenders


def test_unsupported_shape_takes_the_view():
    """Banded local-attention layers aren't implemented in-kernel: the
    engine serves through the view (never a hard failure) and says why,
    with streams still bitwise-greedy-equal to generate()."""
    cfg = tiny_cfg(local_attention_window=8, n_layers=2)
    ok, reason = fused_decode_supported(cfg, 16)
    assert not ok and "local_attention_window" in reason

    model = CausalLM(cfg)
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", max_tokens=64, prompt_bucket_size=16)
    sv = make_serving(eng)
    assert sv.attn_backend == "view"
    kv = sv.metrics.snapshot()["kv_pool"]
    assert kv["attention_backend"] == "view"
    assert "local_attention_window" in kv["attention_reason"]
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
    ok, reason = fused_decode_supported(tiny_cfg(), 16, kv_dtype="int8")
    assert not ok and "int8 pool" in reason
    with lowering_target("tpu"):
        # the production geometries lower for the TPU: many kv heads x 128
        # (BLOOM class), x 64 (OPT class), GQA, MQA, alibi. A block is
        # fetched whole, every kv head of its tokens side by side
        for kw in (dict(d_model=512), dict(d_model=256),
                   dict(d_model=512, n_kv_heads=2),
                   dict(d_model=512, n_kv_heads=1),
                   dict(d_model=512, position_embedding="alibi")):
            cfg = tiny_cfg(attention_interpret=False,
                           compute_dtype=jnp.bfloat16, **kw)
            assert fused_decode_supported(cfg, 16) == (True, ""), kw


# ---------------------------------------------------------------------------
# 4. TP=2 mesh
# ---------------------------------------------------------------------------

def test_kernel_tp_mesh_parity(devices8):
    """TP=2: the kernel decode program (the kernel inside a shard_map over
    the mesh, the pool's merged axis split over ``model`` into contiguous
    groups of kv heads: the layout a Mosaic call needs, run here under the
    interpreter) still compiles once and produces greedy streams
    bitwise-equal to the view path and the single-device generate()
    reference."""
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.parallel import build_mesh

    base = conftest.tiny_cfg(position_embedding="rope")
    values, _ = split_params_axes(
        CausalLM(base).init(jax.random.PRNGKey(4)))

    def run(path):
        cfg = conftest.tiny_cfg(position_embedding="rope",
                                attention_interpret=path == "kernel")
        mesh = build_mesh(MeshConfig(model=2, data=4), devices=devices8)
        eng = InferenceEngine(CausalLM(cfg), DeepSpeedInferenceConfig.from_dict(
            {"dtype": "float32", "max_tokens": 64,
             "tensor_parallel": {"tp_size": 2},
             "serving": {"n_slots": 2, "virtual_clock": True,
                         "kv_pool": {"block_size": 16}}}),
            mesh=mesh)
        eng.params = jax.tree_util.tree_map(
            lambda v, s: jax.device_put(v, s), values, eng.param_shardings)
        reqs = staggered_requests(np.random.RandomState(9), 3,
                                  max_new=(3, 6))
        list(eng.serve(reqs))
        assert eng.serving.attn_backend == path
        assert eng.serving.compile_counts()["decode"] == 1
        assert eng.serving._state["k"].sharding.spec[3] == "model"
        toks = [r.tokens for r in reqs]
        prompts = [r.prompt for r in reqs]
        lens = [r.max_new_tokens for r in reqs]
        eng.destroy()
        return toks, prompts, lens

    kernel_toks, prompts, lens = run("kernel")
    view_toks, _, _ = run("view")
    assert kernel_toks == view_toks

    raw = deepspeed_tpu.init_inference(CausalLM(base), dtype="float32",
                                      max_tokens=64)
    raw.params = values
    for toks, prompt, n in zip(kernel_toks, prompts, lens):
        ref = np.asarray(raw.generate(prompt[None, :], max_new_tokens=n,
                                      greedy=True))
        np.testing.assert_array_equal(np.asarray(toks),
                                      ref[0, len(prompt):])
    raw.destroy()
