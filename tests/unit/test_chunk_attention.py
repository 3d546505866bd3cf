"""The prefill chunk's key-block kernel (``ops/pallas/chunk_attention.py``)
under the Pallas interpreter against the XLA block body it replaces, one
block at a time at the three served configurations' group shapes and over
whole ``blockwise_attention`` / ``expanded_attention`` calls down both
paths; which path a program takes; and the serving engine's booking of it.
CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import get_model, latent, window_moe
from deepspeed_tpu.ops.pallas import chunk_attention as C
from deepspeed_tpu.ops.pallas import lowering_target

F32, BF16 = jnp.float32, jnp.bfloat16


def xla_block(q, k, v, carry, q_start, start, lower, rep, scale, window):
    """The models' XLA block body (``window_moe.blockwise_attention``'s
    ``one_block``) on the kernel's layout: q [b, G, q * rep, dk], k / v [b,
    G, blk, d], carry (m, l, acc) with ``-inf`` for a maximum not yet set."""
    m, l, acc = carry
    rows, blk = q.shape[2], k.shape[2]
    prec = jax.lax.Precision.HIGHEST if q.dtype == F32 else None
    q_idx = q_start + jnp.arange(rows) // rep
    k_idx = start + jnp.arange(blk)
    s = jnp.einsum("bgqd,bgkd->bgqk", q, k, precision=prec,
                   preferred_element_type=F32) * scale
    allowed = (k_idx[None, :] <= q_idx[:, None]) & (k_idx[None, :] >= lower)
    if window:
        allowed &= q_idx[:, None] - k_idx[None, :] < window
    s = jnp.where(allowed, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    e = jnp.exp(s - safe[..., None])
    fix = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
    l = l * fix + jnp.sum(e, axis=-1)
    acc = acc * fix[..., None] + jnp.einsum(
        "bgqk,bgkd->bgqd", e.astype(q.dtype), v, precision=prec,
        preferred_element_type=F32)
    return m_new, l, acc


# (G, R, dk, dv, window, q_len, blk, q_start, start, lower, tiles)
BLOCKS = {
    # kanana2: 32 heads over their own expanded K/V, 192 / 128, blocks of
    # 2048; the queries' diagonal inside the block
    "kanana": (32, 1, 192, 128, 0, 16, 2048, 3000, 2048, 2048, None),
    # trinity: 4 groups of 8, band 2048 over blocks of 1024; a block wholly
    # in the band and behind the queries (no tile masked), then one on the
    # diagonal
    "trinity-window-behind": (4, 8, 128, 128, 2048, 32, 1024, 2500, 1024,
                              1024, None),
    "trinity-window-diagonal": (4, 8, 128, 128, 2048, 32, 1024, 2500, 2048,
                                2048, None),
    "trinity-full": (4, 8, 128, 128, 0, 32, 1024, 1300, 1024, 1024, None),
    # mimo: the window layers' 8 groups of 8 under a band of 128 (key tiles
    # of 256: most of the block skipped), the full layers' 4 groups of 16
    "mimo-sink-window": (8, 8, 192, 128, 128, 64, 1024, 2100, 2048, 2048,
                         None),
    "mimo-full": (4, 16, 192, 128, 0, 32, 1024, 5000, 4096, 4096, None),
    # a whole chunk of 1024 queries in the served tiles, one group: several
    # query tiles, some on the block's diagonal, some wholly past it, some
    # (the band) skipping most of its key tiles
    "kanana-chunk": (1, 1, 192, 128, 0, 1024, 2048, 3000, 2048, 2048, None),
    "trinity-window-chunk": (1, 8, 128, 128, 2048, 1024, 1024, 2500, 2048,
                             2048, None),
    "mimo-sink-window-chunk": (1, 8, 192, 128, 128, 1024, 1024, 2100, 2048,
                               2048, None),
    "mimo-full-chunk": (1, 16, 192, 128, 0, 1024, 1024, 5000, 5120, 5120,
                        None),
    # a block wholly in the queries' future: nothing changes
    "future-block": (2, 4, 64, 32, 0, 32, 256, 100, 256, 256, (8, 64)),
    # the last block of a context of 1500 read where it fits: its rows below
    # 1024 are the block before's
    "last-block-overlap": (2, 4, 64, 32, 0, 32, 1024, 1460, 476, 1024,
                           (8, 128)),
    # tiles that do not divide the queries' first position, a band cutting
    # tiles on both sides
    "unaligned-start-band": (2, 2, 32, 16, 40, 64, 256, 37, 0, 0, (8, 16)),
    "unaligned-start": (1, 4, 32, 32, 0, 64, 128, 75, 0, 0, (16, 32)),
}


@pytest.mark.parametrize("dtype,atol", [(F32, 2e-6), (BF16, 5e-3)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(BLOCKS))
def test_one_block_against_the_xla_body(case, dtype, atol):
    """One key block folded by the kernel (interpreter) and by the XLA body
    from the same carry: a sink's start (every row has a sum, so every
    output is finite) after one earlier block at 0. Seen: float32 within
    4.2e-7, bfloat16 within 1.1e-3 (the probabilities enter P.V in bf16,
    and the maximum they are taken against differs by tile)."""
    G, R, dk, dv, w, q_len, blk, q_start, start, lower, tiles = BLOCKS[case]
    rng = np.random.default_rng(sorted(BLOCKS).index(case))
    rows = q_len * R
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    q, k, v = arr(1, G, rows, dk), arr(1, G, blk, dk), arr(1, G, blk, dv)
    sink = jnp.asarray(rng.standard_normal((1, G, rows)), F32)
    scale = 1.0 / np.sqrt(dk)
    got = C.initial_carry(1, G, rows, dv, sink)
    want = (sink, jnp.ones_like(sink), jnp.zeros((1, G, rows, dv), F32))
    for st, lo in ((0, 0), (start, lower)):
        got = C.chunk_attention_block(q, k, v, got, q_start, st, lo, rep=R,
                                      scale=scale, window=w, tiles=tiles,
                                      interpret=True)
        want = xla_block(q, k, v, want, q_start, st, lo, R, scale, w)
    np.testing.assert_allclose(np.asarray(got[0][..., 1]), want[1],
                               rtol=atol)
    np.testing.assert_allclose(np.asarray(C.finish(got, F32)),
                               want[2] / want[1][..., None], atol=atol)


def _window_call(family, window, kernel, dtype, kv, q_start, q_len):
    model = get_model(family, "tiny", compute_dtype=dtype,
                      attention_interpret=kernel)
    cfg = model.config
    (G, dk), (_, dv) = cfg.kv_geometry(window).values()
    rng = np.random.default_rng(1)
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    q = arr(1, q_len, cfg.n_heads, dk)
    k, v = arr(1, kv, G, dk), arr(1, kv, G, dv)
    sink = jnp.asarray(rng.standard_normal(cfg.n_heads), F32) \
        if window and cfg.sink_window else None
    read = lambda start, n: (jax.lax.dynamic_slice_in_dim(k, start, n, 1),
                             jax.lax.dynamic_slice_in_dim(v, start, n, 1))
    return window_moe.blockwise_attention(cfg, q, read, kv, q_start, window,
                                          sink, kernel=kernel)


def _latent_call(kernel, dtype, kv, q_start, q_len, kv_live):
    model = get_model("kanana2", "tiny", compute_dtype=dtype,
                      attention_interpret=kernel)
    cfg = model.config
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rng = np.random.default_rng(2)
    arr = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    p = {"kv_b": {"kernel": arr(cfg.kv_lora_rank,
                                H * (dn + cfg.v_head_dim)) * 0.2}}
    return latent.expanded_attention(
        cfg, p, arr(1, q_len, H, dn), arr(1, q_len, H, dr),
        arr(1, kv, cfg.kv_lora_rank), arr(1, kv, dr), q_start,
        kv_live=kv_live, kernel=kernel)


# (call, kv, q_start, q_len): KV_BLOCK cut to 16 (window) / 32 (latent)
CALLS = {
    # a band of 24 starting inside a block; a context that is no multiple of
    # the block, its last block read where it fits
    "trinity-window": (("trinity", True), 75, 40, 32),
    "trinity-full": (("trinity", False), 75, 40, 32),
    "mimo-sink-window": (("mimo_v2", True), 90, 50, 32),
    "mimo-full": (("mimo_v2", False), 90, 50, 32),
    "mimo-sink-window-first-chunk": (("mimo_v2", True), 90, 0, 16),
    # a chunk against a latent prefix: blocks from ``kv_live`` on skipped
    "kanana-chunk": (("kanana", 72), 128, 48, 24),
    # the prefill branch: the prompt is the whole context
    "kanana-prefill": (("kanana", None), 64, 0, 64),
}


@pytest.mark.parametrize("dtype,atol", [(F32, 3e-6), (BF16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CALLS))
def test_whole_calls_down_both_paths(case, dtype, atol, monkeypatch):
    """``blockwise_attention`` and ``expanded_attention`` as the cached
    forwards call them, the chunk kernel's path (``attention_interpret``)
    against the XLA body's, over several blocks: the band's first block, a
    sink, a last block read where it fits, blocks past ``kv_live``. Seen:
    float32 within 6e-7, bfloat16 within 2.0e-3 (one bf16 step of the
    outputs)."""
    (what, arg), kv, q_start, q_len = CALLS[case]
    monkeypatch.setattr(window_moe, "KV_BLOCK", 16)
    monkeypatch.setattr(latent, "KV_BLOCK", 32)
    if what == "kanana":
        run = lambda kernel: _latent_call(kernel, dtype, kv, q_start, q_len,
                                          arg)
    else:
        run = lambda kernel: _window_call(what, arg, kernel, dtype, kv,
                                          q_start, q_len)
    with C.traced_paths() as seen:
        got = run(True)
    assert seen == {"kernel"}
    want = run(False)
    assert got.shape == want.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol)


def test_the_path_follows_platform_devices_and_tiles():
    """``"kernel"`` for a TPU target or the interpreter, on one device, where
    the tiles divide the shapes; ``"xla"`` otherwise."""
    from jax.sharding import Mesh

    path = C.chunk_attention_path
    assert path(1024, 8, 1024) == "xla"                     # a CPU
    assert path(1024, 8, 1024, interpret=True) == "kernel"
    devices = np.asarray(jax.devices()[:2])
    one, two = Mesh(devices[:1], ("data",)), Mesh(devices, ("data",))
    with lowering_target("tpu"):
        assert path(1024, 1, 2048, mesh=one) == "kernel"
        assert path(1024, 16, 1024, window=128) == "kernel"
        assert path(1024, 8, 1024, mesh=two) == "xla"
        assert path(1500, 1, 1024) == "xla"                 # no row tile
        assert path(1024, 8, 1100) == "xla"                 # no key tile
    assert path(1024, 8, 1024, interpret=True, mesh=two) == "kernel"
    # what a trace collects: every path chosen inside it, none outside it
    with C.traced_paths() as seen:
        path(1024, 8, 1024)
        path(1024, 8, 1024)
    path(1024, 8, 1024, interpret=True)
    assert seen == {"xla"}
    assert C.chunk_tiles(1024, 1, 2048) == (512, 1024)
    assert C.chunk_tiles(1024, 8, 1024, 2048) == (64, 1024)
    assert C.chunk_tiles(1024, 8, 1024, 128) == (64, 256)
    assert C.chunk_tiles(1024, 16, 1024) == (32, 1024)
    assert C.chunk_tiles(64, 8, 1024) == (64, 1024)


@pytest.mark.parametrize("family,calls,kinds", [
    ("trinity", 6, 2), ("mimo_v2", 7, 2), ("kanana2", 2, 1)])
def test_a_program_traces_the_kernel_once_a_kind(family, calls, kinds):
    """A cached forward's layers of one kind share one trace of the kernel's
    call (one jaxpr, so one lowering): tracing and lowering the call are what
    a server pays per call site in Python when it loads its chunk programs
    from the compile cache."""
    from deepspeed_tpu.models import decoding

    model = get_model(family, "tiny", attention_interpret=True,
                      compute_dtype=F32)
    cfg = model.config
    params = jax.eval_shape(lambda r: jax.tree_util.tree_map(
        lambda p: p.value, model.init(r), is_leaf=lambda x: hasattr(x, "axes")),
        jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: decoding.init_cache(cfg, 1, 128, F32))
    closed = jax.make_jaxpr(lambda p, ids, c: decoding.forward_with_cache(
        model, p, ids, c, 40, 128))(
        params, jax.ShapeDtypeStruct((1, 32), jnp.int32), cache)
    seen = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.params.get("name") == "chunk_attention_block":
                seen.append(id(eqn.params["jaxpr"]))
            for v in eqn.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(closed.jaxpr)
    assert (len(seen), len(set(seen))) == (calls, kinds)


@pytest.mark.parametrize("interpret", [False, True], ids=["cpu", "interpret"])
def test_the_engine_books_every_chunk_by_its_attention_path(interpret):
    """``snapshot()["kv_pool"]["chunk_attention_dispatches"]``: a window
    engine's chunks are booked ``kernel`` where the interpreter runs the
    kernel and ``xla`` on the plain CPU path, each chunk once."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, RequestState

    model = get_model("trinity", "tiny", attention_interpret=interpret)
    eng = deepspeed_tpu.init_inference(
        model, dtype="float32", seed=3, max_tokens=256, prompt_bucket_size=8,
        prompt_bucket_policy="pow2", serving={
            "max_slots": 2, "chunked_prefill": {
                "enabled": True, "chunk_size": 32,
                "decode_steps_between_chunks": 1},
            "kv_pool": {"block_size": 8, "n_blocks": 65,
                        "prefix_cache": False}})
    sv = eng.serving
    req = sv.submit(Request(prompt=np.arange(70, dtype=np.int32) % 500,
                            max_new_tokens=3))
    while req.state is not RequestState.FINISHED:
        sv.step()
    snap = sv.metrics.snapshot()
    path, other = ("kernel", "xla") if interpret else ("xla", "kernel")
    assert snap["kv_pool"]["chunk_attention_dispatches"] == {
        path: snap["moe"]["prefill_chunks"], other: 0}
    assert snap["moe"]["prefill_chunks"] == 3
    eng.destroy()
