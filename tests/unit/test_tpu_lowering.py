"""Lowering for the TPU from the CPU host, and the bring-up contracts around it.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the Pallas ->
Mosaic lowering (block-shape rules) and, on a multi-device mesh, the check
that refuses a Mosaic call GSPMD would have to partition — no chip needed.
Off-TPU every dispatcher used to take its XLA path quietly, so nothing in
tier-1 ever saw either failure; these tests do.

Also here: ``chip_smoke.py`` refuses to run without a TPU, the compile-cache
helper leaves a placed cache alone, and an unknown device kind has no peaks.
"""

import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.config import MeshConfig
from deepspeed_tpu.models import get_model
from deepspeed_tpu.ops.pallas import compiler_verdict, lowering_target
from deepspeed_tpu.parallel import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SDS = jax.ShapeDtypeStruct


def lower_for_tpu(fn, *args):
    """StableHLO text of ``fn`` lowered for the TPU; raises what the
    lowering raises."""
    with lowering_target("tpu"):
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()


def n_mosaic(text):
    return text.count("tpu_custom_call")


# ---------------------------------------------------------------------------
# every Pallas kernel at one production geometry
# ---------------------------------------------------------------------------

def _qkv(b, s, h, d):
    return (SDS((b, s, h, d), jnp.bfloat16),) * 3


def test_flash_kernels_lower():
    from deepspeed_tpu.ops.flash_attention import flash_attention

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    # seq 1024: single-kv-block kernels; seq 2048: the general ones
    for s, d in ((1024, 64), (2048, 128)):
        assert n_mosaic(lower_for_tpu(fwd_bwd, *_qkv(2, s, 16, d))) == 3


def test_flash_gives_way_loudly_not_silently():
    """An unaligned sequence (or a non-TPU platform) takes the XLA scan —
    and says so once per reason, at WARNING level."""
    import importlib
    import logging

    from deepspeed_tpu.ops import pallas as plx
    from deepspeed_tpu.utils.logging import logger

    fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
    plx.note_fallback.cache_clear()
    seen = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    try:
        text = lower_for_tpu(fa.flash_attention, *_qkv(1, 96, 4, 64))
        q = jnp.zeros((1, 128, 2, 8), jnp.float32)
        fa.flash_attention(q, q, q)             # CPU, no interpret
        fa.flash_attention(q, q, q)
    finally:
        logger.removeHandler(handler)
    assert n_mosaic(text) == 0
    assert len(seen) == 2, seen                 # one per distinct reason
    assert "multiples of 128" in seen[0] and "'cpu'" in seen[1]


def test_jax_flash_and_block_sparse_lower():
    from deepspeed_tpu.ops.flash_attention import jax_flash_attention
    from deepspeed_tpu.ops.pallas.block_sparse_attention import \
        BlockSparseAttention
    from deepspeed_tpu.ops.sparse_attention import BSLongformerSparsityConfig

    assert n_mosaic(lower_for_tpu(jax_flash_attention,
                                  *_qkv(2, 1024, 16, 64))) >= 1
    attn = BlockSparseAttention(
        BSLongformerSparsityConfig(block=128, num_sliding_window_blocks=3),
        2048, causal=True)
    assert n_mosaic(lower_for_tpu(attn, *_qkv(1, 2048, 8, 128))) == 1


def test_pallas_ce_lowers():
    from deepspeed_tpu.ops.cross_entropy import fused_cross_entropy

    def loss(x, emb, labels):
        return fused_cross_entropy(x, emb, labels, None, -100, 8, "pallas")

    text = lower_for_tpu(loss, SDS((4096, 1024), jnp.bfloat16),
                         SDS((50304, 1024), jnp.float32),
                         SDS((4096,), jnp.int32))
    assert n_mosaic(text) == 1


def _paged_decode_operands(nh, kvh, dh, sharding=None, layers=24, slots=32,
                           n_blocks=1537, cols=128, bs=16, dv=None):
    """The decode kernel's operands at the OPT-1.3B serve cell's geometry
    unless told otherwise: 32 slots of 128 table columns over 1537 blocks
    of 16 tokens, the pool leaves whole. ``dv``: V heads of another width
    than K heads."""
    sds = lambda shape, dt, sh=sharding: SDS(shape, dt, sharding=sh)
    pool = lambda w: sds((layers, n_blocks, bs, kvh * w), jnp.bfloat16)
    row = lambda w: sds((slots, kvh, w), jnp.bfloat16)
    dv = dv or dh
    return (sds((slots, nh, dh), jnp.bfloat16), row(dh), row(dv), pool(dh),
            pool(dv), SDS((slots, cols), jnp.int32),
            SDS((slots,), jnp.int32), SDS((), jnp.int32))


@pytest.mark.parametrize("nh,kvh,dh,alibi", [
    (32, 32, 64, False),       # OPT-1.3B: the serve cell
    (32, 32, 128, False),      # the same heads at head size 128
    (16, 16, 128, True),       # BLOOM-1.7B: alibi
    (32, 8, 128, False),       # GQA
    (8, 1, 128, False),        # MQA
    # kanana2's latent form: 32 heads over one latent row of 512 and a
    # rope key of 64, the pool's 5-D leaves as the engine holds them
    pytest.param(32, 1, 512, "latent", id="kanana2-latent-512-rope64"),
])
def test_paged_decode_lowers(nh, kvh, dh, alibi):
    from deepspeed_tpu.models.layers import alibi_slopes
    from deepspeed_tpu.ops.pallas.paged_attention import (paged_flash_decode,
                                                          paged_latent_decode)

    if alibi == "latent":
        sds = lambda *shape: SDS(shape, jnp.bfloat16)
        pool = lambda w: sds(7, 2561, 128, 1, w)
        text = lower_for_tpu(
            lambda *a: paged_latent_decode(*a[:8], layer=a[8],
                                           scale=192 ** -0.5),
            sds(32, nh, dh), sds(32, nh, 64), sds(32, dh), sds(32, 64),
            pool(dh), pool(64), SDS((32, 128), jnp.int32),
            SDS((32,), jnp.int32), SDS((), jnp.int32))
        assert n_mosaic(text) == 1 and "7x2561x128x512xbf16" in text
        return
    slopes = alibi_slopes(nh) if alibi else None

    def call(q, kn, vn, kc, vc, table, pos, layer):
        return paged_flash_decode(q, kn, vn, kc, vc, table, pos, layer=layer,
                                  alibi_slopes=slopes)

    assert n_mosaic(lower_for_tpu(
        call, *_paged_decode_operands(nh, kvh, dh))) == 1


def _abstract_params(model, dtype=jnp.bfloat16, sharding=None):
    from deepspeed_tpu.models.layers import Param

    return jax.tree_util.tree_map(
        lambda a: SDS(a.shape, dtype, sharding=sharding),
        jax.eval_shape(lambda r: jax.tree_util.tree_map(
            lambda p: p.value, model.init(r),
            is_leaf=lambda x: isinstance(x, Param)), jax.random.PRNGKey(0)))


def _decode_step(model, bs, kernel=True):
    from deepspeed_tpu.models import decoding as D

    def decode(params, tok, pool, table, pos):
        logits, pool = D.forward_with_paged_cache(
            model, params, tok, pool, table, pos, bs, kernel=kernel)
        return jnp.argmax(logits[:, 0], -1), pool

    return decode


def _decode_operands(model, sharding=None, slots=32, n_blocks=1537, cols=128,
                     bs=16):
    cfg = model.config
    sds = lambda shape, dt: SDS(shape, dt, sharding=sharding)
    pool = {n: sds((cfg.n_layers, n_blocks, bs) + row, jnp.bfloat16)
            for n, row in cfg.pool_geometry.items()}
    return (_abstract_params(model, sharding=sharding),
            sds((slots, 1), jnp.int32), pool, sds((slots, cols), jnp.int32),
            sds((slots,), jnp.int32))


def test_whole_decode_program_lowers_at_the_serve_cells_geometry():
    """OPT-1.3B's decode step as the serve cell runs it (24 layers, 32 slots
    of 2048 positions over 1537 blocks of 16): ONE Mosaic call in the layer
    loop, the pool leaves [24, 1537, 16, 2048] whole, and no tensor of
    ``n_slots x max_len`` rows anywhere in the program."""
    model = get_model("opt", "1.3b", compute_dtype=jnp.bfloat16)
    assert model.config.pool_geometry == {"k": (2048,), "v": (2048,)}
    text = lower_for_tpu(_decode_step(model, 16), *_decode_operands(model))
    assert n_mosaic(text) == 1
    assert "24x1537x16x2048xbf16" in text
    for view in ("32x128x16x2048", "32x2048x32x64", "32x2048x2048",
                 "4096x16x"):
        assert view not in text, view
    # the view path at the same geometry is the program that holds them
    view_text = lower_for_tpu(_decode_step(model, 16, kernel=False),
                              *_decode_operands(model))
    assert n_mosaic(view_text) == 0 and "32x128x16x2048" in view_text


@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip to COMPILE for. Made inside a
    fixture, after a test of this file has started, and only here: a
    process that loads the TPU's library keeps it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_compiled_kernel_decode_updates_the_pool_in_place(v5e):
    """The kernel decode program COMPILED for a v5e at the serve cell's pool
    and table (2 of the 24 layers: the loop body is the same): the donated
    pool is aliased to the output, nothing the size of a pool leaf or of a
    layer of one is copied, sliced or gathered, no ``n_slots x max_len``
    view exists, and the temporaries are a few hundred KB where the view
    path's were gigabytes."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    model = get_model("opt", "1.3b", n_layers=2, compute_dtype=jnp.bfloat16)
    cached = jax.config.jax_enable_compilation_cache
    # a compile-only executable cannot be read back from the persistent
    # cache without a chip: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with lowering_target("tpu"):
            compiled = jax.jit(_decode_step(model, 16), donate_argnums=(2,)) \
                .trace(*_decode_operands(model, sharding=v5e)) \
                .lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()
    leaf = 2 * 1537 * 16 * 2048 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * leaf
    assert mem.temp_size_in_bytes < 8 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 1
    made = [line.split(" = ")[1] for line in text.splitlines()
            if " = " in line]
    for m in made:
        head = m.split("(")[0].split()
        if len(head) < 2:                  # not "<shape> <op>(": no array
            continue
        shape, op = head[0].split("{")[0], head[-1]
        assert not shape.startswith(("bf16[32,2048,", "bf16[32,128,16,",
                                     "bf16[4096,16,")), m
        if shape in ("bf16[2,1537,16,2048]", "bf16[1,1537,16,2048]",
                     "bf16[1537,16,2048]"):
            assert op.startswith(("parameter", "get-tuple-element", "fusion",
                                  "scatter", "bitcast", "while")) \
                and "copy" not in op, m
    # the pool's layout, as the device will keep it: the row minor-most
    pool_format = compiled.input_formats[0][2]["k"]
    assert tuple(pool_format.layout.major_to_minor) == (0, 1, 2, 3)


def _block_write_operands(int8, sharding=None, latent=False):
    L, n_blocks, bs, kvh, dh, max_len = 24, 1537, 16, 32, 64, 2048
    sds = lambda shape, dt: SDS(shape, dt, sharding=sharding)
    if latent:
        # a latent pool with small blocks: 5-D leaves the device lays out
        # with the blocks in the lanes (PERF.md, PR 29)
        rows = {"k": (1, 512), "v": (1, 64)}
        pool = {n: sds((7, 2561, bs) + r, jnp.bfloat16)
                for n, r in rows.items()}
        cache = {n: sds((7, 1, max_len) + r, jnp.bfloat16)
                 for n, r in rows.items()}
        ids = SDS((max_len // bs,), jnp.int32)
        return pool, cache, ids, ids
    shape = (L, n_blocks, bs, kvh * dh)
    pool = {n: sds(shape, jnp.int8 if int8 else jnp.bfloat16)
            for n in ("k", "v")}
    if int8:
        pool.update({n + "_scale": sds(shape[:-1] + (kvh,), jnp.float32)
                     for n in ("k", "v")})
    cache = {n: sds((L, 1, max_len, kvh, dh), jnp.bfloat16)
             for n in ("k", "v")}
    ids = SDS((max_len // bs,), jnp.int32)
    return pool, cache, ids, ids


@pytest.mark.parametrize("int8,latent", [
    (False, False), (True, False), (False, True)],
    ids=["bf16", "int8", "latent"])
def test_kv_block_write_lowers(int8, latent):
    """The column kernel, for a pool said to keep its blocks in the lanes,
    at the serve cell's pool (OPT-1.3B, 1537 blocks of 16, rows merged) and
    at a latent pool's 5-D leaves: one Mosaic call a pool leaf."""
    from deepspeed_tpu.models.decoding import insert_block_kv

    text = lower_for_tpu(
        lambda p, c, i, s: insert_block_kv(p, c, i, s, 16, lanes=True),
        *_block_write_operands(int8, latent=latent))
    assert n_mosaic(text) == (4 if int8 else 2)


def test_kv_block_write_gives_way_loudly_not_silently():
    """Off the TPU (and without the interpreter) a pool said to keep its
    blocks in the lanes is still written, by the XLA scatter, and the
    dispatcher says so."""
    import logging

    from deepspeed_tpu.models.decoding import write_pool_blocks
    from deepspeed_tpu.ops import pallas as plx
    from deepspeed_tpu.utils.logging import logger

    plx.note_fallback.cache_clear()
    seen = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = lambda record: seen.append(record.getMessage())
    logger.addHandler(handler)
    rng = np.random.RandomState(0)
    pool = {"k": jnp.asarray(rng.randn(2, 9, 8, 32), jnp.float32)}
    src = {"k": jnp.asarray(rng.randn(2, 4, 8, 32), jnp.float32)}
    ids, srcs = jnp.asarray([3, 9, 7, 10]), jnp.asarray([1, 0, 2, 0])
    try:
        got = write_pool_blocks(pool, src, ids, srcs, lanes=True)
    finally:
        logger.removeHandler(handler)
    want = np.asarray(pool["k"]).copy()
    want[:, [3, 7]] = np.asarray(src["k"])[:, [1, 2]]
    np.testing.assert_array_equal(np.asarray(got["k"]), want)
    assert len(seen) == 1 and "kv_block_write" in seen[0] \
        and "'cpu'" in seen[0], seen


@pytest.mark.parametrize("bits", [8, 4])
def test_quantized_matmul_lowers(bits):
    from deepspeed_tpu.ops.pallas.quantized_matmul import \
        quantized_matmul_supported

    assert quantized_matmul_supported(2048, 8192, 32, bits=bits,
                                      dtype=jnp.bfloat16) == (True, "")
    ok, reason = quantized_matmul_supported(100, 60, 1, bits=bits,
                                            dtype=jnp.bfloat16)
    assert not ok and "no legal tiling" in reason


def _kanana_programs(n_layers, n_blocks, sharding=None):
    """kanana2's decode step (through the decode kernel's latent form, the
    engine's choice) and 1024-token chunk at the published widths: 32 slots
    of 16,384 positions over a pool of ``n_blocks`` blocks of 128 latent
    rows, ``n_layers`` of them (layer 0 dense)."""
    from deepspeed_tpu.models import decoding as D

    model = get_model("kanana2", "30b-a3b", n_layers=n_layers,
                      compute_dtype=jnp.bfloat16)
    cfg = model.config
    sds = lambda shape, dt: SDS(shape, dt, sharding=sharding)
    params = _abstract_params(model, sharding=sharding)
    slots, bs, max_len = 32, 128, 16384
    pool = {n: sds((n_layers, n_blocks, bs) + row, jnp.bfloat16)
            for n, row in cfg.cache_geometry.items()}
    assert pool["k"].shape[-2:] == (1, 512) and pool["v"].shape[-2:] == (1, 64)
    cache = {n: sds((n_layers, 1, max_len) + row, jnp.bfloat16)
             for n, row in cfg.cache_geometry.items()}

    def decode(params, tok, pool, table, pos):
        return D.forward_with_paged_cache(model, params, tok, pool, table,
                                          pos, bs, kernel=True,
                                          return_routing=True)

    def chunk(params, ids, cache, start, last):
        return D.forward_with_cache(model, params, ids, cache, start, max_len,
                                    last_index=last, return_routing=True)

    return (decode, (params, sds((slots, 1), jnp.int32), pool,
                     sds((slots, max_len // bs), jnp.int32),
                     sds((slots,), jnp.int32)),
            chunk, (params, sds((1, 1024), jnp.int32), cache,
                    sds((), jnp.int32), sds((), jnp.int32)))


def test_latent_expert_serving_programs_lower_with_the_grouped_kernel_alone():
    """kanana2's decode step and prefill chunk at the published widths lower
    for the TPU with two Mosaic calls in the scanned expert layer's body for
    the grouped expert products (``ops/pallas/grouped_matmul.py`` in both
    programs, settled on the chip: ``moe/dropfree.py``) and no
    ``ragged_dot``. Decode attends through the paged decode kernel's latent
    form, lowered once and called by the dense layer and the scanned body
    (its own ``jax.jit``): no ``32 x 16384`` view of latent rows and no
    score row over it remain. The chunk's expanded attention folds its key
    blocks in the chunk kernel (``ops/pallas/chunk_attention.py``), called
    in the dense layer's loop and in the scanned body's: one kernel, lowered
    once, as the two calls have the same shapes."""
    decode, d_args, chunk, c_args = _kanana_programs(3, 257)
    for fn, args, n_attn, n_calls in ((decode, d_args, 0, 0),
                                      (chunk, c_args, 1, 2)):
        text = lower_for_tpu(fn, *args)
        n_paged = 1 if fn is decode else 0
        assert n_mosaic(text) == 2 + n_attn + n_paged
        assert "ragged_dot" not in text
        assert text.count("grouped_matmul") == 2
        assert text.count('"chunk_attention"') == n_attn
        assert text.count("call @chunk_attention_block") == n_calls
        assert text.count('"paged_flash_decode"') == n_paged
        assert text.count("call @_paged_latent_decode(") == 2 * n_paged
    text = lower_for_tpu(decode, *d_args)
    for view in ("32x128x128x1x512", "32x16384x512", "32x16384x64",
                 "32x32x16384"):
        assert view not in text, view


def test_compiled_latent_decode_reads_its_pool_in_place(v5e):
    """kanana2's decode step through the kernel COMPILED for a v5e over the
    serve cell's pool of 2,561 blocks of 128, at 3 layers: both leaves are
    aliased to the output and nothing the size of a leaf is copied, sliced
    or gathered; the rope leaf keeps its tokens in the lanes, as the kernel
    reads it. At this depth a scatter of single 64-wide rows had the
    compiler re-lay the leaf out with its rows in the lanes, at the entry
    and the exit of every step and around every layer's kernel call (at 7
    it happened to keep the layout): the row write reads and writes back
    whole blocks. The temporaries are a few MB (the view program's are 715
    MB)."""
    decode, d_args, _, _ = _kanana_programs(3, 2561, sharding=v5e)
    compiled = _compile_for(decode, d_args)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 3 * 2561 * 128 * (512 + 64) * 2
    assert mem.temp_size_in_bytes < 16 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count("paged_flash_decode") >= 2
    _assert_only_passed_along(text, KANANA_EXPERTS + tuple(
        f"bf16[{lead}2561,{rows}]" for lead in ("3,", "")
        for rows in ("128,1,512", "128,1,64", "128,512", "128,64", "64,128")))
    layouts = {name: tuple(compiled.input_formats[0][2][name].layout
                           .major_to_minor) for name in ("k", "v")}
    assert layouts == {"k": (0, 1, 3, 2, 4), "v": (0, 1, 3, 4, 2)}


@pytest.mark.parametrize("kvh,window,geometry", [
    # full layers: K rows of 768 beside V rows of 512, 4097 blocks of 128
    (4, 0, dict(layers=2, n_blocks=4097, cols=256, bs=128)),
    # window layers: K 1536 / V 1024, a sink a head, band 128, ring of 2
    (8, 128, dict(layers=5, n_blocks=65, cols=2, bs=128)),
], ids=["full-K768-V512", "window-K1536-V1024-sink-ring2"])
def test_paged_decode_lowers_at_mimo_v2_flashs_two_geometries(kvh, window,
                                                              geometry):
    """The kernel at the ``mimo-v2-flash-serve`` cell's two pool groups: 64
    query heads of 192 over K heads of 192 and V heads of 128."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    sink = jnp.zeros((64,), jnp.float32) if window else None

    def call(q, kn, vn, kc, vc, table, pos, layer):
        return paged_flash_decode(q, kn, vn, kc, vc, table, pos, layer=layer,
                                  window=window, ring=bool(window), sink=sink)

    assert n_mosaic(lower_for_tpu(call, *_paged_decode_operands(
        64, kvh, 192, dv=128, **geometry))) == 1


def _trinity_cell_programs(sharding=None):
    """trinity's decode step and 1024-token chunk at the published widths
    and the serve cell's geometry (5 of 32 layers: one dense, one period; 32
    slots of 32,768 positions, blocks of 128, a full group of 5121 blocks
    and a window group of 32 rings of 17)."""
    from deepspeed_tpu.models import decoding as D
    from deepspeed_tpu.serving.kv_pool import window_ring_blocks

    kinds = ("sliding_attention",) * 4 + ("full_attention",)
    model = get_model("trinity", "mini", n_layers=5, first_k_dense=1,
                      layer_types=kinds, compute_dtype=jnp.bfloat16)
    cfg = model.config
    slots, bs, max_len = 32, 128, 32768
    ring = window_ring_blocks(cfg.sliding_window, bs)
    assert ring == 17 and cfg.pool_geometry == {"k": (512,), "v": (512,)}
    sds = lambda shape, dt: SDS(shape, dt, sharding=sharding)
    params = _abstract_params(model, sharding=sharding)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    assert n_params == 4_241_534_720
    pool = {"k": sds((1, 5121, bs, 512), jnp.bfloat16),
            "v": sds((1, 5121, bs, 512), jnp.bfloat16),
            "wk": sds((4, slots * ring + 1, bs, 512), jnp.bfloat16),
            "wv": sds((4, slots * ring + 1, bs, 512), jnp.bfloat16)}
    cache = {n: sds((5, 1, max_len, 4, 128), jnp.bfloat16) for n in "kv"}

    def decode(params, tok, pool, table, wtable, pos):
        return D.forward_with_paged_cache(
            model, params, tok, pool, (table, wtable), pos, bs, kernel=True,
            return_routing=True)

    def chunk(params, ids, cache, start, last):
        return D.forward_with_cache(model, params, ids, cache, start, max_len,
                                    last_index=last, return_routing=True)

    return (decode, (params, sds((slots, 1), jnp.int32), pool,
                     sds((slots, max_len // bs), jnp.int32),
                     sds((slots, ring), jnp.int32), sds((slots,), jnp.int32)),
            chunk, (params, sds((1, 1024), jnp.int32), cache,
                    sds((), jnp.int32), sds((), jnp.int32)))


def test_window_expert_serving_programs_lower_with_the_kernel_and_no_view():
    """trinity's decode step and prefill chunk at the PUBLISHED widths lower
    for the TPU: in decode one Mosaic call a layer for attention (the band in
    the four window layers) and two an expert layer for the grouped products
    (``ops/pallas/grouped_matmul.py``; a period's four layers are written
    out), those eight in the chunk beside one chunk-kernel call a layer (the
    kernel lowered once a kind: window and full), no
    ``ragged_dot`` left, no ``n_slots x max_len`` tensor in decode (the 32 x
    256 x 128 gather of the full group's view, a 32 x 32768 view or score
    row), and in the chunk no score tensor at all: the context is visited in
    blocks of 1024, each folded by the chunk kernel, whose scores stay in
    VMEM (``[32 heads, 1024, 32768]`` float32 would be 4.3 GB; a block's
    ``[4, 8, 1024, 1024]`` was XLA's)."""
    decode, d_args, chunk, c_args = _trinity_cell_programs()
    text = lower_for_tpu(decode, *d_args)
    assert n_mosaic(text) == 5 + 8 and "ragged_dot" not in text
    assert "1x5121x128x512xbf16" in text and "4x545x128x512xbf16" in text
    for view in ("32x256x128x512", "32x32768x", "32x2176x", "x32768xf32",
                 "x32768xbf16"):
        assert view not in text, view
    text = lower_for_tpu(chunk, *c_args)
    assert n_mosaic(text) == 8 + 2 and "ragged_dot" not in text
    assert text.count('"chunk_attention"') == 2
    assert text.count("call @chunk_attention_block") == 5
    assert "5x1x32768x4x128xbf16" in text
    for scores in ("1024x32768xf32", "32x1024x32768", "4x8x1024x32768",
                   "4x8x1024x1024xf32"):
        assert scores not in text, scores


def _compile_for(fn, args, donate=(2,)):
    """``fn`` compiled for the described chip, its third argument donated
    (or ``donate``), with the persistent cache off (such a compile cannot be
    read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with lowering_target("tpu"):
            return jax.jit(fn, donate_argnums=donate).trace(*args) \
                .lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        cc.reset_cache()


def _assert_only_passed_along(text, big):
    """No instruction of a compiled program's ``text`` that makes one of
    the shapes ``big`` copies, slices or gathers it."""
    for line in text.splitlines():
        if " = " not in line:
            continue
        made = line.split(" = ")[1]
        head = made.split("(")[0].split()
        if len(head) < 2:
            continue
        shape, op = head[0].split("{")[0], head[-1]
        if shape in big:
            assert op.startswith(("parameter", "get-tuple-element", "fusion",
                                  "scatter", "bitcast", "while", "tuple")) \
                and "copy" not in op, line[:200]


TRINITY_EXPERTS = ("bf16[512,2048,2048]", "bf16[512,1024,2048]",
                   "bf16[128,2048,2048]", "bf16[128,1024,2048]",
                   "bf16[4,128,2048,2048]", "bf16[4,128,1024,2048]")
KANANA_EXPERTS = ("bf16[768,2048,1536]", "bf16[768,768,2048]",
                  "bf16[128,2048,1536]", "bf16[128,768,2048]",
                  "bf16[6,128,2048,1536]", "bf16[6,128,768,2048]")


def test_compiled_window_decode_copies_no_experts_and_keeps_its_pools(v5e):
    """The same decode program COMPILED for a v5e: both groups' pools are
    aliased to the output, the temporaries are tens of MB (a layer's 128
    experts are 1.6 GB, the full group's view would be 4.3 GB), no
    instruction copies, slices or gathers an expert stack or a pool leaf,
    and the device keeps a token's row in the lanes in both groups."""
    decode, d_args, _, _ = _trinity_cell_programs(sharding=v5e)
    compiled = _compile_for(decode, d_args)
    mem = compiled.memory_analysis()
    pools = 2 * (5121 + 4 * 545) * 128 * 512 * 2
    assert mem.alias_size_in_bytes >= pools
    assert mem.temp_size_in_bytes < 256 << 20, mem.temp_size_in_bytes
    text = compiled.as_text()
    assert text.count("paged_flash_decode") >= 5
    _assert_only_passed_along(text, TRINITY_EXPERTS + (
        "bf16[1,5121,128,512]", "bf16[4,545,128,512]",
        "bf16[5121,128,512]", "bf16[545,128,512]"))
    for name in ("k", "v", "wk", "wv"):
        fmt = compiled.input_formats[0][2][name]
        assert tuple(fmt.layout.major_to_minor) == (0, 1, 2, 3)


@pytest.mark.parametrize("cell", ["trinity", "kanana"])
def test_compiled_chunk_reads_its_experts_in_place(v5e, cell):
    """The 1024-token chunk program (``jit_suffix_routed``) COMPILED for a
    v5e at a cell's widths and depth: the grouped products are the kernel's
    calls, two an expert layer as the program holds it (trinity writes a
    period's four out, kanana scans one body over its six), handed the stack
    as L x E groups (a bitcast of the parameter); no instruction copies, slices or gathers a layer's
    experts (1.6 GB in trinity, 1.2 GB in kanana) or the stack, and the
    temporaries stay what they were with ``ragged_dot`` (249 MB in trinity,
    343 MB in kanana: the parent's, compiled the same way)."""
    if cell == "trinity":
        experts, n_calls, stack = TRINITY_EXPERTS, 8, "bf16[512,2048,2048]"
        temp_limit = 256 << 20
    else:
        experts, n_calls, stack = KANANA_EXPERTS, 2, "bf16[768,2048,1536]"
        temp_limit = 336 << 20
    compiled = _compiled_chunk(v5e, cell)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < temp_limit, mem.temp_size_in_bytes
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "chunk_attention" not in line]
    assert len(calls) == n_calls and "ragged-dot" not in text
    assert all("moe_grouped_matmul/grouped_matmul" in c for c in calls)
    assert any(stack in c for c in calls)       # the whole stack goes in
    _assert_only_passed_along(text, experts)


_COMPILED_CHUNKS = {}


def _compiled_chunk(v5e, cell):
    """The 1024-token chunk program of a cell compiled for a v5e, once a
    process (two tests read it)."""
    if cell not in _COMPILED_CHUNKS:
        if cell == "trinity":
            _, _, chunk, c_args = _trinity_cell_programs(sharding=v5e)
        else:
            _, _, chunk, c_args = _kanana_programs(7, 2561, sharding=v5e)
        _COMPILED_CHUNKS[cell] = _compile_for(chunk, c_args)
    return _COMPILED_CHUNKS[cell]


@pytest.mark.parametrize("cell,scopes,score_blocks", [
    ("trinity", {"window_chunk_attn": 4, "full_chunk_attn": 1},
     ("f32[4,8,1024,1024]", "f32[1,4,8,1024,1024]")),
    ("kanana", {"latent_attn_expanded": 2},
     ("f32[32,1024,2048]", "f32[1,32,1024,2048]")),
])
def test_compiled_chunk_keeps_its_scores_in_vmem(v5e, cell, scopes,
                                                 score_blocks):
    """The same chunk programs: every layer's key blocks are folded by the
    chunk kernel, one call under the layer's attention scope (the scope the
    runners map the device trace to), and no instruction makes a float32
    score block ``[.., q_len, blk]`` (XLA's body wrote 268 MB of them a block
    a layer in kanana2, 134 MB in trinity)."""
    text = _compiled_chunk(v5e, cell).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "chunk_attention" in line]
    assert len(calls) == sum(scopes.values())
    for scope, n in scopes.items():
        assert sum(f"/{scope}/" in c for c in calls) == n, scope
    for line in text.splitlines():
        if " = " in line:
            shape = line.split(" = ")[1].split()[0].split("{")[0]
            assert shape not in score_blocks, line[:200]


@pytest.mark.parametrize("groups,rep,dk,window,blk", [
    (32, 1, 192, 0, 2048),      # kanana2
    (4, 8, 128, 2048, 1024),    # trinity, window layers
    (4, 8, 128, 0, 1024),       # trinity, full layers
    (8, 8, 192, 128, 1024),     # mimo, window layers (key tiles of 256)
    (4, 16, 192, 0, 1024),      # mimo, full layers
    (2, 16, 128, 0, 1024),      # nemotron, attention layers
], ids=["kanana", "trinity-window", "trinity-full", "mimo-window",
        "mimo-full", "nemotron-full"])
def test_chunk_kernel_compiles_at_each_configurations_shapes(
        v5e, groups, rep, dk, window, blk):
    """The chunk kernel alone, one 1024-token chunk against one key block,
    COMPILED for a v5e at each served configuration's group shapes (V heads
    of 128): Mosaic takes the tiles, and the carry is updated in place (both
    of its arrays aliased)."""
    from deepspeed_tpu.ops.pallas.chunk_attention import (
        chunk_attention_block, STAT_LANES)

    rows = 1024 * rep
    sds = lambda shape, dt: SDS(shape, dt, sharding=v5e)

    def fold(q, k, v, stat, acc, start):
        return chunk_attention_block(q, k, v, (stat, acc), start + 100,
                                     start, start, rep=rep, scale=0.1,
                                     window=window)

    compiled = _compile_for(fold, (
        sds((1, groups, rows, dk), jnp.bfloat16),
        sds((1, groups, blk, dk), jnp.bfloat16),
        sds((1, groups, blk, 128), jnp.bfloat16),
        sds((1, groups, rows, STAT_LANES), jnp.float32),
        sds((1, groups, rows, 128), jnp.float32), sds((), jnp.int32)),
        donate=(3, 4))
    assert compiled.memory_analysis().alias_size_in_bytes \
        == 2 * groups * rows * 128 * 4


def test_ssm_state_update_compiles_in_place_at_the_published_widths(v5e):
    """The decode step's Mamba-2 recurrence kernel COMPILED for a v5e at the
    nemotron cell's widths: the stack of five layers' states, [5, 128
    slots, 128 heads, 64, 128] float32 (2.7 GB), donated and aliased to the
    output while one layer is written, one Mosaic call, and no temporary the
    size of a layer's states (537 MB: XLA's form keeps one and reads the
    states twice)."""
    from deepspeed_tpu.ops.pallas.ssm_state_update import ssm_state_update

    sds = lambda shape: SDS(shape, jnp.float32, sharding=v5e)
    states = (5, 128, 128, 64, 128)
    compiled = _compile_for(
        lambda st, da, dtx, b, c: ssm_state_update(st, 2, da, dtx, b, c),
        (sds(states), sds((128, 128)), sds((128, 128, 64)),
         sds((128, 8, 128)), sds((128, 8, 128))), donate=(0,))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == int(np.prod(states)) * 4
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') == 1


def test_ssd_chunk_compiles_at_1024_tokens(v5e):
    """A prefill chunk's Mamba-2 mixer at the published widths (the input
    projection, the conv over the slot's tail, the chunked scan over 1024
    tokens in blocks of 128 from the slot's state, the gated norm, the
    output projection) COMPILED for a v5e: the scan is XLA's einsums (no
    Mosaic call), and the temporaries stay under 512 MB (the in-block
    decays, [8 blocks, 8 groups, 16 heads, 128, 128] float32, are 67
    MB)."""
    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.models.layers import Param

    cfg = get_model("nemotron_h", "3-super", n_layers=1,
                    hybrid_pattern="M").config
    params = jax.tree_util.tree_map(
        lambda a: SDS(a.shape, jnp.bfloat16, sharding=v5e),
        jax.eval_shape(lambda r: jax.tree_util.tree_map(
            lambda p: p.value, hybrid.mamba_init(r, cfg, 0.02),
            is_leaf=lambda x: isinstance(x, Param)), jax.random.PRNGKey(0)))
    sds = lambda shape, dt: SDS(shape, dt, sharding=v5e)
    compiled = _compile_for(
        lambda p, u, ssm, tail, n: hybrid.mamba_chunk(cfg, p, u, ssm, tail,
                                                      n),
        (params, sds((1, 1024, 4096), jnp.bfloat16),
         sds((1, 128, 64, 128), jnp.float32),
         sds((1, 3, 10240), jnp.bfloat16), sds((), jnp.int32)),
        donate=(2, 3))
    assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "ssm_chunk_scan" in text and "ssm_conv" in text


def test_compiler_verdict_carries_the_compilers_words():
    """The blocking the paged kernel shipped with — one kv head of many per
    block — is what Mosaic refuses; the verdict hands back its sentence."""
    from jax.experimental import pallas as pl

    def one_head_block(pool):
        return pl.pallas_call(
            lambda i, o: o.__setitem__(..., i[...]),
            grid=(pool.shape[0], pool.shape[2]),
            in_specs=[pl.BlockSpec((1, 16, 1, 128),
                                   lambda b, g: (b, 0, g, 0))],
            out_specs=pl.BlockSpec((1, 16, 1, 128),
                                   lambda b, g: (b, 0, g, 0)),
            out_shape=pool)(pool)

    ok, reason = compiler_verdict(one_head_block,
                                  SDS((9, 16, 16, 128), jnp.bfloat16))
    assert not ok and "divisible by 8 and 128" in reason, reason
    assert compiler_verdict(one_head_block,
                            SDS((9, 16, 1, 128), jnp.bfloat16)) == (True, "")


# ---------------------------------------------------------------------------
# more than one device: a Mosaic call must sit inside a shard_map
# ---------------------------------------------------------------------------

def test_bare_pallas_call_on_a_mesh_is_refused(devices8):
    """The failure this file exists for: a Pallas call under GSPMD over >1
    device cannot be lowered for the TPU at all."""
    from deepspeed_tpu.ops.pallas.flash_attention import \
        pallas_flash_attention

    mesh = build_mesh(MeshConfig(data=4), devices=devices8[:4])
    q = SDS((4, 1024, 16, 64), jnp.bfloat16,
            sharding=NamedSharding(mesh, P("data")))
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        lower_for_tpu(lambda q: pallas_flash_attention(q, q, q), q)


@pytest.mark.parametrize("impl,extra", [
    ("flash", {}), ("jax_flash", {}),
    ("block_sparse", {"sparse_pattern": "bslongformer", "sparse_block": 128}),
    ("flash", {"fused_ce_impl": "pallas"}),
])
def test_training_loss_lowers_on_a_four_device_mesh(devices8, impl, extra):
    """The attention block (and the Pallas CE) under ZeRO-3-style data
    parallelism over 4 devices: every kernel a config value can reach runs
    inside a shard_map over the mesh."""
    from deepspeed_tpu.models.layers import split_params_axes

    mesh = build_mesh(MeshConfig(data=4), devices=devices8[:4])
    model = get_model("gpt2", "medium", n_layers=2, vocab_size=50304,
                      attention_impl=impl, remat=True,
                      remat_policy="minimal", **extra)
    model.config.mesh = mesh
    params, _ = split_params_axes(jax.eval_shape(
        model.init, jax.random.PRNGKey(0)))
    batch = {"input_ids": SDS((8, 1024), jnp.int32,
                              sharding=NamedSharding(mesh, P("data")))}
    text = lower_for_tpu(
        jax.grad(lambda p, b: model.loss(p, b)), params, batch)
    assert n_mosaic(text) >= 3 + (extra.get("fused_ce_impl") == "pallas")
    assert "shard_map" in text or "sdy.manual_computation" in text


def _tp4_engine(devices, **serving):
    import deepspeed_tpu

    mesh = build_mesh(MeshConfig(model=4), devices=devices[:4])
    model = get_model("opt", "1.3b", n_layers=2, vocab_size=512,
                      attention_interpret=False)
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    return InferenceEngine(model, DeepSpeedInferenceConfig.from_dict(
        {"dtype": "bfloat16", "max_tokens": 256,
         "tensor_parallel": {"tp_size": 4},
         "serving": {"n_slots": 4, "kv_pool": {"block_size": 16,
                                               **serving}}}),
        mesh=mesh)


def test_tp4_flash_prefill_lowers(devices8):
    """The default serving configuration on a 4-chip host: prefill_flash
    turns itself on for a TPU target, and the 128-aligned bucket's kernel
    must lower with 8 of OPT-1.3B's 32 heads a chip."""
    eng = _tp4_engine(devices8)
    try:
        with lowering_target("tpu"):
            text = eng.serving._prefill_program(128).trace(
                eng.params, jnp.zeros((1, 128), jnp.int32), np.int32(128)
            ).lower(lowering_platforms=("tpu",)).as_text()
        assert n_mosaic(text) == 1
    finally:
        eng.destroy()


def test_tp4_kernel_decode_lowers(devices8):
    """TP=4: the kernel is probed at the engine's per-chip geometry (8 of 32
    heads, a 512-wide share of the pool's merged axis), chosen, and its
    decode program lowers for the TPU inside a shard_map; a configuration
    written before PR 31 (``enabled``, ``attention_backend``) still loads,
    warns once a key, and changes nothing of it."""
    from .conftest import STALE_KV_KEYS, unknown_key_warnings

    with lowering_target("tpu"):
        with unknown_key_warnings() as seen:
            eng = _tp4_engine(devices8, enabled=True,
                              attention_backend="gather")
        assert sorted(seen) == STALE_KV_KEYS
        try:
            sv = eng.serving
            assert (sv.attn_backend, sv.attn_reason) == ("kernel", "")
            assert sv._state["k"].shape == (2, 65, 16, 2048)
            assert sv._state["k"].sharding.spec == P(None, None, None,
                                                     "model")
            sv._build_pool_programs()
            text = sv._decode_jit.trace(eng.params, sv._state).lower(
                lowering_platforms=("tpu",)).as_text()
            assert n_mosaic(text) == 1
            assert "shard_map" in text or "sdy.manual_computation" in text
        finally:
            eng.destroy()


def test_kernel_lowers_on_a_four_device_model_mesh(devices8):
    """The kernel alone at the serve cell's geometry with the pool's merged
    axis (contiguous groups of kv heads) and the query heads split over
    ``model`` = 4: per shard in a shard_map, never under GSPMD."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    mesh = build_mesh(MeshConfig(model=4), devices=devices8[:4])
    q, kn, vn, kc, vc, table, pos, layer = _paged_decode_operands(32, 32, 64)
    on = lambda a, *spec: SDS(a.shape, a.dtype,
                              sharding=NamedSharding(mesh, P(*spec)))
    text = lower_for_tpu(
        lambda *a: paged_flash_decode(*a[:7], layer=a[7], mesh=mesh),
        on(q, None, "model"), on(kn, None, "model"), on(vn, None, "model"),
        on(kc, None, None, None, "model"), on(vc, None, None, None, "model"),
        table, pos, layer)
    assert n_mosaic(text) == 1
    assert "shard_map" in text or "sdy.manual_computation" in text
    assert "24x1537x16x512xbf16" in text       # a shard's share of the pool


def test_tp4_kv_block_write_lowers(devices8):
    """The pool split over ``model`` on its kv heads: the kernel runs per
    shard, 8 of 32 heads a chip (compiled for v5e 2x2 the program holds no
    collective: PERF.md, PR 27)."""
    from deepspeed_tpu.models.decoding import insert_block_kv

    mesh = build_mesh(MeshConfig(model=4), devices=devices8[:4])
    heads = NamedSharding(mesh, P(None, None, None, "model"))
    text = lower_for_tpu(
        lambda p, c, i, s: insert_block_kv(p, c, i, s, 16, lanes=True,
                                           mesh=mesh),
        *_block_write_operands(False, sharding=heads))
    assert n_mosaic(text) == 2


def test_quantized_matmul_refused_up_front_on_a_mesh(devices8):
    """A Mosaic call has no partitioning rule: on more than one device the
    inference engine switches the Pallas dequant-matmul off at construction
    and says why."""
    import deepspeed_tpu
    from deepspeed_tpu.models import layers as L

    model = get_model("opt", "125m", n_layers=2, vocab_size=512)
    prev = L._QMM_MODE
    try:
        eng = deepspeed_tpu.init_inference(
            model, dtype="bfloat16", max_tokens=64,
            quant={"enabled": True, "bits": 8})
        assert eng.mesh.size > 1 and L._QMM_MODE == "off"
        eng.destroy()
    finally:
        L._QMM_MODE = prev


# ---------------------------------------------------------------------------
# the contracts around the chip
# ---------------------------------------------------------------------------

def test_chip_smoke_refuses_cpu_before_compiling():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""            # no result of any kind
    assert "not 'tpu'" in proc.stderr


def test_bench_refuses_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""


def test_compile_cache_helper(monkeypatch, tmp_path):
    from deepspeed_tpu.utils import compile_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        assert cc.setup_compile_cache() == str(tmp_path / "x")
        assert jax.config.jax_compilation_cache_dir == before   # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.setup_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_unknown_device_kind_has_no_peaks():
    from deepspeed_tpu.accelerator.peaks import device_peaks

    assert device_peaks("TPU v5 lite").bf16_tflops == 197.0
    assert device_peaks("TPU v5 lite").hbm_gbs == 819.0
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(KeyError, match="no published peak"):
            device_peaks(kind)
