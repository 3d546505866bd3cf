"""Split-KV paged flash-decode Pallas kernel: decode attention that walks the
block table IN-KERNEL.

The serving decode hot path is 1 query row per slot against a long KV window
stored as a paged pool (``serving/kv_pool.py``: ``[n_blocks, block_size, kvh,
dh]`` physical blocks + a per-slot block table). The gather path
(``models/decoding.py:_paged_view``) materializes a dense per-slot view of
that pool per layer — correct and compile-once, but pure transient HBM
traffic: every decode step writes (and immediately re-reads) an
``[S, NB*bs, kvh, dh]`` tensor whose only purpose is to look like the dense
cache. This kernel deletes that view: the block-table indirection happens in
the BlockSpec index map (scalar-prefetched table + cursors, so the DMA
engine chases ``table[s, j]`` directly), and the online-softmax inner loop
masks each slot's ragged cursor in-register — DeepSpeed-Inference's fused
decode attention play (arXiv:2207.00032), TPU-native.

Shape/structure notes (the TPU way, same idioms as
``ops/pallas/flash_attention.py``):

- grid = (slots, kv_splits, blocks_per_split). A grid cell streams one
  physical block WHOLE — all its kv heads, ``[bs, kvh, dh]`` — because
  Mosaic only accepts a block whose last two dims are the array's own (or
  8/128-aligned): one head of many, ``(1, bs, 1, dh)``, is refused. GQA
  still costs nothing: the ``n_heads // kv_heads`` query rows of a group
  each read the same resident tile.
- split-KV: each of the ``kv_splits`` grid cells owns a contiguous run of
  table columns and produces a PARTIAL (max, sum, accumulator) triple; the
  partials combine outside the kernel (a tiny ``[S, kvh, splits, hq]``
  fp32 reduction) — the FlashDecoding shape, so long contexts parallelize
  across the split grid instead of serializing one slot's whole window.
- the freshly-projected k/v row of the CURRENT token never touches the
  pool before attention: it folds into the softmax during the combine, in
  compute dtype — exactly the value the gather path attends (the fresh row
  is written to the view pre-attention there), so int8 pools see the same
  unquantized current row on both paths and the writeback stays where it
  was.
- per-slot cursor masks: a slot's valid pool window is positions
  ``[0, pos)`` (ragged mid-block cursors included); blocks wholly past the
  cursor are compute-skipped (``pl.when``) and their DMA lands on whatever
  block id the table holds there — freed/unbound columns hold the reserved
  GARBAGE block, so the fetch is always in-range and its values are never
  read into the softmax.
- int8 pools dequantize IN-KERNEL: the int8 payload block and its
  per-(token, head) fp32 scale stream to VMEM natively and the
  ``payload.astype(f32) * scale`` happens on the tile — elementwise ops
  identical to ``comm/collectives.py:dequantize_blockwise``, so the fused
  path reads bit-identical dequantized values, at half the pool HBM
  traffic of gathering an already-dequantized view.

Tier-1 runs this kernel under ``interpret=True`` on CPU (the models'
``attention_interpret``, the same switch as the flash kernels' interpret
tests), so correctness — ragged cursors, GQA, alibi, int8, garbage-block
exclusion — is pinned without chips.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _fit_splits(requested, n_columns):
    """Largest split count in [1, requested] dividing ``n_columns`` (the
    block-table width) — a non-dividing request degrades, never crashes."""
    s = max(1, min(int(requested), n_columns))
    while n_columns % s:
        s -= 1
    return s


def fused_decode_supported(cfg, block_size, *, n_slots=8, blocks_per_slot=8,
                           tp=1, kv_dtype=""):
    """Capability probe for the fused backend: ``(ok, reason)``.

    Two structural refusals (what the kernel does not implement: banded
    local-attention layers, ragged GQA groups), then the question goes to
    the compiler: the kernel is lowered for the target platform at the
    engine's per-device geometry (``cfg`` heads / ``tp``, ``block_size``,
    pool dtype) — and compiled, when the backend is a TPU — and a refusal
    comes back as the compiler's own message. A hand-kept rule list here
    once approved a pool blocking Mosaic rejects; the probe must never
    approve a shape that then fails at first dispatch. With
    ``cfg.attention_interpret`` the Pallas interpreter runs the kernel and
    has no layout constraints.
    """
    from . import compiler_verdict, unavailable_reason

    if cfg.local_attention_window > 0:
        return False, ("local_attention_window > 0: banded layer masks are "
                       "not implemented in the fused kernel")
    if cfg.n_heads % cfg.kv_heads:
        return False, (f"n_heads {cfg.n_heads} not a multiple of kv_heads "
                       f"{cfg.kv_heads}")
    if cfg.attention_interpret:
        return True, ""
    reason = unavailable_reason()
    if reason is not None:
        return False, reason
    shard = tp if cfg.kv_heads % tp == 0 else 1
    kvh, nh, dh = cfg.kv_heads // shard, cfg.n_heads // shard, cfg.head_dim
    int8 = kv_dtype == "int8"
    sds = jax.ShapeDtypeStruct
    pool = sds((n_slots * blocks_per_slot + 1, block_size, kvh, dh),
               jnp.int8 if int8 else cfg.compute_dtype)
    scale = sds(pool.shape[:-1] + (1,), jnp.float32) if int8 else None
    row = sds((n_slots, kvh, dh), cfg.compute_dtype)
    slopes = jnp.ones((nh,), jnp.float32) \
        if cfg.position_embedding == "alibi" else None

    def call(q, k_new, v_new, kc, vc, table, pos, ks, vs):
        return paged_flash_decode(q, k_new, v_new, kc, vc, table, pos,
                                  k_scale=ks, v_scale=vs,
                                  scale=cfg.attn_scale, alibi_slopes=slopes)

    ok, reason = compiler_verdict(
        call, sds((n_slots, nh, dh), cfg.compute_dtype), row, row, pool, pool,
        sds((n_slots, blocks_per_slot), jnp.int32),
        sds((n_slots,), jnp.int32), scale, scale)
    return ok, reason and f"TPU compiler: {reason}"


def _decode_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, *rest, scale,
                   block_size, blocks_per_split, int8, alibi, stat_lanes):
    """One (slot, split, block) cell: stream one physical block — every kv
    head of it — fold it into the split's running (m, l, acc) triples, emit
    the partials at the split's last block. ``table_ref``/``pos_ref`` are
    the scalar-prefetched block table and cursors (the index maps already
    used them to aim the DMA; the body re-reads the cursor for the mask).

    Layout: the k/v tile is [bs, kvh, dh] with (kvh, dh) on the (sublane,
    lane) dims, so each of the ``hq`` query rows of a kv group is one
    [kvh, dh] tile and a block is consumed with broadcast-multiplies and
    reductions over the lane dim (q·k) and the leading dim (softmax sums,
    p·v) — VPU work with no relayout. One query row per slot leaves the MXU
    nothing to chew on, and the step is bound by streaming the pool."""
    idx = 0
    if int8:
        ks_ref, vs_ref = rest[idx], rest[idx + 1]
        idx += 2
    slopes_ref = None
    if alibi:
        slopes_ref = rest[idx]
        idx += 1
    o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr = rest[idx:]
    hq, kvh = q_ref.shape[1], q_ref.shape[2]

    s = pl.program_id(0)
    sp = pl.program_id(1)
    jb = pl.program_id(2)

    @pl.when(jb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[s]                       # valid pool window = [0, pos)
    base = (sp * blocks_per_split + jb) * block_size

    @pl.when(base < pos)
    def _step():
        # the allowlisted attention-f32 island (see sanitizer
        # ATTENTION_F32_ALLOW): logits, softmax and the PV accumulator run
        # fp32 on purpose — softmax numerics
        with jax.named_scope("paged_flash_decode"):
            k = k_ref[0]                   # [bs, kvh, dh]
            v = v_ref[0]
            if int8:
                # dequantize ON the tile — elementwise-identical to
                # dequantize_blockwise (f32 payload * per-(token,head)
                # scale, then the compute-dtype cast the gather view takes)
                k = (k.astype(jnp.float32) * ks_ref[0]).astype(q_ref.dtype)
                v = (v.astype(jnp.float32) * vs_ref[0]).astype(q_ref.dtype)
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
            row = jax.lax.broadcasted_iota(
                jnp.int32, (block_size, kvh, 1), 0)
            live = base + row < pos
            for j in range(hq):            # query rows of each kv group
                q = q_ref[0, j].astype(jnp.float32)            # [kvh, dh]
                sc = jnp.sum(k * q[None], axis=-1,
                             keepdims=True) * scale            # [bs, kvh, 1]
                if alibi:
                    # slopes * (kv_pos - cursor): the same int-difference-
                    # then-fp32-multiply as the gather path's per-row alibi
                    dist = (base + row - pos).astype(jnp.float32)
                    sc = sc + slopes_ref[j][None] * dist
                sc = jnp.where(live, sc, NEG_INF)
                m_prev = m_scr[j][:, :1]                       # [kvh, 1]
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=0))
                p = jnp.exp(sc - m_new[None])                  # [bs, kvh, 1]
                corr = jnp.exp(m_prev - m_new)
                l_scr[j] = l_scr[j] * corr + jnp.broadcast_to(
                    jnp.sum(p, axis=0), l_scr.shape[1:])
                acc_scr[j] = acc_scr[j] * corr + jnp.sum(p * v, axis=0)
                m_scr[j] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(jb == blocks_per_split - 1)
    def _emit():
        # partials, not normalized output: splits with no valid positions
        # emit (m=-inf, l=0, acc=0) and drop out of the combine exactly
        o_ref[0, 0] = acc_scr[...]
        m_ref[0, 0] = m_scr[...][..., :stat_lanes]
        l_ref[0, 0] = l_scr[...][..., :stat_lanes]


def paged_flash_decode(q, k_new, v_new, kc, vc, table, pos, *, k_scale=None,
                       v_scale=None, scale=None, alibi_slopes=None,
                       kv_splits=4, interpret=False, mesh=None):
    """Fused paged decode attention: softmax(q·K/√d)·V for ONE query row per
    slot, where K/V live in the paged pool and the kernel walks the block
    table itself.

    - ``q``: [S, n_heads, dh] (compute dtype) — this step's query rows;
    - ``k_new``/``v_new``: [S, kvh, dh] — the freshly-projected k/v of the
      current token (NOT yet in the pool; logically at position ``pos[s]``,
      folded into the softmax in compute dtype during the combine);
    - ``kc``/``vc``: [n_blocks, block_size, kvh, dh] — one layer of the
      pool (int8 payloads when ``k_scale``/``v_scale`` [n_blocks, bs, kvh,
      1] f32 are given: dequantized in-kernel);
    - ``table``: [S, NB] int32 physical block ids (scalar-prefetched: the
      index map reads it to aim each block DMA — no dense view exists);
    - ``pos``: [S] int32 cursors; pool positions [0, pos) are attended,
      everything past the cursor (ragged mid-block tails, unbound
      garbage-block columns) is masked/skipped;
    - ``interpret``: run under the Pallas interpreter (the models'
      ``attention_interpret``; CPU tests);
    - ``mesh``: the mesh the decode program is partitioned over. On more
      than one device the kernel runs inside a ``shard_map`` with the kv
      heads (and their query groups) split over ``model`` when they
      divide it — GSPMD cannot partition a Mosaic call.

    Returns [S, n_heads, dh] in ``q.dtype``.
    """
    from . import shard_kernel

    # heads split over `model` only when the KV heads divide it (the pool's
    # own sharding rule) — q's heads must follow their kv group
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    head_axes = ("model",) if kc.shape[2] % tp == 0 else ()
    at = lambda dim: {dim: head_axes}
    slopes = None if alibi_slopes is None \
        else jnp.asarray(alibi_slopes, jnp.float32)
    int8, alibi = k_scale is not None, slopes is not None
    operands = [q, k_new, v_new, kc, vc, table, pos]
    dim_axes = [at(1), at(1), at(1), at(2), at(2), {}, {}]
    if int8:
        operands += [k_scale, v_scale]
        dim_axes += [at(2), at(2)]
    if alibi:
        operands.append(slopes)
        dim_axes.append(at(0))

    def per_shard(q, k_new, v_new, kc, vc, table, pos, *rest):
        ks, vs = rest[:2] if int8 else (None, None)
        return _paged_flash_decode(
            q, k_new, v_new, kc, vc, table, pos, ks, vs, scale,
            rest[-1] if alibi else None, kv_splits, interpret)

    return shard_kernel(per_shard, mesh, operands, dim_axes, [at(1)])


def _paged_flash_decode(q, k_new, v_new, kc, vc, table, pos, k_scale,
                        v_scale, scale, alibi_slopes, kv_splits, interpret):
    """One device's share of ``paged_flash_decode`` (local head counts)."""
    s_dim, n_heads, dh = q.shape
    n_blocks, block_size, kvh, _ = kc.shape
    nb_cols = table.shape[1]
    hq = n_heads // kvh
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    int8 = k_scale is not None
    alibi = alibi_slopes is not None

    splits = _fit_splits(kv_splits, nb_cols)
    bps = nb_cols // splits
    grid = (s_dim, splits, bps)
    # head h = g * hq + j (kv group g, row j): kernel layout [S, hq, kvh, dh]
    # puts (kvh, dh) on the tile dims next to the pool block's
    qt = q.reshape(s_dim, kvh, hq, dh).transpose(0, 2, 1, 3)

    def kv_index(s, sp, jb, table_ref, pos_ref):
        # THE point of the kernel: the block-table indirection lives here.
        # Unbound columns hold the reserved garbage block — always a valid
        # pool row, compute-skipped in the body. A block is fetched whole
        # (all kv heads): Mosaic wants a block's last two dims to be the
        # array's own (or 8/128-aligned), which one head of many is not.
        return (table_ref[s, sp * bps + jb], 0, 0, 0)

    def q_index(s, sp, jb, table_ref, pos_ref):
        return (s, 0, 0, 0)

    def out_index(s, sp, jb, table_ref, pos_ref):
        return (s, sp, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, hq, kvh, dh), q_index),
        pl.BlockSpec((1, block_size, kvh, dh), kv_index),
        pl.BlockSpec((1, block_size, kvh, dh), kv_index),
    ]
    operands = [qt, kc, vc]
    if int8:
        in_specs += [pl.BlockSpec((1, block_size, kvh, 1), kv_index),
                     pl.BlockSpec((1, block_size, kvh, 1), kv_index)]
        operands += [k_scale, v_scale]
    if alibi:
        in_specs.append(pl.BlockSpec(
            (hq, kvh, 1), lambda s, sp, jb, t, p: (0, 0, 0)))
        operands.append(alibi_slopes.reshape(kvh, hq).T[..., None])

    # the m/l partials keep a LANES-broadcast minor dim in scratch (TPU vreg
    # layout; see flash_attention.py). Interpret mode emits a single lane to
    # HBM; a real TPU emits the full broadcast — a 1-lane minor output dim
    # is a layout Mosaic tiling commonly rejects
    stat_lanes = 1 if interpret else LANES
    out_shape = [
        jax.ShapeDtypeStruct((s_dim, splits, hq, kvh, dh), jnp.float32),
        jax.ShapeDtypeStruct((s_dim, splits, hq, kvh, stat_lanes),
                             jnp.float32),
        jax.ShapeDtypeStruct((s_dim, splits, hq, kvh, stat_lanes),
                             jnp.float32),
    ]
    out_specs = [
        pl.BlockSpec((1, 1, hq, kvh, dh), out_index),
        pl.BlockSpec((1, 1, hq, kvh, stat_lanes), out_index),
        pl.BlockSpec((1, 1, hq, kvh, stat_lanes), out_index),
    ]

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_size=block_size,
        blocks_per_split=bps, int8=int8, alibi=alibi, stat_lanes=stat_lanes)
    acc, m_p, l_p = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((hq, kvh, LANES), jnp.float32),
                pltpu.VMEM((hq, kvh, LANES), jnp.float32),
                pltpu.VMEM((hq, kvh, dh), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table, pos, *operands)

    # -- combine across the split-KV grid (tiny fp32 reduction) ------------
    m_p = m_p[..., 0]                                    # [S, sp, hq, kvh]
    l_p = l_p[..., 0]
    m_c = jnp.max(m_p, axis=1)                           # [S, hq, kvh]
    w = jnp.exp(m_p - m_c[:, None])                      # empty splits -> 0
    l_c = jnp.sum(l_p * w, axis=1)
    acc_c = jnp.sum(acc * w[..., None], axis=1)          # [S, hq, kvh, dh]

    # -- fold the CURRENT token's fresh k/v row (compute dtype, position
    # pos — the row the gather path writes into the view pre-attention;
    # alibi distance is 0 there). Elementwise mul+sum, not a dot: this is
    # [S, hq, kvh] of work, VPU noise.
    s_new = jnp.sum(qt.astype(jnp.float32)
                    * k_new.astype(jnp.float32)[:, None],
                    axis=-1) * scale                     # [S, hq, kvh]
    m_t = jnp.maximum(m_c, s_new)
    corr = jnp.exp(m_c - m_t)
    w_new = jnp.exp(s_new - m_t)
    l_t = l_c * corr + w_new
    acc_t = acc_c * corr[..., None] \
        + w_new[..., None] * v_new.astype(jnp.float32)[:, None]
    out = acc_t / jnp.maximum(l_t, 1e-30)[..., None]     # [S, hq, kvh, dh]
    return out.transpose(0, 2, 1, 3).reshape(s_dim, n_heads, dh) \
        .astype(q.dtype)
