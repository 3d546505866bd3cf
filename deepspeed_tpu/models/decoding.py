"""KV-cache decoding path for the transformer backbone.

TPU-native equivalent of the reference's inference kernels
(``csrc/transformer/inference/csrc/`` — fused "softmax_context" attention with
KV-cache, ``apply_rotary_pos_emb.cu``) and the ``DeepSpeedTransformerInference``
module (``model_implementations/transformers/ds_transformer.py:19``). The CUDA
version hand-manages a contiguous KV workspace; here the cache is a pytree of
``[layers, batch, max_len, kv_heads, head_dim]`` arrays updated with
``dynamic_update_slice`` inside a jitted decode step — XLA keeps the update
in-place through buffer donation.

Kept separate from the training path (``transformer.block_apply``) like the
reference keeps training vs inference kernels separate; a parity test pins
prefill logits == training-forward logits.
"""

import functools

import jax
import jax.numpy as jnp

from . import layers as L
from .transformer import _norm_apply


def init_cache(cfg, batch_size, max_len, dtype=None):
    """Allocate the KV cache: k/v stacked over layers (matches the stacked block
    params, so layer scan indexes both together)."""
    if cfg.hybrid_layers:
        from . import hybrid

        return hybrid.init_cache(cfg, batch_size, max_len, dtype)
    dtype = dtype or cfg.compute_dtype
    return {name: jnp.zeros((cfg.n_layers, batch_size, max_len) + row, dtype)
            for name, row in cfg.cache_geometry.items()}


# ---------------------------------------------------------------------------
# paged (block) KV cache: fixed pool of token blocks + per-slot block table
# ---------------------------------------------------------------------------

def init_paged_cache(cfg, n_blocks, block_size, dtype=None, kv_dtype=None,
                     n_layers=None, geometry=None):
    """Allocate the paged KV pool: ``n_blocks`` physical blocks of
    ``block_size`` tokens each, stacked over layers (a physical block id
    addresses the same block row in EVERY layer, so host allocation is one
    decision per token block, not per layer).

    Leaves are ``[L, n_blocks, block_size] + cfg.pool_geometry[leaf]``: for
    per-head K and V that is ``[L, n_blocks, block_size, kv_heads *
    head_dim]``, a token's whole row minor-most, for every model whatever
    its head size. The TPU lays an array out by its shape: a leaf ending in
    ``[.., kv_heads, 64]`` got its BLOCK axis in the lanes (one block spread
    over the whole pool: an insert cost the pool, PR 27; a decode step
    shuffled it, PR 30); ending in the merged row it keeps a block of
    tokens contiguous (64 KB at OPT-1.3B's widths), which one DMA fetches
    and one scatter writes. ``serving.ServingEngine.pool_layouts()`` reads
    what the device chose.

    ``kv_dtype="int8"`` stores blocks as int8 payloads with per-(token, head)
    fp32 scales (``comm/collectives.py`` blockwise kernels, ZeRO++ idiom):
    k/v ``[.., kv_heads * head_dim]`` int8, k_scale/v_scale ``[..,
    kv_heads]`` f32.

    ``n_layers``: the layers of ONE block group, where a model keeps
    several (``models/window_moe.py``: the full layers' group and the window
    layers' ring, each with its own ``n_blocks``), and ``geometry`` that
    group's rows where they are not ``cfg.pool_geometry`` (``cfg.
    group_pool_geometry``: another K/V head count by kind, V rows narrower
    than K rows)."""
    dtype = dtype or cfg.compute_dtype
    shapes = {name: (n_layers or cfg.n_layers, n_blocks, block_size) + row
              for name, row in (geometry or cfg.pool_geometry).items()}
    if kv_dtype == "int8":
        pool = {name: jnp.zeros(s, jnp.int8) for name, s in shapes.items()}
        pool.update({name + "_scale": jnp.zeros(
            s[:-1] + (s[-1] // cfg.head_dim,), jnp.float32)
            for name, s in shapes.items()})
        return pool
    return {name: jnp.zeros(s, dtype) for name, s in shapes.items()}


def _dequant_rows(q, scale, dtype):
    """int8 payload ``[.., kvh * dh]`` + per-(token, head) scale ``[..,
    kvh]`` -> ``dtype``: ``dequantize_blockwise`` reads the block (one head
    vector) off the two shapes."""
    from ..comm.collectives import dequantize_blockwise

    return dequantize_blockwise(q, scale, dtype=dtype)


def _paged_view(pool, name, layer, table, heads, view_dtype):
    """Gather a slot-major dense view of one layer of the pool through the
    block table.

    ``pool[name]``: [L, n_blocks, bs, kvh * dh]; table: [S, NB] physical
    block ids; returns [S, NB * bs, kvh, dh]: row ``s`` holds slot s's KV
    window in position order (block j covers positions [j*bs, (j+1)*bs)),
    exactly the dense cache layout, so the attention math downstream is the
    SAME program as the dense per-row path."""
    g = pool[name][layer, table]                     # [S, NB, bs, kvh * dh]
    if name + "_scale" in pool:
        g = _dequant_rows(g, pool[name + "_scale"][layer, table], view_dtype)
    s_dim, per_slot, bs, width = g.shape
    return g.reshape(s_dim, per_slot * bs, heads, width // heads)


def _paged_write_rows(pool, layer, rows, table, pos, block_size, valid=None,
                      ring=False):
    """Write each slot's fresh rows ``rows`` (``{"k": [S, kvh, dh], "v":
    ..}``) into layer ``layer`` of the pool at (table[s, pos // bs], pos %
    bs): row-sized updates of the leaves, in place in the layer loop's
    carry. Freed slots carry an all-garbage-block table row, so their dead
    writes land in the reserved garbage block instead of corrupting a
    reallocated block. Returns the pool with the rows written.

    ``valid`` ([S] bool, optional): rows whose write must instead be
    redirected to the reserved garbage block 0 (speculative verify's padded
    draft rows: they can lie past the slot's bound blocks or the KV window,
    and a clamped block index would silently corrupt a REAL block).
    ``ring``: the table is a ring as wide as a window layer's band, block
    ``j`` at column ``j % n_cols`` (``models/window_moe.py``)."""
    j = (pos // block_size) % table.shape[1] if ring \
        else jnp.clip(pos // block_size, 0, table.shape[1] - 1)
    bi = jnp.take_along_axis(table, j[:, None], axis=1)[:, 0]
    if valid is not None:
        bi = jnp.where(valid, bi, 0)  # block 0 = the reserved garbage block
    off = pos % block_size
    pool = dict(pool)
    with jax.named_scope("paged_row_write"):
        for name, r in rows.items():
            flat = r.reshape(r.shape[0], -1)
            if name + "_scale" in pool:
                from ..comm.collectives import quantize_blockwise

                flat, scale = quantize_blockwise(flat, block=r.shape[-1])
                pool[name + "_scale"] = \
                    pool[name + "_scale"].at[layer, bi, off].set(scale)
            pool[name] = pool[name].at[layer, bi, off].set(
                flat.astype(pool[name].dtype))
    return pool


def _project_qkv(cfg, p_attn, h, rope=None):
    """The q/k/v projection + rotary application shared by every cached
    attention path: ONE implementation, so the kernel path can never
    diverge from the view/dense path's projection semantics."""
    b, q_len, _ = h.shape
    q = L.linear_apply(p_attn["q"], h).reshape(b, q_len, cfg.n_heads,
                                               cfg.head_dim)
    k = L.linear_apply(p_attn["k"], h).reshape(b, q_len, cfg.kv_heads,
                                               cfg.head_dim)
    v = L.linear_apply(p_attn["v"], h).reshape(b, q_len, cfg.kv_heads,
                                               cfg.head_dim)
    if rope is not None:
        cos, sin = rope
        q = L.apply_rotary(q, cos, sin, cfg.rotary_dim,
                           cfg.rotary_interleaved)
        k = L.apply_rotary(k, cos, sin, cfg.rotary_dim,
                           cfg.rotary_interleaved)
    return q, k, v


def _attn_paged_kernel(cfg, p_attn, h, pool, layer, table, pos, rope=None):
    """The kernel-path twin of ``_attn_with_cache`` for paged decode
    (q_len == 1): project q/k/v for the current token, then attend straight
    against the POOL (both leaves whole, ``layer`` a scalar) through the
    flash-decode kernel, which walks the block table and reads the live
    blocks only. Returns ``(out [S, 1, d], k_row, v_row)`` with the fresh
    [S, kvh, dh] rows for the caller's row write (the kernel already folded
    them into the softmax in compute dtype, exactly the value the view path
    attends at the cursor)."""
    from ..ops.pallas.paged_attention import paged_flash_decode

    b, q_len, _ = h.shape
    q, k, v = _project_qkv(cfg, p_attn, h, rope=rope)
    slopes = L.alibi_slopes(cfg.n_heads) \
        if cfg.position_embedding == "alibi" else None
    out = paged_flash_decode(q[:, 0], k[:, 0], v[:, 0], pool["k"], pool["v"],
                             table, pos, layer=layer, scale=cfg.attn_scale,
                             alibi_slopes=slopes,
                             interpret=cfg.attention_interpret,
                             mesh=cfg.mesh)
    out = L.linear_apply(p_attn["o"], out.reshape(b, q_len, -1))
    return out, k[:, 0], v[:, 0]


def forward_with_paged_cache(model, params, input_ids, pool, table, pos,
                             block_size, draft_len=None, kernel=False,
                             return_routing=False):
    """One decode step ([S, 1] tokens) reading/writing KV through a TRACED
    block table: the paged twin of ``forward_with_cache``'s per-row decode.

    The pool (leaves ``[L, n_blocks, bs, kv_heads * head_dim]``, see
    ``init_paged_cache``) is the CARRY of the layer loop: a layer reads its
    share through the scalar layer index and writes each slot's new row by
    a row-sized update, so a donated pool is updated in place and no slice
    of a leaf is copied (scanned as ``xs`` the pool was sliced and re-joined,
    4.8 GB a step at OPT-1.3B's pool). Returns (logits [S, 1, vocab], new
    pool). Two ways to attend, and the CALLER'S choice between them is made
    from what it can observe, never by a user's option (``ServingEngine``
    asks ``fused_decode_supported`` once, at its geometry):

    - ``kernel=True``: ``ops/pallas/paged_attention.py`` walks the block
      table and reads the live blocks only. One query row a slot, no banded
      local layers, a pool in the engine's dtype.
    - the VIEW (default, and whatever the kernel does not take): per layer,
      gather the slot-major dense ``n_slots x max_len`` view through
      ``table`` (dequantizing int8 blocks), run the UNCHANGED dense per-row
      attention on it (``_block_cached``), then write each slot's new row
      back. Because the gathered view is bit-identical to the dense cache at
      every unmasked position and the math in between is the same program,
      greedy decode over the view is bitwise-equal to ``generate()`` over
      the dense ``[B, max_len]`` cache (tier-1 pins it).

    ``draft_len`` [S] switches the program into speculative VERIFY mode
    (see ``verify_with_paged_cache``): ``input_ids`` becomes [S, k+1]
    (the slot's last token + k draft candidates at per-slot cursors), all
    k+1 rows are written and all k+1 logit rows returned. Row i's write
    could ever become live only while ``i <= draft_len`` and the position
    is inside the KV window: padded rows compute garbage that the causal
    mask hides in-view and whose pool write redirects to the garbage
    block, and the in-view writes run in reverse row order so a
    window-clamped padded write can never shadow a real row.

    ``return_routing`` (expert models with ``moe_routing="dropfree"``): also
    return what the step's expert layers chose, [L_moe, S, 1, 2k] int32 (ids
    and the bits of their weights: ``moe/dropfree.py``).

    A model of window and full attention layers (``models/window_moe.py``)
    keeps two pool groups: ``pool`` then also holds ``wk`` / ``wv`` and
    ``table`` is the pair (full group's table, window group's ring)."""
    cfg = model.config
    if cfg.hybrid_layers:
        from . import hybrid

        if draft_len is not None or "k_scale" in pool:
            raise ValueError(
                "hybrid stacks decode one row a slot over a pool in the "
                "engine's dtype: speculative verify and an int8 pool are "
                "not implemented")
        logits, pool, ids = hybrid.forward_with_paged_cache(
            model, params, input_ids, pool, table, pos, block_size,
            kernel=kernel)
        return (logits, pool, ids) if return_routing else (logits, pool)
    if cfg.window_layers:
        from . import window_moe

        if draft_len is not None or "k_scale" in pool:
            raise ValueError(
                "window and full attention layers decode one row a slot "
                "over a pool in the engine's dtype: speculative verify and "
                "an int8 pool are not implemented")
        logits, pool, ids = window_moe.forward_with_paged_cache(
            model, params, input_ids, pool, table, pos, block_size,
            kernel=kernel)
        return (logits, pool, ids) if return_routing else (logits, pool)
    if cfg.latent_attention:
        from . import latent

        if draft_len is not None or "k_scale" in pool:
            raise ValueError(
                "latent attention decodes in the absorbed form over a pool "
                "in the engine's dtype: speculative verify and an int8 pool "
                "are not implemented")
        logits, pool, ids = latent.forward_with_paged_cache(
            model, params, input_ids, pool, table, pos, block_size,
            kernel=kernel)
        return (logits, pool, ids) if return_routing else (logits, pool)
    b, q_len = input_ids.shape
    if kernel and (draft_len is not None or q_len != 1):
        raise ValueError(
            "the decode kernel is decode-only (one query row per slot); "
            "speculative verify runs the view path")
    if kernel and (cfg.local_attention_window > 0 or "k_scale" in pool):
        raise ValueError(
            "the decode kernel implements neither banded local-attention "
            "masks nor an int8 pool (fused_decode_supported gates this)")
    positions = pos[:, None] + jnp.arange(q_len)[None, :]
    kv_len = table.shape[1] * block_size
    if draft_len is not None:
        valid = (jnp.arange(q_len)[None, :] <= draft_len[:, None]) \
            & (positions < kv_len)                    # [S, q]
        row_writes = "reverse"
    else:
        valid = None
        row_writes = "block"

    x = L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)
    if cfg.position_embedding == "learned":
        # jnp.take clamps out-of-range (padded-row) positions; those rows'
        # embeddings are garbage by design and masked/redirected above
        x = x + jnp.take(params["wpe"]["weight"].astype(cfg.compute_dtype),
                         positions, axis=0)
    rope = None
    if cfg.position_embedding == "rope":
        rope = L.rotary_embedding(positions, cfg.rotary_dim or cfg.head_dim,
                                  cfg.rope_base)

    def block_step(h, pool, p_i, layer, loc):
        if kernel:
            def attn_impl(p_attn, hh):
                return _attn_paged_kernel(cfg, p_attn, hh, pool, layer,
                                          table, pos, rope=rope)

            h, k_row, v_row = _block_cached(cfg, p_i, h, None, None, pos,
                                            kv_len, rope=rope,
                                            attn_impl=attn_impl)
            return h, _paged_write_rows(pool, layer,
                                        {"k": k_row, "v": v_row}, table, pos,
                                        block_size)
        kview = _paged_view(pool, "k", layer, table, cfg.kv_heads,
                            cfg.compute_dtype)
        vview = _paged_view(pool, "v", layer, table, cfg.kv_heads,
                            cfg.compute_dtype)
        h, kview, vview = _block_cached(cfg, p_i, h, kview, vview, pos,
                                        kv_len, rope=rope, is_local=loc,
                                        row_writes=row_writes)
        # the row each slot just wrote (at its cursor) goes from the view
        # back into the pool
        row_at = jax.vmap(lambda c, p: jax.lax.dynamic_slice(
            c, (p, 0, 0), (1,) + c.shape[1:])[0])
        for i in range(q_len):
            p_row = pos if i == 0 else pos + i
            pool = _paged_write_rows(
                pool, layer,
                {"k": row_at(kview, p_row), "v": row_at(vview, p_row)},
                table, p_row, block_size,
                valid=None if valid is None else valid[:, i])
        return h, pool

    xs = [params["blocks"], jnp.arange(cfg.n_layers)]
    if cfg.local_attention_window > 0:
        from .transformer import local_attention_flags

        xs.append(jnp.asarray(local_attention_flags(cfg)))

    def scan_fn(carry, layer_xs):
        p_i, layer, *loc = layer_xs
        return block_step(*carry, p_i, layer, loc[0] if loc else None), None

    (h, pool), _ = jax.lax.scan(scan_fn, (x, dict(pool)), tuple(xs))
    h = _norm_apply(cfg, params["ln_f"], h)
    if cfg.tie_embeddings:
        logits = L.embedding_attend(params["wte"], h)
    else:
        logits = L.linear_apply(params["lm_head"], h)
    return logits, pool


def verify_with_paged_cache(model, params, input_ids, pool, table, pos,
                            block_size, draft_len):
    """One speculative-decoding VERIFY step against the paged cache: feed
    ``input_ids`` [S, k+1] (each slot's last sampled token + its k draft
    candidates) at per-slot cursors ``pos``, write the candidate KV rows,
    and return ALL k+1 logit rows — the single target forward classic
    speculative decoding needs (arXiv:2211.17192). Row i's logits give the
    target's next-token distribution after consuming row i, so greedy
    acceptance is: take drafts while ``draft[i] == argmax(logits[:, i])``.

    This IS ``forward_with_paged_cache`` with ``draft_len`` set — the same
    gather/attention/writeback scaffold as the decode program, so the
    logits at every accepted position are bitwise what sequential decode
    would have produced there (the multi-position == sequential property
    the suffix-prefill/chunked paths already pin). Rejected candidates'
    rows stay in the pool PAST the rolled-back cursor — causally masked,
    overwritten before they could become visible; the serving engine
    additionally releases/scrubs fully-stale blocks at block granularity.

    Returns (logits [S, k+1, vocab], new pool)."""
    return forward_with_paged_cache(model, params, input_ids, pool, table,
                                    pos, block_size, draft_len=draft_len)


def write_pool_blocks(pool, src, block_ids, src_blocks, *, lanes=False,
                      mesh=None, interpret=False):
    """THE block writer: ``pool[name][:, block_ids[i]] = src[name][:,
    src_blocks[i]]`` for every leaf of ``pool`` and every i whose id lies in
    ``[0, n_blocks)``; an id outside it is padding and writes nothing, so a
    caller pads its ``[blocks_per_slot]`` arrays with DISTINCT ids from
    ``n_blocks`` up and one compiled program serves every request size.
    ``src`` leaves are ``[L, n_src, bs, *row]`` in the pool's own dtypes:
    bytes are moved, never recomputed. Whole blocks are overwritten, so
    nothing of a previous occupant survives; every other block keeps its
    bytes, and a donated pool is updated in place.

    ``lanes``: the device keeps the pool's block axis in the lanes
    (``ops/pallas/kv_block_write.blocks_in_lanes`` of the live pool). There
    one block is one lane of every tile row of the pool and a scatter over
    the block axis costs copies of the whole pool, so the column kernel
    runs, per shard of ``mesh``. Everywhere else the XLA scatter does."""
    from ..ops.pallas import kv_block_write as kw
    from ..ops.pallas import note_fallback, shard_kernel, unavailable_reason

    if lanes:
        tp = mesh.shape.get("model", 1) if mesh is not None else 1
        reason = unavailable_reason(interpret) or kw.unfit_reason(pool, tp)
        if reason is None:
            plan = kw.column_plan(block_ids, src_blocks, pool["k"].shape[1])
            kernel = functools.partial(kw.write_block_columns,
                                       interpret=interpret)
            heads = {3: ("model",)}  # kv heads, merged with dh or alone
            return {name: shard_kernel(
                kernel, mesh, (a, src[name].astype(a.dtype)) + plan,
                [heads, heads, {}, {}, {}], [heads])
                for name, a in pool.items()}
        note_fallback("kv_block_write", reason)
    return {name: a.at[:, block_ids].set(
        src[name][:, src_blocks].astype(a.dtype), mode="drop",
        unique_indices=True) for name, a in pool.items()}


def insert_block_kv(pool, dense_cache, block_ids, src_blocks, block_size,
                    **writer):
    """Copy token blocks ``src_blocks`` of a freshly-prefilled dense b=1
    cache ``[L, 1, max_len, kvh, dh]`` into physical blocks ``block_ids`` of
    the pool, each token's row reshaped to the pool's own (per-head K and V:
    merged, ``kvh * dh``), quantizing the cache ONCE (per (token, head), the
    pool's int8 layout) when the pool is int8. Both id arrays are TRACED and
    padded (``write_pool_blocks``): one compiled program covers every
    request."""
    src = {}
    for name in ("k", "v"):
        d = dense_cache[name]
        rows = d.reshape((d.shape[0], d.shape[2] // block_size, block_size)
                         + pool[name].shape[3:])
        if name + "_scale" in pool:
            from ..comm.collectives import quantize_blockwise

            rows, src[name + "_scale"] = quantize_blockwise(
                rows, block=d.shape[-1])
        src[name] = rows
    return write_pool_blocks(pool, src, block_ids, src_blocks, **writer)


def reset_block_kv(pool, block_id):
    """Zero physical block ``block_id`` (the hygiene scrub of
    ``scrub_freed_slots``; int8 scales zero too, so a dequantized read is
    exactly 0)."""
    out = {}
    for name, a in pool.items():
        z = jnp.zeros(a.shape[:1] + (1,) + a.shape[2:], a.dtype)
        out[name] = jax.lax.dynamic_update_slice(
            a, z, (0, block_id) + (0,) * (a.ndim - 2))
    return out


def gather_slot_cache(cfg, pool, table_row, dtype):
    """Materialize one slot's dense [L, 1, NB*bs, kvh, dh] cache view from
    its block-table row (dequantizing int8 blocks): seeds the suffix
    prefill on a shared-prefix hit: positions below the shared length hold
    the canonical prefix KV, everything above is garbage the suffix prefill
    overwrites or the causal mask hides."""
    out = {}
    for name, row in cfg.cache_geometry.items():
        g = pool[name][:, table_row]               # [L, NB, bs, *pool row]
        if name + "_scale" in pool:
            g = _dequant_rows(g, pool[name + "_scale"][:, table_row], dtype)
        out[name] = g.reshape((g.shape[0], 1, g.shape[1] * g.shape[2])
                              + row).astype(dtype)
    return out


def extract_slot_blocks(pool, table_row):
    """RAW gather of one slot's physical blocks for live migration: every
    pool leaf at its stored dtype — k/v payloads (int8 or dense) AND the
    int8 scales when present — stacked [L, NB, bs, kvh * dh | kvh] in
    table-row order. No dequantization: a dequant -> requant round trip reproduces
    the int8 payload but can perturb the recomputed scale in its last ulp,
    which would break the migrated-stream-is-bitwise contract. Padded
    table entries (GARBAGE_BLOCK) gather the garbage block; the writer
    (``write_pool_blocks``) ignores them via its own id padding."""
    return {name: a[:, table_row] for name, a in pool.items()}


def _attn_with_cache(cfg, p_attn, h, k_cache, v_cache, pos, kv_len, rope=None,
                     is_local=None, prefill=False, row_writes="block"):
    """Attention for q block [b, q, d] against cache[:, :kv_len] after writing the
    new k/v at ``pos``. Returns (out [b, q, d], new k_cache, new v_cache).

    k_cache/v_cache: [b, max_len, kvh, dh]; pos: scalar write offset, OR a
    per-row [b] vector (continuous-batching slot pools, where each co-batched
    request sits at its own cursor); kv_len: static upper bound on valid cache
    length (mask handles the rest).
    ``prefill``: static caller promise that pos == 0 and the q block IS the
    whole visible window — enables the flash fast path below (scalar pos only).
    ``row_writes`` (per-row pos only): "block" writes the whole q block with
    one update per row; "reverse" writes one position at a time, LAST
    position first — required when pos + q may legitimately overrun the
    window (speculative verify's padded draft rows): an overrunning write
    clamps onto the final row, and the reverse order guarantees the valid
    write at any clamp target lands last, so clamped garbage can never
    shadow a real row (the PR 7 overrun class, closed by ordering instead
    of a bucket cap because here the overrun is by design).
    """
    b, q_len, d = h.shape
    per_row = jnp.ndim(pos) == 1
    q, k, v = _project_qkv(cfg, p_attn, h, rope=rope)

    if per_row:
        # each row writes its q block at its OWN cursor (slot-pool decode);
        # vmapped dynamic_update_slice lowers to a per-row scatter
        row_update = jax.vmap(
            lambda c, blk, p: jax.lax.dynamic_update_slice(c, blk, (p, 0, 0)))
        if row_writes == "reverse":
            for i in reversed(range(q_len)):
                k_cache = row_update(k_cache,
                                     k[:, i:i + 1].astype(k_cache.dtype),
                                     pos + i)
                v_cache = row_update(v_cache,
                                     v[:, i:i + 1].astype(v_cache.dtype),
                                     pos + i)
        else:
            k_cache = row_update(k_cache, k.astype(k_cache.dtype), pos)
            v_cache = row_update(v_cache, v.astype(v_cache.dtype), pos)
    else:
        k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                               (0, pos, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                               (0, pos, 0, 0))

    # Prefill is plain causal attention over the just-written prompt rows:
    # cache slot j >= q_len is in the causal future of every query, so the
    # [q, max_len] window the dense path masks away never needs to exist.
    # Route it through the flash kernel so TTFT doesn't pay the O(s^2)
    # logits materialization — on the fresh k/v (cast through the cache
    # dtype to keep the dense path's numerics), repeated BEFORE any cache
    # read so no [b, max_len, heads, dh] tensor materializes. prefill_flash:
    # True/False force, None = on when kernels are lowered for a TPU. What
    # flash_attention then runs (Pallas kernel, or the XLA scan with a
    # logged reason for unaligned buckets / other platforms) is its call.
    flash_wanted = cfg.prefill_flash
    if flash_wanted is None:
        from ..ops.pallas import target_platform

        flash_wanted = target_platform() == "tpu"
    if (flash_wanted and prefill and not per_row and q_len > 1
            and is_local is None and cfg.position_embedding != "alibi"):
        from ..ops.flash_attention import flash_attention

        n_rep = cfg.n_heads // cfg.kv_heads
        out = flash_attention(q,
                              L._repeat_kv(k.astype(k_cache.dtype), n_rep),
                              L._repeat_kv(v.astype(v_cache.dtype), n_rep),
                              causal=True, scale=cfg.attn_scale,
                              block_q=cfg.flash_block_q,
                              block_kv=cfg.flash_block_kv,
                              interpret=cfg.attention_interpret,
                              mesh=cfg.mesh)
        out = L.linear_apply(p_attn["o"], out.reshape(b, q_len, -1))
        return out, k_cache, v_cache

    k_full = L._repeat_kv(k_cache[:, :kv_len], cfg.n_heads // cfg.kv_heads)
    v_full = L._repeat_kv(v_cache[:, :kv_len], cfg.n_heads // cfg.kv_heads)

    # causal vs the cache: query i (global pos+i) sees cache slots <= pos+i
    if per_row:
        kv_idx = jnp.arange(kv_len)[None, None, :]                 # [1, 1, kv]
        q_idx = pos[:, None, None] + jnp.arange(q_len)[None, :, None]  # [b, q, 1]
    else:
        kv_idx = jnp.arange(kv_len)[None, :]
        q_idx = pos + jnp.arange(q_len)[:, None]
    allowed = kv_idx <= q_idx
    if cfg.local_attention_window > 0 and is_local is not None:
        # banded local layers (GPT-Neo): is_local is a traced per-layer bool
        band = q_idx - kv_idx < cfg.local_attention_window
        allowed = allowed & (band | jnp.logical_not(is_local))
    # [b, 1, q, kv] (per-row cursors) or [1, 1, q, kv] (shared cursor)
    mask = allowed[:, None, :, :] if per_row else allowed[None, None, :, :]

    alibi = None
    if cfg.position_embedding == "alibi":
        if per_row:
            # slopes * (kv - q) per row — the same int-difference-then-
            # fp32-multiply as _alibi_slice, so per-row values are bitwise
            # equal to the scalar-cursor path at the same positions
            dist = (kv_idx - q_idx).astype(jnp.float32)  # [b, q, kv]
            alibi = (L.alibi_slopes(cfg.n_heads)[None, :, None, None]
                     * dist[:, None, :, :])
        else:
            alibi = _alibi_slice(cfg, q_len, kv_len, pos)

    out = L.dot_product_attention(
        q, k_full, v_full, mask=mask, scale=cfg.attn_scale, alibi_bias=alibi,
        # bf16 logits cut prefill TTFT's [b,h,s,s] HBM traffic too; decode
        # steps ([b,h,1,kv]) are unaffected either way
        logits_dtype=cfg.attn_logits_jnp_dtype)
    # -1, not d: head-pruned models have attention width n_heads*head_dim < d
    out = L.linear_apply(p_attn["o"], out.reshape(b, q_len, -1))
    return out, k_cache, v_cache


def _alibi_slice(cfg, q_len, kv_len, pos):
    """ALiBi bias for queries at global positions [pos, pos+q) vs keys [0, kv)."""
    full = L.alibi_bias(cfg.n_heads, kv_len, kv_len)  # [h|1xh, kv, kv] layout
    # L.alibi_bias returns [1, heads, q, kv]; slice the query rows
    return jax.lax.dynamic_slice_in_dim(full, pos, q_len, axis=2)


def _mlp(cfg, p, h):
    if cfg.n_experts > 0:
        from ..moe import moe_mlp_apply

        out, _ = moe_mlp_apply(cfg, p["mlp"], h, deterministic=True)
        return out
    act = L.ACTIVATIONS[cfg.activation] if cfg.activation != "swiglu" else None
    mp = jax.tree_util.tree_map(
        lambda a: a.astype(h.dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, p["mlp"])
    if cfg.activation == "swiglu":
        gate = L.linear_apply(mp["gate"], h)
        up = L.linear_apply(mp["up"], h)
        return L.linear_apply(mp["down"], jax.nn.silu(gate) * up)
    return L.linear_apply(mp["proj"], act(L.linear_apply(mp["fc"], h)))


def _block_cached(cfg, p, x, k_cache, v_cache, pos, kv_len, rope=None,
                  is_local=None, prefill=False, row_writes="block",
                  attn_impl=None):
    """One block with cache. x: [b, q, d] compute dtype.

    ``attn_impl(p_attn_cast, h) -> (out, aux1, aux2)`` overrides the dense
    ``_attn_with_cache`` (the paged decode kernel is routed through here so
    the norm/residual/MLP structure, and therefore parity with the view
    path, is shared by construction); the two aux values replace the
    (k_cache, v_cache) return slots."""
    cast = lambda a: a.astype(cfg.compute_dtype) \
        if jnp.issubdtype(a.dtype, jnp.floating) else a
    p_cast = {
        "ln_1": p["ln_1"],
        "ln_2": p["ln_2"],
        "attn": jax.tree_util.tree_map(cast, p["attn"]),
        "mlp": p["mlp"],
    }

    def attn(h):
        if attn_impl is not None:
            return attn_impl(p_cast["attn"], h)
        return _attn_with_cache(cfg, p_cast["attn"], h, k_cache, v_cache, pos,
                                kv_len, rope=rope, is_local=is_local,
                                prefill=prefill, row_writes=row_writes)

    if cfg.parallel_attn_mlp:
        h = _norm_apply(cfg, p_cast["ln_1"], x)
        h_mlp = _norm_apply(cfg, p_cast["ln_2"], x) \
            if cfg.parallel_norm_split else h
        a, kc, vc = attn(h)
        return x + a + _mlp(cfg, p_cast, h_mlp), kc, vc
    if cfg.prenorm:
        a, kc, vc = attn(_norm_apply(cfg, p_cast["ln_1"], x))
        x = x + a
        x = x + _mlp(cfg, p_cast, _norm_apply(cfg, p_cast["ln_2"], x))
        return x, kc, vc
    a, kc, vc = attn(x)
    x = _norm_apply(cfg, p_cast["ln_1"], x + a)
    x = _norm_apply(cfg, p_cast["ln_2"], x + _mlp(cfg, p_cast, x))
    return x, kc, vc


def forward_with_cache(model, params, input_ids, cache, pos, kv_len,
                       prefill=False, row_writes="block", last_index=None,
                       return_routing=False):
    """Run the model on ``input_ids`` [b, q] writing k/v into ``cache`` at ``pos``.

    Used for both prefill (q = prompt length, pos = 0) and decode (q = 1,
    pos = cursor). ``pos`` may be a scalar (whole batch at one cursor) or a
    [b] vector (slot-pool continuous batching: every row at its own cursor).
    Returns (logits [b, q, vocab], new_cache).
    ``prefill=True`` is the caller's static promise that pos == 0 and the
    whole visible window is this q block — it unlocks the flash fast path
    (callers with pos > 0 must leave it False).
    ``row_writes="reverse"`` (per-row pos only) makes multi-row writes safe
    against by-design window overruns — see ``_attn_with_cache``.
    ``last_index`` (traced; latent-attention models): the logits of that one
    row only, [b, 1, vocab]. ``return_routing`` (drop-free expert models):
    also return what the expert layers chose, [L_moe, b, q, 2k] int32.
    """
    cfg = model.config
    if cfg.hybrid_layers:
        from . import hybrid

        logits, cache, ids = hybrid.forward_with_cache(
            model, params, input_ids, cache, pos, kv_len,
            last_index=last_index)
        return (logits, cache, ids) if return_routing else (logits, cache)
    if cfg.window_layers:
        from . import window_moe

        logits, cache, ids = window_moe.forward_with_cache(
            model, params, input_ids, cache, pos, kv_len,
            last_index=last_index)
        return (logits, cache, ids) if return_routing else (logits, cache)
    if cfg.latent_attention:
        from . import latent

        logits, cache, ids = latent.forward_with_cache(
            model, params, input_ids, cache, pos, kv_len, prefill=prefill,
            last_index=last_index)
        return (logits, cache, ids) if return_routing else (logits, cache)
    if last_index is not None or return_routing:
        raise ValueError("last_index and return_routing are the expert "
                         "models' (models/latent.py, models/window_moe.py)")
    b, q_len = input_ids.shape
    if jnp.ndim(pos) == 1:
        positions = pos[:, None] + jnp.arange(q_len)[None, :]  # [b, q]
    else:
        positions = pos + jnp.arange(q_len)[None, :]
        positions = jnp.broadcast_to(positions, (b, q_len))

    x = L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)
    if cfg.position_embedding == "learned":
        x = x + jnp.take(params["wpe"]["weight"].astype(cfg.compute_dtype),
                         positions, axis=0)
    rope = None
    if cfg.position_embedding == "rope":
        rope = L.rotary_embedding(positions, cfg.rotary_dim or cfg.head_dim,
                                  cfg.rope_base)

    if cfg.local_attention_window > 0:
        from .transformer import local_attention_flags

        is_local_arr = jnp.asarray(local_attention_flags(cfg))

        def scan_fn(carry, layer):
            h = carry
            p_i, kc, vc, loc = layer
            h, kc, vc = _block_cached(cfg, p_i, h, kc, vc, pos, kv_len,
                                      rope=rope, is_local=loc,
                                      prefill=prefill, row_writes=row_writes)
            return h, (kc, vc)

        h, (k_new, v_new) = jax.lax.scan(
            scan_fn, x, (params["blocks"], cache["k"], cache["v"], is_local_arr)
        )
    else:
        def scan_fn(carry, layer):
            h = carry
            p_i, kc, vc = layer
            h, kc, vc = _block_cached(cfg, p_i, h, kc, vc, pos, kv_len,
                                      rope=rope, prefill=prefill,
                                      row_writes=row_writes)
            return h, (kc, vc)

        h, (k_new, v_new) = jax.lax.scan(
            scan_fn, x, (params["blocks"], cache["k"], cache["v"])
        )
    h = _norm_apply(cfg, params["ln_f"], h)
    if cfg.tie_embeddings:
        logits = L.embedding_attend(params["wte"], h)
    else:
        logits = L.linear_apply(params["lm_head"], h)
    return logits, {"k": k_new, "v": v_new}


def sample_token(logits, rng, *, temperature=1.0, top_k=0, top_p=1.0,
                 greedy=False):
    """logits: [b, vocab] -> [b] int32.

    ``greedy``, ``top_k`` and ``top_p`` are static (shape the program);
    ``temperature`` may be a TRACED scalar so serving/rollout loops can change
    it without recompiling (the reference recompiles nothing — CUDA kernels
    take it as a runtime arg; so do we). What runs is decided at trace time:
    ``greedy`` (or a Python ``temperature`` of 0) is the argmax alone; the
    sort runs only for a static ``top_k > 0`` or ``0 < top_p < 1``.

    PER-REQUEST mode: pass ``rng`` as a [b, 2] stack of PRNG keys and
    temperature/top_k/top_p as [b] arrays — every co-batched row then samples
    from its OWN rng stream with its own knobs (continuous-batching slot
    pools), all traced so one compiled program covers every mix. Rows with
    temperature <= 0 are greedy, and what runs is decided ON THE DEVICE from
    the rows' knobs (``sample_token_per_request``, which a slot pool calls
    itself to say which rows are live)."""
    if jnp.ndim(rng) == 2:
        return sample_token_per_request(logits, rng, temperature=temperature,
                                        top_k=top_k, top_p=top_p)[0]
    logits = logits.astype(jnp.float32)
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if isinstance(temperature, (int, float)) and temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if isinstance(top_p, (int, float)) and 0.0 < top_p < 1.0:
        logits = _apply_top_p(logits, jnp.full((logits.shape[0],), top_p,
                                               jnp.float32))
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _apply_top_p(logits, top_p, sorted_desc=None):
    """Nucleus filter: per row, keep the smallest prefix of descending-prob
    tokens whose cumulative probability reaches ``top_p``; mask the rest.
    ``top_p`` [b] traced; rows with top_p >= 1 pass through unchanged.
    ``sorted_desc``: optionally pass ``sort(logits)`` descending to reuse a
    sort the caller already paid for (the serving decode hot path)."""
    if sorted_desc is None:
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    # exclusive prefix sum: token j is kept while the mass BEFORE it is < p
    # (so the token that crosses p is included — standard nucleus semantics)
    prefix = jnp.cumsum(probs, axis=-1) - probs
    keep = prefix < top_p[:, None]
    # the top token is ALWAYS kept: top_p <= 0 would otherwise keep nothing,
    # mask everything to -1e30, and sample uniformly over the whole vocab
    keep = keep.at[:, 0].set(True)
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                     keepdims=True)
    filtered = jnp.where(logits < cutoff, -1e30, logits)
    return jnp.where(top_p[:, None] >= 1.0, logits, filtered)


def sample_token_per_request(logits, rngs, *, temperature, top_k, top_p,
                             live=None):
    """Per-request sampling for a slot pool: logits [b, vocab], rngs [b, 2]
    (one PRNG key per row — co-batched requests NEVER share an rng stream),
    temperature/top_k/top_p [b] traced arrays, ``live`` [b] bool the rows
    whose token is used (None: all; a freed slot keeps the knobs of the
    request that left it, so it must not count). Returns ``(tokens,
    sampled)``: [b] int32, and the scalar bool that chose the arm.
    Everything is traced: requests with any knob mix join/leave the batch
    without recompiling, and the ONE program does only the work its live
    rows ask for (``lax.cond`` on what it observes):

    - ``sampled`` false, no live row with ``temperature > 0``: the argmax
      (float32, first index on a tie: the scalar greedy path's) and nothing
      else;
    - true: scale by the temperature, the whole-vocabulary sort with the
      top-k threshold and the nucleus filter (a row that asks for neither
      comes back unchanged), and one categorical per row from its own key.

    A row's token never depends on which arm its neighbours chose: a greedy
    row is the exact argmax in both, and a dead row's token is for the
    caller to mask."""
    logits = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)

    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampling = temperature > 0.0
    if live is not None:
        sampling = sampling & live
    sampled = jnp.any(sampling)

    def sample():
        vocab = logits.shape[-1]
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
        # per-row top-k: threshold at the k-th largest (k <= 0 disables)
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        k = jnp.clip(top_k, 0, vocab)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.clip(k - 1, 0, vocab - 1)[:, None], axis=-1)
        below_kth = lambda a: (k[:, None] > 0) & (a < kth)
        scaled = jnp.where(below_kth(scaled), -1e30, scaled)
        # masking the same tail in the already-sorted array keeps it sorted
        # — one O(b * V log V) sort per sampled step, not two
        sorted_masked = jnp.where(below_kth(sorted_desc), -1e30, sorted_desc)
        scaled = _apply_top_p(scaled, top_p, sorted_desc=sorted_masked)
        tok = jax.vmap(
            lambda key, row: jax.random.categorical(key, row))(rngs, scaled)
        return jnp.where(temperature <= 0.0, greedy_tok,
                         tok.astype(jnp.int32))

    return jax.lax.cond(sampled, sample, lambda: greedy_tok), sampled


def prefill_and_first_token(model, params, ids, rng, temperature, *, max_len,
                            greedy, top_k, dtype, true_len=None):
    """Prefill the KV cache with the prompt and sample the first new token.
    Shared by the serving engine and the hybrid (RLHF) engine — one
    implementation of the rollout math, two jit wrappers.

    ``true_len`` (traced scalar) supports right-padded bucketed prompts: the
    first token is sampled at column ``true_len - 1`` instead of the last
    column. Pad slots beyond ``true_len`` hold garbage k/v but always sit in
    the causally-masked future of every real query, and the decode loop
    overwrites each one exactly when its position enters the window — so no
    mask tensor is needed (the serving engine recompiles per prompt LENGTH
    BUCKET, not per length; cf. the reference re-using one CUDA workspace
    across lengths)."""
    b, prompt_len = ids.shape
    cache = init_cache(model.config, b, max_len, dtype)
    logits, cache = forward_with_cache(model, params, ids, cache, 0, max_len,
                                       prefill=True)
    if true_len is None:
        last = logits[:, prompt_len - 1]
    else:
        last = jax.lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)[:, 0]
    tok = sample_token(last, rng, temperature=temperature,
                       top_k=top_k, greedy=greedy)
    return tok, cache


def decode_tokens(model, params, cache, tok, rng, temperature, *, prompt_len,
                  max_len, steps, greedy, top_k):
    """Scan ``steps`` single-token decode iterations.

    Returns ``(toks [steps, b], cache)``. The final cache is returned (even
    though callers usually drop it) so a caller that donates the input cache
    gives XLA an output to alias — otherwise the donation is unusable and the
    compiled program copies the cache at loop entry."""

    def step(carry, i):
        cache, tok, rng = carry
        rng, r = jax.random.split(rng)
        logits, cache = forward_with_cache(
            model, params, tok[:, None], cache, prompt_len + i, max_len)
        nxt = sample_token(logits[:, 0], r, temperature=temperature,
                           top_k=top_k, greedy=greedy)
        return (cache, nxt, rng), nxt

    (cache, _, _), toks = jax.lax.scan(step, (cache, tok, rng),
                                       jnp.arange(steps))
    return toks, cache


def decode_tokens_until(model, params, cache, tok, rng, temperature, *,
                        prompt_len, max_len, steps, greedy, top_k,
                        eos_token_id):
    """Early-stopping decode: a ``while_loop`` that exits as soon as EVERY row
    has emitted ``eos_token_id`` (the reference's generate-stops-at-eos
    behavior, but inside the compiled program — short answers don't pay for
    ``max_new_tokens`` iterations). Rows that finished keep emitting eos.
    Returns ``(out [steps, b], cache)`` (positions past a row's eos filled
    with eos; the cache is returned for donation aliasing, see
    ``decode_tokens``)."""
    b = tok.shape[0]
    out0 = jnp.full((steps, b), eos_token_id, jnp.int32)
    done0 = tok == eos_token_id

    def cond(carry):
        i, done, *_ = carry
        return jnp.logical_and(i < steps, jnp.logical_not(jnp.all(done)))

    def body(carry):
        i, done, cache, tok, rng, out = carry
        rng, r = jax.random.split(rng)
        logits, cache = forward_with_cache(
            model, params, tok[:, None], cache, prompt_len + i, max_len)
        nxt = sample_token(logits[:, 0], r, temperature=temperature,
                           top_k=top_k, greedy=greedy)
        nxt = jnp.where(done, jnp.asarray(eos_token_id, jnp.int32), nxt)
        out = out.at[i].set(nxt)
        done = jnp.logical_or(done, nxt == eos_token_id)
        return (i + 1, done, cache, nxt, rng, out)

    (_, _, cache, _, _, out) = jax.lax.while_loop(
        cond, body, (jnp.zeros((), jnp.int32), done0, cache, tok, rng, out0))
    return out, cache
