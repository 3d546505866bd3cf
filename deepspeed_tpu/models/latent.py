"""Latent attention (MLA, DeepSeek-V2/V3) and the cached forward of a model
that uses it: the two attention forms, a stack whose layers are not alike
(``first_k_dense`` dense blocks, then expert blocks), and the cache paths.

Per token and layer the cache holds ONE latent row, not per-head K and V:
leaf ``k`` is the normed latent ``c`` (``kv_lora_rank`` wide), leaf ``v`` the
rotated key ``k_rope`` all heads share (``qk_rope_head_dim`` wide), both with
a head axis of 1 (``TransformerConfig.cache_geometry``), so the allocators,
the block writer, the block table and the prefix cache of ``decoding.py`` and
``serving/`` hold them as they hold K and V.

Two forms of one attention (``score = (q_nope . k_nope + q_rope . k_rope) /
sqrt(dn + dr)``):

- expanded (prefill, every chunk, the training forward): K and V are
  expanded from the latent rows a block of positions at a time, so a chunk
  that attends to a 16k-token prefix never holds that prefix's K and V;
- absorbed (decode): ``W_uk`` is folded into the query and ``W_uv`` applied
  after the weighted sum, so the 32 heads read each 576-wide row once: in
  place, by the latent form of the decode kernel, where the engine's probe
  allows it, else from a view of the slots' rows gathered through the
  block table.

The cache rides the layer loop as its carry and is read and written at
``[layer, ...]``: a stack of unlike layers cannot scan the cache as ``xs``
without slicing and re-joining the whole pool every step.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp

from . import layers as L
from .layers import Param

F32 = jnp.float32
# positions of context expanded and attended at a time in the expanded form
KV_BLOCK = 2048


def latent_attention_init(rng, cfg, out_std):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    std = cfg.initializer_range
    return {
        "q": L.linear_init(k1, d, H * (dn + dr), ("embed", "heads"), False,
                           std),
        # [c_raw (r) | k_rope_raw (dr)]: kv_a_proj_with_mqa
        "kv_a": L.linear_init(k2, d, r + dr, ("embed", None), False, std),
        "kv_norm": {"scale": Param(L.ones_init((r,)), (None,))},
        # per head [k_nope (dn) | v (dv)]: kv_b_proj
        "kv_b": L.linear_init(k3, r, H * (dn + dv), (None, "heads"), False,
                              std),
        "o": L.linear_init(k4, H * dv, d, ("heads", "embed"), False, out_std),
    }


def rope_tables(cfg, positions):
    return L.rotary_embedding(positions, cfg.qk_rope_head_dim, cfg.rope_base)


def project(cfg, p, h, rope):
    """h [b, q, d] -> q_nope [b, q, H, dn], q_rope [b, q, H, dr] (rotated),
    c [b, q, r] (normed: what the cache holds), k_rope [b, q, dr] (rotated)."""
    b, q_len, _ = h.shape
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = L.linear_apply(p["q"], h).reshape(b, q_len, H, dn + dr)
    kv_a = L.linear_apply(p["kv_a"], h)
    c = L.rmsnorm_apply(p["kv_norm"], kv_a[..., :r], eps=cfg.layernorm_eps)
    cos, sin = rope
    q_rope = L.apply_rotary(q[..., dn:], cos, sin,
                            interleaved=cfg.rotary_interleaved)
    k_rope = L.apply_rotary(kv_a[..., None, r:], cos, sin,
                            interleaved=cfg.rotary_interleaved)[:, :, 0]
    return q[..., :dn], q_rope, c, k_rope


def score_scale(cfg):
    """``1 / sqrt(qk_nope + qk_rope)``, unless the config names one."""
    return cfg.attn_scale if cfg.attn_scale is not None else 1.0 / math.sqrt(
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def _kv_b(cfg, p):
    H, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    return p["kv_b"]["kernel"].reshape(cfg.kv_lora_rank, H, dn + dv)


def _prec(dtype):
    # a float32 model multiplies in full float32 on every backend
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


def expanded_attention(cfg, p, q_nope, q_rope, c_ctx, kr_ctx, q_start,
                       kv_live=None, kernel=False):
    """Causal attention of queries at positions ``q_start + [0, q)`` against
    context rows ``[0, kv)`` given as latents: K and V are expanded from
    ``c_ctx`` [b, kv, r] and ``kr_ctx`` [b, kv, dr] one block of KV_BLOCK
    positions at a time under an online softmax (float32 statistics).
    ``kv_live`` (traced) bounds the blocks visited: rows from it on are in
    every query's future. ``kernel``: the forward is never differentiated
    (the cached one), so a block may be folded by the chunk kernel where
    ``ops/pallas/chunk_attention.py:chunk_attention_path`` allows; the
    training forward keeps these einsums. Returns [b, q, H * dv]."""
    from ..ops.pallas.chunk_attention import chunk_attention_path

    b, q_len, H, dn = q_nope.shape
    dv = cfg.v_head_dim
    kv = c_ctx.shape[1]
    dtype = q_nope.dtype
    prec = _prec(dtype)
    w = _kv_b(cfg, p).astype(dtype)
    scale = score_scale(cfg)
    q_idx = q_start + jnp.arange(q_len)
    blk = min(KV_BLOCK, kv)
    n_blocks = -(-kv // blk)
    if n_blocks * blk != kv:
        pad = n_blocks * blk - kv
        c_ctx = jnp.pad(c_ctx, ((0, 0), (0, pad), (0, 0)))
        kr_ctx = jnp.pad(kr_ctx, ((0, 0), (0, pad), (0, 0)))
    live = n_blocks if kv_live is None else jnp.minimum(
        (kv_live + blk - 1) // blk, n_blocks)
    if kernel and chunk_attention_path(q_len, 1, blk, 0,
                                       cfg.attention_interpret,
                                       cfg.mesh) == "kernel":
        with jax.named_scope("latent_attn_expanded"):
            out = _expanded_kernel(cfg, w, q_nope, q_rope, c_ctx, kr_ctx,
                                   q_start, n_blocks, live)
        return out.transpose(0, 2, 1, 3).reshape(b, q_len, H * dv)

    def one_block(i, carry):
        m, l, acc = carry
        start = i * blk
        c = jax.lax.dynamic_slice_in_dim(c_ctx, start, blk, 1)
        kr = jax.lax.dynamic_slice_in_dim(kr_ctx, start, blk, 1)
        kv_h = jnp.einsum("bkr,rhd->bkhd", c, w, precision=prec)
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, kv_h[..., :dn],
                        precision=prec, preferred_element_type=F32)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, kr, precision=prec,
                          preferred_element_type=F32)) * scale
        allowed = (start + jnp.arange(blk))[None, :] <= q_idx[:, None]
        s = jnp.where(allowed[None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a block wholly in a query's future leaves its statistics alone
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        e = jnp.exp(s - safe[..., None])
        fix = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
        l = l * fix + jnp.sum(e, axis=-1)
        acc = acc * fix[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", e.astype(dtype), kv_h[..., dn:],
            precision=prec, preferred_element_type=F32)
        return m_new, l, acc

    init = (jnp.full((b, H, q_len), -jnp.inf, F32),
            jnp.zeros((b, H, q_len), F32),
            jnp.zeros((b, H, q_len, dv), F32))
    with jax.named_scope("latent_attn_expanded"):
        if n_blocks == 1:
            _, l, acc = one_block(0, init)
        else:
            _, l, acc = jax.lax.fori_loop(0, live, one_block, init)
        out = (acc / l[..., None]).astype(dtype)
    return out.transpose(0, 2, 1, 3).reshape(b, q_len, H * dv)


def _expanded_kernel(cfg, w, q_nope, q_rope, c_ctx, kr_ctx, q_start,
                     n_blocks, live):
    """``expanded_attention``'s loop with each block folded by the chunk
    kernel (``ops/pallas/chunk_attention.py``): a block's K and V are still
    expanded from its latents by one XLA product, straight into the
    kernel's per-head layout, K as ``[k_nope | k_rope]`` (the rotated key
    every head shares) against Q ``[q_nope | q_rope]``, so the kernel sees
    one score product of width ``dn + dr``. Returns [b, H, q, dv]."""
    from ..ops.pallas import chunk_attention as C

    b, q_len, H, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], cfg.v_head_dim
    dtype = q_nope.dtype
    prec = _prec(dtype)
    blk = min(KV_BLOCK, c_ctx.shape[1])
    q = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)

    def one_block(i, carry):
        start = i * blk
        c = jax.lax.dynamic_slice_in_dim(c_ctx, start, blk, 1)
        kr = jax.lax.dynamic_slice_in_dim(kr_ctx, start, blk, 1)
        kv_h = jnp.einsum("bkr,rhd->bhkd", c, w, precision=prec)
        k = jnp.concatenate([kv_h[..., :dn], jnp.broadcast_to(
            kr[:, None].astype(dtype), (b, H, blk, dr))], -1)
        return C.chunk_attention_block(
            q, k, kv_h[..., dn:], carry, q_start, start, start, rep=1,
            scale=score_scale(cfg), interpret=cfg.attention_interpret)

    carry = C.initial_carry(b, H, q_len, dv)
    if n_blocks == 1:
        carry = one_block(0, carry)
    else:
        carry = jax.lax.fori_loop(0, live, one_block, carry)
    return C.finish(carry, dtype)


def absorbed_attention(cfg, p, q_nope, q_rope, c_ctx, kr_ctx, pos):
    """One query row a sequence (decode) against its latent rows ``[0,
    pos]``: ``q_lat = W_uk^T q_nope``, scores against ``c`` and ``k_rope``,
    ``o = W_uv (sum p c)``. q_nope [S, H, dn], q_rope [S, H, dr]; c_ctx
    [S, kv, r], kr_ctx [S, kv, dr]; pos [S]. Returns [S, H * dv]."""
    prec = _prec(q_nope.dtype)

    def attend(q_lat):
        s = (jnp.einsum("shr,skr->shk", q_lat, c_ctx, precision=prec,
                        preferred_element_type=F32)
             + jnp.einsum("shd,skd->shk", q_rope, kr_ctx, precision=prec,
                          preferred_element_type=F32)) * score_scale(cfg)
        allowed = jnp.arange(c_ctx.shape[1])[None, :] <= pos[:, None]
        s = jnp.where(allowed[:, None, :], s, jnp.finfo(F32).min)
        probs = jax.nn.softmax(s, axis=-1).astype(q_lat.dtype)
        return jnp.einsum("shk,skr->shr", probs, c_ctx, precision=prec)

    return _absorbed(cfg, p, q_nope, attend)


def absorbed_attention_paged(cfg, p, q_nope, q_rope, c, k_rope, pool, table,
                             pos, layer):
    """``absorbed_attention`` straight against the paged pool: the latent
    form of ``ops/pallas/paged_attention.py`` walks each slot's block table
    and reads the latent rows below its cursor, with the current token's
    row (``c`` [S, r], ``k_rope`` [S, dr]) beside them. ``pool``: the leaves
    whole, ``layer`` the traced layer to read. Returns [S, H * dv]."""
    from ..ops.pallas.paged_attention import paged_latent_decode

    def attend(q_lat):
        return paged_latent_decode(
            q_lat, q_rope, c, k_rope, pool["k"], pool["v"], table, pos,
            layer=layer, scale=score_scale(cfg),
            interpret=cfg.attention_interpret, mesh=cfg.mesh)

    return _absorbed(cfg, p, q_nope, attend)


def _absorbed(cfg, p, q_nope, attend):
    """``q_lat = W_uk^T q_nope``, ``o_lat = attend(q_lat)`` [S, H, r], ``o =
    W_uv o_lat``: the absorbed form around either way of attending, under
    the scope the benchmark's trace reduction reads."""
    S, H, dn = q_nope.shape
    dtype = q_nope.dtype
    prec = _prec(dtype)
    w = _kv_b(cfg, p).astype(dtype)
    with jax.named_scope("latent_attn_absorbed"):
        q_lat = jnp.einsum("shd,rhd->shr", q_nope, w[..., :dn],
                           precision=prec)
        o_lat = attend(q_lat)
        out = jnp.einsum("shr,rhd->shd", o_lat, w[..., dn:], precision=prec)
    return out.reshape(S, H * cfg.v_head_dim)


def attention_uncached(cfg, p, h, rope):
    """The training / scoring forward: expanded form over the block itself."""
    q_nope, q_rope, c, k_rope = project(cfg, p, h, rope)
    out = expanded_attention(cfg, p, q_nope, q_rope, c, k_rope, 0)
    return L.linear_apply(p["o"], out)


# ---------------------------------------------------------------------------
# the stack: dense blocks, then expert blocks
# ---------------------------------------------------------------------------
def dense_cfg(cfg):
    """The leading dense layers' view of the config: the same block with the
    experts off, so the dense FFN of width ``d_ff`` is built and run."""
    return dataclasses.replace(cfg, n_experts=0, first_k_dense=0,
                               moe_local_experts=0, moe_expert_offset=0)


def _cast_block(cfg, p):
    cast = lambda a: a.astype(cfg.compute_dtype) \
        if jnp.issubdtype(a.dtype, jnp.floating) else a
    return {"ln_1": p["ln_1"], "ln_2": p["ln_2"],
            "attn": jax.tree_util.tree_map(cast, p["attn"]), "mlp": p["mlp"]}


def _ffn(cfg, p_mlp, h, stacked=None):
    """(y, routed [b, q, 2k] or None: ``moe/dropfree.py``)."""
    if cfg.n_experts > 0:
        from ..moe.dropfree import dropfree_moe_apply

        return dropfree_moe_apply(cfg, p_mlp, h, stacked=stacked)
    from .decoding import _mlp

    return _mlp(cfg, {"mlp": p_mlp}, h), None


def _block(cfg, p, x, cache, attn, stacked=None):
    """One pre-norm block; ``attn(p_attn, normed, cache) -> ([b, q, d],
    cache)``. Returns (x, cache, routed)."""
    from .transformer import _norm_apply

    p = _cast_block(cfg, p)
    a, cache = attn(p["attn"], _norm_apply(cfg, p["ln_1"], x), cache)
    x = x + a
    y, routed = _ffn(cfg, p["mlp"], _norm_apply(cfg, p["ln_2"], x), stacked)
    return x + y, cache, routed


def _run_layers(cfg, params, x, cache, layer_attn):
    """The dense blocks unrolled, then one scan over the expert blocks, with
    ``cache`` (any pytree) as the carry. ``layer_attn(cfg_l, layer)`` returns
    that layer's ``attn(p_attn, h, cache) -> (out, cache)``. Returns (x,
    cache, routed [L_moe, b, q, 2k] or None)."""
    kd = cfg.first_k_dense
    dcfg = dense_cfg(cfg) if kd else cfg
    for i in range(kd):
        p_i = jax.tree_util.tree_map(lambda a: a[i], params["dense_blocks"])
        x, cache, _ = _block(dcfg, p_i, x, cache, layer_attn(dcfg, i))

    # the expert stacks stay whole outside the scan (dropfree_moe_apply reads
    # a layer's experts in place); everything else is scanned a layer at a time
    blocks = params["blocks"]
    experts = {n: blocks["mlp"][n] for n in ("gate_up", "down")}
    rest = dict(blocks, mlp={n: a for n, a in blocks["mlp"].items()
                             if n not in experts})

    def scan_fn(carry, xs):
        x, cache = carry
        p_l, i = xs
        x, cache, routed = _block(cfg, p_l, x, cache,
                                  layer_attn(cfg, kd + i), (experts, i))
        return (x, cache), routed

    (x, cache), routed = jax.lax.scan(
        scan_fn, (x, cache), (rest, jnp.arange(cfg.n_layers - kd)))
    return x, cache, routed


def _head(model, params, h):
    from .transformer import _norm_apply

    cfg = model.config
    h = _norm_apply(cfg, params["ln_f"], h)
    if cfg.tie_embeddings:
        return L.embedding_attend(params["wte"], h)
    return L.linear_apply(params["lm_head"], h)


def forward_with_cache(model, params, input_ids, cache, pos, kv_len,
                       prefill=False, last_index=None):
    """``decoding.forward_with_cache`` for a latent-attention model: the q
    block [b, q] is written at scalar cursor ``pos`` into the dense cache
    (``k`` [L, b, max_len, 1, r], ``v`` [L, b, max_len, 1, dr]) and attends
    to rows ``[0, pos + q)``: expanded for a block of queries, absorbed for
    one. ``last_index`` (traced): return the logits of that one row only
    ([b, 1, vocab]; the head over a whole chunk is 128k columns wide).
    Returns (logits, cache, routed [L_moe, b, q, 2k] or None: the chosen
    expert ids and their weights' bits, ``moe/dropfree.py``)."""
    cfg = model.config
    if jnp.ndim(pos) != 0:
        raise ValueError(
            "latent attention: per-row cursors over a dense cache are not "
            "implemented; decode through the paged pool")
    b, q_len = input_ids.shape
    positions = jnp.broadcast_to(pos + jnp.arange(q_len)[None, :], (b, q_len))
    rope = rope_tables(cfg, positions)
    x = L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)

    def layer_attn(cfg_l, layer):
        def attn(p_attn, h, cache):
            q_nope, q_rope, c, k_rope = project(cfg_l, p_attn, h, rope)
            kc = jax.lax.dynamic_update_slice(
                cache["k"], c[None, :, :, None, :].astype(cache["k"].dtype),
                (layer, 0, pos, 0, 0))
            vc = jax.lax.dynamic_update_slice(
                cache["v"],
                k_rope[None, :, :, None, :].astype(cache["v"].dtype),
                (layer, 0, pos, 0, 0))
            if prefill:
                # pos == 0 and the q block is the whole visible window
                c_ctx, kr_ctx = (c.astype(kc.dtype).astype(c.dtype),
                                 k_rope.astype(vc.dtype).astype(c.dtype))
                live = None
            else:
                c_ctx = jax.lax.dynamic_index_in_dim(
                    kc, layer, 0, False)[:, :kv_len, 0].astype(c.dtype)
                kr_ctx = jax.lax.dynamic_index_in_dim(
                    vc, layer, 0, False)[:, :kv_len, 0].astype(c.dtype)
                live = pos + q_len
            if q_len == 1 and not prefill:
                out = absorbed_attention(
                    cfg_l, p_attn, q_nope[:, 0], q_rope[:, 0], c_ctx, kr_ctx,
                    jnp.broadcast_to(pos, (b,)))[:, None]
            else:
                out = expanded_attention(cfg_l, p_attn, q_nope, q_rope,
                                         c_ctx, kr_ctx, pos, kv_live=live,
                                         kernel=True)
            return L.linear_apply(p_attn["o"], out), {"k": kc, "v": vc}

        return attn

    x, cache, ids = _run_layers(cfg, params, x, cache, layer_attn)
    if last_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    return _head(model, params, x), cache, ids


def forward_with_paged_cache(model, params, input_ids, pool, table, pos,
                             block_size, kernel=False):
    """``decoding.forward_with_paged_cache`` for a latent-attention model:
    one decode step ([S, 1] tokens) in the absorbed form; each slot's new
    row is scattered into the pool at (table[s, pos // bs], pos % bs), freed
    slots into the garbage block. ``kernel``: the decode kernel reads the
    live latent rows in place (``absorbed_attention_paged``); else the
    slots' latent rows are gathered through the block table into a view of
    ``n_slots x max_len`` rows a layer. Returns (logits [S, 1, vocab], pool,
    routed [L_moe, S, 1, 2k] or None)."""
    cfg = model.config
    S, q_len = input_ids.shape
    if q_len != 1:
        raise ValueError("latent attention: speculative verify (several "
                         "query rows a slot) is not implemented")
    rope = rope_tables(cfg, pos[:, None])
    x = L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)
    j = jnp.clip(pos // block_size, 0, table.shape[1] - 1)
    bi = jnp.take_along_axis(table, j[:, None], axis=1)[:, 0]
    off = pos % block_size

    def layer_attn(cfg_l, layer):
        def attn(p_attn, h, pool):
            q_nope, q_rope, c, k_rope = project(cfg_l, p_attn, h, rope)
            with jax.named_scope("latent_row_write"):
                kc = pool["k"].at[layer, bi, off, 0].set(
                    c[:, 0].astype(pool["k"].dtype))
                # the rope key's whole block is read, given the row and
                # written back: the device keeps this leaf with its tokens
                # in the lanes (as the decode kernel reads it), and a
                # scatter of one 64-wide row can have the compiler re-lay
                # the whole leaf out with its rows there and back (it does
                # at 3 of kanana2's layers). A slot's write block is its
                # own; slots parked on the garbage block write garbage,
                # whichever of them wins
                blocks = pool["v"][layer, bi]              # [S, bs, 1, dr]
                mine = jnp.arange(block_size)[None, :, None, None] \
                    == off[:, None, None, None]
                vc = pool["v"].at[layer, bi].set(jnp.where(
                    mine, k_rope[:, 0, None, None].astype(blocks.dtype),
                    blocks))
            if kernel:
                out = absorbed_attention_paged(
                    cfg_l, p_attn, q_nope[:, 0], q_rope[:, 0], c[:, 0],
                    k_rope[:, 0], {"k": kc, "v": vc}, table, pos, layer)
                return (L.linear_apply(p_attn["o"], out[:, None]),
                        {"k": kc, "v": vc})
            with jax.named_scope("latent_view_gather"):
                c_ctx = kc[layer, table][:, :, :, 0].reshape(
                    S, -1, kc.shape[-1]).astype(c.dtype)
                kr_ctx = vc[layer, table][:, :, :, 0].reshape(
                    S, -1, vc.shape[-1]).astype(c.dtype)
            out = absorbed_attention(cfg_l, p_attn, q_nope[:, 0],
                                     q_rope[:, 0], c_ctx, kr_ctx, pos)
            return (L.linear_apply(p_attn["o"], out[:, None]),
                    {"k": kc, "v": vc})

        return attn

    x, pool, ids = _run_layers(cfg, params, x, pool, layer_attn)
    return _head(model, params, x), pool, ids
