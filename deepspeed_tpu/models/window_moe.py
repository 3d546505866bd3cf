"""Window and full attention layers mixed, beside drop-free experts (AFMoE,
Arcee Trinity): the block, a stack whose layers are of two kinds, and the
cached forwards.

A layer is of one of two kinds (``TransformerConfig.layer_types``, the
published list):

- a WINDOW layer (``sliding_attention``) rotates q and k (RoPE over the whole
  head, half-split pairs) and lets query ``i`` see keys ``j`` with ``0 <= i -
  j < sliding_window``;
- a FULL layer (``full_attention``) rotates nothing (no positions at all) and
  sees every ``j <= i``.

Both are grouped-query attention with an RMS norm over the head size on q and
k, and an elementwise sigmoid gate on the attention output before its
projection. A block has four norms, one before and one after each branch:
``x += N2(Attn(N1 x)); x += N4(FFN(N3 x))``. The first ``first_k_dense``
layers have a dense SwiGLU, the rest ``moe/dropfree.py``'s expert layer. The
embedding is scaled by ``embed_scale``.

The two kinds keep their K and V apart in the paged pool (``serving/
kv_pool.py``): a full layer's group holds every token of a request, a window
layer's group a RING of blocks as wide as the band, block ``j`` of a slot at
table column ``j % ring``. So a layer's kind and its index in its group must be
STATIC where the pool is touched: the stack runs its leading dense layers and
whatever expert layers come before the first whole period unrolled, then
scans over whole periods with a period's layers written out
(``layer_plan``). The expert stacks stay whole outside the scan, as in
``models/latent.py`` (whose ``_ffn`` and ``_head`` this imports).

Three attention forms over one projection:

- ``blockwise_attention`` (the uncached forward, prefill, every chunk, and
  ``generate()``'s decode over the dense cache): the context is visited in
  blocks of ``KV_BLOCK`` positions under an online softmax, from the block
  that holds the band's first position (a window layer) or 0 (a full layer)
  to the live length; no ``[heads, q, max_len]`` score tensor exists;
- the decode KERNEL (``ops/pallas/paged_attention.py``) over the pool, with
  its band in a window layer;
- the decode VIEW, where the kernel cannot run (a CPU without the
  interpreter): the slot's blocks gathered through the table, a mask over
  the positions they hold. The window group's view is the ring (a band
  wide), the full group's the ``n_slots x max_len`` view.
"""

import collections
import math

import jax
import jax.numpy as jnp

from . import layers as L
from .latent import _ffn, _head, _prec, dense_cfg
from .layers import Param

F32 = jnp.float32
# positions of context attended at a time by ``blockwise_attention``
KV_BLOCK = 1024
WINDOW, FULL = "sliding_attention", "full_attention"

# one layer as the cache paths see it: ``index`` in the model, ``group`` its
# index among the layers of its kind (both may be traced), ``window`` static
Layer = collections.namedtuple("Layer", "index group window")


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------
def attention_init(rng, cfg, out_std):
    d, H, G, dh = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    p = L.attention_init(rng, d, H, G, False, cfg.initializer_range,
                         out_stddev=out_std, head_dim=dh)
    p["gate"] = L.linear_init(jax.random.fold_in(rng, 4), d, H * dh,
                              ("embed", "heads"), False,
                              cfg.initializer_range)
    p["q_norm"] = {"scale": Param(L.ones_init((dh,)), (None,))}
    p["k_norm"] = {"scale": Param(L.ones_init((dh,)), (None,))}
    return p


def block_init(rng, cfg):
    from .transformer import _mlp_init, _norm_init

    k_attn, k_mlp = jax.random.split(rng)
    out_std = cfg.initializer_range / (2.0 * cfg.n_layers) ** 0.5
    if cfg.n_experts > 0:
        from ..moe.dropfree import dropfree_moe_init

        mlp = dropfree_moe_init(k_mlp, cfg)
    else:
        mlp = _mlp_init(k_mlp, cfg)
    return {"ln_1": _norm_init(cfg), "ln_1_post": _norm_init(cfg),
            "attn": attention_init(k_attn, cfg, out_std),
            "ln_2": _norm_init(cfg), "ln_2_post": _norm_init(cfg),
            "mlp": mlp}


# ---------------------------------------------------------------------------
# the stack's plan
# ---------------------------------------------------------------------------
def layer_groups(cfg):
    """``(window, full)``: the model's layer indices of each kind, in order.
    A layer's place in its list is its index in its pool group."""
    kinds = cfg.layer_types
    return ([i for i, k in enumerate(kinds) if k == WINDOW],
            [i for i, k in enumerate(kinds) if k == FULL])


def layer_plan(cfg):
    """``(unrolled, period, n_periods)``: the layers run one by one (the
    leading dense ones, then the expert layers before the first whole
    period), the kinds of one period's layers, and how many periods the scan
    runs. The period is the shortest that tiles the END of the list, so that
    a published list which does not start on a period's edge (two dense
    layers, then ``layer_types[2:]``) still scans whole periods."""
    kd, n = cfg.first_k_dense, cfg.n_layers
    kinds = cfg.layer_types
    experts = n - kd
    for p in range(1, experts + 1):
        lead = experts % p
        tail = kinds[kd + lead:]
        if tail == tail[:p] * (experts // p):
            return list(range(kd + lead)), tail[:p], experts // p
    return list(range(n)), (), 0


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def _scale(cfg):
    return cfg.attn_scale if cfg.attn_scale is not None \
        else 1.0 / math.sqrt(cfg.head_dim)


def project(cfg, p, h, positions, window):
    """h [b, q, d] -> q [b, q, H, dh], k and v [b, q, G, dh], gate [b, q,
    H * dh]. q and k are normed per head and, in a window layer, rotated at
    ``positions`` [b, q]; a full layer has no positions."""
    b, q_len, _ = h.shape
    H, G, dh = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    eps = cfg.layernorm_eps
    q = L.rmsnorm_apply(p["q_norm"], L.linear_apply(p["q"], h).reshape(
        b, q_len, H, dh), eps)
    k = L.rmsnorm_apply(p["k_norm"], L.linear_apply(p["k"], h).reshape(
        b, q_len, G, dh), eps)
    v = L.linear_apply(p["v"], h).reshape(b, q_len, G, dh)
    if window:
        cos, sin = L.rotary_embedding(positions, dh, cfg.rope_base)
        q = L.apply_rotary(q, cos, sin)
        k = L.apply_rotary(k, cos, sin)
    gate = jax.nn.sigmoid(L.linear_apply(p["gate"], h))
    return q, k, v, gate


def _out(p, attended, gate):
    return L.linear_apply(p["o"], attended * gate)


def blockwise_attention(cfg, q, read, kv, q_start, window):
    """Queries at positions ``q_start + [0, q)`` against context positions
    ``[0, kv)``, given by ``read(start, n) -> (k, v)`` [b, n, G, dh] each:
    one block of ``KV_BLOCK`` positions at a time under an online softmax
    (float32 statistics). The blocks visited run from the one that holds
    the first position any query sees (``q_start - window + 1`` in a window
    layer) to the one that holds the last query. Returns [b, q, H * dh]."""
    b, q_len, H, dh = q.shape
    G = cfg.kv_heads
    dtype = q.dtype
    prec = _prec(dtype)
    scale = _scale(cfg)
    w = cfg.sliding_window if window else 0
    blk = min(KV_BLOCK, kv)
    n_blocks = -(-kv // blk)
    qg = q.reshape(b, q_len, G, H // G, dh)
    q_idx = q_start + jnp.arange(q_len)

    def one_block(i, carry):
        m, l, acc = carry
        # the last block of a context that is no multiple of the block is
        # read where it fits; what it shares with the one before is masked
        start = jnp.minimum(i * blk, kv - blk)
        k, v = read(start, blk)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k.astype(dtype),
                       precision=prec, preferred_element_type=F32) * scale
        k_idx = start + jnp.arange(blk)
        allowed = (k_idx[None, :] <= q_idx[:, None]) \
            & (k_idx[None, :] >= i * blk)
        if w:
            allowed &= q_idx[:, None] - k_idx[None, :] < w
        s = jnp.where(allowed, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a block that a query sees nothing of leaves its statistics alone
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        e = jnp.exp(s - safe[..., None])
        fix = jnp.exp(jnp.where(jnp.isfinite(m), m - safe, -jnp.inf))
        l = l * fix + jnp.sum(e, axis=-1)
        acc = acc * fix[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", e.astype(dtype), v.astype(dtype),
            precision=prec, preferred_element_type=F32)
        return m_new, l, acc

    shape = (b, G, H // G, q_len)
    init = (jnp.full(shape, -jnp.inf, F32), jnp.zeros(shape, F32),
            jnp.zeros(shape + (dh,), F32))
    with jax.named_scope("window_chunk_attn" if window
                         else "full_chunk_attn"):
        if n_blocks == 1:
            _, l, acc = one_block(0, init)
        else:
            hi = jnp.minimum((q_start + q_len + blk - 1) // blk, n_blocks)
            lo = jnp.maximum(q_start - (w - 1), 0) // blk if w else 0
            _, l, acc = jax.lax.fori_loop(lo, hi, one_block, init)
        out = (acc / l[..., None]).astype(dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, q_len, H * dh)


def ring_positions(pos, n_cols, block_size):
    """The position each row of a slot's ring view holds: column ``c`` has
    the newest block ``j <= pos // block_size`` with ``j % n_cols == c``.
    pos [S] -> [S, n_cols * block_size] (negative where no block was yet)."""
    cur = pos // block_size
    col = jnp.arange(n_cols)
    block = cur[:, None] - (cur[:, None] - col[None, :]) % n_cols
    return (block[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]).reshape(pos.shape[0], -1)


def view_attention(cfg, q, k_view, v_view, k_pos, pos, window):
    """One query row a slot against its gathered blocks (the new row
    written): q [S, H, dh]; k_view / v_view [S, T, G, dh]; k_pos [S, T] the
    position each view row holds; pos [S]. Returns [S, H * dh]."""
    S, H, dh = q.shape
    G = cfg.kv_heads
    dtype = q.dtype
    prec = _prec(dtype)
    qg = q.reshape(S, G, H // G, dh)
    s = jnp.einsum("sgrd,stgd->sgrt", qg, k_view.astype(dtype),
                   precision=prec, preferred_element_type=F32) * _scale(cfg)
    allowed = (k_pos <= pos[:, None]) & (k_pos >= 0)
    if window:
        allowed &= pos[:, None] - k_pos < cfg.sliding_window
    s = jnp.where(allowed[:, None, None, :], s, jnp.finfo(F32).min)
    probs = jax.nn.softmax(s, axis=-1).astype(dtype)
    out = jnp.einsum("sgrt,stgd->sgrd", probs, v_view.astype(dtype),
                     precision=prec)
    return out.reshape(S, H * dh)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------
def _cast_block(cfg, p):
    cast = lambda a: a.astype(cfg.compute_dtype) \
        if jnp.issubdtype(a.dtype, jnp.floating) else a
    return dict(p, attn=jax.tree_util.tree_map(cast, p["attn"]))


def _block(cfg, p, x, carry, attn, layer, stacked=None):
    """``x += N2(attn(N1 x)); x += N4(ffn(N3 x))``; ``attn(cfg, p_attn,
    normed, carry, layer) -> ([b, q, d], carry)``. Returns (x, carry,
    routed)."""
    from .transformer import _norm_apply

    p = _cast_block(cfg, p)
    a, carry = attn(cfg, p["attn"], _norm_apply(cfg, p["ln_1"], x), carry,
                    layer)
    x = x + _norm_apply(cfg, p["ln_1_post"], a)
    y, routed = _ffn(cfg, p["mlp"], _norm_apply(cfg, p["ln_2"], x), stacked)
    return x + _norm_apply(cfg, p["ln_2_post"], y), carry, routed


def _run_layers(cfg, params, x, carry, attn):
    """Every layer over ``x`` with ``carry`` (the cache: any pytree) handed
    from layer to layer. Returns (x, carry, routed [L_moe, b, q, 2k])."""
    kd = cfg.first_k_dense
    dcfg = dense_cfg(cfg) if kd else cfg
    window_layers, full_layers = layer_groups(cfg)
    unrolled, period, n_periods = layer_plan(cfg)

    def static(i):
        win = cfg.layer_types[i] == WINDOW
        return Layer(i, (window_layers if win else full_layers).index(i), win)

    blocks = params["blocks"]
    experts = {n: blocks["mlp"][n] for n in ("gate_up", "down")}
    rest = dict(blocks, mlp={n: a for n, a in blocks["mlp"].items()
                             if n not in experts})
    pick = lambda tree, e: jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, e, 0, False), tree)

    def expert_block(x, carry, e, layer):
        # the expert stacks stay whole (dropfree_moe_apply reads a layer's
        # experts in place); the layer's other leaves are picked out
        return _block(cfg, pick(rest, e), x, carry, attn, layer,
                      (experts, e))

    routed = []
    for i in unrolled:
        if i < kd:
            x, carry, _ = _block(dcfg, pick(params["dense_blocks"], i), x,
                                 carry, attn, static(i))
        else:
            x, carry, r = expert_block(x, carry, i - kd, static(i))
            routed.append(r[None])
    if n_periods:
        first = len(unrolled)
        firsts = [static(first + j) for j in range(len(period))]
        per = {True: sum(k == WINDOW for k in period),
               False: sum(k == FULL for k in period)}

        def one_period(state, t):
            x, carry = state
            out = []
            for j, lay in enumerate(firsts):
                layer = Layer(lay.index + t * len(period),
                              lay.group + t * per[lay.window], lay.window)
                x, carry, r = expert_block(x, carry, layer.index - kd, layer)
                out.append(r)
            return (x, carry), jnp.stack(out)

        (x, carry), r = jax.lax.scan(one_period, (x, carry),
                                     jnp.arange(n_periods))
        routed.append(r.reshape((-1,) + r.shape[2:]))
    return x, carry, jnp.concatenate(routed)


def _embed(cfg, params, input_ids):
    x = L.embedding_apply(params["wte"], input_ids, cfg.compute_dtype)
    return x * jnp.asarray(cfg.embed_scale, x.dtype)


def backbone(model, params, input_ids, positions=None):
    """The uncached forward (``CausalLM.apply`` / ``loss``): embedding,
    blocks, final norm -> [b, s, d]."""
    from .transformer import _norm_apply

    cfg = model.config
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def attn(cfg_l, p, h, carry, layer):
        q, k, v, gate = project(cfg_l, p, h, positions, layer.window)
        read = lambda start, n: (
            jax.lax.dynamic_slice_in_dim(k, start, n, 1),
            jax.lax.dynamic_slice_in_dim(v, start, n, 1))
        out = blockwise_attention(cfg_l, q, read, s, 0, layer.window)
        return _out(p, out, gate), carry

    x, _, _ = _run_layers(cfg, params, _embed(cfg, params, input_ids), None,
                          attn)
    return _norm_apply(cfg, params["ln_f"], x)


def forward_with_cache(model, params, input_ids, cache, pos, kv_len,
                       last_index=None):
    """``decoding.forward_with_cache`` for this family: the q block [b, q]
    is written at scalar cursor ``pos`` into the dense cache (``k``, ``v``
    [L, b, max_len, G, dh], every layer whole: the pool's groups part them
    at the insert) and attends to rows ``[0, pos + q)``, a window layer to
    its band of them. ``last_index`` (traced): the logits of that one row
    only. Returns (logits, cache, routed [L_moe, b, q, 2k])."""
    cfg = model.config
    if jnp.ndim(pos) != 0:
        raise ValueError(
            "window and full attention layers: per-row cursors over a dense "
            "cache are not implemented; decode through the paged pool")
    b, q_len = input_ids.shape
    positions = jnp.broadcast_to(pos + jnp.arange(q_len)[None, :], (b, q_len))

    def attn(cfg_l, p, h, cache, layer):
        q, k, v, gate = project(cfg_l, p, h, positions, layer.window)
        cache = {
            name: jax.lax.dynamic_update_slice(
                cache[name], new[None].astype(cache[name].dtype),
                (layer.index, 0, pos, 0, 0))
            for name, new in (("k", k), ("v", v))}

        def read(start, n):
            at = (layer.index, 0, start, 0, 0)
            size = (1, b, n) + cache["k"].shape[3:]
            return (jax.lax.dynamic_slice(cache["k"], at, size)[0],
                    jax.lax.dynamic_slice(cache["v"], at, size)[0])

        out = blockwise_attention(cfg_l, q, read, kv_len, pos, layer.window)
        return _out(p, out, gate), cache

    x, cache, routed = _run_layers(cfg, params,
                                   _embed(cfg, params, input_ids), cache,
                                   attn)
    if last_index is not None:
        x = jax.lax.dynamic_slice_in_dim(x, last_index, 1, axis=1)
    return _head(model, params, x), cache, routed


def forward_with_paged_cache(model, params, input_ids, pool, tables, pos,
                             block_size, kernel=False):
    """``decoding.forward_with_paged_cache`` for this family: one decode
    step ([S, 1] tokens). ``pool`` holds two groups: ``k`` / ``v`` [L_full,
    n_blocks, bs, G * dh], every token of a request, through ``tables[0]``
    [S, max_len / bs]; ``wk`` / ``wv`` [L_window, n_ring_blocks, bs, G *
    dh], a ring of blocks a slot, through ``tables[1]`` [S, ring]: block
    ``j`` at column ``j % ring``. Each slot's new row is written at its
    cursor in every layer's group, then attended with the rows before it:
    by the kernel (``kernel``; its band in a window layer) or through the
    view. Returns (logits [S, 1, vocab], pool, routed [L_moe, S, 1, 2k])."""
    from .decoding import _paged_view, _paged_write_rows

    cfg = model.config
    S, q_len = input_ids.shape
    if q_len != 1:
        raise ValueError("window and full attention layers: speculative "
                         "verify (several query rows a slot) is not "
                         "implemented")
    table, wtable = tables
    G, dh = cfg.kv_heads, cfg.head_dim

    def attn(cfg_l, p, h, pool, layer):
        q, k, v, gate = project(cfg_l, p, h, pos[:, None], layer.window)
        names = ("wk", "wv") if layer.window else ("k", "v")
        tab = wtable if layer.window else table
        group = {"k": pool[names[0]], "v": pool[names[1]]}
        rows = {"k": k[:, 0], "v": v[:, 0]}
        write = lambda group: _paged_write_rows(
            group, layer.group, rows, tab, pos, block_size,
            ring=layer.window)
        if kernel:
            from ..ops.pallas.paged_attention import paged_flash_decode

            with jax.named_scope("window_attn_decode" if layer.window
                                 else "full_attn_decode"):
                out = paged_flash_decode(
                    q[:, 0], rows["k"], rows["v"], group["k"], group["v"],
                    tab, pos, layer=layer.group, scale=cfg_l.attn_scale,
                    window=cfg_l.sliding_window if layer.window else 0,
                    ring=layer.window, interpret=cfg_l.attention_interpret,
                    mesh=cfg_l.mesh).reshape(S, -1)
            # the kernel folded the fresh rows in itself; they land after it
            group = write(group)
        else:
            group = write(group)
            with jax.named_scope("window_attn_decode" if layer.window
                                 else "full_attn_decode"):
                views = [_paged_view(group, n, layer.group, tab, G, q.dtype)
                         for n in ("k", "v")]
                k_pos = ring_positions(pos, tab.shape[1], block_size) \
                    if layer.window else jnp.broadcast_to(
                        jnp.arange(views[0].shape[1])[None, :],
                        views[0].shape[:2])
                out = view_attention(cfg_l, q[:, 0], views[0], views[1],
                                     k_pos, pos, layer.window)
        pool = dict(pool, **{names[0]: group["k"], names[1]: group["v"]})
        return _out(p, out[:, None], gate), pool

    x, pool, routed = _run_layers(cfg, params,
                                  _embed(cfg, params, input_ids), dict(pool),
                                  attn)
    return _head(model, params, x), pool, routed
