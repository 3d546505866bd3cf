"""Window and full attention layers mixed, beside drop-free experts (AFMoE,
Arcee Trinity; Xiaomi MiMo-V2 in the "sink" form, below): the block, a stack
whose layers are of two kinds, and the cached forwards.

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

The SINK form (``TransformerConfig.window_block="sink"``, MiMo-V2) has the
same two kinds of layer, the same stack, plan and cache paths, and another
block: ``x += Attn(N1 x); x += FFN(N2 x)``, no q/k norms, no gate, no
embedding scale; q and k rotated over the first ``rotary_dim`` dims of the
head in BOTH kinds (base ``rope_base`` in a full layer, ``rope_base_window``
in a window layer); ``n_kv_heads`` K/V heads in a full layer and
``n_kv_heads_window`` in a window layer; K heads of ``head_dim`` beside V
heads of ``v_head_dim``, V scaled by ``attn_value_scale``; and in a window
layer a learned SINK a query head, a logit that joins the softmax's maximum
and sum and has no value. Because a layer's K and V projections (and the
sink) differ in shape by kind, they are stacked BY KIND (``kv_by_kind_init``:
``params["kv_window"]`` / ``["kv_full"]``, read at a layer's index in its
group), and the blocks' ``attn`` holds q and o alone. What the two forms share
is everything from ``layer_groups`` down (plan, ``blockwise_attention``,
``view_attention``, the three cached forwards); what they do not is
``attention_init``, ``project`` and the residual form of ``_block``.

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
    if cfg.sink_window:
        # q and o alone: K, V and the sink are stacked by kind
        dv = cfg.kv_geometry(False)["v"][1]
        k_q, k_o = jax.random.split(rng)
        std = cfg.initializer_range
        return {"q": L.linear_init(k_q, d, H * dh, ("embed", "heads"), False,
                                   std),
                "o": L.linear_init(k_o, H * dv, d, ("heads", "embed"), False,
                                   out_std)}
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
    if cfg.sink_window:
        return {"ln_1": _norm_init(cfg),
                "attn": attention_init(k_attn, cfg, out_std),
                "ln_2": _norm_init(cfg), "mlp": mlp}
    return {"ln_1": _norm_init(cfg), "ln_1_post": _norm_init(cfg),
            "attn": attention_init(k_attn, cfg, out_std),
            "ln_2": _norm_init(cfg), "ln_2_post": _norm_init(cfg),
            "mlp": mlp}


def kv_by_kind_init(rng, cfg):
    """The sink form's K and V projections, and the window layers' sinks,
    stacked by kind: ``{"kv_window": {"k", "v", "sink"}, "kv_full": {"k",
    "v"}}``, each leaf ``[layers of the kind, ...]`` in the order of
    ``layer_groups`` (the leading dense layers included). The sink is zero,
    as a fresh logit is; a benchmark draws it from its seed."""
    d, std = cfg.d_model, cfg.initializer_range
    rngs = jax.random.split(jax.random.fold_in(rng, 7), cfg.n_layers)
    out = {}
    for name, window, layers in zip(("kv_window", "kv_full"), (True, False),
                                    layer_groups(cfg)):
        (g, dk), (_, dv) = cfg.kv_geometry(window).values()

        def one(r, g=g, dk=dk, dv=dv, window=window):
            k_k, k_v = jax.random.split(r)
            p = {"k": L.linear_init(k_k, d, g * dk, ("embed", "kv"), False,
                                    std),
                 "v": L.linear_init(k_v, d, g * dv, ("embed", "kv"), False,
                                    std)}
            if window:
                p["sink"] = Param(jnp.zeros((cfg.n_heads,), F32), (None,))
            return p

        stacked = jax.vmap(one)(rngs[jnp.asarray(layers)])
        out[name] = jax.tree_util.tree_map(
            lambda q: Param(q.value, ("layers",) + q.axes), stacked,
            is_leaf=lambda x: isinstance(x, Param))
    return out


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
    if cfg.sink_window:
        return _project_sink(cfg, p, h, positions, window)
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


def _project_sink(cfg, p, h, positions, window):
    """``project`` in the sink form: ``p`` holds the layer's q and o and its
    kind's k and v. q and k are rotated over the first ``rotary_dim`` dims in
    both kinds, at the kind's base; v [b, q, G, dv] comes scaled by
    ``attn_value_scale`` (the same as scaling the attention's output, and
    what the cache then holds); no gate.

    The three products stay flat behind an ``optimization_barrier`` (an
    identity): left to it, the TPU compiler carried the head split back
    into each product, wrote every layer's slice of the stacked q, k and v
    kernels out anew (0.73 GB a decode step at the published widths) and
    copied each q slice again
    (``test_compiled_sink_decode_reads_its_attention_weights_in_place``)."""
    b, q_len, _ = h.shape
    (G, dh), (_, dv) = cfg.kv_geometry(window).values()
    q, k, v = jax.lax.optimization_barrier(
        tuple(L.linear_apply(p[n], h) for n in "qkv"))
    q = q.reshape(b, q_len, cfg.n_heads, dh)
    k = k.reshape(b, q_len, G, dh)
    v = v.reshape(b, q_len, G, dv)
    v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
    base = cfg.rope_base_window if window and cfg.rope_base_window \
        else cfg.rope_base
    cos, sin = L.rotary_embedding(positions, cfg.rotary_dim or dh, base)
    q = L.apply_rotary(q, cos, sin, cfg.rotary_dim)
    k = L.apply_rotary(k, cos, sin, cfg.rotary_dim)
    return q, k, v, None


def _out(p, attended, gate):
    return L.linear_apply(p["o"], attended if gate is None
                          else attended * gate)


def _sink(p, window):
    """The layer's sinks [H] in float32 (a window layer of the sink form),
    or None."""
    return p["sink"] if window and "sink" in p else None


def blockwise_attention(cfg, q, read, kv, q_start, window, sink=None,
                        kernel=False):
    """Queries at positions ``q_start + [0, q)`` against context positions
    ``[0, kv)``, given by ``read(start, n) -> (k, v)`` [b, n, G, dh] each:
    one block of ``KV_BLOCK`` positions at a time under an online softmax
    (float32 statistics). The blocks visited run from the one that holds
    the first position any query sees (``q_start - window + 1`` in a window
    layer) to the one that holds the last query. ``sink`` [H] float32: a
    logit a head that the running maximum and sum START from, and that has
    no value. ``kernel``: the forward is never differentiated (the cached
    ones), so a block may be folded by the chunk kernel where ``ops/pallas/
    chunk_attention.py:chunk_attention_path`` allows; the uncached forward
    keeps these einsums. Returns [b, q, H * dv] (``dv`` the V head's
    width)."""
    from ..ops.pallas.chunk_attention import chunk_attention_path

    b, q_len, H, dh = q.shape
    (G, _), (_, dv) = cfg.kv_geometry(window).values()
    dtype = q.dtype
    prec = _prec(dtype)
    scale = _scale(cfg)
    w = cfg.sliding_window if window else 0
    blk = min(KV_BLOCK, kv)
    qg = q.reshape(b, q_len, G, H // G, dh)
    q_idx = q_start + jnp.arange(q_len)
    scope = "full_chunk_attn" if not window \
        else "window_chunk_attn" if sink is None else "sink_window_chunk_attn"
    if kernel and chunk_attention_path(q_len, H // G, blk, w,
                                       cfg.attention_interpret,
                                       cfg.mesh) == "kernel":
        with jax.named_scope(scope):
            out = _blockwise_kernel(cfg, qg, read, kv, q_start, w, sink, dv)
        return out.reshape(b, G, q_len, H // G, dv).transpose(
            0, 2, 1, 3, 4).reshape(b, q_len, H * dv)

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
            jnp.zeros(shape + (dv,), F32))
    if sink is not None:
        init = (jnp.broadcast_to(sink.reshape(1, G, H // G, 1), shape),
                jnp.ones(shape, F32), init[2])
    with jax.named_scope(scope):
        _, l, acc = _visit_blocks(one_block, init, kv, q_start, q_len, w)
        out = (acc / l[..., None]).astype(dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, q_len, H * dv)


def _visit_blocks(one_block, init, kv, q_start, q_len, w):
    """``one_block(i, carry)`` over the blocks of ``KV_BLOCK`` positions
    that hold a position some query sees: from the one that holds ``q_start
    - w + 1`` (0 without a band) to the one that holds the last query."""
    blk = min(KV_BLOCK, kv)
    n_blocks = -(-kv // blk)
    if n_blocks == 1:
        return one_block(0, init)
    hi = jnp.minimum((q_start + q_len + blk - 1) // blk, n_blocks)
    lo = jnp.maximum(q_start - (w - 1), 0) // blk if w else 0
    return jax.lax.fori_loop(lo, hi, one_block, init)


def _blockwise_kernel(cfg, qg, read, kv, q_start, w, sink, dv):
    """``blockwise_attention``'s loop with each block folded by the chunk
    kernel (``ops/pallas/chunk_attention.py``): the same blocks, the same
    mask, the carry in the kernel's layout. qg [b, q, G, R, dk] -> [b, G,
    q * R, dv]."""
    from ..ops.pallas import chunk_attention as C

    b, q_len, G, R, dk = qg.shape
    dtype = qg.dtype
    blk = min(KV_BLOCK, kv)
    rows = q_len * R
    qk = qg.transpose(0, 2, 1, 3, 4).reshape(b, G, rows, dk)
    # row j * R + r is head g * R + r: its sink
    m0 = None if sink is None else jnp.broadcast_to(
        sink.reshape(1, G, 1, R), (1, G, q_len, R)).reshape(1, G, rows)

    def one_block(i, carry):
        start = jnp.minimum(i * blk, kv - blk)
        k, v = read(start, blk)
        to_groups = lambda a: a.astype(dtype).transpose(0, 2, 1, 3)
        return C.chunk_attention_block(
            qk, to_groups(k), to_groups(v), carry, q_start, start, i * blk,
            rep=R, scale=_scale(cfg), window=w,
            interpret=cfg.attention_interpret)

    carry = _visit_blocks(one_block, C.initial_carry(b, G, rows, dv, m0),
                          kv, q_start, q_len, w)
    return C.finish(carry, dtype)


def ring_positions(pos, n_cols, block_size):
    """The position each row of a slot's ring view holds: column ``c`` has
    the newest block ``j <= pos // block_size`` with ``j % n_cols == c``.
    pos [S] -> [S, n_cols * block_size] (negative where no block was yet)."""
    cur = pos // block_size
    col = jnp.arange(n_cols)
    block = cur[:, None] - (cur[:, None] - col[None, :]) % n_cols
    return (block[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]).reshape(pos.shape[0], -1)


def view_attention(cfg, q, k_view, v_view, k_pos, pos, window, sink=None):
    """One query row a slot against its gathered blocks (the new row
    written): q [S, H, dh]; k_view [S, T, G, dh], v_view [S, T, G, dv];
    k_pos [S, T] the position each view row holds; pos [S]; ``sink`` [H]
    float32: one more column of the softmax, dropped from its output.
    Returns [S, H * dv]."""
    S, H, dh = q.shape
    G = k_view.shape[2]
    dtype = q.dtype
    prec = _prec(dtype)
    qg = q.reshape(S, G, H // G, dh)
    s = jnp.einsum("sgrd,stgd->sgrt", qg, k_view.astype(dtype),
                   precision=prec, preferred_element_type=F32) * _scale(cfg)
    allowed = (k_pos <= pos[:, None]) & (k_pos >= 0)
    if window:
        allowed &= pos[:, None] - k_pos < cfg.sliding_window
    s = jnp.where(allowed[:, None, None, :], s, jnp.finfo(F32).min)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.reshape(1, G, H // G, 1), s.shape[:3] + (1,))], axis=-1)
    probs = jax.nn.softmax(s, axis=-1).astype(dtype)
    if sink is not None:
        probs = probs[..., :-1]
    out = jnp.einsum("sgrt,stgd->sgrd", probs, v_view.astype(dtype),
                     precision=prec)
    return out.reshape(S, -1)


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
    if cfg.sink_window:
        # two norms a layer: the branches join the stream as they are
        x = x + a
        y, routed = _ffn(cfg, p["mlp"], _norm_apply(cfg, p["ln_2"], x),
                         stacked)
        return x + y, carry, routed
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
    if cfg.sink_window:
        # a layer's K and V projections (and sink) are its kind's, at its
        # index in its group
        attn_of_kind = attn

        def attn(cfg_l, p_attn, h, carry, layer):
            kv = pick(params["kv_window" if layer.window else "kv_full"],
                      layer.group)
            kv = {n: jax.tree_util.tree_map(
                lambda w: w.astype(F32 if n == "sink"
                                   else cfg_l.compute_dtype), a)
                for n, a in kv.items()}
            return attn_of_kind(cfg_l, {**p_attn, **kv}, h, carry, layer)

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
        out = blockwise_attention(cfg_l, q, read, s, 0, layer.window,
                                  _sink(p, layer.window))
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
            # the layer's own heads of the cache's (the sink form's cache
            # is as wide as its wider kind)
            at = (layer.index, 0, start, 0, 0)
            return (jax.lax.dynamic_slice(cache["k"], at,
                                          (1, b, n) + k.shape[2:])[0],
                    jax.lax.dynamic_slice(cache["v"], at,
                                          (1, b, n) + v.shape[2:])[0])

        out = blockwise_attention(cfg_l, q, read, kv_len, pos, layer.window,
                                  _sink(p, layer.window), kernel=True)
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

    def attn(cfg_l, p, h, pool, layer):
        q, k, v, gate = project(cfg_l, p, h, pos[:, None], layer.window)
        G, sink = k.shape[2], _sink(p, layer.window)
        scope = "full_attn_decode" if not layer.window else \
            "window_attn_decode" if sink is None else "sink_window_attn_decode"
        names = ("wk", "wv") if layer.window else ("k", "v")
        tab = wtable if layer.window else table
        group = {"k": pool[names[0]], "v": pool[names[1]]}
        rows = {"k": k[:, 0], "v": v[:, 0]}
        write = lambda group: _paged_write_rows(
            group, layer.group, rows, tab, pos, block_size,
            ring=layer.window)
        if kernel:
            from ..ops.pallas.paged_attention import paged_flash_decode

            with jax.named_scope(scope):
                out = paged_flash_decode(
                    q[:, 0], rows["k"], rows["v"], group["k"], group["v"],
                    tab, pos, layer=layer.group, scale=cfg_l.attn_scale,
                    window=cfg_l.sliding_window if layer.window else 0,
                    ring=layer.window, interpret=cfg_l.attention_interpret,
                    mesh=cfg_l.mesh, sink=sink).reshape(S, -1)
            # the kernel folded the fresh rows in itself; they land after it
            group = write(group)
        else:
            group = write(group)
            with jax.named_scope(scope):
                views = [_paged_view(group, n, layer.group, tab, G, q.dtype)
                         for n in ("k", "v")]
                k_pos = ring_positions(pos, tab.shape[1], block_size) \
                    if layer.window else jnp.broadcast_to(
                        jnp.arange(views[0].shape[1])[None, :],
                        views[0].shape[:2])
                out = view_attention(cfg_l, q[:, 0], views[0], views[1],
                                     k_pos, pos, layer.window, sink)
        pool = dict(pool, **{names[0]: group["k"], names[1]: group["v"]})
        return _out(p, out[:, None], gate), pool

    x, pool, routed = _run_layers(cfg, params,
                                  _embed(cfg, params, input_ids), dict(pool),
                                  attn)
    return _head(model, params, x), pool, routed
