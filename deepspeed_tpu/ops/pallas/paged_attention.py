"""Paged flash-decode Pallas kernel: decode attention that walks the block
table IN-KERNEL and reads only the KV that is live.

The serving decode hot path is 1 query row per slot against a KV window kept
in a paged pool (``serving/kv_pool.py``): leaves ``[L, n_blocks, block_size,
kv_heads * head_dim]`` and a per-slot block table. A token's row (all its kv
heads side by side) is the leaf's minor-most axis, so the device keeps one
block of tokens CONTIGUOUS, whatever the head size, and one DMA fetches it.
The view path (``models/decoding.py:_paged_view``) gathers an ``n_slots x
max_len`` dense view of one layer through the table, a layer at a time: at
OPT-1.3B's serving geometry that wrote and read 26 GB a step for 3.2 GB that
were live (PERF.md, PR 30). This kernel reads, for each slot, the blocks
below its cursor and nothing else.

Structure:

- grid = (slots,), run in order. The pool leaves stay in HBM (``pl.ANY``),
  whole: the layer is a scalar operand, so the layer loop of the decode
  program hands the kernel its carry and no slice of a leaf is ever copied.
  Block table and cursors are scalar-prefetched.
- a slot's window is walked in CHUNKS of ``chunk_tokens`` (several blocks):
  one DMA a live block of K and of V into one of two VMEM buffers, the next
  chunk (or the next live slot's first chunk) in flight while this one is
  consumed. A loop bounded by the slot's cursor: copies, waits and steps
  follow the live blocks, not ``n_slots x blocks_per_slot``.
- every head at once, on the MXU: the slot's query rows come in
  BLOCK-DIAGONAL form ``[n_heads, kvh * dh]`` (head h's vector in its kv
  group's ``dh`` lanes, zeros elsewhere), so ``q_bd . K^T`` over a
  ``[tokens, kvh * dh]`` tile is every head's scores in one product, with no
  lane slicing at a 64-wide head, and ``P . V`` gives ``[n_heads, kvh * dh]``
  of which head h's own ``dh`` lanes are its output (the rest is discarded
  on the way out). The MXU does 1/kvh useful work; the step is bound by
  streaming the pool, which it now does at the pool's own width.
- float32 scores, softmax and accumulators, as on every attention path. The
  probabilities enter the PV product as a bf16 pair (high part + remainder)
  against a bf16 pool: two exact passes instead of an emulated f32 product.
- the freshly-projected k/v row of the CURRENT token never touches the pool
  before attention: it seeds the running (max, sum, accumulator), exactly
  the value the view path attends at the cursor, so a slot whose cursor is 0
  attends its own row alone and nothing divides by zero.
- a slot's valid pool window is positions ``[0, pos)``. Blocks wholly past
  the cursor are neither copied nor waited for; what a buffer still holds
  there is masked out of the scores, and the V buffers start as zeros, so
  a probability of exactly 0 never meets bytes that were not KV. Unbound
  table columns hold the reserved GARBAGE block and are never live.

A WINDOW layer (``window`` > 0: a query sees the last ``window`` positions,
its own included) walks a band: the walk starts at the chunk that holds
position ``pos - window + 1`` and masks inside it, so the layer reads at most
``window`` rows and a chunk's edge, whatever the cursor. Its blocks may sit in
a RING (``ring``): block ``j`` of a slot at table column ``j % n_cols``, the
window group of ``serving/kv_pool.py``, whose table is as wide as the band.
With ``window`` 0 the program is the one it was before the band existed.

K and V rows may differ in width (MiMo-V2: K heads of 192 beside V heads of
128; the pool's ``k`` leaf is ``kvh * 192`` wide, its ``v`` leaf ``kvh *
128``): the scores are taken over the K row, the accumulator and the output
are as wide as the V row. A per-head SINK (``sink``: one float a query head,
a learned logit with no key and no value) enters the running maximum and sum
beside the current token's row and adds nothing to the output. With equal
widths and no sink the program is the one it was before either existed.

The LATENT form (``paged_latent_decode``: latent attention, MLA, in its
absorbed form, ``models/latent.py``) is the same walk over other operands.
The pool holds one latent row ``c`` a token (``k`` leaf, 512 wide) and one
rotated key ``k_rope`` every head shares (``v`` leaf, 64 wide), so one K/V
"head" serves all query heads. The scores are ``q_lat . c + q_rope .
k_rope``, and the value rows are the latent rows themselves: the ``k`` chunk
buffer feeds both products, the rope buffer the scores alone. The rope leaf
is read token-minor, ``[dr, block]`` a block, which is how the device keeps
a leaf that ends in 64 lanes at blocks of 128, so no copy of it is made. The
output is the slot's ``o_lat`` ``[n_heads, 512]``; ``W_uv`` is applied by
the caller. Its chunk is sized from the row's bytes (``CHUNK_BYTES``). With
no latent operands the program is the one it was before the form existed.

An int8 pool, several query rows a slot (speculative verify) and GPT-Neo's
per-layer traced local flags take the view path (``fused_decode_supported``
says why).

Tier-1 runs the kernel under ``interpret=True`` on the CPU (the models'
``attention_interpret``) and lowers it for the TPU at OPT-1.3B's geometry.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
CHUNK_TOKENS = 256
# the latent form's chunk: as many bytes as CHUNK_TOKENS of OPT-1.3B's rows
# (2 x 2048 bf16 a token) carry, whole blocks of it: its rows are a seventh
# as wide, and a chunk of 256 of them is too little work a step
CHUNK_BYTES = 2 << 20


def fused_decode_supported(cfg, block_size, *, n_slots=8, blocks_per_slot=8,
                           tp=1, kv_dtype="", window=0, ring=False,
                           n_layers=None, n_blocks=None):
    """May the decode program attend through the kernel? ``(ok, reason)``.

    ``window`` / ``ring``: the band of a window layer whose kind is static
    (``models/window_moe.py``), probed at that group's table width.
    ``n_layers`` / ``n_blocks``: the pool leaves the engine really holds
    (one block group's layers and blocks), where they are not ``cfg.n_layers``
    of ``n_slots * blocks_per_slot + 1`` blocks: a probe over leaves larger
    than the device (two full layers of 32 slots x 32k positions at K rows of
    768 are 11 GB a leaf when sized by the table) is refused for its size,
    not for the kernel.

    Structural refusals first (what the kernel does not implement: GPT-Neo's
    traced per-layer local flags, ragged GQA groups, an int8 pool), then the
    question goes to the compiler: the kernel is lowered for the target
    platform at the engine's per-device geometry (``cfg`` heads / ``tp``,
    ``block_size``, table width) and compiled, when the backend is a TPU;
    a refusal comes back as the compiler's own message. A hand-kept rule
    list here once approved a pool blocking Mosaic rejects; the probe must
    never approve a shape that then fails at first dispatch. With
    ``cfg.attention_interpret`` the Pallas interpreter runs the kernel and
    has no layout constraints.
    """
    from . import compiler_verdict, unavailable_reason

    if cfg.latent_attention:
        return _latent_decode_supported(cfg, block_size, n_slots,
                                        blocks_per_slot, kv_dtype, n_layers,
                                        n_blocks)
    if cfg.local_attention_window > 0:
        return False, ("local_attention_window > 0: a layer's kind is a "
                       "traced flag there, and the decode kernel's band is "
                       "static")
    # the K/V heads and row widths of the layers probed: a model of two
    # kinds of layer may give each kind its own (``cfg.kv_geometry``)
    (kv_heads, dh), (_, dv) = cfg.kv_geometry(bool(window)).values()
    if cfg.n_heads % kv_heads:
        return False, (f"n_heads {cfg.n_heads} not a multiple of kv_heads "
                       f"{kv_heads}")
    if kv_dtype:
        return False, (f"a {kv_dtype} pool: the decode kernel reads a pool "
                       "in the engine's dtype")
    if cfg.attention_interpret:
        return True, ""
    reason = unavailable_reason()
    if reason is not None:
        return False, reason
    shard = tp if kv_heads % tp == 0 else 1
    kvh, nh = kv_heads // shard, cfg.n_heads // shard
    sds = jax.ShapeDtypeStruct
    pool = lambda width: sds(
        (n_layers or cfg.n_layers,
         n_blocks or n_slots * blocks_per_slot + 1, block_size,
         kvh * width), cfg.compute_dtype)
    row = lambda width: sds((n_slots, kvh, width), cfg.compute_dtype)
    slopes = jnp.ones((nh,), jnp.float32) \
        if cfg.position_embedding == "alibi" else None
    sink = jnp.zeros((nh,), jnp.float32) \
        if cfg.sink_window and window else None

    def call(q, k_new, v_new, kc, vc, table, pos, layer):
        return paged_flash_decode(q, k_new, v_new, kc, vc, table, pos,
                                  layer=layer, scale=cfg.attn_scale,
                                  alibi_slopes=slopes, window=window,
                                  ring=ring, sink=sink)

    ok, reason = compiler_verdict(
        call, sds((n_slots, nh, dh), cfg.compute_dtype), row(dh), row(dv),
        pool(dh), pool(dv), sds((n_slots, blocks_per_slot), jnp.int32),
        sds((n_slots,), jnp.int32), sds((), jnp.int32))
    return ok, reason and f"TPU compiler: {reason}"


def _latent_decode_supported(cfg, block_size, n_slots, blocks_per_slot,
                             kv_dtype, n_layers, n_blocks):
    """``fused_decode_supported`` for a latent-attention model: the latent
    form put to the compiler at the pool's 5-D leaves ``[L, n_blocks, bs, 1,
    width]`` as the engine holds them, with the scale and chunk the decode
    program uses, so that the probe's trace is the program's."""
    from ...models.latent import score_scale
    from . import compiler_verdict, unavailable_reason

    if kv_dtype:
        return False, (f"a {kv_dtype} pool: the decode kernel reads a pool "
                       "in the engine's dtype")
    if cfg.attention_interpret:
        return True, ""
    reason = unavailable_reason()
    if reason is not None:
        return False, reason
    sds = jax.ShapeDtypeStruct
    dt = cfg.compute_dtype
    H, r, dr = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    pool = lambda width: sds(
        (n_layers or cfg.n_layers,
         n_blocks or n_slots * blocks_per_slot + 1, block_size, 1, width), dt)

    def call(q_lat, q_rope, c_new, kr_new, kc, krc, table, pos, layer):
        return paged_latent_decode(q_lat, q_rope, c_new, kr_new, kc, krc,
                                   table, pos, layer=layer,
                                   scale=score_scale(cfg))

    ok, reason = compiler_verdict(
        call, sds((n_slots, H, r), dt), sds((n_slots, H, dr), dt),
        sds((n_slots, r), dt), sds((n_slots, dr), dt), pool(r), pool(dr),
        sds((n_slots, blocks_per_slot), jnp.int32),
        sds((n_slots,), jnp.int32), sds((), jnp.int32))
    return ok, reason and f"TPU compiler: {reason}"


def _decode_kernel(layer_ref, table_ref, pos_ref, q_ref, kn_ref, vn_ref,
                   *rest, scale, block_size, chunk_blocks, head_dim, alibi,
                   window, ring, sink=False, latent=False):
    """One slot: walk its live blocks chunk by chunk, fold each chunk into
    the running (m, l, acc), emit the slot's normalized output rows.

    ``q_ref`` [1, n_heads, W] is the block-diagonal query (W = kvh * dh),
    ``kn_ref``/``vn_ref`` [1, 1, W] the current token's fresh row; ``k_hbm``/
    ``v_hbm`` [L, n_blocks, bs, W] stay in HBM and are copied by block;
    ``o_ref`` [1, hq, W]: row j holds, in kv group g's lanes, the output of
    head ``g * hq + j``. (Where V rows are narrower than K rows, every V-side
    W is ``kvh * head_dim`` with ``head_dim`` the V head's; ``sink``: a
    ``[n_heads, 1]`` operand after the slopes.)
    ``kbuf``/``vbuf`` [2, chunk, W] are the two chunk
    buffers, ``cur_ref`` the buffer the next chunk to consume lands in (it
    outlives a grid step: the next live slot's first chunk is already in
    flight when its step begins). ``window`` > 0: the valid pool window is
    ``[max(pos - window + 1, 0), pos)`` and the walk starts at its chunk;
    ``ring``: block ``j`` sits at table column ``j % n_cols``.
    ``latent``: ``q_ref`` [1, n_heads, r] is ``q_lat`` and ``qr_ref`` [1,
    n_heads, dr] (the last operand before the pool) ``q_rope``, ``kn_ref`` /
    ``vn_ref`` the fresh ``c`` / ``k_rope``; ``v_hbm`` is the rope leaf
    token-minor, [L, n_blocks, dr, bs], and ``vbuf`` [2, dr, chunk]; the
    value rows are ``kbuf``'s and ``o_ref`` [1, n_heads, r] is ``o_lat``."""
    if alibi:
        slopes_ref, rest = rest[0], rest[1:]
    if sink:
        sink_ref, rest = rest[0], rest[1:]
    if latent:
        qr_ref, rest = rest[0], rest[1:]
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, acc_scr, cur_ref = rest
    n_slots, n_cols = table_ref.shape
    n_heads, width = q_ref.shape[1], o_ref.shape[2]
    hq = o_ref.shape[1]
    chunk = chunk_blocks * block_size

    s = pl.program_id(0)
    layer = layer_ref[0]
    pos = pos_ref[s]                       # valid pool window = [0, pos)
    n_chunks = (pos + chunk - 1) // chunk

    def band_start(slot):
        """First position of ``slot``'s valid pool window."""
        return jnp.maximum(pos_ref[slot] - (window - 1), 0) if window else 0

    def first_chunk(slot):
        return band_start(slot) // chunk if window else 0

    def for_live_blocks(slot, c, buf, act):
        """``act`` on the K and the V copy of every live block of chunk
        ``c`` of ``slot``, aimed at buffer ``buf``: the same descriptors
        start a chunk and wait for it."""
        for j in range(chunk_blocks):
            col = c * chunk_blocks + j
            live = col * block_size < pos_ref[slot]
            if window:
                live &= (col + 1) * block_size > band_start(slot)

            @pl.when(live)
            def _():
                blk = table_ref[slot, col % n_cols if ring
                                else jnp.minimum(col, n_cols - 1)]
                rows = pl.ds(j * block_size, block_size)
                act(pltpu.make_async_copy(
                    k_hbm.at[layer, blk], kbuf.at[buf, rows], sem.at[0, buf]))
                act(pltpu.make_async_copy(
                    v_hbm.at[layer, blk],
                    vbuf.at[buf, :, rows] if latent else vbuf.at[buf, rows],
                    sem.at[1, buf]))

    start = lambda slot, c, buf: for_live_blocks(
        slot, c, buf, lambda copy: copy.start())
    wait = lambda slot, c, buf: for_live_blocks(
        slot, c, buf, lambda copy: copy.wait())

    def start_next_live(after, buf):
        """Start chunk 0 of the first slot past ``after`` whose cursor is
        not 0 (the slots between have nothing in the pool to read)."""
        nxt = jax.lax.fori_loop(
            0, n_slots,
            lambda i, found: jnp.where(
                (n_slots - 1 - i > after) & (pos_ref[n_slots - 1 - i] > 0),
                n_slots - 1 - i, found),
            n_slots)

        @pl.when(nxt < n_slots)
        def _():
            start(nxt, first_chunk(nxt), buf)

    @pl.when(s == 0)
    def _first():
        # a masked token's probability is exactly 0, and 0 x what a fresh
        # V buffer holds must be 0: only KV (or these zeros) is ever in one.
        # (K needs none: a masked score is replaced, whatever it was. The
        # latent form's value rows are its K buffer's, and its rope buffer
        # feeds the scores alone.)
        values = kbuf if latent else vbuf
        values[...] = jnp.zeros_like(values)
        cur_ref[0] = 0
        start_next_live(-1, 0)

    buf0 = cur_ref[0]
    c0 = first_chunk(s)
    # the allowlisted attention-f32 island (sanitizer ATTENTION_F32_ALLOW):
    # logits, softmax and the PV accumulator run fp32 on purpose
    with jax.named_scope("paged_flash_decode"):
        q = q_ref[0]                                           # [nh, W]
        # the current token's own row (position pos, alibi distance 0)
        m0 = jnp.sum(q.astype(jnp.float32) * kn_ref[0].astype(jnp.float32),
                     axis=-1, keepdims=True)                   # [nh, 1]
        if latent:
            qr = qr_ref[0]                                     # [nh, dr]
            m0 = m0 + jnp.sum(qr.astype(jnp.float32)
                              * vn_ref[0].astype(jnp.float32),
                              axis=-1, keepdims=True)
        m0 = m0 * scale
        acc_scr[...] = jnp.broadcast_to(
            (kn_ref if latent else vn_ref)[0].astype(jnp.float32),
            acc_scr.shape)
        l0 = None
        if sink:
            # the sink's logit joins the maximum and the sum beside the
            # current token's row, which it scales, and has no value row
            own = m0
            m0 = jnp.maximum(own, sink_ref[...])
            l0 = jnp.exp(own - m0) + jnp.exp(sink_ref[...] - m0)
            acc_scr[...] = acc_scr[...] * jnp.exp(own - m0)

        def fold(c, carry):
            m_prev, l_prev = carry
            buf = (buf0 + c - c0) % 2 if window else (buf0 + c) % 2

            @pl.when(c + 1 < n_chunks)
            def _():
                start(s, c + 1, 1 - buf)

            @pl.when(c + 1 == n_chunks)
            def _():
                start_next_live(s, 1 - buf)

            wait(s, c, buf)
            k = kbuf[buf]                                      # [chunk, W]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)            # [nh, chunk]
            if latent:
                sc = sc + jnp.dot(qr, vbuf[buf],
                                  preferred_element_type=jnp.float32)
            sc = sc * scale
            t = c * chunk + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            if alibi:
                # slopes * (kv_pos - cursor): the same int difference, then
                # fp32 multiply, as the view path's per-row alibi
                sc = sc + slopes_ref[...] * (t - pos).astype(jnp.float32)
            valid = t < pos
            if window:
                valid &= t >= band_start(s)
            sc = jnp.where(valid, sc, NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            v = k if latent else vbuf[buf]                     # [chunk, W]
            if v.dtype == jnp.bfloat16:
                hi = p.astype(jnp.bfloat16)
                lo = (p - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                pv = jnp.dot(hi, v, preferred_element_type=jnp.float32) \
                    + jnp.dot(lo, v, preferred_element_type=jnp.float32)
            else:
                pv = jnp.dot(p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
            acc_scr[...] = acc_scr[...] * corr + pv
            return m_new, l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)

        _, l_fin = jax.lax.fori_loop(
            c0, n_chunks, fold, (m0, jnp.ones_like(m0) if l0 is None else l0))
        cur_ref[0] = (buf0 + n_chunks - c0) % 2 if window \
            else (buf0 + n_chunks) % 2

        # head h keeps its own kv group's lanes: row j of the output holds
        # head g * hq + j in the lanes of group g
        acc = acc_scr[...] / l_fin
        if latent:
            # one K/V row every head shares: row h is head h's
            o_ref[0] = acc
            return
        head = jax.lax.broadcasted_iota(jnp.int32, (n_heads, width), 0)
        group = jax.lax.broadcasted_iota(
            jnp.int32, (n_heads, width), 1) // head_dim
        for j in range(hq):
            o_ref[0, pl.ds(j, 1), :] = jnp.sum(
                jnp.where(head == group * hq + j, acc, 0.0), axis=0,
                keepdims=True)


def paged_flash_decode(q, k_new, v_new, kc, vc, table, pos, *, layer=None,
                       scale=None, alibi_slopes=None, window=0, ring=False,
                       sink=None, chunk_tokens=CHUNK_TOKENS, interpret=False,
                       mesh=None):
    """Paged decode attention: softmax(q·K/√d)·V for ONE query row per slot,
    where K/V live in the paged pool and the kernel walks the block table
    itself, reading only the blocks below each slot's cursor.

    - ``q``: [S, n_heads, dh] (compute dtype), this step's query rows;
    - ``k_new``/``v_new``: [S, kvh, dh] and [S, kvh, dv], the
      freshly-projected k/v of the current token (NOT yet in the pool;
      logically at position ``pos[s]``); ``dv`` may differ from ``dh``;
    - ``kc``/``vc``: the pool leaves WHOLE, [L, n_blocks, block_size,
      kvh * dh] and [.., kvh * dv], with ``layer`` (a traced scalar) the
      layer to read; or one layer [n_blocks, block_size, ..] with ``layer``
      None;
    - ``table``: [S, NB] int32 physical block ids; ``pos``: [S] int32
      cursors. Pool positions [0, pos) are attended; everything past the
      cursor (a ragged tail, unbound garbage-block columns) is never read;
    - ``window`` (static; 0 = none): a query sees the last ``window``
      positions, its own included: pool positions ``[max(pos - window + 1,
      0), pos)`` are attended, and nothing before them is read. ``ring``
      (static): block ``j`` of a slot sits at table column ``j % NB`` (a
      table as wide as the band; ``NB * block_size >= window + block_size``);
    - ``sink`` ([n_heads] float, optional): a logit a query head that joins
      the softmax's maximum and sum and has no value (MiMo-V2's window
      layers);
    - ``chunk_tokens``: tokens consumed a step of the kernel's inner loop
      (rounded down to whole blocks);
    - ``interpret``: run under the Pallas interpreter (the models'
      ``attention_interpret``; CPU tests);
    - ``mesh``: the mesh the decode program is partitioned over. On more
      than one device the kernel runs inside a ``shard_map`` with the kv
      heads (contiguous groups of the pool's merged axis) and their query
      groups split over ``model`` when they divide it: GSPMD cannot
      partition a Mosaic call.

    Returns [S, n_heads, dv] in ``q.dtype``.
    """
    from . import shard_kernel

    if layer is None:
        kc, vc, layer = kc[None], vc[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    head_axes = ("model",) if k_new.shape[1] % tp == 0 else ()
    at = lambda dim: {dim: head_axes}
    operands = [q, k_new, v_new, kc, vc, table, pos, layer]
    dim_axes = [at(1), at(1), at(1), at(3), at(3), {}, {}, {}]
    for per_head in (alibi_slopes, sink):
        if per_head is not None:
            operands.append(jnp.asarray(per_head, jnp.float32))
            dim_axes.append(at(0))

    def per_shard(q, k_new, v_new, kc, vc, table, pos, layer, *per_head):
        per_head = list(per_head)
        slopes = per_head.pop(0) if alibi_slopes is not None else None
        return _paged_flash_decode(q, k_new, v_new, kc, vc, table, pos,
                                   layer, slopes, scale, chunk_tokens,
                                   interpret, window, ring,
                                   per_head[0] if per_head else None)

    return shard_kernel(per_shard, mesh, operands, dim_axes, [at(1)])


def _paged_flash_decode(q, k_new, v_new, kc, vc, table, pos, layer, slopes,
                        scale, chunk_tokens, interpret, window=0, ring=False,
                        sink=None):
    """One device's share of ``paged_flash_decode`` (local head counts)."""
    s_dim, n_heads, dh = q.shape
    kvh, dv = k_new.shape[1], v_new.shape[2]
    block_size, width, width_v = kc.shape[2], kc.shape[3], vc.shape[3]
    hq = n_heads // kvh
    assert width == kvh * dh and width_v == kvh * dv, \
        (kc.shape, k_new.shape, vc.shape, v_new.shape)
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    alibi = slopes is not None
    chunk_blocks = max(1, min(chunk_tokens // block_size, table.shape[1]))

    # block-diagonal queries: head h = g * hq + j lives in group g's lanes
    own = jnp.arange(n_heads)[:, None] // hq == jnp.arange(kvh)[None, :]
    q_bd = jnp.where(own[None, :, :, None], q[:, :, None, :], 0) \
        .reshape(s_dim, n_heads, width).astype(kc.dtype)
    row = lambda a: a.reshape(s_dim, 1, -1)

    in_specs = [_per_slot(n_heads, width), _per_slot(1, width),
                _per_slot(1, width_v)]
    operands = [q_bd, row(k_new), row(v_new)]
    for per_head in (slopes, sink):
        if per_head is not None:
            in_specs.append(pl.BlockSpec((n_heads, 1), lambda s, *_: (0, 0)))
            operands.append(per_head.reshape(n_heads, 1))

    chunk = chunk_blocks * block_size
    out = _call(
        dict(scale=scale, block_size=block_size, chunk_blocks=chunk_blocks,
             head_dim=dv, alibi=alibi, window=int(window), ring=bool(ring),
             sink=sink is not None),
        in_specs, operands, kc, vc,
        [pltpu.VMEM((2, chunk, width), kc.dtype),
         pltpu.VMEM((2, chunk, width_v), vc.dtype)],
        (hq, width_v), layer, table, pos, interpret)
    # [S, hq, kvh, dh] -> head h = g * hq + j
    return out.reshape(s_dim, hq, kvh, dv).transpose(0, 2, 1, 3) \
        .reshape(s_dim, n_heads, dv).astype(q.dtype)


def _per_slot(*shape):
    """A block of one slot's rows of a ``[S, *shape]`` operand."""
    return pl.BlockSpec((1,) + shape, lambda s, *_: (s,) + (0,) * len(shape))


def _call(kernel_kw, in_specs, operands, kc, vc, buffers, out_rows, layer,
          table, pos, interpret):
    """The one ``pallas_call`` of every form: a grid step a slot, the layer,
    table and cursors scalar-prefetched, the pool leaves ``kc`` / ``vc``
    whole in HBM after ``operands``, the two chunk ``buffers`` beside the
    DMA semaphores, the float32 accumulator and the buffer cursor. Returns
    the float32 ``[S, *out_rows]``."""
    n_heads, width_v = operands[0].shape[1], out_rows[1]
    return pl.pallas_call(
        functools.partial(_decode_kernel, **kernel_kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(table.shape[0],),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=_per_slot(*out_rows),
            scratch_shapes=buffers + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_heads, width_v), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((table.shape[0],) + tuple(out_rows),
                                       jnp.float32),
        # slots in order: a step starts the next live slot's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_flash_decode",
    )(layer, table, pos, *operands, kc, vc)


def paged_latent_decode(q_lat, q_rope, c_new, kr_new, kc, krc, table, pos, *,
                        layer, scale, chunk_tokens=None, interpret=False,
                        mesh=None):
    """The kernel's LATENT form: absorbed latent attention (MLA) for ONE
    query row a slot over the paged pool of latent rows, reading only the
    blocks below each slot's cursor.

    - ``q_lat``: [S, H, r], the query folded through ``W_uk``; ``q_rope``:
      [S, H, dr], its rotated part;
    - ``c_new`` / ``kr_new``: [S, r] / [S, dr], the current token's normed
      latent and rotated key (logically at position ``pos[s]``);
    - ``kc`` / ``krc``: the pool's leaves whole, [L, n_blocks, bs, 1, r] and
      [L, n_blocks, bs, 1, dr], and ``layer`` (a traced scalar) the layer to
      read;
    - ``table`` / ``pos``, ``chunk_tokens``, ``interpret``, ``mesh``: as
      ``paged_flash_decode`` (``chunk_tokens`` None: ``CHUNK_BYTES`` of
      rows). ``scale`` is the score scale, ``1 / sqrt(dn + dr)``.

    Returns ``o_lat`` = softmax(scale (q_lat . c + q_rope . k_rope)) . c,
    [S, H, r] in ``q_lat.dtype``.
    """
    from . import shard_kernel

    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    kc, krc = (leaf.reshape(leaf.shape[:3] + (-1,)) for leaf in (kc, krc))
    operands = [q_lat, q_rope, c_new, kr_new, kc, krc, table, pos, layer]
    return shard_kernel(
        functools.partial(_paged_latent_decode, scale=scale,
                          chunk_tokens=chunk_tokens, interpret=interpret),
        mesh, operands, [{}] * len(operands), [{}])


@functools.partial(jax.jit, static_argnames=("scale", "chunk_tokens",
                                             "interpret"))
def _paged_latent_decode(q_lat, q_rope, c_new, kr_new, kc, krc, table, pos,
                         layer, *, scale, chunk_tokens, interpret):
    """``paged_latent_decode`` on one device: a ``jax.jit`` of its own, so
    that a program's layers, and the engine's probe before them, share one
    trace and lowering."""
    s_dim, n_heads, r = q_lat.shape
    dr = q_rope.shape[2]
    block_size = kc.shape[2]
    dt = kc.dtype
    if chunk_tokens is None:
        chunk_tokens = CHUNK_BYTES // ((r + dr) * dt.itemsize)
    chunk_blocks = max(1, min(chunk_tokens // block_size, table.shape[1]))
    chunk = chunk_blocks * block_size
    # the rope leaf token-minor, [L, n_blocks, dr, bs]: the device keeps a
    # leaf that ends in 64 lanes with its 128 tokens there, so this is that
    # layout's bitcast, and a block's rope keys are one [dr, bs] copy
    krt = jnp.swapaxes(krc, 2, 3)
    out = _call(
        dict(scale=scale, block_size=block_size, chunk_blocks=chunk_blocks,
             head_dim=r, alibi=False, window=0, ring=False, latent=True),
        [_per_slot(n_heads, r), _per_slot(1, r), _per_slot(1, dr),
         _per_slot(n_heads, dr)],
        [q_lat.astype(dt), c_new.astype(dt).reshape(s_dim, 1, r),
         kr_new.astype(dt).reshape(s_dim, 1, dr), q_rope.astype(dt)],
        kc, krt,
        [pltpu.VMEM((2, chunk, r), dt), pltpu.VMEM((2, dr, chunk), dt)],
        (n_heads, r), layer, table, pos, interpret)
    return out.astype(q_lat.dtype)
