"""One key block of a prefill chunk's online softmax as one Pallas call: the
scores of a tile of queries against a tile of keys live in VMEM and nowhere
else.

The chunk programs of the window / full attention model
(``models/window_moe.py:blockwise_attention``) and of the latent model
(``models/latent.py:expanded_attention``) visit the context in blocks of
1024-2048 positions under an online softmax, a ``fori_loop`` over the blocks
with a float32 carry ``(m, l, acc)``. Done in XLA, a block's body is a handful
of fusions over a float32 ``[heads, q_len, block]`` score tensor, written to
HBM and read back several times: 268 MB a block a layer at kanana2's 32 heads
x 1024 queries x 2048 keys, 1 GB of traffic for 43 GFLOP of products (PERF.md
section 5). Here the body is one call; the carry is read and written once a
block (the output aliases the input), and nothing ``[.., q_len, block]``-shaped
reaches HBM.

Layout (the callers transpose once a chunk, and the block's K and V once a
block):

- queries ``[b, G, q_len * R, dk]``: the ``R`` query heads of K/V group ``g``
  beside each other under each position (row ``j * R + r`` is position
  ``q_start + j``, head ``g * R + r``), so a tile of ``tq`` positions is
  ``tq * R`` consecutive rows that share the group's keys;
- keys ``[b, G, blk, dk]``, values ``[b, G, blk, dv]`` (``dk != dv``
  allowed);
- the carry: ``stat`` ``[b, G, rows, 128]`` float32 (lane 0 the running
  maximum, lane 1 the running sum: a row of the device's lanes either way) and
  ``acc`` ``[b, G, rows, dv]`` float32.

The mask is the XLA body's: key ``k`` is seen by query ``q`` where ``k <= q``,
``k >= lower`` (the rows of a last block read where it fits that belong to the
block before it) and, with a band ``window``, ``q - k < window``. The grid is
(batch, groups, query tiles, key tiles), key tiles innermost. A query tile
visits only the key tiles that hold a key it sees: the key axis of the grid
counts from the first such tile (so a band of 128 in a block of 1024 costs the
band's tiles), index maps clamp a step past the last one to it (the pipeline
fetches nothing new), and ``pl.when`` skips its work. A tile in which every
query sees every key is computed without the mask.

The running maximum is floored at ``NEG_INF`` (a finite number) instead of
starting at ``-inf``: a masked score is ``-inf``, so a row that sees nothing in
a tile adds ``exp(-inf) = 0`` and keeps its statistics, as the XLA body's
``isfinite`` guards do. A model's own ``(m, l, acc)`` go in through
``initial_carry`` and come out through ``finish``.
"""

import contextlib
import contextvars
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
STAT_LANES = 128
# a tile of query rows (positions x the group's heads) and of keys; a band
# narrower than the block is visited in narrower key tiles. On a v5e a
# block's time follows the score elements and the steps, not the products:
# keys 1024 a tile take 0.63 ms where 512 take 0.93 (kanana2's block, rows
# 512); a band of 128 is 0.96 ms in tiles of 256 keys, 1.02 in tiles of 128.
# Rows 1024 a tile are 0-8% faster again, but Mosaic writes a tile's vector
# code out in full: twice the code and the compile time of a call, paid at
# every call site of every chunk program a server loads (PERF.md section 6)
ROW_TILE = 512
KEY_TILE = 1024
BAND_KEY_TILE = 256
VMEM_LIMIT_BYTES = 48 << 20
# the set ``traced_paths`` is collecting into, while one is open
_TRACED = contextvars.ContextVar("chunk_attention_traced", default=None)


def chunk_tiles(q_len, rep, blk, window=0):
    """``(tq, tk)``: query positions and keys a tile, or None where no tile
    fits the shapes. ``tq * rep`` rows are at most ``ROW_TILE`` and a multiple
    of 8 (or every row), ``tk`` is ``KEY_TILE`` (``BAND_KEY_TILE`` where a
    band narrower than the block is attended) or a power of two down to 128
    dividing ``blk`` (or ``blk`` itself, where it is no larger)."""
    target = BAND_KEY_TILE if 0 < window < blk else KEY_TILE
    tk = blk if blk <= target else next(
        (t for t in (target, target // 2, target // 4, target // 8)
         if t >= 128 and blk % t == 0), None)
    most = max(ROW_TILE // rep, 1)
    tq = q_len if q_len <= most else next(
        (t for t in (1 << p for p in range(most.bit_length() - 1, -1, -1))
         if q_len % t == 0 and (t * rep) % 8 == 0), None)
    return None if tk is None or tq is None else (tq, tk)


def chunk_attention_path(q_len, rep, blk, window=0, interpret=False,
                         mesh=None):
    """Which body a chunk's key block takes: ``"kernel"`` (this file) or
    ``"xla"`` (the model's own einsums). Static, from what a program can see
    when it is traced: the platform it is lowered for (a TPU, or
    ``interpret``: the models' ``attention_interpret``), the devices (GSPMD
    cannot partition a Mosaic call and this one has no ``shard_map``: a
    program over several devices keeps XLA's) and the shapes, which the tiles
    must divide. The serving engine books it by chunk dispatch
    (``snapshot()["kv_pool"]["chunk_attention_dispatches"]``), from what
    ``traced_paths`` collected while the program was traced."""
    from . import unavailable_reason

    ok = chunk_tiles(q_len, rep, blk, window) is not None \
        and unavailable_reason(interpret) is None \
        and (interpret or mesh is None or mesh.size == 1)
    path = "kernel" if ok else "xla"
    seen = _TRACED.get()
    if seen is not None:
        seen.add(path)
    return path


@contextlib.contextmanager
def traced_paths():
    """The set of paths ``chunk_attention_path`` returned while the body of
    this ``with`` ran (a program's forward being traced)."""
    seen = set()
    token = _TRACED.set(seen)
    try:
        yield seen
    finally:
        _TRACED.reset(token)


def initial_carry(b, groups, rows, dv, m0=None):
    """The carry before the first block: ``(stat, acc)``, every row's maximum
    at the floor and its sum 0, or, given ``m0`` (broadcastable to ``[b,
    groups, rows]``: a sink's logit), starting from that maximum with a sum
    of 1 (the sink's own term; it has no value row)."""
    shape = (b, groups, rows)
    lane = jnp.arange(STAT_LANES)
    m = jnp.full(shape, NEG_INF, F32) if m0 is None \
        else jnp.broadcast_to(m0.astype(F32), shape)
    l = jnp.zeros(shape, F32) if m0 is None else jnp.ones(shape, F32)
    stat = jnp.where(lane == 0, m[..., None],
                     jnp.where(lane == 1, l[..., None], 0.0))
    return stat, jnp.zeros(shape + (dv,), F32)


def finish(carry, dtype):
    """The attention output ``[b, groups, rows, dv]`` in ``dtype``: the
    accumulator over the sum."""
    stat, acc = carry
    return (acc / stat[..., 1:2]).astype(dtype)


def _live_tiles(pos_ref, qi, *, tq, tk, blk, window):
    """The key tiles of this block that hold a key some query of tile ``qi``
    sees, ``[first, last]``, and whether there is any: the keys seen are
    ``[lo, hi]``. Scalars (index maps and the kernel alike)."""
    q_start, start, lower = pos_ref[0], pos_ref[1], pos_ref[2]
    qa = q_start + qi * tq
    lo = jnp.maximum(lower, qa - (window - 1)) if window else lower
    hi = jnp.minimum(qa + tq - 1, start + blk - 1)
    first = jnp.minimum((lo - start) // tk, blk // tk - 1)
    last = jnp.maximum(hi - start, 0) // tk
    return first, last, lo <= hi


def _key_index(bi, g, qi, kj, pos_ref, *, tq, tk, blk, window):
    first, last, _ = _live_tiles(pos_ref, qi, tq=tq, tk=tk, blk=blk,
                                 window=window)
    # a step past the last live tile stays on it: nothing new is fetched
    return bi, g, jnp.minimum(first + kj, jnp.maximum(last, first)), 0


def _block_kernel(pos_ref, q_ref, k_ref, v_ref, stat_in, acc_in, stat_ref,
                  acc_ref, *, scale, rep, tq, tk, blk, window, precision):
    """One (query tile, key tile) step: the tile's scores, masked where the
    tile needs it, folded into the running statistics and accumulator the
    output blocks hold over the key steps of the query tile."""
    qi, kj = pl.program_id(2), pl.program_id(3)
    start, lower = pos_ref[1], pos_ref[2]
    first, last, any_live = _live_tiles(pos_ref, qi, tq=tq, tk=tk, blk=blk,
                                        window=window)
    qa = pos_ref[0] + qi * tq
    ka = start + (first + kj) * tk

    @pl.when(kj == 0)
    def _():
        stat_ref[...] = stat_in[...]
        acc_ref[...] = acc_in[...]

    def fold(masked):
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=F32) * scale
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            # key position less the tile's first query position; row
            # ``j * rep + r`` is query ``qa + j``, so ``k <= q`` is
            # ``rel * rep <= row`` and ``q - k < window`` is ``row < (rel +
            # window) * rep``
            rel = ka - qa + col
            seen = (rel * rep <= row) & (ka + col >= lower)
            if window:
                seen &= row < (rel + window) * rep
            s = jnp.where(seen, s, -jnp.inf)
        stat = stat_ref[...]
        m_prev, l_prev = stat[:, 0:1], stat[:, 1:2]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        e = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        v = v_ref[...]
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            e.astype(v.dtype), v, precision=precision,
            preferred_element_type=F32)
        lane = jax.lax.broadcasted_iota(jnp.int32, stat.shape, 1)
        stat_ref[...] = jnp.where(
            lane == 0, m_new,
            jnp.where(lane == 1, l_prev * corr + jnp.sum(e, axis=1,
                                                         keepdims=True), 0.0))

    live = any_live & (first + kj <= last)
    # every key of the tile is seen by every query of it
    whole = (ka + tk - 1 <= qa) & (ka >= lower)
    if window:
        whole &= qa + tq - 1 - ka < window

    @pl.when(live & whole)
    def _():
        fold(False)

    @pl.when(live & jnp.logical_not(whole))
    def _():
        fold(True)


@functools.partial(jax.jit, static_argnames=("rep", "scale", "window",
                                             "tiles", "interpret"))
def chunk_attention_block(q, k, v, carry, q_start, start, lower, *, rep,
                          scale, window=0, tiles=None, interpret=False):
    """Fold one key block into the carry.

    A ``jit`` of its own: the layers of one kind in one program call it with
    the same shapes, so they share one trace and one lowering of the kernel.
    A server pays those per call site, in Python, each time it loads its
    chunk programs (``setup_s``), where a compiled program comes from the
    cache.

    - ``q`` [b, G, q_len * rep, dk]: queries at positions ``q_start + [0,
      q_len)``, the ``rep`` heads of a group under each position;
    - ``k`` [b, G, blk, dk], ``v`` [b, G, blk, dv]: the block's keys and
      values, at positions ``start + [0, blk)``; keys below ``lower`` are
      the block before's and are not seen;
    - ``carry`` ``(stat, acc)``: ``initial_carry``'s form;
    - ``q_start``, ``start``, ``lower``: traced scalars (scalar-prefetched);
      ``window`` (static, 0 = none): a query sees keys ``q - k < window``;
    - ``tiles`` ``(tq, tk)``: ``chunk_tiles``' unless given (tests); ``tq``
      must divide ``q_len`` and ``tk`` ``blk``;
    - ``interpret``: run under the Pallas interpreter (CPU tests).

    Returns the new carry. A float32 ``q`` multiplies in full float32."""
    b, groups, rows, dk = q.shape
    blk, dv = k.shape[2], v.shape[3]
    q_len = rows // rep
    tq, tk = tiles or chunk_tiles(q_len, rep, blk, window)
    assert q_len % tq == 0 and blk % tk == 0, (q_len, blk, tq, tk)
    nkt = blk // tk
    nk = nkt if not 0 < window < blk \
        else min(nkt, (tq + window - 2) // tk + 2)
    stat, acc = carry
    pos = jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                     for x in (q_start, start, lower)])
    static = dict(tq=tq, tk=tk, blk=blk, window=int(window))
    by_row = lambda width: pl.BlockSpec(
        (pl.squeezed, pl.squeezed, tq * rep, width),
        lambda bi, g, qi, kj, pos_ref: (bi, g, qi, 0))
    by_key = lambda width: pl.BlockSpec(
        (pl.squeezed, pl.squeezed, tk, width),
        functools.partial(_key_index, **static))
    precision = jax.lax.Precision.HIGHEST if q.dtype == F32 else None
    itemsize = q.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_block_kernel, scale=scale, rep=rep,
                          precision=precision, **static),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, groups, q_len // tq, nk),
            in_specs=[by_row(dk), by_key(dk), by_key(dv),
                      by_row(STAT_LANES), by_row(dv)],
            out_specs=[by_row(STAT_LANES), by_row(dv)],
        ),
        out_shape=[jax.ShapeDtypeStruct(stat.shape, F32),
                   jax.ShapeDtypeStruct(acc.shape, F32)],
        # operand 0 is the prefetched positions
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * groups * rows * blk * (dk + dv),
            transcendentals=b * groups * rows * blk,
            bytes_accessed=(q.size + k.size + v.size) * itemsize
            + 2 * (stat.size + acc.size) * 4),
        interpret=interpret,
        name="chunk_attention",
    )(pos, q, k, v, stat, acc)
