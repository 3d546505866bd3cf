"""Grouped matrix product Pallas kernel: rows sorted by group times each
group's own matrix, the weights read IN PLACE from a stack of groups.

The drop-free expert layer (``moe/dropfree.py``) orders a program's
token-expert pairs by expert and multiplies each expert's rows by that
expert's weights. In a prefill chunk that is thousands of rows over 128
experts, 48-64 rows a group on average and very uneven (one expert of a
1,024-token chunk gets about 260 pairs, a few get none), and the product is
bound by reading every expert's weights once, not by the MXU.
``jax.lax.ragged_dot`` reaches about a third of that floor there, and its
time follows the groups that own rows rather than the rows (PERF.md, PR 36).
This kernel reads each such group's weights once, in blocks of megabytes.

Structure (after ``jax/experimental/pallas/ops/tpu/megablox/gmm.py``, cut to
what the layer needs and tiled for its shapes):

- rows ``[M, K]`` are cut into tiles of ``tm`` rows, the output's columns
  into tiles of ``tn``; K is taken WHOLE, so a product is one pass of the
  MXU over a ``[tm, K] x [K, tn]`` pair, accumulated in float32 and cast
  once: no partial sums travel.
- a VISIT is one (row tile, group) pair in which the group owns rows of the
  tile. ``group_visits`` lists them in row order from ``group_sizes``: a
  group is visited once for every row tile it touches and never otherwise, so
  an empty group (every group of the other layers of a stack) costs nothing,
  not even a grid step: the grid's extent is the number of visits, a value of
  the program.
- grid = (column tiles, visits), visits innermost, run in order. The rows
  and the output tile are the pipeline's: they stay in VMEM over the
  consecutive visits of one row tile (the groups that share it), each of
  which stores only its own rows.
- the weights ``[G, K, N]`` stay whole in HBM (``pl.ANY``): a caller that
  holds a stack ``[L, E, K, N]`` reshapes it to ``L * E`` groups (free) and
  no layer is ever sliced out of it. A group's ``[K, tn]`` block is copied
  ONCE, into one of two VMEM buffers, and the copy of the NEXT group with
  rows (scalar-prefetched: ``next_live``) starts at the group's FIRST visit,
  so it has all of the group's visits to arrive in. Left to the pipeline,
  which looks one grid step ahead, a block would be asked for only at the
  group's last visit, and every visit before that computes with nothing in
  flight: a third of a chunk's visits, 16-20% of its time (PERF.md, PR 36).

A visit multiplies the whole ``tm`` rows whoever owns them, so the MXU does
(visits x tm) / M times the pairs' operations: about 3 at ``tm`` 128 and 64
rows a group. That stays under the time the weights take to arrive: on a v5e
``tm`` 64 and 128 read within 6% of each other at a chunk's shapes (256 is
10-18% slower), and the width of a block decides as much (half of N is 0-5%
slower than N whole). At the serve cells' shapes the products take 68-79% of
the time their weights' bytes take at the published bandwidth.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# a weight block [K, tn] is a few MB (two are in flight), and the whole
# working set stays well inside a v5e core's 128 MiB of VMEM
WEIGHT_BLOCK_BYTES = 8 << 20
VMEM_LIMIT_BYTES = 48 << 20
ROW_TILES = (128, 64, 32, 16, 8)


def row_tile(m):
    """The largest of ``ROW_TILES`` that divides ``m`` rows (128: about a
    group or two of a chunk), or None: the kernel does not take such an
    ``m``."""
    return next((t for t in ROW_TILES if m % t == 0), None)


def choose_tiles(m, k, n, itemsize):
    """``(tm, tn)`` for rows ``[m, k]`` times ``[*, k, n]``: ``tm`` is
    ``row_tile(m)``, ``tn`` the widest multiple of 128 dividing ``n`` whose
    ``[k, tn]`` block is at most ``WEIGHT_BLOCK_BYTES`` (``n`` whole where it
    is no multiple of 128: the interpreter's tiny models)."""
    if n % 128:
        return row_tile(m), n
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize <= WEIGHT_BLOCK_BYTES]
    return row_tile(m), (fits[-1] if fits else 128)


def group_visits(group_sizes, m, tm):
    """The (row tile, group) pairs to compute, in row order, and the order
    in which the groups' weights are wanted.

    ``group_sizes`` [G] int32 (zeros allowed, summing to ``m``) ->
    ``(offsets [G + 1], visit_group [V], visit_tile [V], next_live [G],
    n_visits)``: group g owns rows ``[offsets[g], offsets[g + 1])``; visit v
    multiplies row tile ``visit_tile[v]`` by group ``visit_group[v]``; only
    the first ``n_visits`` (a traced count) are real. ``V = m // tm + min(G,
    m) - 1`` bounds them: every row tile once, and once more for each group
    that starts inside a tile. ``next_live[g]`` is the next group after g
    that owns rows; after the last such group it is the FIRST one (so it
    wraps exactly where ``next_live[g] <= g``)."""
    n_groups = group_sizes.shape[0]
    ends = jnp.cumsum(group_sizes)
    first = (ends - group_sizes) // tm
    tiles = jnp.where(group_sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    v = jnp.arange(m // tm + min(n_groups, m) - 1, dtype=jnp.int32)
    group = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"),
                        n_groups - 1).astype(jnp.int32)
    tile = first[group] + v - (visit_ends[group] - tiles[group])
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # the live groups at or after g, smallest first: a reversed running min
    at_or_after = jax.lax.cummin(jnp.where(
        group_sizes > 0, jnp.arange(n_groups, dtype=jnp.int32), n_groups),
        reverse=True)
    after = jnp.concatenate(
        [at_or_after[1:], jnp.full((1,), n_groups, jnp.int32)])
    next_live = jnp.where(after < n_groups, after, at_or_after[0])
    return (offsets, group, jnp.clip(tile, 0, m // tm - 1), next_live,
            visit_ends[-1])


def _visit_kernel(offsets_ref, group_ref, tile_ref, next_ref, rows_ref,
                  w_hbm, out_ref, wbuf, sem, slot_ref, *, precision):
    """One visit: the tile's rows times the group's block, stored to the
    rows the group owns. The other rows of the output tile keep what the
    tile's earlier visits stored (or are stored by its later ones).

    ``w_hbm`` [G, K, N] stays in HBM; ``wbuf`` [2, K, tn] holds this group's
    block and the next one's, ``slot_ref`` which of the two is this group's
    (it outlives a grid step). At a group's first visit the next live
    group's block is started into the other buffer (of the next column tile
    after the last group) and this group's, started a group ago, is waited
    for."""
    j, v = pl.program_id(0), pl.program_id(1)
    g = group_ref[v]
    tm, tn = out_ref.shape

    def block(group, col, slot):
        return pltpu.make_async_copy(
            w_hbm.at[group, :, pl.ds(pl.multiple_of(col * tn, tn), tn)],
            wbuf.at[slot], sem.at[slot])

    very_first = (j == 0) & (v == 0)

    @pl.when(very_first)
    def _():
        block(g, 0, 0).start()

    @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
    def _():
        slot = jnp.where(very_first, 0, 1 - slot_ref[0])
        slot_ref[0] = slot
        next_col = j + (next_ref[g] <= g).astype(jnp.int32)

        @pl.when(next_col < pl.num_programs(0))
        def _():
            block(next_ref[g], next_col, 1 - slot).start()

        block(g, j, slot).wait()

    prod = jnp.dot(rows_ref[...], wbuf[slot_ref[0]], precision=precision,
                   preferred_element_type=jnp.float32)
    row = tile_ref[v] * tm + jax.lax.broadcasted_iota(
        jnp.int32, prod.shape, 0)
    mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
    out_ref[...] = jnp.where(mine, prod.astype(out_ref.dtype), out_ref[...])


def grouped_matmul(rows, w, group_sizes, *, tiles=None, precision=None,
                   interpret=False):
    """``rows`` [M, K], sorted by group, times ``w[g]`` [K, N] for the rows
    of group g -> [M, N] in ``rows.dtype``, accumulated in float32 over the
    whole of K.

    - ``w`` [G, K, N] in ``rows.dtype``, read in place: only the groups that
      own rows are touched;
    - ``group_sizes`` [G] int32, zeros allowed, SUMMING TO M (every row has a
      group: what the expert layer hands over; a row past the last group
      would be left unwritten);
    - ``tiles`` ``(tm, tn)``: ``choose_tiles``' unless given (the kernel
      check's sweep); ``tm`` must divide M and ``tn`` N;
    - ``interpret``: run under the Pallas interpreter (CPU tests).
    """
    m, k = rows.shape
    n_groups, k_w, n = w.shape
    assert k == k_w and w.dtype == rows.dtype, (rows.shape, w.shape, w.dtype)
    tm, tn = tiles or choose_tiles(m, k, n, rows.dtype.itemsize)
    assert m % tm == 0 and n % tn == 0, (m, n, tm, tn)
    *prefetched, n_visits = group_visits(group_sizes.astype(jnp.int32), m, tm)
    # index maps see the grid position, then the prefetched arrays
    by_tile = lambda at: lambda j, v, off, grp, til, nxt: at(til[v], j)

    return pl.pallas_call(
        functools.partial(_visit_kernel, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetched),
            grid=(n // tn, n_visits),
            in_specs=[pl.BlockSpec((tm, k), by_tile(lambda t, j: (t, 0))),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn), by_tile(lambda t, j: (t, j))),
            scratch_shapes=[pltpu.VMEM((2, k, tn), rows.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        # in order: an output tile is revisited only consecutively, and a
        # visit starts the copy a later one waits for
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(min(n_groups, m) * k * n + m * k * (n // tn)
                            + m * n) * rows.dtype.itemsize),
        interpret=interpret,
        name="grouped_matmul",
    )(*prefetched, rows, w)
