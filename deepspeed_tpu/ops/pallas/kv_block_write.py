"""Pallas TPU kernel: write KV blocks into the paged pool in place, for a
pool that the device keeps with its block axis in the lanes.

The TPU picks an array's device layout from its shape, to waste the fewest
lanes. A pool leaf ``[L, n_blocks, block_size, *row]`` whose last axis is
under 128 wide gets ``n_blocks`` as its minor-most axis: one 128-lane tile
row holds one (layer, token, row element) of 128 CONSECUTIVE BLOCKS, and one
block is one lane of every such row, spread over the whole pool. A
per-block ``dynamic_update_slice`` therefore rewrites a 128-block column of
the pool for every block (PERF.md, PR 27), and an XLA scatter over the
block axis first copies the whole pool into a block-major layout. Since PR
30 the pool stores per-head K and V merged (``kv_heads * head_dim`` wide),
which keeps a block contiguous at every published width: this kernel runs
only for a pool the device still lays out that way (a latent pool with
small blocks, a merged row narrower than a tile), as ``blocks_in_lanes``
reads off the live array.

The kernel works with that layout instead of against it. Seen as
``[rows, n_blocks]`` (a bitcast of the leaf as the device holds it) the pool
is read and written by 128-block COLUMNS, and only the columns that hold a
target block: each row tile of such a column is read, its target lanes are
filled from the source blocks by one lane gather, and the tile is written
back to the same place (``input_output_aliases``). Device time follows the columns a request
touches (its blocks come off a FIFO free list, so mostly one or two), not
the pool.
"""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANES

ROW_TILE = 2048


def blocks_in_lanes(leaf):
    """Does the device keep this pool leaf (a concrete array) with the block
    axis, 1, minor-most? The layout is the backend's choice and is read off
    the array, not guessed from its shape."""
    layout = leaf.format.layout
    return layout is not None and layout.major_to_minor[-1] == 1


def row_tile(rows, dtype):
    """Rows of one tile of the ``[rows, n_blocks]`` view, or None where no
    tile fits: a power of two that divides ``rows`` and fills whole 32-bit
    sublane groups (the kernel moves 32-bit words). On the chip 512 to 8192
    rows read the same time (PERF.md, PR 27)."""
    least = 8 * (4 // jnp.dtype(dtype).itemsize)
    tile = ROW_TILE
    while tile >= least:
        if rows % tile == 0:
            return tile
        tile //= 2
    return None


def unfit_reason(pool, tp=1):
    """Why the kernel cannot take this pool, or None: every leaf needs a row
    tile at the shape one shard holds (kv heads over ``tp`` when they
    divide)."""
    for a in pool.values():
        # axis 3 carries the kv heads (merged with the head size or alone)
        rows = a.size // a.shape[1] // (tp if a.shape[3] % tp == 0 else 1)
        if row_tile(rows, a.dtype) is None:
            return f"{rows} rows of {a.dtype} a shard divide into no row tile"
    return None


def column_plan(block_ids, src_blocks, n_blocks):
    """What the kernel is steered by, from the padded id arrays of one
    write: ``cols`` (the pool columns that hold a target block, ascending,
    then padding), ``n_cols`` (how many are real) and ``perm``
    ``[n_columns, 1, LANES]`` (for lane j of ``cols[i]``: the source block
    that lands on block ``cols[i] * LANES + j``, or -1 to leave the lane as
    it is). Ids outside ``[0, n_blocks)`` are padding and write nothing."""
    n_columns = -(-n_blocks // LANES)
    valid = (block_ids >= 0) & (block_ids < n_blocks)
    ids = jnp.where(valid, block_ids, n_columns * LANES)
    touched = jnp.zeros((n_columns,), bool).at[ids // LANES].set(
        True, mode="drop")
    cols = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    n_cols = jnp.sum(touched).astype(jnp.int32)
    lane_src = jnp.full((n_columns * LANES,), -1, jnp.int32).at[ids].set(
        src_blocks.astype(jnp.int32), mode="drop")
    perm = lane_src.reshape(n_columns, LANES)[cols]
    return cols, n_cols, perm[:, None, :]


def _write_kernel(cols_ref, perm_ref, src_ref, pool_ref, out_ref):
    """One (row tile, column) cell: ``out = pool`` with the lanes named by
    ``perm`` taken from the source tile. All three tiles are viewed as
    32-bit words (rows packed as the device packs them), so one kernel
    serves bf16, int8 and float32 leaves and moves bits, never values."""
    del cols_ref  # the index maps used it to aim the tile
    old = pltpu.bitcast(pool_ref[...], jnp.uint32)
    src = pltpu.bitcast(src_ref[...], jnp.uint32)
    perm = jnp.broadcast_to(perm_ref[0], old.shape)
    lane, chunk = perm & (LANES - 1), perm >> 7  # -1: chunk -1, no source
    new = old
    for s in range(src.shape[1] // LANES):
        moved = jnp.take_along_axis(
            src[:, s * LANES:(s + 1) * LANES], lane, axis=1)
        new = jnp.where(chunk == s, moved, new)
    out_ref[...] = pltpu.bitcast(new, out_ref.dtype)


def write_block_columns(pool_leaf, src_leaf, cols, n_cols, perm,
                        interpret=False):
    """``pool_leaf[:, b] = src_leaf[:, perm(b)]`` for every target block b of
    ``column_plan``; every other block keeps its bytes.

    ``pool_leaf`` ``[L, n_blocks, bs, *row]`` and ``src_leaf``
    ``[L, n_src, bs, *row]`` share a dtype; ``row_tile`` must fit
    ``L * bs * prod(row)``. The result aliases ``pool_leaf``."""
    n_blocks = pool_leaf.shape[1]
    rows = pool_leaf.size // n_blocks
    tile = row_tile(rows, pool_leaf.dtype)
    n_src = src_leaf.shape[1]
    src_lanes = -(-n_src // LANES) * LANES
    # block axis last: a bitcast for the pool as the device holds it, one
    # small relayout for the source
    pool2 = jnp.moveaxis(pool_leaf, 1, -1).reshape(rows, n_blocks)
    src2 = jnp.moveaxis(src_leaf, 1, -1).reshape(rows, n_src)
    if src_lanes != n_src:
        src2 = jnp.pad(src2, ((0, 0), (0, src_lanes - n_src)))
    out2 = pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # columns innermost: a source tile is fetched once a row tile,
            # not once a column (its block index does not move)
            grid=(rows // tile, n_cols),
            in_specs=[
                pl.BlockSpec((1, 1, LANES), lambda r, i, cols: (i, 0, 0)),
                pl.BlockSpec((tile, src_lanes), lambda r, i, cols: (r, 0)),
                pl.BlockSpec((tile, LANES), lambda r, i, cols: (r, cols[i])),
            ],
            out_specs=pl.BlockSpec((tile, LANES),
                                   lambda r, i, cols: (r, cols[i])),
        ),
        out_shape=jax.ShapeDtypeStruct(pool2.shape, pool2.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
        name="kv_block_write",
    )(cols, perm, src2, pool2)
    return jnp.moveaxis(out2.reshape(
        pool_leaf.shape[:1] + pool_leaf.shape[2:] + (n_blocks,)), -1, 1)
