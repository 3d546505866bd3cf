"""Pallas TPU kernel: the one-token recurrence of a Mamba-2 layer for every
decoding slot, its state read once and written once, in place.

Per slot and head (``models/hybrid.py``): ``S <- dA S + (dt x) (x) B`` and
``y = S C``, S ``[head_dim, state]`` float32, dA a scalar, ``dt x`` a
``head_dim`` vector, B and C ``state`` vectors of the head's group. The
states of all slots and Mamba layers are one leaf ``[L, slots, heads,
head_dim, state]`` of the serving engine, which its decode program donates;
the kernel is handed the whole leaf and a STATIC layer, and writes that
layer's blocks back where they were (``input_output_aliases``), so the step
holds one state and moves each byte of it twice.

XLA's own form (``hybrid.state_update``) reads the state twice and writes
it once a layer: the new state is one fusion's output and ``y`` another's,
and each reads the old state (the v5e compile of the cell's decode
program). The kernel computes both from one read.

Layout: a grid step holds ``slot_block`` slots of one group's heads, the
state's last two axes ``[head_dim, state]`` as they are stored (the state
in the lanes, no padding). What a head needs beside its state is given
with the group's heads in the LANES (``dA`` ``[.., 1, heads/group]``, ``dt
x`` ``[.., head_dim, heads/group]``, ``y`` likewise out), so no operand is
padded more than eightfold in HBM (a few MB a layer against the state's
537 MB), and a head's scalar and column are picked out of them with a lane
mask and a lane sum, which is exact.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def slot_block(n_slots):
    """Slots a grid step holds: 4 where they divide (2 MB of state in, 2
    out, double-buffered inside the 16 MB of VMEM a kernel may take), else
    fewer."""
    for sb in (4, 2, 1):
        if n_slots % sb == 0:
            return sb
    return 1


def update_path(interpret=False, mesh=None):
    """``"kernel"`` or ``"xla"``, from what a program can see when it is
    traced: a TPU target (or ``interpret``) on one device (GSPMD cannot
    partition a Mosaic call, and the recurrence has no ``shard_map``)."""
    from . import unavailable_reason

    ok = unavailable_reason(interpret) is None \
        and (interpret or mesh is None or mesh.size == 1)
    return "kernel" if ok else "xla"


def _kernel(s_ref, da_ref, x_ref, b_ref, c_ref, y_ref, o_ref, *, sb, rep):
    P = x_ref.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (P, rep), 1)
    for i in range(sb):
        b, c = b_ref[i, 0], c_ref[i, 0]              # [1, N]
        da, x = da_ref[i, 0], x_ref[i, 0]            # [1, R], [P, R]
        y = jnp.zeros((P, rep), F32)
        for r in range(rep):
            da_r = jnp.sum(jnp.where(lane[:1] == r, da, 0.0), axis=1,
                           keepdims=True)             # [1, 1]
            x_r = jnp.sum(jnp.where(lane == r, x, 0.0), axis=1,
                          keepdims=True)              # [P, 1]
            new = da_r * s_ref[0, i, r] + x_r * b     # [P, N]
            o_ref[0, i, r] = new
            y = jnp.where(lane == r, jnp.sum(new * c, axis=1, keepdims=True),
                          y)
        y_ref[i, 0] = y


def ssm_state_update(state, layer, da, dtx, b, c, *, interpret=False):
    """``state`` [L, S, H, P, N] float32, updated in place at ``layer`` (a
    static int); ``da`` [S, H] (``exp(dt A)``), ``dtx`` [S, H, P] (``dt
    x``), ``b`` and ``c`` [S, G, N], all float32. Returns (y [S, H, P] =
    S_new C, state)."""
    L, S, H, P, N = state.shape
    G = b.shape[1]
    rep = H // G
    sb = slot_block(S)
    da4 = da.reshape(S, G, 1, rep)
    x4 = dtx.reshape(S, G, rep, P).transpose(0, 1, 3, 2)      # [S, G, P, R]
    b4, c4 = b.reshape(S, G, 1, N), c.reshape(S, G, 1, N)
    small = lambda rows, cols: pl.BlockSpec(
        (sb, 1, rows, cols), lambda i, g: (i, g, 0, 0))
    big = pl.BlockSpec((1, sb, rep, P, N), lambda i, g: (layer, i, g, 0, 0))
    y, state = pl.pallas_call(
        functools.partial(_kernel, sb=sb, rep=rep),
        grid=(S // sb, G),
        in_specs=[big, small(1, rep), small(P, rep), small(1, N),
                  small(1, N)],
        out_specs=[small(P, rep), big],
        out_shape=[jax.ShapeDtypeStruct((S, G, P, rep), F32),
                   jax.ShapeDtypeStruct(state.shape, F32)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="ssm_state_update",
    )(state, da4.astype(F32), x4.astype(F32), b4.astype(F32),
      c4.astype(F32))
    return y.transpose(0, 1, 3, 2).reshape(S, H, P), state
