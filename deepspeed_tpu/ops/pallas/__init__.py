"""Pallas TPU kernels, and the rules every dispatcher to them shares.

1. **Which platform a kernel is lowered for** is ``target_platform()``: the
   default backend, or the platform named by an enclosing
   ``lowering_target(...)`` (ahead-of-time lowering for a TPU from a CPU host:
   ``tests/unit/test_tpu_lowering.py``, compile-only tools). A Mosaic kernel
   is emitted only for ``"tpu"``; everywhere else a kernel runs only when the
   caller asks for ``interpret=True`` (the models' ``attention_interpret``).
2. **A Mosaic kernel cannot be partitioned by GSPMD** ("Mosaic kernels cannot
   be automatically partitioned. Please wrap the call in a shard_map",
   ``jax/_src/tpu_custom_call.py``). ``shard_kernel`` wraps the call in a
   ``shard_map`` that is manual over every mesh axis not already manual, with
   the operand dims the kernel is independent over (batch, heads, tokens)
   split across them.
3. **A kernel that gives way says so**: ``note_fallback`` logs each distinct
   (kernel, reason) once at WARNING level.
4. **Whether a kernel compiles at a geometry is the compiler's call**:
   ``compiler_verdict`` lowers it for the TPU (and compiles it, on a TPU
   backend) and hands back the compiler's own words on refusal. Code that
   selects a kernel up front (the serving engine's ``fused`` backend, the
   inference engine's quantized matmul) asks here instead of keeping a rule
   list that drifts from what Mosaic accepts.
"""

import contextlib
import contextvars
import functools
import logging

import jax
from jax.sharding import PartitionSpec as P

from ...utils.logging import logger

_TARGET = contextvars.ContextVar("deepspeed_tpu_pallas_target", default=None)


def target_platform():
    """Platform kernels are lowered for: ``lowering_target``'s, else the
    default backend."""
    return _TARGET.get() or jax.default_backend()


@contextlib.contextmanager
def lowering_target(platform):
    """Trace the enclosed code as if ``platform`` were the backend (pair it
    with ``jit(f).trace(...).lower(lowering_platforms=(platform,))``)."""
    token = _TARGET.set(platform)
    try:
        yield
    finally:
        _TARGET.reset(token)


def unavailable_reason(interpret=False):
    """Why no Pallas kernel can run here, or None: a kernel needs a TPU
    target, or the caller asking for the interpreter."""
    if interpret or target_platform() == "tpu":
        return None
    return (f"platform is {target_platform()!r}, not 'tpu', and interpret "
            "mode was not requested")


@functools.lru_cache(maxsize=None)
def note_fallback(kernel, reason):
    """Log — once per (kernel, reason) — that ``kernel`` was asked for and
    the XLA path runs instead."""
    logger.log(logging.WARNING,
               f"{kernel}: Pallas kernel not used, XLA path runs instead "
               f"({reason})")


def _mesh_axes(mesh):
    """``(shard_map mesh arg, free axis names, axis sizes)`` for ``mesh``
    seen from the current trace context: inside a manual region the context
    mesh must be reused (``mesh=None``) and only its not-yet-manual axes can
    be claimed."""
    ctx = jax.sharding.get_abstract_mesh()
    manual = set() if ctx.empty else set(ctx.manual_axes)
    if not manual:
        return mesh, tuple(mesh.axis_names), dict(mesh.shape)
    return None, tuple(a for a in ctx.axis_names if a not in manual), \
        dict(ctx.shape)


def split_spec(shape, dim_axes, free, sizes):
    """PartitionSpec for an operand of ``shape``: ``dim_axes`` maps a dim to
    the mesh axes it may be split over; an axis is used only if it is free
    (not already manual) and the running product still divides the dim."""
    spec = [None] * len(shape)
    for dim, axes in dim_axes.items():
        used, prod = [], 1
        for a in axes:
            n = sizes.get(a, 1)
            if a in free and n > 1 and shape[dim] % (prod * n) == 0:
                used.append(a)
                prod *= n
        if used:
            spec[dim] = used[0] if len(used) == 1 else tuple(used)
    return P(*spec)


def shard_kernel(fn, mesh, operands, in_dim_axes, out_dim_axes):
    """``fn(*operands)`` with its Pallas call(s) run per shard.

    ``in_dim_axes[i]`` / ``out_dim_axes[j]``: ``{dim: (mesh axes...)}`` for
    operand i / output j — the dims the kernel treats independently.
    Outputs must keep a split dim's extent proportional to the operand's
    (true for every kernel here: batch/heads/tokens pass through). With no
    mesh, one device, or nothing left to claim, ``fn`` is called directly.
    """
    if mesh is None or mesh.size == 1:
        return fn(*operands)
    sm_mesh, free, sizes = _mesh_axes(mesh)
    if not any(sizes[a] > 1 for a in free):
        return fn(*operands)
    in_specs = tuple(split_spec(x.shape, d, free, sizes)
                     for x, d in zip(operands, in_dim_axes))
    out_shapes = jax.eval_shape(fn, *operands)
    flat_out, treedef = jax.tree_util.tree_flatten(out_shapes)
    out_specs = jax.tree_util.tree_unflatten(
        treedef, [split_spec(o.shape, d, free, sizes)
                  for o, d in zip(flat_out, out_dim_axes)])
    kw = {"mesh": sm_mesh} if sm_mesh is not None else {}
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(free), check_vma=False,
                         **kw)(*operands)


def compiler_verdict(fn, *abstract_args):
    """``(ok, reason)``: does ``fn`` (a Pallas kernel call) lower for the TPU
    at these ``jax.ShapeDtypeStruct`` operands? Lowering runs the Pallas ->
    Mosaic checks anywhere; on a TPU backend the kernel is also compiled,
    which is where Mosaic's legalization errors surface. ``reason`` is the
    first sentence of the compiler's message."""
    try:
        with lowering_target("tpu"):
            lowered = jax.jit(fn).trace(*abstract_args).lower(
                lowering_platforms=("tpu",))
        if jax.default_backend() == "tpu":
            lowered.compile()
    except Exception as e:  # any refusal is the answer, not a crash
        text = " ".join(str(e).split()) or type(e).__name__
        cut = text.find(". ")
        return False, text[:cut + 1] if 0 < cut < 400 else text[:400]
    return True, ""
