"""Pallas TPU fused dequant-matmul for weight-only int8/int4 serving.

Reference role: the inference dequant kernels
(``csrc/transformer/inference/csrc/dequantize.cu`` and the int8/int4 gemm
epilogues behind ``pt_binding.cpp``) — the CUDA answer to "never materialize
the fp16 weight". The XLA path (``models/layers.py linear_apply``) hopes the
``unpack + q * scale`` chain fuses into the consuming matmul; measured on
chip (2026-08-01 serving bench, PERF.md) it does for int8 but NOT for the
int4 nibble unpack — the stack/reshape breaks fusion, the full-size bf16
weight round-trips HBM every decode step, and int4 decode lands 3-4x SLOWER
than bf16. Here the packed bytes are what streams HBM->VMEM; the unpack,
group-scale multiply, and MXU dot all happen per-tile in VMEM:

- grid (out_tiles, k_tiles), k innermost, the [m, bn] accumulator resident
  in its output tile across the k sweep (same-index revisit, no refetch);
- int4 avoids an in-kernel row interleave with the identity
  ``y = sum_p x[2p] w[2p] + x[2p+1] w[2p+1]`` = ``x_even @ lo + x_odd @ hi``
  (lo/hi = the two nibbles of the packed byte row p, which hold exactly the
  even/odd input rows per ``ops/quantizer.py pack_int4``);
- groupwise scales (``quantize_per_channel`` layout [groups, 1, out]) are
  applied to the dequantized tile before the dot; a k-tile never straddles a
  group boundary by construction (block_k is clamped to a divisor-aligned
  size, see ``_pick_blocks``).

Forward-only by design: quantized kernels exist only on the serving path
(``inference/engine.py _quantize_weights``); nothing differentiates through
them.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _int8_kernel(x_ref, q_ref, s_ref, o_ref, *, n_groups, dot_dtype):
    kb = pl.program_id(1)
    q = q_ref[...]                                   # [bk, bn] int8
    s = s_ref[...].astype(jnp.float32)               # [nG, bn]
    bk, bn = q.shape
    w = q.astype(jnp.float32).reshape(n_groups, bk // n_groups, bn)
    w = (w * s[:, None, :]).reshape(bk, bn).astype(dot_dtype)
    x = x_ref[...].astype(dot_dtype)                 # [m, bk]
    part = jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kb == 0)
    def _init():
        o_ref[...] = part

    @pl.when(kb != 0)
    def _acc():
        o_ref[...] += part


def _int4_kernel(xe_ref, xo_ref, q_ref, s_ref, o_ref, *, n_groups, dot_dtype):
    kb = pl.program_id(1)
    u = q_ref[...]                                   # [bk2, bn] uint8
    bk2, bn = u.shape
    # nibble arithmetic in int32: Mosaic on v5e has no i8 vector subtract
    # ("failed to legalize operation 'arith.subi'" on vector<..xi8>)
    u = u.astype(jnp.int32)
    lo = (u & 0xF) - 8                               # even input rows
    hi = ((u >> 4) & 0xF) - 8                        # odd input rows
    s = s_ref[...].astype(jnp.float32)               # [nG, bn]
    # nibble row p holds input rows 2p (lo) and 2p+1 (hi); both belong to
    # group p // (g/2), so one [nG, g/2, bn] broadcast scales either nibble
    gh = bk2 // n_groups

    def scaled(w):
        w = w.astype(jnp.float32).reshape(n_groups, gh, bn)
        return (w * s[:, None, :]).reshape(bk2, bn).astype(dot_dtype)

    xe = xe_ref[...].astype(dot_dtype)               # [m, bk2]
    xo = xo_ref[...].astype(dot_dtype)
    part = jax.lax.dot(xe, scaled(lo), preferred_element_type=jnp.float32)
    part += jax.lax.dot(xo, scaled(hi), preferred_element_type=jnp.float32)

    @pl.when(kb == 0)
    def _init():
        o_ref[...] = part

    @pl.when(kb != 0)
    def _acc():
        o_ref[...] += part


def _pick_blocks(k, n, group_size, block_k, block_n):
    """Largest tile sizes that divide the problem AND keep every k-tile
    group-aligned (tile a multiple of the group, so the kernel's per-tile
    scale reshape is exact). Returns None if no legal tiling exists."""
    g = group_size
    if k % g:
        return None
    bk = (min(block_k, k) // g) * g  # round down to a group multiple...
    if bk == 0:
        bk = g  # ...unless the group itself is bigger: one group per tile
    while bk > 0 and k % bk:
        bk -= g
    if bk <= 0:
        return None
    bn = min(block_n, n)
    while bn >= 128 and n % bn:
        bn //= 2
    if bn < 128 or n % bn:
        return None
    return bk, bn


def quantized_matmul(x, q, scale, *, bits, block_k=512, block_n=512,
                     interpret=False):
    """``x [m, k] @ dequant(q, scale) [k, n] -> [m, n]`` in ``x.dtype``.

    ``q``/``scale`` follow ``ops/quantizer.py quantize_per_channel`` (+
    ``pack_int4`` for bits=4: q is uint8 [k/2, n]). Returns None when the
    shapes don't admit a legal tiling — the caller falls back to the XLA
    dequant path.
    """
    m, k = x.shape
    n = q.shape[-1]
    scale = scale.reshape(scale.shape[-3], n)        # [groups, n]
    groups = scale.shape[0]
    if k % groups:
        return None
    group_size = k // groups
    if bits == 4 and group_size % 2:
        return None
    picked = _pick_blocks(k, n, group_size, block_k, block_n)
    if picked is None:
        return None
    bk, bn = picked
    n_kb, n_nb = k // bk, n // bn
    ng_tile = bk // group_size

    # pad the token dim to the fp32 sublane count so tiny decode batches
    # (m = 1..7) still form a legal tile
    m_pad = max(8, ((m + 7) // 8) * 8)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))

    # dtype-faithful dot: bf16 activations keep the MXU-native bf16 dot;
    # fp32 serving must NOT be silently truncated to bf16 (the XLA fallback
    # computes in fp32, and the two paths must agree beyond tileability)
    dot_dtype = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32

    grid = (n_nb, n_kb)  # k innermost: accumulator tile stays resident
    out_shape = jax.ShapeDtypeStruct((m_pad, n), jnp.float32)
    out_spec = pl.BlockSpec((m_pad, bn), lambda j, kb: (0, j),
                            memory_space=pltpu.VMEM)
    s_spec = pl.BlockSpec((ng_tile, bn), lambda j, kb: (kb, j),
                          memory_space=pltpu.VMEM)
    params = dict(
        grid=grid,
        out_specs=out_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )
    if bits == 8:
        y = pl.pallas_call(
            functools.partial(_int8_kernel, n_groups=ng_tile,
                              dot_dtype=dot_dtype),
            in_specs=[
                pl.BlockSpec((m_pad, bk), lambda j, kb: (0, kb),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((bk, bn), lambda j, kb: (kb, j),
                             memory_space=pltpu.VMEM),
                s_spec,
            ],
            **params,
        )(x, q, scale)
    elif bits == 4:
        xe, xo = x[:, 0::2], x[:, 1::2]              # [m_pad, k/2]
        bk2 = bk // 2
        y = pl.pallas_call(
            functools.partial(_int4_kernel, n_groups=ng_tile,
                              dot_dtype=dot_dtype),
            in_specs=[
                pl.BlockSpec((m_pad, bk2), lambda j, kb: (0, kb),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((m_pad, bk2), lambda j, kb: (0, kb),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((bk2, bn), lambda j, kb: (kb, j),
                             memory_space=pltpu.VMEM),
                s_spec,
            ],
            **params,
        )(xe, xo, q, scale)
    else:
        return None
    return y[:m].astype(x.dtype)


def quantized_matmul_supported(k, n, groups, *, bits, dtype, m=8):
    """``(ok, reason)``: does the kernel compile for the TPU at a ``[k, n]``
    weight with ``groups`` scale groups (decode-sized ``m``)? Untileable
    shapes and compiler refusals both come back as the reason."""
    from . import compiler_verdict

    sds = jax.ShapeDtypeStruct
    q = sds((k // 2, n), jnp.uint8) if bits == 4 else sds((k, n), jnp.int8)

    def call(x, q, scale):
        y = quantized_matmul(x, q, scale, bits=bits)
        if y is None:
            raise ValueError(f"no legal tiling for k={k} n={n} "
                             f"group_size={k // groups}.")
        return y

    return compiler_verdict(call, sds((m, k), dtype), q,
                            sds((groups, 1, n), jnp.float32))
