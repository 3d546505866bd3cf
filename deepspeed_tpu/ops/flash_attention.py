"""Memory-efficient attention.

Replaces the reference's fused attention kernels (``csrc/transformer/softmax_kernels.cu``
for training, ``csrc/transformer/inference/csrc/softmax.cu`` "softmax_context" for
inference). Two implementations behind one signature:

- ``pallas_flash_attention`` (``ops/pallas/flash_attention.py``): the hand-tiled TPU
  kernel; what ``flash_attention`` runs on a TPU target (or in interpret mode).
- ``_chunked_attention``: online-softmax attention, chunked over the KV axis with
  ``lax.scan`` so the [batch, heads, q, kv] score matrix is never materialized —
  O(seq) memory like FlashAttention. Pure XLA; what ``flash_attention`` runs where
  the kernel cannot, with the reason logged once.

Inputs q,k,v: [batch, seq, heads, head_dim]; returns the same layout.
"""

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def parse_block_spec(spec):
    """Parse a "bq x bkv[: bq_bwd x bkv_bwd]" tile-size string (the
    BENCH_BLOCKS knob of tools/bench_attention.py). Returns (bq, bkv, bq_bwd, bkv_bwd) with the
    backward pair None when omitted."""
    fwd, _, bwd = spec.partition(":")
    bq, bkv = (int(x) for x in fwd.split("x"))
    if bwd:
        bqb, bkvb = (int(x) for x in bwd.split("x"))
    else:
        bqb = bkvb = None
    return bq, bkv, bqb, bkvb


def shard_attention(kernel, mesh, q, k, v):
    """``kernel(q, k, v)`` on [b, s, h, d] operands, per shard of ``mesh``:
    attention is independent across batch rows and heads, so the kernel runs
    per (data x expert, model) shard (``ops/pallas shard_kernel``)."""
    from .pallas import shard_kernel

    batch_heads = {0: ("data", "expert"), 2: ("model",)}
    return shard_kernel(kernel, mesh, (q, k, v), [batch_heads] * 3,
                        [batch_heads])


def _pallas_unusable(q, k, interpret):
    """Why the Pallas kernels cannot take this call, or None if they can."""
    from .pallas import unavailable_reason

    if q.shape[1] % 128 or k.shape[1] % 128:
        return (f"sequence lengths {q.shape[1]}/{k.shape[1]} are not "
                "multiples of 128")
    return unavailable_reason(interpret)


def flash_attention(q, k, v, causal=True, scale=None, block_size=512,
                    block_q=None, block_kv=None, block_q_bwd=None,
                    block_kv_bwd=None, interpret=False, mesh=None):
    """Flash attention: the Pallas TPU kernel where it can run, else the
    XLA online-softmax scan (``_chunked_attention``) with a logged reason.

    The kernel needs a TPU target (or ``interpret=True``, the CPU tests'
    Pallas interpreter) and 128-aligned sequence lengths. ``mesh``: the
    device mesh the surrounding program is partitioned over — on more than
    one device the kernel runs inside a ``shard_map`` (batch over ``data``,
    heads over ``model``), because GSPMD cannot partition a Mosaic call.
    ``block_*`` override the kernel's tile sizes (ignored by the XLA scan).
    """
    reason = _pallas_unusable(q, k, interpret)
    if reason is not None:
        from .pallas import note_fallback

        note_fallback("flash_attention", reason)
        return _chunked_attention(q, k, v, causal=causal, scale=scale,
                                  block_size=block_size)
    from .pallas.flash_attention import pallas_flash_attention

    s_q, s_kv = q.shape[1], k.shape[1]
    if block_q is None and block_kv is None and s_kv <= 1024:
        # at s_kv <= 1024 a SINGLE kv block per grid step drops the
        # online-softmax rescale loop entirely (fwd 512x{s_kv} + bwd
        # 512x{s_kv} tiles; the builders' round-4 winner shape, PERF.md
        # "Historical"). Longer sequences keep the generic tiles.
        block_q = min(512, s_q)
        block_kv = s_kv
        block_q_bwd = block_q_bwd or min(512, s_q)
        block_kv_bwd = block_kv_bwd or s_kv

    def kernel(q, k, v):
        return pallas_flash_attention(
            q, k, v, causal, scale, min(block_q or 256, s_q),
            min(block_kv or 512, s_kv), interpret, block_q_bwd, block_kv_bwd)

    return shard_attention(kernel, mesh, q, k, v)


def jax_flash_attention(q, k, v, causal=True, scale=None, mesh=None):
    """The official JAX TPU flash kernel behind our [b, s, h, d] signature.

    ``jax.experimental.pallas.ops.tpu.flash_attention`` is the
    production-tuned Mosaic kernel (fwd + custom-vjp bwd, [b, h, s, d]
    layout). Exposed as ``attention_impl="jax_flash"`` for head-to-head
    comparison with the in-repo kernel. It has no interpret mode here: off
    a TPU target it takes the same XLA scan as ``flash_attention`` (logged).

    Known integration asymmetry: under ``remat`` the in-repo kernel saves
    its lse residual by checkpoint name ("minimal" policy), so its backward
    skips the forward recompute; the official kernel's residuals are
    internal to its custom vjp and get recomputed.
    """
    reason = _pallas_unusable(q, k, interpret=False)
    if reason is not None:
        from .pallas import note_fallback

        note_fallback("jax_flash_attention", reason)
        return _chunked_attention(q, k, v, causal=causal, scale=scale)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _jax_flash)

    sm_scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def kernel(q, k, v):
        out = _jax_flash(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, sm_scale=sm_scale)
        return out.transpose(0, 2, 1, 3)

    return shard_attention(kernel, mesh, q, k, v)


def _chunked_attention(q, k, v, causal=True, scale=None, block_size=512):
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    block = min(block_size, s_kv)
    if s_kv % block:
        block = s_kv  # fall back to one chunk for ragged sizes
    n_blocks = s_kv // block

    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # [b,h,q,d]
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)

    k_blocks = kf.reshape(b, h, n_blocks, block, d).transpose(2, 0, 1, 3, 4)
    v_blocks = vf.reshape(b, h, n_blocks, block, d).transpose(2, 0, 1, 3, 4)

    q_idx = jnp.arange(s_q)[:, None] + (s_kv - s_q)  # align causal window to kv end

    def body(carry, inputs):
        m, l, acc = carry
        (kb, vb, blk) = inputs
        logits = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)  # [b,h,q,block]
        if causal:
            kv_idx = blk * block + jnp.arange(block)[None, :]
            mask = kv_idx <= q_idx  # [q, block]
            logits = jnp.where(mask[None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        correction = jnp.exp(m - m_new)
        l_new = l * correction + jnp.sum(p, axis=-1)
        acc_new = acc * correction[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_q), jnp.float32)
    acc0 = jnp.zeros((b, h, s_q, d), jnp.float32)
    blks = jnp.arange(n_blocks)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), (k_blocks, v_blocks, blks))

    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
