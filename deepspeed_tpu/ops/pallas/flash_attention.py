"""Pallas TPU flash-attention: tiled forward AND backward kernels.

The TPU-native replacement for the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` for training, the inference
"softmax_context" kernels in ``csrc/transformer/inference/csrc/softmax.cu``):
online-softmax attention tiled over query blocks x key/value blocks, fp32
accumulators in VMEM scratch, causally-skippable kv blocks.

Layout notes (the TPU way):
- grid = (batch*heads, q_blocks, kv_blocks) with the kv dimension innermost and
  "arbitrary" semantics: the (m, l, acc) running triple lives in VMEM scratch and
  persists across the kv iterations of one q block; K/V HBM->VMEM streaming is
  handled by the BlockSpec pipeline (double-buffered by Pallas), so VMEM holds
  only one K/V block at a time — long sequences never blow VMEM.
- the row statistics (m/l/lse/delta) are kept broadcast across a 128-lane minor
  dim: TPU vregs are (8, 128), so a [block_q, 1] column would relayout on every
  use; [block_q, 128] broadcast is the idiomatic layout (same trick as the
  reference's warp-level row reductions, just vectorized).
- backward = two kernels, the standard FlashAttention-2 split: dKV (grid over kv
  blocks, loop over q) and dQ (grid over q blocks, loop over kv), each
  recomputing the probability tile from (q, k, lse) so nothing O(s^2) is ever
  materialized. ``delta = rowsum(dO * O)`` is computed in-kernel at the first
  visit instead of as a separate XLA pass.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _fit_block(requested, seq):
    """Largest block in [1, requested] that divides seq (backward clamps block
    sizes, which must never silently truncate the grid)."""
    b = max(1, min(requested, seq))
    while seq % b:
        b -= 1
    return b


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal, block_q,
                block_kv, q_offset, n_kvb, emit_lse):
    if emit_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        lse_ref = None
        m_scr, l_scr, acc_scr = rest
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if causal:
        # last kv block any row of this q block attends to; diagonal blocks mask
        limit = (j * block_q + block_q - 1 + q_offset) // block_kv
        last = jnp.minimum(limit, n_kvb - 1)
        on_diag = kb * block_kv + block_kv - 1 > j * block_q + q_offset
        run_full = jnp.logical_and(kb <= limit, jnp.logical_not(on_diag))
        run_diag = jnp.logical_and(kb <= limit, on_diag)
    else:
        last = n_kvb - 1
        run_full = jnp.asarray(True)
        run_diag = jnp.asarray(False)

    def step(masked):
        # dots take the INPUT dtype (bf16 in training) with fp32 accumulation —
        # the MXU's native mode. Upcasting tiles to fp32 before the dot forces
        # fp32xfp32 matmuls at a fraction of bf16 throughput (measured: the
        # whole kernel lost to plain XLA attention until this was fixed).
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv] fp32
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kb * block_kv + col <= j * block_q + row + q_offset,
                          s, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(run_full)
    def _full():
        step(False)

    if causal:
        @pl.when(run_diag)
        def _diag():
            step(True)

    @pl.when(kb == last)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if emit_lse:
            lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l),
                                          lse_ref.shape[1:])


def _fwd_kernel_single(q_ref, k_ref, v_ref, o_ref, *rest, scale, causal,
                       block_q, block_kv, q_offset, emit_lse):
    """One kv block = the whole sequence: plain softmax, NO online-softmax
    machinery. The (m, l, acc) scratch triple, its zero-init pass, the
    correction multiplies, and the acc read-modify-write all drop out — this
    is the configuration the measured 0.4157 winner runs (512x1024 tiles at
    seq 1024), so the bookkeeping it pays is pure overhead."""
    lse_ref = rest[0] if emit_lse else None
    j = pl.program_id(1)
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [bq, bkv] fp32
    if causal:
        # always mask: the elementwise select on [bq, bkv] is noise next to
        # the dot, and skipping it for fully-below-diagonal q blocks would
        # reintroduce the two-branch dispatch this kernel exists to shed
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(col <= j * block_q + row + q_offset, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    acc = jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    if emit_lse:
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), lse_ref.shape[1:])


def _flash_fwd_single(qr, kr, vr, bh, s_q, s_kv, d, causal, scale, bq,
                      interpret, need_lse, out_dtype):
    """pallas_call wrapper for the single-kv-block kernel (2D grid, no
    scratch). kv/v blocks are the full sequence."""
    kernel = functools.partial(
        _fwd_kernel_single, scale=scale, causal=causal, block_q=bq,
        block_kv=s_kv, q_offset=s_kv - s_q, emit_lse=need_lse,
    )
    out_specs = [pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((bh, s_q, d), out_dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((1, bq, LANES), lambda i, j: (i, j, 0),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((bh, s_q, LANES), jnp.float32))
    return pl.pallas_call(
        kernel,
        grid=(bh, s_q // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_kv, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s_kv, d), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(qr, kr, vr)


def _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret,
               need_lse=False):
    """q,k,v: [b, s, h, d] -> out [b, s, h, d] (+ lse [b*h, s_q, 128] fp32)."""
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    if causal and s_q > s_kv:
        # the causal offset math assumes queries align to the END of the kv
        # sequence (q_offset >= 0); with s_q > s_kv early q blocks would have
        # no finalize step and return uninitialized output
        raise ValueError(
            f"causal flash attention requires s_q <= s_kv, got s_q={s_q} "
            f"s_kv={s_kv}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    # clamp to the largest divisor <= requested — a non-dividing request (e.g.
    # default 512 at seq 640) must degrade, not crash at trace time
    bq = _fit_block(block_q, s_q)
    bkv = _fit_block(block_kv, s_kv)
    n_kvb = s_kv // bkv

    # [b, s, h, d] -> [b*h, s, d]
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, s_kv, d)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, s_kv, d)

    if n_kvb == 1:
        res = _flash_fwd_single(qr, kr, vr, b * h, s_q, s_kv, d, causal,
                                scale, bq, interpret, need_lse, q.dtype)
        out = res[0].reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
        if need_lse:
            return out, res[1][..., :1]
        return out

    q_offset = s_kv - s_q
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_kv=bkv,
        q_offset=q_offset, n_kvb=n_kvb, emit_lse=need_lse,
    )

    if causal:
        # above-diagonal iterations are compute-skipped (pl.when) but Pallas
        # would still DMA whatever block the index_map names — clamp them to
        # the diagonal block so the revisit-dedup skips the fetch (at long
        # seq this halves K/V HBM traffic)
        def kv_index(i, j, kb):
            # outer maximum: with s_q > s_kv (unsupported, but reachable via
            # the generic entry point) q_offset < 0 makes the clamp limit
            # negative — keep the index in range instead of handing the DMA
            # an out-of-range block
            return (i, jnp.maximum(
                jnp.minimum(kb, (j * bq + bq - 1 + q_offset) // bkv), 0), 0)
    else:
        def kv_index(i, j, kb):
            return (i, kb, 0)

    out_specs = [pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0),
                              memory_space=pltpu.VMEM)]
    out_shape = [jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype)]
    if need_lse:
        out_specs.append(pl.BlockSpec((1, bq, LANES), lambda i, j, kb: (i, j, 0),
                                      memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((b * h, s_q, LANES), jnp.float32))
    res = pl.pallas_call(
        kernel,
        grid=(b * h, s_q // bq, n_kvb),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bkv, d), kv_index, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bkv, d), kv_index, memory_space=pltpu.VMEM),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    out = res[0].reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
    if need_lse:
        # keep only one lane as the residual: the [.., LANES] broadcast is the
        # in-kernel layout, not worth 128x the HBM between fwd and bwd
        return out, res[1][..., :1]
    return out


# ---------------------------------------------------------------------------
# backward: dQ kernel — grid (b*h, q_blocks, kv_blocks)
# ---------------------------------------------------------------------------
def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_scr, delta_scr, *, scale, causal, block_q, block_kv,
               q_offset, n_kvb):
    j = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        delta = jnp.sum(o * do, axis=-1, keepdims=True)  # [bq, 1]
        delta_scr[...] = jnp.broadcast_to(delta, delta_scr.shape)

    if causal:
        limit = (j * block_q + block_q - 1 + q_offset) // block_kv
        last = jnp.minimum(limit, n_kvb - 1)
        on_diag = kb * block_kv + block_kv - 1 > j * block_q + q_offset
        run_full = jnp.logical_and(kb <= limit, jnp.logical_not(on_diag))
        run_diag = jnp.logical_and(kb <= limit, on_diag)
    else:
        last = n_kvb - 1
        run_full = jnp.asarray(True)
        run_diag = jnp.asarray(False)

    def step(masked):
        # bf16 dot inputs, fp32 accumulation (see _fwd_kernel.step)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bkv] fp32
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(kb * block_kv + col <= j * block_q + row + q_offset,
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bkv] fp32
        ds = p * (dp - delta_scr[:, :1]) * scale
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(run_full)
    def _full():
        step(False)

    if causal:
        @pl.when(run_diag)
        def _diag():
            step(True)

    @pl.when(kb == last)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dq_kernel_single(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *,
                      scale, causal, block_q, block_kv, q_offset):
    """dQ with one kv block = whole sequence: single pass, no accumulation
    scratch (same rationale as _fwd_kernel_single — this is the measured
    winner's bwd tile shape)."""
    j = pl.program_id(1)
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    o = o_ref[0].astype(jnp.float32)
    delta = jnp.sum(o * do.astype(jnp.float32), axis=-1, keepdims=True)
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(col <= j * block_q + row + q_offset, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, :1])
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq_ref[0] = jnp.dot(ds.astype(k.dtype), k,
                        preferred_element_type=jnp.float32).astype(dq_ref.dtype)


def _dkv_kernel_single(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref,
                       dv_ref, *, scale, causal, block_q, block_kv, q_offset):
    """dK/dV with one q block = the whole query range (the maxq bwd tile):
    single pass, no accumulation scratch."""
    jkv = pl.program_id(1)
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    o = o_ref[0].astype(jnp.float32)
    do = do_ref[0]
    delta = jnp.sum(o * do.astype(jnp.float32), axis=-1, keepdims=True)
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(jkv * block_kv + col <= row + q_offset, s, NEG_INF)
    p = jnp.exp(s - lse_ref[0][:, :1])
    dv_ref[0] = jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dk_ref[0] = jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dK/dV kernel — grid (b*h, kv_blocks, q_blocks)
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dk_ref, dv_ref,
                dk_scr, dv_scr, *, scale, causal, block_q, block_kv,
                q_offset, n_qb):
    jkv = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if causal:
        # q block contributes iff its last row reaches this kv block's start
        contrib = qb * block_q + block_q - 1 + q_offset >= jkv * block_kv
        # diagonal iff the kv block's end passes the q block's first row
        on_diag = jkv * block_kv + block_kv - 1 > qb * block_q + q_offset
        run_full = jnp.logical_and(contrib, jnp.logical_not(on_diag))
        run_diag = jnp.logical_and(contrib, on_diag)
    else:
        run_full = jnp.asarray(True)
        run_diag = jnp.asarray(False)

    def step(masked):
        # bf16 dot inputs, fp32 accumulation (see _fwd_kernel.step)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0]
        delta = jnp.sum(o * do.astype(jnp.float32), axis=-1,
                        keepdims=True)  # [bq, 1]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bkv] fp32
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(jkv * block_kv + col <= qb * block_q + row + q_offset,
                          s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])  # [bq, bkv] fp32
        # dV += P^T dO
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * scale  # [bq, bkv] fp32
        # dK += dS^T Q
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32
        )

    @pl.when(run_full)
    def _full():
        step(False)

    if causal:
        @pl.when(run_diag)
        def _diag():
            step(True)

    @pl.when(qb == n_qb - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale, block_q, block_kv, interpret):
    b, s_q, h, d = q.shape
    s_kv = k.shape[1]
    if causal and s_q > s_kv:
        raise ValueError(
            f"causal flash attention requires s_q <= s_kv, got s_q={s_q} "
            f"s_kv={s_kv}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    bq = _fit_block(block_q, s_q)
    bkv = _fit_block(block_kv, s_kv)
    n_qb, n_kvb = s_q // bq, s_kv // bkv
    q_offset = s_kv - s_q

    to3 = lambda x, s: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    qr, kr, vr = to3(q, s_q), to3(k, s_kv), to3(v, s_kv)
    orr, gr = to3(out, s_q), to3(g, s_q)

    if causal:
        # clamp skipped above-diagonal fetches to the diagonal block so the
        # revisit-dedup skips their DMA (see _flash_fwd)
        def kv_index(i, j, kb):
            # outer maximum: with s_q > s_kv (unsupported, but reachable via
            # the generic entry point) q_offset < 0 makes the clamp limit
            # negative — keep the index in range instead of handing the DMA
            # an out-of-range block
            return (i, jnp.maximum(
                jnp.minimum(kb, (j * bq + bq - 1 + q_offset) // bkv), 0), 0)

        def q_index_dkv(i, jkv, qb):
            # dkv grid iterates q blocks; blocks before the kv block's causal
            # reach are compute-skipped — clamp their fetch to the first
            # contributing q block
            return (i, jnp.maximum(qb, (jkv * bkv - q_offset) // bq), 0)
    else:
        def kv_index(i, j, kb):
            return (i, kb, 0)

        def q_index_dkv(i, jkv, qb):
            return (i, qb, 0)

    if n_kvb == 1:
        # single-pass dQ (no accumulation scratch): the winner's bwd shape
        dq = pl.pallas_call(
            functools.partial(_dq_kernel_single, scale=scale, causal=causal,
                              block_q=bq, block_kv=bkv, q_offset=q_offset),
            grid=(b * h, n_qb),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, LANES), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
            interpret=interpret,
        )(qr, kr, vr, orr, gr, lse)
    else:
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal, block_q=bq,
                              block_kv=bkv, q_offset=q_offset, n_kvb=n_kvb),
            grid=(b * h, n_qb, n_kvb),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), kv_index, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), kv_index, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, LANES), lambda i, j, kb: (i, j, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((1, bq, d), lambda i, j, kb: (i, j, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
            interpret=interpret,
        )(qr, kr, vr, orr, gr, lse)

    if n_qb == 1:
        # single-pass dK/dV (no accumulation scratch): the maxq bwd shape
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel_single, scale=scale, causal=causal,
                              block_q=bq, block_kv=bkv, q_offset=q_offset),
            grid=(b * h, n_kvb),
            in_specs=[
                pl.BlockSpec((1, bq, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, LANES), lambda i, j: (i, 0, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j: (i, j, 0), memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s_kv, d), k.dtype),
                jax.ShapeDtypeStruct((b * h, s_kv, d), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")
            ),
            interpret=interpret,
        )(qr, kr, vr, orr, gr, lse)
    else:
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal,
                              block_q=bq, block_kv=bkv, q_offset=q_offset,
                              n_qb=n_qb),
            grid=(b * h, n_kvb, n_qb),
            in_specs=[
                pl.BlockSpec((1, bq, d), q_index_dkv, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j, qb: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j, qb: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), q_index_dkv, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, d), q_index_dkv, memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bq, LANES), q_index_dkv, memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, bkv, d), lambda i, j, qb: (i, j, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, bkv, d), lambda i, j, qb: (i, j, 0), memory_space=pltpu.VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s_kv, d), k.dtype),
                jax.ShapeDtypeStruct((b * h, s_kv, d), v.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((bkv, d), jnp.float32),
                pltpu.VMEM((bkv, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")
            ),
            interpret=interpret,
        )(qr, kr, vr, orr, gr, lse)

    to4 = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return to4(dq, s_q), to4(dk, s_kv), to4(dv, s_kv)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def pallas_flash_attention(q, k, v, causal=True, scale=None, block_q=256,
                           block_kv=512, interpret=False, block_q_bwd=None,
                           block_kv_bwd=None):
    """block_q/block_kv tile the forward; block_q_bwd/block_kv_bwd the two
    backward kernels (default: forward blocks clamped to 256 — the bwd holds
    more live tiles per step, so its sweet spot is smaller)."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret)


def _vjp_fwd(q, k, v, causal, scale, block_q, block_kv, interpret,
             block_q_bwd, block_kv_bwd):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret,
                          need_lse=True)
    # Names for remat policies: saving "attn_out"+"attn_lse" (models' "minimal"
    # policy) makes the backward's residuals fully available — without the lse
    # name the checkpoint recompute must RE-RUN the whole forward kernel just
    # to regenerate the [tokens, 1] lse.
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


def _vjp_bwd(causal, scale, block_q, block_kv, interpret, block_q_bwd,
             block_kv_bwd, residuals, g):
    q, k, v, out, lse = residuals
    lse = jnp.broadcast_to(lse, lse.shape[:-1] + (LANES,))
    return _flash_bwd(q, k, v, out, lse, g, causal, scale,
                      block_q_bwd or min(block_q, 256),
                      block_kv_bwd or min(block_kv, 256), interpret)


pallas_flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
