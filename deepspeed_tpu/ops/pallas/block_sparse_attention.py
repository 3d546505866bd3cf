"""Block-sparse flash attention (splash-style) Pallas TPU kernels.

The TPU-native replacement for the reference's Triton block-sparse attention
(``deepspeed/ops/sparse_attention/matmul.py:17`` SDD/DSD kernels +
``softmax.py``): instead of sparse-matmul primitives over a materialized
layout, the *grid itself* is sparse — per q block, a scalar-prefetched list of
active kv block indices drives the BlockSpec index_map, so inactive blocks
cost neither DMA nor compute (the same idea as the public splash-attention
kernel). The dense flash kernel (``flash_attention.py``) is the special case
"every block active".

Static preprocessing turns a block mask [n_q_blocks, n_kv_blocks] (from
``ops/sparse_attention/sparsity_config.py``) into padded active-block lists
for the forward/dq direction and their transpose for dkv. The online-softmax
math and the FlashAttention-2 backward split are identical to the dense
kernel's.
"""

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30
LANES = 128


def _active_lists(layout):
    """bool [nq, nkv] -> (idx [nq, max_a] int32 padded w/ last, counts [nq])."""
    nq, _ = layout.shape
    lists = [np.nonzero(layout[j])[0] for j in range(nq)]
    counts = np.asarray([len(l) for l in lists], np.int32)
    max_a = max(1, int(counts.max()))
    idx = np.zeros((nq, max_a), np.int32)
    for j, l in enumerate(lists):
        if len(l) == 0:
            continue
        idx[j, :len(l)] = l
        idx[j, len(l):] = l[-1]
    return idx, counts, max_a


def _mask_tile(s, this_kv, j, block_q, block_kv, q_offset):
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(
        this_kv * block_kv + col <= j * block_q + row + q_offset, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward: grid (b*h, n_qb, max_active); kv block index read from prefetch
# ---------------------------------------------------------------------------
def _fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_kv,
                q_offset):
    j = pl.program_id(1)
    a = pl.program_id(2)
    this_kv = idx_ref[j, a]
    n_act = cnt_ref[j]

    @pl.when(a == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if causal:
        on_diag = this_kv * block_kv + block_kv - 1 > j * block_q + q_offset
    else:
        on_diag = jnp.asarray(False)
    run = a < n_act

    def step(masked):
        # bf16 dot inputs + fp32 accumulation (MXU native); upcasting tiles to
        # fp32 before the dot runs fp32xfp32 matmuls at a fraction of bf16
        # throughput (same fix as flash_attention.py)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            s = _mask_tile(s, this_kv, j, block_q, block_kv, q_offset)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(run & jnp.logical_not(on_diag))
    def _full():
        step(False)

    if causal:
        @pl.when(run & on_diag)
        def _diag():
            step(True)

    @pl.when(a == n_act - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(m_scr[:, :1] + jnp.log(l),
                                      lse_ref.shape[1:])


# ---------------------------------------------------------------------------
# backward dQ: same sparse grid as forward
# ---------------------------------------------------------------------------
def _dq_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
               dq_ref, dq_scr, delta_scr, *, scale, causal, block_q, block_kv,
               q_offset):
    j = pl.program_id(1)
    a = pl.program_id(2)
    this_kv = idx_ref[j, a]
    n_act = cnt_ref[j]

    @pl.when(a == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        delta = jnp.sum(o * do, axis=-1, keepdims=True)
        delta_scr[...] = jnp.broadcast_to(delta, delta_scr.shape)

    if causal:
        on_diag = this_kv * block_kv + block_kv - 1 > j * block_q + q_offset
    else:
        on_diag = jnp.asarray(False)
    run = a < n_act

    def step(masked):
        # bf16 dot inputs + fp32 accumulation (see fwd kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if masked:
            s = _mask_tile(s, this_kv, j, block_q, block_kv, q_offset)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_scr[:, :1]) * scale
        dq_scr[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    @pl.when(run & jnp.logical_not(on_diag))
    def _full():
        step(False)

    if causal:
        @pl.when(run & on_diag)
        def _diag():
            step(True)

    @pl.when(a == n_act - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# backward dK/dV: grid (b*h, n_kvb, max_active_q); q block index prefetched
# ---------------------------------------------------------------------------
def _dkv_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, block_q,
                block_kv, q_offset):
    jkv = pl.program_id(1)
    a = pl.program_id(2)
    this_q = idx_ref[jkv, a]
    n_act = cnt_ref[jkv]

    @pl.when(a == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    if causal:
        on_diag = jkv * block_kv + block_kv - 1 > this_q * block_q + q_offset
    else:
        on_diag = jnp.asarray(False)
    run = a < n_act

    def step(masked):
        # bf16 dot inputs + fp32 accumulation (see fwd kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        o = o_ref[0].astype(jnp.float32)
        do = do_ref[0]
        delta = jnp.sum(o * do.astype(jnp.float32), axis=-1, keepdims=True)
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(
                jkv * block_kv + col <= this_q * block_q + row + q_offset,
                s, NEG_INF)
        p = jnp.exp(s - lse_ref[0][:, :1])
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(run & jnp.logical_not(on_diag))
    def _full():
        step(False)

    if causal:
        @pl.when(run & on_diag)
        def _diag():
            step(True)

    @pl.when(a == n_act - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    # a kv block no query attends (possible in the layout transpose) still owns
    # an output tile — zero it or it's garbage
    @pl.when((a == 0) & (n_act == 0))
    def _untouched():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])


# ---------------------------------------------------------------------------
# host-side wrapper
# ---------------------------------------------------------------------------
class BlockSparseAttention:
    """Callable sparse attention for a fixed (seq, pattern, block) shape —
    the reference's ``SparseSelfAttention`` role (sparse_self_attention.py),
    with the layout preprocessing done once at construction."""

    def __init__(self, config, seq_q, seq_kv=None, causal=True, scale=None,
                 interpret=False):
        seq_kv = seq_kv or seq_q
        self.block = config.block
        self.causal = causal
        self.interpret = interpret
        self.scale = scale
        layout = config.layout_for(seq_q, seq_kv, causal=causal)
        self.layout = layout
        self.density = float(layout.mean())
        self._fwd_idx, self._fwd_cnt, self._max_a = _active_lists(layout)
        self._bwd_idx, self._bwd_cnt, self._max_aq = _active_lists(layout.T)
        self.seq_q, self.seq_kv = seq_q, seq_kv

        @jax.custom_vjp
        def attend(q, k, v):
            out, _ = self._forward(q, k, v)
            return out

        def fwd(q, k, v):
            out, lse = self._forward(q, k, v)
            # named residuals so remat policies ("minimal") can save them —
            # without the lse name the backward re-runs the whole forward
            # kernel per layer just to regenerate it (same fix as
            # ops/pallas/flash_attention.py _vjp_fwd)
            out = checkpoint_name(out, "attn_out")
            lse = checkpoint_name(lse, "attn_lse")
            return out, (q, k, v, out, lse)

        def bwd(res, g):
            return self._backward(*res, g)

        attend.defvjp(fwd, bwd)
        self._attend = attend

    def __call__(self, q, k, v):
        """q: [b, s_q, h, d]; k/v: [b, s_kv, h, d] -> [b, s_q, h, d]."""
        return self._attend(q, k, v)

    # -- shared plumbing ----------------------------------------------------
    def _prep(self, x, s):
        b, _, h, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    def _forward(self, q, k, v):
        b, s_q, h, d = q.shape
        s_kv = k.shape[1]
        assert s_q == self.seq_q and s_kv == self.seq_kv, \
            (s_q, s_kv, self.seq_q, self.seq_kv)
        blk = self.block
        scale = self.scale if self.scale is not None else 1.0 / math.sqrt(d)
        qr, kr, vr = (self._prep(q, s_q), self._prep(k, s_kv),
                      self._prep(v, s_kv))
        nq = s_q // blk
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h, nq, self._max_a),
            in_specs=[
                pl.BlockSpec((1, blk, d), lambda i, j, a, idx, cnt: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk, d),
                             lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk, d),
                             lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=[
                pl.BlockSpec((1, blk, d), lambda i, j, a, idx, cnt: (i, j, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk, LANES),
                             lambda i, j, a, idx, cnt: (i, j, 0),
                             memory_space=pltpu.VMEM),
            ],
            scratch_shapes=[
                pltpu.VMEM((blk, LANES), jnp.float32),
                pltpu.VMEM((blk, LANES), jnp.float32),
                pltpu.VMEM((blk, d), jnp.float32),
            ],
        )
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, causal=self.causal,
                              block_q=blk, block_kv=blk, q_offset=s_kv - s_q),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
                jax.ShapeDtypeStruct((b * h, s_q, LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=self.interpret,
        )(jnp.asarray(self._fwd_idx), jnp.asarray(self._fwd_cnt), qr, kr, vr)
        out = out.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
        return out, lse[..., :1]

    def _backward(self, q, k, v, out, lse, g):
        b, s_q, h, d = q.shape
        s_kv = k.shape[1]
        blk = self.block
        scale = self.scale if self.scale is not None else 1.0 / math.sqrt(d)
        lse = jnp.broadcast_to(lse, lse.shape[:-1] + (LANES,))
        qr, kr, vr = (self._prep(q, s_q), self._prep(k, s_kv),
                      self._prep(v, s_kv))
        orr, gr = self._prep(out, s_q), self._prep(g, s_q)
        nq, nkv = s_q // blk, s_kv // blk
        common = dict(scale=scale, causal=self.causal, block_q=blk,
                      block_kv=blk, q_offset=s_kv - s_q)

        dq = pl.pallas_call(
            functools.partial(_dq_kernel, **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b * h, nq, self._max_a),
                in_specs=[
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, LANES),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=pl.BlockSpec((1, blk, d),
                                       lambda i, j, a, idx, cnt: (i, j, 0),
                                       memory_space=pltpu.VMEM),
                scratch_shapes=[
                    pltpu.VMEM((blk, d), jnp.float32),
                    pltpu.VMEM((blk, LANES), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=self.interpret,
        )(jnp.asarray(self._fwd_idx), jnp.asarray(self._fwd_cnt),
          qr, kr, vr, orr, gr, lse)

        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, **common),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(b * h, nkv, self._max_aq),
                in_specs=[
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, LANES),
                                 lambda i, j, a, idx, cnt: (i, idx[j, a], 0),
                                 memory_space=pltpu.VMEM),
                ],
                out_specs=[
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((1, blk, d),
                                 lambda i, j, a, idx, cnt: (i, j, 0),
                                 memory_space=pltpu.VMEM),
                ],
                scratch_shapes=[
                    pltpu.VMEM((blk, d), jnp.float32),
                    pltpu.VMEM((blk, d), jnp.float32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b * h, s_kv, d), k.dtype),
                jax.ShapeDtypeStruct((b * h, s_kv, d), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=self.interpret,
        )(jnp.asarray(self._bwd_idx), jnp.asarray(self._bwd_cnt),
          qr, kr, vr, orr, gr, lse)

        to4 = lambda x, s: x.reshape(b, h, s, d).transpose(0, 2, 1, 3)
        return to4(dq, s_q), to4(dk, s_kv), to4(dv, s_kv)
