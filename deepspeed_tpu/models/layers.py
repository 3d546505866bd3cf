"""Core neural-net layers as pure functions.

The reference builds on torch ``nn.Module``; the TPU-native design keeps models as
pure ``init``/``apply`` function pairs over parameter pytrees. Every parameter carries
*logical axis names* (a tuple of strings, one per dim) in a parallel "axes" pytree —
the sharding layer (``parallel/sharding.py``) maps logical names to mesh axes per
parallelism config. This replaces the reference's module-walking machinery
(``module_inject/replace_module.py``) with data: resharding a model = changing the
rule table, not surgically editing modules.

Logical axis vocabulary (used across the model zoo):
    "vocab"   — vocabulary dim of embeddings / LM head
    "embed"   — model (residual) width
    "mlp"     — feed-forward hidden width (TP-sharded: column parallel in, row out)
    "heads"   — attention heads * head_dim flattened width (TP-sharded)
    "kv"      — kv heads width for GQA/MQA
    "layers"  — scan dim over stacked transformer blocks
    None      — never sharded (biases, layernorm scales use ("embed",) etc.)

Compute dtype: params are stored in fp32 (the master copy; reference
``runtime/fp16/fused_optimizer.py`` keeps the same split) and cast to the compute
dtype (bf16/fp16) at apply time.
"""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


@dataclasses.dataclass
class Param:
    """A parameter leaf paired with its logical axes.

    Registered as a pytree node (value = child, axes = static aux) so transforms
    like ``vmap`` over block init carry the axes metadata through untouched.
    """

    value: jnp.ndarray
    axes: tuple


jax.tree_util.register_pytree_node(
    Param,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: Param(children[0], axes),
)


def split_params_axes(tree):
    """Split a tree of Param into (values, axes) trees."""
    is_param = lambda x: isinstance(x, Param)
    values = jax.tree_util.tree_map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree_util.tree_map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


# ---------------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------------
def normal_init(rng, shape, stddev=0.02, dtype=jnp.float32):
    return jax.random.normal(rng, shape, dtype) * stddev


def zeros_init(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones_init(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


# ---------------------------------------------------------------------------------
# Linear / embedding / layernorm
# ---------------------------------------------------------------------------------
def linear_init(rng, in_dim, out_dim, axes, bias=True, stddev=0.02):
    p = {"kernel": Param(normal_init(rng, (in_dim, out_dim), stddev), axes)}
    if bias:
        p["bias"] = Param(zeros_init((out_dim,)), (axes[-1],))
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x, axis):
    """Identity forward / psum backward over ``axis`` — the conjugate of the
    row-parallel psum, applied to the INPUT of column-parallel matmuls inside a
    manual-TP region (Megatron's f operator): ``d(x @ W_local)/dx`` is a
    partial sum, and this is where it completes."""
    return x


def _psum_f32(x, axis):
    # bf16/f16 all-reduces miscompile in partial-manual regions ("Invalid
    # binary instruction opcode copy", same workaround as parallel/pipeline.py)
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jax.lax.psum(x.astype(jnp.float32), axis).astype(x.dtype)
    return jax.lax.psum(x, axis)


def _tp_copy_fwd(x, axis):
    return x, None


def _tp_copy_bwd(axis, _, g):
    return (_psum_f32(g, axis),)


tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x, axis):
    """psum forward / identity backward — the row-parallel output reduction
    (Megatron's g operator). A bare ``lax.psum`` is WRONG here under legacy
    (check_vma=False) shard_map: its transpose is another psum, which doubles
    every upstream cotangent."""
    return _psum_f32(x, axis)


def _tp_reduce_fwd(x, axis):
    return _psum_f32(x, axis), None


def _tp_reduce_bwd(axis, _, g):
    return (g,)


tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


def linear_apply_rowparallel(p, x, axis):
    """Row-parallel linear INSIDE a manual region over ``axis``: the input's
    feature dim is a local shard, the matmul produces a partial sum,
    ``tp_reduce`` completes it, and the bias is added once after (the
    reference's ``RowParallelLinear`` ordering, ``compression/basic_layer.py:802``)."""
    y = x @ p["kernel"].astype(x.dtype)
    y = tp_reduce(y, axis)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


# Pallas dequant-matmul switch, decided ONCE by the inference engine when it
# quantizes the weights (inference/engine.py _quantize_weights): on only if
# the program runs on one device (a Mosaic call has no partitioning rule) and
# the kernel compiled for the TPU at every weight geometry of the model —
# otherwise the XLA dequant+dot path serves and the engine logs why. The
# DS_TPU_QMM environment variable (off | interpret) overrides it for tests.
_QMM_MODE = "on"


def set_quantized_matmul_enabled(flag):
    global _QMM_MODE
    _QMM_MODE = "on" if flag else "off"


def _quantized_matmul_or_none(p, x, bits):
    """Fused Pallas dequant-matmul — the packed weight is what streams from
    HBM; unpack, group-scale, and the MXU dot happen per-tile in VMEM (XLA
    does not fuse the int4 nibble unpack into the matmul). None (the caller
    runs XLA dequant+dot) when the engine switched the kernel off, and, with
    the reason logged once, where no kernel can run or the shape has no
    legal tiling."""
    import os

    from ..ops.pallas import note_fallback, unavailable_reason

    mode = os.environ.get("DS_TPU_QMM", _QMM_MODE)
    if mode in ("off", "0"):
        return None
    interpret = mode == "interpret"
    reason = unavailable_reason(interpret)
    if reason is not None:
        note_fallback("quantized_matmul", reason)
        return None
    q = p["kernel_q4" if bits == 4 else "kernel_q"]
    xm = x.reshape(-1, x.shape[-1])
    if q.ndim != 2 or xm.shape[0] > 2048:
        # stacked (MoE expert) kernels; prefill-sized token counts whose
        # [m, bn] fp32 accumulator would not fit VMEM
        note_fallback("quantized_matmul",
                      f"weight rank {q.ndim} / {xm.shape[0]} token rows "
                      "outside the kernel's decode-sized 2-D contract")
        return None
    from ..ops.pallas.quantized_matmul import quantized_matmul

    y = quantized_matmul(xm, q, p["kernel_scale"], bits=bits,
                         interpret=interpret)
    if y is None:
        note_fallback("quantized_matmul",
                      f"no legal tiling for weight {tuple(q.shape)}")
        return None
    return y.reshape(x.shape[:-1] + (y.shape[-1],))


def linear_apply(p, x, compute_dtype=None):
    if "kernel_q4" in p or "kernel_q" in p:
        bits = 4 if "kernel_q4" in p else 8
        if compute_dtype is not None:
            x = x.astype(compute_dtype)
        y = _quantized_matmul_or_none(p, x, bits=bits)
        if y is not None:
            if "bias" in p:
                y = y + p["bias"].astype(y.dtype)
            return y
        # XLA path (kernel switched off or unable, see above): unpack +
        # dequant and let XLA fuse what it can into the matmul; the weight
        # still streams from HBM at its quantized width when fusion succeeds
        from ..ops.quantizer import dequantize_per_channel, unpack_int4

        qk = unpack_int4(p["kernel_q4"]) if bits == 4 else p["kernel_q"]
        kernel = dequantize_per_channel(qk, p["kernel_scale"], x.dtype)
    else:
        kernel = p["kernel"]
        if compute_dtype is not None:
            kernel = kernel.astype(compute_dtype)
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
    y = x @ kernel
    if "bias" in p:
        b = p["bias"].astype(y.dtype) if compute_dtype is not None else p["bias"]
        y = y + b
    return y


def embedding_init(rng, vocab_size, embed_dim, stddev=0.02):
    return {"weight": Param(normal_init(rng, (vocab_size, embed_dim), stddev), ("vocab", "embed"))}


def embedding_apply(p, ids, compute_dtype=None):
    w = p["weight"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
    return jnp.take(w, ids, axis=0)


def embedding_attend(p, x):
    """Tied LM head: logits = x @ E^T."""
    return x @ p["weight"].astype(x.dtype).T


def layernorm_init(dim):
    return {
        "scale": Param(ones_init((dim,)), ("embed",)),
        "bias": Param(zeros_init((dim,)), ("embed",)),
    }


def layernorm_apply(p, x, eps=1e-5):
    """LayerNorm computed in fp32 regardless of compute dtype (the reference's fused
    kernels do the same internally; csrc/transformer/normalize_kernels.cu)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dtype)


def rmsnorm_init(dim):
    return {"scale": Param(ones_init((dim,)), ("embed",))}


def rmsnorm_apply(p, x, eps=1e-6):
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(dtype)


# ---------------------------------------------------------------------------------
# Activations (reference: csrc/transformer/gelu_kernels.cu — XLA fuses these)
# ---------------------------------------------------------------------------------
ACTIVATIONS = {
    # jax.nn.gelu defaults to the tanh approximation — matches BLOOM/GPT-2's
    # "gelu"; HF models whose gelu is the exact erf form map to gelu_exact.
    "gelu": jax.nn.gelu,
    "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
    "quick_gelu": lambda x: x * jax.nn.sigmoid(1.702 * x),  # CLIP
    "swiglu": None,  # handled structurally in the MLP
    # squared ReLU, not gated (Nemotron-H's experts: ``mlp_hidden_act``)
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
}


# ---------------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------------
def attention_init(rng, embed_dim, n_heads, n_kv_heads=None, bias=True, stddev=0.02,
                   out_stddev=None, head_dim=None):
    """QKV + output projection. Fused qkv as one matrix (the reference's inference
    kernels fuse qkv gemm the same way; csrc/transformer/inference).

    ``head_dim`` defaults to embed_dim // n_heads; head-pruned models pass the
    original width explicitly, making q/o width n_heads*head_dim < embed_dim."""
    n_kv_heads = n_kv_heads or n_heads
    head_dim = head_dim or embed_dim // n_heads
    q_dim = n_heads * head_dim
    kv_dim = n_kv_heads * head_dim
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "q": linear_init(k1, embed_dim, q_dim, ("embed", "heads"), bias, stddev),
        "k": linear_init(k2, embed_dim, kv_dim, ("embed", "kv"), bias, stddev),
        "v": linear_init(k3, embed_dim, kv_dim, ("embed", "kv"), bias, stddev),
        "o": linear_init(k4, q_dim, embed_dim, ("heads", "embed"), bias,
                         out_stddev or stddev),
    }


def _repeat_kv(x, n_rep):
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def dot_product_attention(q, k, v, mask=None, scale=None, dropout_rate=0.0,
                          dropout_rng=None, alibi_bias=None,
                          logits_dtype=None):
    """Plain XLA attention: softmax(q k^T / sqrt(d)) v, fp32 softmax.

    The reference's fused softmax/dropout kernels (csrc/transformer/softmax_kernels.cu,
    dropout_kernels.cu) are XLA fusions here; the flash/pallas path lives in
    ``ops/flash_attention.py`` and is selected by the model config.
    q,k,v: [batch, seq, heads, head_dim]

    ``logits_dtype=jnp.bfloat16`` materializes the [b,h,q,kv] logits/probs in
    bf16 (HALF the attention HBM traffic — the profiled single-chip MFU
    bottleneck at the bench shape) with a max-subtracted exp and an fp32
    normalization sum, so only the per-element mantissa rounds; default fp32
    is bit-identical to before.
    """
    head_dim = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(head_dim)
    ldt = jnp.float32 if logits_dtype is None else jnp.dtype(logits_dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=ldt) * jnp.asarray(scale, ldt)
    logits = checkpoint_name(logits, "attn_logits")
    if alibi_bias is not None:
        logits = logits + alibi_bias.astype(ldt)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(ldt).min)
    if ldt == jnp.float32:
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        # stable low-precision softmax: bf16 exp (keeps the [q,kv] tensor
        # narrow in HBM); the row max is exact in any dtype (order-stable,
        # no accumulation) — only the normalization SUM needs fp32. The
        # normalization multiplies by the fp32-accumulated reciprocal ROUNDED
        # to ldt, so no full-size fp32 [b,h,q,kv] intermediate exists even
        # inside fusions (pinned by test_bf16_attention_logits_hlo_buffer_
        # dtype); the reciprocal's rounding error (~2^-8 relative) is below
        # the bf16 output rounding already accepted on every element
        m = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(logits - m)
        denom = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
        probs = e * (1.0 / denom).astype(ldt)
    probs = checkpoint_name(probs, "attn_probs")
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = probs * keep / (1.0 - dropout_rate)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_mask(q_len, kv_len, dtype=jnp.bool_):
    """[1, 1, q, kv] lower-triangular mask aligned to the end of the kv window."""
    q_idx = jnp.arange(q_len)[:, None]
    kv_idx = jnp.arange(kv_len)[None, :]
    offset = kv_len - q_len
    return (kv_idx <= q_idx + offset)[None, None, :, :].astype(dtype)


def rotary_embedding(positions, head_dim, base=10000.0, dtype=jnp.float32):
    """RoPE cos/sin tables (reference csrc/transformer/inference/apply_rotary_pos_emb.cu)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., head_dim/2]
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x, cos, sin, rotary_dim=None, interleaved=False):
    """x: [batch, seq, heads, head_dim]; cos/sin: [batch, seq, rd/2].

    ``rotary_dim``: rotate only the first rd dims of each head (GPT-J/NeoX
    partial rotary), pass the remainder through unchanged.
    ``interleaved``: rotate (x0,x1),(x2,x3),... pairs (GPT-J rotate-every-two)
    instead of the half-split (x_i, x_{i+d/2}) convention (NeoX/LLaMA)."""
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        x_rot, x_pass = x[..., :rotary_dim], x[..., rotary_dim:]
        return jnp.concatenate(
            [apply_rotary(x_rot, cos, sin, interleaved=interleaved), x_pass],
            axis=-1)
    cos = cos[:, :, None, :].astype(x.dtype)
    sin = sin[:, :, None, :].astype(x.dtype)
    if interleaved:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.reshape(x.shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def alibi_slopes(n_heads):
    """ALiBi slopes (reference inference kernels support alibi for BLOOM)."""
    def pow2slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        return jnp.asarray(pow2slopes(n_heads))
    closest = 2 ** math.floor(math.log2(n_heads))
    base = pow2slopes(closest)
    extra = pow2slopes(2 * closest)[0::2][: n_heads - closest]
    return jnp.asarray(base + extra)


def alibi_bias(n_heads, q_len, kv_len):
    """[1, heads, q, kv] additive bias."""
    slopes = alibi_slopes(n_heads)  # [h]
    kv_idx = jnp.arange(kv_len)[None, :]
    q_idx = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    dist = kv_idx - q_idx  # <= 0 within causal window
    return (slopes[:, None, None] * dist[None, :, :])[None].astype(jnp.float32)


def dropout(rng, x, rate, deterministic):
    if deterministic or rate == 0.0:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return x * keep / (1.0 - rate)
