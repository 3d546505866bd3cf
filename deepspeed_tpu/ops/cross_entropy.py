"""Fused (vocab-chunked) softmax cross-entropy.

The reference computes the LM loss as a full [batch*seq, vocab] logit matrix
followed by softmax-cross-entropy (torch does the same); at vocab ~50k and fp32
that matrix is the single largest activation in the model — 3.3 GB for a
16x1024 batch — and it is materialized twice (fwd logits + bwd dlogits).

TPU-native replacement: the head matmul and the softmax-CE are one fused op,
chunked over the vocabulary with an online logsumexp — the [tokens, vocab]
matrix never exists. The backward recomputes each chunk's logits (one extra
tokens x d x vocab matmul, ~flops of the head itself) and streams
``dlogits_chunk @ E_chunk`` / ``dlogits_chunk^T @ x`` — O(tokens x d) memory.

This is the same trade the reference's fused training kernels make
(``csrc/transformer/softmax_kernels.cu``: recompute-in-bwd instead of
materialize) applied to the LM head, where it matters most on TPU.

API: embedding in vocab-major layout [V, d] (the tied-``wte`` convention).
"""

import functools

import jax
import jax.numpy as jnp


def _chunking(vocab, n_chunks):
    """(n_chunks, chunk, padded_vocab): uniform chunks via padding — a divisor
    search would silently fall back to ONE chunk for prime-ish vocabs (GPT-2's
    50257!) and materialize the full logit matrix, voiding the op entirely."""
    nc = max(1, min(n_chunks, vocab))
    chunk = -(-vocab // nc)  # ceil
    return nc, chunk, nc * chunk


def _pad_emb(emb, padded_vocab):
    vocab = emb.shape[0]
    if padded_vocab == vocab:
        return emb
    return jnp.pad(emb, ((0, padded_vocab - vocab), (0, 0)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def fused_cross_entropy(x, emb, labels, bias=None, ignore_index=-100,
                        n_chunks=8, impl="xla", interpret=False, mesh=None):
    """Token-mean CE of ``softmax(x @ emb^T + bias)`` against ``labels``.

    x: [tokens, d] (compute dtype); emb: [V, d]; ``bias``: optional [V] logit
    bias (GPT-J-style biased LM head); labels: [tokens] int (``ignore_index``
    entries masked out). Returns a scalar fp32 loss.

    ``impl="pallas"`` streams the forward through the Pallas kernel
    (``ops/pallas/cross_entropy.py`` — chunk logits never touch HBM); the
    backward is the chunked XLA path either way (its cost is two MXU GEMMs
    XLA already runs at peak). ``mesh``: the mesh the surrounding program is
    partitioned over — the kernel then runs per token shard (rows are
    independent; GSPMD cannot partition a Mosaic call).
    """
    loss, _ = _ce_fwd_impl(x, emb, labels, bias, ignore_index, n_chunks,
                           impl, interpret, mesh)
    return loss


def _pad_bias(bias, padded_vocab):
    if bias is None or padded_vocab == bias.shape[0]:
        return bias
    return jnp.pad(bias, (0, padded_vocab - bias.shape[0]))


def _ce_fwd_impl(x, emb, labels, bias, ignore_index, n_chunks, impl="xla",
                 interpret=False, mesh=None):
    if impl not in ("xla", "pallas"):
        # checked here (not in the custom_vjp primal, which grad bypasses)
        # so a typo'd config can never silently bench the wrong kernel
        raise ValueError(f"fused_cross_entropy impl must be 'xla' or "
                         f"'pallas', got {impl!r}")
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0).astype(jnp.int32)
    if impl == "pallas":
        from .pallas import shard_kernel
        from .pallas.cross_entropy import pallas_ce_forward

        tokens = {0: ("data", "expert")}  # rows are independent
        operands, dims = [x, emb, safe_labels], [tokens, {}, tokens]
        if bias is not None:
            operands.append(bias)
            dims.append({})
        lse, lab_logit = shard_kernel(
            lambda *ops: pallas_ce_forward(*ops, interpret=interpret),
            mesh, operands, dims, [tokens, tokens])
        n_valid = jnp.maximum(jnp.sum(valid), 1)
        loss = jnp.sum((lse - lab_logit) * valid) / n_valid
        return loss, (lse, n_valid)
    tokens, d = x.shape
    vocab = emb.shape[0]
    nc, chunk, padded = _chunking(vocab, n_chunks)
    emb_c = _pad_emb(emb, padded).reshape(nc, chunk, d)
    bias_c = None if bias is None \
        else _pad_bias(bias, padded).reshape(nc, chunk)
    starts = jnp.arange(nc, dtype=jnp.int32) * chunk

    def body(carry, inp):
        m, s, lab_logit = carry
        e_c, b_c, c0 = inp
        logits = jax.lax.dot_general(
            x, e_c.astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [tokens, chunk]
        if b_c is not None:
            logits = logits + b_c.astype(jnp.float32)[None, :]
        if padded != vocab:
            # padded (fake-vocab) columns must not contribute to the logsumexp
            col = c0 + jnp.arange(chunk, dtype=jnp.int32)[None, :]
            logits = jnp.where(col < vocab, logits, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1)
        in_chunk = (safe_labels >= c0) & (safe_labels < c0 + chunk)
        idx = jnp.clip(safe_labels - c0, 0, chunk - 1)
        ll = jnp.take_along_axis(logits, idx[:, None], axis=1)[:, 0]
        lab_logit = jnp.where(in_chunk, ll, lab_logit)
        return (m_new, s, lab_logit), None

    m0 = jnp.full((tokens,), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((tokens,), jnp.float32)
    ll0 = jnp.zeros((tokens,), jnp.float32)
    (m, s, lab_logit), _ = jax.lax.scan(body, (m0, s0, ll0),
                                        (emb_c, bias_c, starts))

    lse = m + jnp.log(s)
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum((lse - lab_logit) * valid) / n_valid
    return loss, (lse, n_valid)


def _ce_vjp_fwd(x, emb, labels, bias, ignore_index, n_chunks, impl,
                interpret, mesh):
    loss, (lse, n_valid) = _ce_fwd_impl(x, emb, labels, bias, ignore_index,
                                        n_chunks, impl, interpret, mesh)
    return loss, (x, emb, labels, bias, lse, n_valid)


def _ce_vjp_bwd(ignore_index, n_chunks, impl, interpret, mesh, residuals, g):
    x, emb, labels, bias, lse, n_valid = residuals
    tokens, d = x.shape
    vocab = emb.shape[0]
    nc, chunk, padded = _chunking(vocab, n_chunks)
    emb_c = _pad_emb(emb, padded).reshape(nc, chunk, d)
    bias_c = None if bias is None \
        else _pad_bias(bias, padded).reshape(nc, chunk)
    starts = jnp.arange(nc, dtype=jnp.int32) * chunk

    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0).astype(jnp.int32)
    coef = (g / n_valid.astype(jnp.float32)) * valid.astype(jnp.float32)  # [tokens]

    def body(dx_acc, inp):
        e_c, b_c, c0 = inp
        logits = jax.lax.dot_general(
            x, e_c.astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [tokens, chunk]
        if b_c is not None:
            logits = logits + b_c.astype(jnp.float32)[None, :]
        p = jnp.exp(logits - lse[:, None])
        if padded != vocab:
            col = c0 + jnp.arange(chunk, dtype=jnp.int32)[None, :]
            p = jnp.where(col < vocab, p, 0.0)
        in_chunk = (safe_labels >= c0) & (safe_labels < c0 + chunk)
        idx = jnp.clip(safe_labels - c0, 0, chunk - 1)
        onehot = (jnp.arange(chunk, dtype=jnp.int32)[None, :] == idx[:, None]) \
            & in_chunk[:, None]
        dlogits = (p - onehot.astype(jnp.float32)) * coef[:, None]  # [tokens, chunk] f32
        dl16 = dlogits.astype(x.dtype)
        dx_acc = dx_acc + jax.lax.dot_general(
            dl16, e_c.astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [tokens, d]
        de_c = jax.lax.dot_general(
            dl16, x, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [chunk, d]
        db_c = jnp.sum(dlogits, axis=0)  # [chunk]
        return dx_acc, (de_c, db_c)

    dx0 = jnp.zeros((tokens, d), jnp.float32)
    dx, (de, db) = jax.lax.scan(body, dx0, (emb_c, bias_c, starts))
    de = de.reshape(padded, d)[:vocab]
    dbias = None if bias is None \
        else db.reshape(padded)[:vocab].astype(bias.dtype)
    return dx.astype(x.dtype), de.astype(emb.dtype), None, dbias


fused_cross_entropy.defvjp(_ce_vjp_fwd, _ce_vjp_bwd)
