"""On-chip acceptance of every Pallas kernel a config value can select: each
is compiled by Mosaic at one production geometry and compared with its XLA
reference on the same device.

    python tools/kernel_check.py          # on the chip machine (one process)
    python tools/kernel_check.py "chunk attention" "grouped"   # those alone

One JSON line per kernel: ``{"kernel", "geometry", "max_err", "tol", "ok"}``
(the grouped expert products and the chunk attention also ``"ms"``: the
kernel and ``ragged_dot`` / the XLA block body timed on the same operands),
then a summary; exit code 1 if any kernel failed to compile or to match.
Arguments keep only the cases whose name holds one of them. A TPU is
required: off-chip these kernels only run under the interpreter, which
tier-1 already covers (``tests/unit/test_*attention*.py`` etc.), and
``tests/unit/test_tpu_lowering.py`` covers lowering. Tolerances are bf16
ones: both sides round to bf16 somewhere, in a different order.
"""

import json
import os
import sys
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _err(got, want):
    """Max abs error over a pytree, scaled by the reference's magnitude."""
    import jax

    worst = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        if not np.all(np.isfinite(g)):
            return float("inf")
        worst = max(worst, float(np.max(np.abs(g - w))
                                 / max(np.max(np.abs(w)), 1e-6)))
    return worst


def _ms(fn, operands, reps=10):
    """Milliseconds a call of the jitted ``fn``: ``reps`` dispatched back to
    back behind a warm one, waited for once."""
    import time

    import jax

    fn = jax.jit(fn)
    jax.block_until_ready(fn(*operands))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*operands)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / reps * 1e3, 4)


def _qkv(b, s, h, d, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, s, h, d) * 0.5, jnp.bfloat16)
                 for _ in range(3))


def _attention_case(attend, b, s, h, d, mask=None):
    """(kernel, reference, operands): fwd + bwd of ``attend(q, k, v)`` vs
    exact fp32 attention."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import layers as L

    q, k, v = _qkv(b, s, h, d)
    g = jnp.asarray(np.random.RandomState(1).randn(b, s, h, d), jnp.bfloat16)
    mask = L.causal_mask(s, s) if mask is None else mask

    def ref(q, k, v):
        return L.dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), mask=mask)

    def both(f):
        def run(q, k, v, g):
            out, vjp = jax.vjp(f, q, k, v)
            return out, vjp(g.astype(out.dtype))
        return run

    return both(attend), both(ref), (q, k, v, g)


def flash_single_block():
    from deepspeed_tpu.ops.flash_attention import flash_attention

    return ("b4 s1024 h16 d64 bf16, tiles 512x1024 fwd+bwd",
            *_attention_case(flash_attention, 4, 1024, 16, 64), 3e-2)


def flash_general():
    from deepspeed_tpu.ops.flash_attention import flash_attention

    return ("b2 s2048 h8 d128 bf16, tiles 256x512 fwd+bwd",
            *_attention_case(flash_attention, 2, 2048, 8, 128), 3e-2)


def jax_flash():
    from deepspeed_tpu.ops.flash_attention import jax_flash_attention

    return ("b4 s1024 h16 d64 bf16 fwd+bwd",
            *_attention_case(jax_flash_attention, 4, 1024, 16, 64), 3e-2)


def block_sparse():
    import jax.numpy as jnp

    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.pallas.block_sparse_attention import \
        BlockSparseAttention
    from deepspeed_tpu.ops.sparse_attention import BSLongformerSparsityConfig

    s, blk = 2048, 128
    attn = BlockSparseAttention(
        BSLongformerSparsityConfig(block=blk, num_sliding_window_blocks=3),
        s, causal=True)
    dense = jnp.asarray(np.kron(attn.layout, np.ones((blk, blk), bool)))
    mask = (dense & L.causal_mask(s, s)[0, 0])[None, None]
    return (f"b2 s2048 h8 d128 bf16 bslongformer block 128 "
            f"(density {attn.density:.2f}) fwd+bwd",
            *_attention_case(attn, 2, s, 8, 128, mask=mask), 3e-2)


def pallas_ce():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.cross_entropy import fused_cross_entropy

    rng = np.random.RandomState(0)
    tokens, d, vocab = 4096, 1024, 50304
    x = jnp.asarray(rng.randn(tokens, d) * 0.5, jnp.bfloat16)
    emb = jnp.asarray(rng.randn(vocab, d) * 0.02, jnp.float32)
    labels = jnp.asarray(rng.randint(0, vocab, (tokens,)), jnp.int32)

    def run(impl):
        f = lambda x, e, labels: fused_cross_entropy(
            x, e, labels, None, -100, 8, impl, False)
        return jax.value_and_grad(f, argnums=(0, 1))

    return ("4096 tokens x d1024 x vocab 50304 bf16, loss + grads",
            run("pallas"), run("xla"), (x, emb, labels), 2e-2)


def _paged_case(nh, kvh, dh, alibi=False, dv=None):
    """``dv``: V heads narrower than K heads (MiMo-V2's full layers: K rows
    of ``kvh * dh`` beside V rows of ``kvh * dv``)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    rng = np.random.RandomState(0)
    S, NB, bs, n_layers = 8, 64, 16, 2         # 1024-token window per slot
    n_blocks = S * NB + 1
    dt = jnp.bfloat16
    dv = dv or dh
    # the pool as the engine keeps it: a token's kv heads merged, leaves whole
    kc = jnp.asarray(rng.randn(n_layers, n_blocks, bs, kvh * dh), dt)
    vc = jnp.asarray(rng.randn(n_layers, n_blocks, bs, kvh * dv), dt)
    table = jnp.asarray(1 + rng.permutation(S * NB).reshape(S, NB), jnp.int32)
    pos = jnp.asarray([1, 15, 16, 17, 500, 777, 1000, 1023], jnp.int32)
    q = jnp.asarray(rng.randn(S, nh, dh) * 0.3, dt)
    k_new = jnp.asarray(rng.randn(S, kvh, dh), dt)
    v_new = jnp.asarray(rng.randn(S, kvh, dv), dt)
    slopes = L.alibi_slopes(nh) if alibi else None
    layer = jnp.asarray(1, jnp.int32)

    def kernel(q, k_new, v_new, kc, vc, table, pos, layer):
        return paged_flash_decode(q, k_new, v_new, kc, vc, table, pos,
                                  layer=layer, alibi_slopes=slopes)

    def ref(q, k_new, v_new, kc, vc, table, pos, layer):
        # dense per-slot view through the table, fresh row written at the
        # cursor, exact fp32 softmax over [0, pos]
        f32 = jnp.float32
        view = lambda c: c[layer].astype(f32)[table].reshape(
            S, NB * bs, kvh, -1)
        put = jax.vmap(lambda c, r, p: jax.lax.dynamic_update_slice(
            c, r[None], (p, 0, 0)))
        kk = put(view(kc), k_new.astype(f32), pos)
        vv = put(view(vc), v_new.astype(f32), pos)
        kv_idx = jnp.arange(NB * bs)[None, None, :]
        mask = (kv_idx <= pos[:, None, None])[:, None]
        bias = None
        if alibi:
            dist = (kv_idx - pos[:, None, None]).astype(f32)
            bias = slopes[None, :, None, None] * dist[:, None]
        n_rep = nh // kvh
        return L.dot_product_attention(
            q.astype(f32)[:, None], L._repeat_kv(kk, n_rep),
            L._repeat_kv(vv, n_rep), mask=mask, alibi_bias=bias)[:, 0]

    geom = (f"8 slots x 1024-token window, block 16, {nh}/{kvh} heads x "
            f"{dh}, bf16 pool [2, {n_blocks}, 16, {kvh * dh}], layer 1"
            + (", alibi" if alibi else "")
            + (f", V rows of {kvh * dv}" if dv != dh else ""))
    return geom, kernel, ref, (q, k_new, v_new, kc, vc, table, pos, layer), \
        3e-2


def _paged_band_case(nh, kvh, dh, window, bs, dv=None, sink=False):
    """A window layer's calls (``models/window_moe.py``): the band of the
    last ``window`` positions over a RING of blocks a slot, block ``j`` at
    table column ``j % ring``; cursors before the band fills, at its edge
    and several laps of the ring on. ``dv``: V heads narrower than K heads;
    ``sink``: a logit a head in the softmax's sum (MiMo-V2's window
    layers)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    rng = np.random.RandomState(0)
    S, n_layers = 8, 2
    ring = -(-window // bs) + 1
    n_blocks = S * ring + 1
    dt = jnp.bfloat16
    dv = dv or dh
    kc = jnp.asarray(rng.randn(n_layers, n_blocks, bs, kvh * dh), dt)
    vc = jnp.asarray(rng.randn(n_layers, n_blocks, bs, kvh * dv), dt)
    sinks = jnp.asarray(1.0 + 0.5 * rng.randn(nh), jnp.float32) \
        if sink else None
    table = jnp.asarray(1 + rng.permutation(S * ring).reshape(S, ring),
                        jnp.int32)
    pos = jnp.asarray([1, bs - 1, window - 1, window, window + 1,
                       2 * window + bs + 5, 9 * window + 77,
                       15 * window - 1], jnp.int32)
    q = jnp.asarray(rng.randn(S, nh, dh) * 0.3, dt)
    k_new = jnp.asarray(rng.randn(S, kvh, dh), dt)
    v_new = jnp.asarray(rng.randn(S, kvh, dv), dt)
    layer = jnp.asarray(1, jnp.int32)

    def kernel(q, k_new, v_new, kc, vc, table, pos, layer):
        return paged_flash_decode(q, k_new, v_new, kc, vc, table, pos,
                                  layer=layer, window=window, ring=True,
                                  sink=sinks)

    def ref(q, k_new, v_new, kc, vc, table, pos, layer):
        # the slot's ring through the table; row (c, o) holds position
        # b * bs + o of the newest block b <= cursor's with b % ring == c;
        # the fresh row beside it; exact fp32 softmax over the band
        f32 = jnp.float32
        view = lambda c: c[layer].astype(f32)[table].reshape(
            S, ring * bs, kvh, -1)
        cur = pos // bs
        block = cur[:, None] - (cur[:, None] - jnp.arange(ring)[None]) % ring
        k_pos = (block[:, :, None] * bs + jnp.arange(bs)).reshape(S, -1)
        seen = (k_pos >= 0) & (k_pos < pos[:, None]) \
            & (pos[:, None] - k_pos < window)
        g = nh // kvh
        qg = q.astype(f32).reshape(S, kvh, g, dh)
        sc = jnp.einsum("sgrd,stgd->sgrt", qg, view(kc)) / np.sqrt(dh)
        sc = jnp.where(seen[:, None, None], sc, -jnp.inf)
        own = jnp.einsum("sgrd,sgd->sgr", qg, k_new.astype(f32)) \
            / np.sqrt(dh)
        cols = [sc, own[..., None]]
        if sink:
            # one more column a head, with no value row
            cols.append(jnp.broadcast_to(sinks.reshape(1, kvh, g, 1),
                                         own.shape + (1,)))
        p = jax.nn.softmax(jnp.concatenate(cols, -1), -1)
        n = ring * bs
        out = jnp.einsum("sgrt,stgd->sgrd", p[..., :n], view(vc)) \
            + p[..., n:n + 1] * v_new.astype(f32)[:, :, None]
        return out.reshape(S, nh, dv)

    geom = (f"8 slots, band {window} over a ring of {ring} blocks of {bs}, "
            f"{nh}/{kvh} heads x {dh}, bf16 pool [2, {n_blocks}, {bs}, "
            f"{kvh * dh}], layer 1, cursors to {15 * window - 1}"
            + (f", V rows of {kvh * dv}" if dv != dh else "")
            + (", a sink a head" if sink else ""))
    return geom, kernel, ref, (q, k_new, v_new, kc, vc, table, pos, layer), \
        3e-2


def _paged_latent_case(live_slots, live_rows, chunk_tokens=None):
    """The kernel's latent form (``paged_latent_decode``) as the kanana
    serve cell's decode calls it: 32 slots over the pool's whole leaves, 7
    layers of 2,561 blocks of 128 latent rows (512 + a 64-wide rope key),
    tables of 128 columns, ``live_slots`` slots whose cursors hold
    ``live_rows`` rows between them and the rest at cursor 0. Against the
    view in float32: the slots' rows gathered through the table, exact
    softmax over ``[0, pos]``. TIMED beside the view path's bf16 gather and
    einsums (``models/latent.py:absorbed_attention``); the geometry names
    the byte floor, one read of the live rows at 819 GB/s."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.paged_attention import paged_latent_decode

    S, H, r, dr, bs, cols, n_layers, n_blocks = 32, 32, 512, 64, 128, 128, \
        7, 2561
    dt, f32 = jnp.bfloat16, jnp.float32
    scale = 1.0 / np.sqrt(192)
    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    normal = lambda i, shape, sd=1.0: (jax.random.normal(
        jax.random.fold_in(key, i), shape, f32) * sd).astype(dt)
    # the pool made on the device (2.6 GB), laid out as the engine's is
    kc = normal(0, (n_layers, n_blocks, bs, 1, r))
    krc = normal(1, (n_layers, n_blocks, bs, 1, dr))
    # cursors of 2-16k spread over the live slots, summing to live_rows
    share = np.linspace(1.0, 7.0, live_slots)
    cur = np.minimum(np.floor(share / share.sum() * live_rows), cols * bs - 1)
    cur[-1] += live_rows - cur.sum()
    pos = np.zeros(S, np.int32)
    pos[rng.permutation(S)[:live_slots]] = cur.astype(np.int32)
    table = np.zeros((S, cols), np.int32)
    free = 1 + rng.permutation(n_blocks - 1)
    for s in range(S):
        need = -(-int(pos[s] + 1) // bs) if pos[s] else 0
        table[s, :need], free = free[:need], free[need:]
    q_lat, q_rope = normal(2, (S, H, r), 0.3), normal(3, (S, H, dr), 0.3)
    c_new, kr_new = normal(4, (S, r)), normal(5, (S, dr))
    layer = jnp.asarray(3, jnp.int32)

    def kernel(q_lat, q_rope, c_new, kr_new, kc, krc, table, pos, layer):
        return paged_latent_decode(q_lat, q_rope, c_new, kr_new, kc, krc,
                                   table, pos, layer=layer, scale=scale,
                                   chunk_tokens=chunk_tokens)

    def view(dtype):
        def attend(q_lat, q_rope, c_new, kr_new, kc, krc, table, pos, layer):
            # the slots' rows gathered through the table, the fresh row
            # written at the cursor, softmax over [0, pos]
            rows = lambda leaf, new: jax.vmap(
                lambda v, n, p: jax.lax.dynamic_update_slice(
                    v, n[None], (p, 0)))(
                leaf[layer][table][:, :, :, 0].reshape(S, cols * bs, -1)
                .astype(dtype), new.astype(dtype), pos)
            c, kr = rows(kc, c_new), rows(krc, kr_new)
            prec = jax.lax.Precision.HIGHEST if dtype == f32 else None
            s = (jnp.einsum("shr,skr->shk", q_lat.astype(dtype), c,
                            precision=prec, preferred_element_type=f32)
                 + jnp.einsum("shd,skd->shk", q_rope.astype(dtype), kr,
                              precision=prec, preferred_element_type=f32))
            seen = jnp.arange(cols * bs)[None, None] <= pos[:, None, None]
            p = jax.nn.softmax(jnp.where(seen, s * scale, -jnp.inf), -1)
            return jnp.einsum("shk,skr->shr", p.astype(dtype), c,
                              precision=prec)
        return attend

    floor_ms = live_rows * (r + dr) * 2 / 819e9 * 1e3
    return (f"32 slots, {live_slots} live over {live_rows} latent rows, pool "
            f"[7, 2561, 128, 1, 512 + 64] bf16, table 128, layer 3, chunk "
            f"{chunk_tokens or 'by bytes'}; floor {floor_ms:.4f} ms",
            kernel, view(f32),
            (q_lat, q_rope, c_new, kr_new, kc, krc, jnp.asarray(table),
             jnp.asarray(pos), layer), 3e-2, {"view": view(dt)})


def _qmm_case(bits):
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.quantized_matmul import quantized_matmul
    from deepspeed_tpu.ops.quantizer import (dequantize_per_channel,
                                             pack_int4, quantize_per_channel,
                                             unpack_int4)

    rng = np.random.RandomState(0)
    args = []
    for k, n in ((2048, 8192), (8192, 2048)):   # OPT-1.3B fc / proj
        w = jnp.asarray(rng.randn(k, n) * 0.05, jnp.float32)
        q, scale = quantize_per_channel(w, bits=bits, group_size=64)
        args.append((jnp.asarray(rng.randn(8, k), jnp.bfloat16),
                     pack_int4(q) if bits == 4 else q, scale))

    def fused(*args):
        return [quantized_matmul(x, p, s, bits=bits) for x, p, s in args]

    def ref(*args):
        return [x.astype(jnp.float32) @ dequantize_per_channel(
            unpack_int4(p) if bits == 4 else p, s, jnp.float32)
            for x, p, s in args]

    return (f"int{bits} m8, [2048x8192] and [8192x2048], group 64, bf16",
            fused, ref, tuple(args), 2e-2)


def _block_write_case(int8):
    import jax.numpy as jnp

    from deepspeed_tpu.models.decoding import insert_block_kv

    rng = np.random.RandomState(0)
    L, n_blocks, bs, kvh, dh, max_len = 4, 1537, 16, 32, 64, 2048
    shape = (L, n_blocks, bs, kvh * dh)
    if int8:
        pool = {"k": jnp.asarray(rng.randint(-127, 128, shape), jnp.int8),
                "k_scale": jnp.asarray(rng.rand(*shape[:-1], kvh),
                                       jnp.float32)}
        pool.update(v=-pool["k"], v_scale=2 * pool["k_scale"])
    else:
        pool = {"k": jnp.asarray(rng.randn(*shape), jnp.bfloat16)}
        pool["v"] = -pool["k"]
    cache = {n: jnp.asarray(rng.randn(L, 1, max_len, kvh, dh), jnp.bfloat16)
             for n in ("k", "v")}
    # 37 blocks off a fragmented free list (6 of the 13 columns, the last
    # one among them), sources past a 3-block shared prefix, then padding
    nb = max_len // bs
    ids = n_blocks + np.arange(nb, dtype=np.int32)
    ids[:37] = np.concatenate([np.arange(120, 140), np.arange(700, 710),
                               [1536, 5, 900, 901, 1300, 1301, 1302]])
    srcs = np.zeros((nb,), np.int32)
    srcs[:37] = 3 + np.arange(37)

    def run(lanes):
        return lambda pool, cache, ids, srcs: insert_block_kv(
            pool, cache, ids, srcs, bs, lanes=lanes)

    return (f"{'int8' if int8 else 'bf16'} pool [4, 1537, 16, 2048], 37 of "
            "128 entries real, exact", run(True), run(False),
            (pool, cache, jnp.asarray(ids), jnp.asarray(srcs)), 0.0)


def _skewed_sizes(rng, n_groups, m, empty=3, sigma=0.6):
    """``m`` rows dealt over ``n_groups`` as a serve cell's routing deals a
    chunk's pairs (random weights, a drawn selection bias): a few experts
    several times the mean (about 230 of 8,192 where the mean is 64), a few
    with none."""
    p = np.exp(sigma * rng.randn(n_groups))
    p[rng.choice(n_groups, empty, replace=False)] = 0
    return rng.multinomial(m, p / p.sum()).astype(np.int32)


def _grouped_product_case(n_layers, K, N, M, dtype="bfloat16", E=128):
    """The drop-free expert layer's grouped product (``moe/dropfree.py``:
    ``ops/pallas/grouped_matmul.py`` on a TPU) as the served programs call
    it: ``M`` rows sorted by expert, the weights a stack of ``n_layers`` x
    ``E`` groups of which one layer's have rows, against every expert of
    that layer computed for every row and masked. TIMED beside
    ``jax.lax.ragged_dot`` on the same operands (the row's ``ms``), so the
    choice ``product_path`` makes can be checked again in one call."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.moe import dropfree

    dtype = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    layer = n_layers // 2
    decode = M < 2 * E
    live = _skewed_sizes(rng, E, M, empty=0 if decode else 3,
                         sigma=0.3 if decode else 0.6)
    sizes = np.zeros(n_layers * E, np.int32)
    sizes[layer * E:(layer + 1) * E] = live
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(key, (M, K), dtype)
    w = jnp.concatenate([
        jax.random.normal(jax.random.fold_in(key, l), (E, K, N), dtype) * 0.02
        for l in range(n_layers)])

    def ref(rows, w, sizes):
        ends = jnp.cumsum(sizes)
        idx = jnp.arange(M)

        def one(e, acc):
            g = layer * E + e
            mine = (idx >= ends[g] - sizes[g]) & (idx < ends[g])
            return acc + jnp.where(mine[:, None], jnp.dot(
                rows, w[g], preferred_element_type=jnp.float32,
                precision=dropfree._precision(dtype)), 0.0)

        return jax.lax.fori_loop(0, E, one, jnp.zeros((M, N), jnp.float32))

    return (f"{M} rows x [{n_layers * E}, {K}, {N}] {dtype.name}, one layer's "
            f"{E} groups live: largest {int(live.max())}, "
            f"{int((live == 0).sum())} empty; path "
            f"{dropfree.product_path(M)}", dropfree.grouped_product, ref,
            (rows, w, jnp.asarray(sizes)), 0.02,
            {"ragged_dot": dropfree._ragged_product})


def _chunk_attention_case(groups, rep, dk, window, blk, diagonal=False):
    """One key block of a 1024-token prefill chunk's attention as the served
    chunk programs fold it (``ops/pallas/chunk_attention.py`` on a TPU),
    against the XLA block body the models keep for the uncached forward, on
    the kernel's layout, from a carry a block of history already filled (V
    heads of 128). TIMED beside that body on the same operands (the row's
    ``ms``): a block inside the causal past (no tile masked) or, with
    ``diagonal``, the block that holds the queries."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas import chunk_attention as C

    f32, dt = jnp.float32, jnp.bfloat16
    q_len, dv, scale = 1024, 128, 1.0 / np.sqrt(dk)
    rows = q_len * rep
    start = 4 * blk
    q_start = start + (0 if diagonal else blk + 1024)
    key = jax.random.PRNGKey(0)
    normal = lambda i, shape: jax.random.normal(
        jax.random.fold_in(key, i), shape, f32).astype(dt)
    q = normal(0, (1, groups, rows, dk))
    k, v = normal(1, (1, groups, blk, dk)), normal(2, (1, groups, blk, dv))
    m = jax.random.normal(jax.random.fold_in(key, 3), (1, groups, rows)) * 2
    l = 1.0 + jax.random.uniform(jax.random.fold_in(key, 4), m.shape) * 50
    acc = jax.random.normal(jax.random.fold_in(key, 5), m.shape + (dv,)) * 5
    stat = C.initial_carry(1, groups, rows, dv, m)[0]
    stat = jnp.where(jnp.arange(C.STAT_LANES) == 1, l[..., None], stat)

    def kernel(q, k, v, stat, m, l, acc):
        return C.finish(C.chunk_attention_block(
            q, k, v, (stat, acc), q_start, start, start, rep=rep,
            scale=scale, window=window), f32)

    def xla(q, k, v, stat, m, l, acc):
        q_idx = q_start + jnp.arange(rows) // rep
        k_idx = start + jnp.arange(blk)
        s = jnp.einsum("bgqd,bgkd->bgqk", q, k,
                       preferred_element_type=f32) * scale
        seen = k_idx[None, :] <= q_idx[:, None]
        if window:
            seen &= q_idx[:, None] - k_idx[None, :] < window
        s = jnp.where(seen, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        e = jnp.exp(s - m_new[..., None])
        fix = jnp.exp(m - m_new)
        acc = acc * fix[..., None] + jnp.einsum(
            "bgqk,bgkd->bgqd", e.astype(dt), v, preferred_element_type=f32)
        return acc / (l * fix + jnp.sum(e, axis=-1))[..., None]

    tq, tk = C.chunk_tiles(q_len, rep, blk, window)
    return (f"{groups} groups x {rep} heads, K {dk} / V {dv}, a 1024-token "
            f"chunk against a block of {blk}"
            + (f", band {window}" if window else "")
            + (", the diagonal block" if diagonal else ", all in the past")
            + f"; tiles {tq} x {tk}", kernel, xla,
            (q, k, v, stat, m, l, acc), 3e-2, {"xla": xla})


def _ssm_state_update_case(slots=128, heads=128, head_dim=64, state=128,
                           groups=8):
    """The decode step's Mamba-2 recurrence (``ops/pallas/
    ssm_state_update.py``) at the nemotron cell's widths, one layer of a
    stack of two, against the XLA form ``models/hybrid.state_update``
    writing the same layer. (No time: the kernel writes its operand in
    place, and a call that does not donate it copies the stack first; the
    cell's trace times it, ``ssm_state_roofline_pct``.)"""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid
    from deepspeed_tpu.ops.pallas.ssm_state_update import ssm_state_update

    key = jax.random.PRNGKey(0)
    f32 = jnp.float32
    normal = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i),
                                                shape, f32)
    states = normal(0, (2, slots, heads, head_dim, state))
    da = jax.random.uniform(jax.random.fold_in(key, 1), (slots, heads), f32,
                            0.5, 1.0)
    dtx = normal(2, (slots, heads, head_dim)) * 0.1
    b, c = normal(3, (slots, groups, state)), normal(4, (slots, groups, state))

    def xla(states, da, dtx, b, c):
        y, new = hybrid.state_update(states[1], da, dtx, b, c)
        return y, states.at[1].set(new)

    return (f"{slots} slots x {heads} heads x [{head_dim}, {state}] float32 "
            f"in a stack of 2 layers, {groups} groups of B and C",
            lambda *a: ssm_state_update(a[0], 1, *a[1:]), xla,
            (states, da, dtx, b, c), 1e-5)


def _ssd_case(tokens=1024, heads=128, head_dim=64, state=128, groups=8,
              block=128):
    """The prefill chunk's chunked scan (SSD, ``models/hybrid.ssd_scan``,
    XLA einsums) over ``tokens`` positions at the nemotron cell's widths,
    from a state that is not zero, against the recurrence token by token
    (``lax.scan``). TIMED beside that recurrence (the row's ``ms``): what a
    scan kernel would have to beat."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import hybrid

    @dataclasses.dataclass
    class Shape:
        ssm_chunk: int = block

    key = jax.random.PRNGKey(0)
    f32 = jnp.float32
    normal = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i),
                                                shape, f32)
    x = normal(0, (1, tokens, heads, head_dim))
    dt = jax.nn.softplus(normal(1, (1, tokens, heads)) - 4.0)
    A = -jnp.arange(1, heads + 1, dtype=f32)
    B, C = (normal(i, (1, tokens, groups, state)) * 0.3 for i in (2, 3))
    s0 = normal(4, (1, heads, head_dim, state))

    def ssd(x, dt, B, C, s0):
        with jax.default_matmul_precision("highest"):
            return hybrid.ssd_scan(Shape(), x, dt, A, B, C, s0)

    def recurrence(x, dt, B, C, s0):
        def one(s, t):
            xt, dtt, bt, ct = t
            y, s = hybrid.state_update(s, jnp.exp(dtt * A), dtt[..., None]
                                       * xt, bt, ct)
            return s, y

        s, y = jax.lax.scan(one, s0, (x[0][:, None], dt[0][:, None],
                                      B[0][:, None], C[0][:, None]))
        return y[:, 0][None], s

    return (f"{tokens} positions in blocks of {block}, {heads} heads x "
            f"[{head_dim}, {state}], {groups} groups", ssd, recurrence,
            (x, dt, B, C, s0), 1e-4, {"recurrence": recurrence})


CASES = {
    "ssm state update (nemotron decode, 128 slots)": _ssm_state_update_case,
    "ssd chunk scan (nemotron chunk, 1024 tokens)": _ssd_case,
    "chunk attention (kanana2, 32 x 1, block 2048)":
        lambda: _chunk_attention_case(32, 1, 192, 0, 2048),
    "chunk attention (kanana2, 32 x 1, block 2048, diagonal)":
        lambda: _chunk_attention_case(32, 1, 192, 0, 2048, diagonal=True),
    "chunk attention (trinity window, 4 x 8, band 2048)":
        lambda: _chunk_attention_case(4, 8, 128, 2048, 1024),
    "chunk attention (trinity full, 4 x 8)":
        lambda: _chunk_attention_case(4, 8, 128, 0, 1024),
    "chunk attention (mimo window, 8 x 8, band 128)":
        lambda: _chunk_attention_case(8, 8, 192, 128, 1024, diagonal=True),
    "chunk attention (mimo full, 4 x 16)":
        lambda: _chunk_attention_case(4, 16, 192, 0, 1024),
    "grouped expert product (trinity chunk, gate and up)":
        lambda: _grouped_product_case(4, 2048, 2048, 8192),
    "grouped expert product (trinity chunk, down)":
        lambda: _grouped_product_case(4, 1024, 2048, 8192),
    "grouped expert product (kanana2 chunk, gate and up)":
        lambda: _grouped_product_case(6, 2048, 1536, 6144),
    "grouped expert product (kanana2 chunk, down)":
        lambda: _grouped_product_case(6, 768, 2048, 6144),
    "grouped expert product (trinity decode, gate and up)":
        lambda: _grouped_product_case(4, 2048, 2048, 256),
    "grouped expert product (kanana2 decode, gate and up)":
        lambda: _grouped_product_case(6, 2048, 1536, 192),
    "grouped expert product (float32, 1,536 rows a group)":
        lambda: _grouped_product_case(2, 256, 512, 12288, "float32", E=8),
    "flash fwd+bwd (single kv block)": flash_single_block,
    "flash fwd+bwd (general)": flash_general,
    "jax_flash fwd+bwd": jax_flash,
    "block_sparse fwd+bwd": block_sparse,
    "pallas CE forward": pallas_ce,
    "paged decode (BLOOM class 16x128, alibi)":
        lambda: _paged_case(16, 16, 128, alibi=True),
    "paged decode (OPT class 32x64)": lambda: _paged_case(32, 32, 64),
    "paged decode (GQA 32/8x128)": lambda: _paged_case(32, 8, 128),
    "paged decode (GQA 32/4x128, band 2048 over a ring)":
        lambda: _paged_band_case(32, 4, 128, 2048, 128),
    "paged decode (GQA 64/4, K 192 over V 128)":
        lambda: _paged_case(64, 4, 192, dv=128),
    "paged decode (GQA 64/8, K 192 over V 128, band 128 + sink, ring 2)":
        lambda: _paged_band_case(64, 8, 192, 128, 128, dv=128, sink=True),
    "paged decode (kanana2 latent, 14 slots over 88k rows)":
        lambda: _paged_latent_case(14, 88_000),
    "paged decode (kanana2 latent, 28 slots over 176k rows)":
        lambda: _paged_latent_case(28, 176_000),
    "quantized matmul int8": lambda: _qmm_case(8),
    "quantized matmul int4": lambda: _qmm_case(4),
    "kv block write (bf16 pool)": lambda: _block_write_case(False),
    "kv block write (int8 pool)": lambda: _block_write_case(True),
}


def _check(name, case):
    """One case's JSON row; its operands (a stack of experts is GBs) die
    with this frame, before the next case makes its own."""
    import jax

    try:
        geometry, kernel, reference, operands, tol, *others = case()
        err = _err(jax.jit(kernel)(*operands), jax.jit(reference)(*operands))
        row = {"kernel": name, "geometry": geometry,
               "max_err": round(err, 5), "tol": tol, "ok": err <= tol}
        if others:
            row["ms"] = {n: _ms(f, operands) for n, f in
                         dict(kernel=kernel, **others[0]).items()}
    except Exception as e:
        traceback.print_exc()
        row = {"kernel": name, "ok": False,
               "error": " ".join(str(e).split())[:600]}
    return row


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"kernel_check compiles for a TPU; platform is "
                         f"{dev.platform!r} - refusing to run")
    from _common import setup_compile_cache

    setup_compile_cache()
    print(json.dumps({"device_kind": dev.device_kind, "jax": jax.__version__}),
          flush=True)
    failed = []
    cases = {name: case for name, case in CASES.items()
             if not sys.argv[1:] or any(a in name for a in sys.argv[1:])}
    for name, case in cases.items():
        row = _check(name, case)
        if not row["ok"]:
            failed.append(name)
        print(json.dumps(row), flush=True)
    print(f"# {len(cases) - len(failed)}/{len(cases)} kernels compiled and "
          f"matched on {dev.device_kind}"
          + (f"; FAILED: {failed}" if failed else ""), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
