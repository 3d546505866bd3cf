"""Flash-attention kernel benchmark: Pallas vs XLA across sequence lengths.

Answers "does the Pallas kernel actually win, and where?". Runs on the chip
only (one process; refuses any other platform):

    python tools/bench_attention.py            # fwd+bwd train-shape sweep
    BENCH_FWD_ONLY=1 python tools/bench_attention.py
    BENCH_DECODE=1 python tools/bench_attention.py   # serving decode shapes

Prints one line per (seq, impl): ms/iter and achieved TFLOP/s; causal
attention flops = 2 * 0.5 * s^2 * d * 3 matmuls fwd (+~2.5x bwd).

``BENCH_DECODE=1`` switches to the serving decode shape: 1 query row per
slot against a paged KV pool ``[L, n_blocks, block, kvh * dh]``, and A/Bs the
two decode-attention paths of ``models/decoding.forward_with_paged_cache``
(the ``n_slots x max_len`` gather view, the table-walking kernel) at the OPT
serve cell's geometry by default (32 x 64 heads, 32 slots of 2048 positions,
blocks of 16), every slot full and at the cell's ragged cursors (mean about
510). Each impl runs ``BENCH_DECODE_LAYERS`` layers inside one program, as
the decode step's layer loop does. Decode is bandwidth-bound, so the printed
GB/s over the LIVE KV (bytes below the cursors / measured time) is the number
that matters: the view moves the padded window, written and read again; the
kernel streams the live blocks once.
"""

import os
import sys
import time

import numpy as np


def decode_main():
    """Decode-shape sweep (BENCH_DECODE=1): 1 query x paged KV, ``view``
    against ``kernel``. Env: BENCH_DECODE_HEADS (``nh,kvh,dh``),
    BENCH_DECODE_BLOCK, BENCH_DECODE_SLOTS, BENCH_DECODE_CTX,
    BENCH_DECODE_LAYERS, BENCH_DECODE_CHUNKS (kernel ``chunk_tokens``
    values), BENCH_DECODE_ITERS."""
    from _common import require_tpu, setup_compile_cache

    require_tpu("bench_attention")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.pallas.paged_attention import (CHUNK_TOKENS,
                                                          paged_flash_decode)

    ints = lambda name, default: [int(x) for x in os.environ.get(
        name, default).split(",")]
    h, kvh, dh = ints("BENCH_DECODE_HEADS", "32,32,64")
    bs = ints("BENCH_DECODE_BLOCK", "16")[0]
    n_layers = ints("BENCH_DECODE_LAYERS", "24")[0]
    n_iter = ints("BENCH_DECODE_ITERS", "8")[0]
    chunks = ints("BENCH_DECODE_CHUNKS", str(CHUNK_TOKENS))
    width, cdt = kvh * dh, jnp.bfloat16

    def bench(fn, *args):
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(n_iter):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n_iter / n_layers

    print(f"# decode shapes: {h}/{kvh} heads x {dh}, block {bs}, {n_layers} "
          f"layers a program, device={jax.devices()[0].device_kind}")
    failed = 0
    for s_dim in ints("BENCH_DECODE_SLOTS", "32"):
        for ctx in ints("BENCH_DECODE_CTX", "2048"):
            nb_cols = ctx // bs
            # the serve cell's pool: 3/4 of slots x window, + garbage block
            n_blocks = s_dim * nb_cols * 3 // 8 + 1
            rng = np.random.RandomState(0)
            # distinct K and V pools: one aliased array would let XLA read
            # the bytes once and double the reported GB/s
            kc = jax.random.normal(jax.random.PRNGKey(1),
                                   (n_layers, n_blocks, bs, width), cdt)
            vc = jax.random.normal(jax.random.PRNGKey(2),
                                   (n_layers, n_blocks, bs, width), cdt)
            q = jnp.asarray(rng.randn(s_dim, h, dh), cdt)
            k_new = jnp.asarray(rng.randn(s_dim, kvh, dh), cdt)
            v_new = jnp.asarray(rng.randn(s_dim, kvh, dh), cdt)
            cursors = {
                "ragged": rng.randint(ctx // 32, ctx * 15 // 32, s_dim),
                "half": np.full((s_dim,), ctx * 3 // 8 - 1)}
            for live, pos in cursors.items():
                pos = pos.astype(np.int32)
                table = np.zeros((s_dim, nb_cols), np.int32)
                free = 1 + rng.permutation(n_blocks - 1)
                used = 0
                for i in range(s_dim):
                    n = pos[i] // bs + 1
                    table[i, :n] = free[used:used + n]
                    used += n
                tj, pj = jnp.asarray(table), jnp.asarray(pos)
                kv_bytes = 2 * int(pos.sum()) * width * 2

                def layers(attend):
                    return lambda q, kc, vc: jax.lax.fori_loop(
                        0, n_layers,
                        lambda i, acc: acc + attend(q, kc, vc, i)
                        .astype(jnp.float32),
                        jnp.zeros((s_dim, h, dh), jnp.float32))

                def view(q, kc, vc, layer):
                    g = kc[layer, tj].reshape(s_dim, ctx, kvh, dh)
                    gv = vc[layer, tj].reshape(s_dim, ctx, kvh, dh)
                    mask = (jnp.arange(ctx)[None, None, None, :]
                            < pj[:, None, None, None])
                    n_rep = h // kvh
                    return L.dot_product_attention(
                        q[:, None], L._repeat_kv(g, n_rep),
                        L._repeat_kv(gv, n_rep), mask=mask)[:, 0]

                def kernel(chunk):
                    return lambda q, kc, vc, layer: paged_flash_decode(
                        q, k_new, v_new, kc, vc, tj, pj, layer=layer,
                        chunk_tokens=chunk)

                impls = [("view", view)] + [
                    (f"kernel/{c}", kernel(c)) for c in chunks]
                for name, fn in impls:
                    tag = (f"slots={s_dim:4d} ctx={ctx:6d} {live:6s} "
                           f"live={int(pos.sum()):7d} {name:11s}")
                    try:
                        sec = bench(layers(fn), q, kc, vc)
                        print(f"{tag} {sec * 1e3:9.4f} ms/layer "
                              f"{kv_bytes / sec / 1e9:8.1f} GB/s live KV",
                              flush=True)
                    except Exception as e:  # a failed row is a result
                        failed += 1
                        print(f"{tag} FAILED: {type(e).__name__}: "
                              f"{str(e)[:90]}", flush=True)
    return 1 if failed else 0


def main():
    from _common import require_tpu, setup_compile_cache

    require_tpu("bench_attention")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.flash_attention import flash_attention

    b, h, d = 4, 16, 64
    fwd_only = os.environ.get("BENCH_FWD_ONLY") == "1"
    seqs = [int(s) for s in os.environ.get(
        "BENCH_SEQS", "1024,2048,4096,8192").split(",")]

    def xla_attn(q, k, v):
        return L.dot_product_attention(q, k, v,
                                       mask=L.causal_mask(q.shape[1], k.shape[1]))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True)

    def jaxflash(q, k, v):
        from deepspeed_tpu.ops.flash_attention import jax_flash_attention

        return jax_flash_attention(q, k, v, causal=True)

    _bs_cache = {}

    def block_sparse(q, k, v):
        # bslongformer-style local+global pattern — the long-seq value
        # argument (reference claims 6.3x training speedup and 10x longer
        # sequences, docs/_posts/2020-09-09-sparse-attention.md); density
        # falls with seq so the speedup should GROW with s
        from deepspeed_tpu.ops.sparse_attention import BSLongformerSparsityConfig
        from deepspeed_tpu.ops.pallas.block_sparse_attention import (
            BlockSparseAttention)

        s = q.shape[1]
        if s not in _bs_cache:
            sp = BSLongformerSparsityConfig(
                block=128, num_sliding_window_blocks=3,
                global_block_indices=(0,))
            _bs_cache[s] = BlockSparseAttention(sp, s, causal=True)
        return _bs_cache[s](q, k, v)

    def bench(fn, q, k, v, n=8):
        if fwd_only:
            f = jax.jit(lambda q, k, v: fn(q, k, v))
        else:
            f = jax.jit(jax.grad(
                lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)))
        jax.block_until_ready(f(q, k, v))  # compile + first run
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(q, k, v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n

    print(f"# b={b} h={h} d={d} dtype=bf16 mode={'fwd' if fwd_only else 'fwd+bwd'}")
    failed = 0
    for s in seqs:
        rng = np.random.RandomState(0)
        mk = lambda: jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        # causal: half the s^2 tile pairs; 2 matmuls fwd (qk^T, pv);
        # bwd adds ~3.5x fwd matmul work (dq, dk, dv + prob recompute)
        flops = 2 * (s * s / 2) * d * 2 * b * h
        if not fwd_only:
            flops *= 4.5
        impls = [("xla", xla_attn), ("flash", flash), ("jaxfl", jaxflash),
                 ("bsparse", block_sparse)]
        # BENCH_BLOCKS="128x256,256x512,512x512:256x512": sweep flash kernel
        # block sizes (block_q x block_kv, optional ":bq_bwd x bkv_bwd").
        # Default: a small tile sweep so the crossover table ships with
        # tuning data; 512x1024:512x1024 at seq 1024 engages the
        # single-block kernels (no-scratch fwd + single-pass dq).
        blocks = os.environ.get(
            "BENCH_BLOCKS",
            "512x512:256x512,512x1024:512x512,512x1024:512x1024")
        if blocks:
            from deepspeed_tpu.ops.flash_attention import parse_block_spec
            from deepspeed_tpu.ops.pallas.flash_attention import (
                pallas_flash_attention)

            for spec in blocks.split(","):
                bq, bkv, bqb, bkvb = parse_block_spec(spec)
                impls.append((
                    f"fl{spec}",
                    lambda q, k, v, bq=bq, bkv=bkv, bqb=bqb, bkvb=bkvb:
                    pallas_flash_attention(
                        q, k, v, causal=True, block_q=bq, block_kv=bkv,
                        block_q_bwd=bqb, block_kv_bwd=bkvb)))
        for name, fn in impls:
            try:
                dt = bench(fn, q, k, v)
                print(f"seq={s:6d} {name:6s} {dt * 1e3:9.2f} ms "
                      f"{flops / dt / 1e12:7.1f} TFLOP/s")
            except Exception as e:  # a failed row (e.g. OOM) is a result
                failed += 1
                print(f"seq={s:6d} {name:6s} FAILED: {type(e).__name__}: "
                      f"{str(e)[:100]}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(decode_main() if os.environ.get("BENCH_DECODE") == "1"
             else main())
