"""Flash-attention kernel benchmark: Pallas vs XLA across sequence lengths.

Answers "does the Pallas kernel actually win, and where?". Runs on the chip
only (one process; refuses any other platform):

    python tools/bench_attention.py            # fwd+bwd train-shape sweep
    BENCH_FWD_ONLY=1 python tools/bench_attention.py
    BENCH_DECODE=1 python tools/bench_attention.py   # serving decode shapes

Prints one line per (seq, impl): ms/iter and achieved TFLOP/s; causal
attention flops = 2 * 0.5 * s^2 * d * 3 matmuls fwd (+~2.5x bwd).

``BENCH_DECODE=1`` switches to the serving decode shape — 1 query row per
slot against a long paged KV window — and A/Bs the three decode-attention
paths (dense slot-pool read, paged-gather dense view, fused split-KV
kernel) in bf16 AND int8 across slot counts x context lengths
(``BENCH_DECODE_SLOTS``/``BENCH_DECODE_CTX``/``BENCH_DECODE_BLOCK``).
Decode is bandwidth-bound, so the printed GB/s (ideal KV bytes touched /
measured time) is the number that matters: the gather path pays the dense
view's write+read on top, the fused kernel streams the pool once.
"""

import os
import sys
import time

import numpy as np


def decode_main():
    """Decode-shape sweep (BENCH_DECODE=1): 1 query x long paged KV.

    Impls per (slots, ctx, dtype):
    - ``dense``  — the dense slot-pool read ([S, max_len] cache +
      masked attention), the pre-paging baseline;
    - ``gather`` — the paged gather path (``_paged_view``: dense per-slot
      view through the block table, then the same attention);
    - ``fused``  — the split-KV flash-decode kernel walking the table
      in-kernel (``ops/pallas/paged_attention.py``).
    """
    from _common import require_tpu, setup_compile_cache

    require_tpu("bench_attention")
    setup_compile_cache()
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from deepspeed_tpu.models import layers as L
    from deepspeed_tpu.ops.pallas.paged_attention import paged_flash_decode

    h, kvh, dh = 16, 16, 128
    bs = int(os.environ.get("BENCH_DECODE_BLOCK", 32))
    slot_counts = [int(s) for s in os.environ.get(
        "BENCH_DECODE_SLOTS", "4,16").split(",")]
    ctxs = [int(s) for s in os.environ.get(
        "BENCH_DECODE_CTX", "1024,4096").split(",")]
    dtypes = os.environ.get("BENCH_DECODE_DTYPES", "bf16,int8").split(",")
    n_iter = int(os.environ.get("BENCH_DECODE_ITERS", 16))

    def bench(fn, *args):
        f = jax.jit(fn)
        jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(n_iter):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n_iter

    print(f"# decode shapes: h={h} dh={dh} block={bs} "
          f"device={jax.devices()[0].device_kind}")
    failed = 0
    for s_dim in slot_counts:
        for ctx in ctxs:
            nb_cols = ctx // bs
            n_blocks = s_dim * nb_cols + 1
            rng = np.random.RandomState(0)
            table = np.arange(1, n_blocks).reshape(s_dim, nb_cols) \
                .astype(np.int32)
            pos = np.full((s_dim,), ctx - 1, np.int32)
            for dt in dtypes:
                cdt = jnp.bfloat16
                q = jnp.asarray(rng.randn(s_dim, h, dh), cdt)
                k_new = jnp.asarray(rng.randn(s_dim, kvh, dh), cdt)
                v_new = jnp.asarray(rng.randn(s_dim, kvh, dh), cdt)
                if dt == "int8":
                    kc = jnp.asarray(rng.randint(
                        -127, 127, (n_blocks, bs, kvh, dh)), jnp.int8)
                    vc = jnp.asarray(rng.randint(
                        -127, 127, (n_blocks, bs, kvh, dh)), jnp.int8)
                    ks = jnp.asarray(
                        np.abs(rng.randn(n_blocks, bs, kvh, 1)) * .01,
                        jnp.float32)
                    # distinct tensors: aliased k/v scales would let XLA
                    # cache the duplicate reads and inflate reported GB/s
                    vs = jnp.asarray(
                        np.abs(rng.randn(n_blocks, bs, kvh, 1)) * .01,
                        jnp.float32)
                    kv_bytes = 2 * n_blocks * bs * kvh * (dh + 4)
                else:
                    kc = jnp.asarray(rng.randn(n_blocks, bs, kvh, dh), cdt)
                    vc = jnp.asarray(rng.randn(n_blocks, bs, kvh, dh), cdt)
                    ks = vs = None
                    kv_bytes = 2 * n_blocks * bs * kvh * dh * 2
                tj, pj = jnp.asarray(table), jnp.asarray(pos)

                def gather(q, kc, vc, tj):
                    g, gv = kc[tj], vc[tj]
                    if ks is not None:
                        g = (g.astype(jnp.float32) * ks[tj]).astype(cdt)
                        gv = (gv.astype(jnp.float32) * vs[tj]).astype(cdt)
                    g = g.reshape(s_dim, ctx, kvh, dh)
                    gv = gv.reshape(s_dim, ctx, kvh, dh)
                    mask = (jnp.arange(ctx)[None, None, None, :]
                            <= pj[:, None, None, None])
                    return L.dot_product_attention(
                        q[:, None], g, gv, mask=mask)

                def fused(q, kc, vc, tj):
                    return paged_flash_decode(q, k_new, v_new, kc, vc, tj,
                                              pj, k_scale=ks, v_scale=vs)

                impls = [("gather", gather), ("fused", fused)]
                if dt != "int8":
                    # distinct K and V caches: one aliased array would let
                    # XLA read the bytes once and double the reported GB/s
                    dense_k = jnp.asarray(
                        rng.randn(s_dim, ctx, kvh, dh), cdt)
                    dense_v = jnp.asarray(
                        rng.randn(s_dim, ctx, kvh, dh), cdt)

                    def dense(q, kc, vc, tj):
                        mask = (jnp.arange(ctx)[None, None, None, :]
                                <= pj[:, None, None, None])
                        return L.dot_product_attention(
                            q[:, None], dense_k, dense_v, mask=mask)

                    impls.insert(0, ("dense", dense))
                for name, fn in impls:
                    try:
                        sec = bench(fn, q, kc, vc, tj)
                        print(f"slots={s_dim:4d} ctx={ctx:6d} {dt:5s} "
                              f"{name:6s} {sec * 1e3:9.3f} ms "
                              f"{kv_bytes / sec / 1e9:8.1f} GB/s")
                    except Exception as e:  # a failed row is a result
                        failed += 1
                        print(f"slots={s_dim:4d} ctx={ctx:6d} {dt:5s} "
                              f"{name:6s} FAILED: {type(e).__name__}: "
                              f"{str(e)[:90]}")
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
