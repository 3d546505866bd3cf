#!/usr/bin/env python3
"""chip_smoke.py: does the system still start on the chip?

Drives the two main paths once, through the entry points a user calls, at the
full width of a model the repo supports, over every local device, in ONE
process (a chip belongs to one process at a time):

* server  - OPT-1.3B (24 x 2048, 32 heads x 64, vocab 50272, bf16; random
  weights from a seed) through ``deepspeed_tpu.init_inference()`` and
  ``ServingEngine`` over its paged KV pool, the decode attention the
  engine's choice: requests of several prompt lengths (128-aligned buckets
  run the flash prefill kernel) submitted while others decode, streamed to
  completion, greedy tokens compared with ``InferenceEngine.generate()``,
  exactly one decode compile. One device: TP=1. Several: TP=n, weights
  checked to be spread.
* trainer - GPT-2 medium (24 x 1024, 16 heads, ffn 4096, vocab 50304, seq
  1024, bf16, micro-batch 12 a chip) through ``deepspeed_tpu.initialize()``
  and ``engine.train_batch()`` with ``attention_impl="flash"``: a few steps
  on a fixed seeded batch, loss finite and falling, Mosaic kernels present in
  the compiled step. One device: ZeRO-0. Several: ZeRO-3 over ``data=n``,
  state checked to be spread and all-gathers present.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only if
the platform is ``tpu``, the device kind has published peaks, both legs ran
and every check held - including that each implied utilisation lies in
(0, 1): a timing fence that does not block shows up as more than peak.
Every figure printed is smoke output, not a benchmark number.

    python chip_smoke.py                 # on the chip machine
    python chip_smoke.py --rehearse-cpu  # control-flow rehearsal, tiny model,
                                         # labelled, never prints "ok"
"""

import argparse
import gc
import json
import logging
import os
import sys
import time
import traceback

import numpy as np

MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


class CompileCacheLog(logging.Handler):
    """Names of the programs JAX's persistent compilation cache served
    (hit) or had to compile (miss), read off ``jax._src.compiler``'s own
    log lines."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits, self.misses = [], []
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False   # keep the DEBUG chatter off stderr ...
        log.addHandler(self)

    def emit(self, record):
        msg = str(record.msg)
        if "ersistent compilation cache hit for" in msg:
            self.hits.append(record.args[0])
        elif "PERSISTENT COMPILATION CACHE MISS for" in msg:
            self.misses.append(record.args[0])
        elif record.levelno >= logging.WARNING:
            logging.lastResort.handle(record)   # ... but not the warnings

    def mark(self):
        return len(self.hits), len(self.misses)

    def since(self, mark, names):
        """{program: "hit"|"miss"} for the named programs seen after mark."""
        out = {}
        for kind, seen, start in (("hit", self.hits, mark[0]),
                                  ("miss", self.misses, mark[1])):
            for name in seen[start:]:
                if name in names:
                    out[name] = kind
        return out


def say(leg, **fields):
    print(json.dumps({"leg": leg, **fields}, default=str), flush=True)


def peak_memory():
    """Allocator high-water marks since process start, the largest over
    local devices. On this runtime ``peak_bytes_in_use`` counts live arrays
    only; a running program's temporaries show up under
    ``peak_bytes_reserved`` - the chip's true peak is about their sum."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {k: max((s.get(k, 0) for s in stats), default=0)
            for k in ("peak_bytes_in_use", "peak_bytes_reserved",
                      "bytes_limit")}


def device_share(tree):
    """(largest per-device share of the tree's bytes, total bytes)."""
    import jax

    per_dev, total = {}, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return max(per_dev.values()) / max(total, 1), total


def serve_leg(n_dev, peaks, rehearse, cache_log):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import get_model
    from deepspeed_tpu.serving import Request, RequestState

    shrink = dict(n_layers=2, d_model=128, n_heads=4, d_ff=256,
                  vocab_size=512) if rehearse else {}
    model = get_model("opt", "1.3b", **shrink)
    cfg = model.config
    max_tokens, n_slots, block, new = 512, 8, 16, 24
    mark = cache_log.mark()
    t0 = time.perf_counter()
    engine = deepspeed_tpu.init_inference(
        model, dtype="bfloat16", max_tokens=max_tokens, seed=0,
        tensor_parallel={"enabled": True, "tp_size": n_dev},
        serving={"n_slots": n_slots,
                 "kv_pool": {"block_size": block}})
    jax.block_until_ready(engine.params)
    sv = engine.serving
    init_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    sv.lower_decode().compile()   # the step's own first dispatch then finds
    decode_compile_s = time.perf_counter() - t0   # it in the cache
    share, weight_bytes = device_share(engine.params)
    say("server", model="opt-1.3b", layers=cfg.n_layers, d_model=cfg.d_model,
        heads=cfg.n_heads, head_dim=cfg.head_dim, vocab=cfg.vocab_size,
        tp=n_dev, attn_backend=sv.attn_backend,
        attn_reason=sv.attn_reason, pool_layout=sv.pool_layouts(),
        init_s=round(init_s, 2),
        decode_compile_s=round(decode_compile_s, 2),
        weight_gb=round(weight_bytes / 1e9, 3),
        max_device_share_of_weights=round(share, 3))
    # on the chip the engine chooses the decode kernel (the compiler's
    # verdict at this geometry); the CPU rehearsal has none to choose
    checks = {"attn_backend_is_engines_choice": sv.attn_backend
              == ("view" if rehearse else "kernel")}
    if n_dev > 1:
        # vocab/heads/mlp dims split n ways; norms and biases replicate
        checks["weights_sharded"] = share < 1.0 / n_dev + 0.1

    # prompt lengths -> buckets 64, 128, 128, 256 | 128, 256: the 128-aligned
    # buckets take the flash prefill kernel, 64 the XLA scan (logged)
    rng = np.random.RandomState(1)
    lens_a, lens_b = (40, 100, 128, 200), (72, 256)
    mk = lambda n: Request(
        prompt=rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32),
        max_new_tokens=new)
    wave_a, wave_b = [mk(n) for n in lens_a], [mk(n) for n in lens_b]
    streams = {}

    def pump(until, at_most=None):
        """step() until ``until()`` (or ``at_most`` steps); returns the
        steps taken."""
        steps = 0
        while not until() and steps != at_most:
            for ev in sv.step():
                if ev.token >= 0:
                    streams.setdefault(ev.request_id, []).append(ev.token)
            steps += 1
            if steps > 4000:
                raise RuntimeError("serving loop did not converge")
        return steps

    t_serve = time.perf_counter()
    for r in wave_a:
        sv.submit(r)
    pump(lambda: all(len(r.tokens) >= 4 for r in wave_a))
    for r in wave_b:          # arrive while wave A is mid-decode
        sv.submit(r)
    pump(lambda: all(r.tokens for r in wave_b))
    # steady window: every request admitted and prefilled, decode steps
    # only; step() ends with the host reading the step's tokens - a true
    # fence for the program that produced them
    running = lambda: sum(r.state is RequestState.RUNNING
                          for r in wave_a + wave_b)
    active, t0 = running(), time.perf_counter()
    steps = pump(lambda: running() != active, at_most=8)
    jax.block_until_ready(engine.params)
    step_s = (time.perf_counter() - t0) / max(steps, 1)
    pump(lambda: all(r.state is RequestState.FINISHED
                     for r in wave_a + wave_b))
    serve_s = time.perf_counter() - t_serve

    reqs = wave_a + wave_b
    checks["all_finished"] = all(r.state is RequestState.FINISHED
                                 for r in reqs)
    checks["streamed_equals_stored"] = all(
        streams.get(r.request_id) == list(r.tokens) for r in reqs)
    # greedy streams vs generate(): equal token for token - or, where they
    # part, parted on a tie. The two paths run different programs (8 paged
    # slots vs one dense row), so bf16 rounding can differ in the last bit;
    # with random weights the top two of 50k logits are sometimes closer
    # than that. A divergence passes only if a third implementation (the
    # no-cache scoring forward) puts both candidates within two bf16 steps
    # of the maximum at that position.
    exact, ties, wrong = 0, [], []
    for r in reqs:
        ref = np.asarray(engine.generate(
            r.prompt[None, :], max_new_tokens=new, greedy=True))
        ref, got = list(ref[0, r.prompt_len:]), list(r.tokens)
        if ref == got:
            exact += 1
            continue
        j = next(i for i, (a, b) in enumerate(zip(ref, got)) if a != b)
        ctx = np.concatenate([r.prompt, np.asarray(got[:j], np.int32)])
        logits = np.asarray(engine.forward(ctx[None, :])[0, -1], np.float32)
        top = float(logits.max())
        tol = 2.0 * 2.0 ** (np.floor(np.log2(abs(top))) - 7)
        gaps = (top - float(logits[got[j]]), top - float(logits[ref[j]]))
        rec = dict(prompt_len=r.prompt_len, at=j, served=int(got[j]),
                   generate=int(ref[j]), gaps_to_max=[round(g, 4)
                                                      for g in gaps],
                   tol=round(tol, 4))
        (ties if max(gaps) <= tol else wrong).append(rec)
    checks["greedy_matches_generate"] = not wrong
    counts = sv.compile_counts()
    checks["one_decode_compile"] = counts["decode"] == 1
    # the flash prefill really is a Mosaic kernel at a 128-aligned bucket
    n_mosaic = sv.lower_prefill(128).as_text().count("tpu_custom_call")
    if not rehearse:
        checks["flash_prefill_is_mosaic"] = n_mosaic > 0

    # decode is bandwidth-bound: every step streams this device's weights
    # and the gathered KV view of every slot
    kv_bytes = 2 * cfg.n_layers * n_slots * max_tokens \
        * cfg.kv_heads * cfg.head_dim * 2 / n_dev
    dev_bytes = weight_bytes * share + kv_bytes
    implied = dev_bytes / step_s / 1e9
    snap = sv.metrics.snapshot()
    out = dict(
        requests=len(reqs), prompt_lens=lens_a + lens_b, new_tokens=new,
        compile_counts=counts, prefill_mosaic_calls=n_mosaic,
        streams_equal_generate=f"{exact}/{len(reqs)}",
        tie_divergences=ties, wrong_divergences=wrong,
        serve_wall_s=round(serve_s, 2), steady_decode_step_ms=round(
            step_s * 1e3, 3), steady_active_slots=active,
        decode_tokens_per_s=round(active / step_s, 1),
        ttft_ms_p50_incl_compile=snap["ttft_ms"]["p50"],
        tpot_ms_p50=snap["tpot_ms"]["p50"],
        implied_hbm_gbs_per_chip=round(implied, 1),
        **peak_memory(),
        cache=cache_log.since(mark, {"jit_decode", "jit_prefill"}))
    if peaks is not None:
        out["hbm_share_of_peak"] = round(implied / peaks.hbm_gbs, 4)
        checks["utilisation_in_0_1"] = 0.0 < implied / peaks.hbm_gbs < 1.0
    say("server", **out, checks=checks)
    engine.destroy()
    return checks


def train_leg(n_dev, peaks, rehearse, cache_log):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models import get_model

    shape = dict(vocab_size=50304)   # padded to a multiple of 128
    if rehearse:
        shape = dict(n_layers=2, d_model=128, n_heads=4, d_ff=256,
                     vocab_size=512, max_seq_len=128)
    model = get_model("gpt2", "medium", attention_impl="flash", remat=True,
                      remat_policy="minimal", scan_layers=True,
                      fused_ce=True, **shape)
    cfg = model.config
    micro, seq = (2, 128) if rehearse else (12, 1024)
    stage = 3 if n_dev > 1 else 0
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": micro * n_dev,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 5e-5, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": stage},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    })
    say("trainer", model="gpt2-medium", layers=cfg.n_layers,
        d_model=cfg.d_model, heads=cfg.n_heads, vocab=cfg.vocab_size,
        seq=seq, micro_batch_per_chip=micro, zero_stage=stage,
        mesh=dict(engine.mesh.shape),
        params_m=round(engine.num_parameters / 1e6, 1))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, cfg.vocab_size, (micro * n_dev, seq)).astype(np.int32)}

    mark = cache_log.mark()
    t0 = time.perf_counter()
    compiled = engine.lower_train_step(batch).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    mem = compiled.memory_analysis()
    n_mosaic, n_gather = hlo.count(MOSAIC_CALL), hlo.count("all-gather")
    checks = {}
    if not rehearse:
        # a flash path that fell to the XLA scan shows as zero
        checks["mosaic_calls_in_step"] = n_mosaic > 0
    state = (engine.params, engine.optimizer_state)
    share, state_bytes = device_share(state)
    if n_dev > 1:
        # ZeRO-3: parameters and optimizer state spread over the data axis
        # (leaves under the persistence threshold stay whole)
        checks["state_sharded"] = share < 1.0 / n_dev + 0.05
        checks["all_gathers_in_step"] = n_gather > 0

    t0 = time.perf_counter()
    losses = [engine.train_batch(batch=batch)]
    jax.block_until_ready((losses, engine.params))
    first_s = time.perf_counter() - t0
    losses.append(engine.train_batch(batch=batch))
    jax.block_until_ready((losses, engine.params))
    n_steps = 5
    t0 = time.perf_counter()
    for _ in range(n_steps):
        losses.append(engine.train_batch(batch=batch))
    jax.block_until_ready((losses, engine.params))
    step_s = (time.perf_counter() - t0) / n_steps
    losses = [float(x) for x in losses]

    tok_s_chip = micro * seq / step_s
    flops_per_token = 6.0 * engine.num_parameters
    checks["loss_finite"] = bool(np.all(np.isfinite(losses)))
    # no warm-up schedule, so single steps are noisy: compare window means
    checks["loss_falling"] = bool(
        np.mean(losses[-3:]) < np.mean(losses[:2]))
    out = dict(
        compile_s=round(compile_s, 2), first_step_s=round(first_s, 2),
        steady_step_ms=round(step_s * 1e3, 2), steps_timed=n_steps,
        tokens_per_s_per_chip=round(tok_s_chip, 1),
        losses=[round(x, 4) for x in losses],
        mosaic_calls=n_mosaic, all_gathers=n_gather,
        compiled_args_gb=round(mem.argument_size_in_bytes / 1e9, 3),
        compiled_temp_gb=round(mem.temp_size_in_bytes / 1e9, 3),
        state_gb=round(state_bytes / 1e9, 3),
        max_device_share_of_state=round(share, 3),
        **peak_memory(),
        cache=cache_log.since(mark, {"jit_train_step"}))
    if peaks is not None:
        mfu = tok_s_chip * flops_per_token / 1e12 / peaks.bf16_tflops
        out["implied_mfu_6N"] = round(mfu, 4)
        checks["utilisation_in_0_1"] = 0.0 < mfu < 1.0
    say("trainer", **out, checks=checks)
    engine.destroy()
    return checks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse the control flow on the CPU with a tiny "
                         "model; output is labelled and never says ok")
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed"
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    rehearse = args.rehearse_cpu
    if dev.platform != "tpu" and not rehearse:
        print(f"chip_smoke: platform is {dev.platform!r} ({device}), not "
              "'tpu' - refusing to run (--rehearse-cpu rehearses the "
              "control flow)", file=sys.stderr)
        return 2

    # nothing reaches stdout before the program itself is known to be here
    from deepspeed_tpu.accelerator.peaks import device_peaks
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache

    say("device", **device, jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu, python=sys.version.split()[0])
    if rehearse:
        print("# REHEARSAL on", dev.platform, "- tiny model, control flow "
              "only; nothing below is a chip result", flush=True)
    peaks = None if rehearse else device_peaks(dev.device_kind)
    cache_dir = setup_compile_cache()
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # the serving programs compile in under JAX's default 1 s threshold
        # and would never be written to the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_log = CompileCacheLog()
    say("setup", compile_cache_dir=cache_dir,
        peaks=None if peaks is None else peaks._asdict())

    failed = []
    for name, leg in (("server", serve_leg), ("trainer", train_leg)):
        try:
            checks = leg(n_dev, peaks, rehearse, cache_log)
            failed += [f"{name}.{k}" for k, v in checks.items() if not v]
        except Exception:
            traceback.print_exc()
            failed.append(f"{name}.raised")
        gc.collect()
    say("cache", hits=len(cache_log.hits), misses=len(cache_log.misses))
    if failed:
        print("chip_smoke: FAILED " + ", ".join(failed), file=sys.stderr)
        return 1
    if rehearse:
        print(json.dumps({"rehearsal": True, "passed": True,
                          "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
