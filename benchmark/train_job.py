"""Runner of the ``train_job`` traffic kind: back-to-back ``train_batch``
calls on fresh batches drawn from the seed, through
``deepspeed_tpu.initialize()``.

Set-up: build the engine (weights made on the device from ``--seed``), take
the plain reference's loss on two seeded sequences and the initial
parameters, then run the engine's first step on those two sequences
repeated over the batch (the mean is the same), which also compiles the one
step program; one more warm step. Window: ``train_batch`` on the next batch
is dispatched before the previous step's loss is waited for, as a user's
loop would, and each step's end is taken at ``block_until_ready``.
"""

import time

import numpy as np

from . import harness, shapes, traffic_gen
from .reference import dense_decoder


def run(cell, config, traffic, manifest, args, devices, peaks, cache_log):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.config.config import MeshConfig
    from deepspeed_tpu.parallel import build_mesh

    chips, arch = len(devices), config["arch"]
    seq = config["train"]["seq_len"]
    n_seq = config["train"]["sequences_per_chip"] * chips
    tokens_per_step = n_seq * seq
    model = harness.build_model(config)
    mesh = build_mesh(MeshConfig(**config["mesh"]), devices=devices)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, mesh=mesh,
        config=dict(config["engine"], train_batch_size=n_seq, seed=args.seed))
    harness.note("engine", mesh=dict(engine.mesh.shape),
                 zero_stage=engine.zero_stage, seq=seq,
                 sequences_per_step=n_seq,
                 params=engine.num_parameters,
                 params_from_shapes=shapes.param_count(arch),
                 setup_so_far_s=harness.process_age_s())

    # correctness, outside the window: first-step loss against the reference
    batches = traffic_gen.train_batches(traffic, args.seed, n_seq, seq,
                                        arch["vocab_size"])
    probe = next(batches)["input_ids"][:2]
    ref_loss = dense_decoder.loss(engine.params, probe, arch)
    first = {"input_ids": np.tile(probe, (n_seq // 2, 1))}
    engine_loss = float(engine.train_batch(batch=first))
    tol = config["checks"]["first_loss_tolerance"]
    checks = {"first_step_loss_matches_reference":
              abs(engine_loss - ref_loss) <= tol}
    harness.note("reference", engine_first_loss=engine_loss,
                 reference_loss=ref_loss, difference=engine_loss - ref_loss,
                 tolerance=tol, setup_so_far_s=harness.process_age_s())
    batch = next(batches)
    jax.block_until_ready(engine.train_batch(batch=batch))   # warm

    traced = harness.TracedSlice(args.trace, args.seconds,
                                 traffic["trace_slice_s"], args.trace_dir)
    skipped0, mark = engine.skipped_steps, cache_log.mark()
    losses, ends, pending = [], [], None
    batch = next(batches)
    setup_s = harness.process_age_s()
    t0 = time.perf_counter()
    while True:
        with harness.span("train_batch"):
            loss = engine.train_batch(batch=batch)
        with harness.span("make_batch"):
            batch = next(batches)
        if pending is not None:
            with harness.span("wait_step"):
                jax.block_until_ready(pending)
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= args.seconds:
                with harness.span("wait_step"):
                    jax.block_until_ready(loss)
                ends.append(time.perf_counter())
                losses += [pending, loss]
                break
            losses.append(pending)
            traced.maybe_start(ends[-1] - t0)
        pending = loss
    traced.stop()
    window_s = ends[-1] - t0
    losses = [float(x) for x in losses]
    compiled = cache_log.since(mark)
    skipped = engine.skipped_steps - skipped0
    not_finite = int(np.sum(~np.isfinite(losses)))
    checks.update(
        every_loss_finite=not_finite == 0,
        loss_falls=float(np.median(losses[-5:])) < losses[0],
        no_compile_in_window=not compiled,
        steps_finished=len(losses) >= 5)
    step_ms = list(np.diff([t0] + ends) * 1e3)
    harness.note("window", steps=len(losses), window_s=window_s,
                 step_ms_median=harness.quantile(step_ms, 50),
                 step_ms_p95=harness.quantile(step_ms, 95),
                 step_ms_max=max(step_ms),
                 first_loss=losses[0], last_losses=losses[-5:],
                 skipped_steps=skipped, compiled_in_window=compiled,
                 cache_hits=len(cache_log.hits),
                 cache_misses=len(cache_log.misses))
    harness.note("checks", **checks)
    end_to_end = {
        "train_tokens_per_s_per_chip":
            len(losses) * tokens_per_step / window_s / chips,
        "setup_s": setup_s}
    obs = {"samples": {"train_step_ms": step_ms},
           "counters": {}, "trace": traced.reduced, "arch": arch,
           "work": {"tokens_per_step": tokens_per_step, "seq_len": seq,
                    "chips": chips},
           "peaks": peaks}
    return harness.result_line(
        manifest, cell, args, correct=all(checks.values()),
        attempted=len(losses), failed=skipped + not_finite,
        end_to_end=end_to_end, obs=obs, devices=devices, traced=traced)
