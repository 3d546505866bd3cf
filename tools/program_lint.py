"""Static lint for the compiled hot programs: catch the defect classes that
don't show up as wire bytes.

Runs the program sanitizer (``deepspeed_tpu/profiling/sanitizer.py``) over
the post-SPMD HLO + jaxpr of the framework's hot programs — the ZeRO-3
train step (gather islands included) and the serving decode step — and
reports structured findings: f32 dtype leaks, missing buffer donation,
host transfers inside the step, accidentally-replicated tensors,
recompile hazards, and a liveness-walk peak-HBM estimate.

    # the tier-1-shaped gates (also run in tests/unit/test_sanitizer.py):
    python tools/program_lint.py --program train --preset tiny-test \
        --devices 8 --budget tiny-test/8/bf16 --fail-on error
    python tools/program_lint.py --program decode \
        --budget serving-decode-paged/8/bf16 --fail-on warning
    python tools/program_lint.py --program decode-fused \
        --budget serving-decode-fused/8/bf16 --fail-on warning

    # regression check at headline scale (abstract 256-chip mesh):
    python tools/program_lint.py --program train --preset opt-13b \
        --devices 256 --gather-dtype bf16 --budget opt-13b/256/bf16

    # the self-test pair --fail-on is graded against:
    python tools/program_lint.py --program planted --fail-on error   # exit 3
    python tools/program_lint.py --program clean --fail-on warning   # exit 0

Exit codes: 0 clean, 2 budget violation, 3 findings at/above ``--fail-on``,
1 infrastructure failure. ``--out`` writes the provenance-stamped JSON
report (the artifact-regeneration path runs this next to
``collective_audit.py`` so committed audits carry a budget-checked
sanitizer section).
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sanitizer_config(compute_dtype="bf16"):
    from deepspeed_tpu.profiling.sanitizer import ATTENTION_F32_ALLOW

    return {"compute_dtype": compute_dtype,
            "allow": list(ATTENTION_F32_ALLOW)}


def lint_train(args):
    """The fused ZeRO-3 train step (sanitizer section included by
    ``collective_audit.build_and_audit``)."""
    from collective_audit import build_and_audit

    return build_and_audit(args.preset, args.devices, args.micro,
                           args.gather_dtype, args.grad_reduce_dtype,
                           gather_impl=args.gather_impl)


def lint_decode(args, kernel=False):
    """The serving decode program over a live slot pool. Builds a REAL
    engine (params materialize), so this path is for test-sized presets —
    the decode program's geometry (slot pool, KV layout, donation pattern)
    is preset-independent. ``kernel``: the flash-decode kernel's program
    instead of the gather view's."""
    import jax.numpy as jnp

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from scale_projection import PRESETS

    import deepspeed_tpu

    preset = dict(PRESETS[args.preset])
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    max_len = args.serving_max_len or preset["seq"]
    model = CausalLM(TransformerConfig(
        vocab_size=preset["vocab_size"], max_seq_len=max_len,
        n_layers=preset["n_layers"], n_heads=preset["n_heads"],
        d_model=preset["d_model"], d_ff=preset["d_ff"],
        compute_dtype=jnp.bfloat16,
        # this lint always runs on the CPU platform, where the engine
        # chooses the decode kernel only under the interpreter: the kernel's
        # program is audited as the interpreter lowers it, the view's as is
        attention_interpret=kernel))
    serving = {"n_slots": args.slots, "max_len": max_len,
               "virtual_clock": True,
               "kv_pool": {"block_size": args.kv_block_size,
                           "kv_dtype": args.kv_dtype}}
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": max_len,
                "serving": serving})
    report = engine.decode_program_report()
    report.update({"preset": args.preset, "devices": args.devices,
                   "n_slots": args.slots, "serving_max_len": max_len,
                   "attention_backend": engine.serving.attn_backend,
                   "n_params": engine.module.num_parameters
                   if hasattr(engine.module, "num_parameters") else None})
    engine.destroy()
    return report


def lint_prefill_chunked(args):
    """The chunked suffix-prefill program (serving/engine.py suffix
    programs): one full chunk's bucket written at a traced start position
    against a donated partial b=1 cache — the program every chunk (and every
    shared-prefix suffix hit) dispatches. Gate with
    ``--budget serving-prefill-chunked/8/bf16``."""
    import jax.numpy as jnp

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from scale_projection import PRESETS

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    preset = dict(PRESETS[args.preset])
    max_len = args.serving_max_len or preset["seq"]
    model = CausalLM(TransformerConfig(
        vocab_size=preset["vocab_size"], max_seq_len=max_len,
        n_layers=preset["n_layers"], n_heads=preset["n_heads"],
        d_model=preset["d_model"], d_ff=preset["d_ff"],
        compute_dtype=jnp.bfloat16))
    serving = {"n_slots": args.slots, "max_len": max_len,
               "virtual_clock": True,
               "chunked_prefill": {"enabled": True,
                                   "chunk_size": args.chunk_size}}
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": max_len,
                "serving": serving})
    report = engine.prefill_chunk_report(args.chunk_size)
    report.update({"preset": args.preset, "devices": args.devices,
                   "n_slots": args.slots, "serving_max_len": max_len,
                   "chunk_size": args.chunk_size,
                   "n_params": engine.module.num_parameters
                   if hasattr(engine.module, "num_parameters") else None})
    engine.destroy()
    return report


def lint_verify(args):
    """The speculative-decoding verify program (serving/engine.py): one
    target forward over ``--spec-k`` + 1 positions per slot against the
    donated paged pool state, drafts and per-slot draft lengths traced —
    the program every verify step dispatches. Gate with
    ``--budget serving-verify/8/bf16``."""
    import jax.numpy as jnp

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from scale_projection import PRESETS

    import deepspeed_tpu
    from deepspeed_tpu.models import CausalLM, TransformerConfig

    preset = dict(PRESETS[args.preset])
    max_len = args.serving_max_len or preset["seq"]
    model = CausalLM(TransformerConfig(
        vocab_size=preset["vocab_size"], max_seq_len=max_len,
        n_layers=preset["n_layers"], n_heads=preset["n_heads"],
        d_model=preset["d_model"], d_ff=preset["d_ff"],
        compute_dtype=jnp.bfloat16))
    serving = {"n_slots": args.slots, "max_len": max_len,
               "virtual_clock": True,
               "kv_pool": {"block_size": args.kv_block_size,
                           "kv_dtype": args.kv_dtype},
               "speculative": {"enabled": True, "k": args.spec_k}}
    engine = deepspeed_tpu.init_inference(
        model=model,
        config={"dtype": "bfloat16", "max_tokens": max_len,
                "serving": serving})
    report = engine.verify_program_report(args.spec_k)
    report.update({"preset": args.preset, "devices": args.devices,
                   "n_slots": args.slots, "serving_max_len": max_len,
                   "spec_k": args.spec_k,
                   "kv_block_size": args.kv_block_size,
                   "n_params": engine.module.num_parameters
                   if hasattr(engine.module, "num_parameters") else None})
    engine.destroy()
    return report


def _planted_program(clean=False):
    """A small program with one planted defect per sanitizer rule (or its
    clean twin): f32 dot leak, missing donation, host transfer, replicated
    large tensor, entry-scope gather, baked constant. The self-test target
    for ``--fail-on`` grading and the fixture the unit tests pin."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.profiling.collectives import audit_lowered
    from deepspeed_tpu.profiling.sanitizer import (merge_reports,
                                                   sanitize_jaxpr)

    n = min(8, len(jax.devices()))
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    shard = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    baked = np.ones((512, 512), np.float32)  # 1 MiB baked const (defect)

    def defective(w, big_rep, x, scale):
        y = x.astype(jnp.float32) @ w.astype(jnp.float32)     # f32 dot leak
        jax.debug.print("loss {l}", l=y.sum())                # host transfer
        y = y + big_rep[: y.shape[0], : y.shape[1]]           # replicated use
        g = jax.lax.with_sharding_constraint(                 # entry gather
            x, NamedSharding(mesh, P(None, None)))
        c = jnp.asarray(baked)                                # baked const
        return (w + 1).astype(w.dtype), (y.sum() + g.sum()
                                         + c.sum()).astype(jnp.float32)

    def clean_fn(w, x):
        y = x @ w                                             # bf16 dot
        return w + 1, y.sum().astype(jnp.float32)

    w = jnp.zeros((512, 512), jnp.bfloat16)                   # 512 KiB
    x = jnp.zeros((256, 512), jnp.bfloat16)
    big_rep = jnp.zeros((512, 512), jnp.float32)              # 1 MiB
    with mesh:
        if clean:
            fn = jax.jit(clean_fn, donate_argnums=(0,),
                         in_shardings=(shard, shard),
                         out_shardings=(shard, rep))
            example = (w, x)
        else:
            # w NOT donated but (w + 1) output matches -> donation finding;
            # scale rides as a Python float -> recompile hazard
            fn = jax.jit(defective,
                         in_shardings=(shard, rep, shard, None),
                         out_shardings=(shard, rep))
            example = (w, big_rep, x, 1.0)
        # one trace serves both views (HLO audit + jaxpr sanitizer)
        traced = fn.trace(*example)
        lowered, jaxpr = traced.lower(), traced.jaxpr
    cfg = _sanitizer_config("bf16")
    report = audit_lowered(lowered, n, sanitizer_config=cfg)
    report["sanitizer"] = merge_reports(
        report["sanitizer"],
        sanitize_jaxpr(jaxpr, example_args=example, config=cfg))
    report.update({"preset": "planted-clean" if clean else "planted",
                   "devices": n})
    return report


def print_findings(name, report, top=15):
    san = report.get("sanitizer")
    if san is None:
        print(f"## {name}: no sanitizer section")
        return
    s = san["summary"]
    print(f"\n## program lint: {name} — {s['counts']['error']} errors, "
          f"{s['counts']['warning']} warnings, {s['counts']['info']} info")
    print(f"- f32 dot flops: {s.get('f32_dot_flops_frac', 0.0):.1%} of "
          f"{s.get('total_dot_flops', 0.0):.3g} total | f32 collective wire "
          f"{s.get('f32_collective_wire_bytes', 0.0) / 1e6:.2f} MB")
    print(f"- donation: {s.get('n_aliased_params', 0)} aliased inputs, "
          f"{s.get('undonated_candidates', 0)} candidates "
          f"({s.get('undonated_candidate_bytes', 0.0) / 1e6:.3f} MB above "
          f"threshold)")
    print(f"- host transfers: {s.get('transfer_count', 0)} | replicated "
          f"{s.get('replicated_bytes', 0.0) / 1e6:.1f} MB | entry gathers "
          f"{s.get('entry_gather_bytes', 0.0) / 1e6:.1f} MB")
    if "baked_const_bytes" in s:
        print(f"- jaxpr: {s['baked_const_bytes'] / 1e6:.1f} MB baked consts, "
              f"{s.get('python_scalar_args', 0)} Python scalar args")
    p = san["peak_hbm"]
    print(f"- est peak HBM {p['estimate_bytes'] / 1e9:.4f} GB/chip "
          f"(args {p['argument_bytes'] / 1e9:.4f} + transients "
          f"{p['transient_peak_bytes'] / 1e9:.4f}, peak at "
          f"{p['peak_instruction']})")
    shown = [f for f in san["findings"] if not f.get("allowed")][:top]
    for f in shown:
        loc = f.get("op_name") or f.get("instruction") or ""
        print(f"  [{f['severity']:>7}] {f['rule']}: {f['message']}"
              + (f"  ({loc})" if loc else ""))
    hidden = s["n_findings"] - len(shown)
    if hidden > 0:
        print(f"  ... {hidden} more findings (see --out JSON)")


def child(args):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from _common import setup_compile_cache, stamp_record

    setup_compile_cache()
    t0 = time.time()
    programs = {}
    if args.program in ("train", "all"):
        programs["train"] = lint_train(args)
    if args.program in ("decode", "all"):
        programs["decode"] = lint_decode(args)
    if args.program == "decode-fused":
        # the decode program through the flash-decode kernel
        programs["decode-fused"] = lint_decode(args, kernel=True)
    if args.program in ("prefill-chunked", "all"):
        programs["prefill-chunked"] = lint_prefill_chunked(args)
    if args.program in ("verify", "all"):
        programs["verify"] = lint_verify(args)
    if args.program == "planted":
        programs["planted"] = _planted_program(clean=False)
    if args.program == "clean":
        programs["clean"] = _planted_program(clean=True)
    out = {"programs": programs,
           "lint_seconds": round(time.time() - t0, 1)}
    stamp_record(out, config=vars(args))
    print(json.dumps(out, default=str))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--program", default="all",
                    choices=["train", "decode", "decode-fused",
                             "prefill-chunked", "verify", "all", "planted",
                             "clean"],
                    help="'decode' lints the serving decode program over "
                         "the gather view (--budget serving-decode-paged/8/"
                         "bf16), 'decode-fused' the same program through "
                         "the flash-decode kernel as the interpreter lowers "
                         "it (--budget serving-decode-fused/8/bf16). On a "
                         "chip the engine chooses between the two")
    ap.add_argument("--preset", default="tiny-test")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--gather-dtype", default="bf16",
                    choices=["auto", "fp32", "bf16", "int8"])
    ap.add_argument("--gather-impl", default="shard_map",
                    choices=["constraint", "shard_map"])
    ap.add_argument("--grad-reduce-dtype", default="bf16",
                    choices=["fp32", "bf16"])
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--serving-max-len", type=int, default=None)
    ap.add_argument("--kv-block-size", type=int, default=16)
    ap.add_argument("--kv-dtype", default="", choices=["", "int8"])
    ap.add_argument("--chunk-size", type=int, default=16,
                    help="chunked-prefill chunk (tokens) the "
                         "prefill-chunked program is linted at")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per verify step the speculative "
                         "verify program is linted at (--program verify)")
    ap.add_argument("--budget", default=None,
                    help="key into tools/collective_budgets.json; applies "
                         "to every linted program, violations exit 2")
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warning", "info", "none"],
                    help="exit 3 when any program has findings at/above "
                         "this severity (allowlisted findings excluded)")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--out", default=None)
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--child", action="store_true")
    args = ap.parse_args()
    if args.child:
        return child(args)
    if REPO not in sys.path:
        sys.path.insert(0, REPO)

    # re-exec with the virtual device count (XLA reads the flag at backend
    # init; compile-only, so no collective-timeout flags — see
    # collective_audit.py)
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={args.devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child",
           "--program", args.program, "--preset", args.preset,
           "--devices", str(args.devices), "--micro", str(args.micro),
           "--gather-dtype", args.gather_dtype,
           "--gather-impl", args.gather_impl,
           "--grad-reduce-dtype", args.grad_reduce_dtype,
           "--slots", str(args.slots),
           "--kv-block-size", str(args.kv_block_size),
           "--chunk-size", str(args.chunk_size),
           "--spec-k", str(args.spec_k)]
    if args.kv_dtype:
        cmd += ["--kv-dtype", args.kv_dtype]
    if args.serving_max_len:
        cmd += ["--serving-max-len", str(args.serving_max_len)]
    proc = subprocess.run(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                          text=True, timeout=args.timeout)
    out = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "programs" in cand:
            out = cand
            break
    if proc.returncode != 0 or out is None:
        sys.stdout.write(proc.stdout)
        print(f"child failed rc={proc.returncode}", file=sys.stderr)
        return 1

    for name, report in out["programs"].items():
        print_findings(name, report, top=args.top)

    rc = 0
    if args.budget:
        sys.path.insert(0, REPO)
        from collective_audit import load_budget
        from deepspeed_tpu.profiling.collectives import check_budgets

        budget = load_budget(args.budget)
        for name, report in out["programs"].items():
            violations = check_budgets(report, budget,
                                       n_params=report.get("n_params"),
                                       n_devices=report.get("devices"))
            report["budget"] = args.budget
            report["budget_pass"] = not violations
            if violations:
                report["budget_violations"] = violations
                for msg in violations:
                    print(f"BUDGET VIOLATION [{name}]: {msg}",
                          file=sys.stderr)
                rc = 2
        if rc == 0:
            print(f"- budget {args.budget!r}: PASS "
                  f"({', '.join(out['programs'])})")
    if args.fail_on != "none":
        from deepspeed_tpu.profiling.sanitizer import count_at_or_above

        for name, report in out["programs"].items():
            san = report.get("sanitizer")
            if san is None:
                continue
            n = count_at_or_above(san["findings"], args.fail_on)
            if n:
                print(f"FAIL [{name}]: {n} findings at/above "
                      f"{args.fail_on!r}", file=sys.stderr)
                rc = rc or 3
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1, default=str)
        print(f"- wrote {args.out}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
