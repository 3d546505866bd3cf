"""HLO collective-bytes audit CLI: prove the ZeRO-3 wire dtype, don't claim it.

Builds a REAL engine under ``runtime.engine.abstract_init`` on an
N-virtual-device CPU mesh (the ``tools/scale_projection.py`` technique —
nothing materializes), lowers the fused ZeRO-3 ``per_layer`` train step, and
attributes per-chip-per-step wire bytes to every collective, split by payload
dtype. Core parsing/accounting lives in
``deepspeed_tpu/profiling/collectives.py`` (shared with the FlopsProfiler
and the engine's monitor hook); see its docstring for why the audit reads
the post-SPMD-partitioning HLO snapshot rather than the backend-optimized
text (CPU float-normalization would disguise bf16 gathers as f32).

Thresholds live in ``tools/collective_budgets.json`` (checked in); a budget
violation exits nonzero so regressions fail loudly.
``tests/unit/test_collective_audit.py`` runs the same audit in-process on a
small model / 8-device mesh as a tier-1 gate.

    # the headline proof (v4-256-shaped abstract mesh):
    python tools/collective_audit.py --preset opt-13b --devices 256 \
        --gather-dtype bf16 --budget opt-13b/256/bf16 --out collective_audit_opt13b.json
    # quantized gathers + bf16 grad reduce on a laptop-sized mesh:
    python tools/collective_audit.py --preset tiny-test --devices 8 \
        --gather-dtype int8 --grad-reduce-dtype bf16
"""

import argparse
import json
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS_PATH = os.path.join(REPO, "tools", "collective_budgets.json")


def load_budget(key):
    with open(BUDGETS_PATH) as f:
        budgets = json.load(f)
    if key not in budgets:
        raise KeyError(
            f"no budget {key!r} in {BUDGETS_PATH}; have "
            f"{sorted(k for k in budgets if not k.startswith('_'))}")
    return budgets[key]


def build_and_audit(preset_name, n_devices, micro, gather_dtype,
                    grad_reduce_dtype, gather_impl="shard_map",
                    sanitize=True):
    """Abstract-init the engine, lower the fused ZeRO-3 per_layer train step,
    audit it. Importable: the tier-1 test calls this in-process with the
    conftest's 8 virtual devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    tools_dir = os.path.join(REPO, "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    from scale_projection import PRESETS

    import deepspeed_tpu
    from deepspeed_tpu.config import MeshConfig
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.profiling.collectives import audit_lowered
    from deepspeed_tpu.runtime.engine import abstract_init

    preset = dict(PRESETS[preset_name])
    devices = jax.devices()[:n_devices]
    assert len(devices) == n_devices, \
        f"need {n_devices} virtual devices, have {len(devices)}"
    mesh = build_mesh(MeshConfig(), devices=devices)

    seq = preset["seq"]
    cfg = TransformerConfig(
        vocab_size=preset["vocab_size"], max_seq_len=seq,
        n_layers=preset["n_layers"], n_heads=preset["n_heads"],
        d_model=preset["d_model"], d_ff=preset["d_ff"],
        compute_dtype=jnp.bfloat16,
        remat=True, remat_policy="minimal", scan_layers=True, fused_ce=True,
        attention_impl="xla",  # pallas doesn't lower on CPU; the attention
        # impl changes compute time, not ZeRO-3 collective volume
    )
    config = {
        "train_batch_size": micro * n_devices,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {
            "stage": 3, "zero3_gather_mode": "per_layer",
            "zero3_gather_impl": gather_impl,
            "zero3_gather_dtype": gather_dtype,
            "grad_reduce_dtype": grad_reduce_dtype,
            "param_persistence_threshold": 2 ** 16,
        },
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    with abstract_init():
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=CausalLM(cfg), config=config, mesh=mesh)
    engine._build_train_step()
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (micro * n_devices, seq), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))}
    lowered = engine._train_step_fn.lower(
        engine.params, engine.optimizer_state, batch, engine._scale,
        engine._good_steps, engine._rng, jnp.asarray(1e-4, jnp.float32),
        jnp.asarray(1.0, jnp.float32))
    # the sanitizer rides the same post-SPMD snapshot: the train program is
    # configured bf16 compute (fp32/int8 only change the GATHER wire dtype);
    # the f32 attention-logits einsum is intentional numerics, not a leak
    from deepspeed_tpu.profiling.sanitizer import ATTENTION_F32_ALLOW

    sanitizer_config = {
        "compute_dtype": "bf16",
        "allow": list(ATTENTION_F32_ALLOW),
    } if sanitize else None
    report = audit_lowered(lowered, n_devices,
                           loop_trip_count=preset["n_layers"],
                           sanitizer_config=sanitizer_config)
    if sanitize:
        # jaxpr-level recompile hazards (baked constants, scalar args) merge
        # into the same sanitizer section; old jax without jit(...).trace
        # just skips this half
        trace = getattr(engine._train_step_fn, "trace", None)
        if trace is not None:
            from deepspeed_tpu.profiling.sanitizer import (merge_reports,
                                                           sanitize_jaxpr)

            args = (engine.params, engine.optimizer_state, batch,
                    engine._scale, engine._good_steps, engine._rng,
                    jnp.asarray(1e-4, jnp.float32),
                    jnp.asarray(1.0, jnp.float32))
            report["sanitizer"] = merge_reports(
                report["sanitizer"],
                sanitize_jaxpr(trace(*args).jaxpr, example_args=args,
                               config=sanitizer_config))
    report.update({
        "preset": preset_name, "devices": n_devices, "micro_per_chip": micro,
        "seq": seq, "n_params": engine.num_parameters,
        "gather_dtype": gather_dtype, "gather_impl": gather_impl,
        "grad_reduce_dtype": grad_reduce_dtype,
    })
    return report


def print_report(report, top_exposed=0):
    print(f"\n## collective audit: {report['preset']} x "
          f"{report['devices']} devices, micro={report['micro_per_chip']}, "
          f"gather_dtype={report['gather_dtype']}, "
          f"grad_reduce_dtype={report['grad_reduce_dtype']}\n")
    sched = report.get("schedule", {})
    by_kind = sched.get("by_kind", {})
    for kind, s in report["collectives"].items():
        if s["count"]:
            dt = ", ".join(f"{k}: {v / 1e9:.2f} GB"
                           for k, v in sorted(s["by_dtype"].items()))
            line = (f"- {kind}: {s['count']} ops, "
                    f"{s['wire_bytes'] / 1e9:.2f} GB wire/chip/step ({dt})")
            sk = by_kind.get(kind)
            if sk and (sk["exposed_count"] or sk["overlappable_count"]):
                line += (f" | exposed {sk['exposed_bytes'] / 1e9:.2f} GB "
                         f"({sk['exposed_count']} ops), overlappable "
                         f"{sk['overlappable_bytes'] / 1e9:.2f} GB "
                         f"({sk['overlappable_count']} ops)")
            print(line)
    print(f"- TOTAL: {report['total_wire_bytes'] / 1e9:.2f} GB/chip/step; "
          f"by dtype: "
          + ", ".join(f"{k}: {v / 1e9:.2f} GB"
                      for k, v in sorted(report["total_by_dtype"].items())))
    if sched:
        print(f"- SCHEDULE: exposed {sched['exposed_bytes'] / 1e9:.2f} GB "
              f"({sched['exposed_fraction']:.1%} of wire) vs overlappable "
              f"{sched['overlappable_bytes'] / 1e9:.2f} GB — dependence-graph "
              f"bound: 'overlappable' means independent compute exists to "
              f"hide behind, not that the backend achieved it")
        for o in sched.get("top_exposed", [])[:top_exposed]:
            print(f"  exposed: {o['kind']} {o['dtype']} "
                  f"{o['wire_bytes'] / 1e9:.3f} GB in {o['computation']}"
                  + (" (async)" if o.get("async") else ""))
    print(f"- fp32 argument (master/opt-state) bytes/chip: "
          f"{report['fp32_param_bytes_per_chip'] / 1e9:.3f} GB "
          f"(sharded fp32 state ~ 3 x 4 x P / N = "
          f"{3 * 4 * report['n_params'] / report['devices'] / 1e9:.3f} GB)")
    san = report.get("sanitizer")
    if san:
        s = san["summary"]
        print(f"- SANITIZER: {s['counts']['error']} errors, "
              f"{s['counts']['warning']} warnings, {s['counts']['info']} "
              f"info | f32 dot flops {s['f32_dot_flops_frac']:.1%}, "
              f"undonated candidates "
              f"{s['undonated_candidate_bytes'] / 1e6:.2f} MB, "
              f"host transfers {s['transfer_count']}, replicated "
              f"{s['replicated_bytes'] / 1e6:.1f} MB; est peak HBM "
              f"{san['peak_hbm']['estimate_bytes'] / 1e9:.3f} GB/chip "
              f"(XLA temp+args "
              f"{(report['memory_per_chip']['temp'] + report['memory_per_chip']['arguments']) / 1e9:.3f} GB) "
              f"— see tools/program_lint.py for the finding list")


def child(args):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from _common import setup_compile_cache, stamp_record

    setup_compile_cache()
    t0 = time.time()
    report = build_and_audit(args.preset, args.devices, args.micro,
                             args.gather_dtype, args.grad_reduce_dtype,
                             gather_impl=args.gather_impl)
    report["audit_seconds"] = round(time.time() - t0, 1)
    stamp_record(report, config={
        "preset": args.preset, "devices": args.devices, "micro": args.micro,
        "gather_dtype": args.gather_dtype, "gather_impl": args.gather_impl,
        "grad_reduce_dtype": args.grad_reduce_dtype})
    print(json.dumps(report))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="opt-13b")
    ap.add_argument("--devices", type=int, default=256)
    ap.add_argument("--micro", type=int, default=1,
                    help="micro batch per chip (sequences)")
    ap.add_argument("--gather-dtype", default="bf16",
                    choices=["auto", "fp32", "bf16", "int8"])
    ap.add_argument("--gather-impl", default="shard_map",
                    choices=["constraint", "shard_map"])
    ap.add_argument("--grad-reduce-dtype", default="fp32",
                    choices=["fp32", "bf16"])
    ap.add_argument("--budget", default=None,
                    help="key into tools/collective_budgets.json; "
                         "violations exit nonzero")
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--top-exposed", type=int, default=5,
                    help="list the N largest EXPOSED collectives (ops whose "
                         "computation has no independent compute to hide "
                         "their wire time behind)")
    args = ap.parse_args()
    if args.child:
        return child(args)

    # re-exec on the CPU platform with the virtual device count (XLA reads
    # the flag at backend init — same dance as scale_projection). No
    # collective-timeout flags here: the audit only COMPILES — nothing
    # executes, no rendezvous can time out.
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={args.devices}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child",
           "--preset", args.preset, "--devices", str(args.devices),
           "--micro", str(args.micro), "--gather-dtype", args.gather_dtype,
           "--gather-impl", args.gather_impl,
           "--grad-reduce-dtype", args.grad_reduce_dtype]
    proc = subprocess.run(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                          text=True, timeout=args.timeout)
    report = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "collectives" in cand:
            report = cand
            break
    if proc.returncode != 0 or report is None:
        sys.stdout.write(proc.stdout)
        print(f"child failed rc={proc.returncode}", file=sys.stderr)
        return 1

    print_report(report, top_exposed=args.top_exposed)
    violations = None
    if args.budget:
        sys.path.insert(0, REPO)
        from deepspeed_tpu.profiling.collectives import check_budgets

        budget = load_budget(args.budget)
        violations = check_budgets(report, budget,
                                   n_params=report["n_params"],
                                   n_devices=report["devices"])
        # the artifact records its own gate result: a committed report that
        # says budget_pass=true was actually checked, not just generated
        report["budget"] = args.budget
        report["budget_pass"] = not violations
        if violations:
            report["budget_violations"] = violations
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
        print(f"- wrote {args.out}")
    if violations:
        for msg in violations:
            print(f"BUDGET VIOLATION: {msg}", file=sys.stderr)
        return 2
    if args.budget:
        print(f"- budget {args.budget!r}: PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
