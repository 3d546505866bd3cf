"""v4-256 / OPT-13B ZeRO-3 scale artifact — no hardware required.

The north star (BASELINE.md: ZeRO-3 OPT-13B > 40% MFU on v4-256, matching
the reference's sustained-50-TFLOPS/GPU claim in
``/root/reference/docs/_posts/2021-03-08-zero3-offload.md:15``) needs a scale
argument one host cannot measure. This tool builds it from the REAL compiled
program, not a formula:

1. Constructs the engine for an OPT-13B config on an N-virtual-device CPU mesh
   under ``runtime.engine.abstract_init`` (params/opt-state are
   ShapeDtypeStructs — nothing materializes), lowers + compiles the exact
   fused ZeRO-3 ``per_layer`` train step, and reads XLA's
   ``memory_analysis()``: the per-chip HBM requirement.
2. Parses the optimized HLO for every collective (all-gather / reduce-scatter
   / all-reduce), sums wire bytes per chip per step, and records which
   computation each lives in (the per-layer gathers must sit INSIDE the scan
   body — bounded live memory, the reference's partitioned_param_coordinator
   fetch discipline).
3. Applies an ICI bandwidth model (documented assumptions) to get collective
   time vs compute time per layer — the overlap budget — and a projected MFU.

    python tools/scale_projection.py --devices 256 --micro 2
    python tools/scale_projection.py --devices 64 --preset opt-13b  # smaller host

Writes ``scale_projection_r05.json`` and prints a markdown report for PERF.md.
"""

import argparse
import json
import math
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Model presets (decoder-only, OPT family sizes; OPT-13B per its public card:
# 40 layers, d_model 5120, 40 heads, ffn 4x)
PRESETS = {
    "opt-13b": dict(n_layers=40, d_model=5120, n_heads=40, d_ff=20480,
                    vocab_size=50304, seq=2048),
    "opt-30b": dict(n_layers=48, d_model=7168, n_heads=56, d_ff=28672,
                    vocab_size=50304, seq=2048),
    # headline bench shape, for sanity-checking the pipeline quickly
    "gpt2-350m": dict(n_layers=24, d_model=1024, n_heads=16, d_ff=4096,
                      vocab_size=50304, seq=1024),
    # seconds-scale shape for the tier-1 collective audit (8-device CPU mesh;
    # tests/unit/test_collective_audit.py) and for exercising the audit
    # pipeline end to end without a big compile
    "tiny-test": dict(n_layers=4, d_model=128, n_heads=4, d_ff=256,
                      vocab_size=512, seq=64),
}

# ICI model (documented assumptions; "How to Scale Your Model" numbers):
# v4 is a 3D torus with 2 links/axis/chip at ~45 GB/s unidirectional each.
# A ring all-gather/reduce-scatter decomposed over all 3 axes sustains
# ~6 x 45 = 270 GB/s of wire bandwidth per chip in the ideal case; we also
# report a pessimistic single-axis 90 GB/s scenario.
ICI_BW_OPTIMISTIC = 270e9
ICI_BW_PESSIMISTIC = 90e9
V4_HBM_BYTES = 32e9
V4_PEAK_FLOPS = 275e12
# single-chip measured MFU at the bench shape (PERF.md, 2026-08-01): the
# compute-efficiency prior for the projection
MEASURED_SINGLE_CHIP_MFU = 0.4157

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s8": 1, "u8": 1, "pred": 1, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"=\s+(?:\()?(\w+)\[([\d,]*)\]")
_TUPLE_SHAPES_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _nbytes(dtype, dims):
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def _result_bytes(line, is_start=False):
    if is_start:
        # async start ops return a tuple `(operand, ..., output)`; the OUTPUT
        # (last element) is the gathered/reduced result — taking the first
        # would count the 1/N-sized operand for all-gather (and the full
        # input for reduce-scatter), skewing wire accounting ~N x
        head = line.split("-start(")[0]
        shapes = _TUPLE_SHAPES_RE.findall(head)
        if shapes:
            return _nbytes(*shapes[-1])
        return 0
    m = _SHAPE_RE.search(line)
    if not m:
        return 0
    return _nbytes(*m.groups())


def parse_collectives(hlo, n_devices, loop_trip_count):
    """Per-chip wire bytes + per-computation counts for each collective kind.

    Wire-byte accounting (ring algorithms, per chip): all-gather receives
    (N-1)/N of the full result; reduce-scatter sends (N-1)/N of the full
    input (= result x N); all-reduce is RS+AG = 2 x (N-1)/N x full.

    A collective inside a ``while`` body appears ONCE in the HLO text but
    executes once per loop iteration — the same static-text trap that broke
    the autotuner cost model in r4 (cost_analysis counted a scan body once,
    not x n_layers). Body computations are identified from the ``body=``
    attribute of every while op and their wire bytes are multiplied by
    ``loop_trip_count`` (= n_layers for the layer scan; documented
    approximation — every while in this program IS a layer scan).
    """
    frac = (n_devices - 1) / n_devices
    body_names = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    stats = {k: {"count": 0, "wire_bytes": 0.0, "by_computation": {}}
             for k in ("all-gather", "reduce-scatter", "all-reduce",
                       "all-to-all", "collective-permute")}
    comp = "<entry>"
    for line in hlo.splitlines():
        s = line.strip()
        # computation headers look like: %name (p0: ...) -> type {   (with
        # optional ENTRY prefix)
        if s.endswith("{") and ("(" in s) and ("->" in s) and not s.startswith("ROOT"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*\(", s)
            if m:
                comp = m.group(1)
            continue
        for kind in stats:
            # match the op invocation, not tuple-shape mentions: " kind(" after "= shape"
            if f" {kind}(" in s or f" {kind}-start(" in s:
                b = _result_bytes(s, is_start=f" {kind}-start(" in s)
                if kind == "all-gather":
                    wire = b * frac
                elif kind == "reduce-scatter":
                    wire = b * n_devices * frac
                elif kind == "all-reduce":
                    wire = 2 * b * frac
                elif kind == "collective-permute":
                    wire = b
                else:
                    wire = b * frac
                if comp in body_names:
                    wire *= loop_trip_count
                st = stats[kind]
                st["count"] += 1
                st["wire_bytes"] += wire
                st["by_computation"][comp] = st["by_computation"].get(comp, 0) + 1
                break
    stats["_loop_body_computations"] = sorted(body_names)
    return stats


def child(args):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from _common import setup_compile_cache

    setup_compile_cache()
    import jax

    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sys.path.insert(0, REPO)
    import deepspeed_tpu
    from deepspeed_tpu.runtime.engine import abstract_init
    from deepspeed_tpu.models import CausalLM, TransformerConfig
    from deepspeed_tpu.parallel import build_mesh
    from deepspeed_tpu.config import MeshConfig

    preset = PRESETS[args.preset]
    n = args.devices
    devices = jax.devices()[:n]
    assert len(devices) == n, f"need {n} virtual devices, have {len(devices)}"
    mesh = build_mesh(MeshConfig(), devices=devices)  # pure dp: ZeRO-3 axis

    seq = preset["seq"]
    cfg = TransformerConfig(
        vocab_size=preset["vocab_size"], max_seq_len=seq,
        n_layers=preset["n_layers"], n_heads=preset["n_heads"],
        d_model=preset["d_model"], d_ff=preset["d_ff"],
        compute_dtype=jnp.bfloat16,
        remat=True, remat_policy="minimal", scan_layers=True, fused_ce=True,
        attention_impl="xla",  # pallas doesn't lower on the CPU backend; the
        # attention impl changes compute time, not ZeRO-3 collective volume
    )
    config = {
        "train_batch_size": args.micro * n,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3, "zero3_gather_mode": "per_layer",
                              "param_persistence_threshold": 2 ** 16},
        "gradient_clipping": 1.0,
        "steps_per_print": 10 ** 9,
    }
    t0 = time.time()
    with abstract_init():
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=CausalLM(cfg), config=config, mesh=mesh)
    print(f"# abstract engine: {engine.num_parameters / 1e9:.2f}B params "
          f"({time.time() - t0:.0f}s)", flush=True)

    engine._build_train_step()
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (args.micro * n, seq), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))}
    t0 = time.time()
    lowered = engine._train_step_fn.lower(
        engine.params, engine.optimizer_state, batch, engine._scale,
        engine._good_steps, engine._rng, jnp.asarray(1e-4, jnp.float32),
        jnp.asarray(1.0, jnp.float32))
    print(f"# lowered ({time.time() - t0:.0f}s)", flush=True)
    t0 = time.time()
    compiled = lowered.compile()
    print(f"# compiled ({time.time() - t0:.0f}s)", flush=True)

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    stats = parse_collectives(hlo, n, loop_trip_count=preset["n_layers"])

    P_count = engine.num_parameters
    out = {
        "preset": args.preset, "devices": n, "micro_per_chip": args.micro,
        "seq": seq, "n_params": P_count,
        "memory_per_chip": {
            "temp": mem.temp_size_in_bytes,
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
            "peak_projection": (mem.temp_size_in_bytes
                                + mem.argument_size_in_bytes
                                + mem.output_size_in_bytes
                                - mem.alias_size_in_bytes),
        },
        "collectives": stats,
        "hlo_bytes": len(hlo),
    }
    print(json.dumps(out))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="opt-13b", choices=sorted(PRESETS))
    ap.add_argument("--devices", type=int, default=256)
    ap.add_argument("--micro", type=int, default=2,
                    help="micro batch per chip (sequences)")
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--out", default=os.path.join(REPO, "scale_projection_r05.json"))
    args = ap.parse_args()
    if args.child:
        return child(args)

    # re-exec on the CPU platform with the virtual device count (XLA reads
    # the flag at backend init)
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={args.devices}"
        " --xla_cpu_collective_call_terminate_timeout_seconds=600"
        " --xla_cpu_collective_timeout_seconds=600").strip()
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-u", os.path.abspath(__file__), "--child",
           "--preset", args.preset, "--devices", str(args.devices),
           "--micro", str(args.micro)]
    proc = subprocess.run(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                          text=True, timeout=args.timeout)
    sys.stderr.write("")
    data = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            cand = json.loads(line)
        except ValueError:
            continue
        if isinstance(cand, dict) and "memory_per_chip" in cand:
            data = cand
            break
    print(proc.stdout)
    if proc.returncode != 0 or data is None:
        print(f"child failed rc={proc.returncode}", file=sys.stderr)
        return 1

    # ----- the projection ---------------------------------------------------
    n = data["devices"]
    P_count = data["n_params"]
    tokens_per_chip = data["micro_per_chip"] * data["seq"]
    flops_per_chip = 6.0 * P_count * tokens_per_chip
    t_compute_ideal = flops_per_chip / V4_PEAK_FLOPS
    t_compute = t_compute_ideal / MEASURED_SINGLE_CHIP_MFU

    body_names = set(data["collectives"].pop("_loop_body_computations", []))
    wire = sum(s["wire_bytes"] for s in data["collectives"].values())
    scenarios = {}
    for name, bw in (("optimistic_3axis", ICI_BW_OPTIMISTIC),
                     ("pessimistic_1axis", ICI_BW_PESSIMISTIC)):
        t_ici = wire / bw
        # full-overlap model (evidence: per-layer gathers sit inside the scan
        # body, so the latency-hiding scheduler can run layer i's compute
        # against layer i+1's gather); step time = max of the two streams
        t_step = max(t_compute, t_ici)
        mfu = flops_per_chip / (t_step * V4_PEAK_FLOPS)
        scenarios[name] = {
            "ici_bw_gbs": bw / 1e9,
            "t_ici_s": round(t_ici, 4),
            "t_step_s": round(t_step, 4),
            "projected_mfu": round(mfu, 4),
            "overlap_headroom": round(t_compute / t_ici, 2) if t_ici else None,
        }

    ag = data["collectives"]["all-gather"]
    in_loop = {c: k for c, k in ag["by_computation"].items()
               if c in body_names}
    mem = data["memory_per_chip"]
    report = {
        **data,
        "hlo_bytes": data["hlo_bytes"],
        "assumptions": {
            "v4_peak_flops": V4_PEAK_FLOPS,
            "v4_hbm_bytes": V4_HBM_BYTES,
            "single_chip_mfu_prior": MEASURED_SINGLE_CHIP_MFU,
            "ici_model": "ring collectives; 45 GB/s per link per direction; "
                         "3-axis (270 GB/s) vs 1-axis (90 GB/s) per chip",
            "overlap": "per-layer gathers inside the scan body + TPU "
                       "latency-hiding scheduler => max(compute, ici) step",
        },
        "per_chip_wire_bytes_per_step": wire,
        "t_compute_s_at_measured_mfu": round(t_compute, 4),
        "hbm_fit": {
            "peak_projection_gb": round(mem["peak_projection"] / 1e9, 2),
            "v4_hbm_gb": V4_HBM_BYTES / 1e9,
            "fits": mem["peak_projection"] < V4_HBM_BYTES,
        },
        "gathers_in_loop_body": in_loop,
        "scenarios": scenarios,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)

    print("\n## v4-256 projection (generated by tools/scale_projection.py)\n")
    print(f"- config: {data['preset']} ({P_count / 1e9:.2f}B params), "
          f"ZeRO-3 per_layer over dp={n}, micro={data['micro_per_chip']} x "
          f"seq={data['seq']} per chip")
    print(f"- per-chip HBM (XLA memory_analysis on the compiled step): "
          f"**{mem['peak_projection'] / 1e9:.1f} GB** of {V4_HBM_BYTES / 1e9:.0f} GB"
          f" -> {'FITS' if report['hbm_fit']['fits'] else 'DOES NOT FIT'}")
    for kind, s in data["collectives"].items():
        if s["count"]:
            print(f"- {kind}: {s['count']} ops, "
                  f"{s['wire_bytes'] / 1e9:.1f} GB wire/chip/step "
                  f"(in: {', '.join(sorted(s['by_computation'])[:4])})")
    print(f"- total wire: {wire / 1e9:.1f} GB/chip/step; compute at the "
          f"measured {MEASURED_SINGLE_CHIP_MFU} MFU prior: {t_compute:.2f} s")
    for name, s in scenarios.items():
        print(f"- {name} ({s['ici_bw_gbs']:.0f} GB/s): ici {s['t_ici_s']} s, "
              f"step {s['t_step_s']} s -> **projected MFU {s['projected_mfu']}**"
              f" (overlap headroom {s['overlap_headroom']}x)")
    print(f"- gathers inside the scan body: {in_loop or 'NONE (check!)'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
