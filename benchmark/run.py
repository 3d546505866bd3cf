"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one cell, once, in this one process.

Checks the manifest, refuses anything but a TPU with published peaks, keeps
JAX's persistent compilation cache at a fixed place, and hands the cell to
the runner its traffic file names (``kind``). Nothing in here, or in the
runners, knows a cell, a configuration or a metric by name: they are found
through ``BENCHMARK.json``. The contract's JSON object is the last line of
stdout; ``{"note": ...}`` lines before it carry what else is worth reading.

``--rehearse-cpu`` (never passed by the driver) lays each file's
``rehearse_cpu`` sizes over it and runs the same control flow on the CPU,
on as many virtual devices as the cell has chips. Its output is labelled
and holds no result line and no device metric.
"""

import argparse
import importlib
import os
import sys

from . import check_manifest, harness


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace here instead of deleting it")
    args = ap.parse_args(argv)

    problems = check_manifest.check(harness.CHECKOUT)
    if problems:
        raise harness.Refused("BENCHMARK.json: " + "; ".join(problems))
    manifest = harness.load_json(harness.MANIFEST)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        raise harness.Refused(f"no cell {args.workload!r}; there are "
                              f"{sorted(cells)}")
    cell = cells[args.workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_"
            f"device_count={cell['chips']}").strip()
    config = harness.load_sized(
        os.path.join(harness.CHECKOUT, entry["file"]), args.rehearse_cpu)
    traffic = harness.load_sized(
        harness.traffic_path(cell["traffic"]), args.rehearse_cpu)

    import deepspeed_tpu  # noqa: F401  (no system under test, no run)
    import jax

    devices, peaks = harness.claim_devices(cell["chips"], args.rehearse_cpu)

    cache_dir = None if args.rehearse_cpu else harness.setup_compile_cache()
    cache_log = harness.CompileCacheLog()
    if args.rehearse_cpu:
        print("# REHEARSAL on the CPU: tiny sizes, control flow only; "
              "nothing below is a chip result", flush=True)
    harness.note("start", cell=cell["name"], seed=args.seed,
                 seconds=args.seconds, trace=args.trace,
                 device={"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices)},
                 jax=jax.__version__, compile_cache_dir=cache_dir,
                 peaks=peaks, setup_so_far_s=harness.process_age_s())
    runner = importlib.import_module("benchmark." + traffic["kind"])
    return runner.run(cell["name"], config, traffic, manifest, args,
                      devices, peaks, cache_log)


if __name__ == "__main__":
    sys.exit(main())
