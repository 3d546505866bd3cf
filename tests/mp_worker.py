"""Worker entry for the multi-process harness: force the CPU platform with this
process's virtual device count, join the distributed rendezvous through the
framework's own ``init_distributed``, then run the target function."""

import importlib
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count="
      f"{os.environ.get('DS_TPU_LOCAL_DEVICES', '4')}").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import deepspeed_tpu.comm as dist  # noqa: E402


def main():
    target = sys.argv[1]
    mod_name, fn_name = target.split(":")
    dist.init_distributed()
    fn = getattr(importlib.import_module(mod_name), fn_name)
    fn()
    print(f"WORKER_OK {jax.process_index()}", flush=True)


if __name__ == "__main__":
    main()
