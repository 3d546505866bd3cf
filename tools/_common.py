"""Shared helpers for the benchmark/profiling tools."""

import hashlib
import json
import os
import subprocess
import sys
import time as _time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:  # the tools import the package from the checkout
    sys.path.insert(0, _REPO)


def _git_sha():
    try:
        out = subprocess.run(["git", "rev-parse", "--short=12", "HEAD"],
                             cwd=_REPO, capture_output=True, text=True,
                             timeout=10)
        if out.returncode == 0:
            sha = out.stdout.strip()
            dirty = subprocess.run(["git", "status", "--porcelain"],
                                   cwd=_REPO, capture_output=True, text=True,
                                   timeout=10)
            if dirty.returncode == 0 and dirty.stdout.strip():
                sha += "-dirty"
            return sha
    except (OSError, subprocess.SubprocessError):
        pass
    return "unknown"


def run_stamp(config=None):
    """Provenance stamp for every bench/audit JSON artifact: git SHA,
    config hash, and the backend that produced the numbers.

    A CPU smoke run and an on-chip run of the same tool produce
    byte-similar artifacts; without the embedded backend/SHA they get
    confused later. ``config`` is any JSON-able object describing the run's
    knobs; its sha256 prefix pins "same code, same config" across artifacts.
    """
    import jax

    stamp = {
        "git_sha": _git_sha(),
        "stamp_time": _time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }
    if config is not None:
        blob = json.dumps(config, sort_keys=True, default=str)
        stamp["config_hash"] = hashlib.sha256(blob.encode()).hexdigest()[:12]
    dev = jax.devices()[0]
    stamp["backend"] = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": jax.device_count(),
        "jax": jax.__version__,
    }
    return stamp


def stamp_record(record, config=None):
    """Attach ``run_stamp`` under ``record["provenance"]`` (in place)."""
    record["provenance"] = run_stamp(config)
    return record


def setup_compile_cache():
    """Persistent compilation cache shared by every tool: wherever
    ``JAX_COMPILATION_CACHE_DIR`` placed it, else ``<checkout>/.jax_cache``
    (``deepspeed_tpu/utils/compile_cache.py``)."""
    from deepspeed_tpu.utils.compile_cache import setup_compile_cache as setup

    return setup()


def peak_hbm_gbs(device_kind):
    """Published peak HBM GB/s for ``device_kind`` — the decode-throughput
    roofline denominator (weight-only decode at batch 1 reads every live
    weight byte once per token, so achieved GB/s = weight_bytes x steps/s).
    An unknown kind raises (``deepspeed_tpu/accelerator/peaks.py``)."""
    from deepspeed_tpu.accelerator.peaks import device_peaks

    return device_peaks(device_kind).hbm_gbs


def require_tpu(tool):
    """Refuse to run a device-measuring tool anywhere but on a TPU: a host
    number is never printed under a device metric's name."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(f"{tool} measures a TPU; platform is {platform!r} "
                         "— refusing to run")
