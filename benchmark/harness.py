"""What both runners share: files found by name, the device and its peaks,
the compile cache and its log, the clock of ``setup_s``, the traced slice,
the per-layer readers, and the result line.

Copied (not imported) from the program's own entry points where they were
sound: ``CompileCacheLog``, ``peak_memory`` and the cache rule of
``chip_smoke.py`` / ``utils/compile_cache.py``, the peaks of
``accelerator/peaks.py``.
"""

import contextlib
import importlib.util
import json
import logging
import math
import os
import shutil
import sys
import tempfile
import time

from .check_manifest import DATA_SUFFIXES

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
MANIFEST = os.path.join(CHECKOUT, "BENCHMARK.json")
_IMPORTED_AT = time.time()


class Refused(SystemExit):
    """The run cannot be made here; exit code 2, nothing on stdout."""

    def __init__(self, why):
        print(f"benchmark: {why}", file=sys.stderr)
        super().__init__(2)


def note(name, **fields):
    """Whatever is worth reading besides the result goes on earlier lines."""
    print(json.dumps({"note": name, **fields}, default=str), flush=True)


def process_age_s():
    """Seconds since this process was started, interpreter start-up and
    imports included (Linux: start time from /proc)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time() - _IMPORTED_AT


def load_json(path):
    with open(path) as f:
        return json.load(f)


def merge_over(base, over):
    """``over`` laid over ``base``, group by group."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge_over(out[k], v) \
            if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def load_sized(path, rehearse):
    """A configuration or traffic file; under ``--rehearse-cpu`` with its
    ``rehearse_cpu`` group laid over it (tiny sizes, control flow only)."""
    data = load_json(path)
    small = data.pop("rehearse_cpu", {})
    return merge_over(data, small) if rehearse else data


def traffic_path(name):
    for suffix in DATA_SUFFIXES:
        path = os.path.join(HERE, "traffic", name + suffix)
        if os.path.isfile(path):
            return path
    return None


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(manifest, cell_name, group):
    """The metrics of ``group`` that cell ``cell_name`` reports."""
    return [m for m in manifest[group]
            if cell_name in m.get("workloads", [cell_name])]


def build_model(config):
    from deepspeed_tpu.models import get_model

    m = config["model"]
    model = get_model(m["family"], m["size"], **m["overrides"])
    got = {k: getattr(model.config, k) for k in config["arch"]
           if hasattr(model.config, k)}
    want = {k: config["arch"][k] for k in got}
    if got != want:
        raise SystemExit(f"benchmark: the model the program builds {got} "
                         f"is not the configuration file's {want}")
    return model


# ---------------------------------------------------------------- the device
def claim_devices(chips, rehearse):
    """The first ``chips`` devices and their peaks. Anything but a TPU with
    published peaks is refused: there is no fallback to the CPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not rehearse:
        raise Refused(f"platform is {dev.platform!r}, not 'tpu' "
                      "(--rehearse-cpu rehearses the control flow)")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX finds "
                      f"{len(devices)}")
    if rehearse:
        return devices[:chips], None
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if dev.device_kind not in peaks:
        raise Refused(f"no published peaks for device_kind "
                      f"{dev.device_kind!r} in benchmark/peaks.json")
    return devices[:chips], peaks[dev.device_kind]


def setup_compile_cache():
    """JAX's persistent compilation cache: where JAX_COMPILATION_CACHE_DIR
    says, else the fixed ``<checkout>/.jax_cache``; every program is kept,
    however fast it compiled."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return placed


class CompileCacheLog(logging.Handler):
    """Names of the programs JAX's persistent compilation cache served (hit)
    or had to compile (miss), read off ``jax._src.compiler``'s log lines.
    Every program that is traced and lowered gives one or the other, so a
    line inside the window is a compile inside the window."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits, self.misses = [], []
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def emit(self, record):
        msg = str(record.msg)
        if "ersistent compilation cache hit for" in msg:
            self.hits.append(record.args[0])
        elif "PERSISTENT COMPILATION CACHE MISS for" in msg:
            self.misses.append(record.args[0])
        elif record.levelno >= logging.WARNING:
            logging.lastResort.handle(record)

    def mark(self):
        return len(self.hits), len(self.misses)

    def since(self, mark):
        return self.hits[mark[0]:] + self.misses[mark[1]:]


def memory_peak_bytes(devices):
    """``peak_bytes_in_use`` + ``peak_bytes_reserved`` of the fullest chip:
    on this runtime the first counts live arrays only and a running
    program's temporaries show under the second."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0))
    return int(peak)


def span(name):
    """A harness span in the profiler's own trace (``bench/<name>``), so an
    idle gap of the device gets the name of what the host was doing."""
    import jax

    return jax.profiler.TraceAnnotation("bench/" + name)


def quantile(samples, q):
    import numpy as np

    return float(np.percentile(np.asarray(samples, float), q))


# ----------------------------------------------------------- the traced slice
class TracedSlice:
    """Traces the last ``slice_s`` seconds of a window of ``seconds``. With
    tracing off every method is a no-op, so both kinds of run take one path
    through the runners."""

    def __init__(self, enabled, seconds, slice_s, keep_dir=None):
        self.enabled = enabled
        self.start_after = max(seconds - slice_s, 0.0)
        self.keep_dir = keep_dir
        self.dir = None
        self.running = False
        self.reduced = None

    def maybe_start(self, elapsed):
        if self.enabled and self.dir is None and elapsed >= self.start_after:
            import jax

            self.dir = self.keep_dir or tempfile.mkdtemp(prefix="bench_trace_")
            os.makedirs(self.dir, exist_ok=True)
            # the harness's spans are TraceMe events; tracing every Python
            # call as well would slow the very loop being traced
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.running = True
        return self.running

    def stop(self):
        if not self.running:
            return
        import jax

        from . import trace_reduce

        jax.profiler.stop_trace()
        self.running = False
        path = trace_reduce.find_xplane(self.dir)
        if path is not None:
            self.reduced = trace_reduce.reduce(trace_reduce.load(path))
        if not self.keep_dir:
            shutil.rmtree(self.dir, ignore_errors=True)
        if self.reduced is not None:
            note("trace", **{k: self.reduced[k] for k in (
                "chips", "window_s", "busy_s", "collective_exposed_s",
                "has_collectives", "modules")})


# ------------------------------------------------------- metrics and the line
def read_layer_metrics(manifest, cell_name, obs):
    """Each per-layer metric of the cell through its own reader,
    ``benchmark/layer_metrics/<name>.py``; a reader that finds nothing to
    read returns None and the metric is left out."""
    out, left_out = {}, []
    for m in cell_metrics(manifest, cell_name, "per_layer"):
        reader = load_module(
            os.path.join(HERE, "layer_metrics", m["name"] + ".py"),
            "benchmark_layer_metric_" + m["name"].replace(".", "_"))
        value = None
        with contextlib.suppress(KeyError, TypeError, ZeroDivisionError):
            value = reader.read(obs)
        if value is None:
            left_out.append(m["name"])
        else:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if left_out:
        note("layer_metrics_left_out", names=left_out)
    return out


def result_line(manifest, cell_name, args, *, correct, attempted, failed,
                end_to_end, obs, devices, traced):
    """The contract's one JSON object, printed last. ``--trace 0``: the
    cell's end-to-end metrics. ``--trace 1``: its per-layer metrics, the
    device's busy time and the breakdown."""
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(devices)}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    if args.trace:
        out["metrics"] = read_layer_metrics(manifest, cell_name, obs)
        red = traced.reduced
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"]
                 for m in cell_metrics(manifest, cell_name, "end_to_end")}
        # a metric with no sample is left out (and ``correct`` is false)
        out["metrics"] = {k: {"value": float(end_to_end[k]), "unit": u}
                          for k, u in units.items()
                          if math.isfinite(end_to_end[k])}
    out["device"] = device
    if args.rehearse_cpu:
        # a rehearsal prints no device metric and no result line
        note("rehearsal", passed=bool(correct), attempted=int(attempted),
             failed=int(failed), metric_names=sorted(out["metrics"]),
             device=device)
        return 0 if correct else 1
    print(json.dumps(out), flush=True)
    return 0
