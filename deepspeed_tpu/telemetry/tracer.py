"""Span-based step tracer: nested host spans with optional device fencing.

The observability substrate for the three performance-critical loops (train
step, ZeRO-3 gather schedule, serving decode). A ``SpanTracer`` records
nested host-side spans (begin/end pairs) and instant events against a
pluggable clock, and emits two views of the same record:

- **Chrome-trace JSON** (``trace.json``): the Trace Event Format both
  ``chrome://tracing`` and Perfetto load directly — complete "X" events
  with microsecond ``ts``/``dur``, one row per thread;
- **structured JSONL** (``spans.jsonl``): one JSON object per finished
  span/instant, machine-readable for ``tools/trace_summary.py`` and the
  tier-1 TTFT/TPOT-from-trace assertions.

Host timers measure *dispatch* unless fenced: under jax's async dispatch a
``stop()`` right after a jitted call returns before the device has done any
work. A span opened with ``sync=True`` runs the tracer's ``sync_fn`` (or
``jax.block_until_ready`` on a value the body registered via
``sp.fence(x)``) before reading the end timestamp, so the span covers
execution, not enqueue. The serving tracer instead runs against the
scheduler's own clock (wall or virtual), which is what makes trace-derived
TTFT/TPOT bit-identical to ``ServingMetrics`` under the virtual clock.

Every span is also a ``jax.profiler.TraceAnnotation`` named
``<cat>/<name>`` (a name that carries its own prefix, ``checkpoint/save``,
keeps it), so a profiler trace shows the engine's phases on the device
trace's own clock (``serving/step``, ``serving/read_back``, ``train/step``,
...) and an idle gap of the device can be put down to what the host was
doing. A disabled tracer records nothing but still emits that annotation:
about half a microsecond a span with the profiler off, so the spans stay in
the hot loops unconditionally.
"""

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

from ..utils.logging import logger


def profiler_name(name, cat):
    """The span's name in a profiler trace: ``<cat>/<name>``, or the name
    itself where it carries its own prefix."""
    return name if "/" in name else f"{cat}/{name}"


class _ProfilerSpan(TraceAnnotation):
    """A disabled tracer's span: the profiler annotation alone."""

    def fence(self, value):
        pass

    def set(self, **args):
        pass


def event_to_chrome(e, pid=0):
    """One internal event dict -> Trace Event Format (seconds -> us).
    Shared by ``SpanTracer.to_chrome_trace`` and the fleet merger
    (``telemetry/fleet.py``), which assigns one pid per source so N
    replica streams render as N process lanes."""
    ev = {"ph": e["ph"], "name": e["name"], "cat": e.get("cat", ""),
          "ts": e["ts"] * 1e6, "pid": pid, "tid": e.get("tid", 0),
          "args": e.get("args", {})}
    if e["ph"] == "X":
        ev["dur"] = e.get("dur", 0.0) * 1e6
    elif e["ph"] == "i":
        ev["s"] = "t"
    return ev


class _Span:
    __slots__ = ("tracer", "name", "cat", "sync", "args", "t0", "_fence",
                 "_annotation")

    def __init__(self, tracer, name, cat, sync, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.sync = sync
        self.args = args
        self.t0 = None
        self._fence = None
        self._annotation = TraceAnnotation(profiler_name(name, cat))

    def fence(self, value):
        """Register device value(s) to ``block_until_ready`` at span end
        (only consulted when the span was opened with ``sync=True``)."""
        self._fence = value

    def set(self, **args):
        """Attach/override args after the span is open (e.g. a result
        computed inside the body)."""
        self.args.update(args)

    def __enter__(self):
        self._annotation.__enter__()
        self.t0 = self.tracer._now()
        self.tracer._stack().append(self)
        return self

    def __exit__(self, exc_type, exc, tb):
        tracer = self.tracer
        synced = False
        if self.sync and exc_type is None:
            synced = tracer._run_fence(self._fence)
        t1 = tracer._now()
        stack = tracer._stack()
        if stack and stack[-1] is self:
            stack.pop()
        parent = stack[-1].name if stack else None
        args = self.args
        if synced:
            args = dict(args, synced=True)
        tracer._record({
            "ph": "X", "name": self.name, "cat": self.cat,
            "ts": self.t0, "dur": t1 - self.t0,
            "depth": len(stack), "parent": parent, "args": args,
        })
        self._annotation.__exit__(exc_type, exc, tb)
        return False


class SpanTracer:
    """Nested span recorder with Chrome-trace / JSONL emission.

    ``clock``: a zero-arg callable returning seconds (defaults to
    ``time.perf_counter``; the serving engine passes its scheduler clock so
    virtual-time runs trace in virtual time). ``sync_fn``: zero-arg device
    fence used by ``sync=True`` spans that registered no explicit value.
    """

    def __init__(self, enabled=True, clock=None, sync_fn=None,
                 max_events=100_000, output_path="", job_name="",
                 chrome_trace=True, jsonl=True, meta=None):
        self.enabled = bool(enabled)
        self._clock = clock or time.perf_counter
        self._sync_fn = sync_fn
        self.max_events = int(max_events)
        self.chrome_trace = chrome_trace
        self.jsonl = jsonl
        self.meta = dict(meta or {})
        self.events = []
        self.dropped = 0
        self._seq = 0
        self._local = threading.local()
        self._tids = {}
        self._jsonl_flushed = 0
        self._chrome_flushed = -1
        self.output_dir = None
        if output_path:
            self.output_dir = os.path.join(output_path, job_name) \
                if job_name else output_path

    @classmethod
    def from_config(cls, cfg, clock=None, sync_fn=None, meta=None):
        """Build from a ``telemetry`` config block (None/disabled -> a
        tracer that records nothing: its spans are profiler annotations
        only). Multi-process runs write per-rank trace dirs
        (``<job_name>-rank<N>`` past rank 0): a shared ``trace.json`` is
        whole-file rewritten and a shared ``spans.jsonl`` is truncated by
        each process's first flush, so same-path writers would clobber each
        other."""
        if cfg is None or not getattr(cfg, "enabled", False):
            return cls(enabled=False)
        job = cfg.job_name
        try:
            from .. import comm as dist

            rank = dist.get_rank()
        except Exception:
            rank = 0
        if rank > 0:
            job = f"{job}-rank{rank}"
        return cls(enabled=True, clock=clock, sync_fn=sync_fn,
                   max_events=cfg.max_events,
                   output_path=cfg.output_path or "./traces",
                   job_name=job,
                   chrome_trace=cfg.chrome_trace, jsonl=cfg.jsonl,
                   meta=meta)

    # ------------------------------------------------------------ internals
    def _now(self):
        return self._clock()

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _tid(self):
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _run_fence(self, value):
        try:
            if value is not None:
                import jax

                jax.block_until_ready(value)
                return True
            if self._sync_fn is not None:
                self._sync_fn()
                return True
        except Exception as e:  # tracing must never take down the step
            logger.warning("telemetry: device fence failed: %s", e)
        return False

    def _record(self, event):
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        event["tid"] = self._tid()
        event["seq"] = self._seq
        self._seq += 1
        self.events.append(event)

    # ------------------------------------------------------------------ API
    def span(self, name, cat="host", sync=False, **args):
        """Context manager recording one complete span. ``sync=True`` fences
        the device (``sp.fence(x)`` value, else the tracer's ``sync_fn``)
        before the end timestamp. Enabled or not, the span is also a
        profiler annotation (``profiler_name``); ``args`` go to the record
        only."""
        if not self.enabled:
            return _ProfilerSpan(profiler_name(name, cat))
        return _Span(self, name, cat, sync, args)

    def instant(self, name, cat="mark", ts=None, **args):
        """Point event at ``ts`` (defaults to now)."""
        if not self.enabled:
            return
        self._record({
            "ph": "i", "name": name, "cat": cat,
            "ts": self._now() if ts is None else ts, "dur": 0.0,
            "depth": len(self._stack()), "parent": None, "args": args,
        })

    # ------------------------------------------------------------- emission
    def to_chrome_trace(self):
        """The Trace Event Format dict Perfetto/chrome://tracing load."""
        out = [{"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
                "args": {"name": self.meta.get("process", "deepspeed_tpu")}}]
        out.extend(event_to_chrome(e) for e in self.events)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": dict(self.meta, dropped_events=self.dropped)}

    def write_chrome_trace(self, path):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path

    def write_jsonl(self, path, append=False):
        """Structured JSONL: one object per event. ``append=True`` writes
        only events not yet flushed to this tracer's stream (the
        incremental ``flush()`` path)."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        start = self._jsonl_flushed if append else 0
        # first incremental flush truncates any stale file from a prior run
        mode = "a" if (append and self._jsonl_flushed > 0) else "w"
        with open(path, mode) as f:
            for e in self.events[start:]:
                f.write(json.dumps(e) + "\n")
        self._jsonl_flushed = len(self.events)
        return path

    def flush(self):
        """Write the configured trace files (no-op without an output dir).
        JSONL appends incrementally; the Chrome trace is rewritten whole so
        the file is always a complete, loadable trace."""
        if not self.enabled or self.output_dir is None:
            return None
        os.makedirs(self.output_dir, exist_ok=True)
        if self.jsonl:
            self.write_jsonl(os.path.join(self.output_dir, "spans.jsonl"),
                             append=True)
        if self.chrome_trace and self._chrome_flushed != len(self.events):
            # the whole-file rewrite is skipped when nothing new arrived:
            # a steps_per_print cadence of no-op flushes must stay O(1)
            self.write_chrome_trace(os.path.join(self.output_dir,
                                                 "trace.json"))
            self._chrome_flushed = len(self.events)
        return self.output_dir
