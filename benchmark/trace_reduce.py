"""From a profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read. Needs nothing but ``jax.profiler.ProfileData``.

What a TPU trace holds (seen on a v5e, jax 0.9.0): a plane ``/device:TPU:n``
for each chip with the lines ``XLA Modules`` (one event a jitted program,
named ``jit_<function>``) and ``XLA Ops`` (the core's instruction stream:
``fusion.N``, ``copy.N``, call-like operations such as ``checkpoint.N`` that
CONTAIN the operations of their body, and collectives under their HLO names);
and a plane ``/host:CPU`` whose lines are the host's threads, where the
``jax.profiler.TraceAnnotation`` spans of the harness (``bench/...``) land.
Both are on one clock, in nanoseconds.

``load`` turns the file into plain lists, ``reduce`` turns those into
numbers; the tests feed ``reduce`` synthetic lists.
"""

import glob
import os
import statistics

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
NO_SPAN = "(no harness span)"


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def load(path):
    """``{"devices": {plane: {"ops": [...], "modules": [...]}},
    "host_spans": [...]}``, every event a ``(name, start_ns, duration_ns)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [(short(ev.name), ev.start_ns, ev.duration_ns)
                      for ev in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += [(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events
                               if ev.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "host_spans": host_spans}


def short(name):
    """An operation's event is named by its whole HLO line (``%fusion.1 =
    f32[...] fusion(...)``): keep the instruction's name."""
    return name[1:].split(" ", 1)[0] if name.startswith("%") else name


def is_collective(name):
    return name.startswith(COLLECTIVES)


def merge(intervals):
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events):
    """Each instant of the instruction stream belongs to the innermost
    operation running then (a ``checkpoint.N`` or ``while`` contains its
    body's operations). Returns ``[(name, start, end)]`` pieces that do not
    overlap."""
    pieces, stack = [], []   # stack of [name, end, cursor]

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, cursor = stack.pop()
            if end > cursor:
                pieces.append((name, cursor, end))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            if start > stack[-1][2]:
                pieces.append((stack[-1][0], stack[-1][2], start))
            stack[-1][2] = max(stack[-1][2], start)
        stack.append([name, start + dur, start])
    close(float("inf"))
    return pieces


def innermost_span(spans, t):
    """Name of the shortest harness span that covers time t."""
    best = None
    for name, start, dur in spans:
        if start <= t <= start + dur and (best is None or dur < best[1]):
            best = (name, dur)
    return best[0] if best else NO_SPAN


def top(totals, n=10):
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(loaded):
    """Numbers of the traced window, averaged over the chips in it. Seconds
    throughout. ``None`` if no operation ran on a device."""
    per_chip = []
    for plane, lines in sorted(loaded["devices"].items()):
        ops = lines["ops"]
        if not ops:
            continue
        t0 = min(s for _, s, _ in ops)
        t1 = max(s + d for _, s, d in ops)
        busy = merge((s, s + d) for _, s, d in ops)
        op_s, coll_s = {}, 0.0
        for name, s, e in self_times(ops):
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
            if is_collective(name):
                coll_s += (e - s) * 1e-9
        gaps = {}
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            span = innermost_span(loaded["host_spans"], (e0 + s1) / 2)
            gaps[span] = gaps.get(span, 0.0) + (s1 - e0) * 1e-9
        runs = {}
        for name, _, d in lines["modules"]:
            runs.setdefault(name.split("(")[0], []).append(d * 1e-9)
        # a program in flight when the trace began shows cut short, so a
        # program's time for one run is the median of its runs
        modules = {name: {"runs": len(d), "seconds": sum(d),
                          "median_s": statistics.median(d)}
                   for name, d in runs.items()}
        per_chip.append({
            "window_s": (t1 - t0) * 1e-9,
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "collective_exposed_s": coll_s,
            "op_s": op_s, "gap_s": gaps, "modules": modules})
    if not per_chip:
        return None
    n = len(per_chip)
    mean = lambda key: sum(c[key] for c in per_chip) / n
    first = per_chip[0]   # names and spans: the first chip speaks for all
    return {
        "chips": n,
        "window_s": mean("window_s"),
        "busy_s": mean("busy_s"),
        "collective_exposed_s": mean("collective_exposed_s"),
        "has_collectives": any(is_collective(k) for k in first["op_s"]),
        "modules": first["modules"],
        "device_ops": top(first["op_s"]),
        "idle_gaps": top(first["gap_s"]),
    }


def idle_pct(reduced):
    """Share of the traced window in which no operation ran on the device."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
