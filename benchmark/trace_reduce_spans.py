"""The program's own spans in a profiler trace, beside the harness's: what
``trace_reduce`` does not keep, and what it would take to put each idle gap
of the device down to a phase of the serving step.

The program names its spans ``<cat>/<name>`` (``telemetry/tracer.py``):
``ServingEngine.step()`` is ``serving/step``, with its phases inside it
(``serving/admit``, ``/prefill``, ``/prefill_chunk``, ``/insert``,
``/decode_step``, ``/ahead``, ``/read_back``, ``/book``, ``/upkeep``); the
training engine's read ``train/...``. They land on the host plane beside the
harness's ``bench/...`` spans. The device plane is meant to be on the same
clock, but on a v5e (jax 0.9.0) it came out 0.3-1.6 ms behind, by run: the
reduction moves the device's times by an offset it reads off the trace
(``clock_offset``) before it puts a gap under a host span.

``python3 -m benchmark.trace_reduce_spans <trace dir>`` reduces a trace kept
by ``python3 -m benchmark.run ... --trace 1 --trace-dir <trace dir>``. A
trace without the program's spans (a program older than them) gives the
harness's idle gaps as ``trace_reduce.reduce`` names them, no spans and no
steps, and both per-step numbers None.
"""

import argparse
import bisect
import json
import statistics
import sys

from . import trace_reduce

PREFIXES = ("serving/", "train/")
STEP, READ_BACK = "serving/step", "serving/read_back"
CALL = "PjitFunction("      # jax's host event around a jitted call
EXECUTE = "PJRT_LoadedExecutable_Execute"   # the runtime's launch in it
IDLE_NS = 50_000             # a program that starts on a device idle this long
WINDOW_NS = 200_000          # starts within this after its launch
SEARCH_NS = 10_000_000       # the shifts tried: launch minus start within this


def load(path):
    """``trace_reduce.load`` of the file, ``"program_spans"``: the host
    events named with one of ``PREFIXES``, each ``(name, start_ns,
    duration_ns)``, and ``"launches"``: each call of a jitted function as
    ``(the name of its program on the device, launch_ns)``, the launch being
    the end of the runtime's execute inside the call (``PjitFunction(decode)``
    > ``PJRT_LoadedExecutable_Execute``: ``jit_decode``), else the call's
    end."""
    from jax.profiler import ProfileData

    loaded = trace_reduce.load(path)
    host = [(ev.name, ev.start_ns, ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name == trace_reduce.HOST_PLANE
            for line in plane.lines for ev in line.events]
    loaded["program_spans"] = [e for e in host if e[0].startswith(PREFIXES)]
    executes = sorted((s, s + d) for n, s, d in host if n == EXECUTE)
    starts = [s for s, _ in executes]
    loaded["launches"], outer = [], {}
    for name, s, d in sorted(e for e in host if e[0].startswith(CALL)):
        # a call shows as two nested events of one name: keep the outer
        if name in outer and s + d <= outer[name]:
            continue
        outer[name] = s + d
        i = bisect.bisect_left(starts, s)
        end = executes[i][1] if i < len(executes) \
            and executes[i][0] <= s + d else s + d
        loaded["launches"].append(("jit_" + name[len(CALL):-1], end))
    return loaded


def clock_offset(modules, launches):
    """Nanoseconds to add to the device's times to put them on the host's
    clock. On a v5e (jax 0.9.0) the device plane came out 0.3-1.6 ms
    behind the host's, by run: a program began "before" the host launched
    it, which would put a gap that much early under the host's spans.

    A program launched into an idle device starts right after its launch.
    So take the runs that start after the device has idled ``IDLE_NS``, and
    the shift that puts the most of them within ``WINDOW_NS`` after a launch
    of their program; of the shifts that do as well, the one nearest 0 (a
    program launched at a steady period also pairs with its launch a period
    earlier), and of those the least (no such run then starts before its
    launch). A queued program starts whenever the one before it ends and
    says nothing of the clocks. 0 where fewer than three runs pair (a trace
    without launches)."""
    marks = {}
    for name, t in launches:
        marks.setdefault(name, []).append(t)
    for times in marks.values():
        times.sort()
    fresh, busy_until = [], None
    for start, name, end in sorted((s, n.split("(")[0], s + d)
                                   for n, s, d in modules):
        if busy_until is not None and start - busy_until >= IDLE_NS \
                and name in marks:
            fresh.append((name, start))
        busy_until = end if busy_until is None else max(busy_until, end)

    def score(shift):
        hits = 0
        for name, start in fresh:
            times = marks[name]
            i = bisect.bisect_right(times, start + shift)
            hits += i > 0 and times[i - 1] >= start + shift - WINDOW_NS
        return hits

    shifts = sorted({t - start for name, start in fresh for t in marks[name]
                     if abs(t - start) <= SEARCH_NS})
    best = max(shifts, key=lambda x: (score(x), -abs(x), -x), default=0)
    return best if best and score(best) >= 3 else 0


def innermost_pieces(spans):
    """``[(start, end, name)]`` in time order: at each instant the innermost
    of ``spans`` running then. The spans of one thread nest, so the pieces
    of ``trace_reduce.self_times`` say it."""
    return sorted((s, e, name) for name, s, e in
                  trace_reduce.self_times(spans))


def split(gaps, pieces):
    """Each gap's seconds cut at the pieces' edges: ``{name: seconds}``, the
    time of a gap no piece covers under ``trace_reduce.NO_SPAN``."""
    out, i = {}, 0
    for e0, s1 in gaps:
        while i < len(pieces) and pieces[i][1] <= e0:
            i += 1
        t, j = e0, i
        while t < s1:
            if j < len(pieces) and pieces[j][0] < s1:
                s, e, name = pieces[j]
                if s > t:
                    out[trace_reduce.NO_SPAN] = \
                        out.get(trace_reduce.NO_SPAN, 0.0) + (s - t) * 1e-9
                    t = s
                end = min(e, s1)
                out[name] = out.get(name, 0.0) + (end - t) * 1e-9
                t, j = end, j + 1
            else:
                out[trace_reduce.NO_SPAN] = \
                    out.get(trace_reduce.NO_SPAN, 0.0) + (s1 - t) * 1e-9
                t = s1
    return out


def covered(intervals, lo, hi):
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(min(e, hi) - max(s, lo)
               for s, e in trace_reduce.merge(intervals) if e > lo and s < hi)


def reduce(loaded):
    """On the first chip (whose names speak for all, as in ``trace_reduce``),
    seconds throughout:

    - ``idle_gaps``: the ten largest sums of idle, each gap WHOLE under the
      innermost harness or program span at its midpoint (the rule of
      ``trace_reduce.reduce``, with the program's spans among the names);
    - ``program_spans``: ``{name: {"count", "seconds", "idle_s"}}`` over the
      program spans that lie wholly in the device's window; ``idle_s`` is
      the idle during which the span was the innermost one, each gap cut at
      the spans' edges (a gap from the end of a decode to the next dispatch
      crosses the read-back's tail, the booking, the admission and the
      dispatch, and the midpoint would give all of it to one);
    - ``steps``: ``[duration_s, read_back_s, idle_s]`` for each whole
      ``serving/step`` in the window: its length, its ``serving/read_back``
      spans', and the device's idle inside it;
    - ``steps_uncovered_s``: the part of those steps that no span inside
      them covers;
    - ``clock_offset_s``: what the device's times were moved by
      (``clock_offset``) before any of the above;
    - ``idle_under``: the ten largest sums of idle by the innermost span,
      gaps cut at the spans' edges (harness spans and the time no span
      covers among them);
    - ``idle_s``: all idle in the window, ``idle_to_serving_s`` the part
      inside ``serving/`` spans.

    None if no operation ran on a device."""
    chips = [lines for _, lines in sorted(loaded["devices"].items())
             if lines["ops"]]
    if not chips:
        return None
    shift = clock_offset(chips[0]["modules"], loaded.get("launches", ()))
    ops = [(n, s + shift, d) for n, s, d in chips[0]["ops"]]
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    busy = trace_reduce.merge((s, s + d) for _, s, d in ops)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    program = loaded.get("program_spans", [])
    pieces = innermost_pieces(loaded["host_spans"] + program)
    starts = [s for s, _, _ in pieces]
    midpoint = {}
    for e0, s1 in gaps:
        i = bisect.bisect_right(starts, (e0 + s1) / 2) - 1
        name = pieces[i][2] if i >= 0 and (e0 + s1) / 2 <= pieces[i][1] \
            else trace_reduce.NO_SPAN
        midpoint[name] = midpoint.get(name, 0.0) + (s1 - e0) * 1e-9
    under = split(gaps, pieces)
    gap_ends = [s1 for _, s1 in gaps]

    def idle_in(lo, hi):
        i, total = bisect.bisect_right(gap_ends, lo), 0
        while i < len(gaps) and gaps[i][0] < hi:
            total += min(gaps[i][1], hi) - max(gaps[i][0], lo)
            i += 1
        return total * 1e-9
    inside = [(n, s, d) for n, s, d in program if s >= t0 and s + d <= t1]
    spans = {}
    for name, _, d in inside:
        row = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                      "idle_s": 0.0})
        row["count"] += 1
        row["seconds"] += d * 1e-9
    for name, row in spans.items():
        row["idle_s"] = under.get(name, 0.0)
    steps, uncovered = [], 0.0
    for i, (name, s, d) in enumerate(inside):
        if name != STEP:
            continue
        within = [(n, s2, d2) for j, (n, s2, d2) in enumerate(inside)
                  if j != i and s <= s2 and s2 + d2 <= s + d]
        read_back = sum(d2 for n, _, d2 in within if n == READ_BACK)
        steps.append([d * 1e-9, read_back * 1e-9, idle_in(s, s + d)])
        uncovered += (d - covered([(s2, s2 + d2) for _, s2, d2 in within],
                                  s, s + d)) * 1e-9
    return {"clock_offset_s": shift * 1e-9,
            "idle_gaps": trace_reduce.top(midpoint),
            "idle_under": trace_reduce.top(under),
            "program_spans": spans,
            "steps": steps, "steps_uncovered_s": uncovered,
            "idle_s": sum(s1 - e0 for e0, s1 in gaps) * 1e-9,
            "idle_to_serving_s": sum(v for k, v in under.items()
                                     if k.startswith("serving/"))}


def step_host_ms(reduced):
    """Median over the traced steps of a step's length less its
    ``serving/read_back``: the host's own work in a step."""
    steps = (reduced or {}).get("steps")
    if not steps:
        return None
    return statistics.median(d - r for d, r, _ in steps) * 1e3


def step_device_wait_ms(reduced):
    """The traced steps' idle over their count: what the device waits for
    the host a step."""
    steps = (reduced or {}).get("steps")
    if not steps:
        return None
    return sum(i for _, _, i in steps) / len(steps) * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--json", default=None, help="write the reduction here")
    args = ap.parse_args(argv)
    path = trace_reduce.find_xplane(args.trace_dir)
    if path is None:
        print(f"no *.xplane.pb under {args.trace_dir}", file=sys.stderr)
        return 2
    loaded = load(path)
    whole, spans = trace_reduce.reduce(loaded), reduce(loaded)
    if spans is None:
        print("no operation ran on a device", file=sys.stderr)
        return 2
    steps = spans["steps"]
    out = {"window_s": whole["window_s"], "busy_s": whole["busy_s"],
           "clock_offset_ms": spans["clock_offset_s"] * 1e3,
           "idle_pct": trace_reduce.idle_pct(whole),
           "steps": len(steps),
           "step_s": sum(d for d, _, _ in steps),
           "step_host_ms": step_host_ms(spans),
           "step_device_wait_ms": step_device_wait_ms(spans),
           "steps_idle_s": sum(i for _, _, i in steps),
           "idle_s": spans["idle_s"],
           "idle_to_serving_share": spans["idle_to_serving_s"]
           / spans["idle_s"] if spans["idle_s"] else None,
           "steps_uncovered_share": spans["steps_uncovered_s"]
           / sum(d for d, _, _ in steps) if steps else None,
           "idle_gaps": spans["idle_gaps"],
           "idle_under": spans["idle_under"],
           "program_spans": spans["program_spans"],
           "modules": whole["modules"]}
    text = json.dumps(out, indent=1)
    print(text)
    if args.json:
        with open(args.json, "w") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
