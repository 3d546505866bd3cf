"""Trace-event analysis: per-request serving metrics and per-step phase
tables, computed from the structured JSONL a ``SpanTracer`` emits.

Shared by ``tools/trace_summary.py`` (the CLI) and the tier-1 tests that
assert trace-derived TTFT/TPOT matches ``ServingMetrics`` — the same
arithmetic must read both, so it lives here rather than in either.
"""

import collections
import json


def load_jsonl(path):
    """Read one trace JSONL file -> list of event dicts (blank lines ok)."""
    events = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                events.append(json.loads(line))
    return events


def request_metrics(events):
    """Per-request TTFT/TPOT from serving lifecycle events.

    Reads the events ``serving/engine.py`` emits: ``request/queued``
    (args: request_id, start — arrival or submit time), ``request/
    first_token`` and ``request/finish`` (args: request_id, n_tokens).
    TTFT = first_token.ts - queued.start (queueing delay counts, same
    contract as ``Request.ttft``); TPOT = (finish.ts - first_token.ts) /
    (n_tokens - 1), None under 2 tokens — same contract as ``Request.tpot``.
    """
    out = {}
    for e in events:
        if not e.get("name", "").startswith("request/"):
            continue
        rid = e.get("args", {}).get("request_id")
        if rid is None:
            continue
        r = out.setdefault(rid, {"ttft": None, "tpot": None, "n_tokens": None,
                                 "finish_reason": None, "shed_reason": None})
        kind = e["name"].split("/", 1)[1]
        if kind == "queued":
            r["_start"] = e["args"].get("start", e["ts"])
        elif kind == "first_token":
            r["_first"] = e["ts"]
        elif kind == "finish":
            r["_finish"] = e["ts"]
            r["n_tokens"] = e["args"].get("n_tokens")
            r["finish_reason"] = e["args"].get("reason")
        elif kind == "shed":
            r["shed_reason"] = e["args"].get("reason")
    for r in out.values():
        first, start = r.pop("_first", None), r.pop("_start", None)
        finish = r.pop("_finish", None)
        if first is not None and start is not None:
            r["ttft"] = first - start
        if finish is not None and first is not None \
                and (r["n_tokens"] or 0) >= 2:
            r["tpot"] = (finish - first) / (r["n_tokens"] - 1)
    return out


def phase_table(events, step_key="step"):
    """Per-step phase durations from span events carrying a ``step`` arg.

    Returns ``(steps, phases)`` where ``steps`` is an ordered dict
    ``{step: {phase: seconds}}`` (durations of same-named spans within a
    step sum — micro-steps fold into their phase) and ``phases`` is the
    ordered list of phase names seen.
    """
    steps = collections.OrderedDict()
    phases = []
    for e in events:
        if e.get("ph") != "X":
            continue
        step = e.get("args", {}).get(step_key)
        if step is None:
            continue
        row = steps.setdefault(step, collections.OrderedDict())
        name = e["name"]
        row[name] = row.get(name, 0.0) + e["dur"]
        if name not in phases:
            phases.append(name)
    return steps, phases


def counters_by_step(events, name):
    """Latest value per step of the ``TraceFileMonitor`` scalar rows
    (``{"name", "value", "step"}``) named ``name``."""
    out = {}
    for e in events:
        if e.get("name") != name:
            continue
        step, value = e.get("step"), e.get("value")
        if step is not None and value is not None:
            out[step] = float(value)
    return out
