"""Program sanitizer: static HLO/jaxpr lint for the compiled hot programs.

The collective audit (``collectives.py``) proves wire VOLUME and the schedule
audit proves EXPOSURE; this module statically checks the *other* ways a
compiled program silently goes wrong on TPU, over the same
post-SPMD-partitioning HLO snapshot (``compile_with_partitioned_hlo``) plus
the jaxpr:

- **dtype-leak** — f32 compute (dot/convolution) and f32 collectives in a
  program configured bf16/f16, attributing leaked flops/wire bytes per
  instruction. A whole-model upcast (a lost ``compute_dtype`` cast, an
  optimizer touching activations) shows up as the f32 dot-flops fraction
  jumping, long before a chip run would OOM or slow down.
- **donation** — ENTRY parameters not covered by ``input_output_alias``
  whose (dtype, local shape) matches an un-aliased output: XLA must keep
  BOTH the input and the fresh output buffer live, doubling that tensor's
  HBM residency. Params/optimizer state/KV caches are the bytes that matter.
- **transfer** — host↔device traffic reachable inside the step body:
  infeed/outfeed/send/recv and host-callback custom-calls
  (``xla_python_cpu_callback`` & friends), plus host-memory-space (``S(5)``)
  layouts. One stray ``jax.debug.print`` or ``io_callback`` in a hot program
  serializes every step on a host round-trip.
- **sharding** — post-SPMD fully-replicated ENTRY tensors above a size
  threshold (each chip holds the full array), and large all-gathers at ENTRY
  scope, outside the known gather islands (the while-body layer scans): a
  full-parameter gather that escaped the per-layer schedule.
- **recompile-hazard** (jaxpr-level) — large constants baked into the trace
  (bloat the executable; if the value varies per call, every variation is a
  retrace) and Python int/float/bool leaves in a program's example
  arguments (weak-type flapping between ``1.0`` and ``np.float32(1.0)``
  doubles the jit cache; a host scalar also re-uploads every call).
- **peak-HBM estimate** — a liveness walk over the HLO in program order:
  allocate each result at its definition, free each operand after its last
  use, recurse into called computations (while bodies, reducers) as a
  transient at the call site. An *attributed* estimate (which instruction is
  live at the peak) to compare against ``compiled.memory_analysis()``.

Findings are structured (``rule``, ``severity``, ``message``, ``bytes``,
``flops``, location) and fold into ``audit_lowered``'s report as a
``sanitizer`` section; ``check_budgets`` enforces per-rule budgets from
``tools/collective_budgets.json`` (tier-1 on the tiny training preset and
the serving decode program). ``tools/program_lint.py`` is the CLI.
"""

import re

from .collectives import (
    DTYPE_BYTES,
    KINDS,
    _dot_flops,
    _group_size,
    _nbytes,
    _parse_computations,
)

SEVERITIES = ("info", "warning", "error")
SEVERITY_RANK = {s: i for i, s in enumerate(SEVERITIES)}

# wire accounting shared with parse_collectives_by_dtype (ring algorithms)
_WIRE_FACTOR = {
    "all-gather": lambda b, g, frac: b * frac,
    "reduce-scatter": lambda b, g, frac: b * g * frac,
    "all-reduce": lambda b, g, frac: 2 * b * frac,
    "all-to-all": lambda b, g, frac: b * frac,
    "collective-permute": lambda b, g, frac: b,
}

_COMPUTE_OPS = ("dot", "convolution")

# host-callback / host-placement custom-call targets (CPU and TPU spellings)
_HOST_CALL_RE = re.compile(
    r'custom_call_target="([^"]*(?:callback|MoveToHost|MoveToDevice|'
    r'host_compute|HostExecute)[^"]*)"')
_HOST_SPACE_RE = re.compile(r"\{[\d,]*:\s*S\(5\)\}")  # host memory space
_TRANSFER_OPS = ("infeed", "outfeed", "send", "recv")

# the attention-logits einsum (bqhd,bkhd->bhqk) runs f32 on purpose — softmax
# numerics — in every zoo model, and so do the score and PV products of the
# paged decode kernel (bf16 operands, f32 accumulators, under its named scope
# ``paged_flash_decode``); programs configured bf16 allowlist both so the
# dtype-leak rule flags real upcasts, not this known island
ATTENTION_F32_ALLOW = ("dtype-leak:bqhd,bkhd->bhqk",
                       "dtype-leak:paged_flash_decode")

DEFAULTS = {
    "compute_dtype": "bf16",        # program's configured compute dtype
    "donation_bytes_threshold": 1 << 16,     # 64 KiB: ignore scalar litter
    "donation_error_bytes": 64 << 20,        # >= 64 MiB duplicated -> error
    "replicated_bytes_threshold": 1 << 20,   # 1 MiB per-chip full copy
    "replicated_error_bytes": 256 << 20,
    "entry_gather_bytes_threshold": 1 << 20,
    "const_bytes_threshold": 1 << 20,        # baked-jaxpr-constant floor
    "f32_dot_warn_frac": 0.01,      # one f32 dot >= 1% of dot flops -> warning
    "allow": (),                    # ["rule:substring", ...] demotes to info
}


def finding(rule, severity, message, *, computation=None, instruction=None,
            bytes=0.0, flops=0.0, **extra):
    f = {"rule": rule, "severity": severity, "message": message,
         "bytes": float(bytes), "flops": float(flops)}
    if computation is not None:
        f["computation"] = computation
    if instruction is not None:
        f["instruction"] = instruction
    f.update(extra)
    return f


def _allowed(f, allow):
    """An allowlist entry ``rule:substring`` matches findings of that rule
    whose instruction/computation/message contains the substring."""
    hay = ":".join(str(f.get(k, "")) for k in
                   ("instruction", "computation", "message", "op_name"))
    for entry in allow:
        rule, _, pat = entry.partition(":")
        if rule == f["rule"] and pat in hay:
            return True
    return False


# ---------------------------------------------------------------------------
# HLO structure parsing (entry params, outputs, aliasing)
# ---------------------------------------------------------------------------

_PARAM_RE = re.compile(
    r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\][^ ]*\s+"
    r"parameter\((\d+)\)(.*)")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SHARDING_RE = re.compile(r"sharding=(\{[^,]*?\}|\{[^{}]*\})")


def _is_replicated(sharding):
    """True when a post-SPMD sharding attribute means every device holds the
    full array: ``{replicated}``, or a device tiling whose tile dims are all
    1 with the devices in the trailing replicated dim."""
    if sharding is None:
        return False
    if "replicated}" in sharding and "last_tile" not in sharding:
        return True
    m = re.search(r"devices=\[([\d,]+)\]", sharding)
    if m and "last_tile_dim_replicate" in sharding:
        dims = [int(d) for d in m.group(1).split(",")]
        return all(d == 1 for d in dims[:-1])
    return False


def _entry_region(hlo):
    """The ENTRY computation's lines (between its header and closing brace)."""
    lines = hlo.splitlines()
    out, in_entry = [], False
    for line in lines:
        s = line.strip()
        if s.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if s.startswith("}"):
                break
            out.append(s)
    return out


def parse_entry_params(hlo):
    """ENTRY parameters with their post-SPMD (= per-chip local) shapes:
    ``{index, name, dtype, dims, bytes, sharding, replicated, op_name}``."""
    params = []
    for s in _entry_region(hlo):
        m = _PARAM_RE.match(s)
        if not m:
            continue
        name, dtype, dims, idx, rest = m.groups()
        sh = _SHARDING_RE.search(rest)
        op = _OPNAME_RE.search(rest)
        params.append({
            "index": int(idx), "name": name, "dtype": dtype, "dims": dims,
            "bytes": _nbytes(dtype, dims),
            "sharding": sh.group(1) if sh else None,
            "replicated": _is_replicated(sh.group(1) if sh else None),
            "op_name": op.group(1) if op else None,
        })
    params.sort(key=lambda p: p["index"])
    return params


def parse_entry_outputs(hlo):
    """Output element shapes of the ENTRY ROOT: ``[(dtype, dims), ...]``."""
    for s in _entry_region(hlo):
        if not s.startswith("ROOT"):
            continue
        eq = s.index("=")
        rhs = s[eq + 1:].strip()
        if rhs.startswith("("):
            depth, end = 0, len(rhs)
            for i, ch in enumerate(rhs):
                if ch == "(":
                    depth += 1
                elif ch == ")":
                    depth -= 1
                    if depth == 0:
                        end = i
                        break
            return re.findall(r"(\w+)\[([\d,]*)\]", rhs[:end])
        m = re.match(r"(\w+)\[([\d,]*)\]", rhs)
        return [m.groups()] if m else []
    return []


def parse_input_output_alias(hlo):
    """``{param_index: output_index}`` from the HloModule header's
    ``input_output_alias={ {out}: (param, {sub}, kind), ... }`` attribute."""
    header = ""
    for line in hlo.splitlines():
        if line.lstrip().startswith("HloModule"):
            header = line
            break
    key = "input_output_alias={"
    start = header.find(key)
    if start < 0:
        return {}
    i = start + len(key)
    depth, end = 1, len(header)
    for j in range(i, len(header)):
        if header[j] == "{":
            depth += 1
        elif header[j] == "}":
            depth -= 1
            if depth == 0:
                end = j
                break
    body = header[i:end]
    alias = {}
    for m in re.finditer(r"\{([\d,\s]*)\}:\s*\((\d+)", body):
        out_idx = m.group(1).split(",")[0].strip()
        alias[int(m.group(2))] = int(out_idx) if out_idx else 0
    return alias


def _loop_bodies(hlo):
    return set(re.findall(r"body=%?([\w.\-]+)", hlo))


def _entry_name(hlo):
    m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    return m.group(1) if m else "<entry>"


# ---------------------------------------------------------------------------
# rules
# ---------------------------------------------------------------------------

def rule_dtype_leak(hlo, cfg, loop_trip_count=1):
    """f32 (or f64) compute and collectives in a program configured for a
    narrower dtype. Findings attribute flops (dots/convs) and ring-wire bytes
    (collectives) per instruction; the summary carries the f32 dot-flops
    fraction that the budgets gate."""
    target = cfg["compute_dtype"]
    findings = []
    total_dot_flops = leak_dot_flops = 0.0
    leak_wire = 0.0
    if target in ("f32", "fp32", "float32"):
        wide = ("f64",)
    else:
        wide = ("f32", "f64")
    body_names = _loop_bodies(hlo)
    for comp, instrs in _parse_computations(hlo).items():
        by_name = {i["name"]: i for i in instrs}
        trip = loop_trip_count if comp in body_names else 1
        for i in instrs:
            op = i["opcode"]
            kind = op[:-6] if op.endswith("-start") else op
            if op in _COMPUTE_OPS and i["dtype"] is not None:
                fl = _dot_flops(i, by_name) * trip
                total_dot_flops += fl
                if i["dtype"] in wide:
                    leak_dot_flops += fl
                    opn = _OPNAME_RE.search(i["line"])
                    findings.append(finding(
                        "dtype-leak", "info",
                        f"{i['dtype']} {op} in a {target} program",
                        computation=comp, instruction=i["name"],
                        bytes=_nbytes(i["dtype"], i["dims"]) * trip, flops=fl,
                        op_name=opn.group(1) if opn else None, kind="dot"))
            elif kind in KINDS and i["dtype"] in wide and not \
                    op.endswith("-done"):
                b = _nbytes(i["dtype"], i["dims"])
                g = _group_size(i["line"], 1)
                frac = (g - 1) / g if g > 1 else 1.0
                wire = _WIRE_FACTOR[kind](b, g, frac) * trip
                leak_wire += wire
                findings.append(finding(
                    "dtype-leak", "info",
                    f"{i['dtype']} {kind} wire in a {target} program",
                    computation=comp, instruction=i["name"], bytes=wire,
                    kind="collective"))
    # escalate individually-significant f32 dots: one upcast matmul is a
    # structural leak, not rounding noise
    if total_dot_flops > 0:
        for f in findings:
            if f["flops"] / total_dot_flops >= cfg["f32_dot_warn_frac"]:
                f["severity"] = "warning"
    frac = leak_dot_flops / total_dot_flops if total_dot_flops else 0.0
    return findings, {"f32_dot_flops": leak_dot_flops,
                      "total_dot_flops": total_dot_flops,
                      "f32_dot_flops_frac": frac,
                      "f32_collective_wire_bytes": leak_wire}


def rule_donation(hlo, cfg):
    """ENTRY parameters above the size threshold, not in the module's
    ``input_output_alias`` map, whose (dtype, local dims) matches an output
    element that also has no alias: a donation candidate — the step holds
    input AND output buffers where one would do. ``estimated duplicated
    bytes`` is the sum over candidates (what fixing ``donate_argnums``
    saves in per-chip HBM residency)."""
    params = parse_entry_params(hlo)
    outputs = parse_entry_outputs(hlo)
    alias = parse_input_output_alias(hlo)
    aliased_out = set(alias.values())
    free_outputs = {}
    for idx, (dt, dims) in enumerate(outputs):
        if idx not in aliased_out:
            free_outputs.setdefault((dt, dims), []).append(idx)
    findings = []
    candidate_bytes = aliased_bytes = 0.0
    for p in params:
        if p["index"] in alias:
            aliased_bytes += p["bytes"]
            continue
        slots = free_outputs.get((p["dtype"], p["dims"]))
        if not slots:
            continue
        out_idx = slots.pop(0)  # greedy 1:1 — one output can absorb one input
        if not slots:
            del free_outputs[(p["dtype"], p["dims"])]
        if p["bytes"] < cfg["donation_bytes_threshold"]:
            sev = "info"
        elif p["bytes"] >= cfg["donation_error_bytes"]:
            sev = "error"
        else:
            sev = "warning"
        label = p["op_name"] or p["name"]
        findings.append(finding(
            "donation", sev,
            f"input {label} ({p['dtype']}[{p['dims']}]) is not donated but "
            f"matches un-aliased output #{out_idx} — duplicated HBM "
            f"residency",
            instruction=p["name"], bytes=p["bytes"],
            param_index=p["index"], output_index=out_idx,
            op_name=p["op_name"]))
    cand = sum(f["bytes"] for f in findings
               if f["bytes"] >= cfg["donation_bytes_threshold"])
    return findings, {"undonated_candidate_bytes": cand,
                      "undonated_candidates": len(findings),
                      "aliased_param_bytes": aliased_bytes,
                      "n_aliased_params": len(alias)}


def rule_transfer(hlo):
    """Host↔device traffic inside the program: infeed/outfeed/send/recv
    opcodes, host-callback custom-calls, host-memory-space (S(5)) layouts.
    Always ``error``: one host round-trip serializes every step."""
    findings = []
    for comp, instrs in _parse_computations(hlo).items():
        for i in instrs:
            op = i["opcode"]
            kind = None
            if op.split("-")[0] in _TRANSFER_OPS and not op.endswith("-done"):
                kind = op
            elif op == "custom-call":
                m = _HOST_CALL_RE.search(i["line"])
                if m:
                    kind = f"host callback {m.group(1)}"
            elif _HOST_SPACE_RE.search(i["line"]):
                kind = "host-memory-space tensor"
            if kind:
                findings.append(finding(
                    "transfer", "error",
                    f"{kind} inside the compiled step (host round-trip on "
                    f"the hot path)",
                    computation=comp, instruction=i["name"],
                    bytes=_nbytes(i["dtype"], i["dims"])
                    if i["dtype"] else 0.0))
    return findings, {"transfer_count": len(findings)}


def rule_sharding(hlo, cfg, n_devices):
    """Post-SPMD replication check. Local shapes after partitioning ARE the
    per-chip footprint, so a fully-replicated ENTRY tensor above the
    threshold means every chip holds the whole array. Large all-gathers at
    ENTRY scope (outside the while-body gather islands) are flagged too —
    a full-parameter gather that escaped the per-layer schedule."""
    findings = []
    rep_bytes = 0.0
    for p in parse_entry_params(hlo):
        if not p["replicated"] or p["bytes"] < cfg["replicated_bytes_threshold"]:
            continue
        rep_bytes += p["bytes"]
        sev = "error" if p["bytes"] >= cfg["replicated_error_bytes"] \
            else "warning"
        label = p["op_name"] or p["name"]
        findings.append(finding(
            "sharding", sev,
            f"ENTRY input {label} is fully replicated: each of {n_devices} "
            f"chips holds all {p['bytes'] / 1e6:.1f} MB",
            instruction=p["name"], bytes=p["bytes"], op_name=p["op_name"],
            kind="replicated"))
    entry = _entry_name(hlo)
    bodies = _loop_bodies(hlo)
    entry_gather = 0.0
    for comp, instrs in _parse_computations(hlo).items():
        if comp != entry or comp in bodies:
            continue
        for i in instrs:
            op = i["opcode"]
            if op not in ("all-gather", "all-gather-start") or \
                    i["dtype"] is None:
                continue
            b = _nbytes(i["dtype"], i["dims"])
            if b < cfg["entry_gather_bytes_threshold"]:
                continue
            entry_gather += b
            findings.append(finding(
                "sharding", "warning",
                f"{b / 1e6:.1f} MB all-gather at ENTRY scope, outside the "
                f"per-layer gather islands",
                computation=comp, instruction=i["name"], bytes=b,
                kind="entry-gather"))
    return findings, {"replicated_bytes": rep_bytes,
                      "entry_gather_bytes": entry_gather}


def rule_recompile_hazard(closed_jaxpr=None, example_args=None, cfg=None):
    """jaxpr-level hazards. Large baked constants bloat the executable (and
    every changed value is a full retrace); Python scalar leaves in the
    example arguments flap weak types across the jit cache and re-upload
    from host per call — serving knobs must ride as arrays."""
    cfg = {**DEFAULTS, **(cfg or {})}
    findings = []
    const_bytes = 0.0
    if closed_jaxpr is not None:
        for c in getattr(closed_jaxpr, "consts", ()):
            nbytes = getattr(c, "nbytes", None)
            if nbytes is None:
                shape = getattr(c, "shape", None)
                if shape is None:
                    continue
                n = 1
                for d in shape:
                    n *= int(d)
                try:  # numpy-style dtype names ("float64"), not HLO's "f64"
                    import numpy as _np

                    itemsize = _np.dtype(str(getattr(c, "dtype", "float32"))
                                         ).itemsize
                except (TypeError, ValueError):
                    itemsize = 4
                nbytes = n * itemsize
            if nbytes >= cfg["const_bytes_threshold"]:
                const_bytes += nbytes
                findings.append(finding(
                    "recompile-hazard", "warning",
                    f"{nbytes / 1e6:.1f} MB constant baked into the trace "
                    f"(shape {tuple(getattr(c, 'shape', ()))}): a varying "
                    f"value here retraces the whole program",
                    bytes=nbytes))
    n_scalar = 0
    if example_args is not None:
        import jax

        leaves_paths = jax.tree_util.tree_flatten_with_path(example_args)[0]
        for path, leaf in leaves_paths:
            if isinstance(leaf, (bool, int, float)):
                n_scalar += 1
                findings.append(finding(
                    "recompile-hazard", "warning",
                    f"Python {type(leaf).__name__} argument at "
                    f"{jax.tree_util.keystr(path)}: weak-typed scalar — "
                    f"flaps the jit cache against array-typed calls and "
                    f"re-uploads from host every step; pass a jnp array",
                    arg_path=jax.tree_util.keystr(path)))
    return findings, {"baked_const_bytes": const_bytes,
                      "python_scalar_args": n_scalar}


# ---------------------------------------------------------------------------
# peak-HBM estimator (liveness walk)
# ---------------------------------------------------------------------------

# results that alias/view an operand or are metadata-only: no fresh allocation
_ZERO_ALLOC = {"parameter", "tuple", "get-tuple-element", "bitcast",
               "reshape", "while", "constant", "after-all", "partition-id",
               "replica-id"}
_CALLEE_RE = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation|branch_computations)=\{?%?([\w.\-]+)")


def estimate_peak_hbm(hlo):
    """Liveness walk over the HLO, in program order per computation:
    allocate each instruction's result at its definition, free each operand
    after its last use, and charge a called computation's own peak as a
    transient at the call site (while bodies, reducers, conditionals).

    Approximations, documented: program order stands in for the scheduler's
    order (XLA may rematerialize or reorder), fusion is not modeled (the
    post-SPMD snapshot is pre-fusion, so this over-counts small elementwise
    temporaries), tuples/reshapes/while results are treated as views, and
    donated inputs are still counted on both sides (the donation rule prices
    that separately). Compare against ``compiled.memory_analysis()`` — the
    value here is the ATTRIBUTION: which instruction sits at the peak."""
    comps = _parse_computations(hlo)
    entry = _entry_name(hlo)
    peaks = {}  # computation -> intermediates-only peak bytes
    entry_peak_at = None

    def callees(line):
        return [m for m in _CALLEE_RE.findall(line)]

    # callees appear before callers in HLO dumps; missing ones cost 0
    for comp, instrs in comps.items():
        last_use = {}
        for idx, i in enumerate(instrs):
            for o in i["operands"]:
                last_use[o] = idx
        live = {}
        live_bytes = peak = 0.0
        peak_at = None
        for idx, i in enumerate(instrs):
            b = _nbytes(i["dtype"], i["dims"]) if i["dtype"] else 0.0
            alloc = 0.0 if i["opcode"] in _ZERO_ALLOC else b
            live[i["name"]] = alloc
            live_bytes += alloc
            transient = sum(peaks.get(c, 0.0) for c in callees(i["line"]))
            if live_bytes + transient > peak:
                peak = live_bytes + transient
                peak_at = i["name"]
            for o in set(i["operands"]):
                if last_use.get(o) == idx and o in live:
                    live_bytes -= live.pop(o)
        peaks[comp] = peak
        if comp == entry:
            entry_peak_at = peak_at

    param_bytes = sum(p["bytes"] for p in parse_entry_params(hlo))
    inter = peaks.get(entry, 0.0)
    return {
        "estimate_bytes": param_bytes + inter,
        "argument_bytes": param_bytes,
        "transient_peak_bytes": inter,
        "peak_instruction": entry_peak_at if entry in peaks else None,
    }


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------

def summarize(findings):
    counts = {s: 0 for s in SEVERITIES}
    by_rule = {}
    for f in findings:
        if f.get("allowed"):
            continue
        counts[f["severity"]] += 1
        r = by_rule.setdefault(f["rule"], {"count": 0, "bytes": 0.0,
                                           "flops": 0.0})
        r["count"] += 1
        r["bytes"] += f["bytes"]
        r["flops"] += f["flops"]
    max_sev = "none"
    for s in reversed(SEVERITIES):
        if counts[s]:
            max_sev = s
            break
    return {"counts": counts, "by_rule": by_rule, "max_severity": max_sev,
            "n_findings": sum(counts.values())}


def sanitize_hlo(hlo, config=None, n_devices=1, loop_trip_count=1):
    """Run every HLO-level rule over one post-SPMD program snapshot.

    ``config`` overrides :data:`DEFAULTS` (compute_dtype, thresholds, and an
    ``allow`` list of ``rule:substring`` entries that demote known-intentional
    findings to allowed-info). Returns ``{"findings", "summary", <per-rule
    summaries>, "peak_hbm"}``.
    """
    cfg = {**DEFAULTS, **(config or {})}
    findings = []
    summary = {}
    for fs, st in (rule_dtype_leak(hlo, cfg, loop_trip_count),
                   rule_donation(hlo, cfg),
                   rule_transfer(hlo),
                   rule_sharding(hlo, cfg, n_devices)):
        findings.extend(fs)
        summary.update(st)
    for f in findings:
        if _allowed(f, cfg["allow"]):
            f["allowed"] = True
            f["severity"] = "info"
    # allowed findings drop out of EVERY budgeted aggregate (the rule
    # functions sum before the allowlist applies): an allow entry means
    # "declared intentional — do not gate on it", so only the live findings
    # feed the budget keys below
    live = [f for f in findings if not f.get("allowed")]

    def _live(rule, kind=None, field="bytes"):
        return sum(f[field] for f in live if f["rule"] == rule
                   and (kind is None or f.get("kind") == kind))

    summary["f32_dot_flops"] = _live("dtype-leak", "dot", "flops")
    summary["f32_dot_flops_frac"] = (
        summary["f32_dot_flops"] / summary["total_dot_flops"]
        if summary.get("total_dot_flops") else 0.0)
    summary["f32_collective_wire_bytes"] = _live("dtype-leak", "collective")
    summary["replicated_bytes"] = _live("sharding", "replicated")
    summary["entry_gather_bytes"] = _live("sharding", "entry-gather")
    summary["undonated_candidate_bytes"] = sum(
        f["bytes"] for f in live
        if f["rule"] == "donation"
        and f["bytes"] >= cfg["donation_bytes_threshold"])
    summary["transfer_count"] = sum(
        1 for f in live if f["rule"] == "transfer")
    findings.sort(key=lambda f: (-SEVERITY_RANK[f["severity"]], -f["bytes"]))
    return {
        "findings": findings,
        "summary": {**summary, **summarize(findings)},
        "peak_hbm": estimate_peak_hbm(hlo),
        "config": {k: (list(v) if isinstance(v, tuple) else v)
                   for k, v in cfg.items()},
    }


def sanitize_jaxpr(closed_jaxpr, example_args=None, config=None):
    """jaxpr-level rules only (recompile hazards); merge into an HLO report
    with :func:`merge_reports` or consume standalone."""
    cfg = {**DEFAULTS, **(config or {})}
    findings, stats = rule_recompile_hazard(closed_jaxpr, example_args, cfg)
    for f in findings:
        if _allowed(f, cfg["allow"]):
            f["allowed"] = True
            f["severity"] = "info"
    return {"findings": findings, "summary": {**stats,
                                              **summarize(findings)}}


def merge_reports(hlo_report, jaxpr_report):
    """Fold a jaxpr report into an HLO report (one program, two views)."""
    findings = hlo_report["findings"] + jaxpr_report["findings"]
    findings.sort(key=lambda f: (-SEVERITY_RANK[f["severity"]], -f["bytes"]))
    summary = {**hlo_report["summary"], **{
        k: v for k, v in jaxpr_report["summary"].items()
        if k not in ("counts", "by_rule", "max_severity", "n_findings")}}
    summary.update(summarize(findings))
    return {**hlo_report, "findings": findings, "summary": summary}


def sanitize_lowered(lowered, config=None, n_devices=1, loop_trip_count=1):
    """Compile a jax ``Lowered`` via the pass-dump path and sanitize the
    post-SPMD snapshot (the standalone entry point; ``audit_lowered`` embeds
    the same report as its ``sanitizer`` section)."""
    from .collectives import compile_with_partitioned_hlo

    _, hlo = compile_with_partitioned_hlo(lowered)
    return sanitize_hlo(hlo, config, n_devices, loop_trip_count)


def count_at_or_above(findings, severity):
    """Findings at or above ``severity`` (allowed ones excluded) — the
    ``--fail-on`` gate."""
    floor = SEVERITY_RANK[severity]
    return sum(1 for f in findings
               if not f.get("allowed")
               and SEVERITY_RANK[f["severity"]] >= floor)


def check_sanitizer_budgets(san, budget):
    """Violation strings for one ``sanitizer`` budget sub-dict (see
    tools/collective_budgets.json). Called from ``check_budgets``."""
    v = []
    s = san["summary"]
    if "errors_max" in budget and s["counts"]["error"] > budget["errors_max"]:
        v.append(f"sanitizer: {s['counts']['error']} error-severity findings "
                 f"exceed budget {budget['errors_max']} "
                 f"(first: {_first_msg(san, 'error')})")
    if "warnings_max" in budget and \
            s["counts"]["warning"] > budget["warnings_max"]:
        v.append(f"sanitizer: {s['counts']['warning']} warning findings "
                 f"exceed budget {budget['warnings_max']} "
                 f"(first: {_first_msg(san, 'warning')})")
    if "f32_dot_flops_frac_max" in budget and \
            s.get("f32_dot_flops_frac", 0.0) > budget["f32_dot_flops_frac_max"]:
        v.append(f"sanitizer: f32 dot flops are "
                 f"{s['f32_dot_flops_frac']:.3f} of total, over budget "
                 f"{budget['f32_dot_flops_frac_max']} (dtype leak — a "
                 f"compute_dtype cast went missing?)")
    if "undonated_bytes_max" in budget and \
            s.get("undonated_candidate_bytes", 0.0) > \
            budget["undonated_bytes_max"]:
        v.append(f"sanitizer: {s['undonated_candidate_bytes'] / 1e6:.2f} MB "
                 f"of donation-candidate inputs (budget "
                 f"{budget['undonated_bytes_max'] / 1e6:.2f} MB) — "
                 f"donate_argnums regression doubles that HBM residency")
    if "transfer_count_max" in budget and \
            s.get("transfer_count", 0) > budget["transfer_count_max"]:
        v.append(f"sanitizer: {s['transfer_count']} host transfers inside "
                 f"the step (budget {budget['transfer_count_max']}) — a "
                 f"debug callback left on the hot path?")
    if "replicated_bytes_max" in budget and \
            s.get("replicated_bytes", 0.0) > budget["replicated_bytes_max"]:
        v.append(f"sanitizer: {s['replicated_bytes'] / 1e6:.1f} MB of "
                 f"above-threshold replicated ENTRY tensors (budget "
                 f"{budget['replicated_bytes_max'] / 1e6:.1f} MB)")
    if "entry_gather_bytes_max" in budget and \
            s.get("entry_gather_bytes", 0.0) > budget["entry_gather_bytes_max"]:
        v.append(f"sanitizer: {s['entry_gather_bytes'] / 1e6:.1f} MB of "
                 f"ENTRY-scope all-gathers outside the gather islands "
                 f"(budget {budget['entry_gather_bytes_max'] / 1e6:.1f} MB)")
    if "peak_hbm_gb_max" in budget and \
            san["peak_hbm"]["estimate_bytes"] > budget["peak_hbm_gb_max"] * 1e9:
        v.append(f"sanitizer: estimated peak HBM "
                 f"{san['peak_hbm']['estimate_bytes'] / 1e9:.2f} GB/chip "
                 f"exceeds budget {budget['peak_hbm_gb_max']} GB (liveness "
                 f"estimate, peak at {san['peak_hbm']['peak_instruction']})")
    return v


def _first_msg(san, severity):
    for f in san["findings"]:
        if f["severity"] == severity and not f.get("allowed"):
            return f["message"]
    return "?"
