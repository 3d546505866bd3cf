"""HLO collective-bytes accounting (by kind AND payload dtype).

The compiler is the source of truth for wire traffic, the same way it is for
flops (``flops_profiler.py``): every collective in the compiled step is
parsed out of the HLO with its payload dtype, and ring-algorithm wire costs
are attributed per chip per step. Used by:

- ``tools/collective_audit.py`` — the CI gate that keeps fp32 master
  gathers from silently reappearing on the ZeRO-3 hot path;
- ``FlopsProfiler`` (``collectives=True``) — live wire-bytes alongside
  flops;
- ``DeepSpeedEngine.collective_wire_stats`` — monitor events for training
  runs (``comms_logger.enabled``).

Why the post-partitioning snapshot: the CPU backend's float-normalization
pass legalizes bf16 collectives to f32 + converts (CPU has no native bf16),
so the backend-optimized HLO shows fp32 gathers regardless of what the
program pinned. The snapshot taken right after the SPMD partitioner — via
XLA's pass-dump machinery, per-compile — is the platform-independent SPMD
program a TPU receives, with the partitioner's committed wire dtypes.
(int8 payloads survive even the CPU pipeline: integer collectives are not
float-normalized — a useful cross-check.)
"""

import glob
import os
import re
import shutil
import tempfile

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s64": 8, "u64": 8, "s8": 1, "u8": 1, "pred": 1, "s16": 2,
               "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 0.5, "u4": 0.5}

_RESULT_RE = re.compile(r"=\s+(?:\()?(\w+)\[([\d,]*)\]")
_TUPLE_SHAPES_RE = re.compile(r"(\w+)\[([\d,]*)\]")
KINDS = ("all-gather", "reduce-scatter", "all-reduce", "all-to-all",
         "collective-permute")


def _nbytes(dtype, dims):
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * DTYPE_BYTES.get(dtype, 4)


def _result_shape(line, is_start=False):
    """(dtype, dims) of the op's RESULT. Async ``-start`` ops return a tuple
    ``(operand, ..., output)`` — the output (last element) is the
    gathered/reduced result; counting the first would skew all-gather ~N x."""
    if is_start:
        head = line.split("-start(")[0]
        shapes = _TUPLE_SHAPES_RE.findall(head)
        return shapes[-1] if shapes else None
    m = _RESULT_RE.search(line)
    return m.groups() if m else None


_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line, default_n):
    """Ring size of THIS op: the replica-group size from the op's
    ``replica_groups`` attribute, not the global device count. On a
    multi-axis mesh a ZeRO reduce-scatter spans only the ``data`` group —
    charging it the full device product would overreport by the non-data
    mesh factor. Explicit list form ``{{0,1,..},..}`` and iota form
    ``[groups,size]<=[N]`` are both parsed; absent/empty groups mean
    all devices."""
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return default_n


def parse_collectives_by_dtype(hlo, n_devices, loop_trip_count=1):
    """Per-chip wire bytes for each collective kind, split by payload dtype.

    Wire accounting (ring algorithms, per chip, with G = the op's OWN
    replica-group size, falling back to ``n_devices`` when the op carries no
    groups): all-gather receives (G-1)/G of the full result; reduce-scatter
    sends (G-1)/G of the full input (= result x G); all-reduce is RS+AG =
    2 x (G-1)/G x full; all-to-all moves (G-1)/G of its payload;
    collective-permute moves its payload once.

    Ops inside a ``while`` body appear ONCE in the text but run once per
    iteration — multiplied by ``loop_trip_count`` (= n_layers for the layer
    scan; the same static-text trap that broke the r4 autotuner cost model).
    Documented approximation: every while in the audited programs is a layer
    scan (the audit runs with gradient accumulation 1).
    """
    body_names = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    stats = {k: {"count": 0, "wire_bytes": 0.0, "by_dtype": {},
                 "by_computation": {}} for k in KINDS}
    comp = "<entry>"
    for line in hlo.splitlines():
        s = line.strip()
        # computation headers, both HLO text styles: the full signature form
        # `%name (p: ...) -> type {` and the pass-dump compact form `name {`
        if s.endswith("{") and "=" not in s and not s.startswith("ROOT"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*[({]", s)
            if m and m.group(1) not in ("if", "while", "true", "false"):
                comp = m.group(1)
            continue
        for kind in stats:
            if f" {kind}(" in s or f" {kind}-start(" in s:
                shape = _result_shape(s, is_start=f" {kind}-start(" in s)
                if shape is None:
                    break
                dtype, dims = shape
                b = _nbytes(dtype, dims)
                g = _group_size(s, n_devices)
                frac = (g - 1) / g if g > 1 else 1.0
                if kind == "all-gather":
                    wire = b * frac
                elif kind == "reduce-scatter":
                    wire = b * g * frac
                elif kind == "all-reduce":
                    wire = 2 * b * frac
                elif kind == "all-to-all":
                    wire = b * frac
                else:  # collective-permute
                    wire = b
                if comp in body_names:
                    wire *= loop_trip_count
                st = stats[kind]
                st["count"] += 1
                st["wire_bytes"] += wire
                st["by_dtype"][dtype] = st["by_dtype"].get(dtype, 0.0) + wire
                st["by_computation"][comp] = \
                    st["by_computation"].get(comp, 0) + 1
                break
    stats["_loop_body_computations"] = sorted(body_names)
    return stats


# --------------------------------------------------------------------------
# exposed-vs-overlappable schedule audit
# --------------------------------------------------------------------------

_OPCODE_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)\s+([\w\-]+)\(")
_NAME_RE = re.compile(r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_COMPUTE_OPS = ("dot", "convolution")


def _operand_names(line):
    """Operand instruction names of an HLO instruction line. Handles both
    text styles: signature form carries operand shapes
    (``all-gather(bf16[128,64] %x)``), the pass-dump compact form carries
    bare names (``all-gather(q.1), channel_id=1``)."""
    m = _OPCODE_RE.search(line)
    if not m:
        return []
    start = line.index("(", m.start(1))
    depth, end = 0, len(line)
    for i in range(start, len(line)):
        if line[i] == "(":
            depth += 1
        elif line[i] == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    names = []
    for chunk in line[start + 1:end].split(","):
        chunk = chunk.strip()
        if not chunk:
            continue
        tok = chunk.split()[-1]  # "bf16[8,8] %x" -> "%x"; "q.1" -> "q.1"
        tok = tok.lstrip("%")
        # constants / literals ("true", "0.5", "{...}") aren't operands we
        # can resolve; harmless to include — they just miss the symbol table
        names.append(tok)
    return names


def _parse_computations(hlo):
    """HLO text -> {computation: [instr, ...]} where each instr is
    ``{"name", "opcode", "operands", "dtype", "dims"}`` in program order.
    Same header heuristics as ``parse_collectives_by_dtype``."""
    comps = {}
    comp = "<entry>"
    for line in hlo.splitlines():
        s = line.strip()
        if s.endswith("{") and "=" not in s and not s.startswith("ROOT"):
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*[({]", s)
            if m and m.group(1) not in ("if", "while", "true", "false"):
                comp = m.group(1)
            continue
        nm = _NAME_RE.match(s)
        op = _OPCODE_RE.search(s)
        if not (nm and op):
            continue
        opcode = op.group(1)
        shape = _result_shape(s, is_start=opcode.endswith("-start"))
        comps.setdefault(comp, []).append({
            "name": nm.group(1), "opcode": opcode,
            "operands": _operand_names(s),
            "dtype": shape[0] if shape else None,
            "dims": shape[1] if shape else None,
            "line": s,
        })
    return comps


def _elems(dims):
    n = 1
    for d in (dims or "").split(","):
        if d:
            n *= int(d)
    return n


def _dot_flops(instr, by_name):
    """Flops proxy for a dot/conv: ``2 * sqrt(|lhs| * |rhs| * |result|)``
    — exact ``2*M*K*N`` for a plain matmul (overcounts batched dots by
    ``sqrt(B)``, fine for an is-there-compute-to-hide-behind signal). Falls
    back to ``2 * |result|`` when an operand's shape is unknown."""
    res = _elems(instr["dims"])
    ops = [by_name.get(o) for o in instr["operands"][:2]]
    if len(ops) == 2 and all(o is not None and o["dims"] is not None
                             for o in ops):
        import math

        return 2.0 * math.sqrt(
            max(_elems(ops[0]["dims"]), 1) * max(_elems(ops[1]["dims"]), 1)
            * max(res, 1))
    return 2.0 * res


def _reachable(start_names, adjacency):
    """BFS closure over an adjacency dict name -> [names]."""
    seen = set(start_names)
    frontier = list(start_names)
    while frontier:
        nxt = []
        for n in frontier:
            for m in adjacency.get(n, ()):
                if m not in seen:
                    seen.add(m)
                    nxt.append(m)
        frontier = nxt
    return seen


def audit_schedule(hlo, n_devices, loop_trip_count=1):
    """Classify every collective's wire bytes as *exposed* vs
    *overlappable-behind-compute* by walking the post-SPMD HLO dependency
    graph (ROADMAP item 4's instrument).

    Per collective C (sync op, or an async ``-start``/``-done`` pair merged
    into one node): compute ops (dot/convolution) in the same computation
    that are neither ancestors of C's start nor descendants of C's done are
    *independent* — the scheduler MAY run them concurrently with the wire
    transfer. A collective with no independent compute is **exposed**: every
    flop in its computation either feeds it or waits on it, so its wire time
    lands on the critical path no matter how the backend schedules. This is
    a dependence-structure bound, not a schedule simulation: "overlappable"
    means the graph admits overlap (reported with the independent-flops
    headroom), not that the backend achieved it.

    Wire bytes per op use the same ring accounting (+ while-body trip
    multiplication) as ``parse_collectives_by_dtype``.
    """
    body_names = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    comps = _parse_computations(hlo)
    by_kind = {k: {"exposed_bytes": 0.0, "overlappable_bytes": 0.0,
                   "exposed_count": 0, "overlappable_count": 0}
               for k in KINDS}
    ops = []
    for comp, instrs in comps.items():
        by_name = {i["name"]: i for i in instrs}
        consumers = {}
        for i in instrs:
            for o in i["operands"]:
                if o in by_name:
                    consumers.setdefault(o, []).append(i["name"])
        producers = {i["name"]: [o for o in i["operands"] if o in by_name]
                     for i in instrs}
        trip = loop_trip_count if comp in body_names else 1

        for i in instrs:
            kind = i["opcode"][:-6] if i["opcode"].endswith("-start") \
                else i["opcode"]
            if kind not in KINDS:
                continue  # (-done ops land here too: accounted at -start)
            if i["dtype"] is None:
                continue
            b = _nbytes(i["dtype"], i["dims"])
            g = _group_size(i["line"], n_devices)
            frac = (g - 1) / g if g > 1 else 1.0
            if kind == "all-gather":
                wire = b * frac
            elif kind == "reduce-scatter":
                wire = b * g * frac
            elif kind == "all-reduce":
                wire = 2 * b * frac
            elif kind == "all-to-all":
                wire = b * frac
            else:
                wire = b
            wire *= trip

            # merge an async start with its done: the overlap window is
            # everything not upstream of the start nor downstream of the done
            sinks = [i["name"]]
            if i["opcode"].endswith("-start"):
                for j in instrs:
                    if j["opcode"].endswith("-done") \
                            and i["name"] in j["operands"]:
                        sinks.append(j["name"])
                        break
            ancestors = _reachable([i["name"]], producers)
            descendants = _reachable(sinks, consumers)
            blocked = ancestors | descendants
            indep_flops = sum(
                _dot_flops(j, by_name) * trip for j in instrs
                if j["opcode"] in _COMPUTE_OPS and j["name"] not in blocked)
            exposed = indep_flops <= 0.0
            st = by_kind[kind]
            if exposed:
                st["exposed_bytes"] += wire
                st["exposed_count"] += 1
            else:
                st["overlappable_bytes"] += wire
                st["overlappable_count"] += 1
            ops.append({
                "name": i["name"], "computation": comp, "kind": kind,
                "dtype": i["dtype"], "wire_bytes": wire,
                "async": i["opcode"].endswith("-start"),
                "exposed": exposed,
                "independent_compute_flops": indep_flops,
            })

    exposed_total = sum(s["exposed_bytes"] for s in by_kind.values())
    overlap_total = sum(s["overlappable_bytes"] for s in by_kind.values())
    total = exposed_total + overlap_total
    ops.sort(key=lambda o: (not o["exposed"], -o["wire_bytes"]))
    return {
        "by_kind": by_kind,
        "exposed_bytes": exposed_total,
        "overlappable_bytes": overlap_total,
        "exposed_fraction": exposed_total / total if total else 0.0,
        "top_exposed": [o for o in ops if o["exposed"]][:10],
        "n_collectives": len(ops),
    }


def fp32_param_bytes(hlo):
    """Sum of f32 ENTRY-parameter bytes per chip (masters + optimizer
    moments + small replicated leaves). Proves the master-weight discipline:
    sharded fp32 state is ~3 x 4 x P / N bytes, nowhere near the 12 x P a
    replicated layout would show."""
    total = 0.0
    in_entry = False
    for line in hlo.splitlines():
        s = line.strip()
        if s.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            m = re.match(r"%?[\w.\-]+\s*=\s*f32\[([\d,]*)\][^ ]*\s+parameter\(",
                         s)
            if m:
                total += _nbytes("f32", m.group(1))
    return total


def compile_with_partitioned_hlo(lowered):
    """Compile a jax ``Lowered``, also capturing the post-SPMD-partitioning
    / pre-backend-pipeline HLO snapshot via XLA's pass-dump machinery
    (per-compile compiler options — no env fiddling, no global flags).

    Returns ``(compiled, partitioned_hlo_text)``.
    """
    import jax

    def _reset_cache():
        # the cache object is a lazily-initialized global: flipping the
        # config alone does not evict an already-initialized instance
        from jax._src import compilation_cache as _cc

        _cc.reset_cache()

    d = tempfile.mkdtemp(prefix="collective_audit_")
    # a persistent-compile-cache HIT skips the pass pipeline entirely — no
    # dump gets written — so the cache must be hard-off for this one compile
    # (observed: the second audit of an identical program returned no
    # snapshot; compiler_options are NOT part of the cache key). The cache
    # is switched off, not re-pointed: its directory stays whatever
    # JAX_COMPILATION_CACHE_DIR or utils/compile_cache.py chose.
    cache_prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        _reset_cache()
        compiled = lowered.compile(compiler_options={
            "xla_dump_to": d,
            "xla_dump_hlo_pass_re": "spmd-partition.*",
        })
        files = glob.glob(os.path.join(d, "*after_spmd-partitioning*"))
        if not files:
            raise RuntimeError(
                "XLA dumped no after_spmd-partitioning snapshot; cannot "
                "audit wire dtypes")
        # the audited step is by far the largest module in the dump dir
        path = max(files, key=os.path.getsize)
        with open(path) as f:
            text = f.read()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_prev)
        _reset_cache()  # re-initialize on next use
        shutil.rmtree(d, ignore_errors=True)
    return compiled, text


def audit_lowered(lowered, n_devices, loop_trip_count=1,
                  sanitizer_config=None):
    """Compile + parse: the full wire report for one lowered step program,
    including the exposed-vs-overlappable schedule split. Pass
    ``sanitizer_config`` (a dict of ``sanitizer.DEFAULTS`` overrides — at
    minimum ``{"compute_dtype": ...}``) to also run the static program
    sanitizer over the same snapshot and attach its report as a
    ``sanitizer`` section."""
    compiled, hlo = compile_with_partitioned_hlo(lowered)
    stats = parse_collectives_by_dtype(hlo, n_devices, loop_trip_count)
    schedule = audit_schedule(hlo, n_devices, loop_trip_count)
    sanitizer = None
    if sanitizer_config is not None:
        from .sanitizer import sanitize_hlo

        sanitizer = sanitize_hlo(hlo, sanitizer_config, n_devices,
                                 loop_trip_count)
    mem = compiled.memory_analysis()
    body_names = stats.pop("_loop_body_computations")
    total = sum(s["wire_bytes"] for s in stats.values())
    by_dtype = {}
    for s in stats.values():
        for dt, b in s["by_dtype"].items():
            by_dtype[dt] = by_dtype.get(dt, 0.0) + b
    report = {
        "collectives": stats,
        "schedule": schedule,
        "total_wire_bytes": total,
        "total_by_dtype": by_dtype,
        "fp32_param_bytes_per_chip": fp32_param_bytes(hlo),
        "loop_body_computations": body_names,
        "memory_per_chip": {
            "temp": mem.temp_size_in_bytes,
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "alias": mem.alias_size_in_bytes,
        },
        "hlo_bytes": len(hlo),
    }
    if sanitizer is not None:
        report["sanitizer"] = sanitizer
    return report


def check_budgets(report, budget, n_params=None, n_devices=None):
    """Compare a report against one budget entry (a dict from
    ``tools/collective_budgets.json``). Returns human-readable violation
    strings (empty = pass)."""
    v = []
    ag = report["collectives"]["all-gather"]["wire_bytes"]
    if "all_gather_gb_max" in budget and \
            ag > budget["all_gather_gb_max"] * 1e9:
        v.append(f"all-gather wire {ag / 1e9:.2f} GB/chip/step exceeds "
                 f"budget {budget['all_gather_gb_max']} GB")
    if "fp32_all_gather_gb_max" in budget:
        f32 = report["collectives"]["all-gather"]["by_dtype"].get("f32", 0.0)
        if f32 > budget["fp32_all_gather_gb_max"] * 1e9:
            v.append(f"fp32 all-gather wire {f32 / 1e9:.2f} GB/chip/step "
                     f"exceeds budget {budget['fp32_all_gather_gb_max']} GB "
                     f"(fp32 master gathers reintroduced?)")
    if "total_wire_gb_max" in budget and \
            report["total_wire_bytes"] > budget["total_wire_gb_max"] * 1e9:
        v.append(f"total wire {report['total_wire_bytes'] / 1e9:.2f} "
                 f"GB/chip/step exceeds budget {budget['total_wire_gb_max']} "
                 f"GB")
    sched = report.get("schedule")
    if sched is not None:
        if "exposed_gb_max" in budget and \
                sched["exposed_bytes"] > budget["exposed_gb_max"] * 1e9:
            v.append(f"exposed collective wire "
                     f"{sched['exposed_bytes'] / 1e9:.2f} GB/chip/step "
                     f"exceeds budget {budget['exposed_gb_max']} GB (an "
                     f"overlap regression: bytes that used to hide behind "
                     f"compute now sit on the critical path)")
        if "exposed_fraction_max" in budget and \
                sched["exposed_fraction"] > budget["exposed_fraction_max"]:
            v.append(f"exposed fraction {sched['exposed_fraction']:.3f} of "
                     f"collective wire exceeds budget "
                     f"{budget['exposed_fraction_max']} (schedule audit)")
    if "sanitizer" in budget and report.get("sanitizer") is not None:
        from .sanitizer import check_sanitizer_budgets

        v.extend(check_sanitizer_budgets(report["sanitizer"],
                                         budget["sanitizer"]))
    if budget.get("masters_sharded_fp32") and n_params and n_devices:
        # sharded fp32 state (params + adam moments) ~= 3 x 4 x P / N;
        # 10% + 64 MB slack covers replicated small leaves
        bound = 3 * 4 * n_params / n_devices * 1.10 + 64e6
        got = report["fp32_param_bytes_per_chip"]
        if got > bound:
            v.append(f"fp32 argument bytes/chip {got / 1e9:.3f} GB exceed "
                     f"the sharded-master bound {bound / 1e9:.3f} GB — "
                     f"masters look replicated or upcast")
    return v
