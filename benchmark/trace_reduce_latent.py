"""Device time of named regions inside named programs, from a profiler
trace: what the latent-attention expert cell's roofline readers need and
``trace_reduce.reduce`` (the ten largest operations) does not keep. Made
before the trace directory is deleted.

An operation's event on the ``XLA Ops`` line is named by its HLO line
(``%fusion.460 = bf16[...] fusion(...)``), which says nothing of where in
the program it came from (seen on a v5e, jax 0.9.0: no ``op_name`` in the
event or its stats). The COMPILED program's text does: every instruction
carries ``metadata={op_name="jit(decode)/while/body/.../moe_grouped_matmul/
ragged_dot"}``, with the ``jax.named_scope`` names of the program in it. So
a region is: the instructions of a program whose ``op_name`` holds one of the
region's scope names, or whose own name is one the compiler gives
(``scopes_in``), and its device time the time in which
such an instruction is the innermost running one
(``trace_reduce.self_times``) while that program runs (``XLA Modules``).

A program that lacks these scopes (the parent commit) gives empty regions,
and the readers leave their metrics out.
"""

import bisect
import re

from . import trace_reduce

REGIONS = {
    "experts": ("moe_grouped_matmul",),
    "latent_attention": ("latent_attn_absorbed", "latent_view_gather",
                         "latent_row_write"),
    "expanded_attention": ("latent_attn_expanded",),
}
# the TPU compiler turns ``ragged_dot`` into custom calls it names itself
# (``ragged-dot-none``, ``.1``, and ``ragged-dot-metadata`` for the group
# offsets) and gives ``op_name="ragged-dot-none"``: the scope is gone, the
# instruction's own name is the stable one
NAMED = {"experts": ("ragged-dot",)}


INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = (.*)$', re.M)
OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def scopes_in(compiled_text):
    """``{instruction name: region}`` for the instructions of one compiled
    program (``compiled.as_text()``) that lie under a region's scope, or
    bear one of its instruction names."""
    out = {}
    for name, rest in INSTRUCTION.findall(compiled_text):
        found = OP_NAME.search(rest)
        op_name = found.group(1) if found else ""
        for region, scopes in REGIONS.items():
            if any(s in op_name for s in scopes) or any(
                    name.startswith(n) for n in NAMED.get(region, ())):
                out[name] = region
    return out


def load(path):
    """``{"ops": [(instruction name, start_ns, duration_ns)], "modules":
    [(name, start, duration)]}`` of the first device plane."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = [(trace_reduce.short(ev.name), ev.start_ns, ev.duration_ns)
               for ev in lines["XLA Ops"].events]
        modules = [(ev.name.split("(")[0], ev.start_ns, ev.duration_ns)
                   for ev in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        return {"ops": ops, "modules": modules}
    return None


def reduce(loaded, regions_of=None):
    """``{program: {"runs": n, "seconds": device time of its runs,
    "regions": {region: seconds}}}``; seconds throughout. ``regions_of``:
    ``{program: scopes_in(its compiled text)}``."""
    if not loaded:
        return None
    regions_of = regions_of or {}
    modules = sorted(loaded["modules"], key=lambda m: m[1])
    out = {}
    for name, _, dur in modules:
        prog = out.setdefault(name, {"runs": 0, "seconds": 0.0,
                                     "regions": {}})
        prog["runs"] += 1
        prog["seconds"] += dur * 1e-9
    starts = [m[1] for m in modules]
    for name, start, end in trace_reduce.self_times(loaded["ops"]):
        i = bisect.bisect_right(starts, start) - 1
        if i < 0 or start >= modules[i][1] + modules[i][2]:
            continue
        prog = modules[i][0]
        region = regions_of.get(prog, {}).get(name)
        if region is not None:
            regions = out[prog]["regions"]
            regions[region] = regions.get(region, 0.0) + (end - start) * 1e-9
    return out
