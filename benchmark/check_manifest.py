"""``python3 -m benchmark.check_manifest``: is ``BENCHMARK.json`` one the
driver will take? Refuses what the builder's contract refuses before a
single run, so that no session is lost to a ``manifest_invalid`` again
(PR 22 was: a ``source`` that was not one plain string). Also run at the
start of every ``benchmark.run``. Imports nothing but the standard library.
"""

import json
import os
import re
import sys

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}\Z")
TEXT = re.compile(r"[\x20-\x7e]{1,200}\Z")     # printable ASCII, one line
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
E2E_SOURCES = {"host_clock", "device_trace"}
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
# a width may never be reduced: hidden, intermediate, latent, state and
# projection sizes, head sizes, expansion factors, experts per token
WIDTH = re.compile(
    r"(_dim|_rank)\Z|hidden|intermediate|latent|state|proj|d_model|d_ff|"
    r"ffn|n_embd|n_inner|head_dim|head_size|d_head|d_kv|expansion|expand|"
    r"per_tok|top_k|experts_per", re.I)
MAX_BOUND, MAX_SETUP_BOUND, MIN_BOUND = 0.1, 0.1, 0.01
RUN_SECONDS = (10, 51)
MAX_BYTES = 64 * 1024
# a full check: 2 + 14 runs a cell, run_seconds + 60 s each, 2 x 90 s a
# cell to compile, 1200 s spare, inside 43200 s with the full 24 cells
BUDGET_S, MAX_CELLS = 43200, 24


def non_ascii(text):
    return [c for c in text if ord(c) > 0x7e or (ord(c) < 0x20
                                                 and c not in "\n\r\t")]


def under(path, roots):
    norm = os.path.normpath(path)
    return any(norm == r or norm.startswith(r.rstrip("/") + "/")
               for r in map(os.path.normpath, roots))


def check(root):
    """Every reason the manifest under ``root`` would be refused; empty if
    there is none."""
    bad = []
    say = bad.append
    path = os.path.join(root, "BENCHMARK.json")
    try:
        with open(path, "rb") as f:
            raw = f.read()
        man = json.loads(raw.decode("utf-8"))
    except (OSError, ValueError) as e:
        return [f"cannot read BENCHMARK.json: {e}"]
    if len(raw) > MAX_BYTES:
        say(f"the file has {len(raw)} bytes, more than {MAX_BYTES}")
    if non_ascii(raw.decode("utf-8")):
        say("a character outside printable ASCII")
    if not isinstance(man, dict) or set(man) != TOP_KEYS:
        return bad + [f"top-level keys must be exactly {sorted(TOP_KEYS)}"]

    def text(what, v):
        if not isinstance(v, str) or not TEXT.match(v):
            say(f"{what} must be one string of 1 to 200 printable ASCII "
                f"characters on one line, not {v!r:.60}")

    def name(what, v):
        ok = isinstance(v, str) and NAME.match(v)
        if not ok:
            say(f"{what} {v!r:.70} is not a name (letters, digits, _ . - ; "
                "at most 64; starts with a letter, a digit or _)")
        return ok

    def entries(key, keys, optional=(), lo=1, hi=24):
        rows = man[key]
        if not isinstance(rows, list) or not lo <= len(rows) <= hi:
            say(f"{key} must be a list of {lo} to {hi} entries")
            return []
        good = []
        for r in rows:
            if not isinstance(r, dict) or not (
                    keys <= set(r) <= keys | set(optional)):
                say(f"an entry of {key} must have just the keys "
                    f"{sorted(keys)}{' and may add ' + str(list(optional)) if optional else ''}: {r!r:.80}")
            else:
                good.append(r)
        names = [r["name"] for r in good if name(f"{key} name", r["name"])]
        for n in {n for n in names if names.count(n) > 1}:
            say(f"{key}: the name {n} appears twice")
        return good

    # paths and command
    paths = man["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16:
        say("paths must list 1 to 16 directories")
        paths = []
    for p in paths:
        if not isinstance(p, str) or not PATH.match(p) or p.startswith("/") \
                or ".." in p.split("/"):
            say(f"path {p!r} must be relative, inside the repo, of letters, "
                "digits, _ . - /")
        elif not os.path.isdir(os.path.join(root, p)):
            say(f"path {p} is not a directory")
    paths = [p for p in paths if isinstance(p, str)]
    for p in paths:
        for folder, dirs, names in os.walk(os.path.join(root, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for n in names:
                rel = os.path.relpath(os.path.join(folder, n), root)
                if not PATH.match(rel):
                    say(f"the file name {rel!r} has characters a name "
                        "may not have")
    cmd = man["command"]
    if not isinstance(cmd, list) or not 1 <= len(cmd) <= 32:
        say("command must be a list of 1 to 32 strings")
        cmd = []
    for w in cmd:
        text("a word of command", w)
        if isinstance(w, str):
            if w.startswith("/") or ".." in w.split("/"):
                say(f"command word {w!r} leads out of the repo")
            elif os.path.exists(os.path.join(root, w)) \
                    and not under(w, paths):
                say(f"command names {w}, a file outside paths")
    rs = man["run_seconds"]
    if isinstance(rs, bool) or not isinstance(rs, int) \
            or not RUN_SECONDS[0] <= rs <= RUN_SECONDS[1]:
        say(f"run_seconds must be a whole number from {RUN_SECONDS[0]} to "
            f"{RUN_SECONDS[1]}, not {rs!r}")
    elif (2 + 14 * MAX_CELLS) * (rs + 60) + MAX_CELLS * 180 + 1200 > BUDGET_S:
        say(f"run_seconds {rs} does not fit a full check of {MAX_CELLS} "
            f"cells into {BUDGET_S} s")

    # configurations
    configs = entries("configs", CONFIG_KEYS)
    files = []
    for c in configs:
        text(f"config {c['name']}: source", c["source"])
        text(f"config {c['name']}: why", c["why"])
        f = c["file"]
        if not isinstance(f, str) or not PATH.match(f) \
                or not under(f, paths):
            say(f"config {c['name']}: file {f!r} must lie under paths")
        elif not os.path.isfile(os.path.join(root, f)):
            say(f"config {c['name']}: file {f} not found")
        else:
            files.append(f)
            with open(os.path.join(root, f), encoding="utf-8") as fh:
                body = fh.read()
            if non_ascii(body):
                say(f"{f}: a character outside printable ASCII")
            try:
                json.loads(body)
            except ValueError as e:
                say(f"{f}: not JSON ({e})")
        red = c["reduced"]
        if not isinstance(red, list) or len(red) > 16:
            say(f"config {c['name']}: reduced must list at most 16 keys")
            red = []
        for k in red:
            if name(f"config {c['name']}: reduced key", k) \
                    and WIDTH.search(k):
                say(f"config {c['name']}: reduced names the width {k}; no "
                    "width is ever cut")
    for f in {f for f in files if files.count(f) > 1}:
        say(f"the file {f} serves two configurations")

    # cells
    cells = entries("workloads", CELL_KEYS)
    config_names = {c["name"] for c in configs}
    pairs = []
    for w in cells:
        text(f"cell {w['name']}: why", w["why"])
        if w["config"] not in config_names:
            say(f"cell {w['name']}: no configuration {w['config']!r}")
        if name(f"cell {w['name']}: traffic", w["traffic"]) and not any(
                os.path.isfile(os.path.join(root, p, "traffic",
                                            w["traffic"] + s))
                for p in paths for s in DATA_SUFFIXES):
            say(f"cell {w['name']}: traffic file "
                f"<paths>/traffic/{w['traffic']}.json not found")
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            say(f"cell {w['name']}: chips must be 1 or 4")
        pairs.append((w["config"], w["traffic"]))
    for p in {p for p in pairs if pairs.count(p) > 1}:
        say(f"the pair {p} of configuration and traffic appears twice")
    four = sum(w["chips"] == 4 for w in cells)
    if four > max(1, len(cells) // 4):
        say(f"{four} cells ask for four chips; of {len(cells)} cells at "
            f"most {max(1, len(cells) // 4)} may")
    for c in configs:
        if c["name"] not in {w["config"] for w in cells}:
            say(f"config {c['name']} is used by no cell")
    for p in paths:
        tdir = os.path.join(root, p, "traffic")
        for fn in sorted(os.listdir(tdir)) if os.path.isdir(tdir) else []:
            with open(os.path.join(tdir, fn), encoding="utf-8") as fh:
                if non_ascii(fh.read()):
                    say(f"{p}/traffic/{fn}: a character outside printable "
                        "ASCII")

    # metrics
    cell_names = [w["name"] for w in cells]
    e2e = entries("end_to_end", E2E_KEYS, ("workloads",), hi=16)
    layer = entries("per_layer", LAYER_KEYS, ("workloads",), hi=128)
    both = [m["name"] for m in e2e + layer]
    for n in {n for n in both if both.count(n) > 1}:
        say(f"two metrics are named {n}")

    def where(m):
        cells_of = m.get("workloads", cell_names)
        if not isinstance(cells_of, list) or not cells_of:
            say(f"metric {m['name']}: workloads must list cells")
            return set()
        for n in cells_of:
            if n not in cell_names:
                say(f"metric {m['name']}: no cell {n!r}")
        return set(cells_of)

    for m in e2e + layer:
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            say(f"metric {m['name']}: unit {m['unit']!r:.40} must be 1 to "
                "16 of letters, digits, _ / % . -")
        if m["better"] not in ("lower", "higher"):
            say(f"metric {m['name']}: better must be lower or higher")
    reported = {}
    for m in e2e:
        reported[m["name"]] = where(m)
        if m["source"] not in E2E_SOURCES:
            say(f"metric {m['name']}: an end-to-end metric comes from "
                f"{sorted(E2E_SOURCES)}, not {m['source']!r}")
        b = m["bound"]
        limit = MAX_SETUP_BOUND if m["name"] == "setup_s" else MAX_BOUND
        if isinstance(b, bool) or not isinstance(b, (int, float)):
            say(f"metric {m['name']}: bound {b!r:.40} must be a share of "
                "the median (a number), not an absolute amount")
        elif not MIN_BOUND <= b <= limit:
            say(f"metric {m['name']}: bound {b} must lie in "
                f"[{MIN_BOUND}, {limit}]")
    if "setup_s" not in reported:
        say("end_to_end must hold setup_s")
    elif reported["setup_s"] != set(cell_names):
        say("every cell reports setup_s")
    layer_cells = set()
    for m in layer:
        text(f"metric {m['name']}: layer", m["layer"])
        if m["source"] not in SOURCES:
            say(f"metric {m['name']}: source must be one of "
                f"{sorted(SOURCES)}")
        mine = where(m)
        layer_cells |= mine
        if m["moves"] not in reported:
            say(f"metric {m['name']}: moves {m['moves']!r:.70}, which is "
                "no end-to-end metric")
        elif mine - reported[m["moves"]]:
            say(f"metric {m['name']} is reported in "
                f"{sorted(mine - reported[m['moves']])}, where "
                f"{m['moves']} is not")
        if not (isinstance(m["name"], str) and NAME.match(m["name"])):
            continue
        reader = [f for f in (os.path.join(root, p, "layer_metrics",
                                           m["name"] + ".py") for p in paths)
                  if os.path.isfile(f)]
        if not reader:
            say(f"metric {m['name']}: reader "
                f"<paths>/layer_metrics/{m['name']}.py not found")
            continue
        with open(reader[0], encoding="utf-8") as fh:
            said = dict(re.findall(r'^(NAME|UNIT|LAYER|MOVES) = "(.*)"$',
                                   fh.read(), re.M))
        want = {"NAME": m["name"], "UNIT": m["unit"], "LAYER": m["layer"],
                "MOVES": m["moves"]}
        if said != want:
            say(f"metric {m['name']}: its reader says {said}, the manifest "
                f"{want}")
    for n in cell_names:
        if not any(n in cells_of for k, cells_of in reported.items()
                   if k != "setup_s"):
            say(f"cell {n} reports no end-to-end metric besides setup_s")
        if n not in layer_cells:
            say(f"cell {n} reports no per-layer metric")
    return bad


def main():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    problems = check(root)
    for p in problems:
        print("REFUSED:", p)
    if not problems:
        print("BENCHMARK.json: ok")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
