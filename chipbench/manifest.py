"""``BENCHMARK.json`` and the data files it names: loading, the contract's
rules as a check that runs before anything touches a chip, and the view of
one cell that ``run.py`` works from.

Found by name, so that a later PR adds files and entries and edits none:
``<configs[].file>``, ``chipbench/workloads/<cell>.json``,
``chipbench/layer_metrics/<metric>.json`` and, for a metric whose reader is
not yet there, ``chipbench/layer_readers/<fn>.py`` (``readers.find``).
"""
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}
SOURCES = {"end_to_end": {"host_clock", "device_trace"},
           "per_layer": {"host_clock", "device_trace", "program_span",
                         "program_counter"}}
# `reduced` may never name a width
WIDTH = re.compile(r"(_dim|_rank|hidden|intermediate|latent|state|proj|"
                   r"head_size|d_ff|d_model|units|expansion|"
                   r"experts_per_tok)", re.I)


def load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def workload_file(cell):
    return f"chipbench/workloads/{cell}.json"


def metric_file(metric):
    return f"chipbench/layer_metrics/{metric}.json"


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def _cells_of(metric, cells):
    return metric.get("workloads", cells)


def validate(m, root):
    """Every breach of the contract found, as a list of sentences."""
    err = []
    if len(json.dumps(m)) > 64 * 1024:
        err.append("BENCHMARK.json is over 64 KiB")
    if set(m) != TOP:
        return err + [f"top-level keys {sorted(m)} are not {sorted(TOP)}"]
    paths = m["paths"]
    if not (1 <= len(paths) <= 16) or not all(
            isinstance(p, str) and PATH.match(p) and not p.startswith("/")
            and ".." not in p.split("/") for p in paths):
        err.append(f"paths {paths}: 1 to 16 relative directories")

    def under_paths(rel):
        return any(rel.startswith(p.rstrip("/") + "/") for p in paths)
    cmd = m["command"]
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_text(w) for w in cmd)):
        err.append("command: a list of 1 to 32 one-line strings")
    for w in cmd:
        if w.startswith("/") or ".." in w.split("/") or (
                os.path.exists(os.path.join(root, w)) and not under_paths(w)):
            err.append(f"command word {w!r} leaves the benchmark's paths")
    if not (isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51):
        err.append("run_seconds: a whole number from 1 to 51")
    for section, limit in (("configs", 24), ("workloads", 24),
                           ("end_to_end", 16), ("per_layer", 128)):
        rows = m[section]
        if not 1 <= len(rows) <= limit:
            err.append(f"{section}: 1 to {limit} entries, not {len(rows)}")
        for r in rows:
            extra = set(r) - KEYS[section] - (
                {"workloads"} if section in SOURCES else set())
            if extra or KEYS[section] - set(r):
                err.append(f"{section} {r.get('name')}: keys {sorted(r)}")
            if not NAME.match(str(r.get("name", ""))):
                err.append(f"{section}: name {r.get('name')!r} is illegal")
    for section in ("configs", "workloads"):
        names = [r["name"] for r in m[section]]
        if len(set(names)) != len(names):
            err.append(f"{section}: a name appears twice")
    metrics = [r["name"] for r in m["end_to_end"] + m["per_layer"]]
    if len(set(metrics)) != len(metrics):
        err.append("two metrics have the same name")
    if err:
        return err

    cells = [w["name"] for w in m["workloads"]]
    configs = {c["name"]: c for c in m["configs"]}
    files = [c["file"] for c in m["configs"]]
    if len(set(files)) != len(files):
        err.append("two configurations share a file")
    for c in m["configs"]:
        if not _text(c["source"]) or not _text(c["why"]):
            err.append(f"config {c['name']}: source and why are one line of "
                       f"1 to 200 characters")
        if not (PATH.match(c["file"]) and under_paths(c["file"])
                and os.path.isfile(os.path.join(root, c["file"]))):
            err.append(f"config {c['name']}: file {c['file']} not found "
                       f"under paths")
        if not (isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
                and all(NAME.match(k) and not WIDTH.search(k)
                        for k in c["reduced"])):
            err.append(f"config {c['name']}: reduced {c['reduced']} names a "
                       f"width or an illegal key")
        if not any(w["config"] == c["name"] for w in m["workloads"]):
            err.append(f"config {c['name']} is used by no cell")
    pairs = set()
    for w in m["workloads"]:
        if w["config"] not in configs:
            err.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME.match(w["traffic"]) or not _text(w["why"]):
            err.append(f"cell {w['name']}: traffic is a name, why one line")
        if w["chips"] not in (1, 4):
            err.append(f"cell {w['name']}: chips is 1 or 4")
        if (w["config"], w["traffic"]) in pairs:
            err.append(f"cell {w['name']}: its pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if not os.path.isfile(os.path.join(root, workload_file(w["name"]))):
            err.append(f"cell {w['name']}: no {workload_file(w['name'])}")
    four = sum(w["chips"] == 4 for w in m["workloads"])
    if four > max(1, len(cells) // 4):
        err.append(f"{four} four-chip cells of {len(cells)}: over 25%")

    e2e = {r["name"]: r for r in m["end_to_end"]}
    if "setup_s" not in e2e:
        err.append("end_to_end lacks setup_s")
    for section in SOURCES:
        for r in m[section]:
            if not UNIT.match(str(r["unit"])):
                err.append(f"{r['name']}: unit {r['unit']!r} is illegal")
            if r["better"] not in ("lower", "higher"):
                err.append(f"{r['name']}: better is lower or higher")
            if r["source"] not in SOURCES[section]:
                err.append(f"{r['name']}: source {r['source']!r}")
            unknown = set(_cells_of(r, cells)) - set(cells)
            if unknown or not _cells_of(r, cells):
                err.append(f"{r['name']}: unknown or no cells {unknown}")
    for r in m["end_to_end"]:
        if not (isinstance(r["bound"], float) and 0 < r["bound"] <= 0.1):
            err.append(f"{r['name']}: bound {r['bound']} not in (0, 0.1]")
    from chipbench import readers
    for r in m["per_layer"]:
        if not NAME.match(str(r["layer"])):
            err.append(f"{r['name']}: layer {r['layer']!r} is not a name")
        target = e2e.get(r["moves"])
        if target is None:
            err.append(f"{r['name']}: moves unknown metric {r['moves']}")
        elif set(_cells_of(r, cells)) - set(_cells_of(target, cells)):
            err.append(f"{r['name']}: moves {r['moves']}, which some of its "
                       f"cells do not report")
        try:
            fn = load_json(root, metric_file(r["name"]))["reader"]["fn"]
            if readers.find(fn) is None:
                err.append(f"{r['name']}: no reader {fn!r}")
        except (OSError, KeyError, ValueError) as e:
            err.append(f"{r['name']}: {metric_file(r['name'])}: {e!r}")
    for c in cells:
        mine = [r["name"] for r in m["end_to_end"] if c in _cells_of(r, cells)]
        if "setup_s" not in mine or len(mine) < 2:
            err.append(f"cell {c} reports {mine}: setup_s and one more needed")
        if not any(c in _cells_of(r, cells) for r in m["per_layer"]):
            err.append(f"cell {c} has no per-layer metric")
    for p in paths:
        for d, dirs, names in os.walk(os.path.join(root, p)):
            dirs[:] = [x for x in dirs if x not in ("__pycache__", ".out")]
            for n in names:
                rel = os.path.relpath(os.path.join(d, n), root)
                if not PATH.match(rel):
                    err.append(f"file name {rel!r} has illegal characters")
    return err


def cell(m, root, name):
    """One cell as ``run.py`` uses it: its configuration and workload files
    loaded, and the metrics it reports, each per-layer one with its file."""
    entry = next((w for w in m["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in m['workloads']]}")
    cells = [w["name"] for w in m["workloads"]]
    config = next(c for c in m["configs"] if c["name"] == entry["config"])
    return {
        "name": name, "root": root, "chips": entry["chips"],
        "cfg": load_json(root, config["file"]),
        "wl": load_json(root, workload_file(name)),
        "end_to_end": [r for r in m["end_to_end"]
                       if name in _cells_of(r, cells)],
        "per_layer": [dict(r, file=load_json(root, metric_file(r["name"])))
                      for r in m["per_layer"] if name in _cells_of(r, cells)],
    }
