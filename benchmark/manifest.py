"""BENCHMARK.json and the files it names: found by name, never by edit.

A cell names a configuration and a traffic mix; a configuration names its
file (and in it, its driver); a traffic mix is `traffic/<name>.json`; a
per-layer metric is `layer_metrics/<name>.py`. A later PR adds entries and
files and edits none. Every function takes the `root` of the checkout it
is to read (the default is this one), so a test can rehearse an addition
on a copy.
"""
import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(Exception):
    pass


def load(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _one(entries, name, what):
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise ManifestError(f"{what} {name!r}: {len(hits)} entries in "
                            "BENCHMARK.json")
    return hits[0]


def cell(man: dict, name: str) -> dict:
    return _one(man["workloads"], name, "workload")


def config(man: dict, cell_: dict, root: Path = ROOT) -> dict:
    entry = _one(man["configs"], cell_["config"], "config")
    return json.loads((root / entry["file"]).read_text())


def traffic_path(name: str, root: Path = ROOT) -> Path:
    return root / HERE.name / "traffic" / f"{name}.json"


def traffic(cell_: dict, root: Path = ROOT) -> dict:
    return json.loads(traffic_path(cell_["traffic"], root).read_text())


def metrics_of(man: dict, kind: str, cell_name: str) -> list:
    """The cell's metrics of `kind` ("end_to_end" or "per_layer"): those
    that list it under `workloads`, and those with no such key."""
    return [m for m in man[kind]
            if cell_name in m.get("workloads", [cell_name])]


def reader_path(name: str, root: Path = ROOT) -> Path:
    return root / HERE.name / "layer_metrics" / f"{name}.py"


def reader(name: str, root: Path = ROOT):
    """The per-layer metric's module: LAYER, UNIT, MOVES, SOURCE and
    `read(obs) -> float | None` (None: nothing to read in this run)."""
    path = reader_path(name, root)
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + re.sub(r"\W", "_", name), path)
    if spec is None or not path.exists():
        raise ManifestError(f"per-layer metric {name!r} has no {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def problems(man: dict, root: Path = ROOT) -> list:
    """Everything that does not resolve or breaks the contract's naming
    rules; empty for a sound manifest. The tests hold it empty."""
    bad = []
    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {c["name"]: c for c in man["workloads"]}
    names = ([m["name"] for m in man["end_to_end"] + man["per_layer"]]
             + list(cells) + [c["name"] for c in man["configs"]])
    for n in names + [c["traffic"] for c in cells.values()]:
        if not NAME_RE.match(n):
            bad.append(f"name {n!r} has characters a name may not")
    for m in man["end_to_end"] + man["per_layer"]:
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"unit {m['unit']!r} of {m['name']}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"better {m['better']!r} of {m['name']}")
        if m["source"] not in SOURCES:
            bad.append(f"source {m['source']!r} of {m['name']}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']} lists unknown workload {w!r}")
    for m in man["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {m['name']} takes {m['source']}")
        if not 0.01 <= m["bound"] <= 0.25:
            bad.append(f"bound {m['bound']} of {m['name']}")
    for dup in {n for n in names if names.count(n) > 1}:
        bad.append(f"name {dup!r} is used twice")
    used = set()
    for c in cells.values():
        used.add(c["config"])
        try:
            cfg = config(man, c, root)
            if cfg.get("chips") != c["chips"]:
                bad.append(f"{c['name']}: chips {c['chips']} but its "
                           f"configuration says {cfg.get('chips')}")
        except (ManifestError, OSError, ValueError) as e:
            bad.append(f"{c['name']}: configuration: {e}")
        if not traffic_path(c["traffic"], root).exists():
            bad.append(f"{c['name']}: no {traffic_path(c['traffic'], root)}")
        mine = metrics_of(man, "end_to_end", c["name"])
        if "setup_s" not in [m["name"] for m in mine] or len(mine) < 2:
            bad.append(f"{c['name']}: needs setup_s and one more "
                       "end-to-end metric")
        if not metrics_of(man, "per_layer", c["name"]):
            bad.append(f"{c['name']}: no per-layer metric")
        if c["chips"] not in (1, 4) or not 0 < len(c["why"]) <= 200:
            bad.append(f"{c['name']}: chips or why out of range")
    for c in man["configs"]:
        if c["name"] not in used:
            bad.append(f"config {c['name']} is used by no cell")
        if not any(c["file"].startswith(p + "/") for p in man["paths"]):
            bad.append(f"config file {c['file']} lies outside paths")
    pairs = [(c["config"], c["traffic"]) for c in cells.values()]
    for pair in {p for p in pairs if pairs.count(p) > 1}:
        bad.append(f"configuration and traffic {pair} make two cells")
    if sum(c["chips"] == 4 for c in cells.values()) > max(1, len(cells) // 2):
        bad.append("more than half of the cells ask for 4 chips")
    for m in man["per_layer"]:
        if m["moves"] not in e2e:
            bad.append(f"{m['name']} moves unknown {m['moves']!r}")
            continue
        try:
            mod = reader(m["name"], root)
        except (ManifestError, OSError, SyntaxError) as e:
            bad.append(str(e))
            continue
        for key, attr in (("layer", "LAYER"), ("unit", "UNIT"),
                          ("moves", "MOVES"), ("source", "SOURCE")):
            if getattr(mod, attr, None) != m[key]:
                bad.append(f"{m['name']}: {attr} in its file is "
                           f"{getattr(mod, attr, None)!r}, the manifest "
                           f"says {m[key]!r}")
        if not callable(getattr(mod, "read", None)):
            bad.append(f"{m['name']}: its file has no read(obs)")
        for w in m.get("workloads", list(cells)):
            moved = [x["name"] for x in
                     metrics_of(man, "end_to_end", w)] if w in cells else []
            if m["moves"] not in moved:
                bad.append(f"{m['name']} moves {m['moves']}, which cell "
                           f"{w} does not report")
    return bad
