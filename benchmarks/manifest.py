"""BENCHMARK.json and the data files it names, loaded and cross-checked.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file of its own, found by the NAME the manifest
gives it:

    workloads/<cell>.json       config, traffic, chips, kind, why
    configs/<config>.json       the sizes as run, source, reduced, assumed
    traffic/<traffic>.json      parameters of the general generators
    layer_metrics/<metric>.json layer, unit, source, moves, reader, params
    candidates/<cell>.json      manifest entries of a cell not admitted yet
    kinds/<kind>.py             a driver kind   (run(ctx) -> RunRecord)
    readers/<reader>.py         a reader        (read(record, params))
    builders/<builder>.py       program adapter of a model family
    reference/<reference>.py    the plain reference of a model family

Adding any of them edits no existing file; BENCHMARK.json gains entries.
"""
from __future__ import annotations

import importlib
import json
import os
import re

ROOT = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(ROOT)
MANIFEST = os.path.join(REPO, "BENCHMARK.json")

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    """BENCHMARK.json or a data file breaks the benchmark's contract."""


def valid_name(name) -> bool:
    return isinstance(name, str) and bool(_NAME.match(name))


def valid_unit(unit) -> bool:
    return isinstance(unit, str) and bool(_UNIT.match(unit))


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def data_file(kind: str, name: str, root: str = ROOT) -> dict:
    """The data file ``<root>/<kind>/<name>.json``."""
    if not valid_name(name):
        raise ManifestError(f"{kind} name {name!r} has characters the "
                            "manifest refuses")
    path = os.path.join(root, kind, name + ".json")
    if not os.path.isfile(path):
        raise ManifestError(f"no {kind} file {path}")
    return _load_json(path)


def plugin(kind: str, name: str):
    """The module ``benchmarks.<kind>.<name>`` (kinds, readers, builders,
    reference), found by name."""
    if not valid_name(name) or "." in name or "-" in name:
        raise ManifestError(f"{kind} module name {name!r} is not a "
                            "python identifier")
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def load_manifest(path: str = MANIFEST) -> dict:
    return _load_json(path)


def with_candidate(manifest: dict, name: str, root: str = ROOT) -> dict:
    """The manifest with the entries of ``candidates/<name>.json`` merged
    in: a cell that is written, rehearsed and tested but not admitted
    yet (its file says why). Used by the tests and by hand; the driver
    reads BENCHMARK.json alone."""
    cand = data_file("candidates", name, root)
    merged = dict(manifest)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        have = {e["name"] for e in manifest[key]}
        merged[key] = [dict(e) for e in manifest[key]] + [
            e for e in cand.get(key, []) if e["name"] not in have]
    # metrics the manifest already has and this cell reports too
    for metric in merged["end_to_end"] + merged["per_layer"]:
        if metric["name"] in cand.get("listed_under", []) \
                and name not in metric.get("workloads", [name]):
            metric["workloads"] = list(metric["workloads"]) + [name]
    return merged


def load_cell(name: str, manifest: dict | None = None,
              root: str = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, resolved from the
    manifest and the data files: the cell, its configuration, its
    traffic, and the end-to-end and per-layer metrics it reports."""
    manifest = load_manifest() if manifest is None else manifest
    entry = next((w for w in manifest["workloads"] if w["name"] == name),
                 None)
    if entry is None:
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in manifest['workloads']]})")
    cell = data_file("workloads", name, root)
    for key in ("config", "traffic", "chips"):
        if cell.get(key) != entry[key]:
            raise ManifestError(
                f"workloads/{name}.json says {key}={cell.get(key)!r}, "
                f"BENCHMARK.json says {entry[key]!r}")
    config = data_file("configs", cell["config"], root)
    traffic = data_file("traffic", cell["traffic"], root)

    def reported(metric):
        return name in metric.get("workloads", [name])

    end_to_end = [m for m in manifest["end_to_end"] if reported(m)]
    per_layer = []
    for m in manifest["per_layer"]:
        if reported(m):
            spec = data_file("layer_metrics", m["name"], root)
            per_layer.append(dict(spec, name=m["name"]))
    return {"name": name, "cell": cell, "config": config,
            "traffic": traffic, "chips": int(cell["chips"]),
            "kind": cell["kind"], "end_to_end": end_to_end,
            "per_layer": per_layer}


def check_manifest(manifest: dict | None = None, root: str = ROOT) -> list:
    """Cross-check the manifest against the data files. Returns the list
    of faults found (empty = sound); the tests assert it is empty."""
    manifest = load_manifest() if manifest is None else manifest
    faults = []

    def fault(msg):
        faults.append(msg)

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        fault(f"top-level keys {sorted(manifest)} != {sorted(want)}")
    cfg_names = [c["name"] for c in manifest["configs"]]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    if "setup_s" not in e2e:
        fault("no setup_s among the end-to-end metrics")
    for group in (cfg_names, list(cells), list(e2e) +
                  [m["name"] for m in manifest["per_layer"]]):
        if len(set(group)) != len(group):
            fault(f"duplicate names in {group}")
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            fault(f"config {c.get('name')}: keys {sorted(c)}")
        if not valid_name(c["name"]):
            fault(f"config name {c['name']!r}")
        path = os.path.join(os.path.dirname(root), c["file"])
        if not os.path.isfile(path):
            fault(f"config file {c['file']} missing")
            continue
        body = _load_json(path)
        if body.get("source") != c["source"]:
            fault(f"config {c['name']}: source differs from its file")
        if sorted(body.get("reduced", [])) != sorted(c["reduced"]):
            fault(f"config {c['name']}: reduced differs from its file")
        if not any(w["config"] == c["name"] for w in cells.values()):
            fault(f"config {c['name']} is used by no cell")
    pairs = set()
    for name, w in cells.items():
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            fault(f"workload {name}: keys {sorted(w)}")
        for key in ("name", "config", "traffic"):
            if not valid_name(w[key]):
                fault(f"workload {name}: {key} {w[key]!r}")
        if w["config"] not in cfg_names:
            fault(f"workload {name}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            fault(f"workload {name}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200:
            fault(f"workload {name}: why has {len(w['why'])} characters")
        if (w["config"], w["traffic"]) in pairs:
            fault(f"workload {name}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        try:
            load_cell(name, manifest, root)
        except (ManifestError, KeyError, OSError) as e:
            fault(f"workload {name}: {e}")
    n4 = sum(w["chips"] == 4 for w in cells.values())
    if n4 > max(1, len(cells) // 4):
        fault(f"{n4} of {len(cells)} cells ask for four chips")

    def cells_of(metric):
        listed = metric.get("workloads")
        if listed is None:
            return set(cells)
        for n in listed:
            if n not in cells:
                fault(f"metric {metric['name']}: unknown cell {n}")
        return set(listed)

    for m in manifest["end_to_end"]:
        extra = set(m) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not {"name", "unit", "better", "bound",
                         "source"} <= set(m):
            fault(f"end-to-end {m.get('name')}: keys {sorted(m)}")
        if m["source"] not in ("host_clock", "device_trace"):
            fault(f"end-to-end {m['name']}: source {m['source']}")
        if not 0 < m["bound"] <= 0.1:
            fault(f"end-to-end {m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        extra = set(m) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not {"name", "unit", "better", "source", "layer",
                         "moves"} <= set(m):
            fault(f"per-layer {m.get('name')}: keys {sorted(m)}")
        if m["source"] not in SOURCES:
            fault(f"per-layer {m['name']}: source {m['source']}")
        moved = e2e.get(m["moves"])
        if moved is None:
            fault(f"per-layer {m['name']}: moves unknown "
                  f"{m['moves']!r}")
        elif not cells_of(m) <= cells_of(moved):
            fault(f"per-layer {m['name']}: reported in "
                  f"{sorted(cells_of(m) - cells_of(moved))} where "
                  f"{m['moves']} is not")
        try:
            spec = data_file("layer_metrics", m["name"], root)
        except ManifestError as e:
            fault(str(e))
            continue
        for key in ("layer", "unit", "source", "moves", "better"):
            if spec.get(key) != m[key]:
                fault(f"per-layer {m['name']}: {key} differs from its "
                      "file")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            fault(f"per-layer {m['name']}: a roofline share is in %")
    for m in list(manifest["end_to_end"]) + list(manifest["per_layer"]):
        if not valid_name(m["name"]):
            fault(f"metric name {m['name']!r}")
        if not valid_unit(m["unit"]):
            fault(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            fault(f"metric {m['name']}: better {m['better']!r}")
    for name in cells:
        got_e2e = [m for m in manifest["end_to_end"]
                   if name in cells_of(m)]
        got_pl = [m for m in manifest["per_layer"] if name in cells_of(m)]
        if not any(m["name"] == "setup_s" for m in got_e2e) \
                or len(got_e2e) < 2 or not got_pl:
            fault(f"cell {name}: needs setup_s, another end-to-end "
                  "metric and a per-layer metric")
    return faults
