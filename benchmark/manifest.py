"""BENCHMARK.json and the data files it names: loading, finding a file by
the name in the manifest, and the character and cross-reference rules that
the yardstick's test holds it to. Everything else is the driver's to check."""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TRAFFIC_EXT = (".json", ".jsonl", ".toml", ".txt", ".csv")


def root_of(path: str | None = None) -> str:
    return os.path.abspath(path or os.path.join(os.path.dirname(__file__), ".."))


def load(root: str | None = None) -> dict:
    with open(os.path.join(root_of(root), "BENCHMARK.json")) as f:
        return json.load(f)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def bench_dir(man: dict, root: str | None = None) -> str:
    return os.path.join(root_of(root), man["paths"][0])


def line_ok(s, lo=1, hi=200) -> bool:
    """1 to 200 printable ASCII characters on one line."""
    return (isinstance(s, str) and lo <= len(s) <= hi
            and all(32 <= ord(c) < 127 for c in s))


def cell(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in man['workloads']]}")


def config_of(man: dict, name: str, root: str | None = None) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return _read(os.path.join(root_of(root), c["file"]))
    raise KeyError(f"no config {name!r}")


def traffic_file(man: dict, traffic: str, root: str | None = None) -> str:
    d = os.path.join(bench_dir(man, root), "traffic")
    for ext in TRAFFIC_EXT:
        p = os.path.join(d, traffic + ext)
        if os.path.exists(p):
            return p
    raise FileNotFoundError(f"no traffic file for {traffic!r} under {d}")


def traffic_of(man: dict, traffic: str, root: str | None = None) -> dict:
    return _read(traffic_file(man, traffic, root))


def layer_metric_file(man: dict, name: str, root: str | None = None) -> str:
    """A metric's reader file: ``layer_metrics/<name>.json``, or, for a
    quantity split by the end-to-end metric it moves (``peak_hbm_gb.gbm``,
    ``peak_hbm_gb.glm``), the one file of the name before its last dot."""
    d = os.path.join(bench_dir(man, root), "layer_metrics")
    for n in (name, name.rpartition(".")[0]):
        if n and os.path.isfile(os.path.join(d, n + ".json")):
            return os.path.join(d, n + ".json")
    raise FileNotFoundError(f"no reader file for metric {name!r} under {d}")


def _reports(man: dict, m: dict, workload: str) -> bool:
    if "workloads" in m:
        return workload in m["workloads"]
    if "moves" in m:    # unlisted: every cell that reports what it moves
        return any(e["name"] == m["moves"] and _reports(man, e, workload)
                   for e in man["end_to_end"])
    return True


def metrics_of(man: dict, workload: str, group: str) -> list:
    """The metrics of ``group`` (end_to_end | per_layer) a cell reports."""
    return [m for m in man[group] if _reports(man, m, workload)]


def check(root: str | None = None) -> list[str]:
    """The character and cross-reference rules broken, as text; an empty
    list is what the yardstick's test wants."""
    root = root_of(root)
    man = load(root)
    bad: list[str] = []
    cells = [w["name"] for w in man["workloads"]]
    e2e = {m["name"]: m for m in man["end_to_end"]}
    names = ([c["name"] for c in man["configs"]] + cells
             + [w["traffic"] for w in man["workloads"]] + list(e2e)
             + [m["name"] for m in man["per_layer"]]
             + [k for c in man["configs"] for k in c["reduced"]])
    bad += [f"name {n!r} breaks the character rules" for n in names
            if not (isinstance(n, str) and NAME.match(n))]
    for c in man["configs"]:
        bad += [f"config {c['name']}: {k} must be 1 to 200 printable ASCII "
                f"characters" for k in ("source", "why") if not line_ok(c[k])]
        try:
            body = _read(os.path.join(root, c["file"]))
        except OSError:
            bad.append(f"config {c['name']}: file {c['file']} is missing")
            continue
        if (body.get("source"), sorted(body.get("reduced", []))) != (
                c["source"], sorted(c["reduced"])):
            bad.append(f"config {c['name']}: the file's source or reduced differs")
    for w in man["workloads"]:
        if not line_ok(w["why"]):
            bad.append(f"workload {w['name']}: why must be 1 to 200 printable "
                       f"ASCII characters")
        if w["config"] not in {c["name"] for c in man["configs"]}:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        try:
            traffic_file(man, w["traffic"], root)
        except FileNotFoundError as e:
            bad.append(f"workload {w['name']}: {e}")
    for m in man["end_to_end"] + man["per_layer"]:
        if not UNIT.match(str(m["unit"])):
            bad.append(f"metric {m['name']}: unit {m['unit']!r}")
        if m["source"] not in SOURCES or m["better"] not in ("lower", "higher"):
            bad.append(f"metric {m['name']}: source or better")
        bad += [f"metric {m['name']}: unknown cell {c}"
                for c in m.get("workloads", []) if c not in cells]
    for m in man["per_layer"]:
        if not line_ok(m["layer"]):
            bad.append(f"per_layer {m['name']}: layer")
        if m["moves"] not in e2e:
            bad.append(f"per_layer {m['name']}: moves {m['moves']!r} is no "
                       f"end-to-end metric")
            continue
        bad += [f"per_layer {m['name']}: cell {c} does not report {m['moves']}"
                for c in cells if _reports(man, m, c)
                and not _reports(man, e2e[m["moves"]], c)]
        try:
            if "reader" not in _read(layer_metric_file(man, m["name"], root)):
                bad.append(f"per_layer {m['name']}: its file names no reader")
        except FileNotFoundError as e:
            bad.append(f"per_layer {m['name']}: {e}")
    return bad
