"""``BENCHMARK.json`` as the harness reads it, and ``run.py --check``.

The harness has no cell, configuration or metric name written into its
code: it finds a cell's configuration in ``benchmark/configs/<config>.json``
(the path is the manifest's ``file``), its traffic in
``benchmark/traffic/<traffic>.json`` and a per-layer metric's reader in
``benchmark/layer_metrics/<name>.py``.
"""

from __future__ import annotations

import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def cell_of(man: dict, name: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; "
                     f"it has {[w['name'] for w in man['workloads']]}")


def config_of(man: dict, root: str, cell: dict) -> dict:
    for c in man["configs"]:
        if c["name"] == cell["config"]:
            return load_json(root, c["file"])
    raise SystemExit(f"benchmark: cell {cell['name']!r} names no "
                     f"configuration of BENCHMARK.json")


def traffic_path(man: dict, cell: dict) -> str:
    return os.path.join(man["paths"][0], "traffic", cell["traffic"] + ".json")


def metrics_of(man: dict, cell: dict, group: str) -> list:
    """The entries of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in man[group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def check(man: dict, root: str) -> list:
    """Every fault found; empty means the manifest and its files agree."""
    bad = []

    def need(cond, what):
        if not cond:
            bad.append(what)

    e2e = {m["name"]: m for m in man["end_to_end"]}
    cells = {w["name"]: w for w in man["workloads"]}
    configs = {c["name"]: c for c in man["configs"]}
    need("setup_s" in e2e, "end_to_end lacks setup_s")
    for kind, names in (("metric", [m["name"] for m in man["end_to_end"]
                                    + man["per_layer"]]),
                        ("workload", list(cells)),
                        ("config", list(configs))):
        need(len(names) == len(set(names)), f"a {kind} name appears twice")
        for n in names:
            need(NAME.match(n), f"{kind} name {n!r} has a character the "
                                f"driver refuses")
    for m in man["end_to_end"] + man["per_layer"]:
        need(UNIT.match(m["unit"]), f"unit {m['unit']!r} of {m['name']}")
        need(m["better"] in ("lower", "higher"), f"better of {m['name']}")
        need(m["source"] in SOURCES, f"source of {m['name']}")
        for w in m.get("workloads", []):
            need(w in cells, f"{m['name']} lists unknown workload {w!r}")
    for m in man["end_to_end"]:
        need(m["source"] in ("host_clock", "device_trace"),
             f"end-to-end {m['name']} has source {m['source']}")
        need(0 < m["bound"] <= 0.1, f"bound of {m['name']}")
    for c in man["configs"]:
        path = os.path.join(root, c["file"])
        need(os.path.exists(path), f"config file {c['file']} missing")
        need(any(w["config"] == c["name"] for w in man["workloads"]),
             f"config {c['name']} is used by no cell")
        if os.path.exists(path):
            body = load_json(root, c["file"])
            need(body.get("runner") in ("serve", "train"),
                 f"{c['file']}: runner must be serve or train")
            need(body.get("source") == c["source"],
                 f"{c['file']}: source differs from BENCHMARK.json")
            need(sorted(body.get("reduced", [])) == sorted(c["reduced"]),
                 f"{c['file']}: reduced differs from BENCHMARK.json")
    pairs = set()
    for w in man["workloads"]:
        need(w["config"] in configs, f"cell {w['name']}: unknown config")
        need(os.path.exists(os.path.join(root, traffic_path(man, w))),
             f"cell {w['name']}: traffic file "
             f"{traffic_path(man, w)} missing")
        need(w["chips"] in (1, 4), f"cell {w['name']}: chips")
        need(len(w["why"]) <= 200 and "\n" not in w["why"],
             f"cell {w['name']}: why is over 200 characters")
        need((w["config"], w["traffic"]) not in pairs,
             f"cell {w['name']}: its pair of config and traffic repeats")
        pairs.add((w["config"], w["traffic"]))
        mine = [m["name"] for m in metrics_of(man, w, "end_to_end")]
        need("setup_s" in mine and len(mine) >= 2,
             f"cell {w['name']} reports no end-to-end metric but setup_s")
        need(metrics_of(man, w, "per_layer"),
             f"cell {w['name']} reports no per-layer metric")
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    need(four <= max(1, len(man["workloads"]) // 4),
         f"{four} of {len(man['workloads'])} cells ask for 4 chips")
    layers = set()
    for m in man["per_layer"]:
        reader = os.path.join(man["paths"][0], "layer_metrics",
                              m["name"] + ".py")
        need(os.path.exists(os.path.join(root, reader)),
             f"per-layer metric {m['name']} has no reader {reader}")
        need(m.get("layer") and "\n" not in m["layer"],
             f"per-layer metric {m['name']} names no layer")
        layers.add(m.get("layer"))
        need(m.get("moves") in e2e,
             f"{m['name']} moves {m.get('moves')!r}, no end-to-end metric")
        if m.get("moves") in e2e:
            moved = e2e[m["moves"]]
            for w in man["workloads"]:
                if "workloads" in m and w["name"] not in m["workloads"]:
                    continue
                need("workloads" not in moved
                     or w["name"] in moved["workloads"],
                     f"{m['name']} is read in {w['name']}, which does not "
                     f"report {m['moves']}")
    return bad
