"""``BENCHMARK.json`` and the files it names, found by name.

A cell is one entry of ``workloads``. Everything that belongs to one
configuration, traffic mix, cell or per-layer metric sits in a file of its
own, at a path made from its name:

* ``bench/configs/<config>.json``: the model and its data, as run;
* ``bench/traffic/<traffic>.json``: the round's shape (strategy, wire,
  fan-out, clients, local steps, batch, Dirichlet alpha, lr, eval cadence);
* ``bench/workloads/<cell>.json``: the limits that decide ``correct`` and
  the readings they were set from;
* ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
* ``bench/families/<family>.py`` and ``<family>_ref.py``: the program
  under test and its plain reference, for a configuration's ``family``;
  ``<family>_flops.py``: the operations its round requires
  (``flops.for_family``; the ``vision`` family's are ``bench/flops.py``).

Adding a cell adds files and entries; it edits none of these.
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def config_path(name: str) -> str:
    return os.path.join(BENCH, "configs", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH, "traffic", f"{name}.json")


def workload_path(name: str) -> str:
    return os.path.join(BENCH, "workloads", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(BENCH, "metrics", f"{name}.py")


def family_path(name: str) -> str:
    return os.path.join(BENCH, "families", f"{name}.py")


class Cell:
    """One workload entry with its configuration, traffic and limits."""

    def __init__(self, bench: Dict, name: str):
        entries = {w["name"]: w for w in bench["workloads"]}
        if name not in entries:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(entries)})")
        self.entry = entries[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        self.config = load_json(config_path(self.entry["config"]))
        self.traffic = load_json(traffic_path(self.entry["traffic"]))
        self.limits = load_json(workload_path(name))["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def problems(bench: Dict) -> List[str]:
    """What in ``BENCHMARK.json`` breaks the naming rules or names a file
    that is not there; empty when all is well."""
    out = []
    names = ([c["name"] for c in bench["configs"]]
             + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
             + [w[k] for w in bench["workloads"] for k in ("config", "traffic")]
             + [k for c in bench["configs"] for k in c["reduced"]])
    out += [f"bad name {n!r}" for n in names if not NAME_RE.match(n)]
    out += [f"bad unit {m['unit']!r} of {m['name']}"
            for m in bench["end_to_end"] + bench["per_layer"]
            if not UNIT_RE.match(m["unit"])]
    for c in bench["configs"]:
        if c["file"] != os.path.relpath(config_path(c["name"]), ROOT):
            out.append(f"config {c['name']}: file {c['file']} is not "
                       f"{os.path.relpath(config_path(c['name']), ROOT)}")
    for w in bench["workloads"]:
        for path in (config_path(w["config"]), traffic_path(w["traffic"]),
                     workload_path(w["name"])):
            if not os.path.exists(path):
                out.append(f"workload {w['name']}: missing {path}")
    for m in bench["per_layer"]:
        if not os.path.exists(metric_path(m["name"])):
            out.append(f"per-layer metric {m['name']}: missing reader")
    for c in bench["configs"]:
        fam = load_json(config_path(c["name"])).get("family", "")
        for path in (family_path(fam), family_path(f"{fam}_ref")):
            if not os.path.exists(path):
                out.append(f"config {c['name']}: missing {path}")
    return out
