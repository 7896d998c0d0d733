"""``BENCHMARK.json`` and the data files it names."""

from __future__ import annotations

import importlib
import json
import os
import re
from typing import Dict, List

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
# what a configuration's ``adapter`` has to give, by its ``kind``
ADAPTER_DUTIES = {
    "serve_decoder": ("weights", "model", "free"),
    "train_classifier": ("model", "to_program_tree", "from_program_tree"),
}

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def data_file(*parts: str) -> str:
    """``traffic/x.json``, ``metrics/y.json`` ... under ``benchmarks/``."""
    return os.path.join(HERE, *parts)


# -- the seam: what is particular to an architecture is named in the
# configuration's file and lives in modules of its own

def adapter_of(cfg: dict):
    """``adapters/<cfg["adapter"]>.py``: builds the program for this
    architecture, frees it, and maps the reference's tree to it."""
    mod = importlib.import_module("adapters." + cfg["adapter"])
    missing = [f for f in ADAPTER_DUTIES[cfg["kind"]]
               if not callable(getattr(mod, f, None))]
    if missing:
        raise AttributeError(f"adapter {cfg['adapter']!r} of kind "
                             f"{cfg['kind']!r} lacks {missing}")
    return mod


def reference_of(cfg: dict):
    """``reference/<cfg["reference"]>.py``: the plain reference."""
    return importlib.import_module("reference." + cfg["reference"])


def formulas_of(cfg: dict):
    """The module a bare formula name of a metric file resolves in:
    ``cfg["formulas"]`` under ``benchmarks/``, by default ``formulas``."""
    return importlib.import_module(cfg.get("formulas", "formulas"))


def resolve(name: str, table: dict):
    """``name`` from ``table``, or, where it reads ``module:function``,
    that function of a module under ``benchmarks/``: how a later PR
    brings a reader or a formula of its own as a new file."""
    if ":" in name:
        module, attr = name.split(":", 1)
        return getattr(importlib.import_module(module), attr)
    return table[name]


def formula(name: str, cfg: dict):
    """The function ``name`` of the configuration's formulas module, or,
    where it reads ``module:function``, that function of that module."""
    return resolve(name, vars(formulas_of(cfg)))


class Cell:
    """One entry of ``workloads`` with everything it points at."""

    def __init__(self, bench: dict, name: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(has: {sorted(cells)})")
        self.bench = bench
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        cfg_entry = next(c for c in bench["configs"]
                         if c["name"] == self.spec["config"])
        self.config = _load(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = _load(data_file(
            "traffic", self.spec["traffic"] + ".json"))
        limits = data_file("limits", name + ".json")
        self.limits = _load(limits) if os.path.exists(limits) else {}

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self) -> List[dict]:
        return [m for m in self.bench["end_to_end"] if self._mine(m)]

    def per_layer(self) -> List[dict]:
        """This cell's per-layer metrics, each merged with its own file
        under ``metrics/`` (the reader's name and parameters)."""
        out = []
        for m in self.bench["per_layer"]:
            if not self._mine(m):
                continue
            spec = _load(data_file("metrics", m["name"] + ".json"))
            out.append({**spec, **m})
        return out


def check_manifest(bench: dict) -> List[str]:
    """The contract's rules on names, units and references that a
    machine can check; returns the faults found."""
    bad: List[str] = []

    def name_ok(s, what):
        if not isinstance(s, str) or not NAME_RE.match(s):
            bad.append(f"{what}: bad name {s!r}")

    def line_ok(s, what):
        if not isinstance(s, str) or not 1 <= len(s) <= 200 \
                or "\n" in s or "\t" in s:
            bad.append(f"{what}: not 1-200 characters on one line")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(bench) != want:
        bad.append(f"top-level keys {sorted(bench)} != {sorted(want)}")
        return bad
    if not 1 <= bench["run_seconds"] <= 51:
        bad.append("run_seconds outside 1..51")
    for w in bench["command"]:
        line_ok(w, "command")
    cfgs: Dict[str, dict] = {}
    for c in bench["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad.append(f"config {c.get('name')}: keys {sorted(c)}")
        name_ok(c["name"], "config")
        line_ok(c["source"], "config source")
        line_ok(c["why"], "config why")
        for k in c["reduced"]:
            name_ok(k, f"config {c['name']} reduced")
        if not any(c["file"].startswith(p + "/") for p in bench["paths"]):
            bad.append(f"config {c['name']}: file outside paths")
        if not os.path.exists(os.path.join(ROOT, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        else:
            cfg = _load(os.path.join(ROOT, c["file"]))
            if cfg.get("kind") not in ADAPTER_DUTIES:
                bad.append(f"config {c['name']}: kind {cfg.get('kind')!r}")
            for key in ("adapter", "reference"):
                if not isinstance(cfg.get(key), str):
                    bad.append(f"config {c['name']}: names no {key}")
        cfgs[c["name"]] = c
    cells = set()
    pairs = set()
    for w in bench["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad.append(f"workload {w.get('name')}: keys {sorted(w)}")
        for k in ("name", "config", "traffic"):
            name_ok(w[k], f"workload {k}")
        line_ok(w["why"], f"workload {w['name']} why")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in cfgs:
            bad.append(f"workload {w['name']}: unknown config")
        if (w["config"], w["traffic"]) in pairs or w["name"] in cells:
            bad.append(f"workload {w['name']}: listed twice")
        pairs.add((w["config"], w["traffic"]))
        cells.add(w["name"])
        if not os.path.exists(data_file(
                "traffic", w["traffic"] + ".json")):
            bad.append(f"workload {w['name']}: no traffic file")
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    if four > max(1, len(bench["workloads"]) // 4):
        bad.append(f"{four} four-chip cells of {len(bench['workloads'])}")
    for c in cfgs:
        if not any(w["config"] == c for w in bench["workloads"]):
            bad.append(f"config {c}: used by no cell")
    e2e = {}
    names = set()
    for m in bench["end_to_end"]:
        extra = set(m) - {"name", "unit", "better", "bound", "source",
                          "workloads"}
        if extra or not {"name", "unit", "better", "bound",
                         "source"} <= set(m):
            bad.append(f"end_to_end {m.get('name')}: keys {sorted(m)}")
        name_ok(m["name"], "end_to_end")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better={m['better']!r}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: source {m['source']!r}")
        if m["name"] in names:
            bad.append(f"metric {m['name']} listed twice")
        names.add(m["name"])
        e2e[m["name"]] = set(m.get("workloads", cells))
        if not e2e[m["name"]] <= cells:
            bad.append(f"{m['name']}: unknown workloads")
    if "setup_s" not in e2e:
        bad.append("no setup_s")
    for m in bench["per_layer"]:
        extra = set(m) - {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        if extra or not {"name", "unit", "better", "source", "layer",
                         "moves"} <= set(m):
            bad.append(f"per_layer {m.get('name')}: keys {sorted(m)}")
            continue
        name_ok(m["name"], "per_layer")
        line_ok(m["layer"], f"{m['name']} layer")
        if not UNIT_RE.match(m["unit"]):
            bad.append(f"{m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better={m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
        if m["name"] in names:
            bad.append(f"metric {m['name']} listed twice")
        names.add(m["name"])
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown {m['moves']!r}")
            continue
        mine = set(m.get("workloads", e2e[m["moves"]]))
        if not mine <= e2e[m["moves"]]:
            bad.append(f"{m['name']}: cells {sorted(mine - e2e[m['moves']])}"
                       f" do not report {m['moves']}")
        if not os.path.exists(data_file("metrics",
                                        m["name"] + ".json")):
            bad.append(f"{m['name']}: no file under metrics/")
    for cell in cells:
        mine_e = [n for n, ws in e2e.items() if cell in ws]
        if "setup_s" not in mine_e or len(mine_e) < 2:
            bad.append(f"cell {cell}: needs setup_s and one more metric")
        if not any(cell in m.get("workloads", e2e.get(m.get("moves"), ()))
                   for m in bench["per_layer"]):
            bad.append(f"cell {cell}: no per-layer metric")
    if len(json.dumps(bench)) > 64 * 1024:
        bad.append("BENCHMARK.json over 64 KiB")
    return bad
