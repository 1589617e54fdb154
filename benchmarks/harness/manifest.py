"""``BENCHMARK.json`` and the files it names, loaded and cross-checked.

Whatever belongs to one configuration, one cell or one per-layer metric sits
in a file of its own under one of the manifest's ``paths``:
``<path>/workloads/<cell>.json``, ``<path>/layer_metrics/<metric>.json``,
``<path>/harness/reducers/<reducer>.py`` (or ``<path>/reducers/``) and
``<path>/harness/drivers/<driver>.py`` (or ``<path>/drivers/``); a
configuration's file is the manifest's ``file``.  A later PR adds files and
entries and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class Manifest:
    def __init__(self, path: str | Path | None = None):
        self.path = Path(path) if path else ROOT / "BENCHMARK.json"
        with open(self.path) as f:
            self.raw = json.load(f)
        self.paths = [ROOT / p for p in self.raw["paths"]]
        self.configs = {c["name"]: c for c in self.raw["configs"]}
        self.workloads = {w["name"]: w for w in self.raw["workloads"]}
        self.end_to_end = {m["name"]: m for m in self.raw["end_to_end"]}
        self.per_layer = {m["name"]: m for m in self.raw["per_layer"]}

    # ---- files found by name ------------------------------------------------

    def _find(self, *relative: str) -> Path:
        for base in self.paths:
            for rel in relative:
                p = base / rel
                if p.exists():
                    return p
        raise FileNotFoundError(
            f"none of {relative} under {[str(p) for p in self.paths]}")

    def _json(self, *relative: str) -> dict:
        with open(self._find(*relative)) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        with open(ROOT / self.configs[name]["file"]) as f:
            return json.load(f)

    def workload(self, cell: str) -> dict:
        return self._json(f"workloads/{cell}.json")

    def layer_metric(self, name: str) -> dict:
        return self._json(f"layer_metrics/{name}.json")

    def _module(self, kind: str, name: str):
        if not NAME.match(name):
            raise ValueError(f"bad {kind} name {name!r}")
        path = self._find(f"harness/{kind}/{name}.py", f"{kind}/{name}.py")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks_{kind}_{name.replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reducer(self, name: str):
        return self._module("reducers", name).reduce

    def driver(self, name: str):
        return self._module("drivers", name).run

    # ---- what a cell reports ------------------------------------------------

    def _listed(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def cell_end_to_end(self, cell: str) -> list[str]:
        return [n for n, m in self.end_to_end.items() if self._listed(m, cell)]

    def cell_per_layer(self, cell: str) -> list[str]:
        e2e = set(self.cell_end_to_end(cell))
        return [n for n, m in self.per_layer.items()
                if self._listed(m, cell) and m["moves"] in e2e]

    # ---- cross-checks (the tests run these) ----------------------------------

    def problems(self) -> list[str]:
        bad: list[str] = []
        raw = self.raw
        for key in ("command", "paths", "run_seconds", "configs", "workloads",
                    "end_to_end", "per_layer"):
            if key not in raw:
                bad.append(f"missing key {key}")
        if set(raw) - {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}:
            bad.append("unknown top-level key")
        names: set[str] = set()
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for entry in raw.get(group, []):
                n = entry["name"]
                if not NAME.match(n):
                    bad.append(f"{group}: bad name {n!r}")
                if (group, n) in names:
                    bad.append(f"{group}: duplicate {n!r}")
                names.add((group, n))
        for m in list(self.end_to_end.values()) + list(self.per_layer.values()):
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better must be lower|higher")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: unknown source {m['source']!r}")
            for w in m.get("workloads", []):
                if w not in self.workloads:
                    bad.append(f"{m['name']}: unknown cell {w!r}")
        for m in self.end_to_end.values():
            if not 0 < m["bound"] <= 0.1:
                bad.append(f"{m['name']}: bound outside (0, 0.1]")
            if m["source"] not in ("host_clock", "device_trace"):
                bad.append(f"{m['name']}: end-to-end source")
        if "setup_s" not in self.end_to_end:
            bad.append("no setup_s")
        pairs = set()
        for w in self.workloads.values():
            if w["config"] not in self.configs:
                bad.append(f"{w['name']}: unknown config {w['config']!r}")
            if w["chips"] not in (1, 4):
                bad.append(f"{w['name']}: chips must be 1 or 4")
            if len(w["why"]) > 200 or "\n" in w["why"]:
                bad.append(f"{w['name']}: why too long")
            if (w["config"], w["traffic"]) in pairs:
                bad.append(f"{w['name']}: config/traffic pair repeated")
            pairs.add((w["config"], w["traffic"]))
            e2e = self.cell_end_to_end(w["name"])
            if "setup_s" not in e2e or len(e2e) < 2:
                bad.append(f"{w['name']}: needs setup_s and one more metric")
            if not self.cell_per_layer(w["name"]):
                bad.append(f"{w['name']}: no per-layer metric")
            try:
                cell = self.workload(w["name"])
                if cell["config"] != w["config"]:
                    bad.append(f"{w['name']}: cell file names another config")
                self._find(f"harness/drivers/{cell['driver']}.py",
                           f"drivers/{cell['driver']}.py")
            except (FileNotFoundError, KeyError) as e:
                bad.append(f"{w['name']}: {e}")
        used = {w["config"] for w in self.workloads.values()}
        for c in self.configs.values():
            if c["name"] not in used:
                bad.append(f"config {c['name']} used by no cell")
            if not (ROOT / c["file"]).exists():
                bad.append(f"config {c['name']}: no file {c['file']}")
            elif sorted(self.config(c["name"]).get("reduced", [])) != sorted(c["reduced"]):
                bad.append(f"config {c['name']}: reduced differs from its file")
        four = sum(1 for w in self.workloads.values() if w["chips"] == 4)
        if four > max(1, len(self.workloads) // 4):
            bad.append("too many four-chip cells")
        for m in self.per_layer.values():
            if m["moves"] not in self.end_to_end:
                bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
                continue
            for cell in m.get("workloads", []):
                if cell in self.workloads and \
                        m["moves"] not in self.cell_end_to_end(cell):
                    bad.append(f"{m['name']}: cell {cell} does not report "
                               f"{m['moves']}")
            try:
                spec = self.layer_metric(m["name"])
                for k in ("layer", "unit", "moves"):
                    if spec[k] != m[k]:
                        bad.append(f"{m['name']}: {k} differs from its file")
                self._find(f"harness/reducers/{spec['reducer']}.py",
                           f"reducers/{spec['reducer']}.py")
            except (FileNotFoundError, KeyError) as e:
                bad.append(f"{m['name']}: {e}")
        return bad
