"""``BENCHMARK.json`` and the files it names, loaded and cross-checked.

Whatever belongs to one configuration, one cell or one per-layer metric sits
in a file of its own under one of the manifest's ``paths``:
``<path>/workloads/<cell>.json``, ``<path>/layer_metrics/<metric>.json``,
``<path>/harness/reducers/<reducer>.py`` (or ``<path>/reducers/``) and
``<path>/harness/drivers/<driver>.py`` (or ``<path>/drivers/``); a
configuration's file is the manifest's ``file``.  A later PR adds files and
entries and edits none.

**Another architecture comes by files too.**  A configuration's ``run``
section may name, each found under ``paths`` like a reducer:

* ``"program"`` (default ``"llama"``): ``<path>/harness/programs/<name>.py``
  (or ``<path>/programs/``), which gives ``model_config(conf, **overrides)``,
  the program's model configuration built from the file's published keys;
* ``"reference"`` (default ``"llama"``): ``<path>/reference/<name>.py``,
  which gives ``reference_numbers(conf, wl, seed, token_batches, *,
  devices=None) -> {"losses", "grad_norms", "delta_norms"}``: the plain
  reference following a training cell's first steps, its norms by
  ``compare.layer_norms`` under the leaf names ``program.canonical`` gives
  the program's (``tools/limit_readings.py`` passes the keywords ``q`` and
  ``precision`` besides, for the lower-precision control).

``drivers/train.py`` calls both and stays the one definition of a training
cell's window and of ``correct``.  The reducers ``mfu``, ``scope_roofline``
and ``flash_roofline`` take an optional ``"counts"`` argument in a metric's
file: ``<path>/harness/counting/<name>.py`` (or ``<path>/counting/``), whose
functions they call by name in place of ``counts.py`` / ``scope_counts.py``.

**A cut configuration states its cut** (``config_problems``).  Every key in
``reduced`` is in the file with the value held here; an object ``published``
gives the source's value of each; ``layout`` is an object with ``deployment``
(what the cut stands for) and ``chips_sharing_a_layer`` (and
``leading_dense_layers`` where the file has no ``first_k_dense_replace``).
No width may be listed, and the floors of the ``model-configs`` guide's
section 4 hold: at least four layers after the leading dense ones, at least
8 of any count of experts, at least an eighth of the vocabulary.
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

    def _module_path(self, kind: str, name: str) -> Path:
        if not NAME.match(name):
            raise ValueError(f"bad {kind} name {name!r}")
        return self._find(f"harness/{kind}/{name}.py", f"{kind}/{name}.py")

    def _module(self, kind: str, name: str):
        path = self._module_path(kind, name)
        spec = importlib.util.spec_from_file_location(
            f"benchmarks_{kind}_{name.replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reducer(self, name: str):
        return self._module("reducers", name).reduce

    def driver(self, name: str):
        return self._module("drivers", name).run

    def program(self, conf: dict):
        """The module that builds the program's model configuration."""
        return self._module("programs", _run_name(conf, "program"))

    def reference(self, conf: dict):
        """The module that holds this configuration's plain reference."""
        return self._module("reference", _run_name(conf, "reference"))

    def counts(self, name: str):
        """A module of counts named by a per-layer metric's file."""
        return self._module("counting", name)

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
                self._module_path("drivers", cell["driver"])
            except (FileNotFoundError, KeyError, ValueError) as e:
                bad.append(f"{w['name']}: {e}")
        used = {w["config"] for w in self.workloads.values()}
        for c in self.configs.values():
            if c["name"] not in used:
                bad.append(f"config {c['name']} used by no cell")
            if not (ROOT / c["file"]).exists():
                bad.append(f"config {c['name']}: no file {c['file']}")
                continue
            conf = self.config(c["name"])
            bad += config_problems(c, conf)
            for kind, key in (("programs", "program"),
                              ("reference", "reference")):
                try:
                    self._module_path(kind, _run_name(conf, key))
                except (FileNotFoundError, ValueError) as e:
                    bad.append(f"config {c['name']}: {key}: {e}")
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
                self._module_path("reducers", spec["reducer"])
                if "counts" in spec.get("args", {}):
                    self._module_path("counting", spec["args"]["counts"])
            except (FileNotFoundError, KeyError, ValueError) as e:
                bad.append(f"{m['name']}: {e}")
        return bad


def _run_name(conf: dict, key: str) -> str:
    """The module a configuration's ``run`` names for ``key``; the Llama one
    where it names none."""
    return conf.get("run", {}).get(key, "llama")


#: what every decoder's configuration file states
CONFIG_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
               "vocab_size", "rms_norm_eps", "source", "reduced", "assumed",
               "layout", "run")
#: what the Llama program and reference read besides
LLAMA_KEYS = ("intermediate_size", "num_key_value_heads", "rope_theta")
_WIDTH = re.compile(r"(_dim|_rank|_size|_width|_factor)$|per_tok|top_k")


def config_problems(entry: dict, conf: dict) -> list[str]:
    """What is wrong with a configuration's file beside its manifest entry:
    a key every decoder has left out, an entry that disagrees with the file,
    or a cut that is hidden, names a width or goes under a floor."""
    name = entry["name"]
    bad = [f"config {name}: no key {k}" for k in CONFIG_KEYS if k not in conf]
    if _run_name(conf, "program") == "llama":
        bad += [f"config {name}: no key {k}" for k in LLAMA_KEYS
                if k not in conf]
    if conf.get("source") != entry["source"]:
        bad.append(f"config {name}: source differs from its file")
    reduced = conf.get("reduced", [])
    if sorted(reduced) != sorted(entry["reduced"]):
        bad.append(f"config {name}: reduced differs from its file")
    if bad or not reduced:
        return bad
    published, layout = conf.get("published", {}), conf["layout"]
    if not (isinstance(layout, dict)
            and isinstance(layout.get("deployment"), str)
            and isinstance(layout.get("chips_sharing_a_layer"), int)
            and layout["chips_sharing_a_layer"] >= 1):
        bad.append(f"config {name}: a cut configuration's layout names its "
                   "deployment and chips_sharing_a_layer")
        layout = {}
    for key in reduced:
        if _WIDTH.search(key) and key != "vocab_size":
            bad.append(f"config {name}: reduced names a width, {key}")
        elif key not in conf:
            bad.append(f"config {name}: reduced key {key} is not in the file")
        elif key not in published or published[key] == conf[key]:
            bad.append(f"config {name}: no published value of {key}")
        elif "expert" in key and conf[key] < 8:
            bad.append(f"config {name}: {key} {conf[key]} is under the floor of 8")
    if "vocab_size" in reduced and "vocab_size" in published \
            and 8 * conf["vocab_size"] < published["vocab_size"]:
        bad.append(f"config {name}: vocab_size is under an eighth of the "
                   "published vocabulary")
    dense = layout.get("leading_dense_layers", conf.get("first_k_dense_replace", 0))
    if "num_hidden_layers" in reduced and conf["num_hidden_layers"] - dense < 4:
        bad.append(f"config {name}: fewer than four layers after the "
                   f"{dense} leading dense one(s)")
    return bad
