"""BENCHMARK.json and every file it names load and cross-check; the tests'
tiny manifest adds a cell, two configurations, a per-layer metric and a
reducer by NEW files and entries alone."""

import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness.manifest import NAME, UNIT, Manifest  # noqa: E402

TINY = ROOT / "tests/benchmarks/fixtures/BENCHMARK.tiny.json"
REAL = Manifest()


def test_manifest_has_exactly_the_contract_keys():
    assert set(REAL.raw) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert REAL.raw["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= REAL.raw["run_seconds"] <= 51
    assert len(json.dumps(REAL.raw)) < 64 * 1024


@pytest.mark.parametrize("path", [None, TINY], ids=["real", "tiny"])
def test_manifest_cross_checks(path):
    assert Manifest(path).problems() == []


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_units_and_entry_keys(group):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"},
    }[group]
    for entry in REAL.raw[group]:
        assert NAME.match(entry["name"]), entry["name"]
        assert set(entry) <= allowed, (entry["name"], set(entry) - allowed)
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                    and "\t" not in entry[key], (entry["name"], key)
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
        if "traffic" in entry:
            assert NAME.match(entry["traffic"])


def test_every_moves_names_a_metric_its_cells_report():
    for name, m in REAL.per_layer.items():
        assert m["moves"] in REAL.end_to_end, name
        for cell in m.get("workloads", []):
            assert m["moves"] in REAL.cell_end_to_end(cell), (name, cell)
    for cell in REAL.workloads:
        assert "setup_s" in REAL.cell_end_to_end(cell)
        assert len(REAL.cell_end_to_end(cell)) >= 2
        assert REAL.cell_per_layer(cell)


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in REAL.paths:
        for p in base.rglob("*"):
            if "__pycache__" in p.parts or p.suffix == ".pyc":
                continue
            assert ok.match(str(p.relative_to(ROOT))), p


@pytest.mark.parametrize("name", sorted(REAL.configs))
def test_configuration_files_carry_the_published_keys(name):
    conf = REAL.config(name)
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_hidden_layers", "num_key_value_heads", "vocab_size",
                "rope_theta", "rms_norm_eps", "source", "reduced", "assumed",
                "layout", "run"):
        assert key in conf, key
    assert conf["source"] == REAL.configs[name]["source"]
    assert conf["reduced"] == REAL.configs[name]["reduced"] == []


@pytest.mark.parametrize("name", sorted(REAL.per_layer))
def test_layer_metric_files_and_reducers_load(name):
    spec = REAL.layer_metric(name)
    assert callable(REAL.reducer(spec["reducer"]))
    assert spec["layer"] == REAL.per_layer[name]["layer"]


def test_tiny_manifest_adds_by_files_alone():
    tiny = Manifest(TINY)
    spec = tiny.layer_metric("tiny.steps_count")
    assert "tests/benchmarks/fixtures" in str(
        tiny._find(f"reducers/{spec['reducer']}.py"))
    assert callable(tiny.reducer("note_value"))
    assert tiny.workload("tiny-qlora.train-tiny")["driver"] == "train"
