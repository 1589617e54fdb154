"""BENCHMARK.json and every file it names load and cross-check; the tests'
tiny manifest adds a cell, two configurations, a per-layer metric and a
reducer by NEW files and entries alone, and the cut manifest a CUT
configuration of another program, reference and counts module."""

import copy
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness.manifest import (  # noqa: E402
    CONFIG_KEYS, LLAMA_KEYS, NAME, UNIT, Manifest, config_problems)

TINY = ROOT / "tests/benchmarks/fixtures/BENCHMARK.tiny.json"
CUT = ROOT / "tests/benchmarks/fixtures/BENCHMARK.cut.json"
REAL = Manifest()
CELLS = ["mistral-7b-qlora.train-sft-2k", "mistral-7b-qlora.train-sft-8k"]


def test_manifest_has_exactly_the_contract_keys():
    assert set(REAL.raw) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert REAL.raw["command"] == ["python3", "benchmarks/run.py"]
    assert 1 <= REAL.raw["run_seconds"] <= 51
    assert len(json.dumps(REAL.raw)) < 64 * 1024


@pytest.mark.parametrize("path", [None, TINY, CUT], ids=["real", "tiny", "cut"])
def test_manifest_cross_checks(path):
    assert Manifest(path).problems() == []


def test_the_real_manifest_has_its_two_cells_and_no_metric_by_default():
    assert list(REAL.workloads) == CELLS
    assert REAL.problems() == []
    for cell in CELLS:
        assert REAL.workload(cell)["driver"] == "train"
        assert REAL.cell_end_to_end(cell) == ["train_tokens_per_s_chip", "setup_s"]
        assert len(REAL.cell_per_layer(cell)) == 16
    # no per-layer metric applies to a cell a later PR adds unless it lists it
    for m in REAL.raw["per_layer"]:
        assert m["workloads"] == CELLS, m["name"]


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


def _configs():
    for label, path in (("real", None), ("tiny", TINY), ("cut", CUT)):
        m = Manifest(path)
        for name in sorted(m.configs):
            yield pytest.param(m, name, id=f"{label}:{name}")


@pytest.mark.parametrize("manifest,name", _configs())
def test_configuration_files_carry_the_published_keys(manifest, name):
    entry, conf = manifest.configs[name], manifest.config(name)
    for key in CONFIG_KEYS:
        assert key in conf, key
    if conf["run"].get("program", "llama") == "llama":
        for key in LLAMA_KEYS:
            assert key in conf, key
    assert conf["source"] == entry["source"]
    assert conf["reduced"] == entry["reduced"]
    assert config_problems(entry, conf) == []
    if not conf["reduced"]:
        return      # full width and depth: nothing more to state
    for key in conf["reduced"]:
        assert key in conf and conf["published"][key] != conf[key], key
    assert conf["layout"]["deployment"] and conf["layout"]["chips_sharing_a_layer"] >= 1


def test_the_accepted_configuration_is_whole():
    assert REAL.configs["mistral-7b-qlora"]["reduced"] == []
    assert REAL.config("mistral-7b-qlora")["reduced"] == []


def _cut():
    m = Manifest(CUT)
    return copy.deepcopy(m.configs["tiny-cut"]), m.config("tiny-cut")


def _hide(entry, conf):
    entry["reduced"] = []                     # the entry hides the file's cut


def _no_published(entry, conf):
    del conf["published"]["vocab_size"]


def _same_as_published(entry, conf):
    conf["published"]["num_hidden_layers"] = conf["num_hidden_layers"]


def _under_layers(entry, conf):
    conf["num_hidden_layers"] = 4
    conf["layout"]["leading_dense_layers"] = 1   # three layers after it


def _under_vocabulary(entry, conf):
    conf["vocab_size"] = 255                  # 2048 / 8 = 256


def _under_experts(entry, conf):
    for side in (entry, conf):
        side["reduced"] = side["reduced"] + ["n_routed_experts"]
    conf["n_routed_experts"], conf["published"]["n_routed_experts"] = 4, 256


def _absent_key(entry, conf):
    for side in (entry, conf):
        side["reduced"] = side["reduced"] + ["num_nextn_predict_layers"]
    conf["published"]["num_nextn_predict_layers"] = 1


def _a_width(entry, conf):
    for side in (entry, conf):
        side["reduced"] = side["reduced"] + ["intermediate_size"]
    conf["published"]["intermediate_size"] = 512


def _no_deployment(entry, conf):
    conf["layout"] = "one device"


def _no_llama_key(entry, conf):
    del conf["rope_theta"]
    conf["run"]["program"] = "llama"


@pytest.mark.parametrize("break_it,says", [
    (_hide, "reduced differs from its file"),
    (_no_published, "no published value of vocab_size"),
    (_same_as_published, "no published value of num_hidden_layers"),
    (_under_layers, "fewer than four layers"),
    (_under_vocabulary, "under an eighth"),
    (_under_experts, "under the floor of 8"),
    (_absent_key, "num_nextn_predict_layers is not in the file"),
    (_a_width, "names a width, intermediate_size"),
    (_no_deployment, "names its deployment"),
    (_no_llama_key, "no key rope_theta"),
])
def test_a_cut_that_breaks_one_rule_is_a_problem(break_it, says):
    entry, conf = _cut()
    assert config_problems(entry, conf) == []
    break_it(entry, conf)
    bad = config_problems(entry, conf)
    assert len(bad) == 1 and says in bad[0], bad


def test_another_architecture_need_not_state_the_llama_keys():
    entry, conf = _cut()
    for key in LLAMA_KEYS:
        del conf[key]
    assert conf["run"]["program"] != "llama"
    assert config_problems(entry, conf) == []


def test_program_reference_and_counts_are_found_by_name():
    cut, real = Manifest(CUT), REAL
    conf = cut.config("tiny-cut")
    assert "tests/benchmarks/fixtures" in cut.program(conf).__file__
    assert "tests/benchmarks/fixtures" in cut.reference(conf).__file__
    assert callable(cut.program(conf).model_config)
    assert callable(cut.reference(conf).reference_numbers)
    assert callable(cut.counts("tiny_counts").flash_call_flops)
    mistral = real.config("mistral-7b-qlora")
    assert real.program(mistral).__file__.endswith("harness/programs/llama.py")
    assert real.reference(mistral).__file__.endswith("benchmarks/reference/llama.py")
    with pytest.raises(FileNotFoundError):
        real.counts("tiny_counts")              # not under the real paths
    conf["run"]["program"] = "no-such-program"
    with pytest.raises(FileNotFoundError):
        cut.program(conf)


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


def test_a_missing_program_reference_or_counts_module_is_a_problem(tmp_path):
    raw = json.loads(CUT.read_text())
    conf = json.loads((ROOT / raw["configs"][0]["file"]).read_text())
    conf["run"]["reference"] = "nowhere"
    (tmp_path / "conf.json").write_text(json.dumps(conf))
    raw["configs"][0]["file"] = str(tmp_path / "conf.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    bad = Manifest(tmp_path / "BENCHMARK.json").problems()
    assert len(bad) == 1 and "reference" in bad[0] and "nowhere" in bad[0], bad
