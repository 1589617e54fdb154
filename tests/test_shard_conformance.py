"""Sharding-conformance lint rules (analysis/rules_sharding.py).

Layers, mirroring ``tests/test_project_analysis.py``:

* registry plumbing — the jax-importing rules are HEAVY (excluded from the
  default registry that rides the 10s lint stage, opted in via
  ``--rules``/``include_heavy``), the pure-AST rules ride by default, and
  ``--list-rules``/SARIF surface all of them;
* per-rule TP / clean / suppression fixtures for the two fast rules
  (``shard-undefined-axis``, ``shard-unsharded-device-put``);
* MUTATION tests against the real package via ``source_overrides``
  (slow-marked; run by the ``shard-audit-fast`` ci_check stage): delete a
  live ``LLAMA_RULES`` entry and the weight-fallthrough check turns red;
  duplicate a pattern and the shadowed-rule check turns red; add a rule
  matching nothing and the dead-rule check turns red — while HEAD stays
  green on the same machinery.
"""

import json
from pathlib import Path

import pytest

from finetune_controller_tpu.analysis import rules_sharding
from finetune_controller_tpu.analysis.engine import (
    all_project_rules,
    lint_paths,
    main,
)
from finetune_controller_tpu.analysis.project import build_project

PKG = Path(__file__).resolve().parent.parent / "finetune_controller_tpu"

FAST_IDS = ("shard-undefined-axis", "shard-unsharded-device-put")
HEAVY_IDS = ("shard-rule-coverage", "shard-divisibility",
             "collective-conformance")


def _write(tmp_path: Path, files: dict[str, str]) -> Path:
    import textwrap

    for name, src in files.items():
        p = tmp_path / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return tmp_path


def _fast_lint(tmp_path, files, rules=FAST_IDS):
    root = _write(tmp_path, files)
    prules = all_project_rules()
    prules = {k: prules[k] for k in rules}
    return lint_paths([str(root)], rules={}, project_rules=prules)


def _heavy_lint(rule_ids, source_overrides=None):
    """Run a heavy-rule subset over the REAL package (the ci_check stage's
    shape), optionally with mutated sources swapped in memory."""
    prules = {
        k: v for k, v in all_project_rules(include_heavy=True).items()
        if k in rule_ids
    }
    assert set(prules) == set(rule_ids)
    return lint_paths(
        [str(PKG)], rules={}, project_rules=prules,
        source_overrides=source_overrides or {},
    )


MESH_SRC = """
    class AxisNames:
        DATA = "dp"
        FSDP = "fsdp"
        TENSOR = "tp"
        BATCH_AXES = (DATA, FSDP)
"""


# ---------------------------------------------------------------------------
# registry plumbing
# ---------------------------------------------------------------------------


def test_heavy_rules_excluded_from_default_registry():
    """The 10s lint budget survives v3 because the jax-importing rules are
    not in the default registry — they run only when named."""
    default = all_project_rules()
    for rid in HEAVY_IDS:
        assert rid not in default
    for rid in FAST_IDS:
        assert rid in default


def test_heavy_rules_present_with_include_heavy():
    full = all_project_rules(include_heavy=True)
    for rid in FAST_IDS + HEAVY_IDS:
        assert rid in full
        assert full[rid].plane == "sharding"
    for rid in HEAVY_IDS:
        assert full[rid].heavy


def test_list_rules_tags_heavy(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in FAST_IDS + HEAVY_IDS:
        assert rid in out
    for line in out.splitlines():
        if any(line.strip().startswith(rid) for rid in HEAVY_IDS):
            assert "[heavy" in line


def test_sarif_covers_sharding_findings(tmp_path, capsys):
    """A sharding finding round-trips through SARIF with its rule id and
    summary in the driver's rule list (CI annotations)."""
    _write(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/train/__init__.py": "",
        "pkg/train/loader.py": (
            "import jax\n\n\ndef f(x):\n    return jax.device_put(x)\n"
        ),
    })
    assert main([str(tmp_path), "--format", "sarif"]) == 1
    doc = json.loads(capsys.readouterr().out)
    run = doc["runs"][0]
    assert any(
        r["ruleId"] == "shard-unsharded-device-put" for r in run["results"]
    )
    driver_rules = {
        r["id"]: r["shortDescription"]["text"]
        for r in run["tool"]["driver"]["rules"]
    }
    assert "explicit sharding" in driver_rules["shard-unsharded-device-put"]


# ---------------------------------------------------------------------------
# shard-undefined-axis (fast, fixtures)
# ---------------------------------------------------------------------------


def test_undefined_axis_flagged(tmp_path):
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/train/__init__.py": "",
        "pkg/train/step.py": """
            from jax.sharding import PartitionSpec

            SPEC = PartitionSpec("fsdp", "tensr")
        """,
    })
    assert [f.rule for f in result.findings] == ["shard-undefined-axis"]
    assert "'tensr'" in result.findings[0].message


def test_defined_axes_clean(tmp_path):
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/train/__init__.py": "",
        "pkg/train/step.py": """
            from jax.sharding import NamedSharding, PartitionSpec

            def shard(mesh, x):
                return NamedSharding(mesh, PartitionSpec("dp", "fsdp"))
        """,
    })
    assert result.findings == []


def test_keyword_args_are_not_axis_names(tmp_path):
    """memory_kind="pinned_host" (the KV host-tiering idiom) is a keyword
    argument, not an axis — it must not false-positive."""
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/serve/__init__.py": "",
        "pkg/serve/kv.py": """
            from jax.sharding import NamedSharding, PartitionSpec

            def host_spec(mesh):
                return NamedSharding(
                    mesh, PartitionSpec(), memory_kind="pinned_host"
                )
        """,
    })
    assert result.findings == []


def test_local_mesh_axes_allowed(tmp_path):
    """A module constructing its own diagnostics Mesh may name its axes."""
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/tools/__init__.py": "",
        "pkg/tools/diag.py": """
            import jax
            from jax.sharding import Mesh, PartitionSpec

            def probe(devs):
                mesh = Mesh(devs, ("probe",))
                return PartitionSpec("probe")
        """,
    })
    assert result.findings == []


def test_no_mesh_module_opts_out(tmp_path):
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/train/__init__.py": "",
        "pkg/train/step.py": """
            from jax.sharding import PartitionSpec

            SPEC = PartitionSpec("anything")
        """,
    })
    assert result.findings == []


def test_undefined_axis_suppression(tmp_path):
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/train/__init__.py": "",
        "pkg/train/step.py": """
            from jax.sharding import PartitionSpec

            # ftc: ignore[shard-undefined-axis] -- fixture
            SPEC = PartitionSpec("tensr")
        """,
    })
    assert len(result.findings) == 1 and result.findings[0].suppressed


# ---------------------------------------------------------------------------
# shard-unsharded-device-put (fast, fixtures)
# ---------------------------------------------------------------------------


def test_bare_device_put_on_multichip_path_flagged(tmp_path):
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/train/__init__.py": "",
        "pkg/train/loader.py": """
            import jax

            def to_device(x):
                return jax.device_put(x)
        """,
    })
    assert [f.rule for f in result.findings] == ["shard-unsharded-device-put"]


def test_device_put_with_sharding_clean(tmp_path):
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/train/__init__.py": "",
        "pkg/train/loader.py": """
            import jax

            def to_device(x, sharding):
                a = jax.device_put(x, sharding)
                b = jax.device_put(x, device=sharding)
                return a, b
        """,
    })
    assert result.findings == []


def test_device_put_outside_multichip_segments_ignored(tmp_path):
    """controller/ ctl code moves host scalars around — not a hot path."""
    result = _fast_lint(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/parallel/__init__.py": "",
        "pkg/parallel/mesh.py": MESH_SRC,
        "pkg/controller/__init__.py": "",
        "pkg/controller/admin.py": """
            import jax

            def stage(x):
                return jax.device_put(x)
        """,
    })
    assert result.findings == []


# ---------------------------------------------------------------------------
# table reconstruction parity (the AST twin matches the runtime table)
# ---------------------------------------------------------------------------


def test_ast_table_matches_runtime_fingerprint():
    """The coverage rule lints the table it RECONSTRUCTS from source — this
    pin proves the reconstruction is the real LLAMA_RULES (same patterns,
    same specs, same order) so mutation tests mutate the thing that runs."""
    from finetune_controller_tpu.parallel.sharding import LLAMA_RULES

    project = build_project([str(PKG)])
    mesh_mod = rules_sharding._mesh_module(project)
    attr_map, _defined = rules_sharding._axis_table(mesh_mod)
    tables = [
        t for t in rules_sharding._find_tables(project, attr_map)
        if t.parsed and t.name == "LLAMA_RULES"
    ]
    assert len(tables) == 1
    rebuilt = rules_sharding._build_rules(tables[0])
    assert rebuilt.fingerprint() == LLAMA_RULES.fingerprint()


# ---------------------------------------------------------------------------
# heavy rules on the real package: HEAD green, mutations red (slow)
# ---------------------------------------------------------------------------

SHARD_PY = PKG / "parallel" / "sharding.py"


@pytest.mark.slow
def test_head_is_clean_under_heavy_rules():
    """The repo's own rule table passes coverage + divisibility at HEAD —
    the lint-clean satellite, and the baseline every mutation test below
    flips from."""
    result = _heavy_lint(("shard-rule-coverage", "shard-divisibility"))
    assert [f for f in result.findings if not f.suppressed] == []
    assert result.errors == []


@pytest.mark.slow
def test_deleted_rule_turns_coverage_red():
    """Delete the live down_proj/kernel rule: the leaf falls through to the
    bare ``.*`` catch-all and the weight-fallthrough check fires — the
    deleted-rule trap the ISSUE names."""
    src = SHARD_PY.read_text()
    line = '        (r"down_proj/kernel", P(Ax.TENSOR, Ax.FSDP)),\n'
    assert line in src
    mutated = src.replace(line, "")
    result = _heavy_lint(
        ("shard-rule-coverage",), {str(SHARD_PY): mutated}
    )
    hits = [f for f in result.findings if "down_proj/kernel" in f.message]
    assert hits, [f.message for f in result.findings]
    assert all(f.rule == "shard-rule-coverage" for f in hits)
    assert any("catch-all" in f.message for f in hits)


@pytest.mark.slow
def test_shadowed_rule_turns_coverage_red():
    """A duplicate pattern inserted after the original never matches first
    — flagged as shadowed, at its own line, naming the superseding rule."""
    src = SHARD_PY.read_text()
    anchor = '        (r".*", P()),'
    assert anchor in src
    mutated = src.replace(
        anchor,
        '        (r"router/kernel", P(Ax.FSDP, None)),\n' + anchor,
    )
    result = _heavy_lint(
        ("shard-rule-coverage",), {str(SHARD_PY): mutated}
    )
    assert any(
        "shadowed" in f.message and "router/kernel" in f.message
        for f in result.findings
    ), [f.message for f in result.findings]


@pytest.mark.slow
def test_dead_rule_turns_coverage_red():
    """A rule whose pattern matches no catalog leaf is dead weight."""
    src = SHARD_PY.read_text()
    anchor = '        (r".*", P()),'
    mutated = src.replace(
        anchor,
        '        (r"no_such_param_family/kernel2", P()),\n' + anchor,
    )
    result = _heavy_lint(
        ("shard-rule-coverage",), {str(SHARD_PY): mutated}
    )
    assert any(
        "dead" in f.message and "no_such_param_family" in f.message
        for f in result.findings
    ), [f.message for f in result.findings]


@pytest.mark.slow
def test_undefined_axis_in_table_turns_coverage_red():
    """A spec axis the AxisNames table does not define is red even before
    any topology is consulted."""
    src = SHARD_PY.read_text()
    line = '        (r"router/kernel", P(Ax.FSDP, None)),'
    assert line in src
    mutated = src.replace(
        line, '        (r"router/kernel", P("bogus_axis", None)),'
    )
    result = _heavy_lint(
        ("shard-rule-coverage",), {str(SHARD_PY): mutated}
    )
    assert any("bogus_axis" in f.message for f in result.findings), \
        [f.message for f in result.findings]


@pytest.mark.slow
def test_indivisible_spec_turns_divisibility_red():
    """Shard the tiny LoRA rank dim (16) over the dp×fsdp product: on the
    REALSCALE dcn2x16 topology that product is 32 and stops dividing —
    the static twin of validate_spec fires at the entry's line."""
    src = SHARD_PY.read_text()
    line = '        (r"o_proj/lora_a|down_proj/lora_a", P(Ax.TENSOR, None)),'
    assert line in src
    mutated = src.replace(
        line,
        '        (r"o_proj/lora_a|down_proj/lora_a",'
        ' P(Ax.TENSOR, (Ax.DATA, Ax.FSDP))),',
    )
    result = _heavy_lint(("shard-divisibility",), {str(SHARD_PY): mutated})
    hits = [f for f in result.findings if "lora_a" in f.message]
    assert hits, [f.message for f in result.findings]
    assert all(f.rule == "shard-divisibility" for f in hits)
    assert any("divisible" in f.message for f in hits)
