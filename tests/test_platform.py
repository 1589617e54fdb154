"""Process-level JAX set-up: the compile-cache helper and the env-flag parser.

Platform selection itself is JAX's own (``JAX_PLATFORMS``); what the entry
points share is one rule for where compiled programs are cached.
"""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

from finetune_controller_tpu import platform as plat

REPO = Path(__file__).resolve().parents[1]

@pytest.fixture
def config_updates(monkeypatch):
    """Record (and swallow) jax.config.update calls."""
    import jax

    calls: list[tuple[str, object]] = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.append((k, v)))
    return calls


def test_compile_cache_env_set_wins_and_no_other_dir_is_set(
        monkeypatch, tmp_path, config_updates):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the answer and
    the code sets no directory of its own."""
    want = str(tmp_path / "given")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert plat.enable_compile_cache() == want
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_compile_cache_env_dir_is_what_jax_itself_uses(tmp_path):
    """... because JAX reads the variable by itself: a fresh process that
    only calls the helper ends up configured with the given directory."""
    want = str(tmp_path / "given")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from finetune_controller_tpu.platform import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        env=dict(os.environ, PYTHONPATH=str(REPO),
                 JAX_COMPILATION_CACHE_DIR=want),
        cwd="/", capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-2:] == [want, want]


def test_compile_cache_unset_is_the_fixed_path_in_the_checkout(
        monkeypatch, tmp_path, config_updates):
    """Without the variable: <checkout>/.cache/xla, whatever the cwd — the
    directory is part of the cache key, so it must not move."""
    want = str(REPO / ".cache" / "xla")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    monkeypatch.chdir(tmp_path)
    assert plat.enable_compile_cache() == want
    assert plat.compile_cache_dir() == want
    assert dict(config_updates) == {
        "jax_compilation_cache_dir": want,
        "jax_persistent_cache_min_compile_time_secs": 0.5,
    }


def test_compile_cache_min_compile_time_env_is_respected(
        monkeypatch, config_updates):
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "3")
    plat.enable_compile_cache()
    assert "jax_persistent_cache_min_compile_time_secs" not in dict(
        config_updates)


def test_no_entry_point_sets_a_cache_dir_of_its_own():
    """One helper owns the cache directory: no other module of the program
    (or chip_smoke.py) writes ``jax_compilation_cache_dir``, and
    nothing derives a cache path from tempfile, a pid or the clock."""
    offenders = []
    files = list((REPO / "finetune_controller_tpu").rglob("*.py"))
    files += [REPO / "chip_smoke.py", REPO / "tests" / "conftest.py"]
    for path in files:
        if path.name == "platform.py" and path.parent.name == "finetune_controller_tpu":
            continue
        if "jax_compilation_cache_dir" in path.read_text():
            offenders.append(str(path.relative_to(REPO)))
    assert offenders == []


@pytest.mark.parametrize("name", [
    "FTC_FLASH_BLOCK_Q", "FTC_FLASH_BLOCK_K", "FTC_FLASH_EXP_DTYPE",
    "FTC_RING_INNER", "FTC_ULYSSES_INNER", "BENCH_",
])
def test_no_retired_knob_in_the_package(name):
    """The attention kernel is chosen by ``ops/attention.py`` from what it
    observes and speed is measured by ``benchmarks/run.py``: the package
    reads none of the retired kernel knobs and cites no ``BENCH_*`` name."""
    offenders = [
        str(path.relative_to(REPO))
        for path in (REPO / "finetune_controller_tpu").rglob("*.py")
        if name in path.read_text()
    ]
    assert offenders == []


def test_device_report_names_what_jax_reports():
    import jax

    rep = plat.device_report()
    assert rep == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()),
    }
