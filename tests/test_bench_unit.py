"""Unit tests for bench.py's accounting helpers.

The training bench measures a chip or fails: no CPU leg, no cached verdict,
no assumed peak — pin what is left.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def _load_bench(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("bench_mod", REPO / "bench.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_peak_tflops_mapping(monkeypatch, tmp_path):
    bench = _load_bench(monkeypatch, tmp_path)
    assert bench._peak_tflops("TPU v5e") == 197.0
    assert bench._peak_tflops("TPU v5p") == 459.0
    assert bench._peak_tflops("TPU v5 lite") == 197.0


def test_unknown_device_kind_fails_instead_of_assuming_a_peak(
        monkeypatch, tmp_path, capsys):
    """A device with no published peak is an error, never a default: MFU
    against a borrowed peak would be a made-up number."""
    bench = _load_bench(monkeypatch, tmp_path)
    with pytest.raises(SystemExit) as exc:
        bench._peak_tflops("TPU v9 mystery")
    assert exc.value.code == 1
    err = json.loads(capsys.readouterr().err.strip().splitlines()[-1])
    assert "unknown device_kind" in err["bench_error"]
    assert err["device_kind"] == "TPU v9 mystery"


def test_training_bench_fails_without_a_chip(monkeypatch, tmp_path, capsys):
    """On the CPU the default bench must refuse to run: tokens/sec/chip and
    MFU are device metrics, and there is no CPU leg to fall back to."""
    bench = _load_bench(monkeypatch, tmp_path)
    for knob in ("BENCH_MODE", "BENCH_TINY"):
        monkeypatch.delenv(knob, raising=False)
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 1
    captured = capsys.readouterr()
    assert captured.out.strip() == ""  # no result line of any kind
    err = json.loads(captured.err.strip().splitlines()[-1])
    assert "no TPU found" in err["bench_error"] and err["platform"] == "cpu"
    src = (REPO / "bench.py").read_text()
    for gone in ("execve", "PROBE_CACHE", "_latest_session_tpu_record",
                 "CPU_FALLBACK", "or 197.0"):
        assert gone not in src, gone


def test_jsonable_scrubs_nonfinite(monkeypatch, tmp_path):
    bench = _load_bench(monkeypatch, tmp_path)
    out = bench._jsonable([1.0, float("nan"), float("inf")])
    assert out[0] == 1.0 and out[1] == "nan" and out[2] == "inf"
    json.dumps(out)  # RFC-JSON safe


def test_session_log_append_captures_env(monkeypatch, tmp_path):
    bench = _load_bench(monkeypatch, tmp_path)
    log = tmp_path / "session.jsonl"
    monkeypatch.setattr(bench, "SESSION_LOG", str(log))
    monkeypatch.setenv("BENCH_MODE", "qlora")
    monkeypatch.delenv("BENCH_SESSION_LOG", raising=False)
    bench._session_log_append({"metric": "m", "value": 1.0})
    rec = json.loads(log.read_text())
    assert rec["step"] == "adhoc_bench"
    assert rec["env"]["BENCH_MODE"] == "qlora"
    assert rec["metric"] == "m" and "ts" in rec
    # disabled via BENCH_SESSION_LOG=0
    monkeypatch.setenv("BENCH_SESSION_LOG", "0")
    bench._session_log_append({"metric": "m2", "value": 2.0})
    assert len(log.read_text().splitlines()) == 1
