"""The trace reduction on a small synthetic ``XSpace`` (text proto under
``fixtures/``), against values worked by hand.

Events take the form real ones have (PERF.md section 6, PR 24): the name is
the instruction's text, a Pallas kernel one ``custom-call`` NAMED AS THE
KERNEL (``%flash_fwd.43 = ...``), no ``metadata={...}``.  Chip 0, times in ms
after the trace's origin: a ``while`` 0-40 spanning its body's ops; fusion
0-10; all-gather 10-30 and, beside it, flash_bwd_dq 10-16, flash_bwd_dkv
16-24 and a FOURTH Mosaic call of another name (``dequant_int4_rows``, one
bf16 result like dQ's) 24-25; flash_fwd 25-40, idle 40-60, fusion 60-70,
idle 70-80, flash_fwd 80-100; programs: train step 0-40, decode 60-100.
Chip 1: a ``while`` 0-60 whose one op runs 0-50 (the container's last 10 ms
are a wait, not work).  Host: ``input`` 30-65, ``dispatch`` 68-82, and a
profiler span that is not ours.
"""

import json
import re
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import counts, trace as T  # noqa: E402
from benchmarks.harness.manifest import Manifest  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.txt"
MS = 1e-3


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(FIXTURE.read_text())
    return T.from_profile_data(pd, ("input", "dispatch"))


def test_planes_lines_and_host_filter(tr):
    assert sorted(tr.devices) == [0, 1]
    assert len(tr.devices[0]) == 9 and len(tr.modules[0]) == 2
    assert sorted(e.name for e in tr.host) == ["dispatch", "input"]


def test_window_and_busy_share(tr):
    lo, hi = T.window(tr)
    assert (hi - lo) == pytest.approx(100 * MS)
    # chip 0 busy 0-40, 60-70, 80-100 = 70 ms; chip 1's leaf op 50 ms of its
    # 60 ms ``while``; mean 60
    assert T.busy_seconds(tr, lo, hi) == pytest.approx(60 * MS)
    assert T.busy_seconds(tr, lo, lo + 50 * MS) == pytest.approx(45 * MS)


def test_kernel_time_with_and_without_module(tr):
    assert T.kernel_seconds(tr, "^%flash_fwd") == pytest.approx((15 + 20) * MS / 2)
    in_decode = T.kernel_events(tr, "^%flash_fwd", module="decode")
    assert [round(e.seconds / MS) for e in in_decode] == [20]
    assert T.kernel_events(tr, "paged") == []


def test_top_ops_label_and_leave_out_containers(tr):
    ops = dict(T.top_ops(tr))
    assert ops["fusion.1 bf16[8,128]"] == pytest.approx((10 + 50) * MS / 2)
    assert ops["flash_fwd.43 bf16[8,32,2048,128]"] == pytest.approx(35 * MS / 2)
    assert ops["all-gather.3 bf16[4096,128]"] == pytest.approx(20 * MS / 2)
    assert not any(k.startswith("while") for k in ops)
    assert T.is_container("%while.12 = (s32[]{:T(128)}) while(%x)")
    assert T.label("plain") == "plain"


def test_idle_gaps_are_named_by_the_open_annotation(tr):
    lo, hi = T.window(tr)
    gaps = dict(T.idle_gaps(tr, lo, hi, device=0))
    # gap 40-60 began inside `input` (30-65); gap 70-80 inside `dispatch`
    assert gaps == {"input": pytest.approx(20 * MS),
                    "dispatch": pytest.approx(10 * MS)}


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 12)], [(0, 2), (4, 6)]),
    ([(0, 5), (3, 8)], [], [(0, 8)]),
    ([(0, 5)], [(0, 5)], []),
])
def test_interval_subtract(a, b, want):
    assert T.subtract(a, b) == want


# ---- the flash metrics match the kernels by name (ISSUE 26) --------------------

REAL = Manifest()
MISTRAL = json.loads((ROOT / "benchmarks/configs/mistral-7b-qlora.json").read_text())


def flash_run(tr):
    return types.SimpleNamespace(
        trace=tr, traced=T.window(tr), conf=MISTRAL, manifest=REAL,
        notes={"batch": 16, "seq": 2048}, peaks=counts.peaks_for("TPU v5 lite"))


def reduce(run, metric, **more):
    spec = REAL.layer_metric(metric)
    return REAL.reducer(spec["reducer"])(run, **spec.get("args", {}), **more)


def test_flash_time_share_counts_the_three_kernels_and_no_other_mosaic_call(tr):
    # in the train step: dQ 6 + dK/dV 8 + forward 15 = 29 ms on chip 0, none
    # on chip 1; the 1 ms ``dequant_int4_rows`` call and the forward kernel
    # inside the decode program are not counted
    assert reduce(flash_run(tr), "flash.time_share_pct") == pytest.approx(
        100 * (29 / 2) / 100)
    every = T.kernel_seconds(tr, r"custom-call\(.*tpu_custom_call", "train_step")
    assert every == pytest.approx(30 * MS / 2)     # what the old pattern read


def test_flash_roofline_takes_each_kernel_by_its_name(tr, capsys):
    # two chips in the trace: 8 sequences a chip.  One call each of the three
    # kernels in the train step plus the forward call in the decode program
    # (the roofline reads every call of a kernel, wherever it ran)
    peak = 197e12
    unit = 2 * 2048 * 2048 * 32 * 128 / 2 * 8       # one causal matmul
    need = (2 * 2 + 3 + 4) * unit / peak            # fwd twice, dQ, dK/dV
    spent = (15 + 20 + 6 + 8) * MS
    got = reduce(flash_run(tr), "flash_attention_roofline")
    assert got == pytest.approx(100 * need / spent)
    assert "compute" in capsys.readouterr().out
    # the stray call has dQ's result type: by result type it would be taken
    stray = [e for e in tr.devices[0] if e.name.startswith("%dequant_int4_rows")]
    assert len(stray) == 1 and re.search(
        r"= bf16\[[\d,]+\]\{[^}]*\} custom-call\(.*tpu_custom_call", stray[0].name)


@pytest.mark.parametrize("name,kind", [
    ("%flash_fwd.5 = (bf16[2,32,8192,128]{3,2,1,0}, f32[2,32,8192,1]{3,2,1,0}) custom-call(", "fwd"),
    ("flash_fwd = (bf16[2,32,8192,128]{3,2,1,0}, f32[2,32,8192,1]{3,2,1,0}) custom-call(", "fwd"),
    ("%flash_fwd.7.remat = (bf16[8,32,2048,128]{3,2,1,0}) custom-call(", "fwd"),
    ("%flash_bwd_dq.11 = bf16[8,32,2048,128]{3,2,1,0} custom-call(", "bwd_dq"),
    ("%flash_bwd_dkv.11 = (f32[8,8,2048,128]{3,2,1,0}, f32[8,8,2048,128]{3,2,1,0}) custom-call(", "bwd_dkv"),
    ("%flash_fwd_mla.3 = bf16[8,32,2048,128]{3,2,1,0} custom-call(", None),
    ("%dequant_int4_rows.7 = bf16[4096,14336]{1,0} custom-call(", None),
    ("%fusion.1 = bf16[8,128]{1,0} fusion(bf16[8,128]{1,0} %flash_fwd.5)", None),
])
def test_flash_patterns_match_instruction_names_only(name, kind):
    kernels = REAL.layer_metric("flash_attention_roofline")["args"]["kernels"]
    hits = [k["kind"] for k in kernels if re.search(k["pattern"], name)]
    assert hits == ([kind] if kind else [])
    share = REAL.layer_metric("flash.time_share_pct")["args"]["pattern"]
    assert bool(re.search(share, name)) == (kind is not None)
