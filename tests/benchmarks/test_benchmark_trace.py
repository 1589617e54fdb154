"""The trace reduction on a small synthetic ``XSpace`` (text proto under
``fixtures/``), against values worked by hand.

Chip 0, times in ms after the trace's origin: a ``while`` 0-40 spanning
its body's ops; fusion 0-10, all-gather 10-30, _fwd_kernel 25-40, idle
40-60, fusion 60-70, idle 70-80, _fwd_kernel 80-100; programs: train step
0-40, decode 60-100.  Chip 1: a ``while`` 0-60 whose one op runs 0-50 (the
container's last 10 ms are a wait, not work).  Host:
``input`` 30-65, ``dispatch`` 68-82, and a profiler span that is not ours.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import trace as T  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.txt"
MS = 1e-3


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(FIXTURE.read_text())
    return T.from_profile_data(pd, ("input", "dispatch"))


def test_planes_lines_and_host_filter(tr):
    assert sorted(tr.devices) == [0, 1]
    assert len(tr.devices[0]) == 6 and len(tr.modules[0]) == 2
    assert sorted(e.name for e in tr.host) == ["dispatch", "input"]


def test_window_and_busy_share(tr):
    lo, hi = T.window(tr)
    assert (hi - lo) == pytest.approx(100 * MS)
    # chip 0 busy 0-40, 60-70, 80-100 = 70 ms; chip 1's leaf op 50 ms of its
    # 60 ms ``while``; mean 60
    assert T.busy_seconds(tr, lo, hi) == pytest.approx(60 * MS)
    assert T.busy_seconds(tr, lo, lo + 50 * MS) == pytest.approx(45 * MS)


def test_kernel_time_with_and_without_module(tr):
    assert T.kernel_seconds(tr, "custom-call.*_fwd_kernel") == pytest.approx((15 + 20) * MS / 2)
    in_decode = T.kernel_events(tr, "custom-call.*_fwd_kernel", module="decode")
    assert [round(e.seconds / MS) for e in in_decode] == [20]
    assert T.kernel_events(tr, "paged") == []


def test_top_ops_label_and_leave_out_containers(tr):
    ops = dict(T.top_ops(tr))
    assert ops["fusion.1 bf16[8,128]"] == pytest.approx((10 + 50) * MS / 2)
    assert ops["attn.43 bf16[8,32,2048,128]"] == pytest.approx(35 * MS / 2)
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
