"""The open-loop generator: its schedule from a seed, and its lateness."""

import asyncio
import collections
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness import loadgen  # noqa: E402

TRAFFIC = {
    "rate_rps": 4.0, "lead_in_s": 4.0,
    "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 16, "max": 2048},
    "output": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 8, "max": 256},
}
BIG = 2**31 + 7                          # the driver's seeds pass 32 signed bits


def test_the_seed_draws_the_schedule_and_the_tokens():
    a = loadgen.schedule(TRAFFIC, 20.0, BIG)
    assert a == loadgen.schedule(TRAFFIC, 20.0, BIG)
    other = loadgen.schedule(TRAFFIC, 20.0, BIG + 1)
    assert [x.due_s for x in a] != [x.due_s for x in other]
    assert loadgen.prompt_tokens(BIG, a[3], 32768) == loadgen.prompt_tokens(BIG, a[3], 32768)
    assert loadgen.prompt_tokens(BIG, a[3], 32768) != loadgen.prompt_tokens(BIG + 1, a[3], 32768)
    assert len(loadgen.prompt_tokens(5, a[3], 32768)) == a[3].prompt_len


@pytest.mark.parametrize("seconds", [10.0, 20.0, 45.0])
def test_the_window_is_offered_the_cells_rate(seconds):
    s = loadgen.schedule(TRAFFIC, seconds, BIG)
    n = sum(x.in_window for x in s)
    assert 0.6 * 4.0 * seconds <= n <= 1.4 * 4.0 * seconds
    assert collections.Counter(x.in_window for x in s)[False] >= 1


def test_schedule_shape():
    s = loadgen.schedule(TRAFFIC, 20.0, 11)
    lead = TRAFFIC["lead_in_s"]
    assert [x.due_s for x in s] == sorted(x.due_s for x in s)
    assert all((x.due_s >= lead) == x.in_window for x in s)
    assert max(x.due_s for x in s) < lead + 20.0
    n = sum(x.in_window for x in s)
    assert 50 <= n <= 110          # Poisson, 4 a second for 20 s
    assert all(16 <= x.prompt_len <= 2048 and 8 <= x.output_len <= 256 for x in s)
    assert [x.index for x in s] == list(range(len(s)))


def test_offer_sends_on_schedule_and_reports_lateness():
    arrivals = [loadgen.Arrival(i, 0.02 * i, 16, 8, True) for i in range(10)]
    got = []

    async def send(a):
        got.append(a.index)
        await asyncio.sleep(0.2)       # a slow server does not slow the offer
        return a.index

    async def go():
        import time

        t0 = time.monotonic() + 0.01
        sent = await loadgen.offer(arrivals, send, t0)
        assert sent[-1].sent - t0 < 0.2 + 0.15   # all sent before any finished
        out = await asyncio.gather(*(s.task for s in sent))
        return sent, out

    sent, out = asyncio.run(go())
    assert out == list(range(10)) and got == list(range(10))
    late = [s.sent - s.due for s in sent]
    assert all(l >= 0 for l in late) and max(late) < 0.1
