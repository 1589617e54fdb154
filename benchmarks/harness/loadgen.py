"""The open-loop load generator: one general generator, driven by a cell's
data file.

A cell's ``traffic`` block fixes a rate, the length distributions and a
lead-in.  ``--seed`` draws everything else: one Poisson schedule over the
lead-in plus the window, a lognormal prompt and answer length for each
arrival, and the token ids.

Requests are timed from when they were DUE, not from when they were sent;
how late the generator ran is recorded beside them.
"""

from __future__ import annotations

import asyncio
import dataclasses
import math
import time
from typing import Awaitable, Callable

import numpy as np


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float            # seconds after the generator starts
    prompt_len: int
    output_len: int
    in_window: bool         # due inside the measured window (else lead-in)


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic: dict, seconds: float, seed: int) -> list[Arrival]:
    """Arrivals for ``lead_in_s`` (fills the lanes; not measured) plus
    ``seconds`` (the window)."""
    rng = np.random.default_rng([int(seed), 0xA771])
    lead = float(traffic["lead_in_s"])
    t, due = 0.0, []
    while True:
        t += rng.exponential(1.0 / traffic["rate_rps"])
        if t >= lead + seconds:
            break
        due.append(t)
    prompts = _lengths(rng, traffic["prompt"], len(due))
    outputs = _lengths(rng, traffic["output"], len(due))
    return [Arrival(i, t, int(p), int(o), t >= lead)
            for i, (t, p, o) in enumerate(zip(due, prompts, outputs))]


def prompt_tokens(seed: int, arrival: Arrival, vocab: int) -> list[int]:
    rng = np.random.default_rng([int(seed), 0x70C, arrival.index])
    return rng.integers(0, vocab, arrival.prompt_len).tolist()


@dataclasses.dataclass
class Sent:
    arrival: Arrival
    due: float               # on ``clock``
    sent: float
    task: asyncio.Task


async def offer(arrivals: list[Arrival], send: Callable[[Arrival], Awaitable],
                t_start: float, clock=time.monotonic) -> list[Sent]:
    """Send every arrival at its due time whether or not earlier ones have
    finished (open loop).  Returns one record per arrival; its task holds
    the result or the exception."""
    sent: list[Sent] = []
    for a in arrivals:
        due = t_start + a.due_s
        delay = due - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        sent.append(Sent(a, due, clock(), asyncio.ensure_future(send(a))))
    return sent
