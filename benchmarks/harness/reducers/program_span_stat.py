"""A statistic of the PROGRAM's own host spans (``obs.annotate``), read from
the host planes of the run's profiler trace and clipped to the traced
window: ``stat`` of the durations of the spans called any of ``spans``,
times ``scale`` — or, with ``stat`` ``"share"``, their summed duration as a
share of the traced window in %."""

import sys

from benchmarks.harness import scopes as S
from benchmarks.harness.stats import STATS


def reduce(run, spans: list, stat: str, scale: float = 1.0):
    seconds = [e.seconds for e in S.host_spans(run, spans)]
    if not seconds:
        return None
    if stat != "share":
        return STATS[stat](seconds) * scale
    lo, hi = run.traced
    if hi <= lo:
        return None     # no device operation in the trace: no window
    share = 100.0 * sum(seconds) / (hi - lo)
    if share > 105.0:
        sys.exit(f"benchmark: spans {spans} read {share} % of the traced "
                 "window — spans counted twice")
    return share
