"""One order statistic of a recorded series, times ``scale``."""

from benchmarks.harness.stats import STATS


def reduce(run, series: str, stat: str, scale: float = 1.0):
    values = run.recorder.series.get(series)
    if not values:
        return None
    return STATS[stat](values) * scale
