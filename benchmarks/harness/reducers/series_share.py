"""Summed durations of a series (seconds) as a share of the window, in %."""


def reduce(run, series: str):
    values = run.recorder.series.get(series)
    if not values or not run.window_s:
        return None
    return 100.0 * sum(values) / run.window_s
