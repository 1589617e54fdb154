"""Tests only: a reducer added by a new file alone — reads a driver's note."""


def reduce(run, key: str):
    return float(run.notes[key]) if key in run.notes else None
