"""Device time of the train step's leaf operations whose name stack lies
under any of ``scopes`` (named scopes, flax modules or kernel names; every
operation when left out) and, with ``step_pass``, in that pass of the step
(``forward``, ``recompute``, ``backward``, ``optimizer``, or ``none`` for the
operations no pass claims) — as a share of the traced window, averaged over
chips, in %.  How a name stack is read: ``harness/scopes.py``."""

import sys

from benchmarks.harness import scopes as S


def reduce(run, scopes: list | None = None, step_pass: str | None = None):
    chips = S.step_ops(run)
    if not chips or not any(chips):
        return None
    lo, hi = run.traced
    seconds = S.seconds(chips, scopes, step_pass)
    if seconds <= 0.0 and step_pass != "none":
        return None     # nothing carries these names (a program without them)
    share = 100.0 * seconds / (hi - lo)
    if share > 105.0:
        sys.exit(f"benchmark: scope share {scopes or ''} {step_pass or ''} reads "
                 f"{share} % of the traced window — operations counted twice")
    return share
