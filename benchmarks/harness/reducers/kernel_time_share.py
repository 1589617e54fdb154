"""Device time of the kernels whose name matches ``pattern`` (inside
programs matching ``module``, if given), as a share of the traced window,
averaged over chips, in %."""

from benchmarks.harness import trace as T


def reduce(run, pattern: str, module: str | None = None):
    if run.trace is None:
        return None
    lo, hi = run.traced
    seconds = T.kernel_seconds(run.trace, pattern, module)
    if seconds <= 0.0:
        return None
    return 100.0 * seconds / (hi - lo)
