"""Required FLOPs per token (the benchmark's count) times tokens per second
per chip, over the chip's published bf16 peak, in %.  Recomputed operations
are not in the count.  ``count`` is a function ``(conf, seq)`` of
``harness/counts.py`` or, with ``counts``, of the module of that name
(``manifest.counts``)."""

from benchmarks.harness import counts as default_counts


def reduce(run, count: str, rate: str, counts: str | None = None):
    if rate not in run.end_to_end:
        return None
    module = run.manifest.counts(counts) if counts else default_counts
    per_token = getattr(module, count)(run.conf, run.notes["seq"])
    return 100.0 * per_token * run.end_to_end[rate] / run.peaks["bf16_flops"]
