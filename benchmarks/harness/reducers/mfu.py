"""Required FLOPs per token (the benchmark's count) times tokens per second
per chip, over the chip's published bf16 peak, in %.  Recomputed operations
are not in the count."""

from benchmarks.harness import counts


def reduce(run, count: str, rate: str):
    if rate not in run.end_to_end:
        return None
    per_token = getattr(counts, count)(run.conf, run.notes["seq"])
    return 100.0 * per_token * run.end_to_end[rate] / run.peaks["bf16_flops"]
