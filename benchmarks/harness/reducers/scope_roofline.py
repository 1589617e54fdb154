"""Roofline share of a named part of the train step: the FLOPs its tokens
NEED (``count``, a function ``(conf)`` of ``harness/scope_counts.py`` or, with
``counts``, of the module of that name — ``manifest.counts`` —, per token) over
the chip's bf16 peak, against the device time of the operations under
``scopes`` in the traced steps, in %.  Recomputed operations are in the time
and not in the need."""

from benchmarks.harness import scope_counts, scopes as S


def reduce(run, scopes: list, count: str, counts: str | None = None):
    chips = S.step_ops(run)
    if not chips:
        return None
    spent = S.seconds(chips, scopes)
    if spent <= 0.0:
        return None
    tokens = (run.notes["traced_steps"] * run.notes["batch"] * run.notes["seq"]
              / len(chips))
    module = run.manifest.counts(counts) if counts else scope_counts
    need = getattr(module, count)(run.conf) * tokens / run.peaks["bf16_flops"]
    print(f"scope roofline {scopes}: needs {need:.4f} s of {spent:.4f} s in "
          f"{run.notes['traced_steps']} traced step(s)", flush=True)
    return 100.0 * need / spent
