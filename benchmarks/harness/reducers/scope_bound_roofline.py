"""Roofline share of a named part of the train step that either peak may
bound: the least time the chip could take for what its tokens NEED — the
larger of ``count`` FLOPs over the bf16 peak and ``nbytes`` bytes over the HBM
peak, both functions ``(conf)`` per token of the module ``counts``
(``manifest.counts``) — against the device time of the operations under
``scopes`` in the traced steps, in %.  Recomputed operations are in the time
and not in the need.  Nothing where no operation carries the names (a program
without them)."""

from benchmarks.harness import counts as C, scopes as S


def reduce(run, scopes: list, count: str, nbytes: str, counts: str):
    chips = S.step_ops(run)
    if not chips:
        return None
    spent = S.seconds(chips, scopes)
    if spent <= 0.0:
        return None
    tokens = (run.notes["traced_steps"] * run.notes["batch"] * run.notes["seq"]
              / len(chips))
    module = run.manifest.counts(counts)
    need, bound = C.roofline_seconds(getattr(module, count)(run.conf) * tokens,
                                     getattr(module, nbytes)(run.conf) * tokens,
                                     run.peaks)
    print(f"scope roofline {scopes}: bound by {bound}; needs {need:.4f} s of "
          f"{spent:.4f} s in {run.notes['traced_steps']} traced step(s)",
          flush=True)
    return 100.0 * need / spent
