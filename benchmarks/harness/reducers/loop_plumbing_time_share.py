"""Device time of the train step's leaf operations that run in the body of
the layer loop (``loop``: the structural names of a scanned stack's loop)
and under none of ``inside`` (the name every operation of a scanned layer's
own modules carries) — what the loop itself does: slicing each layer's
leaves out of the stacked arrays (a copy, where a custom call needs an
operand whole), stacking what the forward pass leaves for the backward one —
as a share of the traced window, averaged over chips, in %.  ``0.0`` where
the loop's body holds nothing but its layers' operations.  How a name stack
is read: ``harness/scopes.py``."""

from benchmarks.harness import scopes as S


def reduce(run, loop: list, inside: list):
    chips = S.step_ops(run)
    if not chips or not any(chips):
        return None
    lo, hi = run.traced
    loop, inside = set(loop), set(inside)
    seconds = sum(op.seconds for ops in chips for op in ops
                  if loop <= op.names and not inside & op.names) / len(chips)
    return 100.0 * seconds / (hi - lo)
