"""Device time by the program's own names.

JAX gives every operation a NAME STACK — the ``jax.named_scope``s and flax
modules open where it was traced, wrapped by the transformations it went
through — and XLA carries it into the compiled program's metadata; a fusion
keeps the stack of the instruction it was built around.  On a TPU the
profiler writes that stack into the stat ``tf_op`` of an ``XLA Ops`` event's
metadata (``xspace.py``).  For the QLoRA train step it reads (PERF.md
section 6, PR 24)::

    jit(_train_step)/jvp(LlamaForCausalLM)/while/body/closed_call/blocks/block/attn/q_proj/base_matmul/dot_general
    jit(_train_step)/transpose(jvp(LlamaForCausalLM))/while/body/closed_call/checkpoint/rematted_computation/blocks/block/mlp/up_proj/dequant_int4/convert_element_type
    jit(_train_step)/transpose(jvp(LlamaForCausalLM))/while/body/closed_call/checkpoint/blocks/block/mlp/down_proj/lora_delta/dot_general
    jit(_train_step)/jvp(loss)/jit(log_softmax)/reduce_max
    jit(_train_step)/optimizer/jit(clip)/mul

so a component is a scope's or a module's name, a structural word (``while``,
``body``, ``closed_call``, ``checkpoint``, ``rematted_computation``) or
either inside transformations ``jvp(...)``, ``transpose(...)``, ``jit(...)``;
the last component is the primitive.
"""

from __future__ import annotations

import dataclasses
import re

from benchmarks.harness import trace as T, xspace

STEP_MODULE = "jit__train_step"
PASSES = ("forward", "recompute", "backward", "optimizer")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


def stack(tf_op: str) -> tuple[set[str], set[str]]:
    """``(names, transformations)`` of a name stack: the program's own frame
    (a leading ``jit(...)``) and the primitive (the last component) left
    out."""
    parts = tf_op.removesuffix(":").split("/")[:-1]
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    names: set[str] = set()
    transforms: set[str] = set()
    for part in parts:
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            transforms.add(m.group(1))
            part = m.group(2)
        names.add(part)
    return names, transforms


def step_pass(names: set[str], transforms: set[str]) -> str | None:
    """Which pass of the train step an operation belongs to, by its name
    stack alone; ``None`` for one the stack does not place."""
    if "optimizer" in names:
        return "optimizer"
    if "rematted_computation" in names:
        return "recompute"      # replayed under remat, inside the backward loop
    if "transpose" in transforms or "checkpoint" in names:
        return "backward"
    if "jvp" in transforms:
        return "forward"
    return None


@dataclasses.dataclass(frozen=True)
class Op:
    seconds: float
    names: frozenset
    which_pass: str | None


def step_ops(run) -> list[list[Op]] | None:
    """Per chip, the leaf operations that ran inside the train step's
    program, clipped to the traced window.  ``None`` without a device trace."""
    if run.trace is None:
        return None
    if hasattr(run, "_step_ops"):
        return run._step_ops
    lo, hi = run.traced
    chips = []
    for plane in xspace.run_planes(run) or []:
        if not T.DEVICE_PLANE.match(plane.name):
            continue
        spans = [(m.start, m.end) for m in plane.lines.get(T.MODULES_LINE, [])
                 if STEP_MODULE in m.name]
        ops = []
        for e in plane.lines.get(T.OPS_LINE, []):
            if T.is_container(e.name):
                continue
            mid = (e.start + e.end) / 2     # ends meet to a rounding
            if not any(s <= mid <= t for s, t in spans):
                continue
            seconds = min(e.end, hi) - max(e.start, lo)
            if seconds <= 0.0:
                continue
            names, transforms = stack(str(e.stats.get("tf_op", "")))
            ops.append(Op(seconds, frozenset(names),
                          step_pass(names, transforms)))
        chips.append(ops)
    run._step_ops = chips
    return chips


def seconds(chips: list[list[Op]], scopes=None, which_pass=None) -> float:
    """Summed seconds, averaged over chips, of the operations under any of
    ``scopes`` (every one, when ``None``) and of ``which_pass`` (any, when
    ``None``; ``"none"`` for those no pass claims)."""
    if which_pass not in (None, "none", *PASSES):
        raise ValueError(f"no such pass of the step: {which_pass!r}")
    want = set(scopes) if scopes else None

    def take(op: Op) -> bool:
        if want is not None and not (want & op.names):
            return False
        if which_pass == "none":
            return op.which_pass is None
        return which_pass is None or op.which_pass == which_pass

    return sum(op.seconds for ops in chips for op in ops if take(op)) \
        / max(1, len(chips))


def host_spans(run, names) -> list[xspace.Event]:
    """The program's annotations called any of ``names`` in the host planes
    of the run's trace, clipped to the traced window (unclipped where the
    trace holds no device operation to give one; none without a trace)."""
    names = set(names)
    lo, hi = run.traced if run.trace is not None else (float("-inf"), float("inf"))
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for plane in xspace.run_planes(run) or []
            if plane.name.startswith("/host:")
            for events in plane.lines.values()
            for e in events
            if e.name in names and e.end > lo and e.start < hi]
