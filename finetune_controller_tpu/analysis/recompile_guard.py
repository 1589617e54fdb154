"""Runtime complement to the static recompile rules: count compilations.

The static rules (``recompile-jit-in-loop``, ``recompile-fresh-callable``)
catch the lexical traps, but the expensive production failure is dynamic: a
Python scalar or shape that varies per step flows into a jitted function's
signature and every step silently pays a full XLA compile.  On a CPU test
run that is a warm fuzzy 100 ms; on a v5e slice it is minutes per step of
burned TPU time that profiles as "mysteriously slow", not as an error.

:class:`RecompileGuard` wraps already-jitted callables and fingerprints each
call's *compilation signature* — pytree structure plus per-leaf
(shape, dtype, weak_type).  Python numeric scalars contribute only their
TYPE (jit traces them as weak-typed arrays, so a varying value does not
recompile); any other non-array leaf can only reach jit as a static
argument, where its value IS part of the cache key.  A new signature means
a new trace/compile.  Past ``budget`` distinct signatures the guard warns once
(``on_excess="warn"``) or raises :class:`RecompileBudgetExceeded`
(``on_excess="raise"``), quoting the program's name and what its last compile
cost as the process's start-up log heard it (``obs/trace.py::StartupLog``).  Where the wrapped fn exposes jit's own
``_cache_size()`` the guard cross-checks it, so signatures the fingerprint
cannot see (e.g. closure captures) still surface.

Threaded into the hot paths behind config flags:

* ``TrainConfig.recompile_budget`` (0 = off) wraps the trainer's step/eval
  jits; ``TrainConfig.recompile_action`` picks warn vs raise;
* the benchmark (``benchmarks/harness/drivers/train.py``) sets a budget of
  1 with ``"raise"`` — a recompiling timed run is a measurement bug and must
  fail loudly, not print a slow number.
"""

from __future__ import annotations

import logging
from typing import Any, Callable

import jax

logger = logging.getLogger(__name__)

__all__ = ["RecompileBudgetExceeded", "RecompileGuard"]


class RecompileBudgetExceeded(RuntimeError):
    """More distinct jit signatures than the configured budget."""


def _leaf_signature(leaf: Any) -> Any:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        return ("array", tuple(shape), str(dtype),
                bool(getattr(leaf, "weak_type", False)))
    if isinstance(leaf, (bool, int, float, complex)):
        # jit traces a Python scalar as a weak-typed 0-d array: the TYPE is
        # part of its cache key, the value is not — fingerprinting the value
        # would flag recompiles that never happen
        return ("pyscalar", type(leaf).__name__)
    # any other leaf can only reach a jitted fn as a STATIC argument, where
    # its value genuinely keys the cache
    try:
        hash(leaf)
        return ("static", leaf)
    except TypeError:
        return ("static", repr(leaf))


def signature_of(*args: Any, **kwargs: Any) -> tuple:
    """The (structure, leaf-signature) fingerprint jit keys its cache on."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    # the treedef object itself is hashable/eq-comparable; str()-ifying a
    # TrainState-sized treedef every step would be measurable host overhead
    # inside the very windows the benchmark times
    return (treedef, tuple(_leaf_signature(x) for x in leaves))


def _compiled_so_far(fn: Any) -> str:
    """What the start-up log (obs/trace.py::StartupLog) heard of ``fn``'s
    program so far — which step recompiles, and what a compile of it costs."""
    from ..obs import trace

    name = f"jit({getattr(fn, '__name__', '')})"
    heard = trace.STARTUP.programs.get(name)
    if heard is None:
        return ""
    cost = heard["trace_s"] + heard["lower_s"] + heard["backend_s"]
    return (
        f" {name} has compiled {heard['count']} time(s) in this process, "
        f"the last in {cost:.2f} s (trace {heard['trace_s']:.2f}, lower "
        f"{heard['lower_s']:.2f}, backend {heard['backend_s']:.2f}, cache "
        f"{heard['cache']}): another signature pays that again."
    )


class RecompileGuard:
    """Count distinct compilation signatures across a set of wrapped fns.

    One guard instance spans a whole training run: the budget covers the
    SUM of compilations over every label (init + per-batch-structure step +
    eval is the healthy ceiling a caller budgets for).
    """

    def __init__(
        self,
        budget: int,
        *,
        on_excess: str = "warn",   # "warn" | "raise"
        name: str = "recompile-guard",
    ):
        if budget < 1:
            raise ValueError(f"budget must be >= 1, got {budget}")
        if on_excess not in ("warn", "raise"):
            raise ValueError(f"on_excess must be 'warn' or 'raise', got {on_excess!r}")
        self.budget = budget
        self.on_excess = on_excess
        self.name = name
        self._seen: dict[str, set[tuple]] = {}
        self._warned = False

    @property
    def compilations(self) -> int:
        """Distinct signatures observed so far, across all labels."""
        return sum(len(s) for s in self._seen.values())

    def counts(self) -> dict[str, int]:
        return {label: len(sigs) for label, sigs in self._seen.items()}

    def check(self, label: str, sig: tuple, fn: Any = None) -> None:
        sigs = self._seen.setdefault(label, set())
        if sig in sigs:
            return
        sigs.add(sig)
        total = self.compilations
        # cross-check against jit's real cache where exposed: captures the
        # recompiles our arg fingerprint cannot see (closure-captured
        # scalars, donated-buffer changes)
        cache_size = getattr(fn, "_cache_size", None)
        if callable(cache_size):
            try:
                total = max(total, int(cache_size()))
            except Exception:  # pragma: no cover - jax internals drift
                logger.debug("jit _cache_size() probe failed", exc_info=True)
        if total <= self.budget:
            if total > 1:
                logger.info(
                    "%s: compilation %d/%d (label=%s)",
                    self.name, total, self.budget, label,
                )
            return
        detail = (
            f"{self.name}: {total} distinct jit compilations exceed the "
            f"budget of {self.budget} (per label: {self.counts()}). A "
            "signature changing per call usually means a shape or a static "
            "Python value varies per step — pad to a fixed shape or hoist "
            "the varying value into an array argument."
            + _compiled_so_far(fn)
        )
        if self.on_excess == "raise":
            raise RecompileBudgetExceeded(detail)
        if not self._warned:  # one warning, not one per extra compile
            self._warned = True
            logger.warning("%s", detail)

    def wrap(self, fn: Callable, label: str) -> Callable:
        """Wrap a (jitted) callable; each call checks its signature first."""

        def guarded(*args: Any, **kwargs: Any):
            self.check(label, signature_of(*args, **kwargs), fn)
            return fn(*args, **kwargs)

        guarded.__name__ = f"guarded_{getattr(fn, '__name__', label)}"
        guarded.__wrapped__ = fn
        # AOT consumers (train/aot.py) lower the step jit without calling it
        if hasattr(fn, "lower"):
            guarded.lower = fn.lower
        return guarded
