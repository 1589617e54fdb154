"""Runtime transfer guard: no silent device↔host syncs in the hot windows.

The static rules (``host-sync-in-jit`` and its transitive v2) catch syncs a
reader can see; the expensive production regression is the one nobody
wrote: a debug ``jax.device_get`` left in the step path, a numpy array
slipping into a jitted call (implicit host→device transfer every step), a
logging helper that materialises a device value per token.  On CPU tests
these are free; on a TPU they serialize the dispatch pipeline and profile
as "mysteriously slow", never as an error.

:class:`TransferGuard` wraps the two host-side hot windows — the trainer's
jitted step call and the serve engine's decode dispatch — in a guard that
makes any transfer a LOUD failure:

* **jax's native transfer guards**: inside the window,
  ``jax.transfer_guard_host_to_device("disallow")`` (an np array reaching
  the jit boundary raises on every backend) and
  ``jax.transfer_guard_device_to_host("disallow_explicit")`` (any
  device→host materialisation raises — on accelerators; the CPU backend's
  arrays ARE host memory, so XLA never reports a d2h transfer there);
* **a thread-local ``jax.device_get`` trap**: installed once, the wrapper
  checks a thread-local "inside a guarded window" flag and trips the guard
  — this is what makes an injected ``jax.device_get`` abort the window on
  the CPU CI box too, and it is thread-safe by construction (the serve
  engine steps in worker threads while other threads use jax freely).

The first call per label is exempt: tracing/compilation legitimately
transfers closure constants host→device, and the guard targets the steady
state, not the compile.

Knobs (docs/static_analysis.md § Transfer guard):

* ``TrainConfig.transfer_guard`` — ``"raise"`` / ``"warn"`` / ``"off"``;
  the empty default inherits ``FTC_TRANSFER_GUARD`` from the env;
* ``FTC_TRANSFER_GUARD`` — same values, read by the serve engine and as
  the trainer fallback; off when unset;
* the benchmark (``benchmarks/harness/drivers/train.py``) arms ``raise``
  for the whole run: a silently reintroduced sync ABORTS the run instead of
  printing a slow number — the ``recompile_guard`` contract, for transfers;
  ``tests/test_transfer_guard.py`` holds the trainer step and the serve
  decode to zero trips under ``raise``;
* ``FTC_FAULT_TRANSFER=1`` — chaos hand for tests: the guard itself
  injects a ``jax.device_get`` inside the window, proving the abort path.

``action="warn"`` swaps the disallow levels for jax's ``log`` levels and
downgrades trap trips to a once-per-label warning — observation mode for
triaging an existing pipeline without stopping it.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
from typing import Any, Callable

import jax

logger = logging.getLogger(__name__)

__all__ = ["TransferGuard", "TransferGuardError"]


class TransferGuardError(RuntimeError):
    """A device↔host transfer happened inside a guarded hot window."""


_WINDOW = threading.local()  # .guard / .label while inside a window
_trap_installed = False
_orig_device_get: Callable | None = None


def _install_device_get_trap() -> None:
    """Wrap ``jax.device_get`` once, process-wide: outside a window the
    wrapper is a thread-local read and a call — measured noise.  Inside a
    window it trips the active guard (works on EVERY backend, including
    CPU where XLA's own d2h guard cannot see a transfer)."""
    global _trap_installed, _orig_device_get
    if _trap_installed:
        return
    _trap_installed = True
    _orig_device_get = jax.device_get

    def guarded_device_get(x: Any) -> Any:
        guard = getattr(_WINDOW, "guard", None)
        if guard is not None:
            guard._trip(
                f"jax.device_get inside guarded window "
                f"{getattr(_WINDOW, 'label', '?')!r}"
            )
        return _orig_device_get(x)

    guarded_device_get.__wrapped__ = _orig_device_get
    jax.device_get = guarded_device_get


def _is_transfer_error(exc: BaseException) -> bool:
    text = str(exc)
    return "isallowed" in text and "transfer" in text


class TransferGuard:
    """Guard hot windows against device↔host transfers.

    One instance spans a run (trainer) or an engine lifetime (serve);
    ``trips`` counts violations observed — the default-on clean-path
    assertion is ``trips == 0``.
    """

    def __init__(
        self,
        action: str = "raise",  # "raise" | "warn"
        *,
        name: str = "transfer-guard",
        skip_first: bool = True,
        inject_fault: bool | None = None,
    ):
        if action not in ("raise", "warn"):
            raise ValueError(
                f"action must be 'raise' or 'warn', got {action!r}"
            )
        self.action = action
        self.name = name
        self.skip_first = skip_first
        self.trips = 0
        self._warned: set[str] = set()
        self._calls: dict[str, int] = {}
        #: chaos hand: perform a real jax.device_get INSIDE the window so
        #: tests prove the abort path end to end
        self._fault = (
            inject_fault if inject_fault is not None
            else os.environ.get("FTC_FAULT_TRANSFER", "") not in ("", "0")
        )
        _install_device_get_trap()

    @classmethod
    def from_env(
        cls, default: str = "off", *, name: str = "transfer-guard"
    ) -> "TransferGuard | None":
        """Build from ``FTC_TRANSFER_GUARD`` (off/warn/raise); None = off."""
        mode = os.environ.get("FTC_TRANSFER_GUARD", default).strip().lower()
        if mode in ("", "0", "off", "false"):
            return None
        if mode in ("1", "on", "true"):
            mode = "raise"
        return cls(mode, name=name)

    # ---- the window --------------------------------------------------------

    def _trip(self, what: str) -> None:
        self.trips += 1
        detail = (
            f"{self.name}: {what} — a device<->host sync in a guarded hot "
            "window serializes the dispatch pipeline every step. Move the "
            "transfer outside the window (log-cadence host reads, explicit "
            "device_put before dispatch), or run with "
            "FTC_TRANSFER_GUARD=warn to observe without aborting."
        )
        if self.action == "raise":
            raise TransferGuardError(detail)
        label = getattr(_WINDOW, "label", "?")
        if label not in self._warned:
            self._warned.add(label)
            logger.warning("%s", detail)

    @contextlib.contextmanager
    def window(self, label: str):
        """Guard one hot-window execution.  Re-entrant per thread (the
        inner window wins); the first call per label is exempt so compile-
        time constant transfers don't trip the steady-state guard."""
        n = self._calls.get(label, 0)
        self._calls[label] = n + 1
        if self.skip_first and n == 0:
            yield
            return
        prev_guard = getattr(_WINDOW, "guard", None)
        prev_label = getattr(_WINDOW, "label", None)
        _WINDOW.guard, _WINDOW.label = self, label
        h2d = "disallow" if self.action == "raise" else "log"
        d2h = "disallow_explicit" if self.action == "raise" else "log_explicit"
        try:
            with jax.transfer_guard_host_to_device(h2d), \
                    jax.transfer_guard_device_to_host(d2h):
                yield
        except TransferGuardError:
            raise
        except Exception as exc:
            if _is_transfer_error(exc):
                self.trips += 1
                raise TransferGuardError(
                    f"{self.name}: XLA blocked a transfer inside window "
                    f"{label!r}: {exc}"
                ) from exc
            raise
        finally:
            _WINDOW.guard, _WINDOW.label = prev_guard, prev_label

    def run(self, label: str, fn: Callable, *args: Any, **kwargs: Any) -> Any:
        """Run ``fn`` inside a guarded window; the fault hand (if armed)
        device_gets the result INSIDE the window."""
        with self.window(label):
            out = fn(*args, **kwargs)
            self._maybe_inject(out)
            return out

    def _maybe_inject(self, out: Any) -> None:
        if not self._fault:
            return
        leaves = [
            x for x in jax.tree_util.tree_leaves(out)
            if hasattr(x, "shape") and hasattr(x, "dtype")
        ]
        if leaves:
            jax.device_get(leaves[0])

    def wrap(self, fn: Callable, label: str) -> Callable:
        """Wrap a (jitted) callable so every call runs in a guarded window."""

        def guarded(*args: Any, **kwargs: Any):
            return self.run(label, fn, *args, **kwargs)

        guarded.__name__ = f"transfer_guarded_{getattr(fn, '__name__', label)}"
        guarded.__wrapped__ = fn
        # AOT consumers (train/aot.py) lower the step jit without calling it
        if hasattr(fn, "lower"):
            guarded.lower = fn.lower
        return guarded
