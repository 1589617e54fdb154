"""Runtime shard audit: live state must carry the rule table's shardings.

The static sharding rules (``rules_sharding.py``) prove the PARTITION TABLE
is sound, and the AOT collective audit (``collective_audit.py``) proves the
COMPILED STEP moves what the docs say it moves — but neither sees the
arrays a running job actually holds.  The production bug class left over is
silent replication: an elastic restore, a checkpoint resharding path, or a
serve load that lands a leaf with the wrong (usually fully-replicated)
sharding.  Nothing fails — GSPMD inserts the resharding copies at the next
jit boundary and every step quietly pays full-param traffic, profiling as
"mysteriously slow", never as an error.

:class:`ShardAuditor` is the ``transfer_guard``-shaped complement: at the
checkpoint/restore boundaries (``train/trainer.py``) and on serve load
(``serve/loader.py``) it walks the live state tree and asserts each
device leaf's ``.sharding`` equals the expected :class:`NamedSharding`
from the rule table.  Host-side (numpy) leaves carry no sharding and are
skipped — the audit targets device state only.

Knobs (docs/static_analysis.md § Shard audit):

* ``TrainConfig.shard_audit`` — ``"raise"`` / ``"warn"`` / ``"off"``; the
  empty default inherits ``FTC_SHARD_AUDIT`` from the env;
* ``FTC_SHARD_AUDIT`` — same values, read by the serve loader and as the
  trainer fallback; off when unset;
* the benchmark (``benchmarks/harness/drivers/train.py``) arms ``raise``:
  a mis-sharded timed run ABORTS instead of printing a slow number;
* ``FTC_FAULT_SHARD=1`` — chaos hand for tests: the auditor itself
  re-``device_put``s one sharded leaf as fully replicated before checking,
  proving the abort path end to end.

The comparison is STRUCTURAL (``NamedSharding.__eq__``: mesh + spec), not
"semantic equivalence on this device count" — on the 1-device CI backend
every sharding is semantically equivalent to every other, and the audit
must still catch a replicated leaf there.  Leaves whose sharding is not a
``NamedSharding`` (e.g. a ``SingleDeviceSharding`` from host-side
construction) fall back to ``is_equivalent_to``, so single-device tests
don't false-positive on arrays that never crossed a mesh.

Process-wide counters (``metrics_snapshot``) surface as
``ftc_shard_audit_{checks,violations}_total`` on ``/metrics``
(docs/observability.md catalog).
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any

logger = logging.getLogger(__name__)

__all__ = [
    "ShardAuditor",
    "ShardAuditError",
    "incr",
    "metrics_snapshot",
]

#: process-wide counters (the transport/__init__.py idiom): plain ints
#: behind a lock, snapshot by the controller's /metrics exposition
METRICS: dict[str, int] = {
    "checks_total": 0,
    "violations_total": 0,
}
_METRICS_LOCK = threading.Lock()


def incr(name: str, n: int = 1) -> None:
    with _METRICS_LOCK:
        METRICS[name] = METRICS.get(name, 0) + n


def metrics_snapshot() -> dict[str, int]:
    with _METRICS_LOCK:
        return dict(METRICS)


class ShardAuditError(RuntimeError):
    """A live state leaf's sharding diverged from the rule table."""


class ShardAuditor:
    """Assert live state leaves carry their rule-table shardings.

    One instance spans a trainer run or a serve load; ``checks`` /
    ``violations`` count leaves audited and divergences observed — the
    default-on clean-path assertion is ``violations == 0``.
    """

    def __init__(
        self,
        action: str = "raise",  # "raise" | "warn"
        *,
        name: str = "shard-audit",
        inject_fault: bool | None = None,
    ):
        if action not in ("raise", "warn"):
            raise ValueError(
                f"action must be 'raise' or 'warn', got {action!r}"
            )
        self.action = action
        self.name = name
        self.checks = 0
        self.violations = 0
        self._warned: set[str] = set()
        #: chaos hand: re-device_put ONE sharded leaf as replicated before
        #: checking, so tests prove the abort path with a REAL
        #: mis-sharded array, not a mocked comparison
        self._fault = (
            inject_fault if inject_fault is not None
            else os.environ.get("FTC_FAULT_SHARD", "") not in ("", "0")
        )
        self._fault_fired = False

    @classmethod
    def from_env(
        cls, default: str = "off", *, name: str = "shard-audit"
    ) -> "ShardAuditor | None":
        """Build from ``FTC_SHARD_AUDIT`` (off/warn/raise); None = off."""
        mode = os.environ.get("FTC_SHARD_AUDIT", default).strip().lower()
        if mode in ("", "0", "off", "false"):
            return None
        if mode in ("1", "on", "true"):
            mode = "raise"
        return cls(mode, name=name)

    # ---- the audit ---------------------------------------------------------

    def _leaf_matches(self, leaf: Any, expected: Any) -> bool:
        import jax

        actual = getattr(leaf, "sharding", None)
        if actual is None:
            return True  # host-side (numpy) leaf — not audited
        if actual == expected:
            return True
        if not isinstance(actual, jax.sharding.NamedSharding):
            # a SingleDeviceSharding etc. never spells an intent; accept it
            # when it lays bytes out identically to the expectation
            try:
                return actual.is_equivalent_to(expected, leaf.ndim)
            except Exception:  # ftc: ignore[silent-except] -- an
                # incomparable sharding (cross-mesh, exotic layout) IS a
                # violation; the caller reports path + both specs
                return False
        return False

    def _inject(self, leaf: Any, expected: Any) -> Any:
        """The fault hand: return a REAL fully-replicated copy of ``leaf``."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            leaf, NamedSharding(expected.mesh, PartitionSpec())
        )

    def audit(self, tree: Any, expected: Any, *, label: str) -> int:
        """Walk ``tree`` against the same-structure ``expected`` shardings;
        returns the number of violations found at this boundary (and raises
        on the first batch of them when ``action == "raise"``)."""
        import jax

        bad: list[str] = []
        checked = 0
        leaves = jax.tree_util.tree_leaves_with_path(tree)
        exp_leaves = jax.tree_util.tree_leaves(
            expected, is_leaf=lambda x: hasattr(x, "spec")
        )
        for (kp, leaf), exp in zip(leaves, exp_leaves):
            if not hasattr(exp, "spec"):
                continue
            if (
                self._fault
                and not self._fault_fired
                and getattr(leaf, "sharding", None) is not None
                and len(exp.spec) > 0
            ):
                self._fault_fired = True
                leaf = self._inject(leaf, exp)
            checked += 1
            if not self._leaf_matches(leaf, exp):
                actual = getattr(leaf, "sharding", None)
                bad.append(
                    f"{jax.tree_util.keystr(kp)}: expected "
                    f"{getattr(exp, 'spec', exp)}, found "
                    f"{getattr(actual, 'spec', actual)}"
                )
        self.checks += checked
        incr("checks_total", checked)
        if not bad:
            return 0
        self.violations += len(bad)
        incr("violations_total", len(bad))
        shown = "; ".join(bad[:4]) + (
            f"; … {len(bad) - 4} more" if len(bad) > 4 else ""
        )
        detail = (
            f"{self.name}: {len(bad)} leaf/leaves mis-sharded at {label!r} — "
            f"{shown}. A leaf that lost its rule-table sharding (usually to "
            "full replication) makes every subsequent step pay a silent "
            "GSPMD reshard; fix the restore/load path, or run with "
            "FTC_SHARD_AUDIT=warn to observe without aborting."
        )
        if self.action == "raise":
            raise ShardAuditError(detail)
        if label not in self._warned:
            self._warned.add(label)
            logger.warning("%s", detail)
        return len(bad)
