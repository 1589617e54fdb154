"""The comparison that decides ``correct``: each number beside its limit,
printed in every run."""

from __future__ import annotations

import math


class Comparison:
    def __init__(self) -> None:
        self.rows: list[tuple[str, float, float, bool]] = []

    def check(self, name: str, value: float, limit: float) -> bool:
        """``value`` must be a finite number at or under ``limit``."""
        ok = isinstance(value, (int, float)) and math.isfinite(value) \
            and value <= limit
        self.rows.append((name, float(value), float(limit), ok))
        print(f"compare {name}: {value:.6g} (limit {limit:g}) "
              f"{'ok' if ok else 'NOT CORRECT'}", flush=True)
        return ok

    def require(self, name: str, ok: bool, detail: str = "") -> bool:
        self.rows.append((name, 0.0 if ok else 1.0, 0.0, bool(ok)))
        print(f"compare {name}: {'ok' if ok else 'NOT CORRECT'} {detail}",
              flush=True)
        return bool(ok)

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some leaves are all but zero)."""
    norms = sorted(ref.values())
    floor = norms[len(norms) // 2]
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in ref)
