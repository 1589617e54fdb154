"""The comparison that decides ``correct``: each number beside its limit,
printed in every run."""

from __future__ import annotations

import math

import numpy as np

from benchmarks.harness import weights


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

    def compared(self) -> dict:
        """Every number compared beside its limit, by name, for the result's
        line (a ``require`` reads 0 where it held and 1 where not, against
        the limit 0)."""
        return {name: {"value": value, "limit": limit}
                for name, value, limit, _ok in self.rows}

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


def layer_norms(flat: dict) -> dict:
    """Norm of every leaf, by canonical name; a leaf of the scanned stack
    (``weights.is_stacked``) per layer, ``name[l] -> ||.||``, any other leaf
    (an adapter on a layer outside the stack, on the head) as one entry."""
    out = {}
    for name, arr in flat.items():
        a = np.asarray(arr, np.float64)
        if weights.is_stacked(name):
            for l in range(a.shape[0]):
                out[f"{name}[{l}]"] = float(np.sqrt(np.sum(a[l] ** 2)))
        else:
            out[name] = float(np.sqrt(np.sum(a ** 2)))
    return out


def host(tree):
    """``tree`` on the host in float32."""
    import jax

    return jax.tree.map(lambda x: np.asarray(x, np.float32), tree)


def worst_leaf_gap(prog: dict, ref: dict) -> float:
    """The widest gap between the program's norm of a leaf and the
    reference's, measured against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some leaves are all but zero)."""
    norms = sorted(ref.values())
    floor = norms[len(norms) // 2]
    return max(abs(prog[k] - ref[k]) / max(ref[k], floor) for k in ref)
