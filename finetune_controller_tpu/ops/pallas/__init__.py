"""Hand-written TPU Pallas kernels for the ops where XLA's defaults lose.

Benchmark-first policy (SURVEY.md §7: 'benchmark first, hand-write second'):
a kernel is timed in a benchmark cell's trace (``PERF.md`` §3, §5).
Everything runs in interpreter mode on CPU so the test suite exercises kernel
logic without TPU hardware.
"""

from __future__ import annotations

import jax


def bare_mosaic_call_ok() -> bool:
    """Whether a Mosaic (Pallas TPU) call may be issued bare where the caller
    is being traced.  The chip's compiler cannot partition such a call, so
    under the trainer's mesh (``parallel.ring.ring_mesh``) of several devices
    it must run inside ``shard_map``: bare is right with no mesh, a
    one-device mesh, or a caller already inside a ``shard_map`` body."""
    from ...parallel.ring import get_ring_mesh

    mesh = get_ring_mesh()
    return (
        mesh is None
        or mesh.size == 1
        or bool(jax.sharding.get_abstract_mesh().manual_axes)
    )
