"""Hand-written TPU Pallas kernels for the ops where XLA's defaults lose.

Benchmark-first policy (SURVEY.md §7: 'benchmark first, hand-write second'):
a kernel is timed in a benchmark cell's trace (``PERF.md`` §3, §5).
Everything runs in interpreter mode on CPU so the test suite exercises kernel
logic without TPU hardware.
"""

from __future__ import annotations

import types

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


def _call(fn, *args, **kwargs):
    return fn(*args, **kwargs)


#: ``fn(*args, **kwargs)`` from a frame with ROOM above it, for tracing a
#: kernel's body.  CPython (3.11 on) keeps its frames in 16 KiB chunks and
#: unmaps a chunk when the chunk's first frame returns.  A kernel's body is
#: ONE frame that makes a thousand calls while it is traced; where that frame
#: lies at a chunk's end, every one of those calls maps a chunk and unmaps it
#: again.  On the chip's host a trace of ``ssd_scan_fwd``'s body took 0.07 s
#: or 1.7 s by the depth of the stack it was called at, and 2 to 3 s of a
#: mixer step's trace went there (``PERF.md`` section 6, PR 42; PR 38 met the
#: same cliff in an import).  A frame too large for a chunk gets a chunk of
#: its own of twice its size (32 KiB of slots: a 64 KiB chunk), so the body
#: and what it calls have 31 KiB before the next chunk's end — flat at 0.075 s
#: over 32 depths.
call_with_room = types.FunctionType(
    _call.__code__.replace(co_stacksize=4096, co_name="call_with_room"),
    globals(), "call_with_room")
