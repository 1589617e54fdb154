"""Pipeline parallelism (GPipe) over the ``pp`` mesh axis.

SURVEY.md §2.3 lists PP as the one parallelism strategy absent from both the
reference (which never sees tensors) and round 1. This is the TPU-native
take: layer-stacked parameters (the ``nn.scan`` representation the Llama
family already uses) are sharded on their leading layer axis over ``pp``, so
each device holds a contiguous *stage* of ``L / pp`` layers. Microbatches
stream through stages under ``shard_map``; activations hop stage → stage via
``jax.lax.ppermute`` (nearest-neighbour ICI traffic), and the whole schedule
is a differentiable ``lax.scan`` over ticks, so reverse-mode autodiff derives
the backward pipeline (activation hops reverse through the ppermute
transpose) for free — no hand-written backward schedule.

Schedule: plain GPipe with ``M`` microbatches over ``P`` stages,
``T = M + P − 1`` ticks and the classic ``(P−1)/T`` bubble
(:func:`bubble_fraction` — the trainer logs it for every pp run). Idle ticks
still execute the stage body (SPMD — every device runs the same program) with
their output masked out, which costs the same wall-clock the bubble would
anyway.

**Why GPipe and not 1F1B (a considered decision, round 5):** 1F1B's benefit
over GPipe is peak-activation memory — it holds at most ``P`` microbatches'
activations where GPipe holds ``M``. It does NOT shrink the bubble (same
``(P−1)/(M+P−1)``). The cost would be structural: this implementation gets
its backward pipeline *derived by autodiff* from a single differentiable
``lax.scan`` — reverse-mode replays the ticks backward and transposes the
``ppermute`` hops automatically. 1F1B interleaves forward and backward ticks
in one schedule, which autodiff cannot derive; it needs a hand-written
backward schedule with manual activation stashing (and custom_vjp through
the collectives). On TPU the memory lever 1F1B buys is already covered
cheaper: per-layer remat (``remat_policy``) bounds stashed activations to
the remat boundaries, and ``M`` is a free dial (the trainer's default
``M = 2P`` keeps the bubble ≤ ``(P−1)/(3P−1)`` ≈ 33% worst-case, 20% at
``P=2``). If a future profile shows activation residency — not bubble — as
the pp bottleneck at a scale remat can't hold, that is the signal to revisit.

Composition: ``pp × dp`` (the classic GPipe layout). Weights within a stage
are replicated across ``dp``; combining pp with fsdp/tp/sp is rejected at
mesh-resolution time rather than silently mis-sharded. (pp × fsdp would need
manual per-stage weight all-gathers inside the shard_map body — XLA's
automatic FSDP gathering doesn't reach in there; rejected rather than
half-supported.)
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import AxisNames as Ax

# stage body: (stage_params, x_mb, positions_mb, segids_mb) -> y_mb
StageFn = Callable[[Any, jax.Array, jax.Array, jax.Array | None], jax.Array]


def bubble_fraction(n_micro: int, pp: int) -> float:
    """GPipe idle fraction: ``(P−1) / (M + P − 1)`` — the share of the
    ``M + P − 1`` ticks each stage spends masked out."""
    return (pp - 1) / (n_micro + pp - 1)


def default_pp_microbatches(local_batch: int, pp: int) -> int:
    """The trainer's default schedule: the largest microbatch count ≤ 2·pp
    that divides the per-data-shard batch (2·pp halves the GPipe bubble).
    One definition — the trainer and the AOT report both call this, so the
    reported schedule cannot drift from what actually runs."""
    return max(
        (m for m in range(1, 2 * pp + 1) if local_batch % m == 0), default=1
    )


def validate_pp_mesh(mesh: Mesh) -> None:
    """GPipe composes with dp only; other intra-slice axes must be 1."""
    for axis in (Ax.FSDP, Ax.TENSOR, Ax.SEQ, Ax.EXPERT):
        if mesh.shape.get(axis, 1) > 1 and mesh.shape.get(Ax.PIPE, 1) > 1:
            raise ValueError(
                f"pipeline parallelism composes with dp only; axis {axis!r} "
                f"has size {mesh.shape[axis]} (use pp×dp, or drop pp)"
            )


def _gpipe_local(
    stage_params: Any,          # leading dim = L/P (this stage's layers)
    x: jax.Array,               # (B_loc, S, D) activations after embedding
    positions: jax.Array,       # (B_loc, S)
    segment_ids: jax.Array,     # (B_loc, S)
    *,
    stage_fn: StageFn,
    n_micro: int,
    axis_name: str,
) -> jax.Array:
    p_count = axis_size(axis_name)
    p_idx = jax.lax.axis_index(axis_name)
    b_loc, s, d = x.shape
    if b_loc % n_micro:
        raise ValueError(f"local batch {b_loc} not divisible by {n_micro} microbatches")
    b_mb = b_loc // n_micro

    x_mb = x.reshape(n_micro, b_mb, s, d)
    pos_mb = positions.reshape(n_micro, b_mb, s)
    seg_mb = segment_ids.reshape(n_micro, b_mb, s)

    ticks = n_micro + p_count - 1
    perm_fwd = [(i, i + 1) for i in range(p_count - 1)]

    def tick(carry, t):
        buf, outs = carry
        # which microbatch this stage works on at tick t (GPipe diagonal)
        mb = t - p_idx
        active = (mb >= 0) & (mb < n_micro)
        mb_c = jnp.clip(mb, 0, n_micro - 1)
        pos = jax.lax.dynamic_index_in_dim(pos_mb, mb_c, keepdims=False)
        seg = jax.lax.dynamic_index_in_dim(seg_mb, mb_c, keepdims=False)
        y = stage_fn(stage_params, buf, pos, seg)
        # idle ticks produce garbage: mask it so it neither propagates nor
        # backpropagates
        y = jnp.where(active, y, jnp.zeros_like(y))

        # last stage collects its finished microbatch
        write = active & (p_idx == p_count - 1)
        updated = jax.lax.dynamic_update_index_in_dim(outs, y, mb_c, axis=0)
        outs = jnp.where(write, updated, outs)

        # activations hop to the next stage; stage 0 pulls the next microbatch
        if p_count > 1:
            recv = jax.lax.ppermute(y, axis_name, perm_fwd)
        else:
            recv = y
        nxt = jnp.clip(t + 1, 0, n_micro - 1)
        first_in = jax.lax.dynamic_index_in_dim(x_mb, nxt, keepdims=False)
        buf = jnp.where(p_idx == 0, first_in, recv)
        return (buf, outs), None

    buf0 = x_mb[0]
    outs0 = jnp.zeros_like(x_mb)
    (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(ticks))

    # result lives on the last stage; psum of the masked buffer broadcasts it
    # so every stage returns the same (replicated-over-pp) activations for
    # the head/loss (ppermute cannot fan out one source to many destinations)
    if p_count > 1:
        outs = jnp.where(p_idx == p_count - 1, outs, jnp.zeros_like(outs))
        outs = jax.lax.psum(outs, axis_name)
    return outs.reshape(b_loc, s, d)


def gpipe_blocks(
    stacked_params: Any,
    x: jax.Array,
    positions: jax.Array,
    segment_ids: jax.Array | None,
    *,
    stage_fn: StageFn,
    mesh: Mesh,
    n_micro: int,
) -> jax.Array:
    """Run the layer-stacked block params as a GPipe pipeline over ``pp``.

    ``stacked_params`` leaves have a leading layer axis (the ``nn.scan``
    layout) sharded over ``pp``; ``x`` is the embedded activations, sharded
    over the batch axes and replicated over ``pp``.
    """
    validate_pp_mesh(mesh)
    if segment_ids is None:
        segment_ids = jnp.zeros(x.shape[:2], jnp.int32)

    act_spec = P(Ax.BATCH_AXES, None, None)
    tok_spec = P(Ax.BATCH_AXES, None)
    param_specs = jax.tree.map(lambda _: P(Ax.PIPE), stacked_params)

    fn = shard_map(
        partial(
            _gpipe_local, stage_fn=stage_fn, n_micro=n_micro, axis_name=Ax.PIPE
        ),
        mesh=mesh,
        in_specs=(param_specs, act_spec, tok_spec, tok_spec),
        out_specs=act_spec,
        check_vma=False,
    )
    return fn(stacked_params, x, positions, segment_ids.astype(jnp.int32))
