"""Ring attention: causal attention with the sequence dimension sharded over
the ``sp`` mesh axis — the long-context strategy (SURVEY.md §2.3: SP/CP is
'pure greenfield for the TPU build'; the reference never sees a sequence).

Mechanics (blockwise/ring attention): each device holds a contiguous
``S/n``-token shard of Q, K and V. For ``n`` steps, every device computes
blockwise attention between its Q shard and the K/V shard currently resident,
folds the result into online-softmax accumulators (running max ``m``, sum
``l``, weighted values ``acc``), then rotates K/V one hop around the ring via
``jax.lax.ppermute`` — the permute rides ICI neighbour links, and XLA
overlaps the collective with the next block's compute. Peak activation
memory per device stays O(S/n · D); total traffic is the K/V bytes × (n−1).

Causality is enforced by *global* positions, so whole steps where every key
follows every query (src shard entirely in the future) contribute nothing and
are masked out — with causal input the average device does ~n/2 useful block
matmuls.

The public wrapper :func:`ring_attention_sharded` runs the local kernel under
``shard_map`` on the trainer's mesh; inside the model it is reached via
``attention_impl="ring"`` with the mesh provided by :func:`ring_mesh` (the
trainer installs it before tracing).
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size, pcast
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import AxisNames

NEG_INF = float(jnp.finfo(jnp.float32).min)

_ring_mesh: Mesh | None = None


@contextlib.contextmanager
def ring_mesh(mesh: Mesh):
    """Install the mesh ring attention shards over (read at trace time)."""
    global _ring_mesh
    prev = _ring_mesh
    _ring_mesh = mesh
    try:
        yield
    finally:
        _ring_mesh = prev


def get_ring_mesh() -> Mesh | None:
    return _ring_mesh


def _block_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B, Sq, H, D) × k (B, Sk, Hkv, D) → (B, Hkv, G, Sq, Sk) f32 GQA scores."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qr = q.reshape(b, sq, hkv, g, d)
    return jnp.einsum(
        "bskgd,btkd->bkgst", qr, k, preferred_element_type=jnp.float32
    )


def _ring_attention_local(
    q: jax.Array,            # (B, S_local, H, D) — this device's Q shard
    k: jax.Array,            # (B, S_local, Hkv, D)
    v: jax.Array,
    segment_ids: jax.Array,  # (B, S_local)
    *,
    axis_name: str,
) -> jax.Array:
    n = axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    scale = d ** -0.5

    q32 = q.astype(jnp.float32) * scale
    local_pos = jnp.arange(s_local)
    q_pos = i * s_local + local_pos                      # (S_local,) global

    # mark the accumulator inits as device-varying so the fori carry types
    # match after the ppermute makes K/V varying (shard_map vma tracking)
    vary = (*AxisNames.BATCH_AXES, axis_name)
    acc = pcast(jnp.zeros((b, hkv, g, s_local, d), jnp.float32), vary, to="varying")
    m = pcast(jnp.full((b, hkv, g, s_local, 1), NEG_INF, jnp.float32), vary, to="varying")
    l = pcast(jnp.zeros((b, hkv, g, s_local, 1), jnp.float32), vary, to="varying")

    def step(t, carry):
        acc, m, l, k_blk, v_blk, kseg_blk = carry
        src = (i - t) % n                                # whose K/V we hold
        k_pos = src * s_local + local_pos

        s_scores = _block_scores(q32, k_blk.astype(jnp.float32))
        mask = q_pos[:, None] >= k_pos[None, :]          # (Sq, Sk) causal, global
        seg = segment_ids[:, None, None, :, None] == kseg_blk[:, None, None, None, :]
        full_mask = mask[None, None, None] & seg
        s_scores = jnp.where(full_mask, s_scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(s_scores, axis=-1, keepdims=True))
        # zero p under the mask explicitly: a fully-masked row (e.g. a step
        # whose whole K/V shard is in the future) keeps m_new == NEG_INF, so
        # exp(s - m_new) would be exp(0) = 1 per lane and corrupt l
        p = jnp.where(full_mask, jnp.exp(s_scores - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bkgst,btkd->bkgsd", p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

        # rotate K/V one hop (skip after the last step)
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt, v_nxt, kseg_nxt = jax.lax.cond(
            t < n - 1,
            lambda ops: tuple(
                jax.lax.ppermute(o, axis_name, perm) for o in ops
            ),
            lambda ops: ops,
            (k_blk, v_blk, kseg_blk),
        )
        return acc_new, m_new, l_new, k_nxt, v_nxt, kseg_nxt

    acc, m, l, *_ = jax.lax.fori_loop(
        0, n, step, (acc, m, l, k, v, segment_ids)
    )
    out = acc / jnp.maximum(l, 1e-30)                    # masked rows → 0
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s_local, h, d)
    return out.astype(q.dtype)


def ring_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: jax.Array | None = None,
    mesh: Mesh | None = None,
    axis_name: str = AxisNames.SEQ,
) -> jax.Array:
    """Causal GQA attention with S sharded over ``axis_name``.

    Global shapes as ``ops.attention.causal_attention``; S must divide by the
    axis size. Batch stays sharded over the batch axes, heads replicated
    across ``sp`` (Ulysses-style head-sharding would instead all-to-all here).
    The per-hop block kernel is einsum + masked softmax: it materialises the
    (S/n)² score block per hop (a streaming inner comes with the four-chip
    ``sp`` cell that measures it — ROADMAP.md C8).
    """
    mesh = mesh or _ring_mesh
    if mesh is None:
        raise ValueError("ring attention needs a mesh (use ring_mesh(...) or pass mesh=)")
    if mesh.shape[axis_name] == 1:
        from ..ops.attention import xla_causal_attention

        return xla_causal_attention(q, k, v, segment_ids=segment_ids)
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)

    qkv_spec = P(AxisNames.BATCH_AXES, axis_name, None, None)
    seg_spec = P(AxisNames.BATCH_AXES, axis_name)

    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis_name),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, segment_ids.astype(jnp.int32))
