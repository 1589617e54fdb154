"""Ulysses-style sequence parallelism: all-to-all head sharding.

The second SP strategy SURVEY.md §2.3 demands ("ring attention, blockwise,
Ulysses"). Where ring attention (`parallel/ring.py`) keeps heads replicated
and rotates K/V shards around the `sp` axis — n-1 ppermute hops, online
merging — Ulysses trades layout instead of time: one all-to-all converts
each device's (B, S/n, H, D) sequence shard into a (B, S, H/n, D) HEAD
shard, every device runs ONE ordinary causal attention over the full
sequence for its head subset, and a second all-to-all converts back.

Trade-offs (why both strategies exist):

* Ulysses does a single attention per device over the full sequence length
  (no per-hop merge math) at the cost of two all-to-alls of the activations; ring never moves Q/out but moves
  K+V (n-1) times and fragments attention into n blocks.
* Ulysses caps at ``sp | n_kv_heads`` (each device needs whole KV heads —
  GQA group alignment); ring has no head constraint. A 2-level hierarchy
  (Ulysses within a host, ring across hosts) is the natural composition for
  very long context on many chips; this module implements the single-level
  strategy, selected per job via ``attention_impl``.

GQA alignment proof: all_to_all splits H into n contiguous chunks; chunk i
holds q heads [i·H/n, (i+1)·H/n) and KV chunk i holds kv heads
[i·Hkv/n, (i+1)·Hkv/n). With group size g = H/Hkv, q head h attends kv head
h//g, and for h in chunk i: h//g ∈ [i·Hkv/n, (i+1)·Hkv/n) — exactly the KV
heads resident on the same device. The local kernel's standard GQA mapping
is therefore globally correct.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import AxisNames
from .ring import get_ring_mesh


def _ulysses_local(
    q: jax.Array,            # (B, S_local, H, D)
    k: jax.Array,            # (B, S_local, Hkv, D)
    v: jax.Array,
    segment_ids: jax.Array,  # (B, S_local)
    *,
    axis_name: str,
    have_segments: bool,
) -> jax.Array:
    from ..ops.attention import xla_causal_attention

    # seq-shard -> head-shard: split the head axis across sp, gather the
    # sequence axis (tiled all-to-all = the Ulysses/DeepSpeed layout swap)
    a2a = partial(
        jax.lax.all_to_all, axis_name=axis_name,
        split_axis=2, concat_axis=1, tiled=True,
    )
    q_h = a2a(q)                                   # (B, S, H/n, D)
    k_h = a2a(k)
    v_h = a2a(v)
    seg = (
        jax.lax.all_gather(segment_ids, axis_name, axis=1, tiled=True)
        if have_segments else None
    )

    # a shard_map body: the kernel is called directly, past the dispatch
    # that would see the sp axis and choose "ring"
    out_h = xla_causal_attention(q_h, k_h, v_h, segment_ids=seg)

    # head-shard -> seq-shard: the inverse all-to-all
    return jax.lax.all_to_all(
        out_h, axis_name=axis_name, split_axis=1, concat_axis=2, tiled=True,
    )


def ulysses_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: jax.Array | None = None,
    mesh: Mesh | None = None,
    axis_name: str = AxisNames.SEQ,
) -> jax.Array:
    """Causal GQA attention, S sharded over ``axis_name`` via head all-to-all.

    Global shapes as ``ops.attention.causal_attention``. Requires
    ``axis_size | n_kv_heads`` (and hence ``| n_heads``); callers wanting
    more sp than KV heads should use ring attention.  The local kernel is
    ``xla_causal_attention`` (a flash inner comes with the four-chip ``sp``
    cell that measures it — ROADMAP.md C8).
    """
    mesh = mesh or get_ring_mesh()
    if mesh is None:
        raise ValueError(
            "ulysses attention needs a mesh (use ring_mesh(...) or pass mesh=)"
        )
    n = mesh.shape[axis_name]
    if n == 1:
        from ..ops.attention import xla_causal_attention

        return xla_causal_attention(q, k, v, segment_ids=segment_ids)
    h, hkv = q.shape[2], k.shape[2]
    if hkv % n or h % n:
        raise ValueError(
            f"ulysses needs the sp axis ({n}) to divide n_kv_heads ({hkv}) "
            f"and n_heads ({h}); use attention_impl='ring' for more sp than "
            "KV heads"
        )
    have_segments = segment_ids is not None
    if segment_ids is None:
        segment_ids = jnp.zeros(q.shape[:2], jnp.int32)

    qkv_spec = P(AxisNames.BATCH_AXES, axis_name, None, None)
    seg_spec = P(AxisNames.BATCH_AXES, axis_name)
    fn = shard_map(
        partial(_ulysses_local, axis_name=axis_name,
                have_segments=have_segments),
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, seg_spec),
        out_specs=qkv_spec,
    )
    return fn(q, k, v, segment_ids.astype(jnp.int32))
