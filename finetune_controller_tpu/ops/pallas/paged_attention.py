"""Block-sparse paged-attention kernel (Pallas TPU).

Why hand-write this: the gather-based ``paged_cache_attention``
(``ops/attention.py``) materialises every lane's full logical cache
``(B, MP*T, Hkv, D)`` from the page pool in HBM on **every** decode step —
a pure memory-bandwidth tax that scales with the pool's page count, not
with the tokens actually attended.  This kernel walks each lane's page
list directly through the BlockSpec index map: the scalar-prefetched page
table routes page-slot ``ip`` of lane ``ib`` to physical pool page
``table[ib, ip]`` — each KV page is DMA'd from HBM into VMEM exactly once
and the gathered copy never exists outside VMEM scratch.

Grid = (lane, page-slots + query-row blocks).  The first ``MP`` steps of a
lane are a pure copy phase (page ``ip`` lands in its logical slot of the
scratch cache); the remaining steps each attend one block of query rows
against the whole gathered cache, one KV head at a time.

Layouts are chosen for the chip's compiler (Mosaic), which takes 2-D dots
with an f32 accumulator and wants the two minor dims of every VMEM buffer
tile-aligned:

* the pools are viewed as ``(P, T, Hkv*D)`` (a free reshape), so a page is
  a ``(T, Hkv*D)`` tile and KV head ``h`` is the static lane slice
  ``[h*D, (h+1)*D)`` of the scratch cache;
* queries are regrouped outside the kernel to ``(B, Hkv, S*G, D)`` (row
  ``s*G + g``), so each KV head sees one ``(rows, D)`` matrix and the GQA
  group shares its K/V slab;
* masks come from 2-D iotas; the causal bound ``col <= idx + row // G`` is
  evaluated as ``(col - idx) * G <= row`` (no vector division).

Numerics: bf16 (storage-dtype) matmul inputs with f32 accumulation, an f32
softmax over the whole gathered length, probabilities rounded to the value
dtype for the PV matmul and normalised afterwards in f32 — the flash
recipe.  The gather path rounds its scores to the storage dtype first, so
the two agree to storage-dtype rounding (a bf16 ulp; ~1e-6 in f32), not
bit for bit; ``tests/test_paged_attention.py`` pins the tolerance in
interpret mode and ``chip_smoke.py`` checks it on the chip.

Table slots beyond a lane's length point at the scratch page (id 0) —
they stream in like any other page and mask to exact zeros (the f32-min
fill underflows ``exp`` to 0.0), identical to the gather path's semantics.

Runs in interpreter mode off-TPU so CPU CI exercises the same kernel
logic (the ``flash_attention.py`` convention).  Dispatch between this
kernel and the gather path is ``FTC_PAGED_ATTN`` (``ops/attention.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

#: VMEM the kernel asks the compiler for (MiB) unless ``FTC_PAGED_VMEM_MB``
#: says otherwise.  The same number is the dispatch budget
#: (``ops/attention.py``) and the ``vmem_limit_bytes`` handed to Mosaic, so
#: "fits the budget" and "the compiler grants it" are one statement.  The
#: v5e compiler grants up to 120 MiB (tests/test_chip_compile.py holds the
#: default to a compile at a 16k-token lane).
DEFAULT_VMEM_MB = 64

#: query rows attended per grid step are capped so the (rows, M) f32 score
#: block stays near 1 MiB however long the gathered cache is
_MAX_ROW_BLOCK = 512
_SCORE_BLOCK_ELEMS = 1 << 19


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _row_block(rows: int, m: int) -> int:
    """Query rows per attend step: a multiple of 16 (one packed bf16
    sublane tile), at most ``_MAX_ROW_BLOCK`` and small enough that the
    score block stays near ``_SCORE_BLOCK_ELEMS``."""
    cap = max(16, min(_MAX_ROW_BLOCK, _SCORE_BLOCK_ELEMS // m // 16 * 16))
    return min(cap, _round_up(rows, 16))


def paged_attention_supported(
    page_tokens: int, hkv: int, head_dim: int, itemsize: int
) -> bool:
    """Shapes the chip's compiler takes: a page must fill whole packed
    sublane tiles (16 rows of bf16, 8 of f32) and the fused ``Hkv*D`` lane
    dim whole 128-lane tiles, with KV heads at 64-lane boundaries."""
    sublanes = 8 * max(1, 4 // itemsize)
    return (
        page_tokens % sublanes == 0
        and (hkv * head_dim) % 128 == 0
        and head_dim % 64 == 0
    )


def paged_attention_vmem_bytes(
    q_shape: tuple, pages_per_lane: int, page_tokens: int, hkv: int, itemsize: int
) -> int:
    """VMEM one grid step keeps resident: the two gathered-cache scratch
    buffers, the double-buffered K/V page and Q/O row blocks (lane dim
    padded to 128), and the f32 score-block temporaries of the attend step
    (the compiler was seen to hold ~8 of them).  The dispatch layer
    compares this against the budget that is also the kernel's
    ``vmem_limit_bytes``."""
    _, s, h, d = q_shape
    m = pages_per_lane * page_tokens
    r = _row_block(s * (h // hkv), m)
    scratch = 2 * m * hkv * d * itemsize
    kv_blocks = 2 * 2 * page_tokens * hkv * d * itemsize
    q_out = 2 * 2 * hkv * r * _round_up(d, 128) * itemsize
    scores = 8 * r * m * 4
    return scratch + kv_blocks + q_out + scores


def _paged_kernel(
    # scalar-prefetch refs (PrefetchScalarGridSpec, num_scalar_prefetch=2)
    table_ref,  # (B, MP) int32 physical page ids — used by the index maps
    idx_ref,  # (B,) int32 per-lane first-query position
    # tensor refs
    q_ref,  # (1, Hkv, R, D) — query rows s*G+g of this row block
    k_ref,  # (1, T, Hkv*D) — page table[ib, ip]
    v_ref,  # (1, T, Hkv*D)
    o_ref,  # (1, Hkv, R, D)
    # VMEM scratch — the gathered logical cache, never materialised in HBM
    k_acc,  # (MP*T, Hkv*D)
    v_acc,  # (MP*T, Hkv*D)
    *,
    group: int,
    scale: float,
):
    t = k_ref.shape[1]
    m = k_acc.shape[0]
    mp = m // t
    ib = pl.program_id(0)  # read outside pl.when: interpret lowers the
    ip = pl.program_id(1)  # when-body via lax.cond, no pallas context there

    @pl.when(ip < mp)
    def _copy():
        start = pl.multiple_of(ip * t, t)
        k_acc[pl.ds(start, t), :] = k_ref[0]
        v_acc[pl.ds(start, t), :] = v_ref[0]

    @pl.when(ip >= mp)
    def _attend():
        _, hkv, r, d = q_ref.shape
        rows = (ip - mp) * r + jax.lax.broadcasted_iota(jnp.int32, (r, m), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (r, m), 1)
        # query row s*G+g sits at position idx+s and sees slots <= idx+s
        valid = (cols - idx_ref[ib]) * group <= rows
        for h in range(hkv):
            k = k_acc[:, h * d:(h + 1) * d]  # (M, D)
            v = v_acc[:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(
                q_ref[0, h], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # (R, M) f32
            s = jnp.where(valid, s, NEG_INF)
            p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
            l = jnp.sum(p, axis=-1, keepdims=True)
            o = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            o_ref[0, h] = (o / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "vmem_limit_bytes"))
def _paged_attention(
    q, k_pool, v_pool, page_table, idx, *, interpret: bool,
    vmem_limit_bytes: int | None = None,
):
    b, s, h, d = q.shape
    p, t, hkv, _ = k_pool.shape
    mp = page_table.shape[1]
    g = h // hkv
    rows = s * g
    r = _row_block(rows, mp * t)
    rows_pad = _round_up(rows, r)

    # (B, S, H, D) -> (B, Hkv, S*G, D): one (rows, D) matrix per KV head;
    # pad rows (masked like any other, sliced away below) fill the last block
    qr = q.reshape(b, s, hkv, g, d).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, rows, d)
    if rows_pad != rows:
        qr = jnp.pad(qr, [(0, 0), (0, 0), (0, rows_pad - rows), (0, 0)])

    # the index maps hold still outside their phase, so nothing is re-read:
    # K/V stay on the lane's last page while rows attend, Q/O on row block 0
    # while pages stream in
    kv_spec = pl.BlockSpec(
        (1, t, hkv * d),
        lambda ib, ip, table, idx: (table[ib, jnp.minimum(ip, mp - 1)], 0, 0),
    )
    q_spec = pl.BlockSpec(
        (1, hkv, r, d),
        lambda ib, ip, table, idx: (ib, 0, jnp.maximum(ip - mp, 0), 0),
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, group=g, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, mp + rows_pad // r),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((mp * t, hkv * d), k_pool.dtype),
                pltpu.VMEM((mp * t, hkv * d), v_pool.dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_pad, d), v_pool.dtype),
        compiler_params=pltpu.CompilerParams(
            # page slots fill the VMEM scratch sequentially per lane
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes,
        ),
        interpret=interpret,
        name="paged_decode",
    )(page_table, idx, qr, k_pool.reshape(p, t, hkv * d),
      v_pool.reshape(p, t, hkv * d))
    out = out[:, :, :rows].reshape(b, hkv, s, g, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, h, d)


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    idx: jax.Array,
    *,
    interpret: bool | None = None,
    vmem_limit_bytes: int | None = None,
) -> jax.Array:
    """Paged-cache attention reading the pool through the page table.

    Shapes match :func:`ops.attention.paged_cache_attention`: ``q``
    (B, S, H, D); pools (P, T, Hkv, D); ``page_table`` (B, MP) int32;
    ``idx`` scalar or (B,) — the absolute position of the chunk's first
    query token.  Returns (B, S, H, D) in the pool dtype, equal to the
    gather path up to storage-dtype rounding.  ``vmem_limit_bytes`` is the
    VMEM the compiler may use (default ``DEFAULT_VMEM_MB``).
    """
    if q.dtype != k_pool.dtype or q.dtype != v_pool.dtype:
        raise ValueError(
            f"paged_attention: q/k/v dtypes must match — the matmuls take "
            f"storage-dtype inputs (got {q.dtype}, {k_pool.dtype}, "
            f"{v_pool.dtype}); use the gather path for mixed dtypes"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if vmem_limit_bytes is None:
        vmem_limit_bytes = DEFAULT_VMEM_MB << 20
    b = q.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(idx, jnp.int32).reshape(-1), (b,))
    page_table = page_table.astype(jnp.int32)
    return _paged_attention(
        q, k_pool, v_pool, page_table, idx, interpret=interpret,
        vmem_limit_bytes=vmem_limit_bytes,
    )
