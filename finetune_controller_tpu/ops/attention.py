"""Attention implementations with a single dispatch point.

:func:`resolve_attention_impl` is the ONE place a kernel is chosen, from what
the program can observe (the backend, the row length, the mesh's ``sp``
axis); :func:`causal_attention` and the trainer call it, nothing re-derives
it.  ``impl``:
  * ``"auto"``   — Pallas flash on a TPU at rows of ``PALLAS_MIN_SEQ`` and
                   longer, XLA otherwise.
  * ``"xla"``    — einsum + masked softmax; XLA fuses this well on TPU and it
                   runs everywhere (CPU tests).  Default.
  * ``"pallas"`` — hand-written TPU flash attention (``ops.pallas``); wins
                   at the benchmark shapes by never materialising (S, S).
  * ``"ring"``   — ring attention over the ``sp`` mesh axis for long context
                   (``parallel.ring``); requires shard_map.
  * ``"ulysses"`` — all-to-all head-sharded sequence parallelism
                   (``parallel.ulysses``); ``sp`` must divide ``n_kv_heads``.

All paths compute softmax in float32 regardless of input dtype (bf16 inputs,
f32 accumulation — the MXU-friendly recipe).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, S, H, D); k: (B, S, Hkv, D) → scores (B, Hkv, G, S, S)."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q = q.reshape(b, s, hkv, g, d)
    return jnp.einsum("bskgd,btkd->bkgst", q, k)


#: a selection's layout: key ``s`` of a query is bit ``(s % 4096) // 128`` of
#: the int32 word ``(s // 4096) * 128 + s % 128`` of that query's row, so a
#: flash kernel's (rows, 128) block of words holds 4,096 keys and a run of
#: 128 keys is one shift and one mask of it, whatever the key block
SELECTION_LANES = 128
SELECTION_KEYS = 32 * SELECTION_LANES


def selection_shape(batch: int, seq: int) -> tuple[int, int, int]:
    """Shape of the words that hold a selection over rows of ``seq`` tokens."""
    return batch, seq, -(-seq // SELECTION_KEYS) * SELECTION_LANES


def pack_selection(mask: jax.Array) -> jax.Array:
    """A per-query set of keys, ``(B, S, S)`` bool, as the words attention
    takes: ``(B, S, ceil(S / 4096) * 128)`` int32, a thirty-second of a
    byte mask (33.5 MB a row of 16,384 tokens)."""
    b, s, n = mask.shape
    groups = -(-n // SELECTION_KEYS)
    mask = jnp.pad(mask, [(0, 0), (0, 0), (0, groups * SELECTION_KEYS - n)])
    bits = mask.reshape(b, s, groups, 32, SELECTION_LANES).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=3,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(
        words.reshape(b, s, groups * SELECTION_LANES), jnp.int32)


def unpack_selection(selection: jax.Array, n_keys: int) -> jax.Array:
    """:func:`pack_selection`'s inverse: ``(B, S, n_keys)`` bool (``(B, Hs, S,
    n_keys)`` of a selection a key/value head)."""
    *lead, w = selection.shape
    words = jax.lax.bitcast_convert_type(selection, jnp.uint32).reshape(
        *lead, w // SELECTION_LANES, 1, SELECTION_LANES)
    bits = (words >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return bits.reshape(*lead, -1)[..., :n_keys] != 0


def pack_block_selection(blocks: jax.Array, block: int) -> jax.Array:
    """A per-query set of BLOCKS of ``block`` keys, ``(..., S, n_blocks)``
    bool, as :func:`pack_selection`'s words of the keys they hold, ``(..., S,
    ceil(n_blocks * block / 4096) * 128)`` int32, without the per-key mask
    ever existing: ``128 // block`` blocks lie side by side along a word
    group's lanes, so a word is the bits of ``32`` blocks, ``128 // block``
    apart, repeated over its block's lanes.  ``block`` divides 128."""
    if SELECTION_LANES % block:
        raise ValueError(f"a block of {block} keys does not divide "
                         f"{SELECTION_LANES} lanes")
    *lead, n = blocks.shape
    side = SELECTION_LANES // block
    groups = -(-n * block // SELECTION_KEYS)
    blocks = jnp.pad(blocks, [(0, 0)] * len(lead) + [(0, groups * 32 * side - n)])
    bits = blocks.reshape(*lead, groups, 32, side).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=-2,
                    dtype=jnp.uint32)                         # (..., groups, side)
    words = jnp.repeat(words, block, axis=-1)
    return jax.lax.bitcast_convert_type(
        words.reshape(*lead, groups * SELECTION_LANES), jnp.int32)


def xla_causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
    selection: jax.Array | None = None,
    window: int | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Causal (optionally segment-masked) GQA attention.

    Shapes: q (B, S, H, D); k (B, S, Hkv, D); v (B, S, Hkv, Dv) with
    H % Hkv == 0.  Dv may differ from D (latent attention's 192 / 128); the
    softmax scale comes from D.  ``selection`` (:func:`pack_selection`)
    restricts every query, in all heads, to its own set of keys; (B, Hs, S,
    W): each of ``Hs`` runs of query heads (a key/value head's group where
    ``Hs`` = Hkv) to its own.  ``window``:
    key ``s`` serves query ``t`` iff ``t - window < s <= t``.  ``sink`` (H,)
    float32: one more column of logit ``sink[h]`` in every row's softmax,
    dropped after it, so a row's weights sum to under 1.  Returns
    (B, S, H, Dv) in q.dtype.
    """
    b, s, h, d = q.shape
    hkv = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    scores = _gqa_scores(q * scale, k).astype(jnp.float32)

    pos = jnp.arange(s)
    mask = pos[:, None] >= pos[None, :]  # (S, S) causal
    if window is not None:
        mask = mask & (pos[:, None] - pos[None, :] < window)
    mask = mask[None, None, None]
    if segment_ids is not None:
        seg = segment_ids[:, None, None, :, None] == segment_ids[:, None, None, None, :]
        mask = mask & seg
    if selection is not None:
        picked = unpack_selection(selection, s)
        if selection.ndim == 3:
            picked = picked[:, None, None]
        else:           # a set for each run of h / Hs query heads
            picked = jnp.repeat(picked, h // selection.shape[1], axis=1).reshape(
                b, hkv, h // hkv, s, s)
        mask = mask & picked
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, hkv, h // hkv, 1, 1),
            scores.shape[:-1] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]

    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, s, h, v.shape[-1])


def chunked_cache_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    idx: jax.Array,
) -> jax.Array:
    """A chunk of S query tokens against a static-length KV cache.

    q: (B, S, H, D); caches (B, M, Hkv, D); ``idx`` is the absolute position
    of the chunk's FIRST query token — scalar (whole batch in lockstep) or
    (B,) per-row.  Query j attends cache slots ``<= idx + j``: causal within
    the chunk, full visibility over the already-cached prefix.  S = 1 is the
    classic decode step; S > 1 is a suffix prefill continuing a prefix cache
    (``serve/prefix_cache.py``).  Same f32-softmax and 1/sqrt(D) conventions
    as :func:`xla_causal_attention`, so a chunked fill matches a monolithic
    one bit-for-bit: masked slots contribute exactly 0 to the softmax (the
    f32-min fill underflows exp to 0.0), making per-row results independent
    of the cache length and of whatever stale data other slots hold.
    """
    b, s, h, d = q.shape
    hkv = k_cache.shape[2]
    g = h // hkv
    qh = (q * d ** -0.5).reshape(b, s, hkv, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qh, k_cache).astype(jnp.float32)
    idx = jnp.asarray(idx)
    if idx.ndim:  # (B,) per-row positions -> broadcast over (b, k, g, s, t)
        idx = idx.reshape(b, 1, 1, 1, 1)
    qpos = idx + jnp.arange(s).reshape(1, 1, 1, s, 1)
    valid = jnp.arange(k_cache.shape[1]).reshape(1, 1, 1, 1, -1) <= qpos
    scores = jnp.where(valid, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v_cache)
    return out.reshape(b, s, h, d)


def single_token_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    idx: jax.Array,
) -> jax.Array:
    """One decode step against a static-length KV cache.

    q: (B, 1, H, D); caches (B, M, Hkv, D); ``idx`` is the position of the
    query token — scalar (whole batch in lockstep, the ``cached_generate``
    path) or (B,) per-row (the serving engine, where each slot decodes at its
    own position) — cache slots > idx are masked out.  The S = 1 case of
    :func:`chunked_cache_attention` (the integer ``idx + 0`` query position
    folds away, so the compiled program is unchanged).
    """
    return chunked_cache_attention(q, k_cache, v_cache, idx)


def paged_gather(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """Assemble per-row logical KV caches from a shared page pool.

    ``pool`` is (P, T, Hkv, D) — P fixed-size pages of T sequence positions
    each, shared by every decode lane; ``page_table`` is (B, MP) int32
    physical page ids, row ``b`` listing the pages that hold lane ``b``'s
    positions ``[i*T, (i+1)*T)``.  Returns the gathered (B, MP*T, Hkv, D)
    logical cache — a compute-time temporary the attention below consumes;
    the *resident* KV is only ever the pool, which is what lets lanes hold
    pages proportional to their actual length instead of a full-length
    reservation (``serve/kv_pages.py``).

    Table slots a lane has not materialized yet point at the scratch page
    (id 0); whatever bytes they gather sit at positions beyond the lane's
    cache index and are masked to an exact-zero softmax contribution.
    """
    b, mp = page_table.shape
    _, t, hkv, d = pool.shape
    return pool[page_table].reshape(b, mp * t, hkv, d)


def _check_paged_impl(name: str, raw: str) -> str:
    val = raw.strip().lower()
    if val not in ("auto", "kernel", "gather"):
        raise ValueError(f"{name}={raw!r}: expected auto, kernel or gather")
    return val


def _check_positive_int(name: str, raw) -> int:
    try:
        val = int(raw)
    except (TypeError, ValueError):
        raise ValueError(f"{name}={raw!r}: not an integer") from None
    if val <= 0:
        raise ValueError(f"{name}={val}: must be positive")
    return val


def _paged_vmem_budget() -> int:
    """Bytes of VMEM the paged kernel may use: ``FTC_PAGED_VMEM_MB`` (MiB),
    default ``ops.pallas.paged_attention.DEFAULT_VMEM_MB``.  One number is
    both the dispatch budget and the ``vmem_limit_bytes`` the kernel hands
    the compiler."""
    import os

    from .pallas.paged_attention import DEFAULT_VMEM_MB

    return _check_positive_int(
        "FTC_PAGED_VMEM_MB", os.environ.get("FTC_PAGED_VMEM_MB") or DEFAULT_VMEM_MB
    ) << 20


def paged_kernel_eligible(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, page_table: jax.Array
) -> bool:
    """Whether the chip's compiler takes the Pallas paged kernel for this
    call: matching storage dtypes, tile-aligned page and head shapes
    (``paged_attention_supported``), and a VMEM need
    (``paged_attention_vmem_bytes``) within the ``FTC_PAGED_VMEM_MB`` budget
    the kernel is compiled with.  Arguments need only ``.shape``/``.dtype``;
    ``tests/test_chip_compile.py`` holds this predicate to real compiles."""
    if q.dtype != k_pool.dtype or q.dtype != v_pool.dtype:
        return False
    from .pallas.paged_attention import (
        paged_attention_supported,
        paged_attention_vmem_bytes,
    )

    _, t, hkv, d = k_pool.shape
    itemsize = jnp.dtype(k_pool.dtype).itemsize
    if not paged_attention_supported(t, hkv, d, itemsize):
        return False
    need = paged_attention_vmem_bytes(
        q.shape, page_table.shape[1], t, hkv, itemsize
    )
    return need <= _paged_vmem_budget()


def paged_attention_impl(
    q: jax.Array, k_pool: jax.Array, v_pool: jax.Array, page_table: jax.Array
) -> str:
    """Resolve the paged-attention path for this call: ``kernel`` or
    ``gather``.

    ``FTC_PAGED_ATTN`` ∈ {``auto`` (default), ``kernel``, ``gather``} —
    ``auto`` picks the Pallas kernel on TPU when
    :func:`paged_kernel_eligible` says the compiler takes it, the gather
    path otherwise.  Explicit ``kernel`` is the operator override and the
    CI hook: it forces the kernel everywhere (interpret mode on CPU) and
    fails to compile on a TPU where ``auto`` would have declined.
    """
    import os

    impl = _check_paged_impl(
        "FTC_PAGED_ATTN", os.environ.get("FTC_PAGED_ATTN") or "auto"
    )
    if impl != "auto":
        return impl
    if jax.default_backend() == "tpu" and paged_kernel_eligible(
        q, k_pool, v_pool, page_table
    ):
        return "kernel"
    return "gather"


def paged_cache_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    idx: jax.Array,
) -> jax.Array:
    """:func:`chunked_cache_attention` reading through a page table.

    Two implementations behind one seam, dispatched by
    :func:`paged_attention_impl` (``FTC_PAGED_ATTN``):

    * ``gather`` — the reference: per-lane logical caches are gathered from
      the shared pools and the exact :func:`chunked_cache_attention`
      numerics run over them, so a paged decode/suffix-prefill is
      bit-identical to the unpaged one whenever the gathered length equals
      the contiguous cache length (the engine sizes ``MP*T == cache_len``
      when the page size divides it; otherwise the tail positions are
      masked exact-zeros like any other beyond-index slot).
    * ``kernel`` — ``ops.pallas.paged_attention``: walks the page table in
      the BlockSpec index map so each KV page is read from HBM once and
      the gathered copy only ever exists in VMEM scratch.  f32-accumulated
      MXU matmuls: agrees with the gather path to storage-dtype rounding
      (a bf16 ulp), not bit for bit.

    S = 1 is the decode step, S > 1 a (bucket-padded) prefill or suffix
    prefill.
    """
    if paged_attention_impl(q, k_pool, v_pool, page_table) == "kernel":
        from .pallas.paged_attention import paged_attention

        return paged_attention(
            q, k_pool, v_pool, page_table, idx,
            vmem_limit_bytes=_paged_vmem_budget(),
        )
    return chunked_cache_attention(
        q,
        paged_gather(k_pool, page_table),
        paged_gather(v_pool, page_table),
        idx,
    )


def _flash_attention_on_mesh(q, k, v, segment_ids, selection=None,
                             window=None, sink=None) -> jax.Array:
    """The Pallas flash kernel, one call per device.

    The chip's compiler cannot partition a Mosaic kernel, so under a mesh of
    more than one device (the trainer's, installed by
    ``parallel.ring.ring_mesh``) the call runs inside ``shard_map``: the
    batch splits over ``dp``/``fsdp`` as the trainer already shards it, the
    heads over ``tp``, and each device runs the kernel on its own shard — no
    q/k/v is gathered.  Heads split only where ``tp`` divides both the query
    and the KV head count (a shard then keeps whole GQA groups); otherwise
    they stay whole on every ``tp`` member, which repeats the attention
    ``tp`` times over but is still correct.  The other axes (``sp``/``ep``/
    ``pp``) see replicated operands.  Where ``bare_mosaic_call_ok`` (no mesh,
    a single-device mesh, a caller already inside a ``shard_map`` body such
    as a pipeline stage) the kernel is called bare.  ``window`` is static; a
    ``sink`` splits with the query heads.
    """
    from .pallas import bare_mosaic_call_ok
    from .pallas.flash_attention import flash_attention

    if bare_mosaic_call_ok():
        return flash_attention(q, k, v, segment_ids=segment_ids,
                               selection=selection, window=window, sink=sink)

    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import AxisNames
    from ..parallel.ring import get_ring_mesh

    mesh = get_ring_mesh()
    tp = mesh.shape.get(AxisNames.TENSOR, 1)
    heads = (
        AxisNames.TENSOR
        if tp > 1 and q.shape[2] % tp == 0 and k.shape[2] % tp == 0 else None
    )
    qkv_spec = P(AxisNames.BATCH_AXES, None, heads, None)
    operands, in_specs = (q, k, v), (qkv_spec, qkv_spec, qkv_spec)
    if segment_ids is not None:
        operands += (segment_ids,)
        in_specs += (P(AxisNames.BATCH_AXES, None),)
    if selection is not None:
        # a query's set serves all its heads: whole on every ``tp`` member; a
        # set a key/value head splits with the heads
        operands += (selection,)
        in_specs += (P(AxisNames.BATCH_AXES, None, None) if selection.ndim == 3
                     else P(AxisNames.BATCH_AXES, heads, None, None),)

    if sink is not None:
        operands += (sink,)
        in_specs += (P(heads),)

    given = [name for name, operand in (("segment_ids", segment_ids),
                                        ("selection", selection),
                                        ("sink", sink))
             if operand is not None]

    def local(q, k, v, *rest):
        return flash_attention(q, k, v, window=window, **dict(zip(given, rest)))

    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=qkv_spec,
        # the pallas_call declares no vma on its out_shapes, so the static
        # varying-axes checker cannot track it (as in parallel/ring.py)
        check_vma=False,
    )(*operands)


#: shortest row at which ``"auto"`` takes the flash kernels on a TPU.  From a
#: 2026-07-31 timing of the gradient path on a v5e at 8 rows, 32/4 heads of 64
#: (XLA ahead at 512, Pallas at 1024 and further ahead at 2048); all three
#: ledger cells (2,048 / 4,096 / 8,192 tokens a row) sit above it.
PALLAS_MIN_SEQ = 1024

ATTENTION_IMPLS = ("auto", "xla", "pallas", "ring", "ulysses")


def resolve_attention_impl(
    impl: str, seq_len: int, *, mesh=None, backend: str | None = None
) -> str:
    """The implementation ``impl`` means for rows of ``seq_len`` tokens:
    one of ``"xla"``, ``"pallas"``, ``"ring"``, ``"ulysses"``.

    ``mesh`` defaults to the one the trainer installed
    (``parallel.ring.ring_mesh``).  With an ``sp`` axis of more than one
    device the sequence is sharded, so attention must go through a
    sequence-parallel path or XLA would all-gather S every layer: anything
    else becomes ``"ring"``.  Without one, ``"ring"`` and ``"ulysses"`` are
    plain attention.  ``"auto"`` is the flash kernels on a TPU
    (``backend``, default ``jax.default_backend()``) from ``PALLAS_MIN_SEQ``.
    """
    if impl not in ATTENTION_IMPLS:
        raise ValueError(
            f"unknown attention impl: {impl!r} (expected one of {ATTENTION_IMPLS})"
        )
    if mesh is None:
        from ..parallel.ring import get_ring_mesh

        mesh = get_ring_mesh()
    sp = 1 if mesh is None else mesh.shape.get("sp", 1)
    if impl in ("ring", "ulysses"):
        return impl if sp > 1 else "xla"
    if sp > 1:
        return "ring"
    if impl == "auto":
        on_tpu = (backend or jax.default_backend()) == "tpu"
        return "pallas" if on_tpu and seq_len >= PALLAS_MIN_SEQ else "xla"
    return impl


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "xla",
    segment_ids: jax.Array | None = None,
    selection: jax.Array | None = None,
    window: int | None = None,
    sink: jax.Array | None = None,
) -> jax.Array:
    """Causal GQA attention by the implementation
    :func:`resolve_attention_impl` gives for ``impl`` and this row length;
    with ``selection`` (:func:`pack_selection`; with an axis of key/value
    heads before the rows', a set for each head's group) over each query's
    own set of keys, under a ``window`` of that many keys and beside a per-head ``sink``
    logit (:func:`xla_causal_attention`), all of which the XLA path and the
    flash kernels take and the sequence-parallel paths do not."""
    impl = resolve_attention_impl(impl, q.shape[1])
    if impl == "xla":
        return xla_causal_attention(
            q, k, v, segment_ids=segment_ids, selection=selection,
            window=window, sink=sink)
    if impl == "pallas":
        return _flash_attention_on_mesh(
            q, k, v, segment_ids, selection, window, sink)
    if window is not None or sink is not None:
        raise NotImplementedError(
            f"attention under a window or beside a sink has no {impl!r} path: "
            "a shard's window reaches into the shard before it, and the sink "
            "would have to join the merge of the hops' partial sums "
            "(ROADMAP.md B9)")
    if selection is not None:
        raise NotImplementedError(
            f"attention over a selection of keys has no {impl!r} path: the "
            "selection is per query over ALL keys and would have to travel "
            "with the sequence shards")
    if impl == "ring":
        from ..parallel.ring import ring_attention_sharded

        return ring_attention_sharded(q, k, v, segment_ids=segment_ids)
    from ..parallel.ulysses import ulysses_attention_sharded

    return ulysses_attention_sharded(q, k, v, segment_ids=segment_ids)
