"""FlashAttention-2-style causal GQA attention as Pallas TPU kernels.

Why hand-write this (the reference delegates all kernels to the user's CUDA
image — SURVEY.md §2.2): the XLA path materialises the (S, S) score matrix in
HBM per head; these kernels stream K/V blocks through VMEM with an online
softmax, so activation memory is O(S · D) instead of O(S²) and the matmuls
stay on the MXU at (block_q × head_dim) × (head_dim × block_k) tiles.

Kernel structure (the canonical Mosaic pipeline shape): grid =
(batch, q_heads, outer_blocks, inner_blocks) with the inner dimension
iterated sequentially per core — online-softmax state lives in VMEM scratch
across inner iterations and Mosaic double-buffers the inner operand's block
DMAs behind the MXU work. GQA is handled in the index map (q head h reads kv
head h // group_size), so no K/V duplication ever happens.

Nothing is done above the causal diagonal.  A block the frontier excludes
skips all compute via ``pl.when`` AND fetches nothing: the index maps of the
operands the inner axis sweeps are clamped to the nearest block the frontier
admits (``_kv_block_index``, ``_q_block_index``), so an excluded step names the
block already resident and Mosaic issues no copy.  A square block the diagonal
crosses is computed in row (dK/dV: key) bands of ``DIAG_TILE`` — each against
what lies at or under its own diagonal tile, which alone builds the position
mask — so ``c(c+1)/2`` of its ``c²`` sub-tiles are computed
(``causal_work_over_need`` is the count).  What is left out contributed an
exact 0.0 to every sum and never raised a row's maximum.

Differentiation is a full Pallas path under ``jax.custom_vjp``:

* forward saves O(S) residuals — the output and the per-row logsumexp — never
  the (S, S) probabilities;
* backward runs two kernels in the FlashAttention-2 style: a dQ kernel
  (inner loop over K/V blocks) and a dK/dV kernel (inner loop over Q blocks),
  both recomputing p = exp(s − lse) on the fly.

Masked-row semantics: every p is explicitly zeroed under the mask (NOT just
the scores set to −inf), so fully-masked rows — padding segments, padded
tails — genuinely accumulate l == 0 and emit zeros with zero gradients.

Runs in interpreter mode off-TPU so CPU CI exercises the same kernel logic
(SURVEY.md §4 test strategy). Dispatch between this kernel and the XLA path
is ``ops/attention.py::resolve_attention_impl``'s.
"""

from __future__ import annotations

import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# Default block size.  All three ledger cells run it with no override
# (``mistral-7b-qlora.train-sft-2k`` / ``-8k`` at head size 128,
# ``joyai-llm-flash-lora.train-sft-4k`` at 192/128; PERF.md §5 has a call's
# time in each).  It came from a 2026-07-31 timing on a v5e at head size 64
# (block 1024 ahead of 512 on the seq-8192 gradient path), older than the
# cells.  Capped to the sequence length at call time, so short-sequence
# callers are unaffected.
DEFAULT_BLOCK = 1024

# Width of the sub-tiles a square diagonal block is computed in, capped to
# the block: 4 a side of a 1024 block, 10 of its 16 computed.  From one v5e
# timing of the three kernels at the three cells' shapes (PR 31, chip call 2:
# forward + dQ + dK/dV of a call, ms, at 512 / 256 / 128 — 33.90 / 33.82 /
# 36.04 at 2 x 8192 d128, 10.82 / 10.73 / 12.95 at 8 x 2048 d128, 14.27 /
# 14.14 / 15.21 at 2 x 4096 q/k 192 v 128; whole blocks 40.29, 13.57, 17.16).
# The forward alone prefers 512 by 2-4 %, dQ 256 by as much; one width serves.
DIAG_TILE = 256

#: the whole block, as a row or key range of it
ALL = ...


def _padded_len(s: int, bq: int, bk: int) -> int:
    """``s`` rounded up to a common multiple of both blocks."""
    return math.lcm(bq, bk) * pl.cdiv(s, math.lcm(bq, bk))


def _diag_tiles(bq: int, bk: int, causal: bool) -> int:
    """Sub-tiles a side of a block the causal diagonal crosses; 1 = the block
    is computed whole under its mask (no diagonal, a block that is not square
    or no wider than the sub-tile)."""
    t = min(DIAG_TILE, bq)
    if causal and bq == bk and bq > t and bq % t == 0:
        return bq // t
    return 1


def _kv_block_index(iq, ik, bq: int, bk: int, causal: bool):
    """The K/V (and key segment id) block step ``(iq, ik)`` of the forward and
    dQ grids names: its own where the causal frontier admits it, else the
    last admitted one — already resident, so nothing is copied."""
    if not causal:
        return ik
    return jnp.minimum(ik, ((iq + 1) * bq - 1) // bk)


def _q_block_index(ik, j, nq: int, bq: int, bk: int, causal: bool):
    """The q-side (Q, dO, lse, delta, query segment id) block inner step ``j``
    of the dK/dV grid names for key block ``ik``: its own, ``j % nq``, where
    the frontier admits it, else the first admitted one — the block the next
    computing step wants."""
    iq = j % nq
    if not causal:
        return iq
    return jnp.maximum(iq, (ik * bk) // bq)


def causal_work_over_need(
    seq: int, block_q: int | None = None, block_k: int | None = None
) -> float:
    """Score area the causal kernels compute ÷ the causal triangle's, S²/2
    (what ``benchmarks/harness/counts.py`` charges a call): every admitted
    block whole, a sub-tiled diagonal block by the sub-tiles at or under its
    diagonal.  Static, like the mechanism: a function of the row length and
    the blocks alone."""
    bq = min(block_q or DEFAULT_BLOCK, seq)
    bk = min(block_k or DEFAULT_BLOCK, seq)
    s_pad = _padded_len(seq, bq, bk)
    c = _diag_tiles(bq, bk, True)
    area = 0
    for iq in range(s_pad // bq):
        for ik in range(s_pad // bk):
            if ik * bk <= (iq + 1) * bq - 1:
                diagonal = c > 1 and iq == ik
                area += (c * (c + 1) // 2) * (bq // c) ** 2 if diagonal else bq * bk
    return area / (seq * seq / 2)


def _resolve_tuning(
    q, block_q: int | None, block_k: int | None, exp_dtype: str | None
) -> tuple[int, int, str]:
    """Fill unset tuning knobs with the measured TPU defaults.

    ``exp_dtype=None`` follows the input dtype: bf16 Q/K/V get the bf16 exp
    path — p is about to be rounded to bf16 for the MXU anyway
    (``p.astype(v.dtype)``), so computing exp in bf16 after the f32
    max-subtract adds <0.4% relative error to an already-bf16-rounded
    quantity; the three ledger cells (bf16 compute) all take this path and
    their plain reference holds it to ``correct``'s limits. Full-precision
    inputs keep the f32 exp — the numerics oracle is untouched.
    """
    if block_q is None:
        block_q = DEFAULT_BLOCK
    if block_k is None:
        block_k = DEFAULT_BLOCK
    if exp_dtype is None:
        exp_dtype = "bfloat16" if q.dtype == jnp.bfloat16 else "float32"
    return block_q, block_k, exp_dtype


def _dimension_semantics(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem)


def _segment_mask(qseg_ref, kseg_ref, rows=ALL, keys=ALL):
    """Same-segment mask of the block's ``rows`` x ``keys`` from the
    (1, 1, b*) segment-id refs."""
    return qseg_ref[0, 0, rows][:, None] == kseg_ref[0, 0, keys][None, :]


def _block_positions(iq, ik, bq, bk):
    """Absolute (q_pos, k_pos) iotas for a (bq, bk) score block — the masked
    (non-interior) kernel paths compare these; which bound each kernel also
    applies against seq_len differs (fwd/dq mask padded KEYS, dkv masks
    padded QUERIES), so the comparisons stay at the call sites."""
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return q_pos, k_pos


def _diagonal_tiles(tiles, block, first, seq_len, segment_refs, key_bands=False):
    """The sub-tiles of a square diagonal block at or under its diagonal, as
    ``(rows, keys, mask)`` with ``mask`` None where every pair is valid.

    The block (first query = first key = ``first``) is cut in ``tiles`` bands
    of rows — of keys with ``key_bands``, the dK/dV kernel's accumulators —
    and each band meets its own diagonal tile plus, in ONE tile, all that
    lies left of it (``key_bands``: under it).  Only a tile ON the diagonal
    builds the position mask, and since its rows and keys start together the
    compare is position-free: one mask serves all of them.  The skip is by
    position alone; what is computed still gets the padded tail's mask (keys
    beyond ``seq_len``; ``key_bands``: queries, as in the whole-block paths)
    and the segment mask."""
    t = block // tiles
    on_diagonal = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
                   >= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))
    padded = 0 if key_bands else 1  # axis of the score tile that may be padding
    for i in range(tiles):
        band = slice(i * t, (i + 1) * t)
        rest = slice((i + 1) * t, block) if key_bands else slice(0, i * t)
        for off_band, mask in ((band, on_diagonal), (rest, None)):
            rows, keys = (off_band, band) if key_bands else (band, off_band)
            shape = (rows.stop - rows.start, keys.stop - keys.start)
            if not all(shape):
                continue
            valid = []
            if seq_len % block:
                pos = first + (rows, keys)[padded].start + (
                    jax.lax.broadcasted_iota(jnp.int32, shape, padded))
                valid.append(pos < seq_len)
            if segment_refs is not None:
                valid.append(_segment_mask(*segment_refs, rows, keys))
            for other in valid:
                mask = other if mask is None else mask & other
            yield rows, keys, mask


def _reduce_rows(parts, combine, reduce):
    """``reduce`` every row over the key ranges ``parts`` (same rows; widths
    multiples of the narrowest) with ONE reduction across lanes: the parts
    are folded chunk by chunk elementwise first, in float32."""
    if len(parts) == 1:
        return reduce(parts[0])
    width = min(part.shape[1] for part in parts)
    chunks = [part[:, at:at + width].astype(jnp.float32)
              for part in parts for at in range(0, part.shape[1], width)]
    return reduce(functools.reduce(combine, chunks))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref,      # (1, 1, bq, d)
    k_ref,      # (1, 1, bk, d)
    v_ref,      # (1, 1, bk, dv)  — dv may differ from d (latent attention)
    qseg_ref,   # (1, 1, bq)
    kseg_ref,   # (1, 1, bk)
    o_ref,      # (1, 1, bq, dv)
    lse_ref,    # (1, 1, bq, 1)
    acc_ref,    # VMEM scratch (bq, dv) f32
    m_ref,      # VMEM scratch (bq, 1) f32
    l_ref,      # VMEM scratch (bq, 1) f32
    *,
    seq_len: int,
    scale: float,
    use_segments: bool,
    exp_dtype: str = "float32",
    causal: bool = True,
    diag_tiles: int = 1,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    edt = jnp.dtype(exp_dtype)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    if causal:
        # causal frontier: this k block is live iff its first key position is
        # <= the q block's last query position
        needed = ik * bk <= (iq + 1) * bq - 1
        # interior = every (q, k) pair in the block is causally valid AND
        # inside the real sequence: the iota/compare/where mask passes can be
        # skipped. The attention kernel is VPU-bound (S^2 elementwise vs
        # 2dS^2 MXU flops at small head dims), so dropping mask passes on the
        # ~N^2/2 interior blocks is a direct win at long sequence.
        interior = ((ik + 1) * bk - 1 <= iq * bq) & ((ik + 1) * bk <= seq_len)
    else:
        # full (non-causal) attention — the ring-attention off-diagonal
        # steps, where every key is in the query's global past
        needed = ik * bk < seq_len
        interior = (ik + 1) * bk <= seq_len

    def _online_update(parts, rows=ALL):
        """ONE online-softmax update of the block's ``rows`` (the state is per
        row, so a row band updates its own slice) with ``parts``: the scores
        ``s`` of key ranges ``keys``, each under its ``mask`` (None = every
        pair valid)."""
        parts = [(s if mask is None else jnp.where(mask, s, NEG_INF), mask, keys)
                 for s, mask, keys in parts]
        m_prev = m_ref[rows]
        m_new = jnp.maximum(m_prev, _reduce_rows(
            [s for s, _, _ in parts], jnp.maximum,
            lambda s: jnp.max(s, axis=-1, keepdims=True)))
        # zero p under the mask explicitly: for a fully-masked row m_new is
        # still NEG_INF and exp(s - m_new) would be exp(0) = 1 per lane,
        # accumulating l = block count instead of 0.
        # exp_dtype="bfloat16" computes the S²-elementwise exp — the VPU-bound
        # hot loop at small head dims — in bf16 after the f32 max-subtract
        # (safe: arguments are <= 0, so the bf16 range is never stressed;
        # precision is ~3 decimal digits on a probability-like quantity).
        # f32 stays the default until the chip A/B proves a win.
        ps = []
        for s, mask, _ in parts:
            diff = s - m_new
            p = jnp.exp(diff if edt == jnp.float32 else diff.astype(edt))
            if mask is not None:
                p = jnp.where(mask, p, jnp.zeros((), p.dtype))
            ps.append(p)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows] = l_ref[rows] * alpha + _reduce_rows(
            ps, jnp.add,
            lambda p: jnp.sum(p, axis=-1, keepdims=True, dtype=jnp.float32))
        # p rounds to the value dtype for the MXU (the FlashAttention-2
        # recipe); accumulation stays f32 in VMEM scratch
        vs = [v_ref[0, 0, keys] for _, _, keys in parts]
        acc = acc_ref[rows] * alpha
        for p, v in zip(ps, vs):
            acc = acc + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_ref[rows] = acc
        m_ref[rows] = m_new

    def _scores(rows=ALL, keys=ALL):
        # matmul inputs stay in their storage dtype (bf16 in production) with
        # f32 MXU accumulation; the scale folds in AFTER the dot, in f32
        return jax.lax.dot_general(
            q_ref[0, 0, rows], k_ref[0, 0, keys], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (rows, keys) f32

    segment_refs = (qseg_ref, kseg_ref) if use_segments else None
    if diag_tiles > 1:
        # square blocks: a needed block left of the diagonal holds real keys
        # only, so the one block that is not interior is the diagonal one
        @pl.when(iq == ik)
        def _compute_diagonal():
            # a row band's tiles in ONE update: what an update costs a row
            # (reductions across lanes, rescaling) is paid once a band
            tiles = _diagonal_tiles(diag_tiles, bq, iq * bq, seq_len, segment_refs)
            for rows, band in itertools.groupby(tiles, key=lambda tile: tile[0]):
                _online_update([(_scores(rows, keys), mask, keys)
                                for _, keys, mask in band], rows)
    else:
        @pl.when(needed & ~interior)
        def _compute_masked():
            s = _scores()
            q_pos, k_pos = _block_positions(iq, ik, bq, bk)
            mask = k_pos < seq_len  # tail block: beyond-S lanes are padding
            if causal:
                mask &= q_pos >= k_pos
            if use_segments:
                mask &= _segment_mask(qseg_ref, kseg_ref)
            _online_update([(s, mask, ALL)])

    @pl.when(needed & interior)
    def _compute_interior():
        _online_update([(
            _scores(),
            _segment_mask(qseg_ref, kseg_ref) if use_segments else None,
            ALL,
        )])

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        # fully-masked rows (padding segments) have l == 0: emit zeros, not NaN
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # logsumexp residual for the backward; empty rows stay deeply negative
        # so the backward's exp(s - lse) is masked there anyway
        lse_ref[0, 0] = m_ref[...] + jnp.log(jnp.maximum(l, 1e-30))


def _pad_inputs(q, k, v, segment_ids, bq, bk, kv_segment_ids=None):
    """Pad S to a common block multiple: pl.ds/dynamic_slice CLAMP
    out-of-bounds starts, which would silently read the wrong K rows on a
    ragged tail block. Padded keys are masked via k_pos >= seq_len; padded
    query rows are sliced away by the callers."""
    s = q.shape[1]
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    s_pad = _padded_len(s, bq, bk)
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        segment_ids = jnp.pad(segment_ids, [(0, 0), (0, s_pad - s)])
        kv_segment_ids = jnp.pad(kv_segment_ids, [(0, 0), (0, s_pad - s)])
    return q, k, v, segment_ids, kv_segment_ids, s_pad


def _flash_forward(
    q: jax.Array,           # (B, S, H, D)
    k: jax.Array,           # (B, S, Hkv, D)
    v: jax.Array,           # (B, S, Hkv, Dv): Dv may differ from D
    segment_ids: jax.Array,  # (B, S) int32
    *,
    block_q: int,
    block_k: int,
    interpret: bool,
    use_segments: bool = True,
    exp_dtype: str = "float32",
    causal: bool = True,
    kv_segment_ids: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, H, Dv), lse (B, H, S_pad, 1) f32).  The softmax
    scale comes from the q/k head size; V keeps its own width in HBM."""
    b, s, h, d = q.shape
    hkv, d_v = k.shape[2], v.shape[3]
    group = h // hkv
    scale = d ** -0.5

    bq = min(block_q, s)
    bk = min(block_k, s)
    q, k, v, segment_ids, kv_segment_ids, s_pad = _pad_inputs(
        q, k, v, segment_ids, bq, bk, kv_segment_ids)

    # (B, H, S, D) — heads on the grid, sequence contiguous for tiling
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    # segments ride as (B, 1, S): TPU block shapes must keep their last two
    # dims (8, 128)-aligned or equal to the array dims — a (1, bq) block of a
    # (B, S) array satisfies neither
    seg3 = segment_ids[:, None, :]
    kseg3 = kv_segment_ids[:, None, :]

    nq = pl.cdiv(s_pad, bq)
    nk = pl.cdiv(s_pad, bk)

    kv = functools.partial(_kv_block_index, bq=bq, bk=bk, causal=causal)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, seq_len=s, scale=scale,
                          use_segments=use_segments, exp_dtype=exp_dtype,
                          causal=causal, diag_tiles=_diag_tiles(bq, bk, causal)),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bq), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, iq, ik: (ib, 0, kv(iq, ik))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d_v), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s_pad, d_v), q.dtype),
            jax.ShapeDtypeStruct((b, h, s_pad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d_v), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=_dimension_semantics(
            "parallel", "parallel", "parallel", "arbitrary"
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qt, kt, vt, seg3, kseg3)

    return out.transpose(0, 2, 1, 3)[:, :s], lse


# ---------------------------------------------------------------------------
# backward — FlashAttention-2 split: dQ kernel + dK/dV kernel
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(
    q_ref,      # (1, 1, bq, d)
    k_ref,      # (1, 1, bk, d)
    v_ref,      # (1, 1, bk, dv)
    do_ref,     # (1, 1, bq, dv)
    lse_ref,    # (1, 1, bq, 1)
    delta_ref,  # (1, 1, bq, 1)
    qseg_ref,   # (1, 1, bq)
    kseg_ref,   # (1, 1, bk)
    dq_ref,     # (1, 1, bq, d)
    dq_acc,     # VMEM scratch (bq, d) f32
    *,
    seq_len: int,
    scale: float,
    use_segments: bool,
    exp_dtype: str = "float32",
    causal: bool = True,
    diag_tiles: int = 1,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    edt = jnp.dtype(exp_dtype)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    if causal:
        needed = ik * bk <= (iq + 1) * bq - 1
        # all (q, k) pairs valid (see forward kernel): skip the mask passes
        interior = ((ik + 1) * bk - 1 <= iq * bq) & ((ik + 1) * bk <= seq_len)
    else:
        needed = ik * bk < seq_len
        interior = (ik + 1) * bk <= seq_len

    def _update(mask, rows=ALL, keys=ALL):
        # storage-dtype (bf16) matmul inputs + f32 accumulation — see the
        # forward kernel's note; the scale folds in after the s dot
        q = q_ref[0, 0, rows]                                  # (rows, d)
        k = k_ref[0, 0, keys]
        v = v_ref[0, 0, keys]
        do = do_ref[0, 0, rows]
        lse = lse_ref[0, 0, rows]                              # (rows, 1)
        delta = delta_ref[0, 0, rows]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        diff = s - lse
        p = jnp.exp(diff if edt == jnp.float32 else diff.astype(edt))
        if mask is not None:
            p = jnp.where(mask, p, jnp.zeros((), p.dtype))

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        dq_acc[rows] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    segment_refs = (qseg_ref, kseg_ref) if use_segments else None
    if diag_tiles > 1:
        # as in the forward kernel: the one non-interior block, in row bands
        @pl.when(iq == ik)
        def _compute_diagonal():
            for rows, keys, mask in _diagonal_tiles(
                    diag_tiles, bq, iq * bq, seq_len, segment_refs):
                _update(mask, rows, keys)
    else:
        @pl.when(needed & ~interior)
        def _compute_masked():
            q_pos, k_pos = _block_positions(iq, ik, bq, bk)
            mask = k_pos < seq_len
            if causal:
                mask &= q_pos >= k_pos
            if use_segments:
                mask &= _segment_mask(qseg_ref, kseg_ref)
            _update(mask)

    @pl.when(needed & interior)
    def _compute_interior():
        _update(_segment_mask(qseg_ref, kseg_ref) if use_segments else None)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    k_ref,      # (1, 1, bk, d)
    v_ref,      # (1, 1, bk, dv)
    q_ref,      # (1, 1, bq, d)  — q head = ihkv*group + j // nq
    do_ref,     # (1, 1, bq, dv)
    lse_ref,    # (1, 1, bq, 1)
    delta_ref,  # (1, 1, bq, 1)
    kseg_ref,   # (1, 1, bk)
    qseg_ref,   # (1, 1, bq)
    dk_ref,     # (1, 1, bk, d)  — one accumulator per KV head (GQA group
    dv_ref,     # (1, 1, bk, dv)    reduced IN kernel, no per-q-head partials)
    dk_acc,     # VMEM scratch (bk, d) f32
    dv_acc,     # VMEM scratch (bk, dv) f32
    *,
    n_q_blocks: int,
    seq_len: int,
    scale: float,
    use_segments: bool,
    exp_dtype: str = "float32",
    causal: bool = True,
    diag_tiles: int = 1,
):
    ik, j = pl.program_id(2), pl.program_id(3)
    n_inner = pl.num_programs(3)   # = group * n_q_blocks
    iq = j % n_q_blocks            # q block within the current group member
    bk = k_ref.shape[2]
    bq = q_ref.shape[2]
    edt = jnp.dtype(exp_dtype)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if causal:
        # this q block contributes iff its last query can see the block's
        # first key
        needed = (iq + 1) * bq - 1 >= ik * bk
        # all pairs causally valid AND no padded q rows: mask passes skippable
        interior = ((ik + 1) * bk - 1 <= iq * bq) & ((iq + 1) * bq <= seq_len)
    else:
        needed = iq * bq < seq_len
        interior = (iq + 1) * bq <= seq_len

    def _update(mask, rows=ALL, keys=ALL):
        # storage-dtype (bf16) matmul inputs + f32 accumulation — see the
        # forward kernel's note; the scale folds in after the s dot and at
        # the dK finalize (it used to ride on a pre-scaled f32 q)
        k = k_ref[0, 0, keys]                                  # (keys, d)
        v = v_ref[0, 0, keys]
        q = q_ref[0, 0, rows]                                  # (rows, d)
        do = do_ref[0, 0, rows]
        lse = lse_ref[0, 0, rows]                              # (rows, 1)
        delta = delta_ref[0, 0, rows]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                              # (bq, bk)
        diff = s - lse
        p = jnp.exp(diff if edt == jnp.float32 else diff.astype(edt))
        if mask is not None:
            p = jnp.where(mask, p, jnp.zeros((), p.dtype))

        # dV += pᵀ · dO
        dv_acc[keys] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta)
        # dK += scale · dsᵀ · q (scale applied once, at finalize)
        dk_acc[keys] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    segment_refs = (qseg_ref, kseg_ref) if use_segments else None
    masked = needed & ~interior
    tail = seq_len % bq != 0
    if diag_tiles > 1:
        # the diagonal block in key bands (the accumulators are per key),
        # each against the queries at or under its own diagonal tile
        masked &= iq != ik

        @pl.when(iq == ik)
        def _compute_diagonal():
            for rows, keys, mask in _diagonal_tiles(
                    diag_tiles, bk, ik * bk, seq_len, segment_refs, key_bands=True):
                _update(mask, rows, keys)

    # with sub-tiled diagonals only a tail leaves whole blocks to mask: the
    # last q block's padded queries, against every key block left of it
    if diag_tiles == 1 or tail:
        @pl.when(masked)
        def _compute_masked():
            q_pos, k_pos = _block_positions(iq, ik, bq, bk)
            mask = q_pos < seq_len
            if causal:
                mask &= q_pos >= k_pos
            if use_segments:
                mask &= _segment_mask(qseg_ref, kseg_ref)
            _update(mask)

    @pl.when(needed & interior)
    def _compute_interior():
        _update(_segment_mask(qseg_ref, kseg_ref) if use_segments else None)

    @pl.when(j == n_inner - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, segment_ids, out, lse, g,
    *, block_q: int, block_k: int, interpret: bool, use_segments: bool = True,
    exp_dtype: str = "float32", causal: bool = True, dlse=None,
    kv_segment_ids=None,
):
    b, s, h, d = q.shape
    hkv, d_v = k.shape[2], v.shape[3]
    group = h // hkv
    scale = d ** -0.5

    bq = min(block_q, s)
    bk = min(block_k, s)
    q_p, k_p, v_p, seg_p, kseg_p, s_pad = _pad_inputs(
        q, k, v, segment_ids, bq, bk, kv_segment_ids)
    g_p = jnp.pad(g, [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]) if s_pad != s else g
    out_p = (
        jnp.pad(out, [(0, 0), (0, s_pad - s), (0, 0), (0, 0)])
        if s_pad != s else out
    )

    qt = q_p.transpose(0, 2, 1, 3)      # (B, H, S, D)
    kt = k_p.transpose(0, 2, 1, 3)      # (B, Hkv, S, D)
    vt = v_p.transpose(0, 2, 1, 3)      # (B, Hkv, S, Dv)
    dot = g_p.transpose(0, 2, 1, 3)     # (B, H, S, Dv)
    outt = out_p.transpose(0, 2, 1, 3)

    # delta_i = Σ_d dO_i · O_i — O(S·D) precompute, plain XLA
    delta = jnp.sum(
        dot.astype(jnp.float32) * outt.astype(jnp.float32), axis=-1, keepdims=True
    )  # (B, H, S_pad, 1)
    if dlse is not None:
        # lse cotangent: ∂lse_i/∂s_ij = p_ij, so ds_ij gains dlse_i·p_ij —
        # which is exactly ds = p·(dp − (delta − dlse)). Folding it into
        # delta means the backward kernels need no change at all.
        delta = delta - dlse

    seg3 = seg_p[:, None, :]  # (B, 1, S_pad) — see _flash_forward
    kseg3 = kseg_p[:, None, :]

    nq = pl.cdiv(s_pad, bq)
    nk = pl.cdiv(s_pad, bk)

    diag_tiles = _diag_tiles(bq, bk, causal)

    kv = functools.partial(_kv_block_index, bq=bq, bk=bk, causal=causal)
    qb = functools.partial(_q_block_index, nq=nq, bq=bq, bk=bk, causal=causal)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, seq_len=s, scale=scale,
                          use_segments=use_segments, exp_dtype=exp_dtype,
                          causal=causal, diag_tiles=diag_tiles),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bq, d_v), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, iq, ik: (ib, 0, kv(iq, ik))),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_dimension_semantics(
            "parallel", "parallel", "parallel", "arbitrary"
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qt, kt, vt, dot, lse, delta, seg3, kseg3)

    # dK/dV: grid over KV heads; each instance owns one key block and the
    # inner dimension sweeps (group member, q block), so the GQA group sum
    # accumulates in VMEM scratch — no per-q-head f32 partials in HBM.
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, n_q_blocks=nq, seq_len=s, scale=scale,
            use_segments=use_segments, exp_dtype=exp_dtype, causal=causal,
            diag_tiles=diag_tiles,
        ),
        grid=(b, hkv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, j: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, ik, j: (ib, ih, ik, 0)),
            pl.BlockSpec(
                (1, 1, bq, d),
                lambda ib, ih, ik, j: (ib, ih * group + j // nq, qb(ik, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, d_v),
                lambda ib, ih, ik, j: (ib, ih * group + j // nq, qb(ik, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda ib, ih, ik, j: (ib, ih * group + j // nq, qb(ik, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda ib, ih, ik, j: (ib, ih * group + j // nq, qb(ik, j), 0),
            ),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, ik, j: (ib, 0, ik)),
            pl.BlockSpec((1, 1, bq), lambda ib, ih, ik, j: (ib, 0, qb(ik, j))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, j: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, ik, j: (ib, ih, ik, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, s_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, s_pad, d_v), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d_v), jnp.float32),
        ],
        compiler_params=_dimension_semantics(
            "parallel", "parallel", "parallel", "arbitrary"
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(kt, vt, qt, dot, lse, delta, kseg3, seg3)

    dq = dq.transpose(0, 2, 1, 3)[:, :s]
    dk = dk.transpose(0, 2, 1, 3)[:, :s].astype(k.dtype)
    dv = dv.transpose(0, 2, 1, 3)[:, :s].astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------


# --- the single custom_vjp: (out, lse) ---------------------------------------
#
# One vjp serves both public surfaces: the plain out-only path (a dropped
# lse output gets a zero cotangent, and dlse=0 leaves the backward's delta
# untouched — identical gradients) and the ring-attention inner, which
# merges per-step partials across hops via their per-row logsumexp and
# needs lse differentiable. The lse cotangent folds into the backward's
# delta (see _flash_backward), keeping one backward implementation.


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_attention_lse(q, k, v, segment_ids, kv_segment_ids, block_q,
                         block_k, interpret, use_segments, exp_dtype, causal):
    out, lse = _flash_forward(
        q, k, v, segment_ids, block_q=block_q, block_k=block_k,
        interpret=interpret, use_segments=use_segments, exp_dtype=exp_dtype,
        causal=causal, kv_segment_ids=kv_segment_ids,
    )
    return out, lse[:, :, : q.shape[1]]


def _flash_lse_fwd(q, k, v, segment_ids, kv_segment_ids, block_q, block_k,
                   interpret, use_segments, exp_dtype, causal):
    out, lse = _flash_forward(
        q, k, v, segment_ids, block_q=block_q, block_k=block_k,
        interpret=interpret, use_segments=use_segments, exp_dtype=exp_dtype,
        causal=causal, kv_segment_ids=kv_segment_ids,
    )
    # Named so a remat policy (models/llama.py remat_policy_fn, e.g.
    # "mlp_flash") can SAVE these residuals: under plain per-layer remat the
    # backward re-runs this whole forward kernel just to rebuild out/lse —
    # ~125 ms/step of the TinyLlama bench profile. checkpoint_name inside a
    # custom_vjp fwd is honored by save_only_these_names (verified by jaxpr:
    # the named values move to the primal pass and the remat region consumes
    # them as constants).
    res_out = checkpoint_name(out, "flash_out")
    res_lse = checkpoint_name(lse, "flash_lse")
    return (out, lse[:, :, : q.shape[1]]), (
        q, k, v, segment_ids, kv_segment_ids, res_out, res_lse,
    )


def _flash_lse_bwd(block_q, block_k, interpret, use_segments, exp_dtype,
                   causal, residuals, g):
    g_out, g_lse = g
    q, k, v, segment_ids, kv_segment_ids, out, lse = residuals
    s_pad = lse.shape[2]
    dlse = g_lse.astype(jnp.float32)
    if dlse.shape[2] != s_pad:
        dlse = jnp.pad(
            dlse, [(0, 0), (0, 0), (0, s_pad - dlse.shape[2]), (0, 0)]
        )
    dq, dk, dv = _flash_backward(
        q, k, v, segment_ids, out, lse, g_out,
        block_q=block_q, block_k=block_k, interpret=interpret,
        use_segments=use_segments, exp_dtype=exp_dtype, causal=causal,
        dlse=dlse, kv_segment_ids=kv_segment_ids,
    )
    return dq, dk, dv, None, None


_flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    causal: bool = True,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    exp_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Flash attention returning ``(out, lse)`` with ``lse`` (B, H, S, 1) f32.

    ``causal=False`` computes full (bidirectional) attention — the ring
    off-diagonal steps, where every resident key is in the query's global
    past. ``kv_segment_ids`` (default: same as ``segment_ids``) supports the
    ring case where the resident K/V shard carries segments from another
    sequence shard. Both outputs are differentiable.

    Unset ``block_q``/``block_k``/``exp_dtype`` resolve to the measured TPU
    defaults (see :func:`_resolve_tuning`).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k, exp_dtype = _resolve_tuning(q, block_q, block_k, exp_dtype)
    b, s, _, _ = q.shape
    use_segments = segment_ids is not None or kv_segment_ids is not None
    if segment_ids is None:
        segment_ids = jnp.zeros((b, s), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    return _flash_attention_lse(
        q, k, v, segment_ids.astype(jnp.int32),
        kv_segment_ids.astype(jnp.int32), block_q, block_k, interpret,
        use_segments, exp_dtype, causal,
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: jax.Array | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    exp_dtype: str | None = None,
) -> jax.Array:
    """Causal GQA flash attention. Shapes as ``ops.attention.causal_attention``.

    Unset ``block_q``/``block_k``/``exp_dtype`` resolve to the defaults
    (1024-token blocks; exp dtype follows the input dtype —
    ``_resolve_tuning``, the ONE place a default lives).  The program
    passes none of the three: they are here for tests, which run small
    blocks through the kernels, and for whoever next changes the rule in
    ``_resolve_tuning`` from what it can observe (head size, row length).
    Blocks are capped to S at call time."""
    out, _ = flash_attention_with_lse(
        q, k, v, segment_ids=segment_ids, block_q=block_q, block_k=block_k,
        interpret=interpret, exp_dtype=exp_dtype,
    )
    return out
