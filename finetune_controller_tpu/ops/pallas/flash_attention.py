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

A second frontier: with a static ``window`` a key ``s`` serves a query ``t``
iff ``t - window < s <= t``.  The inner axis of all three grids then sweeps
ONLY the blocks the window reaches (``_window_blocks_back`` + 1 key blocks a
query block in the forward and dQ kernels, as many query blocks a key block
and group member in dK/dV), so a block wholly behind the window is neither
computed nor fetched nor walked; how far a step's block lies behind the
diagonal is static, so a block pair is computed in bands of the window's
width rounded up to 128 lanes (``_window_band``, no wider than ``DIAG_TILE``),
each against the tiles it admits: whole where every pair is valid, under a
position-free mask where the causal or the trailing edge crosses
(``_window_tile_spans``; ``window_work_over_need`` is the count: 2.0 at a
window of 128).  A window call is traced under the names ``flash_swa_fwd``,
``flash_swa_bwd_dq`` and ``flash_swa_bwd_dkv``; with no window the kernels trace
the bodies, grids and index maps they always did.

A sink: a per-head float32 logit ``b_h`` that joins each row's softmax
normaliser (and so its logsumexp) and no output sum — the state every row's
online softmax STARTS from in the forward kernel (``m = b_h``, ``l = 1``,
``acc = 0``).  The backward kernels take no sink: ``p = exp(s - lse)`` with the
``lse`` that holds it and ``ds = p (dp - delta)`` are the sums' own; its
gradient, ``-sum_t exp(b_h - lse_t) delta_t``, is a few per-row terms outside
the kernels (scope ``attn_sink``), which the compiler drops where the leaf is
frozen.

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

from ..attention import SELECTION_KEYS, SELECTION_LANES

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


def _diag_tiles(bq: int, bk: int) -> int:
    """Sub-tiles a side of a block the causal diagonal crosses; 1 = the block
    is computed whole under its mask (a block that is not square or no wider
    than the sub-tile)."""
    t = min(DIAG_TILE, bq)
    if bq == bk and bq > t and bq % t == 0:
        return bq // t
    return 1


def _kv_block_index(iq, ik, bq: int, bk: int):
    """The K/V (and key segment id) block step ``(iq, ik)`` of the forward and
    dQ grids names: its own where the causal frontier admits it, else the
    last admitted one — already resident, so nothing is copied."""
    return jnp.minimum(ik, ((iq + 1) * bq - 1) // bk)


def _q_block_index(ik, j, nq: int, bq: int, bk: int):
    """The q-side (Q, dO, lse, delta, query segment id) block inner step ``j``
    of the dK/dV grid names for key block ``ik``: its own, ``j % nq``, where
    the frontier admits it, else the first admitted one — the block the next
    computing step wants."""
    return jnp.maximum(j % nq, (ik * bk) // bq)


def causal_work_over_need(
    seq: int, block_q: int | None = None, block_k: int | None = None,
    head_widths: tuple[int, int] = (128, 128),
) -> float:
    """Score area the causal kernels compute ÷ the causal triangle's, S²/2
    (what ``benchmarks/harness/counts.py`` charges a call): every admitted
    block whole, a sub-tiled diagonal block by the sub-tiles at or under its
    diagonal.  Static, like the mechanism: a function of the row length and
    the blocks alone — those given, else the ones a call with heads of
    ``head_widths`` (q/k, v) gets."""
    bq = min(block_q or _default_block(*head_widths), seq)
    bk = min(block_k or _default_block(*head_widths), seq)
    s_pad = _padded_len(seq, bq, bk)
    c = _diag_tiles(bq, bk)
    area = 0
    for iq in range(s_pad // bq):
        for ik in range(s_pad // bk):
            if ik * bk <= (iq + 1) * bq - 1:
                diagonal = c > 1 and iq == ik
                area += (c * (c + 1) // 2) * (bq // c) ** 2 if diagonal else bq * bk
    return area / (seq * seq / 2)


#: a window's bands are whole lanes wide
WINDOW_LANES = 128


def _window_band(window: int, block: int) -> int:
    """Width of the bands a block pair under a window is computed in: the
    window rounded up to whole lanes (a band then meets its own diagonal tile
    and the ONE tile the trailing edge crosses), no wider than ``DIAG_TILE``
    or the block; the whole block where that does not divide it."""
    t = min(DIAG_TILE, block, WINDOW_LANES * pl.cdiv(window, WINDOW_LANES))
    return block if block % t else t


def _window_blocks_back(window: int, block: int) -> int:
    """Key blocks behind a query block's own that its window reaches: the
    first query of a block sees back to key ``- (window - 1)``."""
    return pl.cdiv(window - 1, block)


def _window_tile_spans(t: int, block: int, offset: int, window: int,
                       key_bands: bool = False):
    """The tiles of a square block pair a window admits, band by band, as
    static ``(band, [(first, stop, lower, upper), ...])``.

    ``offset`` = the pair's first query - its first key (0 on the diagonal,
    a whole block a step behind it).  A tile's pairs lie at query - key =
    ``d + e`` with ``d`` its own first query - first key and ``e`` = row -
    column in ``(-t, t)``; the window admits ``0 <= d + e < window``.  A tile
    with no such pair is skipped; one that holds only such pairs needs no
    mask and joins its neighbours ``[first, stop)`` (in tiles); one an edge
    crosses stands alone with ``lower`` (``e >= lower``: the causal edge)
    and / or ``upper`` (``e <= upper``: the trailing edge) set.  Bands are of
    rows; of keys with ``key_bands`` (the dK/dV kernel's accumulators)."""
    n = block // t
    for i in range(n):
        spans = []
        for c in range(n):
            d = offset + ((c - i) if key_bands else (i - c)) * t
            if d + t - 1 < 0 or d - (t - 1) > window - 1:
                continue
            lower = -d if d - (t - 1) < 0 else None
            upper = window - 1 - d if d + t - 1 > window - 1 else None
            whole = lower is None and upper is None
            if whole and spans and spans[-1][1] == c and spans[-1][2:] == (None, None):
                spans[-1] = (spans[-1][0], c + 1, None, None)
            else:
                spans.append((c, c + 1, lower, upper))
        if spans:
            yield i, spans


def _window_steps(window: int, block: int, steps: int, key_bands: bool = False):
    """``(band width, [blocks apart, ...])``: of the ``steps`` block pairs an
    inner axis sweeps — 0, 1, ... blocks apart — those the window admits a
    tile of (the farthest always; a pair between may hold none only where the
    window is under a block)."""
    t = _window_band(window, block)
    return t, [apart for apart in range(steps) if any(
        _window_tile_spans(t, block, apart * block, window, key_bands))]


def _window_tiles(t, block, offset, window, segment_refs, key_bands=False):
    """:func:`_window_tile_spans` as ``(rows, keys, mask)`` of the block pair,
    ``mask`` None where every pair is valid.  An edge's mask is position-free
    (``offset`` is static), so one compare serves every block it recurs in.
    No mask for a padded tail: under a causal frontier a padded key serves
    padded queries alone, whose rows are cut away, and a padded query's
    cotangent and delta are zero."""
    e = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
         - jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))
    for i, spans in _window_tile_spans(t, block, offset, window, key_bands):
        band = slice(i * t, (i + 1) * t)
        for first, stop, lower, upper in spans:
            other = slice(first * t, stop * t)
            rows, keys = (other, band) if key_bands else (band, other)
            mask = None
            if lower is not None:
                mask = e >= lower
            if upper is not None:
                mask = e <= upper if mask is None else mask & (e <= upper)
            if segment_refs is not None:
                same = _segment_mask(*segment_refs, rows, keys)
                mask = same if mask is None else mask & same
            yield rows, keys, mask


def window_work_over_need(
    seq: int, window: int, block: int | None = None,
    head_widths: tuple[int, int] = (128, 128),
) -> float:
    """Score area the kernels compute under a ``window`` ÷ what the window
    needs, ``sum_t min(t + 1, window)`` pairs a row and head — the count
    beside :func:`causal_work_over_need`, as static: row length, window and
    block (given, else what a call with heads of ``head_widths`` gets)."""
    b = min(block or _default_block(*head_widths), seq)
    t = _window_band(window, b)
    blocks = _padded_len(seq, b, b) // b
    area = 0
    for back in range(min(_window_blocks_back(window, b), blocks - 1) + 1):
        tiles = sum(stop - first
                    for _, spans in _window_tile_spans(t, b, back * b, window)
                    for first, stop, _, _ in spans)
        area += (blocks - back) * tiles * t * t
    w = min(window, seq)
    return area / (w * (w + 1) // 2 + (seq - w) * w)


def _default_block(qk_width: int, v_width: int) -> int:
    """The block from the head sizes: ``DEFAULT_BLOCK`` up to 192 beside 128
    (every cell measured at it), half of it above.  At 64 heads of 256 beside
    256 and 16,384 rows the chip's compiler refuses the dK/dV kernel at 1024
    (VMEM: its two float32 accumulators, a (1024, 1024) score tile and its
    double-buffered operands) and takes all three at 512.  What it refuses
    follows more than the head sizes — compiled here for a described v5e, 2 x
    4,096 rows at 16 heads of 256 are refused at 1024 and the same rows at 8
    heads, or 16 over 8 or 4 key heads, are taken (PR 32) — so every call
    above 192 + 128 gets the block that always compiles; what 512 costs a
    256-wide model that would have compiled at 1024 is not measured."""
    return DEFAULT_BLOCK if qk_width + v_width <= 320 else DEFAULT_BLOCK // 2


def _resolve_tuning(
    q, block_q: int | None, block_k: int | None, exp_dtype: str | None,
    v=None,
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
    block = _default_block(q.shape[-1], (q if v is None else v).shape[-1])
    if block_q is None:
        block_q = block
    if block_k is None:
        block_k = block
    if exp_dtype is None:
        exp_dtype = "bfloat16" if q.dtype == jnp.bfloat16 else "float32"
    return block_q, block_k, exp_dtype


def _dimension_semantics(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem)


def _segment_mask(qseg_ref, kseg_ref, rows=ALL, keys=ALL):
    """Same-segment mask of the block's ``rows`` x ``keys`` from the
    (1, 1, b*) segment-id refs."""
    return qseg_ref[0, 0, rows][:, None] == kseg_ref[0, 0, keys][None, :]


def _selected(sel_ref, ik, bq, bk, mask, rows=ALL, keys=ALL):
    """``mask`` (None = every pair valid) of the block's ``rows`` x ``keys``
    cut to the pairs the selection holds; ``sel_ref`` None = no selection.
    ``sel_ref`` is the (1, bq, 128) block of packed words
    (``ops/attention.py::pack_selection``) that holds key block ``ik``: a
    run of 128 keys is one bit of every word, so a tile's mask is a shift and
    a mask a run, joined along the lanes."""
    if sel_ref is None:
        return mask
    rows = slice(0, bq) if rows is ALL else rows
    keys = slice(0, bk) if keys is ALL else keys
    words = sel_ref[0, rows]                                   # (rows, 128)
    first = (ik * bk) % SELECTION_KEYS // SELECTION_LANES
    runs = [
        jax.lax.shift_right_logical(
            words, jnp.broadcast_to(first + run, words.shape).astype(words.dtype)
        ) & 1
        for run in range(keys.start // SELECTION_LANES, keys.stop // SELECTION_LANES)]
    picked = (runs[0] if len(runs) == 1 else jnp.concatenate(runs, axis=1)) != 0
    return picked if mask is None else mask & picked


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
    *refs,      # [sel_ref (1, bq, 128): the selection's words, where one is
                #  given;] [sink_ref (1, 1, 1) f32: the head's sink logit,
                #  ``has_sink``;] then o_ref (1, 1, bq, dv), lse_ref (1, 1, bq,
                #  1) and the VMEM scratch acc_ref (bq, dv), m_ref, l_ref
                #  (bq, 1) f32
    seq_len: int,
    scale: float,
    use_segments: bool,
    exp_dtype: str = "float32",
    diag_tiles: int = 1,
    window: int | None = None,
    window_back: int = 0,
    has_sink: bool = False,
):
    sel_ref = refs[0] if len(refs) - has_sink == 6 else None
    sink_ref = refs[-6] if has_sink else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs[-5:]
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    edt = jnp.dtype(exp_dtype)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if has_sink:
            # the sink is where every row's online softmax starts: one more
            # column of logit b_h that joins the maximum and the normaliser
            # (exp(b_h - b_h) = 1) and adds nothing to the output sum
            m_ref[...] = jnp.broadcast_to(sink_ref[0], m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    # causal frontier: this k block is live iff its first key position is
    # <= the q block's last query position
    needed = ik * bk <= (iq + 1) * bq - 1
    # interior = every (q, k) pair in the block is causally valid AND inside
    # the real sequence: the iota/compare/where mask passes can be skipped.
    # The attention kernel is VPU-bound (S^2 elementwise vs 2dS^2 MXU flops at
    # small head dims), so dropping mask passes on the ~N^2/2 interior blocks
    # is a direct win at long sequence.
    interior = ((ik + 1) * bk - 1 <= iq * bq) & ((ik + 1) * bk <= seq_len)

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
    if window is not None:
        # the second frontier: the inner axis sweeps ``window_back + 1`` key
        # blocks, step ``ik`` naming the one ``window_back - ik`` behind the
        # query block's own.  How far behind is static at each step, so which
        # tiles of the pair the window admits, and each edge's mask, are too
        t, behind = _window_steps(window, bq, window_back + 1)
        for back in behind:
            @pl.when((ik == window_back - back) & (iq >= back))
            def _compute_band(back=back):
                tiles = _window_tiles(t, bq, back * bq, window, segment_refs)
                for rows, band in itertools.groupby(tiles, key=lambda tile: tile[0]):
                    _online_update([(_scores(rows, keys), mask, keys)
                                    for _, keys, mask in band], rows)
    else:
        if diag_tiles > 1:
            # square blocks: a needed block left of the diagonal holds real keys
            # only, so the one block that is not interior is the diagonal one
            @pl.when(iq == ik)
            def _compute_diagonal():
                # a row band's tiles in ONE update: what an update costs a row
                # (reductions across lanes, rescaling) is paid once a band
                tiles = _diagonal_tiles(diag_tiles, bq, iq * bq, seq_len, segment_refs)
                for rows, band in itertools.groupby(tiles, key=lambda tile: tile[0]):
                    _online_update([
                        (_scores(rows, keys),
                         _selected(sel_ref, ik, bq, bk, mask, rows, keys), keys)
                        for _, keys, mask in band], rows)
        else:
            @pl.when(needed & ~interior)
            def _compute_masked():
                s = _scores()
                q_pos, k_pos = _block_positions(iq, ik, bq, bk)
                # tail block: beyond-S lanes are padding
                mask = (k_pos < seq_len) & (q_pos >= k_pos)
                if use_segments:
                    mask &= _segment_mask(qseg_ref, kseg_ref)
                _online_update([(s, _selected(sel_ref, ik, bq, bk, mask), ALL)])

        @pl.when(needed & interior)
        def _compute_interior():
            _online_update([(
                _scores(),
                _selected(sel_ref, ik, bq, bk, _segment_mask(qseg_ref, kseg_ref)
                          if use_segments else None),
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


def _selection_operand(selection, s_pad: int, bq: int, bk: int, heads: int):
    """The selection's words (B, S, W) padded to the padded rows and to whole
    groups of 4,096 keys, and the ``(block, of key block, of batch and query
    head)`` its BlockSpecs use: a (1, bq, 128) block of words holds the keys
    of 4,096 // bk blocks.  A selection a key/value head, (B, Hs, S, W), is
    read as ``B * Hs`` rows' (no copy): query head ``ih`` of ``heads`` reads
    the set of its group, ``ih // (heads // Hs)``."""
    if bk % SELECTION_LANES or SELECTION_KEYS % bk:
        raise ValueError(
            f"attention over a selection needs a key block that is a multiple "
            f"of {SELECTION_LANES} and divides {SELECTION_KEYS}, not {bk}")
    row_of = lambda ib, ih: ib                                  # noqa: E731
    if selection.ndim == 4:
        sets = selection.shape[1]
        if heads % sets:
            raise ValueError(f"a selection for each of {sets} key/value heads "
                             f"does not split {heads} query heads")
        selection = selection.reshape((-1,) + selection.shape[2:])
        row_of = lambda ib, ih: ib * sets + ih // (heads // sets)  # noqa: E731
    b, s, w = selection.shape
    width = pl.cdiv(s_pad, SELECTION_KEYS) * SELECTION_LANES
    if (s, w) != (s_pad, width):
        selection = jnp.pad(selection, [(0, 0), (0, s_pad - s), (0, width - w)])
    return (selection, (1, bq, SELECTION_LANES),
            lambda ik: ik * bk // SELECTION_KEYS, row_of)


def _check_window(window, bq, bk, selection):
    """A window call is over square blocks, with no selection."""
    if window is None:
        return
    if window < 1 or bq != bk or selection is not None:
        raise ValueError(
            f"a window of {window} keys needs square blocks (got {bq} x "
            f"{bk}) and takes no selection")


def _window_kv_block_index(iq, ik, back: int):
    """The K/V block inner step ``ik`` of a window call's forward and dQ grids
    names for query block ``iq``: the one ``back - ik`` behind it, and block 0
    — the first a computing step wants — where there is none."""
    return jnp.maximum(iq - (back - ik), 0)


def _window_q_block_index(ik, j, nq: int, ahead: int):
    """The q-side block inner step ``j`` of a window call's dK/dV grid names
    for key block ``ik``: the one ``j % ahead`` ahead of it, and the last block
    — already resident — past the rows' end."""
    return jnp.minimum(ik + j % ahead, nq - 1)


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
    kv_segment_ids: jax.Array | None = None,
    selection: jax.Array | None = None,
    sink: jax.Array | None = None,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B, S, H, Dv), lse (B, H, S_pad, 1) f32).  The softmax
    scale comes from the q/k head size; V keeps its own width in HBM.  ``sink``
    (H,) float32: a logit a head that joins every row's normaliser (and so
    its ``lse``) and no output sum.  ``window``: a key ``s`` serves a query
    ``t`` iff ``t - window < s <= t``; the inner axis then sweeps the key
    blocks a query block's window reaches and no other."""
    b, s, h, d = q.shape
    hkv, d_v = k.shape[2], v.shape[3]
    group = h // hkv
    scale = d ** -0.5

    bq = min(block_q, s)
    bk = min(block_k, s)
    _check_window(window, bq, bk, selection)
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

    kv = functools.partial(_kv_block_index, bq=bq, bk=bk)
    kernel_kw = {}
    if window is not None:
        back = min(_window_blocks_back(window, bq), nq - 1)
        kernel_kw = dict(window=window, window_back=back)
        nk = back + 1
        kv = functools.partial(_window_kv_block_index, back=back)
    sel_operands, sel_specs = (), []
    if selection is not None:
        selection, block, words_of, row_of = _selection_operand(
            selection, s_pad, bq, bk, h)
        sel_operands = (selection,)
        sel_specs = [pl.BlockSpec(
            block, lambda ib, ih, iq, ik: (row_of(ib, ih), iq, words_of(kv(iq, ik))))]
    if sink is not None:
        sel_operands += (sink.astype(jnp.float32).reshape(h, 1, 1),)
        sel_specs.append(pl.BlockSpec(
            (1, 1, 1), lambda ib, ih, iq, ik: (ih, 0, 0)))
        kernel_kw["has_sink"] = True

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, seq_len=s, scale=scale,
                          use_segments=use_segments, exp_dtype=exp_dtype,
                          diag_tiles=_diag_tiles(bq, bk), **kernel_kw),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bq), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, iq, ik: (ib, 0, kv(iq, ik))),
            *sel_specs,
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
        name="flash_fwd" if window is None else "flash_swa_fwd",
    )(qt, kt, vt, seg3, kseg3, *sel_operands)

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
    *refs,      # [sel_ref (1, bq, 128), where a selection is given;] then
                #  dq_ref (1, 1, bq, d) and the VMEM scratch dq_acc (bq, d) f32
    seq_len: int,
    scale: float,
    use_segments: bool,
    exp_dtype: str = "float32",
    diag_tiles: int = 1,
    window: int | None = None,
    window_back: int = 0,
):
    sel_ref = refs[0] if len(refs) == 3 else None
    dq_ref, dq_acc = refs[-2:]
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    edt = jnp.dtype(exp_dtype)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    needed = ik * bk <= (iq + 1) * bq - 1
    # all (q, k) pairs valid (see forward kernel): skip the mask passes
    interior = ((ik + 1) * bk - 1 <= iq * bq) & ((ik + 1) * bk <= seq_len)

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
    if window is not None:
        # as in the forward kernel: step ``ik`` names the key block
        # ``window_back - ik`` behind the query block's own
        t, behind = _window_steps(window, bq, window_back + 1)
        for back in behind:
            @pl.when((ik == window_back - back) & (iq >= back))
            def _compute_band(back=back):
                for rows, keys, mask in _window_tiles(
                        t, bq, back * bq, window, segment_refs):
                    _update(mask, rows, keys)
    else:
        if diag_tiles > 1:
            # as in the forward kernel: the one non-interior block, in row bands
            @pl.when(iq == ik)
            def _compute_diagonal():
                for rows, keys, mask in _diagonal_tiles(
                        diag_tiles, bq, iq * bq, seq_len, segment_refs):
                    _update(_selected(sel_ref, ik, bq, bk, mask, rows, keys),
                            rows, keys)
        else:
            @pl.when(needed & ~interior)
            def _compute_masked():
                q_pos, k_pos = _block_positions(iq, ik, bq, bk)
                mask = (k_pos < seq_len) & (q_pos >= k_pos)
                if use_segments:
                    mask &= _segment_mask(qseg_ref, kseg_ref)
                _update(_selected(sel_ref, ik, bq, bk, mask))

        @pl.when(needed & interior)
        def _compute_interior():
            _update(_selected(
                sel_ref, ik, bq, bk,
                _segment_mask(qseg_ref, kseg_ref) if use_segments else None))

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
    *refs,      # [sel_ref (1, bq, 128), where a selection is given;] then
                #  dk_ref (1, 1, bk, d), dv_ref (1, 1, bk, dv) — one accumulator
                #  per KV head (GQA group reduced IN kernel, no per-q-head
                #  partials) — and the VMEM scratch dk_acc (bk, d), dv_acc
                #  (bk, dv) f32
    n_q_blocks: int,
    seq_len: int,
    scale: float,
    use_segments: bool,
    exp_dtype: str = "float32",
    diag_tiles: int = 1,
    window: int | None = None,
):
    sel_ref = refs[0] if len(refs) == 5 else None
    dk_ref, dv_ref, dk_acc, dv_acc = refs[-4:]
    ik, j = pl.program_id(2), pl.program_id(3)
    n_inner = pl.num_programs(3)   # = group * n_q_blocks
    # q block within the current group member; under a window ``n_q_blocks``
    # is the blocks a key block serves and ``iq`` how far AHEAD of it
    iq = j % n_q_blocks
    bk = k_ref.shape[2]
    bq = q_ref.shape[2]
    edt = jnp.dtype(exp_dtype)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    # this q block contributes iff its last query can see the block's first key
    needed = (iq + 1) * bq - 1 >= ik * bk
    # all pairs causally valid AND no padded q rows: mask passes skippable
    interior = ((ik + 1) * bk - 1 <= iq * bq) & ((iq + 1) * bq <= seq_len)

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
    if window is not None:
        # inner step ``iq`` of a group member names the q block ``iq`` ahead
        # of the key block: static, so the tiles and the masks are.  Key
        # bands, each against the query tiles its window reaches
        t, before = _window_steps(window, bk, n_q_blocks, key_bands=True)
        for ahead in before:
            @pl.when((iq == ahead) & (ik + ahead < pl.num_programs(2)))
            def _compute_band(ahead=ahead):
                for rows, keys, mask in _window_tiles(
                        t, bk, ahead * bk, window, segment_refs, key_bands=True):
                    _update(mask, rows, keys)
    else:
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
                    _update(_selected(sel_ref, ik, bq, bk, mask, rows, keys),
                            rows, keys)

        # with sub-tiled diagonals only a tail leaves whole blocks to mask: the
        # last q block's padded queries, against every key block left of it
        if diag_tiles == 1 or tail:
            @pl.when(masked)
            def _compute_masked():
                q_pos, k_pos = _block_positions(iq, ik, bq, bk)
                mask = (q_pos < seq_len) & (q_pos >= k_pos)
                if use_segments:
                    mask &= _segment_mask(qseg_ref, kseg_ref)
                _update(_selected(sel_ref, ik, bq, bk, mask))

        @pl.when(needed & interior)
        def _compute_interior():
            _update(_selected(
                sel_ref, ik, bq, bk,
                _segment_mask(qseg_ref, kseg_ref) if use_segments else None))

    @pl.when(j == n_inner - 1)
    def _finalize():
        dk_ref[0, 0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, segment_ids, out, lse, g,
    *, block_q: int, block_k: int, interpret: bool, use_segments: bool = True,
    exp_dtype: str = "float32", dlse=None,
    kv_segment_ids=None, selection=None, sink=None, window=None,
):
    """``(dq, dk, dv, dsink)``; ``dsink`` None where no sink is given.  The
    kernels take no sink: ``lse`` holds it, so ``p = exp(s - lse)`` and ``ds =
    p (dp - delta)`` are the sums' own, and its gradient ``-sum_t exp(b_h -
    lse_t) delta_t`` is a few per-row terms out here, which the compiler
    drops where the leaf is frozen."""
    b, s, h, d = q.shape
    hkv, d_v = k.shape[2], v.shape[3]
    group = h // hkv
    scale = d ** -0.5

    bq = min(block_q, s)
    bk = min(block_k, s)
    _check_window(window, bq, bk, selection)
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
    dsink = None
    if sink is not None:
        with jax.named_scope("attn_sink"):
            share = jnp.exp(sink.astype(jnp.float32)[None, :, None, None] - lse)
            dsink = -jnp.sum(share * delta, axis=(0, 2, 3)).astype(sink.dtype)

    seg3 = seg_p[:, None, :]  # (B, 1, S_pad) — see _flash_forward
    kseg3 = kseg_p[:, None, :]

    nq = pl.cdiv(s_pad, bq)
    nk = pl.cdiv(s_pad, bk)

    diag_tiles = _diag_tiles(bq, bk)

    kv = functools.partial(_kv_block_index, bq=bq, bk=bk)
    qb = functools.partial(_q_block_index, nq=nq, bq=bq, bk=bk)
    # the inner axes: every key block (dQ), every q block a group member (dK/dV)
    dq_inner, dkv_inner, dq_kw, dkv_kw = nk, nq, {}, {}
    if window is not None:
        back = min(_window_blocks_back(window, bq), nq - 1)
        dq_inner = dkv_inner = back + 1
        dq_kw = dict(window=window, window_back=back)
        dkv_kw = dict(window=window)
        kv = functools.partial(_window_kv_block_index, back=back)
        qb = functools.partial(_window_q_block_index, nq=nq, ahead=back + 1)
    sel_operands, dq_sel_specs, dkv_sel_specs = (), [], []
    if selection is not None:
        selection, block, words_of, row_of = _selection_operand(
            selection, s_pad, bq, bk, h)
        sel_operands = (selection,)
        dq_sel_specs = [pl.BlockSpec(
            block, lambda ib, ih, iq, ik: (row_of(ib, ih), iq, words_of(kv(iq, ik))))]
        # the dK/dV grid walks key/value heads: its member ``j // dkv_inner``
        # is query head ``ih * group + j // dkv_inner``
        dkv_sel_specs = [pl.BlockSpec(
            block, lambda ib, ih, ik, j: (
                row_of(ib, ih * group + j // dkv_inner), qb(ik, j), words_of(ik)))]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, seq_len=s, scale=scale,
                          use_segments=use_segments, exp_dtype=exp_dtype,
                          diag_tiles=diag_tiles, **dq_kw),
        grid=(b, h, nq, dq_inner),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, iq, ik: (ib, ih // group, kv(iq, ik), 0)),
            pl.BlockSpec((1, 1, bq, d_v), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, bq), lambda ib, ih, iq, ik: (ib, 0, iq)),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, iq, ik: (ib, 0, kv(iq, ik))),
            *dq_sel_specs,
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_dimension_semantics(
            "parallel", "parallel", "parallel", "arbitrary"
        ),
        interpret=interpret,
        name="flash_bwd_dq" if window is None else "flash_swa_bwd_dq",
    )(qt, kt, vt, dot, lse, delta, seg3, kseg3, *sel_operands)

    # dK/dV: grid over KV heads; each instance owns one key block and the
    # inner dimension sweeps (group member, q block), so the GQA group sum
    # accumulates in VMEM scratch — no per-q-head f32 partials in HBM.
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, n_q_blocks=dkv_inner, seq_len=s, scale=scale,
            use_segments=use_segments, exp_dtype=exp_dtype,
            diag_tiles=diag_tiles, **dkv_kw,
        ),
        grid=(b, hkv, nk, group * dkv_inner),
        in_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik, j: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, bk, d_v), lambda ib, ih, ik, j: (ib, ih, ik, 0)),
            pl.BlockSpec(
                (1, 1, bq, d),
                lambda ib, ih, ik, j: (ib, ih * group + j // dkv_inner, qb(ik, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, d_v),
                lambda ib, ih, ik, j: (ib, ih * group + j // dkv_inner, qb(ik, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda ib, ih, ik, j: (ib, ih * group + j // dkv_inner, qb(ik, j), 0),
            ),
            pl.BlockSpec(
                (1, 1, bq, 1),
                lambda ib, ih, ik, j: (ib, ih * group + j // dkv_inner, qb(ik, j), 0),
            ),
            pl.BlockSpec((1, 1, bk), lambda ib, ih, ik, j: (ib, 0, ik)),
            pl.BlockSpec((1, 1, bq), lambda ib, ih, ik, j: (ib, 0, qb(ik, j))),
            *dkv_sel_specs,
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
        name="flash_bwd_dkv" if window is None else "flash_swa_bwd_dkv",
    )(kt, vt, qt, dot, lse, delta, kseg3, seg3, *sel_operands)

    dq = dq.transpose(0, 2, 1, 3)[:, :s]
    dk = dk.transpose(0, 2, 1, 3)[:, :s].astype(k.dtype)
    dv = dv.transpose(0, 2, 1, 3)[:, :s].astype(v.dtype)
    return dq, dk, dv, dsink


# ---------------------------------------------------------------------------
# custom_vjp plumbing
# ---------------------------------------------------------------------------


# --- the single custom_vjp: (out, lse) ---------------------------------------
#
# One vjp serves both public surfaces: the plain out-only path (a dropped
# lse output gets a zero cotangent, and dlse=0 leaves the backward's delta
# untouched — identical gradients) and a caller that merges partial results
# through their per-row logsumexp and needs lse differentiable. The lse
# cotangent folds into the backward's delta (see _flash_backward), keeping
# one backward implementation.


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _flash_attention_lse(q, k, v, segment_ids, kv_segment_ids, selection, sink,
                         block_q, block_k, interpret, use_segments, exp_dtype,
                         window):
    out, lse = _flash_forward(
        q, k, v, segment_ids, block_q=block_q, block_k=block_k,
        interpret=interpret, use_segments=use_segments, exp_dtype=exp_dtype,
        kv_segment_ids=kv_segment_ids, selection=selection,
        sink=sink, window=window,
    )
    return out, lse[:, :, : q.shape[1]]


def _flash_lse_fwd(q, k, v, segment_ids, kv_segment_ids, selection, sink,
                   block_q, block_k, interpret, use_segments, exp_dtype, window):
    out, lse = _flash_forward(
        q, k, v, segment_ids, block_q=block_q, block_k=block_k,
        interpret=interpret, use_segments=use_segments, exp_dtype=exp_dtype,
        kv_segment_ids=kv_segment_ids, selection=selection,
        sink=sink, window=window,
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
        q, k, v, segment_ids, kv_segment_ids, selection, sink, res_out, res_lse,
    )


def _flash_lse_bwd(block_q, block_k, interpret, use_segments, exp_dtype,
                   window, residuals, g):
    g_out, g_lse = g
    q, k, v, segment_ids, kv_segment_ids, selection, sink, out, lse = residuals
    s_pad = lse.shape[2]
    dlse = g_lse.astype(jnp.float32)
    if dlse.shape[2] != s_pad:
        dlse = jnp.pad(
            dlse, [(0, 0), (0, 0), (0, s_pad - dlse.shape[2]), (0, 0)]
        )
    dq, dk, dv, dsink = _flash_backward(
        q, k, v, segment_ids, out, lse, g_out,
        block_q=block_q, block_k=block_k, interpret=interpret,
        use_segments=use_segments, exp_dtype=exp_dtype,
        dlse=dlse, kv_segment_ids=kv_segment_ids, selection=selection,
        sink=sink, window=window,
    )
    return dq, dk, dv, None, None, None, dsink


_flash_attention_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: jax.Array | None = None,
    kv_segment_ids: jax.Array | None = None,
    selection: jax.Array | None = None,
    sink: jax.Array | None = None,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    exp_dtype: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Causal flash attention returning ``(out, lse)`` with ``lse`` (B, H, S,
    1) f32.

    ``kv_segment_ids`` (default: same as ``segment_ids``) gives the keys
    segment ids of their own (no caller in the program passes it: ROADMAP.md
    C3). ``selection`` (``ops/attention.py::pack_selection``,
    (B, S, W) int32) cuts every query, in all its heads, to its own set of
    keys — (B, Hs, S, W): a set for each of ``Hs`` runs of query heads (a
    key/value head's group picks its own), read in place through the index
    maps —: one more operand of the three kernels, which mask by it; with none
    they trace the bodies they always did.  ``window`` (a static count of keys:
    key ``s`` serves query ``t`` iff ``t - window < s <= t``) and ``sink``
    ((H,) float32, a learned logit a head that joins each row's normaliser and
    logsumexp and no output sum; differentiable) are the module docstring's;
    with neither the kernels trace the bodies they always did.  Both outputs
    are differentiable.

    Unset ``block_q``/``block_k``/``exp_dtype`` resolve to the measured TPU
    defaults (see :func:`_resolve_tuning`).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q, block_k, exp_dtype = _resolve_tuning(
        q, block_q, block_k, exp_dtype, v)
    b, s, _, _ = q.shape
    use_segments = segment_ids is not None or kv_segment_ids is not None
    if segment_ids is None:
        segment_ids = jnp.zeros((b, s), jnp.int32)
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    return _flash_attention_lse(
        q, k, v, segment_ids.astype(jnp.int32),
        kv_segment_ids.astype(jnp.int32), selection, sink, block_q, block_k,
        interpret, use_segments, exp_dtype, window,
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    segment_ids: jax.Array | None = None,
    selection: jax.Array | None = None,
    sink: jax.Array | None = None,
    window: int | None = None,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool | None = None,
    exp_dtype: str | None = None,
) -> jax.Array:
    """Causal GQA flash attention. Shapes as ``ops.attention.causal_attention``;
    ``window`` and ``sink`` as :func:`flash_attention_with_lse` takes them.

    Unset ``block_q``/``block_k``/``exp_dtype`` resolve to the defaults
    (1024-token blocks; exp dtype follows the input dtype —
    ``_resolve_tuning``, the ONE place a default lives).  The program
    passes none of the three: they are here for tests, which run small
    blocks through the kernels, and for whoever next changes the rule in
    ``_resolve_tuning`` from what it can observe (head size, row length).
    Blocks are capped to S at call time."""
    out, _ = flash_attention_with_lse(
        q, k, v, segment_ids=segment_ids, selection=selection, sink=sink,
        window=window, block_q=block_q, block_k=block_k, interpret=interpret,
        exp_dtype=exp_dtype,
    )
    return out
