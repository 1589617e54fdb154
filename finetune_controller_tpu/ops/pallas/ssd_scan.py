"""The state-space recurrence's chunked form (``models/ssm.py::ssd_chunked``)
as Pallas TPU kernels: a chunk stays on the chip.

Why hand-write this: in plain ``jnp`` a layer and pass writes the float32
decay matrices ``[B, nc, Q, Q, H]`` (537 MB at 128 heads over 8,192 rows),
every chunk's own ``P x N`` states and the entering states (268 MB each) to
HBM; the recurrence needs ``x``, ``B``, ``C``, the step sizes and ``y`` once.

Kernel structure: grid = (batch, block of heads, chunk), the chunk axis
innermost and sequential.  A block of heads is ONE GROUP's (or a divisor of
it where a group's states would not fit VMEM: :func:`heads_per_block`), so
the group's scores ``C B^T`` are made once a chunk and shared by its heads —
or, where a group is too few heads to tile by itself (linear attention: every
head its own keys and queries, ``G = H``), a block SPANS whole groups and the
body walks them one after another, each with its own ``B``, ``C`` and scores.
The states of the block's heads, ``[N, heads * P]`` float32, live in VMEM
scratch from one chunk of a row to the next.  A step of the grid::

    scores = C B^T                                          # [Q, Q] float32
    a head:  L = exp(where(s <= t [and same run], cs_t - cs_s, -inf))
             y = ((scores o L).bf16) @ ((dt x).bf16)        # within the chunk
               + (C @ state.bf16) o exp(cs)                 # what entered it
               + D x
    state <- exp(total) state + B^T @ ((dt x o exp(total - cs)).bf16)

The arithmetic is ``ssd_chunked``'s, rounding for rounding: cumulative sums
(made outside, 4 MB a layer), decays and the carried state float32, every
product bf16 operands into a float32 sum, a masked exponent ``-inf`` BEFORE
``exp``.  Per-row scalars (step sizes, their cumulative sums) reach the kernel
lane-dense, ``[nc, H, Q]`` in blocks ``(heads, Q)``; their ``exp`` is taken
there, all heads in two registers, and the lot is turned into columns once a
chunk.  A head narrower than the 128 lanes shares its tile of ``x`` with its
neighbours (two heads of 64): the kernels work a TILE at a time — each head's
product over the whole tile, its own lanes kept — so nothing is shifted along
the lanes and no store is masked.

Differentiation is a second kernel under ``jax.custom_vjp`` that walks the
chunks in REVERSE with the state's cotangent in scratch.  It needs each
chunk's entering state: the forward rule's kernel emits it ONCE in the compute
type (``[B, nc, N, H * P]`` bf16), the plain call (a forward pass whose
residuals nobody keeps) does not.  The kernel returns cotangents for ``x``,
``B``, ``C``, the step sizes and their cumulative sums; ``a``'s follows outside
through ``cumsum(dt * a)``, ``d``'s is a sum over rows made outside (dead code
where the leaf is frozen).

Packed rows restart as in ``ssd_chunked``: ``runs`` reaches the kernel as
three per-row marks (the run, "of the run that entered the chunk", "of the run
the chunk ends in"), which zero exactly what the ``jnp`` form zeroes.

What a step's TRACE pays for the kernels: each body is sixteen heads unrolled,
a thousand calls from one frame.  The calls are built once for their shapes
(:func:`_forward_call`, :func:`_backward_call`), so a stack that traces its
mixer seven times a step traces each body once, and from a frame with room
(``ops/pallas::call_with_room``: at the end of one of CPython's 16 KiB chunks
of frames such a body maps and unmaps a chunk at every call it makes).

:func:`ssd_scan` is what the mixer calls; it chooses (:func:`ssd_scan_impl`)
the kernels on a TPU where a Mosaic call may be issued bare and the shapes
tile, ``ssd_chunked`` everywhere else.  Interpreter mode off-TPU is for the
tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import bare_mosaic_call_ok, call_with_room

#: the VMEM the kernels ask Mosaic for (its default is 16 MiB of a v5e core's
#: 128); blocks are taken where :func:`_vmem_bytes` reckons two thirds of it —
#: the reckoning leaves out what the compiler spills
VMEM_LIMIT = 64 * 2 ** 20

#: rows of the marks' block (a float32 tile's sublanes): run, from_before,
#: to_end, five of nothing
MARK_ROWS = 8


#: heads of a block that spans groups, at most: a body is unrolled a head at a
#: time, and sixteen is what the blocks of one group trace and hold
SPAN_HEADS = 16


def _vmem_bytes(heads: int, p: int, n: int, chunk: int, itemsize: int,
                groups: int = 1) -> int:
    """Bytes a step of the BACKWARD kernel (the larger) holds for a block of
    ``heads`` over ``groups`` groups: every pipelined block twice, the
    scratch, the whole-block float32 results of its products."""
    wide = heads * p
    blocks = chunk * wide * (2 * itemsize + 4) + n * wide * itemsize  # x, dx, dy, entering
    blocks += 4 * chunk * n * 4 * groups                              # b, c, db, dc
    scratch = n * wide * 4 + 2 * chunk * wide * itemsize
    results = 3 * chunk * wide * 4 + 2 * n * wide * 4 + 6 * chunk * chunk * 4
    return 2 * blocks + scratch + results


def heads_per_block(h: int, p: int, g: int, n: int, chunk: int,
                    itemsize: int = 2) -> int:
    """Heads a step of the grid holds: a whole group's where its states fit
    VMEM (:func:`_vmem_bytes`), else the largest divisor of a group that
    does, else — a group too few heads to tile, down to one head a group —
    the most whole groups that do, up to ``SPAN_HEADS`` heads; 0 where nothing
    tiles — a block's columns ``heads * P`` must be whole lanes (128) and
    whole lane tiles of heads, its rows of per-head scalars whole sublanes
    (8, or all ``H``), the chunk and ``N`` whole lanes too, and a group that
    shares a block with others whole lane tiles by itself."""
    if h % g or chunk % 128 or n % 128 or (128 % p and p % 128):
        return 0
    hg = h // g

    def tiles(heads, groups=1):
        return ((heads * p) % 128 == 0 and (heads % 8 == 0 or heads == h)
                and 3 * _vmem_bytes(heads, p, n, chunk, itemsize, groups)
                <= 2 * VMEM_LIMIT)

    for heads in range(hg, 0, -1):
        if hg % heads == 0 and tiles(heads):
            return heads
    if (hg * p) % 128:
        return 0
    for groups in range(min(h, SPAN_HEADS) // hg, 1, -1):
        if g % groups == 0 and tiles(groups * hg, groups):
            return groups * hg
    return 0


def ssd_scan_impl(h: int, p: int, g: int, n: int, chunk: int, *,
                  backend: str | None = None) -> tuple[str, int]:
    """``("pallas", heads a block)`` where the kernels run, else ``("xla",
    0)``: on a TPU (``backend``, default ``jax.default_backend()``), where a
    Mosaic call may be issued bare (no mesh, a one-device mesh, or inside a
    ``shard_map`` body: ``bare_mosaic_call_ok``) and the shapes tile.  Static,
    asked where the caller is traced: ``train-started`` carries it."""
    heads = heads_per_block(h, p, g, n, chunk)
    if ((backend or jax.default_backend()) == "tpu" and heads
            and bare_mosaic_call_ok()):
        return "pallas", heads
    return "xla", 0


def ssd_scan(x, dt, a, b, c, d, runs=None, *, chunk: int):
    """``ssd_chunked(x, dt, a, b, c, d, runs, chunk=chunk)`` by the form
    :func:`ssd_scan_impl` chooses for these shapes here."""
    from ...models.ssm import ssd_chunked

    impl, heads = ssd_scan_impl(x.shape[2], x.shape[3], *b.shape[2:], chunk)
    if impl == "xla":
        return ssd_chunked(x, dt, a, b, c, d, runs, chunk=chunk)
    return ssd_scan_pallas(x, dt, a, b, c, d, runs, chunk=chunk,
                           heads_per_block=heads)


# ---- the kernels ----------------------------------------------------------------

_NT = (((1,), (1,)), ((), ()))   # a @ b^T
_TN = (((0,), (0,)), ((), ()))   # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _exp_where(keep, exponent):
    """``models/ssm.py::_exp_where`` on a block: the excluded exponent never
    reaches ``exp``."""
    if keep is None:
        return jnp.exp(exponent)
    return jnp.exp(jnp.where(keep, exponent, -jnp.inf))


def _lane_tile(p: int) -> int:
    """Heads a 128-lane tile of ``x`` holds: the kernels work a tile at a
    time, so a head of 64 never costs a lane shift or a masked store."""
    return max(1, 128 // p)


def _of_heads(values, lane, p: int):
    """One tile-wide array out of one value a head of the tile (each
    broadcastable to the tile): head ``i``'s on the lanes ``[i p, (i + 1)
    p)``."""
    out = values[0]
    for i, value in enumerate(values[1:], 1):
        out = jnp.where(lane >= i * p, value, out)
    return out


def _own_lanes(value, lane, i: int, p: int):
    """``value`` on head ``i``'s lanes of its tile, zero on the others'."""
    if _lane_tile(p) == 1:
        return value
    return jnp.where((lane >= i * p) & (lane < (i + 1) * p), value,
                     jnp.zeros_like(value))


class _Chunk:
    """What both kernels make of one chunk's per-row scalars, all heads of
    the block at once and lane-dense (``(heads, Q)``: two registers where a
    column a head would be sixteen), then turned ONCE into columns."""

    def __init__(self, dt_rows, cs_rows, marks, n: int):
        heads, chunk = cs_rows.shape
        total = cs_rows[:, chunk - 1:]                          # (heads, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.within = row >= col
        from_before = to_end = carried = None
        if marks is not None:
            self.within &= marks.T[:, 0:1] == marks[0:1, :]
            from_before, to_end = marks[1:2, :] > 0, marks[2:3, :] > 0
            # runs never decrease: the chunk's last row came with the
            # entering state only if the whole chunk did
            carried = jnp.broadcast_to(marks[1:2, chunk - 1:] > 0, (n, 1))
        self.cs_rows = cs_rows
        cols = jnp.concatenate([
            cs_rows, dt_rows, _exp_where(from_before, cs_rows),
            _exp_where(to_end, total - cs_rows)], axis=0).T      # (Q, 4 heads)
        self.cs, self.dt, self.weight, self.reach = (
            cols[:, i * heads:(i + 1) * heads] for i in range(4))
        # what a head's entering state keeps to the chunk's end, a column of
        # N: the (1, 1) total goes down the sublanes BEFORE exp and along the
        # lanes after it (Mosaic broadcasts one way at a time)
        self.through = [
            _exp_where(carried, jnp.broadcast_to(total[j:j + 1, :], (n, 1)))
            for j in range(heads)]

    def decay(self, j: int):
        """``L`` of head ``j``, ``(Q, Q)`` float32."""
        return _exp_where(self.within,
                          self.cs[:, j:j + 1] - self.cs_rows[j:j + 1, :])


def _get(ref, at=None):
    """The columns ``at`` of a block (None: all of it)."""
    return ref[...] if at is None else ref[:, at]


def _put(ref, at, value):
    if at is None:
        ref[...] = value
    else:
        ref[:, at] = value


def _groups_of(heads: int, p: int, n: int, groups: int):
    """``(first head, its x columns, its B / C columns)`` of each group a
    block of ``heads`` spans; one group: the whole block, no slice taken."""
    if groups == 1:
        return [(0, None, None)]
    per = heads // groups
    return [(i * per, slice(i * per * p, (i + 1) * per * p),
             slice(i * n, (i + 1) * n)) for i in range(groups)]


def _fwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, cs_ref, *rest,
                heads: int, p: int, has_runs: bool, emit_entering: bool,
                groups: int = 1):
    rest = list(rest)
    marks_ref = rest.pop(0) if has_runs else None
    y_ref = rest.pop(0)
    entering_ref = rest.pop(0) if emit_entering else None
    state_ref, fedr_ref = rest
    chunk, dtype, n = x_ref.shape[0], x_ref.dtype, state_ref.shape[0]
    first = pl.program_id(1) * heads
    tile = _lane_tile(p)
    width = tile * p
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    lane_n = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    ch = _Chunk(dt_ref[...], cs_ref[...], marks_ref[...] if has_runs else None, n)
    for head0, cols, gn in _groups_of(heads, p, n, groups):
        base = head0 * p
        bq, cq = _get(b_ref, gn), _get(c_ref, gn)
        scores = _dot(cq, bq, _NT)                              # (Q, Q)
        entering = _get(state_ref, cols).astype(dtype)          # (N, heads P)
        if emit_entering:
            _put(entering_ref, cols, entering)
        read = _dot(cq, entering)                               # (Q, heads P)
        tiles = range(head0, head0 + heads // groups, tile)
        for j0 in tiles:
            at = slice(j0 * p, j0 * p + width)
            rel = slice(at.start - base, at.stop - base)
            of = range(j0, j0 + tile)
            x = x_ref[:, at].astype(jnp.float32)
            fed = x * _of_heads([ch.dt[:, j:j + 1] for j in of], lane, p)
            fedb = fed.astype(dtype)
            y = _of_heads([_dot((scores * ch.decay(j)).astype(dtype), fedb)
                           for j in of], lane, p)
            y = y + read[:, rel] * _of_heads(
                [ch.weight[:, j:j + 1] for j in of], lane, p)
            y_ref[:, at] = y + x * _of_heads(
                [d_ref[first + j] for j in of], lane, p)
            fedr_ref[:, at] = (fed * _of_heads(
                [ch.reach[:, j:j + 1] for j in of], lane, p)).astype(dtype)
        own = _dot(bq, _get(fedr_ref, cols), _TN)               # (N, heads P)
        for j0 in tiles:
            at = slice(j0 * p, j0 * p + width)
            state_ref[:, at] = state_ref[:, at] * _of_heads(
                ch.through[j0:j0 + tile], lane_n, p) + own[
                    :, at.start - base:at.stop - base]


def _bwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, cs_ref, *rest,
                heads: int, p: int, has_runs: bool, groups: int = 1):
    rest = list(rest)
    marks_ref = rest.pop(0) if has_runs else None
    (entering_ref, dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dcs_ref,
     dstate_ref, fedr_ref, dyw_ref) = rest
    chunk, dtype, n = x_ref.shape[0], x_ref.dtype, dstate_ref.shape[0]
    first = pl.program_id(1) * heads
    tile = _lane_tile(p)
    width = tile * p
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    lane_n = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (chunk, heads), 1)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) == chunk - 1

    @pl.when(pl.program_id(2) == 0)     # the row's LAST chunk: nothing leaves it
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    ch = _Chunk(dt_ref[...], cs_ref[...], marks_ref[...] if has_runs else None, n)
    dcs_rows = []
    for head0, cols, gn in _groups_of(heads, p, n, groups):
        base = head0 * p
        bq, cq = _get(b_ref, gn), _get(c_ref, gn)
        scores = _dot(cq, bq, _NT)
        entering = _get(entering_ref, cols)                     # (N, heads P)
        read = _dot(cq, entering)
        # cotangent of the state that LEFT this chunk, and of what fed it
        dstate = _get(dstate_ref, cols)
        dleft = dstate.astype(dtype)                            # (N, heads P)
        dfedr = _dot(bq, dleft)                                 # (Q, heads P)
        # d(through) a column: sum over N of dstate o entering
        kept = jnp.sum(dstate * entering.astype(jnp.float32), axis=0,
                       keepdims=True)                           # (1, heads P)
        dscores = jnp.zeros((chunk, chunk), jnp.float32)
        if not head0:       # per-head columns of the whole block
            ddt_cols = jnp.zeros((chunk, heads), jnp.float32)
            dcs_cols = jnp.zeros((chunk, heads), jnp.float32)
        for j0 in range(head0, head0 + heads // groups, tile):
            at = slice(j0 * p, j0 * p + width)
            rel = slice(at.start - base, at.stop - base)
            of = range(j0, j0 + tile)
            x = x_ref[:, at].astype(jnp.float32)
            dy = dy_ref[:, at]
            dyb = dy.astype(dtype)
            step_size = _of_heads([ch.dt[:, j:j + 1] for j in of], lane, p)
            reach = _of_heads([ch.reach[:, j:j + 1] for j in of], lane, p)
            weight = _of_heads([ch.weight[:, j:j + 1] for j in of], lane, p)
            fed = x * step_size
            fedb = fed.astype(dtype)
            fedr = fed * reach
            fedr_ref[:, at] = fedr.astype(dtype)
            dyw_ref[:, at] = (dy * weight).astype(dtype)
            dfeds, ddecays = [], []
            for j in of:
                # within the chunk: y = weighed.bf16 @ fed.bf16
                decay = ch.decay(j)
                weighed = scores * decay
                dweighed = _dot(_own_lanes(dyb, lane, j - j0, p), fedb, _NT)
                dscores = dscores + dweighed * decay
                ddecays.append(dweighed * weighed)              # d/d(cs_t - cs_s)
                dfeds.append(_dot(weighed.astype(dtype), dyb, _TN))
            dfed = _of_heads(dfeds, lane, p) + dfedr[:, rel] * reach
            dx_ref[:, at] = (dfed * step_size + dy * _of_heads(
                [d_ref[first + j] for j in of], lane, p)).astype(dx_ref.dtype)
            # per-row terms of the tile, then each head's share of them
            dreach = dfedr[:, rel] * fedr
            dweight = dy * read[:, rel] * weight - dreach
            ddt = dfed * x
            through = _of_heads([t[:1] for t in ch.through[j0:j0 + tile]],
                                lane[:1], p)
            dtotal = jnp.sum(dreach, axis=0, keepdims=True) + through * kept[:, rel]
            for j in of:
                own = functools.partial(_own_lanes, lane=lane, i=j - j0, p=p)
                dcs = (jnp.sum(ddecays[j - j0], axis=1, keepdims=True)
                       + jnp.sum(own(dweight), axis=1, keepdims=True)
                       + jnp.where(last_row, jnp.sum(
                           _own_lanes(dtotal, lane[:1], j - j0, p),
                           axis=1, keepdims=True), 0.0))
                dcs_cols = jnp.where(head == j, dcs, dcs_cols)
                dcs_rows.append(-jnp.sum(ddecays[j - j0], axis=0, keepdims=True))
                ddt_cols = jnp.where(
                    head == j, jnp.sum(own(ddt), axis=1, keepdims=True), ddt_cols)
            dstate_ref[:, at] = dstate[:, rel] * _of_heads(
                ch.through[j0:j0 + tile], lane_n, p)
        dsb = dscores.astype(dtype)
        _put(dstate_ref, cols, _get(dstate_ref, cols)            # through the read
             + _dot(cq, _get(dyw_ref, cols), _TN))
        _put(dc_ref, gn, _dot(dsb, bq) + _dot(_get(dyw_ref, cols), entering, _NT))
        _put(db_ref, gn, _dot(dsb, cq, _TN) + _dot(_get(fedr_ref, cols), dleft, _NT))
    ddt_ref[...] = ddt_cols.T
    dcs_ref[...] = dcs_cols.T + jnp.concatenate(dcs_rows, axis=0)


# ---- the calls ------------------------------------------------------------------


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


def _specs(heads: int, p: int, n: int, hg: int, chunk: int, nc: int,
           has_runs: bool, reverse: bool, groups: int = 1):
    """Block specs of what both kernels read — ``d`` whole in SMEM, then
    ``x``, ``b``, ``c``, the step sizes, the cumulative sums and (with runs)
    the marks — and the makers of the specs that follow them: a chunk of a
    block of heads' ``width`` columns (``of_group``: of its GROUP's), of
    per-head rows, and the spec of a chunk's entering states.  ``reverse``
    walks a row's chunks from the last.  A block that spans ``groups`` groups
    reads their ``B`` and ``C`` side by side: block ``j``'s are the ``j``-th
    run of ``groups * n`` columns."""
    def at(k):
        return nc - 1 - k if reverse else k

    def of_rows(width, of_group=False):
        return pl.BlockSpec(
            (None, chunk, width),
            lambda i, j, k: (i, at(k), j * heads // hg
                             if of_group and groups == 1 else j))

    def per_head(rows=heads, shared=False):
        return pl.BlockSpec((None, None, rows, chunk),
                            lambda i, j, k: (i, at(k), 0 if shared else j, 0))

    specs = [pl.BlockSpec(memory_space=pltpu.SMEM), of_rows(heads * p),
             of_rows(groups * n, True), of_rows(groups * n, True), per_head(),
             per_head()]
    if has_runs:
        specs.append(per_head(MARK_ROWS, shared=True))
    entering = pl.BlockSpec((None, None, n, heads * p),
                            lambda i, j, k: (i, at(k), 0, j))
    return specs, of_rows, per_head, entering


@functools.lru_cache(maxsize=None)
def _forward_call(shape, dtype, groups: int, chunk: int, heads: int, p: int,
                  n: int, has_runs: bool, emit_entering: bool, interpret: bool):
    """The forward kernel's call for ``x`` of ``shape`` and ``dtype``: ``y``
    ``(B, S, H P)`` float32 and, where asked, every chunk's entering state
    ``(B, nc, N, H P)`` in the compute type.  Built ONCE for its arguments:
    Pallas traces a kernel's body anew for every call it is asked to build,
    and a scanned, rematerialised stack traces its mixer seven times a
    step."""
    bsz, s, wide = shape
    nc, hg = s // chunk, wide // p // groups
    spanned = max(1, heads // hg)
    specs, of_rows, _, entering = _specs(
        heads, p, n, hg, chunk, nc, has_runs, False, spanned)
    out_shape = [jax.ShapeDtypeStruct((bsz, s, wide), jnp.float32)]
    out_specs = [of_rows(heads * p)]
    if emit_entering:
        out_shape.append(jax.ShapeDtypeStruct((bsz, nc, n, wide), dtype))
        out_specs.append(entering)
    return pl.pallas_call(
        functools.partial(call_with_room, _fwd_kernel, heads=heads, p=p,
                          has_runs=has_runs, emit_entering=emit_entering,
                          groups=spanned),
        grid=(bsz, wide // (heads * p), nc), in_specs=specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, heads * p), jnp.float32),
                        pltpu.VMEM((chunk, heads * p), dtype)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_scan_fwd")


@functools.lru_cache(maxsize=None)
def _backward_call(shape, dtype, groups: int, chunk: int, heads: int, p: int,
                   n: int, has_runs: bool, interpret: bool):
    """The backward kernel's call, built once as :func:`_forward_call`:
    cotangents of ``x`` (its type), of ``b`` and ``c`` — float32, one ``(B,
    S, N)`` slab a BLOCK of heads, which the caller sums over a group's
    blocks (a block that spans groups: a slab a group) — and of the step
    sizes and cumulative sums, ``(B, nc, H, Q)``."""
    bsz, s, wide = shape
    nc, blocks, hg = s // chunk, wide // (heads * p), wide // p // groups
    spanned = max(1, heads // hg)
    specs, of_rows, per_head, entering = _specs(
        heads, p, n, hg, chunk, nc, has_runs, True, spanned)
    per_row = jax.ShapeDtypeStruct((bsz, nc, wide // p, chunk), jnp.float32)
    per_block = jax.ShapeDtypeStruct((bsz, s, blocks * spanned * n), jnp.float32)
    return pl.pallas_call(
        functools.partial(call_with_room, _bwd_kernel, heads=heads, p=p,
                          has_runs=has_runs, groups=spanned),
        grid=(bsz, blocks, nc),
        in_specs=specs + [entering, of_rows(heads * p)],
        out_specs=[of_rows(heads * p), of_rows(spanned * n),
                   of_rows(spanned * n), per_head(), per_head()],
        out_shape=[jax.ShapeDtypeStruct(shape, dtype), per_block, per_block,
                   per_row, per_row],
        scratch_shapes=[pltpu.VMEM((n, heads * p), jnp.float32),
                        pltpu.VMEM((chunk, heads * p), dtype),
                        pltpu.VMEM((chunk, heads * p), dtype)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_scan_bwd")


def _forward(d, x, b, c, dt_rows, cs_rows, marks, dims, interpret,
             emit_entering: bool):
    chunk, heads, p, n = dims
    call = _forward_call(x.shape, x.dtype, b.shape[2] // n, chunk, heads, p, n,
                         marks is not None, emit_entering, interpret)
    with jax.named_scope("ssd_scan"):
        return call(d, x, b, c, dt_rows, cs_rows,
                    *(() if marks is None else (marks,)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(d, x, b, c, dt_rows, cs_rows, marks, dims, interpret):
    """``y`` of the padded, flattened inputs; ``dims``: ``(chunk, heads a
    block, P, N)``."""
    return _forward(d, x, b, c, dt_rows, cs_rows, marks, dims, interpret,
                    emit_entering=False)[0]


def _scan_fwd(d, x, b, c, dt_rows, cs_rows, marks, dims, interpret):
    y, entering = _forward(d, x, b, c, dt_rows, cs_rows, marks, dims,
                           interpret, emit_entering=True)
    return y, (d, x, b, c, dt_rows, cs_rows, marks, entering)


def _scan_bwd(dims, interpret, res, dy):
    d, x, b, c, dt_rows, cs_rows, marks, entering = res
    chunk, heads, p, n = dims
    bsz, s, wide = x.shape
    call = _backward_call(x.shape, x.dtype, b.shape[2] // n, chunk, heads, p, n,
                          marks is not None, interpret)
    with jax.named_scope("ssd_scan"):
        dx, db, dc, ddt, dcs = call(
            d, x, b, c, dt_rows, cs_rows, *(() if marks is None else (marks,)),
            entering, dy)
        # a sum over rows, dead code where the leaf is frozen
        dd = jnp.einsum("bshp,bshp->h", dy.reshape(bsz, s, wide // p, p),
                        x.reshape(bsz, s, wide // p, p).astype(jnp.float32))

        def of_group(t, like):       # a group's blocks of heads, summed
            return t.reshape(bsz, s, like.shape[2] // n, -1, n).sum(3).reshape(
                like.shape).astype(like.dtype)

        db, dc = of_group(db, b), of_group(dc, c)
    return (dd.astype(d.dtype), dx, db, dc, ddt, dcs,
            None if marks is None else jnp.zeros_like(marks))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_pallas(x, dt, a, b, c, d, runs=None, *, chunk: int,
                    heads_per_block: int, interpret: bool | None = None):
    """``ssd_chunked`` by the kernels, ``heads_per_block`` heads a step of
    the grid (:func:`heads_per_block` gives it).  Shapes and types are
    ``ssd_chunked``'s; ``interpret`` defaults to off-TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    pad = -s % chunk
    if pad:   # rows of step size zero neither decay nor feed the state
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
        if runs is not None:
            runs = jnp.pad(runs, ((0, 0), (0, pad)), mode="edge")
    nc = (s + pad) // chunk
    with jax.named_scope("ssd_scan"):
        # per-row scalars lane-dense, a chunk's rows along the lanes
        dt_rows = jnp.swapaxes(
            dt.astype(jnp.float32).reshape(bsz, nc, chunk, h), 2, 3)  # (B, nc, H, Q)
        # log-decay of a row, summed along its chunk
        cs_rows = jnp.cumsum(dt_rows * a.astype(jnp.float32)[:, None], axis=3)
        marks = None
        if runs is not None:
            rc = runs.reshape(bsz, nc, chunk)
            end = rc[:, :, -1]
            # the document the state entering a chunk belongs to (the first
            # chunk's is empty: any index serves)
            before = jnp.concatenate([rc[:, :1, 0], end[:, :-1]], axis=1)
            marks = jnp.stack(
                [rc, rc == before[..., None], rc == end[..., None]],
                axis=2).astype(jnp.float32)                         # (B, nc, 3, Q)
            marks = jnp.pad(marks, ((0, 0), (0, 0), (0, MARK_ROWS - 3), (0, 0)))
    y = _scan(d.astype(jnp.float32), x.reshape(bsz, nc * chunk, h * p),
              b.reshape(bsz, nc * chunk, g * n), c.reshape(bsz, nc * chunk, g * n),
              dt_rows, cs_rows, marks, (chunk, heads_per_block, p, n), interpret)
    return y.reshape(bsz, nc * chunk, h, p)[:, :s]
