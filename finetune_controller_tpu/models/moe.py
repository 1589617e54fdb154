"""Mixture-of-Experts FFN with expert parallelism: one layer, two families.

BASELINE config #4 (Mixtral 8x7B on v5p-64) and the fine-grained family
(hundreds of narrow experts, sigmoid scores, a shared expert).  The reference
has no EP at all (SURVEY.md §2.3: 'new: expert mesh axis'); this is the
TPU-native design:

- experts are ONE stacked parameter tensor ``(E, d, f)`` a projection
  (``experts/{gate,up,down}_proj/kernel``), sharded over the ``ep`` mesh axis
  (``parallel/sharding.py`` rules), so expert compute is one batched or
  grouped matmul on the MXU;
- the router runs in float32: ``scoring`` ``softmax`` (Mixtral) or
  ``sigmoid``; with ``select_bias`` a frozen per-expert bias is added to the
  scores FOR SELECTION ONLY (the auxiliary-loss-free balancing of the
  fine-grained family) and the combine weights stay the unbiased scores,
  normalised over the chosen experts and scaled;
- two dispatches, both static-shaped under one ``jit``:

  * ``capacity`` (the Mixtral presets' default): a static capacity per
    expert; slot assignment (the GShard cumsum trick) yields a unique
    (expert, slot) per routed pair, so dispatch/combine are a small int
    scatter plus row gathers, not (T, E, C) one-hot einsums (equivalence
    pinned by ``tests/test_model.py::test_moe_permutation_dispatch_matches_
    dense``).  Pairs over an expert's capacity are DROPPED (combine weight
    zero) — a trade that only suits few wide experts;
  * ``dropless``: the ``T·k`` pairs sorted by expert, one grouped matrix
    product over the experts held (static total rows, dynamic group sizes:
    ``_grouped_dot``), un-sorted and combined with float32 weights.  No pair
    is dropped at any imbalance.  On one TPU the product is the Pallas
    megablox kernel, everywhere else the compiler's own lowering of
    ``jax.lax.ragged_dot``, which the kernel beat 1.6x (forward) and 2.3x
    (activation gradient) at 65,536 rows x 256 experts of 2048 x 768 with
    row tiles of 512.  Its tiles follow the groups it is given
    (``_GmmTiling``): the kernel visits a row tile once for every group with
    a row in it and multiplies the whole tile each time, so the row tile is
    the largest no larger than an even group's rows (``gmm_row_tile``: 256
    there, 2.0 x the rows needed where 512 computed 2.99 x), and under 512
    rows the contraction is not tiled, so a group's weights are fetched once
    — a call 2.4-2.7 ms where it took 3.4.  ``gmm_work_over_need`` is the
    step's counter of it.
    Both permutations are gathers in BOTH passes (``_rows_of_tokens``,
    ``_unsort``): the transpose of a row gather is a scatter-add, which a
    TPU serialises.

- the layer ROUTES ONCE A STEP: what routing makes that is integer — the
  chosen experts, the sort of the pairs and its inverse, the rows a pair and
  a pair the rows of a held share, an expert's pairs — is marked
  ``moe_routing`` (``_kept``) and ``models/llama.py::remat_policy_fn`` keeps
  that name under every policy, so a layer's replay in the backward pass
  reads them and holds no ``top_k``, no sort and no scatter.  The scores and
  the weights carry a cotangent and are the remat policy's business;

- inside a scanned stack the dropless layer's three grouped products read
  the layer's experts IN PLACE in the stacked leaf ``(L, E, d, f)``
  (``_grouped_dot(..., layer)``: ``L·E`` groups, all empty but the layer's
  own).  The Pallas kernel is a custom call and takes its operand whole, so
  the slice ``nn.scan`` hands the body was copied for it: at 256 experts of
  2048 x 768 three 805 MB kernels a layer in the forward loop and again in
  the backward one, 24 copies and 0.059 s of a 0.489 s step over four layers
  (12 %), and 2.0 GB of temporaries.  Taken where that copy would be made
  and nothing else is lost: the Pallas kernel runs (one TPU, no mesh), the
  layer was handed its index and the stacked leaves (``models/llama.py``:
  the scanned stack of a model with adapters on — trained experts would
  have a weight gradient visit all ``L·E`` groups a layer), and its
  experts — all of them or the share it holds — are stored unquantised in
  the compute type (a dequantised or cast kernel is a fresh whole array
  already).  Everywhere else the per-layer slice, as before.  The variable
  tree is the same either way;
- ``experts_held = (first, count)``: the layer routes over ALL experts and
  computes the part of the result its own ``count`` experts give (plus the
  shared expert) — what expert parallelism needs of one member, and what a
  chip holding a share of the experts runs.  Only the share's own pairs are
  gathered and multiplied, ``held_row_bound`` rows a pass: one pass where
  the router is balanced, as many as the load asks for where it is not
  (``_dropless_held``); the pass's rows are summed back at their tokens —
  the combine, and the dispatch's gradient — with work that follows the
  ROWS where a pass holds at most half the routed pairs, not ``k`` gathers
  of ``T`` rows most of which read the zero row (``held_sum_form``);
- an optional shared expert (a dense MLP every token passes, handed in as
  a module so its projections carry LoRA like any other);
- experts WITHOUT a gate (``gated=False``): ``down(relu(up x)^2)``, two
  stacked leaves and two grouped products a pass where a SwiGLU expert has
  three, in all three dispatches (``_experts_of_rows``);
- experts IN A LATENT (``fc1_latent_proj`` / ``fc2_latent_proj``, modules as
  the shared expert is): the router scores the layer's input, the rows that
  are dispatched, gathered, multiplied and combined are ``fc1_latent_proj``'s
  output, and ``fc2_latent_proj`` brings the combined sum back — applied to a
  held share's PARTIAL sum, which is what makes the shares of one layer add
  up to the whole; the shared expert stays on the layer's input;
- Switch-Transformer load-balancing aux loss, sown into the ``moe_aux``
  collection where the configuration asks for one (the trainer folds it into
  the objective), and five counters sown into ``moe_stats``:
  ``load_max_over_mean`` (the fullest expert's pairs over the mean),
  ``pairs`` (pairs that reached an expert the layer holds: ``T·k`` when it
  holds them all and nothing is dropped), ``experts_in_place`` (1.0 where
  the layer read its experts in place), ``pairs_over_bound`` (a held
  share's pairs beyond its first pass's rows: computed in later passes) and,
  where the Pallas kernel runs, ``gmm_work_over_need`` (rows a grouped
  product multiplies over rows that have a group, at its row tile).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def _kept(routing: jax.Array) -> jax.Array:
    """Integer data of the routing, which the backward pass reads and cannot
    differentiate: kept for it under every remat policy
    (``models/llama.py::remat_policy_fn``) — a few hundred KB a layer where
    replaying it is a ``top_k``, a sort and two scatters."""
    return checkpoint_name(routing, "moe_routing")


class _StackedKernel(nn.Module):
    """``(E, in, out)`` expert kernels under one projection's name — a plain
    param, or int4 packed+scales quantized per expert
    (``quant.quantized_param``, shared with LoRADense)."""

    shape: tuple[int, int, int]
    dtype: Any
    param_dtype: Any
    quantize_base: bool = False
    quant_block: int = 64

    @nn.compact
    def __call__(self) -> jax.Array:
        init = nn.initializers.lecun_normal()
        if not self.quantize_base:
            return self.param(
                "kernel", init, self.shape, self.param_dtype
            ).astype(self.dtype)
        from .quant import quantized_param

        return quantized_param(
            self, "kernel", self.shape, init, self.quant_block, self.dtype)


#: the stacked leaves of a gated expert, in the order the kernels are handed
#: about; an expert without a gate holds the last two
EXPERT_KERNELS = ("gate_proj", "up_proj", "down_proj")


def expert_kernel_names(gated: bool) -> tuple[str, ...]:
    return EXPERT_KERNELS if gated else EXPERT_KERNELS[1:]


class _Experts(nn.Module):
    """The stacked kernels of the experts this layer holds: ``(gate, up,
    down)``, or ``(up, down)`` of experts without a gate."""

    n_held: int
    d_model: int
    d_ff: int
    dtype: Any
    param_dtype: Any
    quantize_base: bool = False
    quant_block: int = 64
    gated: bool = True

    @nn.compact
    def __call__(self):
        e, d, f = self.n_held, self.d_model, self.d_ff
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype,
                  quantize_base=self.quantize_base,
                  quant_block=self.quant_block)
        return tuple(
            _StackedKernel((e, f, d) if name == "down_proj" else (e, d, f),
                           name=name, **kw)()
            for name in expert_kernel_names(self.gated))


def _experts_of_rows(rows, kernels, product):
    """Every row through its own expert: ``down(silu(gate x) * up x)`` of
    three kernels, ``down(relu(up x)^2)`` of two (an expert without a gate:
    two products a pass where a gated one has three).  ``product(lhs,
    kernel)``: the dispatch's own — grouped over sorted rows, or batched
    over an expert's slots."""
    if len(kernels) == 3:
        w_gate, w_up, w_down = kernels
        gate = product(rows, w_gate)
        up = product(rows, w_up)
        return product(nn.silu(gate) * up, w_down)
    w_up, w_down = kernels
    return product(jnp.square(nn.relu(product(rows, w_up))), w_down)


class _Router(nn.Module):
    """Float32 router logits ``(T, E)`` and, with ``select_bias``, the frozen
    per-expert selection bias."""

    n_experts: int
    select_bias: bool
    param_dtype: Any

    @nn.compact
    def __call__(self, xt: jax.Array):
        d = xt.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.normal(stddev=d ** -0.5),
            (d, self.n_experts), self.param_dtype)
        logits = jnp.einsum(
            "td,de->te", xt.astype(jnp.float32), kernel.astype(jnp.float32))
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.n_experts,),
            self.param_dtype) if self.select_bias else None
        return logits, bias


# ---- the grouped matrix product of the dropless dispatch ---------------------


def _largest_tile(dim: int, tiles=(1024, 768, 512, 384, 256, 128)) -> int:
    return next((t for t in tiles if dim % t == 0), 128)


#: what the tiles of one grouped product may take of the 16 MB of VMEM a
#: Pallas call is given: the compiler's own stack needs the rest
_GMM_VMEM_BYTES = 12 * 2 ** 20


def _gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """VMEM of the megablox kernel at these tiles: the row, weight and result
    tiles of ``itemsize`` bytes an element double-buffered, and the float32
    accumulator."""
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def gmm_row_tile(rows: int, groups: int) -> int:
    """Row tile of the grouped product: the largest of 512, 256, 128 that
    divides ``rows`` and is no larger than the rows an even load gives one of
    the ``groups``.  The kernel visits a row tile once for EVERY group with a
    row in it, each visit is a whole tile's product, and the other groups'
    rows are masked away at the store: it multiplies ``rows + (groups' - 1) ·
    tile`` rows (``groups'``: the non-empty ones) — 2.99 x the need at 512 and
    256 rows a group, 2.0 x at 256.  A tile smaller than a group's rows
    masks fewer rows still (1.5 x at 128) but pays a visit's fixed cost
    (~1.4 us on a v5e: zeroing the accumulator, the masked store) more often:
    3-6 % of a call gained at even load at 2048 x 768, nothing under a
    skewed load, a loss at half those widths (``PERF.md`` section 6)."""
    return next((t for t in (512, 256, 128)
                 if rows % t == 0 and t * groups <= rows), 128)


@dataclasses.dataclass(frozen=True)
class _GmmTiling:
    """Tiles of the megablox kernel from the shapes of THIS product — the
    kernel asks once for the forward product and once, ``k`` and ``n``
    swapped, for the activation gradient — and the ``groups`` its rows are
    spread over (a layer's OWN experts, also where they are read in place
    among a stack's ``L·G``), of ``itemsize`` bytes an element.  Hashable: a
    static argument of the kernel's ``jit``."""

    groups: int
    itemsize: int = 2

    def __call__(self, m: int, k: int, n: int) -> tuple[int, int, int]:
        tm = gmm_row_tile(m, self.groups)
        return (tm, *_gmm_widths(tm, k, n, self.itemsize))


def _gmm_widths(tm: int, k: int, n: int, itemsize: int) -> tuple[int, int]:
    """Contraction and width tiles beside a row tile of ``tm``.  Under 512
    rows HBM paces a visit (at 256 x 768 its product is 192 FLOPs a byte
    fetched, where the chip does 240 in the time it moves one), and what a
    visit fetches is what counts: the contraction whole and as much of the
    width as VMEM holds, so that a visit addresses the blocks the one before
    it did — a group's weights are fetched once, not once a row tile it
    spans, and a row tile's rows once, not once a group in it.  On the chip
    at 65,536 rows over 256 experts of 2048 x 768: 2.44 / 2.71 ms a call
    where the tiled contraction took 3.03 / 2.95 at the same row tile, and
    3.86 at 128 rows, slower than 512's 3.49.  At 512 rows the product
    outlasts its fetches (307 FLOPs a byte) and the tiles stay the widest of
    1024 or under that divide, as the chip has run them since PR 27 (whole
    contractions would buy 4-7 % of a call there: ``PERF.md`` section 6)."""
    if tm < 512 and k % 128 == 0 and n % 128 == 0:
        for tn in (n, *(t for t in (1024, 768, 512, 384, 256, 128) if n % t == 0)):
            if _gmm_vmem_bytes(tm, k, tn, itemsize) <= _GMM_VMEM_BYTES:
                return k, tn
    return _largest_tile(k), _largest_tile(n)


def gmm_work_over_need(sizes, tile: int):
    """Rows the kernel multiplies over rows that have a group, at row tile
    ``tile`` and group ``sizes`` (consecutive rows from row 0): (row tile,
    group) pairs with a row in common x ``tile`` / ``sum(sizes)``."""
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tile
    visits = jnp.where(sizes > 0, -(-ends // tile) - first, 0).sum()
    return (visits * tile).astype(jnp.float32) / jnp.maximum(ends[-1], 1)


def _pallas_grouped_dot_ok(rows: int) -> bool:
    """The Pallas kernel where the compiler takes it: on a TPU, rows a
    multiple of its smallest row tile, and where a Mosaic call may be issued
    bare (it cannot be partitioned, and this layer wraps it in no
    ``shard_map``)."""
    from ..ops.pallas import bare_mosaic_call_ok

    return (jax.default_backend() == "tpu" and rows % 128 == 0
            and bare_mosaic_call_ok())


def _grouped_dot(lhs, rhs, sizes, layer=None):
    """``lhs[rows of group g] @ rhs[g]`` for consecutive groups of ``sizes``
    rows: ``(M, k) x (G, k, n) -> (M, n)``, differentiable in both operands.
    ``sizes`` must cover every group of ``rhs`` and every row of ``lhs``
    for the Pallas kernel (rows no group covers are left unwritten there).

    With ``layer`` (a traced index), ``rhs`` is a scanned stack's whole leaf
    ``(L, G, k, n)`` and the product is the one with ``rhs[layer]``, read IN
    PLACE: the leading dimensions merge (a bitcast) and the ``L·G`` groups are
    empty outside ``[layer·G, (layer+1)·G)``.  An empty group costs the kernel
    no grid step, and the row tile of group ``g`` reads block ``layer·G + g``."""
    g = rhs.shape[-3]
    if layer is not None:
        n_layers = rhs.shape[0]
        rhs = rhs.reshape((n_layers * g,) + rhs.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * g,), sizes.dtype), sizes, (layer * g,))
    if _pallas_grouped_dot_ok(lhs.shape[0]):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(
            lhs, rhs, sizes, lhs.dtype, _GmmTiling(g, lhs.dtype.itemsize))
    return jax.lax.ragged_dot(lhs, rhs, sizes)


# ---- the two permutations of the dropless dispatch, gathers both ways -------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_of_tokens(x, order, inverse, k: int):
    """``x[order // k]``: row ``r`` of the sorted pairs is token
    ``order[r] // k``.  ``inverse`` is the inverse permutation of ``order``."""
    return x[order // k]


def _rows_fwd(x, order, inverse, k):
    return x[order // k], (inverse, x.shape[0])


def _rows_bwd(k, res, g):
    inverse, t = res
    # a token's k pairs, found again through the inverse permutation and
    # summed: a gather, where autodiff would scatter-add
    dx = g[inverse].reshape(t, k, g.shape[-1]).sum(1, dtype=jnp.float32)
    return dx.astype(g.dtype), None, None


_rows_of_tokens.defvjp(_rows_fwd, _rows_bwd)


@jax.custom_vjp
def _unsort(rows, order, inverse):
    """``rows[inverse]``: the sorted pairs back in (token, choice) order."""
    return rows[inverse]


_unsort.defvjp(lambda rows, order, inverse: (rows[inverse], order),
               lambda order, g: (g[order], None, None))


# ---- a held share of the experts: its pairs alone, in rows sized by shapes ----


def held_row_bound(pairs: int, n_held: int, n_experts: int) -> int:
    """Rows ONE pass of a held share's gathers and grouped products is sized
    for: twice the pairs an even router sends ``n_held`` of ``n_experts``
    experts, rounded up to the grouped product's row tile and never more than
    all ``pairs``.  From shapes alone.  A balanced router (a trained selection
    bias; seeded experts, whose fullest SINGLE expert reads 1.17-1.38 x its
    mean and a sixteenth of them together far closer) fits the first pass; a
    router that sends the share more gets further passes of as many rows, as
    many as its load asks for (``MoEMLP._dropless_held``): what is static is a
    pass's memory, not the pairs computed."""
    even = -(-pairs * n_held // n_experts)
    return min(pairs, -(-2 * even // 512) * 512)


def _dropless_rows(pairs: int, n_held: int, n_experts: int) -> int:
    """Rows one call of a dropless layer's grouped products covers: every
    pair, or a pass of a held share's."""
    return (pairs if n_held == n_experts
            else held_row_bound(pairs, n_held, n_experts))


def dropless_row_tile(pairs: int, n_held: int, n_experts: int) -> int | None:
    """The row tile a dropless layer's grouped products run at with ``pairs``
    routed pairs a call and ``n_held`` of ``n_experts`` experts held, or None
    where the Pallas kernel does not run.  Static: the ``train-started``
    event carries it."""
    rows = _dropless_rows(pairs, n_held, n_experts)
    return gmm_row_tile(rows, n_held) if _pallas_grouped_dot_ok(rows) else None


#: the compiler under which ``held_sum_form``'s two readings were taken on
#: the chip (PERF.md section 6, PR 46): which side of a cutoff wins is this
#: compiler's fusions as much as arithmetic, so read both again under another
SUM_FORMS_READ_UNDER = {"jax": "0.9.0", "libtpu": "0.0.34"}

#: rows a block of the rows form's one-hot product sums: the matrix unit's
#: own tile
_SUM_BLOCK = 128


def held_sum_form(tokens: int, k: int, bound: int) -> str:
    """How a held pass sums its rows back at their tokens (the combine, and
    the gradient of the dispatch's gather, which is the same sum without
    weights): ``"rows"`` — the pass's ``bound`` rows gathered into token
    order and summed there (``_sum_of_rows``: work in proportion to
    ``bound``) — or ``"choices"`` — a gather of ``tokens`` rows a choice, each
    added to a ``(tokens, d)`` float32 accumulator (``_sum_of_pairs``: in
    proportion to ``tokens · k``).  From shapes alone: the rows form where a
    pass holds at most half the routed pairs — every share of up to a quarter
    of the experts, ``held_row_bound`` being twice an even share.  What the
    v5e read, a sum alone (rows / choices, ms): at ``bound / (tokens · k)``
    0.125 (16,384 rows for top-8 of 16,384 tokens, a sixteenth of the experts
    held) 3.5 / 12.1 at 4,096 columns and 4.6 / 17.4 at 6,144; at 0.5 (90,112
    rows for top-22 of 8,192 tokens, a quarter held) 3.1 / 6.0 at 1,024 — the
    three cells' shapes, whose steps were read too.  Above it, alone only:
    at 0.75, 3.1 / 3.8 (top-8 of 4,096 tokens at 4,096 columns) and 4.3 / 6.3
    (top-22 of 8,192 at 1,024); at 1.0, a pass of every pair, 3.7 / 4.0 and
    5.2 / 6.4 — the rows form still ahead, by less and less, and no step has
    run it there, so the cutoff stays at the largest ratio a step was read
    at.  A token's rows must also fit a block of the product."""
    return ("rows" if 2 * bound <= tokens * k and k <= _SUM_BLOCK
            else "choices")


def _sum_of_pairs(rows, row_of_pair, weight=None):
    """``sum_j weight[t, j] * rows[row_of_pair[t, j]]`` in float32, ``(T, d)``;
    an index of ``len(rows)`` reads a zero row.  One gather of ``T`` rows a
    choice, never the ``(T, k, d)`` array of them all."""
    padded = jnp.concatenate([rows, jnp.zeros((1, rows.shape[1]), rows.dtype)])
    out = jnp.zeros((row_of_pair.shape[0], rows.shape[1]), jnp.float32)
    for j in range(row_of_pair.shape[1]):
        term = padded[row_of_pair[:, j]].astype(jnp.float32)
        out = out + (term if weight is None else term * weight[:, j, None])
    return out


class _ByToken(NamedTuple):
    """A pass's rows in (token, choice) order for ``_sum_of_rows``: ``n``
    places, the live rows first, in blocks of ``_SUM_BLOCK`` with one block
    of dead places at the end.  Integers, kept with the rest of the routing."""

    #: the row at each place, ``(n,)`` (a dead place's: any)
    row: jax.Array
    #: its (token, choice) pair as one index into ``T·k`` (a dead place's: any)
    pair: jax.Array
    #: which of its block's tokens it belongs to, counted from the block's
    #: first place; ``-1`` for a dead place
    slot: jax.Array
    #: where a token's sum lands in the blocks' results, ``(T,)``: the slot of
    #: its first row in that row's block (a token without a row: a slot of the
    #: dead block, which sums nothing)
    place: jax.Array
    #: for every block after the first, the token its first place CONTINUES
    #: from the block before (its rows straddle the two), or ``T``
    straddler: jax.Array


def _rows_by_token(pair_of_row, row_of_pair) -> _ByToken:
    t, k = row_of_pair.shape
    bound = pair_of_row.shape[0]
    n = -(-bound // _SUM_BLOCK) * _SUM_BLOCK + _SUM_BLOCK
    # the pairs of this pass in (token, choice) order ARE ``row_of_pair`` read
    # flat, the others skipped: a running count places them, no sort
    held = row_of_pair.reshape(-1) < bound
    ahead = jnp.cumsum(held, dtype=jnp.int32)
    elsewhere = n + jnp.arange(t * k, dtype=jnp.int32)    # dropped, and unique
    row = jnp.zeros((n,), jnp.int32).at[jnp.where(held, ahead - 1, elsewhere)].set(
        row_of_pair.reshape(-1), mode="drop", unique_indices=True)
    pair = pair_of_row[row]
    live = jnp.arange(n, dtype=jnp.int32) < ahead[-1]
    token = jnp.where(live, pair // k, t)
    # a block's tokens numbered from its first place's
    opens = live & jnp.concatenate(
        [jnp.ones((1,), bool), token[1:] != token[:-1]])
    nth = jnp.cumsum(opens, dtype=jnp.int32).reshape(-1, _SUM_BLOCK)
    slot = (nth - nth[:, :1]).reshape(n)
    count = held.reshape(t, k).sum(1, dtype=jnp.int32)
    first = jnp.minimum(ahead.reshape(t, k)[:, -1] - count, n - 1)
    place = jnp.where(
        count > 0, first // _SUM_BLOCK * _SUM_BLOCK + slot[first], n - 1)
    token = token.reshape(-1, _SUM_BLOCK)
    straddler = jnp.where(token[1:, 0] == token[:-1, -1], token[1:, 0], t)
    return _ByToken(row, pair, jnp.where(live, slot, -1), place, straddler)


def _sum_of_rows(rows, by_token: _ByToken, weight=None):
    """``_sum_of_pairs``' sum over the rows that exist.  ONE gather puts the
    pass's rows in token order; every block of ``_SUM_BLOCK`` places is then
    summed into its own tokens by a product with the (weighted) one-hot of
    ``slot`` — float32 weights on bf16 rows at the matrix unit's highest
    precision, each product exact and accumulated in float32: the same terms
    as ``_sum_of_pairs`` in another order —; one gather of ``T`` rows brings
    each token the sum of its first row's block, and the token whose rows
    straddle two blocks (at most one a block, ``k`` being no more than a
    block) is added the second block's part."""
    n, d = by_token.row.shape[0], rows.shape[1]
    blocks = n // _SUM_BLOCK
    slot = by_token.slot.reshape(blocks, 1, _SUM_BLOCK)
    hot = slot == jnp.arange(_SUM_BLOCK, dtype=slot.dtype)[None, :, None]
    # a dead row may hold anything (the Pallas product leaves it unwritten),
    # and nothing times zero is not always zero
    sorted_rows = jnp.where((by_token.slot >= 0)[:, None], rows[by_token.row], 0)
    if weight is None:
        onehot = hot.astype(rows.dtype)
    else:
        onehot = jnp.where(hot, weight.reshape(-1)[by_token.pair].reshape(
            blocks, 1, _SUM_BLOCK).astype(jnp.float32), 0)
        sorted_rows = sorted_rows.astype(jnp.float32)
    part = jnp.einsum(
        "bsr,brd->bsd", onehot, sorted_rows.reshape(blocks, _SUM_BLOCK, d),
        precision=(jax.lax.Precision.HIGHEST
                   if onehot.dtype == jnp.float32 else None),
        preferred_element_type=jnp.float32)
    return part.reshape(n, d)[by_token.place].at[by_token.straddler].add(
        part[1:, 0], mode="drop")


def _sum_at_tokens(rows, row_of_pair, by_token, weight=None):
    """A pass's rows summed at their tokens, ``(T, d)`` float32, in the form
    ``_held_pass`` chose: ``by_token`` is None for the choices form."""
    if by_token is None:
        return _sum_of_pairs(rows, row_of_pair, weight)
    return _sum_of_rows(rows, by_token, weight)


@jax.custom_vjp
def _held_rows_of_tokens(x, token_of_row, row_of_pair, by_token):
    """``x[token_of_row]``: the tokens of the leading sorted pairs.
    ``row_of_pair`` is the way back, ``(T, k)``: the row a pair landed in;
    ``by_token``: the rows in token order, or None (``held_sum_form``)."""
    return x[token_of_row]


_held_rows_of_tokens.defvjp(
    lambda x, token_of_row, row_of_pair, by_token: (
        x[token_of_row], (row_of_pair, by_token)),
    # a token's pairs, found again and summed: no scatter-add of the rows
    lambda res, g: (_sum_at_tokens(g, *res).astype(g.dtype), None, None, None))


@jax.custom_vjp
def _held_combine(rows, weight, row_of_pair, pair_of_row, by_token):
    """``sum_j weight[t, j] * rows[row_of_pair[t, j]]``: the rows back at
    their tokens, weighted.  ``pair_of_row``: the (token, choice) pair, as one
    index into ``T·k``, of every row; ``by_token`` as above."""
    return _sum_at_tokens(rows, row_of_pair, by_token, weight)


def _held_combine_fwd(rows, weight, row_of_pair, pair_of_row, by_token):
    return (_sum_at_tokens(rows, row_of_pair, by_token, weight),
            (rows, weight, row_of_pair, pair_of_row))


def _held_combine_bwd(res, g):
    rows, weight, row_of_pair, pair_of_row = res
    k = weight.shape[1]
    g_rows = g[pair_of_row // k]
    d_rows = (g_rows * weight.reshape(-1)[pair_of_row][:, None]).astype(rows.dtype)
    # a pair's weight gradient is its row's: one reduction over the pass's
    # rows, then the (T, k) layout by a gather of scalars
    d_row = (rows.astype(jnp.float32) * g_rows).sum(-1)
    d_weight = jnp.concatenate([d_row, jnp.zeros((1,), d_row.dtype)])[row_of_pair]
    return d_rows, d_weight.astype(weight.dtype), None, None, None


_held_combine.defvjp(_held_combine_fwd, _held_combine_bwd)


def _sizes_in_rows(sizes, start, bound: int):
    """The part of every group that lies in the rows ``[start, start +
    bound)`` of the pairs sorted by group."""
    ends = jnp.cumsum(sizes)
    return (jnp.clip(ends - start, 0, bound)
            - jnp.clip(ends - sizes - start, 0, bound))


def _pass_rows(order, sizes, start, bound: int, t: int, k: int, form: str):
    """The integers of the pass over the rows ``[start, start + bound)`` of
    the pairs sorted with the held experts' first, all kept: every held
    expert's rows in the pass, ``pair_of_row`` (the pair, as one index into
    ``T·k``, of every row), ``row_of_pair`` (the way back, ``(T, k)``; ``bound``
    — a zero row — for a pair of an expert held elsewhere or of another
    pass), which rows are ``live`` and, in the ``"rows"`` form
    (``held_sum_form``), the rows in token order."""
    mine = sizes.sum()
    sizes = _kept(_sizes_in_rows(sizes, start, bound))
    pair_of_row = jax.lax.dynamic_slice(order, (start,), (bound,))
    row = jnp.arange(bound, dtype=jnp.int32)
    live = row < mine - start
    row_of_pair = _kept(
        jnp.full((t * k,), bound, jnp.int32).at[pair_of_row].set(
            jnp.where(live, row, bound), mode="drop", unique_indices=True
        ).reshape(t, k))
    pair_of_row = _kept(jnp.minimum(pair_of_row, t * k - 1))
    by_token = (jax.tree.map(_kept, _rows_by_token(pair_of_row, row_of_pair))
                if form == "rows" else None)
    return sizes, pair_of_row, row_of_pair, live, by_token


def _held_pass(x, top_w, kernels, layer, order, sizes, start, bound: int):
    """What the held experts give the rows ``[start, start + bound)`` of the
    pairs sorted with the held experts' first, back at their tokens: ``(T,
    d)`` float32.  ``order`` holds ``start + bound`` entries at least (padded
    past the ``T·k`` pairs with indices no pair has); ``sizes`` are the held
    experts' pairs, whole; ``layer``: as ``_grouped_dot`` takes it."""
    t, k = top_w.shape
    with jax.named_scope("moe_dispatch"):
        sizes, pair_of_row, row_of_pair, live, by_token = _pass_rows(
            order, sizes, start, bound, t, k, held_sum_form(t, k, bound))
        rows = _held_rows_of_tokens(x, pair_of_row // k, row_of_pair, by_token)
    with jax.named_scope("experts"):
        # rows behind the last group are covered by no group: the compiler's
        # product writes zeros there, the Pallas one nothing — and nothing
        # reads them (``row_of_pair`` names none, and the rows form masks them)
        out_rows = _experts_of_rows(rows, kernels, functools.partial(
            _grouped_dot, sizes=sizes, layer=layer))
        out_rows = jnp.where(live[:, None], out_rows, 0)
    with jax.named_scope("moe_combine"):
        return _held_combine(out_rows, top_w, row_of_pair, pair_of_row, by_token)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _held_later_passes(out, x, top_w, kernels, layer, order, sizes, bound: int):
    """``out`` plus what ``_held_pass`` gives every ``bound`` rows after the
    first: as many passes as the share's load asks for, none where it fits the
    first.  The backward pass walks them again, a pass recomputed at a time,
    and keeps nothing but the arguments: a skewed step costs time, never
    memory.  (Two loops written out, because a ``scan`` of ``cond``s under
    ``jax.checkpoint`` holds a pass's buffers beside the step's whether or
    not a pass runs: the compile refused the 16k step by 1.7 GB.)"""
    return _held_later_fwd(out, x, top_w, kernels, layer, order, sizes, bound)[0]


def _held_later_fwd(out, x, top_w, kernels, layer, order, sizes, bound):
    out = jax.lax.fori_loop(
        1, -(-sizes.sum() // bound), lambda p, out: out + _held_pass(
            x, top_w, kernels, layer, order, sizes, p * bound, bound), out)
    return out, (x, top_w, kernels, layer, order, sizes)


def _held_later_bwd(bound, res, g):
    x, top_w, kernels, layer, order, sizes = res
    # experts read in place in a stack take no gradient (``MoEMLP.__call__``)
    wrt = (x, top_w) if layer is not None else (x, top_w, kernels)

    def one(p, grads):
        def rows(x, top_w, kernels=kernels):
            return _held_pass(x, top_w, kernels, layer, order, sizes,
                              p * bound, bound)

        return jax.tree.map(jnp.add, grads, jax.vjp(rows, *wrt)[1](g))

    grads = jax.lax.fori_loop(1, -(-sizes.sum() // bound), one,
                              jax.tree.map(jnp.zeros_like, wrt))
    return (g, *grads, *(None,) * (6 - len(grads)))


_held_later_passes.defvjp(_held_later_fwd, _held_later_bwd)


class MoEMLP(nn.Module):
    """Drop-in replacement for the dense SwiGLU MLP."""

    d_model: int
    d_ff: int
    n_experts: int
    top_k: int = 2
    capacity_factor: float = 1.25
    #: ``capacity`` (static slots per expert, pairs over them dropped) |
    #: ``dropless`` (sorted pairs, one grouped product, nothing dropped)
    dispatch: str = "capacity"
    scoring: str = "softmax"           # | "sigmoid"
    #: a frozen per-expert bias added to the scores for SELECTION only
    select_bias: bool = False
    #: factor on the chosen experts' weights, after they are divided by
    #: their sum
    routed_scale: float = 1.0
    #: ``(first, count)``: the experts this layer holds and computes; it still
    #: routes over all ``n_experts``.  ``None`` = all of them
    experts_held: tuple[int, int] | None = None
    #: the shared expert every token passes, or ``None`` (a module, so that
    #: its projections are the model's ordinary LoRA-carrying ones)
    shared: nn.Module | None = None
    #: experts with a gate matrix (SwiGLU: three products a pass) or without
    #: (``down(relu(up x)^2)``: two)
    gated: bool = True
    #: experts in a latent: the projection into it and the one back out
    #: (modules, as ``shared`` is, under these names), or ``None`` for experts
    #: at the router's width.  The router and the shared expert read the
    #: layer's input; the rows dispatched, multiplied and combined are the
    #: latent's, and ``fc2_latent_proj`` — linear, so the partial sums of the
    #: shares of a layer add up — is applied to the combined sum
    fc1_latent_proj: nn.Module | None = None
    fc2_latent_proj: nn.Module | None = None
    #: sow the Switch load-balancing term into ``moe_aux``
    aux_loss: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: store the stacked expert kernels as blockwise int4 (models/quant.py,
    #: vmapped over the expert axis) — the QLoRA trade at MoE scale: experts
    #: are ~95% of a Mixtral-family model's weights, so quantizing them is
    #: what fits a 10B-class 8-expert model on one v5e chip
    quantize_base: bool = False
    quant_block: int = 64

    @nn.compact
    def __call__(self, x: jax.Array, deterministic: bool = True,
                 layer=None, stacked=None) -> jax.Array:
        """``layer`` and ``stacked``: this layer's index in a scanned stack
        and the stack's three expert kernels whole, ``(L, E, ., .)`` each
        (``models/llama.py`` hands them where the experts take no gradient);
        the layer then reads its experts in place where that saves a copy."""
        if self.dispatch not in ("capacity", "dropless"):
            raise ValueError(f"unknown MoE dispatch {self.dispatch!r}")
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown MoE scoring {self.scoring!r}")
        first, n_held = self.experts_held or (0, self.n_experts)
        if not (0 <= first and n_held >= 1 and first + n_held <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} lies outside "
                             f"the {self.n_experts} experts")
        if self.dispatch == "capacity" and n_held != self.n_experts:
            raise ValueError("experts_held needs the dropless dispatch")
        b, s, d = x.shape
        t = b * s
        e, k = self.n_experts, self.top_k
        xt = x.reshape(t, d)

        # ---- router (f32) --------------------------------------------------
        with jax.named_scope("moe_route"):
            logits, bias = _Router(
                e, self.select_bias, self.param_dtype, name="router")(xt)
            scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                      else jax.nn.softmax(logits, axis=-1))         # (T, E)
            select = scores if bias is None else scores + bias.astype(jnp.float32)
            top_idx = _kept(jax.lax.top_k(select, k)[1])            # (T, k)
            top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
            top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
            top_w = top_w * self.routed_scale
            onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (T, k, E)
            load = _kept(onehot.sum((0, 1)).astype(jnp.int32))      # pairs an expert

        if self.fc1_latent_proj is not None:
            # the experts' rows: the latent's (no activation, no norm)
            xt = self.fc1_latent_proj(x, deterministic).reshape(t, -1)
        kernels = _Experts(
            n_held, xt.shape[1], self.d_ff, self.dtype, self.param_dtype,
            self.quantize_base, self.quant_block, self.gated, name="experts")()
        # the Pallas kernel is a custom call and takes its operand whole, so
        # the slice the scan hands this body would be copied for it; the
        # compiler's own product fuses that slice and has nothing to save.
        # Unquantised and stored in the compute type: else ``kernels`` is a
        # fresh array already, or a cast of the whole stack
        held = self.dispatch == "dropless" and n_held != e
        in_place = (
            stacked is not None and self.dispatch == "dropless"
            and not self.quantize_base
            and all(w.dtype == jnp.dtype(self.dtype) for w in stacked)
            and _pallas_grouped_dot_ok(_dropless_rows(t * k, n_held, e)))
        if held:
            out, pairs = self._dropless_held(
                xt, top_idx, top_w, load, stacked if in_place else kernels,
                first, layer if in_place else None)
        elif in_place:
            out, pairs = self._dropless(xt, top_idx, top_w, load, stacked, layer)
        elif self.dispatch == "dropless":
            out, pairs = self._dropless(xt, top_idx, top_w, load, kernels)
        else:
            out, pairs = self._capacity(xt, top_idx, top_w, onehot, kernels)
        if self.fc2_latent_proj is not None:
            out = self.fc2_latent_proj(
                out.astype(self.dtype).reshape(b, s, -1), deterministic
            ).reshape(t, d)
        if self.shared is not None:
            out = out + self.shared(x, deterministic).reshape(t, d).astype(out.dtype)

        if self.aux_loss:
            # ---- load-balancing aux loss (Switch eq. 4) ---------------------
            frac_routed = load / t                   # f_e: fraction per expert
            mean_prob = scores.mean(0)               # P_e
            self.sow("moe_aux", "load_balance", e * jnp.sum(frac_routed * mean_prob))
        self.sow("moe_stats", "load_max_over_mean", load.max() * (e / (t * k)))
        self.sow("moe_stats", "pairs", pairs.astype(jnp.float32))
        self.sow("moe_stats", "experts_in_place", jnp.float32(in_place))
        return out.reshape(b, s, d).astype(x.dtype)

    # ---- dropless: sorted pairs, one grouped product --------------------------

    def _dropless(self, xt, top_idx, top_w, load, kernels, layer=None):
        """Every expert held.  ``layer``: ``kernels`` are a scanned stack's
        whole leaves and this layer's experts are read in place there."""
        t, d = xt.shape
        k = self.top_k
        with jax.named_scope("moe_dispatch"):
            order = _kept(
                jnp.argsort(top_idx.reshape(-1), stable=True).astype(jnp.int32))
            inverse = _kept(jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=jnp.int32), unique_indices=True))
            rows = _rows_of_tokens(xt.astype(self.dtype), order, inverse, k)
        with jax.named_scope("experts"):
            out_rows = _experts_of_rows(rows, kernels, functools.partial(
                _grouped_dot, sizes=load, layer=layer))
        self._sow_gmm_work(load, t * k)
        with jax.named_scope("moe_combine"):
            pair_out = _unsort(out_rows, order, inverse).reshape(t, k, d)
            out = jnp.einsum("tk,tkd->td", top_w,
                             pair_out.astype(jnp.float32))
        return out, load.sum()

    # ---- dropless, a share of the experts held: its own pairs alone -----------

    def _dropless_held(self, xt, top_idx, top_w, load, kernels, first: int,
                       layer=None):
        """The part of the layer's result the held experts give (``layer``
        as in ``_dropless``).  The pairs
        are sorted with the held experts' first, and only those leading rows
        are gathered, multiplied and combined, ``held_row_bound`` rows a pass
        (``_held_pass``): a sixteenth of the experts gets a sixteenth of the
        pairs, and the ``(T·k, d)`` gathers of all of them (1.6 GB each at
        131,072 pairs of width 6144) are what does not fit beside the model.
        A balanced router's share fits the first pass.  Never a drop: where a
        router sends the share more, further passes of as many rows follow
        (``_held_later_passes``), as many as the load asks for; the pairs
        they computed are counted (``pairs_over_bound``)."""
        t, d = xt.shape
        e, k = self.n_experts, self.top_k
        n_held = kernels[0].shape[-3]
        bound = held_row_bound(t * k, n_held, e)
        further = -(-t * k // bound) - 1       # the most a router can ask for
        with jax.named_scope("moe_dispatch"):
            # held experts first, in order: their pairs are the leading rows
            key = (top_idx.reshape(-1) - first) % e                 # (T·k,)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            order = _kept(jnp.concatenate([order, jnp.arange(
                t * k, (further + 1) * bound, dtype=jnp.int32)]))
            sizes = jnp.roll(load, -first)[:n_held]
            mine = sizes.sum()
        x = xt.astype(self.dtype)
        out = _held_pass(x, top_w, kernels, layer, order, sizes, 0, bound)
        if further:
            out = _held_later_passes(
                out, x, top_w, kernels, layer, order, sizes, bound)
        self.sow("moe_stats", "pairs_over_bound",
                 jnp.maximum(mine - bound, 0).astype(jnp.float32))
        self._sow_gmm_work(_sizes_in_rows(sizes, 0, bound), t * k)
        return out, mine

    def _sow_gmm_work(self, sizes, pairs: int) -> None:
        """``gmm_work_over_need`` of one call of the Pallas kernel on groups
        of ``sizes`` rows (a held share's: of its first pass) with ``pairs``
        routed pairs; nothing where the compiler's own product runs, which
        has no tile."""
        tile = dropless_row_tile(pairs, sizes.shape[0], self.n_experts)
        if tile:
            self.sow("moe_stats", "gmm_work_over_need",
                     gmm_work_over_need(sizes, tile))

    # ---- capacity: static slots per expert, pairs over them dropped -----------

    def _capacity(self, xt, top_idx, top_w, onehot, kernels):
        t, d = xt.shape
        e, k = self.n_experts, self.top_k
        compute_dtype = self.dtype
        # static per-expert capacity (tokens), padded to a lane-friendly size
        capacity = max(8, math.ceil(t / e * self.capacity_factor * k))
        capacity = min(capacity, t)

        with jax.named_scope("moe_dispatch"):
            # ---- slot assignment (slot-major priority, static shapes) ------
            slot_major = onehot.transpose(1, 0, 2).reshape(k * t, e)    # slot 0 first
            position = jnp.cumsum(slot_major, axis=0) - slot_major      # rank within expert
            position = position.reshape(k, t, e).transpose(1, 0, 2)     # (T, k, E)
            pos_idx = (position * onehot).sum(-1).astype(jnp.int32)     # (T, k)

            # ---- scatter/gather dispatch (no (T, E, C) one-hot matmuls) ----
            # The classic GShard dense dispatch materialises (T, E, C) one-hot
            # tensors and runs "tec,td->ecd" / "tec,ecd->td" einsums whose
            # cost is (E·C)·T·d MACs — at T=8192 with C=T·cf·k/E that is
            # ~T/(3·d_ff) of the expert matmuls themselves (~50% overhead at
            # the mixtral-proxy bench shapes, and growing linearly with T;
            # measured MFU collapsed 0.38 → 0.26 from bs4 → bs8). Because
            # every routed (token, k) pair owns a UNIQUE (expert, slot),
            # dispatch is really a permutation: scatter the 1-D token ids
            # (cheap), then gather rows.
            valid = pos_idx < capacity                                  # (T, k) bool
            n_slots = e * capacity
            # invalid pairs target index n_slots: OOB for the scatter
            # (dropped) and exactly the appended zero row for the combine
            slot = _kept(jnp.where(valid, top_idx * capacity + pos_idx, n_slots))
            t_ids = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k))
            # empty slots keep sentinel T -> gather the appended zero row, so
            # unfilled capacity computes on zeros exactly as the dense dispatch
            token_of_slot = _kept(jnp.full((n_slots,), t, jnp.int32).at[
                slot.reshape(-1)
            ].set(t_ids.reshape(-1), mode="drop"))
            xt_pad = jnp.concatenate(
                [xt.astype(compute_dtype), jnp.zeros((1, d), compute_dtype)]
            )
            expert_in = xt_pad[token_of_slot].reshape(e, capacity, d)

        # ---- expert compute (batched over the ep axis) ----------------------
        with jax.named_scope("experts"):
            expert_out = _experts_of_rows(
                expert_in, kernels, functools.partial(jnp.einsum, "ecd,edf->ecf"))

        # combine: per routed pair, gather its slot's output row (invalid
        # pairs hit the zero row — identical to the dense combine, where
        # their weight mass was masked) and weight by the renormed router
        with jax.named_scope("moe_combine"):
            out_flat = jnp.concatenate(
                [expert_out.reshape(n_slots, d), jnp.zeros((1, d), compute_dtype)]
            )
            gathered = out_flat[slot]                                   # (T, k, d)
            out = (top_w.astype(compute_dtype)[..., None] * gathered).sum(1)
        return out, valid.sum()


def n_held(cfg) -> int:
    """Experts a device holds: ``experts_held``'s count, else every one."""
    return (cfg.experts_held or (0, cfg.n_experts))[1]


def run_description(cfg, tokens: int) -> dict:
    """What an expert model says of a dropless layer's grouped products at
    ``train-started``, at ``tokens`` a microbatch and under the mesh in scope:
    their row tile where the Pallas kernel runs (``moe_gmm_work_over_need``
    among the step's counters is what it costs) and, of a held share, the
    rows of one pass over the routed pairs and the form its per-token sums
    run in for those shapes."""
    if cfg.moe_dispatch != "dropless":
        return {}
    attrs: dict[str, Any] = {}
    pairs, held = tokens * cfg.moe_top_k, n_held(cfg)
    tile = dropless_row_tile(pairs, held, cfg.n_experts)
    if tile:
        attrs["moe_gmm_row_tile"] = tile
    if held != cfg.n_experts:
        bound = held_row_bound(pairs, held, cfg.n_experts)
        attrs["moe_held_sum_form"] = held_sum_form(tokens, cfg.moe_top_k, bound)
        attrs["moe_held_rows_over_pairs"] = bound / pairs
    return attrs


def moe_aux_loss(collections: dict) -> jax.Array:
    """Sum every sown load-balance term (scan stacks them per layer)."""
    leaves = jax.tree_util.tree_leaves(collections.get("moe_aux", {}))
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(leaf) for leaf in leaves)


#: a counter sown into ``moe_stats`` -> how the layers' readings become the step's
_COUNTERS = {"load_max_over_mean": jnp.max, "pairs": jnp.min,
             "experts_in_place": jnp.sum, "pairs_over_bound": jnp.max,
             "gmm_work_over_need": jnp.max}


def moe_counters(collections: dict) -> dict:
    """The step's counters from the sown ``moe_stats`` (scan stacks them per
    layer): ``moe_load_max_over_mean`` of the worst layer, ``moe_pairs`` of
    the layer that computed the fewest (``T·k`` where nothing is dropped),
    ``moe_experts_in_place``, the number of layers whose grouped products read
    their experts in place in the scanned stack, and of the worst layer
    ``moe_pairs_over_bound`` and ``moe_gmm_work_over_need``.  Empty for a
    model without expert layers."""
    sown: dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            collections.get("moe_stats", {})):
        name = next(n for p in path if (n := str(getattr(p, "key", p))) in _COUNTERS)
        sown.setdefault(name, []).append(jnp.ravel(leaf))
    return {f"moe_{name}": _COUNTERS[name](jnp.concatenate(sown[name]))
            for name in _COUNTERS if name in sown}
