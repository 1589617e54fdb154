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
    ``_grouped_dot`` — on one TPU the Pallas megablox kernel, measured 1.6x
    (forward) and 2.3x (activation gradient) faster at 65,536 rows x 256
    experts of 2048 x 768 than the compiler's own lowering of
    ``jax.lax.ragged_dot``, which is the path everywhere else), un-sorted and
    combined with float32 weights.  No pair is dropped at any imbalance.
    Both permutations are gathers in BOTH passes (``_rows_of_tokens``,
    ``_unsort``): the transpose of a row gather is a scatter-add, which a
    TPU serialises.

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
  have a weight gradient visit all ``L·E`` groups a layer), it holds all
  its experts, and they are stored unquantised in the compute type (a
  dequantised or cast kernel is a fresh whole array already).  Everywhere
  else the per-layer slice, as before.  The variable tree is the same
  either way;
- ``experts_held = (first, count)``: the layer routes over ALL experts and
  computes the part of the result its own ``count`` experts give (plus the
  shared expert) — what expert parallelism needs of one member, and what a
  chip holding a share of the experts runs;
- an optional shared expert (a dense SwiGLU every token passes, handed in as
  a module so its projections carry LoRA like any other);
- Switch-Transformer load-balancing aux loss, sown into the ``moe_aux``
  collection where the configuration asks for one (the trainer folds it into
  the objective), and three counters sown into ``moe_stats``:
  ``load_max_over_mean`` (the fullest expert's pairs over the mean),
  ``pairs`` (pairs that reached an expert: ``T·k`` when nothing is dropped)
  and ``experts_in_place`` (1.0 where the layer read its experts in place).
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp


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


class _Experts(nn.Module):
    """The stacked kernels of the experts this layer holds."""

    n_held: int
    d_model: int
    d_ff: int
    dtype: Any
    param_dtype: Any
    quantize_base: bool = False
    quant_block: int = 64

    @nn.compact
    def __call__(self):
        e, d, f = self.n_held, self.d_model, self.d_ff
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype,
                  quantize_base=self.quantize_base,
                  quant_block=self.quant_block)
        return (_StackedKernel((e, d, f), name="gate_proj", **kw)(),
                _StackedKernel((e, d, f), name="up_proj", **kw)(),
                _StackedKernel((e, f, d), name="down_proj", **kw)())


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


def _gmm_tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """Tiles of the megablox kernel from the shapes of THIS product (the
    activation-gradient product swaps ``k`` and ``n``): rows 512 where they
    divide, the widest tile of 1024 or under that divides each width — (512,
    1024, 768) and (512, 768, 1024) at 2048 x 768 experts, 8 MB of VMEM."""
    return (_largest_tile(m, (512, 256, 128)), _largest_tile(k), _largest_tile(n))


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
    if layer is not None:
        n_layers, g = rhs.shape[:2]
        rhs = rhs.reshape((n_layers * g,) + rhs.shape[2:])
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * g,), sizes.dtype), sizes, (layer * g,))
    if _pallas_grouped_dot_ok(lhs.shape[0]):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiling)
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
            _, top_idx = jax.lax.top_k(select, k)                   # (T, k)
            top_w = jnp.take_along_axis(scores, top_idx, axis=-1)
            top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
            top_w = top_w * self.routed_scale
            onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)  # (T, k, E)
            load = onehot.sum((0, 1))                               # pairs an expert

        kernels = _Experts(
            n_held, d, self.d_ff, self.dtype, self.param_dtype,
            self.quantize_base, self.quant_block, name="experts")()
        # the Pallas kernel is a custom call and takes its operand whole, so
        # the slice the scan hands this body would be copied for it; the
        # compiler's own product fuses that slice and has nothing to save.
        # Everything held, unquantised and stored in the compute type: else
        # ``kernels`` is a fresh array already, or a cast of the whole stack
        in_place = (
            stacked is not None and self.dispatch == "dropless"
            and self.experts_held is None and not self.quantize_base
            and all(w.dtype == jnp.dtype(self.dtype) for w in stacked)
            and _pallas_grouped_dot_ok(t * k))
        if in_place:
            out, pairs = self._dropless(
                xt, top_idx, top_w, load, stacked, first, layer)
        elif self.dispatch == "dropless":
            out, pairs = self._dropless(xt, top_idx, top_w, load, kernels, first)
        else:
            out, pairs = self._capacity(xt, top_idx, top_w, onehot, kernels)
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

    def _dropless(self, xt, top_idx, top_w, load, kernels, first: int,
                  layer=None):
        """``layer``: ``kernels`` are a scanned stack's whole leaves and this
        layer's experts are read in place there (all of them held)."""
        t, d = xt.shape
        e, k = self.n_experts, self.top_k
        w_gate, w_up, w_down = kernels
        n_held = e if layer is not None else w_gate.shape[0]
        with jax.named_scope("moe_dispatch"):
            # held experts first, in order: their pairs are the leading rows
            # and every other pair falls behind the last group
            key = (top_idx.reshape(-1) - first) % e                 # (T·k,)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.zeros_like(order).at[order].set(
                jnp.arange(t * k, dtype=jnp.int32), unique_indices=True)
            sizes = jnp.roll(load, -first)[:n_held].astype(jnp.int32)
            rows = _rows_of_tokens(xt.astype(self.dtype), order, inverse, k)
        with jax.named_scope("experts"):
            # a share of the experts leaves rows no group covers: the
            # compiler's product writes zeros there, the Pallas one nothing
            dot = (functools.partial(_grouped_dot, layer=layer) if n_held == e
                   else jax.lax.ragged_dot)
            gate = dot(rows, w_gate, sizes)
            up = dot(rows, w_up, sizes)
            out_rows = dot(nn.silu(gate) * up, w_down, sizes)
            if n_held != e:
                # rows behind the last group belong to experts held elsewhere
                held = jnp.arange(t * k) < sizes.sum()
                out_rows = jnp.where(held[:, None], out_rows, 0)
        with jax.named_scope("moe_combine"):
            pair_out = _unsort(out_rows, order, inverse).reshape(t, k, d)
            out = jnp.einsum("tk,tkd->td", top_w,
                             pair_out.astype(jnp.float32))
        return out, sizes.sum()

    # ---- capacity: static slots per expert, pairs over them dropped -----------

    def _capacity(self, xt, top_idx, top_w, onehot, kernels):
        t, d = xt.shape
        e, k = self.n_experts, self.top_k
        w_gate, w_up, w_down = kernels
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
            slot = jnp.where(valid, top_idx * capacity + pos_idx, n_slots)
            t_ids = jnp.broadcast_to(jnp.arange(t)[:, None], (t, k))
            # empty slots keep sentinel T -> gather the appended zero row, so
            # unfilled capacity computes on zeros exactly as the dense dispatch
            token_of_slot = jnp.full((n_slots,), t, jnp.int32).at[
                slot.reshape(-1)
            ].set(t_ids.reshape(-1), mode="drop")
            xt_pad = jnp.concatenate(
                [xt.astype(compute_dtype), jnp.zeros((1, d), compute_dtype)]
            )
            expert_in = xt_pad[token_of_slot].reshape(e, capacity, d)

        # ---- expert compute (batched over the ep axis) ----------------------
        with jax.named_scope("experts"):
            gate = jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
            up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
            h = nn.silu(gate) * up
            expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)

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


def moe_aux_loss(collections: dict) -> jax.Array:
    """Sum every sown load-balance term (scan stacks them per layer)."""
    leaves = jax.tree_util.tree_leaves(collections.get("moe_aux", {}))
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return sum(jnp.sum(leaf) for leaf in leaves)


#: a counter sown into ``moe_stats`` -> how the layers' readings become the step's
_COUNTERS = {"load_max_over_mean": jnp.max, "pairs": jnp.min,
             "experts_in_place": jnp.sum}


def moe_counters(collections: dict) -> dict:
    """The step's counters from the sown ``moe_stats`` (scan stacks them per
    layer): ``moe_load_max_over_mean`` of the worst layer, ``moe_pairs`` of
    the layer that computed the fewest (``T·k`` where nothing is dropped) and
    ``moe_experts_in_place``, the number of layers whose grouped products read
    their experts in place in the scanned stack.  Empty for a model without
    expert layers."""
    sown: dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            collections.get("moe_stats", {})):
        name = next(n for p in path if (n := str(getattr(p, "key", p))) in _COUNTERS)
        sown.setdefault(name, []).append(jnp.ravel(leaf))
    return {f"moe_{name}": _COUNTERS[name](jnp.concatenate(sown[name]))
            for name in _COUNTERS if name in sown}
