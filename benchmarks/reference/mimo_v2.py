"""Plain float32 reference of the window/full-attention expert model's LoRA
training step, found by name (``"reference": "mimo_v2"``).

Written from the published description (the model's ``config.json`` keys and
its card: "SWA(128) with learnable sink bias; global GQA — 48L, 5 SWA : 1
global; qk 192 / v 128", "256 experts, top-8, 0 shared"), independent of the
program's modules.  ``h = E[token]``; the layers of ``hybrid_layer_pattern``
and ``moe_layer_freq`` number by number, **walked in a Python loop**; every
layer is ``h = h + Attn(RMSNorm(h))`` then ``h = h + FFN(RMSNorm(h))``:

* **attention, both kinds**: ``q = W_q u`` (heads of ``head_dim``), ``k = W_k
  u`` (the KIND's key/value heads), ``v = attention_value_scale * W_v u``
  (heads of ``v_head_dim``); rotary embedding on the FIRST ``int(head_dim x
  partial_rotary_factor)`` columns of every q and k head, half-split pairs
  within them, at the kind's base, **applied to a slice**; scores ``q k *
  head_dim^-0.5`` under an **explicit** ``[S, S]`` **mask** — ``s <= t`` in a
  full layer, ``t - sliding_window < s <= t`` in a window layer; a window
  layer's **sink is one more column** ``b_h`` concatenated to the scores
  before a plain softmax and dropped after it (no online softmax, no
  logsumexp, no block skipped); a block of query heads at a time, as many as
  fit ``reference/model.py``'s ``SCORE_BYTES``, each recomputed on the way back;
* **the dense layer**: ``W_down (silu(W_gate u) * W_up u)``;
* **the expert layer**: float32 router ``s = sigmoid(W_r u)`` over ALL the
  published experts; the ``num_experts_per_tok`` largest of ``s`` (+ the
  selection bias where the file keeps one) are chosen, weights ``s[chosen] /
  (sum + 1e-20)``; **every HELD expert on every row under a mask**
  (``reference/mla_moe.py::routed_experts``: the dense matrix of weights,
  zero where an expert was not chosen — no sort, no grouped product, no row
  bound); no shared expert.

``logits = lm_head(RMSNorm(h))``.  The attention projections and the dense
layer's three may carry a LoRA branch.

**Departures from the published model, all stated**: weights are random from a
seed; the rows are whole documents (no ``segment_ids`` reach a benchmark
cell: the program's window also stops at a document's start); the experts
held elsewhere add nothing (``n_routed_experts`` in ``reduced``: one member's
share, as the program computes it); the three multi-token-prediction layers
are not run; ``attention_chunk_size`` is read by nothing.

Weights are regenerated leaf by leaf from the seed (``harness/weights.py``)
under the program's canonical names, in the type the program stores them
(bf16; the sink float32), and used at their exact float32 value.  **Where a
leaf lives follows the two lists** (``reference/nemotron_h.py::places`` over
one letter a layer, a leading dense layer's in lower case): a run that
repeats is one stack, ``blocks``, whose unit's layers are ``layer_<j>``; a
layer that does not repeat is ``layer_<i>``.  Reverse mode is written out
layer by layer as ``reference/train.py`` does; clip and AdamW are that
file's.  ``q`` is the lower-precision control's hook: both operands of every
matrix product.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, weights
from benchmarks.reference import mla_moe
from benchmarks.reference import train as ref_train
from benchmarks.reference.model import (head_logits, heads_per_block, identity,
                                        rms_norm, rope, top_weights)
from benchmarks.reference.nemotron_h import Place, layer_lora, places


def letters(conf: dict) -> str:
    """One letter a layer from the two lists: ``F`` full, ``W`` window, in
    lower case where the layer keeps the dense MLP."""
    return "".join(
        "FW"[kind] if experts else "fw"[kind]
        for kind, experts in zip(conf["hybrid_layer_pattern"],
                                 conf["moe_layer_freq"]))


class Arch(NamedTuple):
    vocab_size: int
    hidden_size: int
    pattern: str            # :func:`letters`
    n_heads: int
    kv_heads: tuple         # (full, window)
    head_dim: int
    v_dim: int
    rotary: int
    thetas: tuple           # (full, window)
    window: int
    sink: bool              # on the window kind
    value_scale: float
    rms_eps: float
    dense_ff: int
    n_experts: int          # the router's width: the published count
    experts_held: tuple     # (first, count) of those this chip computes
    top_k: int
    expert_ff: int
    routed_scale: float
    select_bias: bool
    base_dtype: str
    lora_rank: int
    lora_alpha: float
    lora_targets: tuple

    @classmethod
    def from_config(cls, conf: dict, experts_held=None) -> "Arch":
        run = conf["run"]
        if (not conf["norm_topk_prob"] or conf["n_group"] != 1
                or conf["topk_group"] != 1 or conf["n_shared_experts"]
                or conf["scoring_func"] != "sigmoid"
                or conf["add_full_attention_sink_bias"]
                or conf["attention_bias"]):
            raise ValueError("this reference computes normalised sigmoid "
                             "top-k weights with no group limit, no shared "
                             "expert, no bias and a sink on the window kind")
        held = conf["n_routed_experts"]
        total = held
        if "n_routed_experts" in conf.get("reduced", []):
            total = conf["published"]["n_routed_experts"]
        return cls(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            pattern=letters(conf), n_heads=conf["num_attention_heads"],
            kv_heads=(conf["num_key_value_heads"],
                      conf["swa_num_key_value_heads"]),
            head_dim=conf["head_dim"], v_dim=conf["v_head_dim"],
            rotary=int(conf["head_dim"] * conf["partial_rotary_factor"]),
            thetas=(float(conf["rope_theta"]), float(conf["swa_rope_theta"])),
            window=conf["sliding_window"],
            sink=bool(conf["add_swa_attention_sink_bias"]),
            value_scale=float(conf["attention_value_scale"]),
            rms_eps=float(conf["layernorm_epsilon"]),
            dense_ff=conf["intermediate_size"],
            n_experts=total, experts_held=tuple(experts_held or (0, held)),
            top_k=conf["num_experts_per_tok"],
            expert_ff=conf["moe_intermediate_size"], routed_scale=1.0,
            select_bias=(conf["topk_method"] == "noaux_tc"
                         and run.get("selection_bias", "seeded") == "seeded"),
            base_dtype=run["frozen_dtype"], lora_rank=int(run["lora_rank"]),
            lora_alpha=float(run["lora_alpha"]),
            lora_targets=tuple(run["lora_targets"]),
        )

    def proj_shapes(self, kind: str) -> dict[str, tuple[int, int]]:
        """The LoRA-carrying projections of a layer of ``kind`` (a letter of
        ``pattern``), ``name -> (in, out)``."""
        d, h, qk, v = self.hidden_size, self.n_heads, self.head_dim, self.v_dim
        kv = self.kv_heads[kind in "wW"]
        out = {"attn/q_proj": (d, h * qk), "attn/k_proj": (d, kv * qk),
               "attn/v_proj": (d, kv * v), "attn/o_proj": (h * v, d)}
        if kind.islower():
            f = self.dense_ff
            out.update({"mlp/gate_proj": (d, f), "mlp/up_proj": (d, f),
                        "mlp/down_proj": (f, d)})
        return out


def layer_weights(arch: Arch, key, place: Place, index) -> dict:
    """One layer's frozen weights, regenerated from the seed: norms, sink and
    projections in float32 (the exact value of what is stored), the stacked
    routed experts in their stored type (up-cast an expert at a time).
    ``index`` (the repeat in its stack) may be traced."""
    base = jnp.dtype(arch.base_dtype)

    def draw(name, shape, dtype=base):
        full = f"{place.prefix}/{name}"
        if not place.repeats:
            return weights.layer_leaf(key, full, 0, shape, dtype)
        if weights.is_stacked(full):
            return weights.layer_leaf(key, full, index, shape, dtype)
        return weights.leaf(key, full, (place.repeats,) + shape, dtype,
                            stacked=False)[index]

    d = arch.hidden_size
    out = {n: draw(f"{n}/scale", (d,)).astype(jnp.float32)
           for n in ("attn_norm", "mlp_norm")}
    for name, shape in arch.proj_shapes(place.kind).items():
        out[name] = draw(f"{name}/kernel", shape).astype(jnp.float32)
    if place.kind in "wW" and arch.sink:
        out["attn/sink"] = draw("attn/sink/bias", (arch.n_heads,), jnp.float32)
    if place.kind.isupper():
        f, e, held = arch.expert_ff, arch.n_experts, arch.experts_held[1]
        out["moe/router"] = draw("moe/router/kernel", (d, e)).astype(jnp.float32)
        if arch.select_bias:
            out["moe/router/bias"] = draw(
                "moe/router/bias", (e,)).astype(jnp.float32)
        # the leaf the program holds: its own experts', drawn at that shape
        # (a share is not a slice of the uncut draw)
        for name, shape in (("gate_proj", (held, d, f)), ("up_proj", (held, d, f)),
                            ("down_proj", (held, f, d))):
            out[f"moe/experts/{name}"] = draw(f"moe/experts/{name}/kernel", shape)
    return out


def init_lora(arch: Arch, key) -> dict:
    """The seeded adapters by canonical name: a stack's with the repeats' axis
    first, a lone layer's one array each."""
    out = {}
    for place in places(arch.pattern):
        for name, (i, o) in arch.proj_shapes(place.kind).items():
            if name.rsplit("/", 1)[-1] not in arch.lora_targets \
                    or not arch.lora_rank:
                continue
            lead = (place.repeats,) if place.repeats else ()
            for leaf, shape in (("lora_a", (i, arch.lora_rank)),
                                ("lora_b", (arch.lora_rank, o))):
                full = f"{place.prefix}/{name}/{leaf}"
                if full not in out:
                    out[full] = weights.leaf(
                        key, full, lead + shape, jnp.float32,
                        stacked=weights.is_stacked(full))
    return out


def rotate_leading(x, positions, theta: float, columns: int):
    """Rotary embedding (rotate-half) on the first ``columns`` of every head
    of ``x`` (B, S, H, D); the other columns as they are."""
    return jnp.concatenate(
        [rope(x[..., :columns], positions, theta), x[..., columns:]], axis=-1)


def attention_weights(scores, mask, sink=None):
    """A row's weights on its keys: softmax of ``scores`` (..., S, S) under
    ``mask`` — with ``sink`` (..., 1, 1) one more column of that logit in the
    softmax, dropped after it, so the weights sum to ``1 - p_sink``."""
    scores = jnp.where(mask, scores, -jnp.inf)
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    column = jnp.broadcast_to(sink, scores.shape[:-1] + (1,))
    return jax.nn.softmax(
        jnp.concatenate([scores, column], axis=-1), axis=-1)[..., :-1]


def attention(arch: Arch, w: dict, proj: Callable, u, window: bool,
              q: Callable = identity):
    """One layer's attention on the normed rows ``u``; ``window``: its kind."""
    bsz, s, _ = u.shape
    nh, nkv = arch.n_heads, arch.kv_heads[window]
    hd, vd, theta = arch.head_dim, arch.v_dim, arch.thetas[window]
    pos = jnp.broadcast_to(jnp.arange(s), (bsz, s))
    qh = rotate_leading(proj("attn/q_proj", u).reshape(bsz, s, nh, hd), pos,
                        theta, arch.rotary)
    kh = rotate_leading(proj("attn/k_proj", u).reshape(bsz, s, nkv, hd), pos,
                        theta, arch.rotary)
    vh = arch.value_scale * proj("attn/v_proj", u).reshape(bsz, s, nkv, vd)
    # query head h reads key/value head h // (heads / key-value heads)
    kh = jnp.repeat(kh, nh // nkv, axis=2)
    vh = jnp.repeat(vh, nh // nkv, axis=2)
    t = jnp.arange(s)
    mask = t[:, None] >= t[None, :]
    if window:
        mask = mask & (t[:, None] - t[None, :] < arch.window)
    sink = w.get("attn/sink")
    hb = heads_per_block(bsz, nh, s)

    @jax.checkpoint
    def block(operands):
        """A block of query heads; its scores are recomputed on the way back,
        so no ``(B, H, S, S)`` array ever exists."""
        qb, kb, vb, sb = operands
        scores = jnp.einsum("bqhd,bkhd->bhqk", q(qb), q(kb)) * hd ** -0.5
        probs = attention_weights(
            scores, mask, None if sink is None else sb[None, :, None, None])
        return jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(vb))

    def split(a):
        return jnp.moveaxis(a.reshape(bsz, s, nh // hb, hb, a.shape[-1]), 2, 0)

    sinks = (jnp.zeros((nh,)) if sink is None else sink).reshape(nh // hb, hb)
    ctx = jax.lax.map(block, (split(qh), split(kh), split(vh), sinks))
    return proj("attn/o_proj", jnp.moveaxis(ctx, 0, 2).reshape(bsz, s, nh * vd))


def layer_forward(arch: Arch, kind: str, w: dict, lora_l: dict, x,
                  q: Callable = identity):
    """One layer of ``kind`` (a letter of :func:`letters`).  ``lora_l``: this
    layer's adapters by their name inside the layer (``attn/q_proj/lora_a``
    ...), absent = no branch."""
    scale = arch.lora_alpha / arch.lora_rank if arch.lora_rank else 0.0

    def proj(name, h):
        y = jnp.matmul(q(h), q(w[name]))
        a = lora_l.get(f"{name}/lora_a")
        if a is not None:
            b = lora_l[f"{name}/lora_b"]
            y = y + jnp.matmul(q(jnp.matmul(q(h), q(a))), q(b)) * scale
        return y

    x = x + attention(arch, w, proj, rms_norm(x, w["attn_norm"], arch.rms_eps),
                      kind in "wW", q)
    u = rms_norm(x, w["mlp_norm"], arch.rms_eps)
    if kind.islower():
        return x + proj("mlp/down_proj", jax.nn.silu(proj("mlp/gate_proj", u))
                        * proj("mlp/up_proj", u))
    bsz, s, d = u.shape
    return x + mla_moe.routed_experts(
        arch, w, u.reshape(bsz * s, d), q).reshape(bsz, s, d)


def make_loss_and_grads(arch: Arch, q: Callable = identity, precision="highest",
                        rows_per_block: int = 1):
    """``fn(key, lora, tokens) -> (loss, grads)``; tokens (B, S) int32, all
    positions count (targets are tokens shifted by one); the loss is one mean
    over the global batch, walked in blocks of rows."""
    layers = places(arch.pattern)

    def _fwd(key, lora_l, place, index, x):
        return layer_forward(arch, place.kind,
                             layer_weights(arch, key, place, index), lora_l, x, q)

    @jax.jit
    def embed(key, tokens):
        return top_weights(arch, key)["embedding"][tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames="place")
    def layer_fwd(key, lora, index, x, *, place):
        with jax.default_matmul_precision(precision):
            return _fwd(key, layer_lora(lora, place, index), place, index, x)

    @functools.partial(jax.jit, static_argnames="place")
    def layer_bwd(key, lora, index, x, dy, *, place):
        with jax.default_matmul_precision(precision):
            _, vjp = jax.vjp(lambda ll, xx: _fwd(key, ll, place, index, xx),
                             layer_lora(lora, place, index), x)
            dl, dx = vjp(dy)
            return dx, dl

    @jax.jit
    def head(key, x, tokens):
        def nll_sum(xx):
            with jax.default_matmul_precision(precision):
                logits = head_logits(arch, top_weights(arch, key), xx[:, :-1], q)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).sum()

        return jax.value_and_grad(nll_sum)(x)

    @functools.partial(jax.jit, static_argnames="place")
    def accumulate(grads, dl, index, *, place):
        out = dict(grads)
        for name, g in dl.items():
            full = f"{place.prefix}/{name}"
            out[full] = (grads[full].at[index].add(g) if place.repeats
                         else grads[full] + g)
        return out

    def fn(key, lora, tokens):
        tokens = np.asarray(tokens, np.int32)
        grads = jax.tree.map(jnp.zeros_like, lora)
        total = 0.0
        for r0 in range(0, tokens.shape[0], rows_per_block):
            tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
            x, saved = embed(key, tok), []
            for place in layers:
                saved.append(x)
                x = layer_fwd(key, lora, jnp.asarray(place.index, jnp.int32), x,
                              place=place._replace(index=0))
            nll, dx = head(key, x, tok)
            total += float(nll)
            for place in reversed(layers):
                index = jnp.asarray(place.index, jnp.int32)
                static = place._replace(index=0)
                dx, dl = layer_bwd(key, lora, index, saved.pop(), dx, place=static)
                grads = accumulate(grads, dl, index, place=static)
        inv = 1.0 / (tokens.shape[0] * (tokens.shape[1] - 1))
        return total * inv, jax.tree.map(lambda g: g * inv, grads)

    return fn


def reference_numbers(conf, wl, seed, token_batches, *, q=identity,
                      precision="highest", steps=None, experts_held=None,
                      devices=None):
    """Follow the first steps with the plain reference: per-step loss, the
    first clipped gradient's norms, the adapters' change (``compare.
    layer_norms`` under the program's canonical names).  One device: a cell
    of this configuration holds one chip (``devices`` is not used)."""
    arch = Arch.from_config(conf, experts_held)
    key = weights.root_key(seed)
    lora0 = init_lora(arch, key)
    fn = make_loss_and_grads(arch, q, precision,
                             rows_per_block=wl.get("reference_rows", 1))
    opt = ref_train.AdamW(wl["lr"], weight_decay=0.0, clip_norm=wl["clip_norm"])
    lora, losses, g1 = lora0, [], None
    for k in range(steps or wl["reference_steps"]):
        loss, grads = fn(key, lora, token_batches[k])
        losses.append(float(loss))
        lora, clipped = opt.update(lora, grads)
        if k == 0:
            g1 = compare.layer_norms(compare.host(clipped))
    delta = jax.tree.map(lambda a, b: a - b, lora, lora0)
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": compare.layer_norms(compare.host(delta))}
