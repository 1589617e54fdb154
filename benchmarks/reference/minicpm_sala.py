"""Plain float32 reference of the sparse / lightning hybrid's LoRA training
step, found by name (``"reference": "minicpm_sala"``).

Written from the configuration file's published keys, its ``assumed`` lines,
the InfLLM-v2 selection as the MiniCPM4 report describes it (arXiv:2506.07900)
and Lightning Attention-2's recurrence, independent of the program's modules:
no kernel, no chunk algebra, no packed selection.  ``h = E[token] * scale_emb``;
a layer of either kind is ``h += m * Mixer(RMSNorm h)``, ``h += m *
SwiGLU(RMSNorm h)`` with ``m = scale_depth / sqrt(published layers)``:

* ``minicpm4`` (:func:`sparse_attention`): ``q`` (heads of ``head_dim``), ``k``,
  ``v`` (``num_key_value_heads``), no bias, a learned RMSNorm over each q and k
  head, NO rotary embedding, softmax scale ``head_dim^-0.5``.  In a row longer
  than ``dense_len`` each key/value head's group of query heads keeps ``topk``
  blocks of ``block_size`` keys a query (:func:`selected_blocks`): compressed
  keys are the means of ``kernel_size`` keys every ``kernel_stride`` (whole
  windows only); a head's softmax over the compressed keys whose window ends
  at or before the query, summed over the group; a block scores the largest
  sum over the compressed keys whose window overlaps it; block 0 and the
  ``window_size / block_size`` blocks that end with the query's own are
  forced; the rest by a FULL stable sort of the block scores a query, ties to
  the lower block.  Attention is ONE query head at a time under the explicit
  mask, query rows in blocks whose scores fit ``SCORE_BYTES``.  Then ``o *
  sigmoid(W_g x)``, ``W_o``;
* ``lightning-attn`` (:func:`lightning`): ``q, k, v`` (``lightning_nh`` heads of
  ``lightning_head_dim`` each), the same per-head norms, rotate-half rotary
  embedding at ``rope_theta`` on all columns of q and k, then **the recurrence
  itself, token by token under** ``lax.scan`` (``falcon_h1.recurrence``: a
  float32 state a head, replayed in blocks of rows on the way back): ``S_t =
  lambda_h S_{t-1} + k_t^T v_t``, ``o_t = head_dim^-0.5 q_t S_t``, ``lambda_h
  = exp(-2^(-8 h / H))``; RMSNorm over all of ``o``; ``* sigmoid(W_g x)``;
  ``W_o``.

The SwiGLU half is walked in blocks of rows too (it is row by row anyway), so
that one 32,768-token row's float32 transients fit beside the layer's.

``logits = lm_head(RMSNorm(h) * dim_model_base / hidden_size)``.  Every
projection may carry a LoRA branch.  Departures from the published model, all
stated in the file's ``assumed``: weights are random from a seed; the rows are
whole documents.  Weights are regenerated leaf by leaf from the seed
(``harness/weights.py``) under the program's canonical names in the type the
program stores them (bf16) and used at their exact float32 value.  Reverse
mode is written out layer by layer as ``reference/train.py`` does; clip and
AdamW are that file's.  ``q`` is the lower-precision control's hook: both
operands of every matrix product, the selection's scores, attention's and the
recurrence's (``v``, ``k``, ``q``) among them.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, weights
from benchmarks.reference import train as ref_train
from benchmarks.reference.falcon_h1 import recurrence
from benchmarks.reference.model import (head_logits, identity, rms_norm, rope,
                                        top_weights)
from benchmarks.reference.nemotron_h import (init_lora, layer_lora, layer_weights,  # noqa: F401
                                             places)

#: a published mixer type -> the program's letter for such a layer
MIXERS = {"minicpm4": "S", "lightning-attn": "L"}
#: bytes of float32 scores (one query head, a block of query rows, every key)
#: the reference holds at once
SCORE_BYTES = 1 << 29


class Arch(NamedTuple):
    vocab_size: int
    hidden_size: int
    pattern: str
    n_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    rms_eps: float
    lightning_heads: int
    lightning_head_dim: int
    scale_emb: float
    residual_scale: float
    head_in_scale: float
    topk: int
    block: int
    window: int
    init_blocks: int
    dense_len: int
    kernel: int
    stride: int
    base_dtype: str
    lora_rank: int
    lora_alpha: float
    lora_targets: tuple

    @classmethod
    def from_config(cls, conf: dict) -> "Arch":
        run, sparse = conf["run"], conf["sparse_config"]
        if conf["lightning_nkv"] != conf["lightning_nh"]:
            raise ValueError("this reference gives every lightning head its own keys")
        if conf["attn_use_rope"] or not conf["lightning_use_rope"]:
            raise ValueError("this reference rotates the lightning layers' q "
                             "and k and not the sparse layers'")
        if not (conf["qk_norm"] and conf["use_output_gate"]
                and conf["use_output_norm"] and conf["attn_use_output_gate"]):
            raise ValueError("this reference norms q and k, gates both kinds' "
                             "output and norms the lightning layers'")
        depth = conf.get("published", {}).get(
            "num_hidden_layers", conf["num_hidden_layers"])
        return cls(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            pattern="".join(MIXERS[m] for m in conf["mixer_types"]),
            n_heads=conf["num_attention_heads"],
            n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            intermediate_size=conf["intermediate_size"],
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
            lightning_heads=conf["lightning_nh"],
            lightning_head_dim=conf["lightning_head_dim"],
            scale_emb=float(conf["scale_emb"]),
            residual_scale=float(conf["scale_depth"]) / float(depth) ** 0.5,
            head_in_scale=conf["dim_model_base"] / conf["hidden_size"],
            topk=sparse["topk"], block=sparse["block_size"],
            window=sparse["window_size"], init_blocks=sparse["init_blocks"],
            dense_len=sparse["dense_len"], kernel=sparse["kernel_size"],
            stride=sparse["kernel_stride"],
            base_dtype=run["frozen_dtype"], lora_rank=int(run["lora_rank"]),
            lora_alpha=float(run["lora_alpha"]),
            lora_targets=tuple(run["lora_targets"]),
        )

    def proj_shapes(self, kind: str) -> dict[str, tuple[int, int]]:
        """The LoRA-carrying projections of a layer of ``kind`` (with
        ``other_shapes``, ``pattern`` and the adapters' sizes: what
        ``nemotron_h.layer_weights`` and ``init_lora`` read of an ``Arch``)."""
        d, f = self.hidden_size, self.intermediate_size
        if kind == "S":
            q, kv, name = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim, "sparse_attn"
        else:
            q = kv = self.lightning_heads * self.lightning_head_dim
            name = "lightning"
        return {
            f"{name}/q_proj": (d, q), f"{name}/k_proj": (d, kv),
            f"{name}/v_proj": (d, kv), f"{name}/o_gate": (d, q),
            f"{name}/o_proj": (q, d),
            "mlp/gate_proj": (d, f), "mlp/up_proj": (d, f), "mlp/down_proj": (f, d),
        }

    def other_shapes(self, kind: str) -> dict[str, tuple]:
        d = self.hidden_size
        out = {"attn_norm/scale": (d,), "mlp_norm/scale": (d,)}
        if kind == "S":
            out.update({"sparse_attn/q_norm/scale": (self.head_dim,),
                        "sparse_attn/k_norm/scale": (self.head_dim,)})
        else:
            p = self.lightning_head_dim
            out.update({"lightning/q_norm/scale": (p,),
                        "lightning/k_norm/scale": (p,),
                        "lightning/o_norm/scale": (self.lightning_heads * p,)})
        return out


def _rows_per_block(seq: int, per_row: int) -> int:
    """Query rows whose float32 intermediates (``per_row`` numbers a row) fit
    ``SCORE_BYTES``: the largest divisor of ``seq`` that does."""
    fit = max(1, SCORE_BYTES // (4 * per_row))
    return max(r for r in range(1, seq + 1) if seq % r == 0 and r <= fit)


def selected_blocks(arch: Arch, qh, kh, q: Callable = identity):
    """``(B, Hkv, S, blocks)`` bool: the blocks of keys each query of each
    key/value head's group attends.  ``qh: (B, S, H, D)``, ``kh: (B, S, Hkv,
    D)``.  Query rows a block at a time; a full stable sort a query."""
    qh, kh = jax.lax.stop_gradient((qh, kh))
    b, s, h, d = qh.shape
    g = kh.shape[2]
    n_blocks = -(-s // arch.block)
    n_c = max((s - arch.kernel) // arch.stride + 1, 0)
    starts = np.arange(n_c) * arch.stride
    # the mean of each whole window of keys, offset by offset: (B, n_c, Hkv, D)
    compressed = jnp.mean(jnp.stack(
        [kh[:, i:i + (n_c - 1) * arch.stride + 1:arch.stride]
         for i in range(arch.kernel)]), axis=0
    ) if n_c else jnp.zeros((b, 0, g, d), jnp.float32)
    ends = jnp.asarray(starts + arch.kernel - 1)
    # the compressed keys whose window overlaps each block, padded to the
    # most any block has: (blocks, m) indices and which of them are real
    first_key = np.arange(n_blocks)[:, None] * arch.block
    overlaps = ((starts[None, :] < first_key + arch.block)
                & (starts[None, :] + arch.kernel > first_key))
    most = max(int(overlaps.sum(1).max()), 1) if n_c else 1
    idx = np.zeros((n_blocks, most), np.int32)
    real = np.zeros((n_blocks, most), bool)
    for blk in range(n_blocks):
        mine = np.flatnonzero(overlaps[blk]) if n_c else np.zeros((0,), np.int64)
        idx[blk, :len(mine)], real[blk, :len(mine)] = mine, True
    idx, real = jnp.asarray(idx), jnp.asarray(real)
    rows = _rows_per_block(s, 2 * b * h * max(n_c, 1))
    of = jnp.arange(n_blocks)

    def block_of_rows(first):
        t = first * rows + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(qh, first * rows, rows, axis=1)
        qb = qb.reshape(b, rows, g, h // g, d)
        scores = jnp.einsum("btgpd,bjgd->bgptj", q(qb), q(compressed)) * d ** -0.5
        seen = (ends[None, :] <= t[:, None])                    # (rows, n_c)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        probs = jnp.where(seen, probs, 0.0)     # a row that sees none: zeros
        summed = probs.sum(axis=2)                              # (B, Hkv, rows, n_c)
        if n_c:
            block_scores = jnp.max(jnp.where(
                real & seen[:, idx], summed[..., idx], 0.0), axis=-1)
        else:
            block_scores = jnp.zeros((b, g, rows, n_blocks), jnp.float32)
        own = t // arch.block
        causal = of[None, :] <= own[:, None]
        forced = causal & ((of[None, :] < arch.init_blocks)
                           | (of[None, :] > own[:, None] - arch.window // arch.block))
        ranked = jnp.where(forced, jnp.inf, block_scores)
        ranked = jnp.where(causal, ranked, -jnp.inf)
        # a full sort, best first, equal scores in the blocks' order
        order = jnp.argsort(-ranked, axis=-1, stable=True)
        place = jnp.argsort(order, axis=-1, stable=True)
        wanted = jnp.minimum(causal.sum(-1), arch.topk)
        return causal & (place < wanted[:, None])

    picked = jax.lax.map(block_of_rows, jnp.arange(s // rows))  # (n, B, Hkv, rows, blocks)
    return jnp.moveaxis(picked, 0, 2).reshape(b, g, s, n_blocks)


def masked_attention(qh, kh, vh, picked, block: int, q: Callable = identity):
    """Causal attention of ``qh: (B, S, H, D)`` over ``kh``, ``vh: (B, S, Hkv,
    D)``, one query head at a time, its rows in blocks: each query over the
    keys at or before it — of its group's ``picked`` blocks ``(B, Hkv, S,
    blocks)`` where given."""
    b, s, h, d = qh.shape
    g = kh.shape[2]
    rows = _rows_per_block(s, b * s)
    n = s // rows
    at = jnp.arange(s)

    @jax.checkpoint
    def one(operands):
        head, first = operands
        group = head // (h // g)
        qb = jax.lax.dynamic_slice_in_dim(qh[:, :, head], first * rows, rows, axis=1)
        kg, vg = kh[:, :, group], vh[:, :, group]               # (B, S, D)
        scores = jnp.einsum("btd,bsd->bts", q(qb), q(kg)) * d ** -0.5
        t = first * rows + jnp.arange(rows)
        mask = (at[None, :] <= t[:, None])[None]
        if picked is not None:
            mine = jax.lax.dynamic_slice_in_dim(
                picked[:, group], first * rows, rows, axis=1)   # (B, rows, blocks)
            mask = mask & jnp.repeat(mine, block, axis=-1)[..., :s]
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bts,bsd->btd", q(probs), q(vg))

    heads, firsts = jnp.meshgrid(jnp.arange(h), jnp.arange(n), indexing="ij")
    ctx = jax.lax.map(one, (heads.reshape(-1), firsts.reshape(-1)))
    # (H * n, B, rows, D) -> (B, S, H * D)
    ctx = ctx.reshape(h, n, b, rows, d).transpose(2, 1, 3, 0, 4)
    return ctx.reshape(b, s, h * d)


def sparse_attention(arch: Arch, w: dict, proj: Callable, u, q: Callable = identity):
    bsz, s, _ = u.shape
    hd = arch.head_dim
    qh = rms_norm(proj("sparse_attn/q_proj", u).reshape(bsz, s, arch.n_heads, hd),
                    w["sparse_attn/q_norm/scale"], arch.rms_eps)
    kh = rms_norm(proj("sparse_attn/k_proj", u).reshape(bsz, s, arch.n_kv_heads, hd),
                    w["sparse_attn/k_norm/scale"], arch.rms_eps)
    vh = proj("sparse_attn/v_proj", u).reshape(bsz, s, arch.n_kv_heads, hd)
    picked = selected_blocks(arch, qh, kh, q) if s > arch.dense_len else None
    ctx = masked_attention(qh, kh, vh, picked, arch.block, q)
    return proj("sparse_attn/o_proj",
                ctx * jax.nn.sigmoid(proj("sparse_attn/o_gate", u)))


def lightning(arch: Arch, w: dict, proj: Callable, u, positions,
              q: Callable = identity):
    bsz, s, _ = u.shape
    h, p = arch.lightning_heads, arch.lightning_head_dim

    def heads(name, norm=None):
        x = proj(f"lightning/{name}", u).reshape(bsz, s, h, p)
        if norm is None:
            return x
        return rope(rms_norm(x, w[f"lightning/{norm}/scale"], arch.rms_eps),
                    positions, arch.rope_theta)

    qh, kh, vh = heads("q_proj", "q_norm"), heads("k_proj", "k_norm"), heads("v_proj")
    log_decay = -jnp.exp2(-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    # every head a group of one: fed (B, S, H, 1, P), keys and queries (B, S, H, N)
    y = recurrence(vh[:, :, :, None, :],
                   jnp.broadcast_to(log_decay[:, None], (bsz, s, h, 1)),
                   kh, qh, q)
    y = (y * p ** -0.5).reshape(bsz, s, h * p)
    y = rms_norm(y, w["lightning/o_norm/scale"], arch.rms_eps)
    return proj("lightning/o_proj", y * jax.nn.sigmoid(proj("lightning/o_gate", u)))


def layer_forward(arch: Arch, kind: str, w: dict, lora_l: dict, x, positions,
                  q: Callable = identity):
    """One block of ``kind``.  ``lora_l``: this layer's adapters by their name
    inside the layer (``lightning/q_proj/lora_a`` ...), absent = no branch."""
    scale = arch.lora_alpha / arch.lora_rank if arch.lora_rank else 0.0

    def proj(name, h):
        y = jnp.matmul(q(h), q(w[name]))
        a = lora_l.get(f"{name}/lora_a")
        if a is not None:
            b = lora_l[f"{name}/lora_b"]
            y = y + jnp.matmul(q(jnp.matmul(q(h), q(a))), q(b)) * scale
        return y

    u = rms_norm(x, w["attn_norm/scale"], arch.rms_eps)
    mixed = (sparse_attention(arch, w, proj, u, q) if kind == "S"
             else lightning(arch, w, proj, u, positions, q))
    x = x + mixed * arch.residual_scale
    r = rms_norm(x, w["mlp_norm/scale"], arch.rms_eps)

    @jax.checkpoint
    def mlp(rows):
        """SwiGLU of a block of rows, replayed on the way back: a 32,768-token
        row's 16,384-wide float32 transients (2.1 GB each, six of them under
        the reverse pass) never exist whole."""
        act = jax.nn.silu(proj("mlp/gate_proj", rows)) * proj("mlp/up_proj", rows)
        return proj("mlp/down_proj", act)

    bsz, s, d = r.shape
    rows = _rows_per_block(s, 6 * bsz * arch.intermediate_size)
    out = jax.lax.map(mlp, jnp.moveaxis(r.reshape(bsz, s // rows, rows, d), 1, 0))
    return x + jnp.moveaxis(out, 0, 1).reshape(bsz, s, d) * arch.residual_scale


def make_loss_and_grads(arch: Arch, q: Callable = identity, precision="highest",
                        rows_per_block: int = 1):
    """``fn(key, lora, tokens) -> (loss, grads)``; tokens (B, S) int32, all
    positions count (targets are tokens shifted by one); the loss is one mean
    over the global batch, walked in blocks of rows.  ``fn.logits(key, lora,
    tokens)`` is the forward pass alone (the tests')."""
    layers = places(arch.pattern)

    def _fwd(key, lora_l, place, index, x):
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return layer_forward(arch, place.kind,
                             layer_weights(arch, key, place, index), lora_l, x,
                             pos, q)

    @jax.jit
    def embed(key, tokens):
        return (top_weights(arch, key)["embedding"][tokens].astype(jnp.float32)
                * arch.scale_emb)

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

    def _logits(key, xx):
        top = top_weights(arch, key)
        # the normed state times dim_model_base / hidden_size, then the head
        top = dict(top, final_norm=top["final_norm"] * arch.head_in_scale)
        return head_logits(arch, top, xx, q)

    @jax.jit
    def head(key, x, tokens):
        def nll_sum(xx):
            with jax.default_matmul_precision(precision):
                logits = _logits(key, xx[:, :-1])
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

    def forward(key, lora, tok, saved=None):
        x = embed(key, tok)
        for place in layers:
            if saved is not None:
                saved.append(x)
            x = layer_fwd(key, lora, jnp.asarray(place.index, jnp.int32), x,
                          place=place._replace(index=0))
        return x

    def fn(key, lora, tokens):
        tokens = np.asarray(tokens, np.int32)
        grads = jax.tree.map(jnp.zeros_like, lora)
        total = 0.0
        for r0 in range(0, tokens.shape[0], rows_per_block):
            tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
            saved: list = []
            x = forward(key, lora, tok, saved)
            nll, dx = head(key, x, tok)
            total += float(nll)
            for place in reversed(layers):
                index = jnp.asarray(place.index, jnp.int32)
                static = place._replace(index=0)
                dx, dl = layer_bwd(key, lora, index, saved.pop(), dx, place=static)
                grads = accumulate(grads, dl, index, place=static)
        inv = 1.0 / (tokens.shape[0] * (tokens.shape[1] - 1))
        return total * inv, jax.tree.map(lambda g: g * inv, grads)

    def logits(key, lora, tokens):
        with jax.default_matmul_precision(precision):
            return _logits(key, forward(key, lora, jnp.asarray(tokens, jnp.int32)))

    fn.logits = logits
    return fn


def reference_numbers(conf, wl, seed, token_batches, *, q=identity,
                      precision="highest", steps=None, devices=None):
    """Follow the first steps with the plain reference: per-step loss, the
    first clipped gradient's norms, the adapters' change (``compare.
    layer_norms`` under the program's canonical names).  One device: a cell
    of this configuration holds one chip (``devices`` is not used)."""
    arch = Arch.from_config(conf)
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
