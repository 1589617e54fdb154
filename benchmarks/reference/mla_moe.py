"""Plain float32 reference of the latent-attention expert model's LoRA
training step, found by name (``"reference": "mla_moe"``).

Written from the published description (the model's ``config.json`` keys and
the DeepSeek-V2/V3 papers its family follows), independent of the program's
modules.  Per layer, ``x: [B, S, d]``:

* **latent attention** (un-absorbed): ``c_q = RMSNorm(x W_qa)``; ``q = c_q
  W_qb`` -> heads of ``[q_nope | q_rope]``; ``[c_kv | k_rope] = x W_kva``;
  ``c_kv = RMSNorm(c_kv)``; heads of ``[k_nope | v] = c_kv W_kvb``; rotary
  embedding on adjacent pairs (``rope_interleave``), theta as published, no
  scaling, on ``q_rope`` and on the ONE ``k_rope`` head all heads share;
  scores ``(q_nope k_nope^T + q_rope k_rope^T) (nope + rope)^-0.5``, causal
  softmax, times ``v``; ``o = concat W_o``;
* **expert layer**: ``s = sigmoid(x W_r)``; the top-k of ``s + b`` (``b`` the
  frozen selection bias; zero, and no leaf, where the configuration's ``run``
  says ``"selection_bias": "zero"``) are chosen; weights are the UNbiased ``s`` there,
  over their sum (+1e-20), times ``routed_scaling_factor``; ``y = sum_k w_k
  down_k(silu(gate_k x) * up_k x)`` plus the shared expert.  No sorting: every
  expert held runs on every row of a block under the dense weight matrix
  (zero where an expert was not chosen).  Nothing is dropped;
* the first ``first_k_dense_replace`` layers keep a dense SwiGLU MLP.

Every projection outside the routed experts may carry a LoRA branch.  Weights
are regenerated leaf by leaf from the seed (``harness/weights.py``) under the
program's canonical names, in the type the program stores them (bf16), and
used at their exact float32 value.  Reverse mode is written out layer by
layer (``jax.vjp`` of one layer at a time) over blocks of rows, as
``reference/train.py`` does; clip and AdamW are that file's.  ``q`` is the
lower-precision control's hook (applied to both operands of every matrix
product), ``experts_held = (first, count)`` the share of the experts whose
part of the result is computed.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, weights
from benchmarks.reference import train as ref_train
from benchmarks.reference.model import (head_logits, heads_per_block, identity,
                                        rms_norm, top_weights)

DENSE_PREFIX = "layer_{}"       # a leading dense layer's leaves, one array each


class Arch(NamedTuple):
    vocab_size: int
    hidden_size: int
    n_layers: int
    n_dense: int
    n_heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    dense_ff: int
    expert_ff: int
    n_experts: int
    top_k: int
    n_shared: int
    routed_scale: float
    select_bias: bool
    rope_theta: float
    rms_eps: float
    base_dtype: str
    lora_rank: int
    lora_alpha: float
    lora_targets: tuple
    experts_held: tuple

    @classmethod
    def from_config(cls, conf: dict, experts_held=None) -> "Arch":
        run = conf["run"]
        if not conf["norm_topk_prob"]:
            raise ValueError("this reference normalises the top-k weights")
        return cls(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            n_layers=conf["num_hidden_layers"],
            n_dense=conf["first_k_dense_replace"],
            n_heads=conf["num_attention_heads"],
            q_rank=conf["q_lora_rank"], kv_rank=conf["kv_lora_rank"],
            nope=conf["qk_nope_head_dim"], rope=conf["qk_rope_head_dim"],
            v_dim=conf["v_head_dim"], dense_ff=conf["intermediate_size"],
            expert_ff=conf["moe_intermediate_size"],
            n_experts=conf["n_routed_experts"],
            top_k=conf["num_experts_per_tok"],
            n_shared=conf["n_shared_experts"],
            routed_scale=float(conf["routed_scaling_factor"]),
            # a run may hold the selection bias at zero: no such leaf then
            select_bias=(conf["topk_method"] == "noaux_tc"
                         and run.get("selection_bias", "seeded") == "seeded"),
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
            base_dtype=run["frozen_dtype"], lora_rank=int(run["lora_rank"]),
            lora_alpha=float(run["lora_alpha"]),
            lora_targets=tuple(run["lora_targets"]),
            experts_held=tuple(experts_held or (0, conf["n_routed_experts"])),
        )

    def proj_shapes(self, dense: bool) -> dict[str, tuple[int, int]]:
        """The LoRA-carrying projections of a layer, ``name -> (in, out)``."""
        d, h = self.hidden_size, self.n_heads
        out = {
            "attn/q_a_proj": (d, self.q_rank),
            "attn/q_b_proj": (self.q_rank, h * (self.nope + self.rope)),
            "attn/kv_a_proj_with_mqa": (d, self.kv_rank + self.rope),
            "attn/kv_b_proj": (self.kv_rank, h * (self.nope + self.v_dim)),
            "attn/o_proj": (h * self.v_dim, d),
        }
        group, f = (("mlp", self.dense_ff) if dense
                    else ("moe/shared", self.n_shared * self.expert_ff))
        if dense or self.n_shared:
            out.update({f"{group}/gate_proj": (d, f), f"{group}/up_proj": (d, f),
                        f"{group}/down_proj": (f, d)})
        return out

    def norm_shapes(self) -> dict[str, int]:
        return {"attn_norm": self.hidden_size, "mlp_norm": self.hidden_size,
                "attn/q_a_norm": self.q_rank, "attn/kv_a_norm": self.kv_rank}


def _place(arch: Arch, layer: int) -> tuple[str, int, bool]:
    """``(name prefix, index in its stack, dense?)`` of model layer ``layer``."""
    if layer < arch.n_dense:
        return DENSE_PREFIX.format(layer), 0, True
    return weights.STACKED, layer - arch.n_dense, False


def layer_weights(arch: Arch, key, prefix: str, index, dense: bool) -> dict:
    """One layer's frozen weights, regenerated from the seed: norms and
    projections in float32 (the exact value of what is stored), the stacked
    routed experts in their stored type (up-cast an expert at a time)."""
    base = jnp.dtype(arch.base_dtype)

    def draw(name, shape):
        return weights.layer_leaf(key, f"{prefix}/{name}", index, shape, base)

    out = {n: draw(f"{n}/scale", (w,)).astype(jnp.float32)
           for n, w in arch.norm_shapes().items()}
    for name, shape in arch.proj_shapes(dense).items():
        out[name] = draw(f"{name}/kernel", shape).astype(jnp.float32)
    if not dense:
        d, f, e = arch.hidden_size, arch.expert_ff, arch.n_experts
        out["moe/router"] = draw("moe/router/kernel", (d, e)).astype(jnp.float32)
        if arch.select_bias:
            out["moe/router/bias"] = draw("moe/router/bias", (e,)).astype(jnp.float32)
        # the leaf the program holds: its own experts', drawn at that shape
        # (so a share of the experts is not a slice of the uncut draw)
        held = arch.experts_held[1]
        for name, shape in (("gate_proj", (held, d, f)), ("up_proj", (held, d, f)),
                            ("down_proj", (held, f, d))):
            out[f"moe/experts/{name}"] = draw(f"moe/experts/{name}/kernel", shape)
    return out


def init_lora(arch: Arch, key) -> dict:
    """The seeded adapters by canonical name: the expert layers' stacked over
    their stack (``blocks/...``: ``(L, ...)``), a leading dense layer's one
    array each (``layer_0/...``)."""
    out = {}
    rank = arch.lora_rank
    n_stack = arch.n_layers - arch.n_dense
    for prefix, dense, lead in (
            [(DENSE_PREFIX.format(l), True, ()) for l in range(arch.n_dense)]
            + [(weights.STACKED, False, (n_stack,))] * bool(n_stack)):
        for name, (i, o) in arch.proj_shapes(dense).items():
            if name.rsplit("/", 1)[-1] not in arch.lora_targets or not rank:
                continue
            for leaf, shape in (("lora_a", (i, rank)), ("lora_b", (rank, o))):
                full = f"{prefix}/{name}/{leaf}"
                out[full] = weights.leaf(key, full, lead + shape, jnp.float32,
                                         stacked=bool(lead))
    return out


def layer_lora(lora: dict, prefix: str, index) -> dict:
    """One layer's adapters by their name inside the layer."""
    cut = len(prefix) + 1
    return {n[cut:]: (v[index] if weights.is_stacked(n) else v)
            for n, v in lora.items() if n.startswith(prefix + "/")}


def rope_pairs(x, positions, theta):
    """x: (B, S, H, D); adjacent pairs ``(x[2i], x[2i+1])`` rotated by
    ``position * theta^(-2i/D)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * inv        # (B, S, half)
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def route(arch: Arch, w: dict, h, q: Callable = identity):
    """``(chosen experts (T, k), their weights (T, k))`` of rows ``h``."""
    scores = jax.nn.sigmoid(jnp.matmul(q(h), q(w["moe/router"])))
    select = scores + w["moe/router/bias"] if arch.select_bias else scores
    _, chosen = jax.lax.top_k(select, arch.top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20)
    return chosen, weight * arch.routed_scale


def flipped_pair_share(arch: Arch, w: dict, h):
    """A reading, not part of the result: the share of the chosen (token,
    expert) pairs that change when the rows ``h`` are rounded to bfloat16 —
    how often a near-tie of the top-k falls the other way between a bf16
    program and this float32 reference."""
    exact, _ = route(arch, w, h)
    rounded, _ = route(arch, w, h.astype(jnp.bfloat16).astype(jnp.float32))
    both = (jax.nn.one_hot(exact, arch.n_experts).sum(1)
            * jax.nn.one_hot(rounded, arch.n_experts).sum(1)).sum()
    return 1.0 - both / exact.size


def routed_experts(arch: Arch, w: dict, h, q: Callable = identity):
    """The held experts' part of the expert layer for rows ``h: (T, d)``:
    every held expert on every row, weighted by the dense weight matrix.
    Two nested loops over the experts, each body recomputed on the way back,
    so neither a (T, E, d) array nor E copies of the sum are ever held."""
    chosen, weight = route(arch, w, h, q)
    first, count = arch.experts_held
    dense_w = (jax.nn.one_hot(chosen, arch.n_experts, dtype=jnp.float32)
               * weight[..., None]).sum(1)[:, first:first + count]   # (T, held)
    inner = max(c for c in range(1, 17) if count % c == 0)
    kernels = tuple(w[f"moe/experts/{n}"] for n in ("gate_proj", "up_proj",
                                                     "down_proj"))

    def chunks(a):
        return a.reshape((count // inner, inner) + a.shape[1:])

    @jax.checkpoint
    def one(acc, xs):
        gate, up, down, col = xs
        act = jax.nn.silu(jnp.matmul(q(h), q(gate.astype(jnp.float32)))) \
            * jnp.matmul(q(h), q(up.astype(jnp.float32)))
        return acc + col[:, None] * jnp.matmul(q(act), q(down.astype(jnp.float32))), None

    @jax.checkpoint
    def chunk(acc, xs):
        return jax.lax.scan(one, acc, xs)[0], None

    xs = tuple(chunks(k) for k in kernels) + (chunks(dense_w.T),)
    return jax.lax.scan(chunk, jnp.zeros_like(h), xs)[0]


def layer_forward(arch: Arch, w: dict, lora_l: dict, x, positions, dense: bool,
                  q: Callable = identity, probe: bool = False):
    """One decoder layer.  ``lora_l``: this layer's adapters by their name
    inside the layer (``attn/q_a_proj/lora_a`` ...), absent = no branch.
    With ``probe`` returns ``(y, flipped_pair_share)`` of an expert layer."""
    scale = arch.lora_alpha / arch.lora_rank if arch.lora_rank else 0.0

    def proj(name, h):
        y = jnp.matmul(q(h), q(w[name]))
        a = lora_l.get(f"{name}/lora_a")
        if a is not None:
            b = lora_l[f"{name}/lora_b"]
            y = y + jnp.matmul(q(jnp.matmul(q(h), q(a))), q(b)) * scale
        return y

    def swiglu(group, h):
        act = jax.nn.silu(proj(f"{group}/gate_proj", h)) * proj(f"{group}/up_proj", h)
        return proj(f"{group}/down_proj", act)

    bsz, s, d = x.shape
    nh, dn, dr, dv = arch.n_heads, arch.nope, arch.rope, arch.v_dim
    h = rms_norm(x, w["attn_norm"], arch.rms_eps)
    c_q = rms_norm(proj("attn/q_a_proj", h), w["attn/q_a_norm"], arch.rms_eps)
    qh = proj("attn/q_b_proj", c_q).reshape(bsz, s, nh, dn + dr)
    kv_a = proj("attn/kv_a_proj_with_mqa", h)
    c_kv = rms_norm(kv_a[..., :arch.kv_rank], w["attn/kv_a_norm"], arch.rms_eps)
    kv = proj("attn/kv_b_proj", c_kv).reshape(bsz, s, nh, dn + dv)
    q_nope, q_rope = qh[..., :dn], rope_pairs(qh[..., dn:], positions, arch.rope_theta)
    k_nope, vh = kv[..., :dn], kv[..., dn:]
    k_rope = rope_pairs(kv_a[..., None, arch.kv_rank:], positions,
                        arch.rope_theta)[:, :, 0]               # (B, S, rope)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]

    def attend(qn, qr, kn, vb):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q(qn), q(kn))
                  + jnp.einsum("bqhd,bkd->bhqk", q(qr), q(k_rope))
                  ) * (dn + dr) ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", q(probs), q(vb))

    hb = heads_per_block(bsz, nh, s)
    if hb == nh:
        ctx = attend(q_nope, q_rope, k_nope, vh)
    else:
        # heads are independent: one block of them at a time, its scores
        # recomputed on the way back, so no (B, H, S, S) array ever exists
        def split(t):
            return jnp.moveaxis(
                t.reshape(bsz, s, nh // hb, hb, t.shape[-1]), 2, 0)

        ctx = jax.lax.map(lambda b: jax.checkpoint(attend)(*b),
                          tuple(split(t) for t in (q_nope, q_rope, k_nope, vh)))
        ctx = jnp.moveaxis(ctx, 0, 2)
    x = x + proj("attn/o_proj", ctx.reshape(bsz, s, nh * dv))
    h = rms_norm(x, w["mlp_norm"], arch.rms_eps)
    if dense:
        return x + swiglu("mlp", h)
    rows = h.reshape(bsz * s, d)
    y = routed_experts(arch, w, rows, q).reshape(bsz, s, d)
    if arch.n_shared:
        y = y + swiglu("moe/shared", h)
    return (x + y, flipped_pair_share(arch, w, rows)) if probe else x + y


def make_loss_and_grads(arch: Arch, q: Callable = identity, precision="highest",
                        rows_per_block: int = 1):
    """``fn(key, lora, tokens) -> (loss, grads)``; tokens (B, S) int32, all
    positions count (targets are tokens shifted by one); the loss is one mean
    over the global batch, walked in blocks of rows."""

    def _fwd(key, lora_l, prefix, index, dense, x, probe=False):
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return layer_forward(arch, layer_weights(arch, key, prefix, index, dense),
                             lora_l, x, pos, dense, q, probe)

    @jax.jit
    def embed(key, tokens):
        return top_weights(arch, key)["embedding"][tokens].astype(jnp.float32)

    @functools.partial(jax.jit, static_argnames=("prefix", "dense"))
    def layer_fwd(key, lora, index, x, *, prefix, dense):
        with jax.default_matmul_precision(precision):
            return _fwd(key, layer_lora(lora, prefix, index), prefix, index,
                        dense, x, probe=not dense)

    @functools.partial(jax.jit, static_argnames=("prefix", "dense"))
    def layer_bwd(key, lora, index, x, dy, *, prefix, dense):
        with jax.default_matmul_precision(precision):
            _, vjp = jax.vjp(
                lambda ll, xx: _fwd(key, ll, prefix, index, dense, xx),
                layer_lora(lora, prefix, index), x)
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

    @functools.partial(jax.jit, static_argnames=("prefix",))
    def accumulate(grads, dl, index, *, prefix):
        out = dict(grads)
        for name, g in dl.items():
            full = f"{prefix}/{name}"
            out[full] = (grads[full].at[index].add(g) if weights.is_stacked(full)
                         else grads[full] + g)
        return out

    def fn(key, lora, tokens):
        tokens = np.asarray(tokens, np.int32)
        grads = jax.tree.map(jnp.zeros_like, lora)
        total, flips = 0.0, []
        for r0 in range(0, tokens.shape[0], rows_per_block):
            tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
            x, saved = embed(key, tok), []
            for l in range(arch.n_layers):
                prefix, index, dense = _place(arch, l)
                saved.append(x)
                x = layer_fwd(key, lora, jnp.asarray(index, jnp.int32), x,
                              prefix=prefix, dense=dense)
                if not dense:
                    x, flip = x
                    flips.append(flip)
            nll, dx = head(key, x, tok)
            total += float(nll)
            for l in reversed(range(arch.n_layers)):
                prefix, index, dense = _place(arch, l)
                index = jnp.asarray(index, jnp.int32)
                dx, dl = layer_bwd(key, lora, index, saved.pop(), dx,
                                   prefix=prefix, dense=dense)
                grads = accumulate(grads, dl, index, prefix=prefix)
        inv = 1.0 / (tokens.shape[0] * (tokens.shape[1] - 1))
        if flips:
            print("reference: share of top-k pairs that flip when an expert "
                  "layer's input is rounded to bfloat16, largest of "
                  f"{len(flips)} layer-blocks: {max(float(f) for f in flips):.5f}",
                  flush=True)
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
