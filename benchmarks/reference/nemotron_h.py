"""Plain float32 reference of the pattern model's LoRA training step, found by
name (``"reference": "nemotron_h"``).

Written from the published description (the model's ``config.json`` keys, its
card's "LatentMoE: experts in 1024-d latent", the Mamba-2 paper's recurrence),
independent of the program's modules.  ``h = E[token]``; the layers of
``hybrid_override_pattern`` letter by letter, **walked in a Python loop**; every
layer is ``h = h + Mixer_kind(RMSNorm(h))`` — one norm, one mixer, one add, no
MLP half:

* ``*`` **attention without positions**: ``q = W_q u`` (heads of ``head_dim``),
  ``k = W_k u``, ``v = W_v u``, NO rotary embedding and no other position term,
  causal softmax at scale ``head_dim^-0.5``, ``W_o``; one key/value head's
  query heads at a time (sixteen at the published sizes);
* ``M`` **the mixer**: ``[z | xBC | dt] = W_in u``; ``xBC = silu(conv(xBC) +
  b)`` (causal, depthwise); ``delta = softplus(dt + dt_bias)`` (no clamp), ``A =
  -exp(A_log)``; **the recurrence itself, token by token under** ``lax.scan``
  (``reference/falcon_h1.py::recurrence``: ``S_t = exp(delta_t A) S_{t-1} +
  delta_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``; head ``i`` reads group ``i //
  (heads / groups)``) — NOT the chunked form the program computes: the two
  share no algebra; ``y = RMSNorm(y * silu(z))`` over each group's channels
  (gate first), ``W_out``.  That file's ``mixer`` is this one with every
  multiplier at 1 (a product with 1.0 is exact in float32);
* ``E`` **the expert layer**: float32 router ``s = sigmoid(W_r u)`` over ALL the
  published experts on the full-width state; the ``num_experts_per_tok`` largest
  of ``s`` (+ the selection bias where the file keeps one) are chosen, weights
  ``s[chosen] / (sum + 1e-20) * routed_scaling_factor``; ``r = W_fc1 u`` (no
  activation, no norm) is what an expert takes; **every HELD expert on every
  row under a mask**: ``sum_e m[t, e] W_down,e relu(W_up,e r_t)^2`` with ``m``
  the dense matrix of weights (zero where ``e`` was not chosen) — no sort, no
  grouped product, no row bound, no gate matrix; ``W_fc2`` applied to that sum
  (of the HELD experts' terms: the share's part of the layer), plus the shared
  expert ``W_down relu(W_up u)^2`` on the full-width state, written out.

``logits = lm_head(RMSNorm(h))``.  Every projection named above but the
router and the routed experts may carry a LoRA branch.

**Departures from the published model, all stated**: weights are random from a
seed; the rows are whole documents (no ``segment_ids`` reach a benchmark
cell), where the program would restart the state and the convolution at a
document boundary and the published code would not; the grouped norm is taken
over each of ``n_groups`` runs of channels; the experts held elsewhere add
nothing (``n_routed_experts`` in ``reduced``: one member's share, as the
program computes it); the next-token-prediction layer is not run.

Weights are regenerated leaf by leaf from the seed (``harness/weights.py``)
under the program's canonical names, in the type the program stores them
(bf16), and used at their exact float32 value.  **Where a leaf lives follows
the pattern** (:func:`places`): a run of the pattern that repeats is one
stack — ``blocks`` (drawn a repeat at a time) or, a later one,
``blocks_<first layer>`` (ONE array with the repeats' axis first, as the
harness draws any stack it does not know) — whose unit's layers are
``layer_<j>``; a layer that does not repeat is ``layer_<i>``.  Reverse mode is
written out layer by layer as ``reference/train.py`` does; clip and AdamW are
that file's.  ``q`` is the lower-precision control's hook: both operands of
every matrix product, the recurrence's (``delta x``, ``B``, ``C``) among them.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, weights
from benchmarks.reference import falcon_h1
from benchmarks.reference import train as ref_train
from benchmarks.reference.model import (head_logits, identity, rms_norm,
                                        top_weights)

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


class Place(NamedTuple):
    """Where one layer's leaves live: ``prefix/...`` and, in a stack of
    ``repeats`` (0: no stack), the repeat ``index``."""
    prefix: str
    index: int
    repeats: int
    kind: str


def places(pattern: str) -> list[Place]:
    """Layer by layer, where the program keeps it: at each layer the repeated
    unit that covers the most layers from there (the shortest such) is one
    stack, ``blocks`` or a later ``blocks_<first layer>``; a layer nothing
    repeats stands by itself, ``layer_<i>``."""
    out, at, stacks, n = [], 0, 0, len(pattern)
    while at < n:
        unit, repeats = pattern[at], 1
        for u in range(1, (n - at) // 2 + 1):
            r = 1
            while pattern[at + r * u:at + (r + 1) * u] == pattern[at:at + u]:
                r += 1
            if r > 1 and u * r > len(unit) * repeats:
                unit, repeats = pattern[at:at + u], r
        if repeats == 1:
            out.append(Place(f"layer_{at}", 0, 0, unit))
        else:
            stack = f"blocks_{at}" if stacks else weights.STACKED
            out += [Place(f"{stack}/layer_{j}", r, repeats, kind)
                    for r in range(repeats) for j, kind in enumerate(unit)]
            stacks += 1
        at += len(unit) * repeats
    return out


class Arch(NamedTuple):
    vocab_size: int
    hidden_size: int
    pattern: str
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rms_eps: float
    ssm_inner: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    n_experts: int          # the router's width: the published count
    experts_held: tuple     # (first, count) of those this chip computes
    top_k: int
    expert_ff: int
    latent: int
    shared_ff: int
    routed_scale: float
    select_bias: bool
    base_dtype: str
    lora_rank: int
    lora_alpha: float
    lora_targets: tuple
    #: what ``falcon_h1.mixer`` multiplies by: this family has no multiplier
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0,) * 5

    @classmethod
    def from_config(cls, conf: dict, experts_held=None) -> "Arch":
        run = conf["run"]
        if conf["mlp_hidden_act"] != "relu2" or not conf["norm_topk_prob"] \
                or conf["n_group"] != 1 or conf["topk_group"] != 1:
            raise ValueError("this reference computes squared-ReLU experts "
                             "without a gate under normalised top-k weights "
                             "with no group limit")
        if set(conf["hybrid_override_pattern"]) - set(KINDS) or len(
                conf["hybrid_override_pattern"]) != conf["num_hidden_layers"]:
            raise ValueError("hybrid_override_pattern: one of M E * a layer")
        held = conf["n_routed_experts"]
        total = held
        if "n_routed_experts" in conf.get("reduced", []):
            total = conf["published"]["n_routed_experts"]
        return cls(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            pattern=conf["hybrid_override_pattern"],
            n_heads=conf["num_attention_heads"],
            n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            rms_eps=float(conf["layer_norm_epsilon"]),
            ssm_inner=conf["mamba_num_heads"] * conf["mamba_head_dim"],
            ssm_heads=conf["mamba_num_heads"],
            ssm_head_dim=conf["mamba_head_dim"],
            ssm_state=conf["ssm_state_size"], ssm_groups=conf["n_groups"],
            ssm_conv=conf["conv_kernel"],
            n_experts=total, experts_held=tuple(experts_held or (0, held)),
            top_k=conf["num_experts_per_tok"],
            expert_ff=conf["moe_intermediate_size"],
            latent=conf["moe_latent_size"],
            shared_ff=(conf["n_shared_experts"]
                       * conf["moe_shared_expert_intermediate_size"]),
            routed_scale=float(conf["routed_scaling_factor"]),
            select_bias=run.get("selection_bias", "seeded") == "seeded",
            base_dtype=run["frozen_dtype"], lora_rank=int(run["lora_rank"]),
            lora_alpha=float(run["lora_alpha"]),
            lora_targets=tuple(run["lora_targets"]),
        )

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def proj_shapes(self, kind: str) -> dict[str, tuple[int, int]]:
        """The LoRA-carrying projections of a layer of ``kind``, ``name ->
        (in, out)``."""
        d, hd = self.hidden_size, self.head_dim
        q, kv = self.n_heads * hd, self.n_kv_heads * hd
        return {
            "*": {"attn/q_proj": (d, q), "attn/k_proj": (d, kv),
                  "attn/v_proj": (d, kv), "attn/o_proj": (q, d)},
            "M": {"mamba/in_proj": (d, self.ssm_inner + self.conv_channels
                                    + self.ssm_heads),
                  "mamba/out_proj": (self.ssm_inner, d)},
            "E": {"moe/fc1_latent_proj": (d, self.latent),
                  "moe/fc2_latent_proj": (self.latent, d),
                  "moe/shared/up_proj": (d, self.shared_ff),
                  "moe/shared/down_proj": (self.shared_ff, d)},
        }[kind]

    def other_shapes(self, kind: str) -> dict[str, tuple]:
        """Every other leaf of a layer of ``kind``, ``name -> shape``: used in
        float32 but for the stacked routed experts, which stay as stored and
        are up-cast an expert at a time."""
        d, h, held = self.hidden_size, self.ssm_heads, self.experts_held[1]
        return {"norm/scale": (d,), **{
            "*": {},
            "M": {"mamba/conv1d/kernel": (self.ssm_conv, self.conv_channels),
                  "mamba/conv1d/bias": (self.conv_channels,),
                  "mamba/A_log/bias": (h,), "mamba/dt_bias/bias": (h,),
                  "mamba/D/scale": (h,), "mamba/norm/scale": (self.ssm_inner,)},
            "E": {"moe/router/kernel": (d, self.n_experts),
                  **({"moe/router/bias": (self.n_experts,)}
                     if self.select_bias else {}),
                  # the leaf the program holds: its own experts', drawn at
                  # that shape (a share is not a slice of the uncut draw)
                  "moe/experts/up_proj/kernel": (held, self.latent, self.expert_ff),
                  "moe/experts/down_proj/kernel": (held, self.expert_ff, self.latent)},
        }[kind]}


def layer_weights(arch: Arch, key, place: Place, index) -> dict:
    """One layer's frozen weights, regenerated from the seed; ``index`` (the
    repeat in its stack) may be traced."""
    base = jnp.dtype(arch.base_dtype)

    def draw(name, shape):
        full = f"{place.prefix}/{name}"
        if not place.repeats:
            return weights.layer_leaf(key, full, 0, shape, base)
        if weights.is_stacked(full):
            return weights.layer_leaf(key, full, index, shape, base)
        return weights.leaf(key, full, (place.repeats,) + shape, base,
                            stacked=False)[index]

    out = {}
    for name, shape in arch.other_shapes(place.kind).items():
        leaf = draw(name, shape)
        out[name] = leaf if "/experts/" in name else leaf.astype(jnp.float32)
    for name, shape in arch.proj_shapes(place.kind).items():
        out[name] = draw(f"{name}/kernel", shape).astype(jnp.float32)
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


def layer_lora(lora: dict, place: Place, index) -> dict:
    """One layer's adapters by their name inside the layer."""
    cut = len(place.prefix) + 1
    return {n[cut:]: (v[index] if place.repeats else v)
            for n, v in lora.items() if n.startswith(place.prefix + "/")}


def attention(arch: Arch, proj: Callable, u, q: Callable = identity):
    """Causal attention with NO position term."""
    bsz, s, _ = u.shape
    hd, nh, nkv = arch.head_dim, arch.n_heads, arch.n_kv_heads
    qh = proj("attn/q_proj", u).reshape(bsz, s, nkv, nh // nkv, hd)
    kh = proj("attn/k_proj", u).reshape(bsz, s, nkv, hd)
    vh = proj("attn/v_proj", u).reshape(bsz, s, nkv, hd)
    at = jnp.arange(s)
    causal = (at[:, None] >= at[None, :])[None, None]

    @jax.checkpoint
    def group(operands):
        """The query heads that share one key/value head; its scores are
        recomputed on the way back, so no ``(B, H, S, S)`` array ever exists."""
        qg, kg, vg = operands
        scores = jnp.einsum("bqhd,bkd->bhqk", q(qg), q(kg)) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", q(probs), q(vg))

    ctx = jax.lax.map(group, (jnp.moveaxis(qh, 2, 0), jnp.moveaxis(kh, 2, 0),
                              jnp.moveaxis(vh, 2, 0)))
    return proj("attn/o_proj", jnp.moveaxis(ctx, 0, 2).reshape(bsz, s, nh * hd))


def route(arch: Arch, w: dict, rows, q: Callable = identity):
    """``(T, held)`` float32: each row's weight on every HELD expert, zero
    where the expert was not among its ``top_k``."""
    scores = jax.nn.sigmoid(jnp.matmul(q(rows), q(w["moe/router/kernel"])))
    select = scores + w["moe/router/bias"] if arch.select_bias else scores
    _, chosen = jax.lax.top_k(select, arch.top_k)
    weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (weight.sum(-1, keepdims=True) + 1e-20) * arch.routed_scale
    first, count = arch.experts_held
    return (jax.nn.one_hot(chosen, arch.n_experts, dtype=jnp.float32)
            * weight[..., None]).sum(1)[:, first:first + count]


def expert_layer(arch: Arch, w: dict, proj: Callable, u, q: Callable = identity):
    """The share's part of the expert layer on the normed rows ``u``: every
    held expert on every row under the mask of :func:`route`, in the latent;
    ``fc2`` on that sum; the shared expert beside it.  Two nested loops over
    the experts, each body recomputed on the way back, so neither a ``(T, E,
    .)`` array nor ``E`` copies of the sum are ever held."""
    bsz, s, d = u.shape
    rows = u.reshape(bsz * s, d)
    mask = route(arch, w, rows, q)
    r = proj("moe/fc1_latent_proj", rows)
    count = arch.experts_held[1]
    inner = max(c for c in range(1, 17) if count % c == 0)

    def chunks(a):
        return a.reshape((count // inner, inner) + a.shape[1:])

    @jax.checkpoint
    def one(acc, xs):
        up, down, col = xs
        act = jnp.square(jax.nn.relu(jnp.matmul(q(r), q(up.astype(jnp.float32)))))
        return acc + col[:, None] * jnp.matmul(
            q(act), q(down.astype(jnp.float32))), None

    @jax.checkpoint
    def chunk(acc, xs):
        return jax.lax.scan(one, acc, xs)[0], None

    routed = jax.lax.scan(chunk, jnp.zeros_like(r), (
        chunks(w["moe/experts/up_proj/kernel"]),
        chunks(w["moe/experts/down_proj/kernel"]), chunks(mask.T)))[0]
    shared = proj("moe/shared/down_proj", jnp.square(jax.nn.relu(
        proj("moe/shared/up_proj", rows))))
    return (proj("moe/fc2_latent_proj", routed) + shared).reshape(bsz, s, d)


def layer_forward(arch: Arch, kind: str, w: dict, lora_l: dict, x,
                  q: Callable = identity):
    """One layer of ``kind``.  ``lora_l``: this layer's adapters by their name
    inside the layer (``mamba/in_proj/lora_a`` ...), absent = no branch."""
    scale = arch.lora_alpha / arch.lora_rank if arch.lora_rank else 0.0

    def proj(name, h):
        y = jnp.matmul(q(h), q(w[name]))
        a = lora_l.get(f"{name}/lora_a")
        if a is not None:
            b = lora_l[f"{name}/lora_b"]
            y = y + jnp.matmul(q(jnp.matmul(q(h), q(a))), q(b)) * scale
        return y

    u = rms_norm(x, w["norm/scale"], arch.rms_eps)
    if kind == "M":
        return x + falcon_h1.mixer(arch, w, proj, u, q)
    if kind == "E":
        return x + expert_layer(arch, w, proj, u, q)
    return x + attention(arch, proj, u, q)


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
