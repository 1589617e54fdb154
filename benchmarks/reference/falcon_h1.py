"""Plain float32 reference of the hybrid state-space model's LoRA training
step, found by name (``"reference": "falcon_h1"``).

Written from the published description (the model's ``config.json`` keys, the
``falcon_h1`` architecture as its authors describe it — "parallel Mamba-2 +
attention heads per block" — and the Mamba-2 paper's recurrence), independent
of the program's modules.  ``h = E[token] * embedding_multiplier``; per layer,
``u = RMSNorm(h)``:

1. **attention**: ``q = W_q (u * attention_in_multiplier)`` (heads of
   ``head_dim``), ``k = (W_k ...) * key_multiplier``, ``v = W_v ...``, no
   biases; rotary embedding on the two halves of a head, theta as published, no
   scaling; causal softmax at scale ``head_dim^-0.5``; ``W_o``; times
   ``attention_out_multiplier``.  One group of query heads (those that share a
   key/value head) at a time;
2. **the mixer**, beside it on the SAME ``u``: ``[z | xBC | dt] = W_in (u *
   ssm_in_multiplier) * mu`` (``mu``: ``ssm_multipliers`` over z, x, B, C, dt);
   ``xBC = silu(conv(xBC) + b)`` (causal, depthwise, ``mamba_d_conv`` rows);
   ``delta = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; **the
   recurrence itself, token by token under** ``lax.scan``: ``S_t = exp(delta_t
   A) S_{t-1} + delta_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t`` (:func:`recurrence`;
   head ``i`` reads group ``i // (heads / groups)``; rematerialised in blocks
   of rows, so 8,192 steps of a 32 x 128 x 256 state fit) — NOT the chunked
   form the program computes: the two share no algebra; ``y = RMSNorm(y *
   silu(z))`` over each group's channels (gate first: ``mamba_norm_before_gate``
   false), ``W_out``; times ``ssm_out_multiplier``;
3. ``h = h + attention + mixer`` (one norm, two mixers, one add); then ``h = h
   + W_down(silu(W_gate r * mlp_multipliers[0]) * W_up r) * mlp_multipliers[1]``
   with ``r = RMSNorm(h)``.

``logits = lm_head(RMSNorm(h)) * lm_head_multiplier``.  Every projection may
carry a LoRA branch.  Departures from the published model, all stated: weights
are random from a seed; the rows are whole documents (no ``segment_ids``
reach a benchmark cell), where the program would restart the state and the
convolution at a document boundary and the published code would not; the
grouped norm is taken over each of ``mamba_n_groups`` runs of channels.
Weights are regenerated leaf by leaf from the seed (``harness/weights.py``)
under the program's canonical names, in the type the program stores them
(bf16), and used at their exact float32 value.  Reverse mode is written out
layer by layer over blocks of rows as ``reference/train.py`` does; clip and
AdamW are that file's.  ``q`` is the lower-precision control's hook: both
operands of every matrix product, the recurrence's (``delta x``, ``B``, ``C``)
among them.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import compare, weights
from benchmarks.reference import train as ref_train
from benchmarks.reference.model import (head_logits, identity, rms_norm, rope,
                                        top_weights)

PREFIX = weights.STACKED
#: rows of the recurrence replayed at once on the way back
SCAN_BLOCK = 128


class Arch(NamedTuple):
    vocab_size: int
    hidden_size: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    intermediate_size: int
    rope_theta: float
    rms_eps: float
    ssm_inner: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    embedding_multiplier: float
    lm_head_multiplier: float
    attention_in_multiplier: float
    attention_out_multiplier: float
    key_multiplier: float
    ssm_in_multiplier: float
    ssm_out_multiplier: float
    ssm_multipliers: tuple
    mlp_multipliers: tuple
    base_dtype: str
    lora_rank: int
    lora_alpha: float
    lora_targets: tuple

    @classmethod
    def from_config(cls, conf: dict) -> "Arch":
        run = conf["run"]
        if conf["mamba_norm_before_gate"] or not conf["mamba_rms_norm"]:
            raise ValueError("this reference gates, then takes the grouped RMSNorm")
        if conf["mamba_d_ssm"] != conf["mamba_n_heads"] * conf["mamba_d_head"]:
            raise ValueError("mamba_d_ssm is not mamba_n_heads heads of mamba_d_head")
        return cls(
            vocab_size=conf["vocab_size"], hidden_size=conf["hidden_size"],
            n_layers=conf["num_hidden_layers"],
            n_heads=conf["num_attention_heads"],
            n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            intermediate_size=conf["intermediate_size"],
            rope_theta=float(conf["rope_theta"]),
            rms_eps=float(conf["rms_norm_eps"]),
            ssm_inner=conf["mamba_d_ssm"], ssm_heads=conf["mamba_n_heads"],
            ssm_head_dim=conf["mamba_d_head"], ssm_state=conf["mamba_d_state"],
            ssm_groups=conf["mamba_n_groups"], ssm_conv=conf["mamba_d_conv"],
            embedding_multiplier=float(conf["embedding_multiplier"]),
            lm_head_multiplier=float(conf["lm_head_multiplier"]),
            attention_in_multiplier=float(conf["attention_in_multiplier"]),
            attention_out_multiplier=float(conf["attention_out_multiplier"]),
            key_multiplier=float(conf["key_multiplier"]),
            ssm_in_multiplier=float(conf["ssm_in_multiplier"]),
            ssm_out_multiplier=float(conf["ssm_out_multiplier"]),
            ssm_multipliers=tuple(float(m) for m in conf["ssm_multipliers"]),
            mlp_multipliers=tuple(float(m) for m in conf["mlp_multipliers"]),
            base_dtype=run["frozen_dtype"], lora_rank=int(run["lora_rank"]),
            lora_alpha=float(run["lora_alpha"]),
            lora_targets=tuple(run["lora_targets"]),
        )

    @property
    def conv_channels(self) -> int:
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    def proj_shapes(self) -> dict[str, tuple[int, int]]:
        """The LoRA-carrying projections of a layer, ``name -> (in, out)``."""
        d, hd, f = self.hidden_size, self.head_dim, self.intermediate_size
        q, kv = self.n_heads * hd, self.n_kv_heads * hd
        return {
            "attn/q_proj": (d, q), "attn/k_proj": (d, kv), "attn/v_proj": (d, kv),
            "attn/o_proj": (q, d),
            "mamba/in_proj": (d, self.ssm_inner + self.conv_channels + self.ssm_heads),
            "mamba/out_proj": (self.ssm_inner, d),
            "mlp/gate_proj": (d, f), "mlp/up_proj": (d, f), "mlp/down_proj": (f, d),
        }

    def vector_shapes(self) -> dict[str, tuple]:
        """Every other leaf of a layer, ``name -> shape``."""
        d, h = self.hidden_size, self.ssm_heads
        return {
            "attn_norm/scale": (d,), "mlp_norm/scale": (d,),
            "mamba/conv1d/kernel": (self.ssm_conv, self.conv_channels),
            "mamba/conv1d/bias": (self.conv_channels,),
            "mamba/A_log/bias": (h,), "mamba/dt_bias/bias": (h,),
            "mamba/D/scale": (h,), "mamba/norm/scale": (self.ssm_inner,),
        }


def layer_weights(arch: Arch, key, layer) -> dict:
    """One layer's frozen weights in float32 (the exact value of what is
    stored), regenerated from the seed; ``layer`` may be traced."""
    base = jnp.dtype(arch.base_dtype)
    out = {}
    for name, shape in arch.vector_shapes().items():
        out[name] = weights.layer_leaf(
            key, f"{PREFIX}/{name}", layer, shape, base).astype(jnp.float32)
    for name, shape in arch.proj_shapes().items():
        out[name] = weights.layer_leaf(
            key, f"{PREFIX}/{name}/kernel", layer, shape, base).astype(jnp.float32)
    return out


def init_lora(arch: Arch, key) -> dict:
    """The seeded adapters, stacked over layers: ``name -> (L, ...)``."""
    out = {}
    for name, (i, o) in arch.proj_shapes().items():
        if name.split("/")[1] not in arch.lora_targets or not arch.lora_rank:
            continue
        for leaf, shape in (("lora_a", (i, arch.lora_rank)),
                            ("lora_b", (arch.lora_rank, o))):
            full = f"{PREFIX}/{name}/{leaf}"
            out[full] = weights.leaf(key, full, (arch.n_layers,) + shape,
                                     jnp.float32, stacked=True)
    return out


def recurrence(fed, log_decay, b, c, q: Callable = identity,
               block: int = SCAN_BLOCK):
    """``S_t = exp(log_decay_t) S_{t-1} + fed_t (x) B_t``, ``y_t = S_t C_t``,
    a token at a time: ``fed: (B, S, G, J, P)`` (``delta x``, ``J`` heads a
    group), ``log_decay: (B, S, G, J)`` (``delta A``; ``-inf`` restarts the
    state), ``b``, ``c: (B, S, G, N)`` -> ``y: (B, S, G, J, P)``.  The state
    ``(B, G, J, P, N)`` is float32; rows go in blocks of ``block`` whose steps
    are replayed on the way back, so one block's states exist at a time."""
    bsz, s, g, j, p = fed.shape
    n = b.shape[-1]
    pad = -s % block
    rows = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q(fed), log_decay, q(b), q(c))]

    def token(state, row):
        f, ld, bt, ct = row
        state = (state * jnp.exp(ld)[..., None, None]
                 + f[..., :, None] * bt[:, :, None, None, :])
        return state, (state * ct[:, :, None, None, :]).sum(-1)

    @jax.checkpoint
    def rows_of_a_block(state, block_rows):
        return jax.lax.scan(token, state, block_rows)

    def blocks(t):      # (B, S, ...) -> (S / block, block, B, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((t.shape[0] // block, block) + t.shape[1:])

    _, y = jax.lax.scan(rows_of_a_block,
                        jnp.zeros((bsz, g, j, p, n), jnp.float32),
                        tuple(blocks(t) for t in rows))
    return jnp.moveaxis(y.reshape((s + pad,) + y.shape[2:]), 0, 1)[:, :s]


def conv_rows(x, kernel, bias):
    """Causal depthwise convolution: ``y_t = bias + sum_k kernel[k] x_{t - (K
    - 1 - k)}``, rows before the first count as zero.  ``x: (B, S, C)``."""
    taps = kernel.shape[0]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    stacked = jnp.stack([padded[:, k:k + x.shape[1]] for k in range(taps)], axis=2)
    return (stacked * kernel).sum(axis=2) + bias


def mixer(arch: Arch, w: dict, proj: Callable, u, q: Callable = identity):
    """The state-space mixer on the normed rows ``u``; ``proj(name, rows)``
    is the layer's projection with its adapter."""
    bsz, s, _ = u.shape
    h, p, g, n = arch.ssm_heads, arch.ssm_head_dim, arch.ssm_groups, arch.ssm_state
    inner, gn = arch.ssm_inner, arch.ssm_groups * arch.ssm_state
    mz, mx, mb, mc, mdt = arch.ssm_multipliers
    mu = jnp.concatenate([jnp.full((width,), m, jnp.float32) for width, m in
                          ((inner, mz), (inner, mx), (gn, mb), (gn, mc), (h, mdt))])
    zxbcdt = proj("mamba/in_proj", u * arch.ssm_in_multiplier) * mu
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(conv_rows(xbc, w["mamba/conv1d/kernel"], w["mamba/conv1d/bias"]))
    x = xbc[..., :inner].reshape(bsz, s, g, h // g, p)
    b = xbc[..., inner:inner + gn].reshape(bsz, s, g, n)
    c = xbc[..., inner + gn:].reshape(bsz, s, g, n)
    delta = jax.nn.softplus(dt + w["mamba/dt_bias/bias"]).reshape(bsz, s, g, h // g)
    a = -jnp.exp(w["mamba/A_log/bias"]).reshape(g, h // g)
    y = recurrence(x * delta[..., None], delta * a, b, c, q)
    y = y + x * w["mamba/D/scale"].reshape(g, h // g, 1)
    gated = (y.reshape(bsz, s, inner) * jax.nn.silu(z)).reshape(bsz, s, g, inner // g)
    normed = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + arch.rms_eps)
    return proj("mamba/out_proj",
                normed.reshape(bsz, s, inner) * w["mamba/norm/scale"])


def attention(arch: Arch, proj: Callable, u, positions, q: Callable = identity):
    bsz, s, _ = u.shape
    hd, nh, nkv = arch.head_dim, arch.n_heads, arch.n_kv_heads
    qh = rope(proj("attn/q_proj", u).reshape(bsz, s, nh, hd), positions,
              arch.rope_theta)
    kh = rope((proj("attn/k_proj", u) * arch.key_multiplier)
              .reshape(bsz, s, nkv, hd), positions, arch.rope_theta)
    vh = proj("attn/v_proj", u).reshape(bsz, s, nkv, hd)
    causal = positions[:, None, :, None] >= positions[:, None, None, :]

    @jax.checkpoint
    def group(operands):
        """The query heads that share one key/value head: ``qg: (B, S, heads
        a group, D)``, ``kg``, ``vg: (B, S, D)``; its scores are recomputed on
        the way back, so no ``(B, H, S, S)`` array ever exists."""
        qg, kg, vg = operands
        scores = jnp.einsum("bqhd,bkd->bhqk", q(qg), q(kg)) * hd ** -0.5
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", q(probs), q(vg))

    ctx = jax.lax.map(group, (
        jnp.moveaxis(qh.reshape(bsz, s, nkv, nh // nkv, hd), 2, 0),
        jnp.moveaxis(kh, 2, 0), jnp.moveaxis(vh, 2, 0)))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(bsz, s, nh * hd)
    return proj("attn/o_proj", ctx)


def layer_forward(arch: Arch, w: dict, lora_l: dict, x, positions,
                  q: Callable = identity):
    """One block.  ``lora_l``: this layer's adapters by their name inside the
    layer (``attn/q_proj/lora_a`` ...), absent = no branch."""
    scale = arch.lora_alpha / arch.lora_rank if arch.lora_rank else 0.0

    def proj(name, h):
        y = jnp.matmul(q(h), q(w[name]))
        a = lora_l.get(f"{name}/lora_a")
        if a is not None:
            b = lora_l[f"{name}/lora_b"]
            y = y + jnp.matmul(q(jnp.matmul(q(h), q(a))), q(b)) * scale
        return y

    u = rms_norm(x, w["attn_norm/scale"], arch.rms_eps)
    x = (x + attention(arch, proj, u * arch.attention_in_multiplier, positions, q)
         * arch.attention_out_multiplier
         + mixer(arch, w, proj, u, q) * arch.ssm_out_multiplier)
    r = rms_norm(x, w["mlp_norm/scale"], arch.rms_eps)
    gate_by, down_by = arch.mlp_multipliers
    act = jax.nn.silu(proj("mlp/gate_proj", r) * gate_by) * proj("mlp/up_proj", r)
    return x + proj("mlp/down_proj", act) * down_by


def _layer_lora(lora: dict, layer) -> dict:
    cut = len(PREFIX) + 1
    return {name[cut:]: v[layer] for name, v in lora.items()}


def make_loss_and_grads(arch: Arch, q: Callable = identity, precision="highest",
                        rows_per_block: int = 1):
    """``fn(key, lora, tokens) -> (loss, grads)``; tokens (B, S) int32, all
    positions count (targets are tokens shifted by one); the loss is one mean
    over the global batch, walked in blocks of rows."""

    def _fwd(key, lora_l, layer, x):
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return layer_forward(arch, layer_weights(arch, key, layer), lora_l, x,
                             pos, q)

    @jax.jit
    def embed(key, tokens):
        return (top_weights(arch, key)["embedding"][tokens].astype(jnp.float32)
                * arch.embedding_multiplier)

    @jax.jit
    def layer_fwd(key, lora, layer, x):
        with jax.default_matmul_precision(precision):
            return _fwd(key, _layer_lora(lora, layer), layer, x)

    @jax.jit
    def layer_bwd(key, lora, layer, x, dy):
        with jax.default_matmul_precision(precision):
            _, vjp = jax.vjp(lambda ll, xx: _fwd(key, ll, layer, xx),
                             _layer_lora(lora, layer), x)
            dl, dx = vjp(dy)
            return dx, dl

    @jax.jit
    def head(key, x, tokens):
        def nll_sum(xx):
            with jax.default_matmul_precision(precision):
                logits = head_logits(arch, top_weights(arch, key), xx[:, :-1], q)
            logp = jax.nn.log_softmax(logits * arch.lm_head_multiplier, axis=-1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).sum()

        return jax.value_and_grad(nll_sum)(x)

    @jax.jit
    def accumulate(grads, dl, layer):
        return {name: g.at[layer].add(dl[name[len(PREFIX) + 1:]])
                for name, g in grads.items()}

    def fn(key, lora, tokens):
        tokens = np.asarray(tokens, np.int32)
        grads = jax.tree.map(jnp.zeros_like, lora)
        total = 0.0
        for r0 in range(0, tokens.shape[0], rows_per_block):
            tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
            x, saved = embed(key, tok), []
            for l in range(arch.n_layers):
                saved.append(x)
                x = layer_fwd(key, lora, jnp.asarray(l, jnp.int32), x)
            nll, dx = head(key, x, tok)
            total += float(nll)
            for l in reversed(range(arch.n_layers)):
                layer = jnp.asarray(l, jnp.int32)
                dx, dl = layer_bwd(key, lora, layer, saved.pop(), dx)
                grads = accumulate(grads, dl, layer)
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
