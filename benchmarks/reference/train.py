"""Plain reference for a LoRA training step: next-token cross-entropy over
every position of the batch, its gradient with respect to the adapters,
clip-by-global-norm and AdamW.

Reverse mode is written out layer by layer (``jax.vjp`` of one plain layer at
a time, weights regenerated from the seed inside each call) and the batch is
walked in blocks of rows, so the reference holds one layer's float32 weights
and one block's activations at a time.  The loss is the sum over the batch's
target positions divided by their count, as one mean over the global batch.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import model as ref


def make_loss_and_grads(arch: ref.Arch, q: Callable = ref.identity,
                        precision="highest", rows_per_block: int = 2):
    """``fn(key, lora, tokens) -> (loss, grads)``; tokens (B, S) int32, all
    positions count (targets are tokens shifted by one)."""

    def _fwd(key, lora_l, l, x):
        pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
        return ref.layer_forward(arch, ref.layer_weights(arch, key, l),
                                 lora_l, x, pos, q)

    @jax.jit
    def embed(key, tokens):
        return ref.top_weights(arch, key)["embedding"][tokens].astype(jnp.float32)

    @jax.jit
    def layer_fwd(key, lora, l, x):
        with jax.default_matmul_precision(precision):
            return _fwd(key, ref._layer_lora(lora, l), l, x)

    @jax.jit
    def layer_bwd(key, lora, l, x, dy):
        with jax.default_matmul_precision(precision):
            _, vjp = jax.vjp(lambda ll, xx: _fwd(key, ll, l, xx),
                             ref._layer_lora(lora, l), x)
            dl, dx = vjp(dy)
            return dx, dl

    @jax.jit
    def head(key, x, tokens):
        def nll_sum(xx):
            with jax.default_matmul_precision(precision):
                logits = ref.head_logits(arch, ref.top_weights(arch, key),
                                         xx[:, :-1], q)
            logp = jax.nn.log_softmax(logits, axis=-1)
            tgt = tokens[:, 1:]
            return -jnp.take_along_axis(logp, tgt[..., None], -1).sum()

        return jax.value_and_grad(nll_sum)(x)

    @jax.jit
    def accumulate(grads, dl, l):
        return {k: g.at[l].add(dl[k]) for k, g in grads.items()}

    def fn(key, lora, tokens, devices=None):
        """``devices``: where the row blocks are computed, round robin — the
        blocks are independent, and dispatch is asynchronous, so a four-chip
        host follows four blocks at once.  ``None``: the default device."""
        tokens = np.asarray(tokens, np.int32)
        devices = list(devices) if devices else [None]
        put = (lambda x, d: x) if devices == [None] else jax.device_put
        keys = [put(key, d) for d in devices]
        loras = [put(lora, d) for d in devices]
        blocks = [(put(tokens[r0:r0 + rows_per_block], devices[i % len(devices)]),
                   i % len(devices))
                  for i, r0 in enumerate(range(0, tokens.shape[0], rows_per_block))]
        count = tokens.shape[0] * (tokens.shape[1] - 1)
        grads = [jax.tree.map(jnp.zeros_like, lo) for lo in loras]
        totals = []
        # waves of one block a device: layers outside, blocks inside, so the
        # devices work side by side
        for w0 in range(0, len(blocks), len(devices)):
            wave = blocks[w0:w0 + len(devices)]
            xs = [embed(keys[d], tok) for tok, d in wave]
            saved = [[] for _ in wave]
            for l in range(arch.n_layers):
                li = jnp.asarray(l, jnp.int32)
                for j, (_tok, d) in enumerate(wave):
                    saved[j].append(xs[j])
                    xs[j] = layer_fwd(keys[d], loras[d], put(li, devices[d]), xs[j])
            dxs = []
            for j, (tok, d) in enumerate(wave):
                nll, dx = head(keys[d], xs[j], tok)
                totals.append(nll)
                dxs.append(dx)
            for l in reversed(range(arch.n_layers)):
                li = jnp.asarray(l, jnp.int32)
                for j, (_tok, d) in enumerate(wave):
                    lj = put(li, devices[d])
                    dxs[j], dl = layer_bwd(keys[d], loras[d], lj,
                                           saved[j].pop(), dxs[j])
                    grads[d] = accumulate(grads[d], dl, lj)
        inv = 1.0 / count
        home = devices[0]
        total = sum(float(t) for t in totals)
        summed = grads[0]
        for g in grads[1:]:
            summed = jax.tree.map(jnp.add, summed, put(g, home))
        return total * inv, jax.tree.map(lambda g: g * inv, summed)

    return fn


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    factor = jnp.where(norm > max_norm, max_norm / norm, 1.0)
    return jax.tree.map(lambda g: g * factor, grads)


class AdamW:
    """Adam with bias correction and decoupled weight decay at a constant
    learning rate (Loshchilov & Hutter), on clipped gradients."""

    def __init__(self, lr: float, b1=0.9, b2=0.999, eps=1e-8,
                 weight_decay=0.0, clip_norm=1.0):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps
        self.wd, self.clip = weight_decay, clip_norm
        self.t = 0
        self.mu = self.nu = None

    def update(self, params, grads):
        """Returns ``(new_params, clipped_grads)``."""
        g = clip_by_global_norm(grads, self.clip)
        if self.mu is None:
            self.mu = jax.tree.map(jnp.zeros_like, g)
            self.nu = jax.tree.map(jnp.zeros_like, g)
        self.t += 1
        self.mu = jax.tree.map(lambda m, x: self.b1 * m + (1 - self.b1) * x,
                               self.mu, g)
        self.nu = jax.tree.map(lambda v, x: self.b2 * v + (1 - self.b2) * x * x,
                               self.nu, g)
        c1, c2 = 1 - self.b1 ** self.t, 1 - self.b2 ** self.t
        new = jax.tree.map(
            lambda p, m, v: p - self.lr * (
                (m / c1) / (jnp.sqrt(v / c2) + self.eps) + self.wd * p),
            params, self.mu, self.nu)
        return new, g
