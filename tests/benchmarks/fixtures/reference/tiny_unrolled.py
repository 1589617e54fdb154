"""Tests only: the plain reference of ``programs/tiny_unrolled.py``, added by
a new file alone.  The same layer equations (``reference/model.py``) under
the unrolled program's leaf names: layer ``l``'s weights are drawn as
``layer_<l>/...``, and every adapter leaf is one entry of the norms."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.harness import compare, weights
from benchmarks.reference import model as ref, train as ref_train


def reference_numbers(conf, wl, seed, token_batches, *, q=ref.identity,
                      precision="highest", devices=None):
    arch = ref.Arch.from_config(conf)
    key = weights.root_key(seed)
    lora0 = {}
    for l in range(arch.n_layers):
        for name, (i, o) in arch.proj_shapes().items():
            for leaf, shape in (("lora_a", (i, arch.lora_rank)),
                                ("lora_b", (arch.lora_rank, o))):
                full = f"layer_{l}/{name}/{leaf}"
                lora0[full] = weights.leaf(key, full, shape, jnp.float32,
                                           stacked=False)

    @jax.jit
    def loss_and_grads(lora, tokens):
        def mean_nll(lora):
            top = ref.top_weights(arch, key)
            x = top["embedding"][tokens].astype(jnp.float32)
            pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
            with jax.default_matmul_precision(precision):
                for l in range(arch.n_layers):
                    here = f"layer_{l}/"
                    x = ref.layer_forward(
                        arch, ref.layer_weights(arch, key, 0, f"layer_{l}"),
                        {f"{weights.STACKED}/{n[len(here):]}": v
                         for n, v in lora.items() if n.startswith(here)},
                        x, pos, q)
                logits = ref.head_logits(arch, top, x[:, :-1], q)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

        return jax.value_and_grad(mean_nll)(lora)

    opt = ref_train.AdamW(wl["lr"], weight_decay=0.0, clip_norm=wl["clip_norm"])
    lora, losses, g1 = lora0, [], None
    for k in range(wl["reference_steps"]):
        loss, grads = loss_and_grads(lora, jnp.asarray(token_batches[k]))
        losses.append(float(loss))
        lora, clipped = opt.update(lora, grads)
        if k == 0:
            g1 = compare.layer_norms(compare.host(clipped))
    delta = jax.tree.map(lambda a, b: a - b, lora, lora0)
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": compare.layer_norms(compare.host(delta))}
