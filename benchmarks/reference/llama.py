"""The plain reference of a Llama-family training cell, found by name
(``"reference": "llama"``, the default): the first steps followed from the
seed with ``reference/model.py`` and ``reference/train.py``."""

from __future__ import annotations

from benchmarks.harness import compare, weights
from benchmarks.reference import model as ref_model, train as ref_train


def reference_numbers(conf, wl, seed, token_batches, *, q=ref_model.identity,
                      precision="highest", steps=None, n_layers=None,
                      devices=None):
    """Follow the first steps with the plain reference: per-step loss, the
    first clipped gradient's per-layer norms, the adapters' change."""
    import jax

    arch = ref_model.Arch.from_config(conf, n_layers)
    key = weights.root_key(seed)
    lora0 = ref_model.init_lora(arch, key)
    fn = ref_train.make_loss_and_grads(
        arch, q, precision, rows_per_block=wl.get("reference_rows", 2))
    opt = ref_train.AdamW(wl["lr"], weight_decay=0.0, clip_norm=wl["clip_norm"])
    lora, losses, g1 = lora0, [], None
    for k in range(steps or wl["reference_steps"]):
        loss, grads = fn(key, lora, token_batches[k], devices)
        losses.append(float(loss))
        lora, clipped = opt.update(lora, grads)
        if k == 0:
            g1 = compare.layer_norms(compare.host(clipped))
    delta = jax.tree.map(lambda a, b: a - b, lora, lora0)
    return {"losses": losses, "grad_norms": g1,
            "delta_norms": compare.layer_norms(compare.host(delta))}
