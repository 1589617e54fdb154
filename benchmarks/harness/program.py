"""Where the benchmark touches the program's constructors: this file fills
the program's variable trees with the benchmark's seeded weights
(``weights.py``) in one jitted call, and ``programs/<name>.py`` (found by the
name in a configuration's ``run``, ``manifest.py``) builds the program's
model configuration from the file's PUBLISHED keys.  Everything else under
``benchmarks/`` is the program's caller, not its user: drivers call
``Trainer.step`` / ``Batcher.submit`` and read results, spans and counters.
"""

from __future__ import annotations

from typing import Any

from . import weights

#: path components of the program's trees that are not part of a leaf's
#: canonical name: collection names and the scan wrapper
_DROP = {"params", "lora", "block", "frozen", "trainable"}


def canonical(path) -> str:
    parts = [getattr(k, "key", getattr(k, "name", None)) or str(k)
             for k in path]
    return "/".join(p for p in parts if p not in _DROP)


def fill(shapes: Any, key, quant_block: int):
    """A tree like ``shapes`` (of ShapeDtypeStruct) holding the weights of
    ``key`` (``weights.root_key(seed)``).  Trace this inside a jit, with the
    key an ARGUMENT of it: nothing is made on the host, and one compiled
    program serves every seed (a seed closed over is a constant of the
    program, which then compiles anew, 11 s, for every new seed)."""
    import jax

    def one(path, s):
        name = canonical(path)
        return weights.leaf(key, name, s.shape, s.dtype,
                            stacked=weights.is_stacked(name),
                            quant_block=quant_block)

    return jax.tree_util.tree_map_with_path(one, shapes)


def seeded_train_state(trainer, seed: int):
    """The trainer's state with the benchmark's weights: frozen base and
    adapters from ``seed``, step 0, a fresh optimizer state — one jitted call
    with the trainer's own shardings (the program's ``init_state`` is not
    used: its weights are its own, drawn leaf by leaf)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(trainer._raw_init, jax.random.PRNGKey(0))
    qb = trainer.model_cfg.quant_block

    def make(key):
        frozen = fill(shapes.frozen, key, qb)
        trainable = fill(shapes.trainable, key, qb)
        return shapes.__class__(
            step=jnp.zeros((), jnp.int32), frozen=frozen,
            trainable=trainable, opt_state=trainer.tx.init(trainable),
        )

    with trainer.mesh:
        return jax.jit(make, out_shardings=trainer._state_shardings)(
            weights.root_key(seed))


def seeded_serving_variables(model, seed: int):
    """``{"params": ..., "lora": ...}`` for serving, on the default device,
    float leaves in the compute type (what ``serve/loader.py`` leaves on the
    device under ``quantize_base``: int4 base, adapter unmerged)."""
    import jax
    import jax.numpy as jnp

    cfg = model.cfg
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32)))
    shapes = {k: shapes[k] for k in ("params", "lora") if k in shapes}

    def served(path, s):
        # the frozen base in the job's frozen type, the adapter in float32:
        # what Trainer._assemble(state.frozen, host["trainable"]) hands over
        if getattr(path[0], "key", None) == "params" and s.dtype == jnp.float32:
            return jax.ShapeDtypeStruct(s.shape, cfg.dtype)
        return s

    shapes = jax.tree_util.tree_map_with_path(served, shapes)
    return jax.jit(lambda key: fill(shapes, key, cfg.quant_block))(
        weights.root_key(seed))
