"""Worker payload builders: how a worker process reconstructs its serving
model WITHOUT the parent shipping weights over the wire.

A worker spec names a builder (``"deploy_dir"``, ``"tiny_test"``, or a fully
qualified ``"package.module:callable"``) plus kwargs; the builder returns
``(model, variables)`` ready for :class:`~finetune_controller_tpu.serve.
engine.BatchEngine`.  The two built-ins cover the real path and the test
path:

* ``deploy_dir`` — rebuild from a staged promoted prefix exactly as the
  in-process loader does (``serve/loader.py::load_serving_model``), so a
  process-mode fleet and an in-process fleet decode bit-identically from the
  same artifacts;
* ``tiny_test`` — the deterministic tiny preset (same seed ⇒ same weights in
  every process), which is what makes the cross-process bit-identity proofs
  in ``tests/test_transport.py`` possible without staging checkpoints.

Builders run INSIDE the worker process (its own JAX runtime); everything
here imports jax lazily so the spec-parsing half stays import-light.
"""

from __future__ import annotations

import importlib
from typing import Any, Callable


def tiny_test(preset: str = "tiny-test", seed: int = 0,
              lora_rank: int = 0) -> tuple[Any, dict]:
    """Deterministic tiny model (tests): same ``seed`` ⇒
    bit-identical weights in every process on the same backend."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import PRESETS, LlamaForCausalLM
    from ..models.lora import LoRAConfig

    cfg = PRESETS[preset]
    if lora_rank:
        cfg = cfg.replace(lora=LoRAConfig(rank=lora_rank))
    model = LlamaForCausalLM(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(seed)}, jnp.zeros((1, 4), jnp.int32)
    )
    return model, variables


def deploy_dir(dir: str, merge_lora: bool = True,
               multi_tenant: bool = False) -> tuple[Any, dict]:
    """Rebuild serving weights from a staged promoted prefix (the parent's
    ``serve/loader.py::fetch_promoted`` output, shared read-only by every
    worker of the fleet).  ``multi_tenant`` strips the job's own LoRA into
    nothing here — the PARENT registry owns the self-adapter and installs it
    through the stack-sync RPC like any other tenant."""
    from ..serve.loader import load_serving_model, strip_lora_for_multitenant

    model, variables, _meta = load_serving_model(
        dir, merge_lora=merge_lora and not multi_tenant
    )
    if multi_tenant:
        model, variables, _tree, _alpha, _rank = \
            strip_lora_for_multitenant(model, variables)
    return model, variables


def rollout_base(dir: str) -> tuple[Any, dict]:
    """Reconstruct the rlhf learner's FROZEN BASE for a remote rollout actor.

    The learner's checkpoints only hold the trainable adapter; the base the
    actor must decode with is written once by ``prefs/rollout_plane.py::
    write_rollout_base`` into ``<artifacts>/rollout_base/`` (model spec JSON
    + flax-msgpack params) — adapter deltas then arrive over the
    ``rollout_policy_version`` RPC, so base weights never ride the wire and
    the actor's step-0 policy is bit-identical to the learner's."""
    import json
    import os

    from flax import serialization

    from ..models.llama import LlamaForCausalLM
    from ..train.cli import build_model_config

    base = os.path.join(dir, "rollout_base")
    with open(os.path.join(base, "model.json")) as f:
        model_spec = json.load(f)
    cfg = build_model_config({"model": model_spec})
    if cfg.image_size:  # pragma: no cover - MM rlhf unsupported
        raise ValueError("rollout_base only supports text-only policies")
    model = LlamaForCausalLM(cfg)
    with open(os.path.join(base, "params.msgpack"), "rb") as f:
        params = serialization.msgpack_restore(f.read())
    return model, {"params": params}


_BUILTINS: dict[str, Callable[..., tuple[Any, dict]]] = {
    "tiny_test": tiny_test,
    "deploy_dir": deploy_dir,
    "rollout_base": rollout_base,
}


def resolve_builder(name: str) -> Callable[..., tuple[Any, dict]]:
    """Builder lookup: a built-in name or ``module:attr``.  Dotted paths are
    how tests and future consumers (rollout actors, pipeline stages) plug in
    payloads; the spec file is written by this process's own transport layer,
    so this is configuration, not an untrusted-input surface."""
    if name in _BUILTINS:
        return _BUILTINS[name]
    if ":" not in name:
        raise ValueError(
            f"unknown payload builder {name!r} "
            f"(built-ins: {sorted(_BUILTINS)}; or use 'module:callable')"
        )
    mod_name, _, attr = name.partition(":")
    mod = importlib.import_module(mod_name)
    fn = getattr(mod, attr, None)
    if not callable(fn):
        raise ValueError(f"payload builder {name!r} is not callable")
    return fn
