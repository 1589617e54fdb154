"""AOT collective audit: the compiled step moves what the docs say it moves.

The multi-chip hot path is a handful of collectives — FSDP weight
all-gathers, gradient reduce-scatters/all-reduces, TP activation
reductions — and the expensive regression is a NEW one nobody meant to
add: a partition-rule edit or an optimizer change that makes XLA
all-gather full parameters inside the step body turns into a silent
bandwidth tax that profiles as "slow", never as an error.  EQuARX-style
collective quantization (ROADMAP item 3) is about to make this set
load-bearing, so it gets the metric-catalog treatment (PR 13): the
compiled HLO's collective set is diffed BOTH WAYS against a
machine-checked **Collective catalog** in ``docs/performance.md`` — an
undocumented collective or a documented-but-vanished one turns the
``collective-conformance`` lint rule red.

Mechanics mirror ``train/aot.py``: each topology audits in a fresh
subprocess whose CPU backend fakes the device count
(``--xla_force_host_platform_device_count``), AOT-lowering the jitted
train step and the serve engine's decode step over the tiny preset with
the real rule-table shardings — zero parameter-sized buffers are
allocated for the train leg, and the whole thing runs on a laptop-class
CPU box.  ``diff_catalog`` is a PURE function of (observed sets, catalog
text), so the catalog-mutation tests re-diff without re-compiling.
"""

from __future__ import annotations

import json
import re
from pathlib import Path
from typing import Any

__all__ = [
    "TOPOLOGIES",
    "audit_topology",
    "run_audit_subprocess",
    "full_audit",
    "parse_catalog",
    "diff_catalog",
]

#: the simulated topologies the conformance gate audits: the three mesh
#: shapes whose collective signatures differ in kind (pure data-parallel,
#: FSDP weight gathering, and a dp×tp hybrid adding TP activation
#: reductions).  Tiny preset, so the subprocess compiles in seconds.
TOPOLOGIES: dict[str, dict[str, Any]] = {
    "dp2": dict(mesh=dict(dp=2), n_devices=2),
    "fsdp2": dict(mesh=dict(fsdp=2), n_devices=2),
    "dp2tp2": dict(mesh=dict(dp=2, tp=2), n_devices=4),
}

#: the audited steps per topology
STEPS = ("train", "serve")

_CATALOG_HEADING = re.compile(r"^(#+)\s.*collective catalog", re.IGNORECASE)


def audit_topology(name: str) -> dict[str, Any]:
    """Lower + compile the train step and serve decode step on the named
    simulated topology; return ``{"name", "train": [...], "serve": [...]}``
    with each step's sorted compiled-collective set.  Must run in a process
    whose backend has at least ``n_devices`` (virtual CPU) devices."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import PRESETS, LlamaForCausalLM
    from ..models.lora import LoRAConfig
    from ..parallel.mesh import MeshSpec
    from ..parallel.sharding import LLAMA_RULES, sharding_for_tree
    from ..train.aot import _COLLECTIVE_RE
    from ..train.trainer import TrainConfig, Trainer

    spec = TOPOLOGIES[name]
    devices = jax.devices()[: spec["n_devices"]]
    if len(devices) < spec["n_devices"]:
        raise RuntimeError(
            f"{name} needs {spec['n_devices']} devices, backend has "
            f"{len(devices)} — set xla_force_host_platform_device_count "
            "before JAX init"
        )
    mesh = MeshSpec(**spec["mesh"]).build(devices)

    # ---- train leg: the aot.py abstract recipe on the tiny preset ----------
    model_cfg = PRESETS["tiny-test"].replace(lora=LoRAConfig(rank=4))
    train_cfg = TrainConfig(
        mode="lora", batch_size=4, seq_len=32, total_steps=10
    )
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)
    state_shapes = jax.eval_shape(trainer.raw_init, jax.random.PRNGKey(0))
    abstract_state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shapes, trainer.state_shardings,
    )
    b, s = train_cfg.batch_size, train_cfg.seq_len
    abstract_batch = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((b, s), jnp.float32),
    }
    step = trainer._get_step_jit(abstract_batch)
    train_hlo = step.lower(abstract_state, abstract_batch).compile().as_text()

    # ---- serve leg: the engine's REAL decode jit, weights rule-sharded ----
    from ..serve.engine import BatchEngine, EngineConfig

    serve_model = LlamaForCausalLM(PRESETS["tiny-test"])
    variables = serve_model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.zeros((1, 4), jnp.int32)
    )
    variables = jax.tree.map(
        jax.device_put, variables,
        sharding_for_tree(variables, mesh, LLAMA_RULES),
    )
    engine = BatchEngine(
        serve_model, variables,
        EngineConfig(slots=2, prompt_buckets=(16,), max_new_tokens=16),
    )
    slots = engine.config.slots
    decode_args = (
        engine.variables, engine._tenants_arg(), engine._cache,
        jnp.zeros((slots, 1), jnp.int32), jnp.zeros((slots, 1), jnp.int32),
        jnp.zeros((slots,), jnp.float32), jnp.zeros((slots,), jnp.int32),
        jnp.asarray(engine._rng_keys),
        engine._page_table_arg(), engine._adapter_ids_arg(),
    )
    serve_hlo = engine._decode.lower(*decode_args).compile().as_text()

    return {
        "name": name,
        "train": sorted(set(_COLLECTIVE_RE.findall(train_hlo))),
        "serve": sorted(set(_COLLECTIVE_RE.findall(serve_hlo))),
    }


def run_audit_subprocess(name: str, timeout: float = 300.0) -> dict[str, Any]:
    """Audit one topology in a fresh subprocess owning its virtual device
    count (the XLA flag must precede backend init — the same constraint as
    ``train/aot.py::run_report_subprocess``)."""
    import os
    import subprocess
    import sys

    spec = TOPOLOGIES[name]
    env = dict(os.environ)
    kept = " ".join(
        p for p in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in p
    )
    env["XLA_FLAGS"] = (
        f"{kept} --xla_force_host_platform_device_count={spec['n_devices']}"
    ).strip()
    out = subprocess.run(
        [sys.executable, "-m",
         "finetune_controller_tpu.analysis.collective_audit", name],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"collective audit {name} failed:\n" + out.stderr[-2000:]
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def full_audit() -> dict[str, dict[str, list[str]]]:
    """Audit every topology (one subprocess each); returns
    ``{topology: {"train": [...], "serve": [...]}}``."""
    out: dict[str, dict[str, list[str]]] = {}
    for name in TOPOLOGIES:
        report = run_audit_subprocess(name)
        out[name] = {step: report[step] for step in STEPS}
    return out


# ---- the documented catalog ------------------------------------------------


def parse_catalog(text: str) -> tuple[dict[tuple[str, str], set[str]], int]:
    """Parse the ``## Collective catalog`` section of docs/performance.md:
    table rows ``| topology | step | op, op |`` scoped to the heading (the
    metric-catalog convention — the section ends at the next heading of the
    same or higher level).  Returns ``((topology, step) -> ops, heading
    line number)``; an absent heading returns ``({}, 0)`` (catalog opt-out,
    mirroring the metric rule)."""
    rows: dict[tuple[str, str], set[str]] = {}
    lines = text.splitlines()
    start = level = None
    for i, line in enumerate(lines):
        m = _CATALOG_HEADING.match(line)
        if m:
            start, level = i, len(m.group(1))
            break
    if start is None:
        return {}, 0
    for line in lines[start + 1:]:
        hm = re.match(r"^(#+)\s", line)
        if hm and len(hm.group(1)) <= level:
            break
        cells = [c.strip() for c in line.strip().strip("|").split("|")]
        if len(cells) < 3 or cells[0] in ("topology", "") \
                or set(cells[0]) <= {"-", " ", ":"}:
            continue
        topo, step, ops = cells[0], cells[1], cells[2]
        rows[(topo, step)] = {
            op.strip().strip("`") for op in ops.split(",")
            if op.strip().strip("`") not in ("", "none")
        }
    return rows, start + 1


def diff_catalog(
    observed: dict[str, dict[str, list[str]]],
    catalog: dict[tuple[str, str], set[str]],
) -> list[str]:
    """Both-direction diff of the audited collective sets against the
    documented catalog; returns human-readable drift messages (empty =
    conformant).  Pure — the mutation tests re-diff edited catalog text
    against one recorded audit without re-compiling anything."""
    out: list[str] = []
    for topo, steps in sorted(observed.items()):
        for step in STEPS:
            seen = set(steps[step])
            documented = catalog.get((topo, step))
            if documented is None:
                out.append(
                    f"collective set for {topo}/{step} "
                    f"({', '.join(sorted(seen)) or 'none'}) has no Collective "
                    "catalog row in docs/performance.md"
                )
                continue
            for op in sorted(seen - documented):
                out.append(
                    f"compiled {topo}/{step} step contains {op!r} but the "
                    "Collective catalog does not document it — an unexpected "
                    "collective in the step body is a silent bandwidth tax; "
                    "document it or fix the sharding that introduced it"
                )
            for op in sorted(documented - seen):
                out.append(
                    f"Collective catalog documents {op!r} for {topo}/{step} "
                    "but the compiled step no longer contains it — drop the "
                    "row or restore the collective"
                )
    for topo, step in sorted(catalog):
        if topo not in observed:
            out.append(
                f"Collective catalog documents topology {topo!r} but the "
                "audit does not simulate it (analysis/collective_audit.py "
                "TOPOLOGIES)"
            )
    return out


def catalog_path() -> Path:
    """docs/performance.md relative to the repo root (best-effort)."""
    return Path(__file__).resolve().parents[2] / "docs" / "performance.md"


def main() -> None:
    import os
    import sys

    import jax

    # same contract as train/aot.py: virtual CPU devices, platform forced
    # before backend init
    jax.config.update("jax_platforms", os.environ.get("AOT_PLATFORM", "cpu"))
    print(json.dumps(audit_topology(sys.argv[1])))


if __name__ == "__main__":
    main()
