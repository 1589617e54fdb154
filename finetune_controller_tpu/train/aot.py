"""Ahead-of-time validation of configs too big to execute on available chips.

BASELINE configs #2 (Llama-3-8B LoRA FSDP on a v5e-16 slice) and #4
(Mixtral-8x7B MoE LoRA on v5p-64) cannot run on one chip.  What CAN be proven
without the hardware: ``jax.jit(...).lower()`` over ``ShapeDtypeStruct``
inputs traces and SPMD-partitions the FULL-SIZE training step on an
n-virtual-device mesh without allocating a single parameter buffer, and
``.compile()`` runs the whole XLA pipeline on it.  From the artifacts we
check:

* the parameter sharding specs the partitioner was given (FSDP sharding on
  every weight; expert-parallel sharding on MoE expert kernels),
* the cross-device collectives present in the compiled HLO (all-gather for
  FSDP parameter gathering, reduce-scatter/all-reduce for gradient
  reduction, all-to-all/ragged variants for MoE dispatch),
* arithmetic per-device bytes of the resident train state (params + optimizer
  + master copies, each leaf divided by its sharded mesh axes) against the
  target chip's HBM.

The reference has no analogue — its training plane is a user container it
never inspects; this is the TPU-native replacement for "trust me, it fits".

Driver integration: ``__graft_entry__.dryrun_multichip`` runs these reports
in subprocesses (the virtual device count must be fixed before JAX backend
init); ``tests/test_aot_realscale.py`` asserts on the reports in CI.
"""

from __future__ import annotations

import json
import logging
import math
import re
from typing import Any

#: chip HBM capacities (GiB, usable ~ spec minus runtime reserve)
_HBM_GIB = {"v5e": 16.0, "v5p": 95.0}

#: the BASELINE configs that need >1 chip, at their REAL shapes.
#: ``num_slices > 1`` marks a multi-slice (DCN) leg: devices are grouped into
#: contiguous virtual slices (mirroring real multi-slice enumeration order),
#: dp runs across slices, and the report classifies every compiled collective
#: as intra-slice (ICI) or cross-slice (DCN).
REALSCALE: dict[str, dict[str, Any]] = {
    "llama3-8b-fsdp16": dict(
        preset="llama3-8b", mesh=dict(fsdp=16), n_devices=16,
        batch=16, seq=2048, chip="v5e",
    ),
    "mixtral-8x7b-ep8-fsdp8": dict(
        preset="mixtral-8x7b", mesh=dict(fsdp=8, ep=8), n_devices=64,
        batch=64, seq=2048, chip="v5p",
    ),
    # 2 × v5e-16 slices over DCN: dp across slices, FSDP inside each slice —
    # the standard multi-slice recipe (SURVEY §2.3; reference seam:
    # PyTorchJobDeployer.py:186-249 replica fan-out, which never saw a mesh)
    "llama3-8b-dcn2x16": dict(
        preset="llama3-8b", mesh=dict(dp=2, fsdp=16), n_devices=32,
        batch=32, seq=2048, chip="v5e", num_slices=2,
    ),
    # real-shape PIPELINE leg (round 5): the 8B model's layer stack split
    # into 2 GPipe stages × dp4 on v5p (pp composes with dp; the frozen base
    # is replicated over dp, so the roomier chip hosts this layout). The
    # report carries the schedule's analytic bubble fraction.
    "llama3-8b-dp4-pp2": dict(
        preset="llama3-8b", mesh=dict(dp=4, pp=2), n_devices=8,
        batch=32, seq=2048, chip="v5p",
    ),
}

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|ragged-all-to-all)\b"
)

# `%x = ... all-gather(...), ..., replica_groups={{0,1},{2,3}}, ...` or the
# iota form `replica_groups=[2,16]<=[32]` / `[16,2]<=[2,16]T(1,0)`
_GROUPED_OP_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|ragged-all-to-all)"
    r"(?:-start)?\([^\n]*?replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}"
    r"|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)"
)


def _parse_groups(text: str) -> list[list[int]]:
    """Materialise a replica_groups literal (explicit or iota form)."""
    import numpy as np

    if text.startswith("{{"):
        return [
            [int(x) for x in grp.split(",") if x.strip() != ""]
            for grp in text[2:-2].split("},{")
        ]
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text)
    out_shape = [int(x) for x in m.group(1).split(",")]
    src_shape = [int(x) for x in m.group(2).split(",")]
    arr = np.arange(math.prod(src_shape)).reshape(src_shape)
    if m.group(3):
        arr = arr.transpose([int(x) for x in m.group(3).split(",")])
    return arr.reshape(out_shape).tolist()


def classify_collectives(hlo: str, per_slice: int) -> dict[str, dict[str, int]]:
    """Count compiled collectives by op kind × (intra|cross)-slice.

    A collective whose every replica group stays inside one ``per_slice``
    block of contiguous device ids rides ICI; a group spanning blocks rides
    DCN.  This is the mechanically-checkable form of "fsdp inside the slice,
    only the dp gradient reduction crosses DCN".
    """
    counts: dict[str, dict[str, int]] = {}
    for m in _GROUPED_OP_RE.finditer(hlo):
        op, groups_text = m.group(1), m.group(2)
        groups = _parse_groups(groups_text)
        intra = all(
            len({dev // per_slice for dev in grp}) <= 1 for grp in groups
        )
        bucket = counts.setdefault(op, {"intra_slice": 0, "cross_slice": 0})
        bucket["intra_slice" if intra else "cross_slice"] += 1
    return counts


def _sharded_bytes(shape, dtype, spec, mesh_shape: dict[str, int]) -> float:
    """Bytes per device for one leaf: total bytes over the product of mesh
    axis sizes its PartitionSpec shards over."""
    total = math.prod(shape) * dtype.itemsize if shape else dtype.itemsize
    denom = 1
    for entry in (spec or ()):
        if entry is None:
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        for ax in axes:
            denom *= mesh_shape.get(ax, 1)
    return total / denom


def aot_report(name: str) -> dict[str, Any]:
    """Lower + compile the named REALSCALE config abstractly; return the
    evidence dict.  Must run in a process whose JAX backend has at least
    ``n_devices`` devices (virtual CPU devices are fine — use
    ``--xla_force_host_platform_device_count``)."""
    import jax
    import jax.numpy as jnp

    from ..models.llama import PRESETS
    from ..models.lora import LoRAConfig
    from ..parallel.mesh import MeshSpec
    from .trainer import TrainConfig, Trainer

    spec = REALSCALE[name]
    devices = jax.devices()[: spec["n_devices"]]
    if len(devices) < spec["n_devices"]:
        raise RuntimeError(
            f"{name} needs {spec['n_devices']} devices, backend has "
            f"{len(devices)} — set xla_force_host_platform_device_count "
            "before JAX init"
        )
    num_slices = spec.get("num_slices", 1)
    slice_of = None
    if num_slices > 1:
        per_slice = spec["n_devices"] // num_slices
        slice_of = [i // per_slice for i in range(spec["n_devices"])]
    mesh = MeshSpec(**spec["mesh"]).build(devices, slice_of=slice_of)
    model_cfg = PRESETS[spec["preset"]].replace(lora=LoRAConfig(rank=16))
    train_cfg = TrainConfig(
        mode="lora", batch_size=spec["batch"], seq_len=spec["seq"],
        total_steps=10,
    )
    trainer = Trainer(model_cfg, train_cfg, mesh=mesh)

    # abstract state: shapes from eval_shape, shardings from the rule engine —
    # zero parameter memory is allocated anywhere in this function
    state_shapes = jax.eval_shape(trainer.raw_init, jax.random.PRNGKey(0))
    abstract_state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state_shapes, trainer.state_shardings,
    )
    b, s = spec["batch"], spec["seq"]
    abstract_batch = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((b, s), jnp.float32),
    }
    step = trainer._get_step_jit(abstract_batch)
    compiled = step.lower(abstract_state, abstract_batch).compile()
    hlo = compiled.as_text()
    collectives = sorted(set(_COLLECTIVE_RE.findall(hlo)))
    dcn_split = None
    if num_slices > 1:
        dcn_split = classify_collectives(hlo, spec["n_devices"] // num_slices)

    # param sharding evidence: flatten specs with paths
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    leaves = jax.tree_util.tree_leaves_with_path(trainer.state_shardings)
    spec_samples: dict[str, str] = {}
    state_bytes = 0.0
    shape_leaves = {
        jax.tree_util.keystr(p): l
        for p, l in jax.tree_util.tree_leaves_with_path(state_shapes)
    }
    fsdp_sharded = unsharded_big = 0
    ep_sharded = pp_sharded = 0
    for path, sharding in leaves:
        key = jax.tree_util.keystr(path)
        shp = shape_leaves[key]
        pspec = sharding.spec
        state_bytes += _sharded_bytes(shp.shape, shp.dtype, pspec, mesh_shape)
        flat_axes = [
            ax
            for entry in pspec if entry is not None
            for ax in (entry if isinstance(entry, (tuple, list)) else (entry,))
        ]
        if "fsdp" in flat_axes:
            fsdp_sharded += 1
        elif "pp" in flat_axes:
            # stage-sharded on the leading layer axis — sharded, just not
            # by fsdp; must not be reported as an unsharded giant
            pp_sharded += 1
        elif math.prod(shp.shape or (1,)) * shp.dtype.itemsize > 4 << 20:
            unsharded_big += 1
            spec_samples.setdefault(f"UNSHARDED {key}", str(pspec))
        if "ep" in flat_axes:
            ep_sharded += 1
        if "kernel" in key and len(spec_samples) < 12:
            spec_samples.setdefault(key, str(pspec))

    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            }
    except Exception:
        # memory_analysis is best-effort (backend-dependent API surface);
        # the report ships without it rather than failing the compile check
        logging.getLogger(__name__).debug(
            "compiled.memory_analysis() unavailable", exc_info=True
        )

    pp = mesh_shape.get("pp", 1)
    pp_schedule = None
    if pp > 1:
        from ..parallel.pipeline import (
            bubble_fraction,
            default_pp_microbatches,
        )

        local = b // (mesh_shape.get("dp", 1) * mesh_shape.get("fsdp", 1))
        n_micro = default_pp_microbatches(local, pp)
        pp_schedule = {
            "n_micro": n_micro,
            "bubble_fraction": round(bubble_fraction(n_micro, pp), 4),
        }

    hbm = _HBM_GIB[spec["chip"]] * (1 << 30)
    return {
        "name": name,
        "mesh": mesh_shape,
        "n_devices": spec["n_devices"],
        "batch": b, "seq": s,
        "param_count": model_cfg.param_count(),
        "collectives": collectives,
        "num_slices": num_slices,
        "dcn_split": dcn_split,
        "pp_schedule": pp_schedule,
        "fsdp_sharded_leaves": fsdp_sharded,
        "pp_sharded_leaves": pp_sharded,
        "ep_sharded_leaves": ep_sharded,
        "unsharded_big_leaves": unsharded_big,
        "state_bytes_per_device": int(state_bytes),
        "hbm_bytes": int(hbm),
        "state_fits_hbm": state_bytes < hbm,
        "spec_samples": spec_samples,
        "xla_memory_analysis": mem,
    }


def run_report_subprocess(name: str, timeout: float = 540.0) -> dict[str, Any]:
    """Produce the named report in a fresh subprocess that owns its virtual
    device count (the flag must be set before JAX backend init, so the
    current process — whose backend is usually already initialised — can't
    do it in-process).  Shared by ``__graft_entry__.dryrun_multichip`` and
    the CI tests."""
    import os
    import subprocess
    import sys

    spec = REALSCALE[name]
    env = dict(os.environ)
    kept = " ".join(
        p for p in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in p
    )
    env["XLA_FLAGS"] = (
        f"{kept} --xla_force_host_platform_device_count={spec['n_devices']}"
    ).strip()
    out = subprocess.run(
        [sys.executable, "-m", "finetune_controller_tpu.train.aot", name],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"AOT real-scale validation {name} failed:\n" + out.stderr[-2000:]
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    import os
    import sys

    import jax

    # The dryrun contract is virtual CPU devices whatever the environment
    # says: force the platform before backend init.
    jax.config.update("jax_platforms", os.environ.get("AOT_PLATFORM", "cpu"))
    print(json.dumps(aot_report(sys.argv[1])))


if __name__ == "__main__":
    main()
