"""Compile a training cell's step for a DESCRIBED v5e, without the chip, and
print what the chip's compiler says of its memory — or its refusal.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/compile_step.py \\
        --workload <cell> [--batch N] [--seq N] [--remat POLICY]

The trainer is the one ``drivers/train.py`` builds for the cell, on the
described topology's devices; the step is lowered on shapes carrying the
trainer's own shardings.  ``jax.default_backend`` is made to answer ``tpu``
so the program takes its TPU branches (the flash kernels, the donated
state).  Nothing runs: a compile that passes is not a chip run.  Not part of
a benchmark run.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
import types
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.harness.manifest import Manifest  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest", default=None)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--seq", type=int)
    ap.add_argument("--remat")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmarks.harness.drivers import train
    from finetune_controller_tpu.parallel.ring import ring_mesh

    manifest = Manifest(args.manifest)
    entry = manifest.workloads[args.workload]
    wl = manifest.workload(args.workload)
    conf = manifest.config(entry["config"])
    wl.update({k: v for k, v in (("batch", args.batch), ("seq", args.seq)) if v})
    if args.remat:
        conf["run"]["remat_policy"] = args.remat
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    run = types.SimpleNamespace(manifest=manifest, conf=conf, workload=wl,
                                chips=entry["chips"])
    trainer = train.build_trainer(run, devices=topo.devices[:entry["chips"]])
    state = jax.eval_shape(trainer.raw_init, jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        state, trainer.state_shardings)
    shape = (wl["batch"], wl["seq"])
    batch = {k: jax.ShapeDtypeStruct(
        shape, dtype, sharding=trainer._batch_leaf_sharding(
            jax.ShapeDtypeStruct(shape, dtype)))
        for k, dtype in (("tokens", jnp.int32), ("loss_mask", jnp.float32))}
    t = time.perf_counter()
    with trainer.mesh, ring_mesh(trainer.mesh):
        compiled = trainer._get_step_jit(batch).lower(state, batch).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    print(f"{args.workload} batch {shape[0]} x {shape[1]} remat "
          f"{conf['run']['remat_policy']}: compiled in "
          f"{time.perf_counter() - t:.1f} s; arguments "
          f"{ma.argument_size_in_bytes} + outputs {ma.output_size_in_bytes} - "
          f"aliased {ma.alias_size_in_bytes} + temporaries "
          f"{ma.temp_size_in_bytes} = {total} B per chip")


if __name__ == "__main__":
    main()
