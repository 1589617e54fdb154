"""The proof that a refactor left a cell's program alone: the hash of its step.

    JAX_PLATFORMS=cpu python3 scripts/step_hash.py <root> <cell> [nocompile] [<manifest>]

Lowers the training step of ``<cell>`` as the checkout at ``<root>`` builds it
(``benchmarks/harness/drivers/train.py::build_trainer`` on a DESCRIBED v5e, as
``benchmarks/tools/compile_step.py`` does) and prints the ``sha256`` of the
StableHLO text with every ``tpu_custom_call``'s ``backend_config`` cut out (a
Mosaic payload carries the kernel's source locations).  Two trees whose
hashes agree trace the same program cell by cell; the kernels' bodies are
outside the hash and keep their own tests.  The text follows the PATH of the
checkout, so both sides are lowered at ONE directory: copy each tree there in
turn (``git archive <commit> | tar -x -C <dir>``), one process at a time —
the TPU's library is held by one.  Without ``nocompile`` the step is also
compiled and the compiler's memory printed.  ``<manifest>`` defaults to
``<root>/BENCHMARK.json``; a test fixture's path lowers a tiny cell.

Nothing runs on a device and nothing under ``benchmarks/`` is changed.
"""

from __future__ import annotations

import hashlib
import os
import re
import sys
import time
import types

OPS = ("stablehlo.gather", "stablehlo.scatter", "stablehlo.sort",
       "chlo.top_k", "tpu_custom_call")


def lower_step(manifest, cell: str):
    """``(lowered, text)``: the cell's step lowered for the described v5e and
    its StableHLO text with the Mosaic payloads cut out."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from benchmarks.harness.drivers import train
    from finetune_controller_tpu.parallel.ring import ring_mesh

    entry = manifest.workloads[cell]
    wl = manifest.workload(cell)
    conf = manifest.config(entry["config"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    run = types.SimpleNamespace(manifest=manifest, conf=conf, workload=wl,
                                chips=entry["chips"])
    backend = jax.default_backend
    jax.default_backend = lambda: "tpu"  # the program's TPU branches
    try:
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
        with trainer.mesh, ring_mesh(trainer.mesh):
            lowered = trainer._get_step_jit(batch).lower(state, batch)
    finally:
        jax.default_backend = backend
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""',
                  lowered.as_text())
    return lowered, text


def step_hash(manifest, cell: str) -> str:
    return hashlib.sha256(lower_step(manifest, cell)[1].encode()).hexdigest()


def main(argv: list[str]) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    root, cell = os.path.abspath(argv[0]), argv[1]
    rest = argv[2:]
    compile_too = "nocompile" not in rest
    paths = [a for a in rest if a != "nocompile"]
    sys.path.insert(0, root)
    os.chdir(root)

    import jax

    from benchmarks.harness.manifest import Manifest

    jax.config.update("jax_enable_compilation_cache", False)
    lowered, text = lower_step(Manifest(paths[0] if paths else None), cell)
    ops = {name: len(re.findall(name, text)) for name in OPS}
    print(f"{cell} @ {root}: stablehlo sha256 "
          f"{hashlib.sha256(text.encode()).hexdigest()[:16]} bytes {len(text)} "
          f"ops {ops}", flush=True)
    if compile_too:
        t = time.perf_counter()
        ma = lowered.compile().memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        print(f"{cell}: compiled in {time.perf_counter() - t:.1f} s; arguments "
              f"{ma.argument_size_in_bytes} + temporaries "
              f"{ma.temp_size_in_bytes} = {total} B; live peak "
              f"{getattr(ma, 'peak_memory_in_bytes', None)}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
