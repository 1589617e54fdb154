"""Collective-bandwidth diagnostics — the ``nccl-tests`` workflow, TPU-native.

The reference's distributed backend is NCCL inside the user image; when a
cluster is slow, operators reach for nccl-tests' all-reduce bus-bandwidth
sweep.  The rebuild's collectives are XLA programs over ICI/DCN, so its
diagnostic is one too: jitted ``psum`` / ``all_gather`` / ``ppermute``
sweeps over the live device mesh, reporting per-size timings and achieved
algorithmic/bus bandwidth.  An operator runs it inside a worker pod (or any
host with chips) to validate a slice before blaming the training loop:

    python -m finetune_controller_tpu.parallel.diagnostics [--sizes-mb 1,16,128]

Bus-bandwidth accounting follows the nccl-tests conventions, with ``S`` =
the per-device shard: all-reduce moves ``2·S·(n-1)/n`` per device,
all-gather receives ``S·(n-1)``, a ppermute ring step moves ``S``.

Single-device meshes degrade gracefully (no inter-chip traffic — reported
as such) so the same command works on a dev box; the CPU test mesh
exercises the full sweep in CI.
"""

from __future__ import annotations

import json
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _timed_chain(fn, x, *, warmup: int = 2, iters: int = 5) -> float:
    """Per-call seconds with a data-dependency chain + host fetch.

    Independent repeated calls through an async or caching
    remote-TPU runtime can appear nearly free even under
    ``block_until_ready`` — and this tool's whole job is telling an operator
    the truth about a slice. Every collective here maps a sharded array to a
    same-shape sharded array, so the output feeds the next call directly.
    """
    for _ in range(warmup):
        x = fn(x)
    float(jnp.sum(x[:1].astype(jnp.float32)))  # host sync
    t0 = time.perf_counter()
    for _ in range(iters):
        x = fn(x)
    float(jnp.sum(x[:1].astype(jnp.float32)))
    return (time.perf_counter() - t0) / iters


def collective_diagnostics(
    sizes_mb: Sequence[float] = (1, 16, 64),
    devices: Sequence[Any] | None = None,
) -> dict[str, Any]:
    """Sweep the three collective shapes training traffic is made of.

    ``psum`` (gradient reduction), ``all_gather`` (FSDP parameter gather),
    ``ppermute`` ring step (ring attention / pipeline transfers).
    """
    from jax import shard_map

    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    spec = NamedSharding(mesh, P("x"))
    report: dict[str, Any] = {
        "n_devices": n,
        "device_kind": devs[0].device_kind,
        "platform": devs[0].platform,
        "collectives": {},
    }
    if n == 1:
        report["note"] = "single device: no inter-chip traffic to measure"
        return report

    # Every body maps a per-device (elems,) block to a per-device (elems,)
    # block (out_specs=P("x"), same global shape), so calls CHAIN — the
    # output feeds the next call, defeating async-runtime overlap.
    def make(op):
        if op == "psum":
            # each device contributes S and receives the sum: ring
            # all-reduce moves 2*S*(n-1)/n per device
            body = lambda x: jax.lax.psum(x, "x")
            bus_factor = 2 * (n - 1) / n
        elif op == "all_gather":
            # each device receives the other n-1 shards and keeps its own:
            # the gathered row-0 keeps the chain shape
            body = lambda x: jax.lax.all_gather(x, "x")[0]
            bus_factor = n - 1.0
        else:  # ppermute ring step: S per device over one link hop
            perm = [(i, (i + 1) % n) for i in range(n)]
            body = lambda x: jax.lax.ppermute(x, "x", perm)
            bus_factor = 1.0
        # ftc: ignore[recompile-fresh-callable] -- compiled once per collective op (3 total) per diagnostics invocation; not a hot path
        fn = jax.jit(
            shard_map(
                body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                # psum/gather-row-0 outputs are replicated-by-construction;
                # the static replication checker can't always infer that
                check_vma=False,
            )
        )
        return fn, bus_factor

    for op in ("psum", "all_gather", "ppermute"):
        fn, bus_factor = make(op)
        rows = {}
        for size_mb in sizes_mb:
            # per-DEVICE payload S: size_mb of f32, rounded up to whole
            # lanes; the global (elems*n,) array is created ALREADY sharded —
            # materializing it on one device first would OOM the very slices
            # this tool targets (128 MB x 256 chips = 32 GB on device 0)
            elems = max(8, int(size_mb * (1 << 20) // 4))
            # ftc: ignore[recompile-jit-in-loop] -- a fresh trivial fill compile per payload size is the only way to create the array ALREADY sharded; cost is noise next to the measured collective
            x = jax.jit(
                lambda: jnp.ones((elems * n,), jnp.float32),
                out_shardings=spec,
            )()
            sec = _timed_chain(fn, x)
            payload = elems * 4  # bytes contributed per device
            if op == "all_gather":
                algo = payload * n / sec  # bytes gathered per device
            else:
                algo = payload / sec
            rows[f"{size_mb:g}"] = {
                "time_ms": round(sec * 1e3, 3),
                "algo_bw_gbps": round(algo / 1e9, 3),
                "bus_bw_gbps": round(payload * bus_factor / sec / 1e9, 3),
            }
        report["collectives"][op] = rows
    return report


def main() -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser(prog="ftc-collective-diagnostics")
    ap.add_argument("--sizes-mb", default="1,16,64")
    ap.add_argument(
        "--platform", default=os.environ.get("JAX_PLATFORMS", ""),
        help="force a JAX platform (e.g. cpu for the virtual test mesh)",
    )
    args = ap.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from .distributed import maybe_initialize_distributed

    maybe_initialize_distributed()
    sizes = [float(s) for s in args.sizes_mb.split(",") if s]
    print(json.dumps(collective_diagnostics(sizes)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
