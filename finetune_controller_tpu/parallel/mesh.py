"""Device-mesh construction for TPU slices.

The reference framework had no notion of a device mesh at all — its
``cluster_nodes``/``accelerator_count`` pair (reference
``app/models/base/finetuning.py:86-93``) was forwarded to Kubernetes as replica
counts and everything else happened inside the user's container.  Here the mesh
is the core abstraction: every parallelism strategy (DP, FSDP, TP, SP/CP, EP,
PP) is an axis of one logical mesh, and XLA inserts the collectives.

Axis layout convention (fastest-varying axis innermost so that TP rides ICI
neighbours within a host, FSDP next, DP outermost across slices/DCN):

    mesh shape = (dp, fsdp, ep, pp, sp, tp)
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from .. import platform

logger = logging.getLogger(__name__)


class AxisNames:
    """Canonical mesh-axis names used across the framework."""

    DATA = "dp"      # pure data parallelism (gradient all-reduce)
    FSDP = "fsdp"    # data parallelism with fully-sharded params (ZeRO-3)
    EXPERT = "ep"    # expert parallelism for MoE layers
    PIPE = "pp"      # pipeline stages
    SEQ = "sp"       # sequence/context parallelism (ring attention)
    TENSOR = "tp"    # tensor (megatron-style) parallelism

    ORDER = (DATA, FSDP, EXPERT, PIPE, SEQ, TENSOR)
    # Axes over which the batch dimension is split:
    BATCH_AXES = (DATA, FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh request; ``-1`` on at most one axis means "infer"."""

    dp: int = 1
    fsdp: int = -1
    ep: int = 1
    pp: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> dict[str, int]:
        sizes = {
            AxisNames.DATA: self.dp,
            AxisNames.FSDP: self.fsdp,
            AxisNames.EXPERT: self.ep,
            AxisNames.PIPE: self.pp,
            AxisNames.SEQ: self.sp,
            AxisNames.TENSOR: self.tp,
        }
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        known = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"cannot infer {unknown[0]}: {n_devices} devices not divisible "
                    f"by product of fixed axes {known}"
                )
            sizes[unknown[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {known} devices but {n_devices} are available"
            )
        return sizes

    def build(
        self,
        devices: Sequence[jax.Device] | None = None,
        slice_of: Sequence[int] | None = None,
    ) -> Mesh:
        return build_mesh(self, devices, slice_of=slice_of)


def order_devices_for_dcn(
    devices: Sequence,
    sizes: dict[str, int],
    slice_of: Sequence[int] | None = None,
) -> list:
    """Order devices so the mesh maps onto the ICI/DCN hierarchy.

    On a multi-slice TPU deployment each device carries a ``slice_index``;
    ICI only connects chips within a slice, traffic between slices rides
    DCN.  The mesh is reshaped row-major with ``dp`` outermost, so grouping
    devices by slice makes every dp-subdivision fall on slice boundaries
    whenever ``dp`` is a multiple of the slice count — inner axes (fsdp/ep/
    pp/sp/tp) then ride ICI and only the dp gradient all-reduce crosses DCN,
    the standard multi-slice recipe (dp-over-DCN x FSDP-over-ICI).

    Emits a warning when an inner axis is forced across a slice boundary
    (e.g. fsdp spanning two slices): still correct — XLA compiles DCN
    collectives — but bandwidth-bound.  Single-slice and CPU/test devices
    (no ``slice_index``) come back unchanged.

    ``slice_of`` overrides the per-device slice assignment — used to model a
    multi-slice topology on devices that carry no ``slice_index`` (virtual
    CPU meshes in the dryrun/AOT legs), exercising the same ordering path a
    real 2-slice deployment takes.
    """
    if slice_of is not None:
        if len(slice_of) != len(devices):
            raise ValueError(
                f"slice_of has {len(slice_of)} entries for {len(devices)} devices"
            )
        slice_of = list(slice_of)
    else:
        # None slice_index (e.g. a CPU device mixed in) becomes its own -1
        # "slice": it must neither raise a None-vs-int TypeError in the sort
        # nor be excluded from the per-slice tiling arithmetic below.
        slice_of = [
            s if (s := getattr(d, "slice_index", None)) is not None else -1
            for d in devices
        ]
    distinct = set(slice_of)
    if len(distinct) <= 1:
        return list(devices)
    ordered = [
        d for _, d in sorted(
            enumerate(devices),
            key=lambda it: (slice_of[it[0]], it[0]),  # stable within a slice
        )
    ]
    n_slices = len(distinct)
    per_slice = len(ordered) // n_slices
    inner = math.prod(v for a, v in sizes.items() if a != AxisNames.DATA)
    # clean hierarchy iff each slice holds a whole number of inner tiles
    if inner > per_slice or (per_slice and per_slice % inner):
        logger.warning(
            "mesh inner axes (%d devices) do not tile the %d-device slices: "
            "an intra-slice axis will cross DCN — consider dp=%d so only "
            "data-parallel gradient reduction leaves a slice",
            inner, per_slice, n_slices,
        )
    return ordered


def build_mesh(
    spec: MeshSpec,
    devices: Sequence[jax.Device] | None = None,
    slice_of: Sequence[int] | None = None,
) -> Mesh:
    devices = list(devices if devices is not None else platform.devices())
    fixed = [spec.dp, spec.fsdp, spec.ep, spec.pp, spec.sp, spec.tp]
    if -1 not in fixed and math.prod(fixed) < len(devices):
        # A fully-specified mesh smaller than the host's device count is
        # honoured on a prefix of the devices (e.g. a 1-chip job on a
        # multi-device test host). Slice-group FIRST so the prefix fills
        # whole slices instead of straddling DCN on an interleaved
        # enumeration ({} sizes = sort only, warnings come later).
        keep = math.prod(fixed)
        order = order_devices_for_dcn(devices, {}, slice_of=slice_of)
        if slice_of is not None:
            index_of = {id(d): i for i, d in enumerate(devices)}
            slice_of = [slice_of[index_of[id(d)]] for d in order[:keep]]
        devices = order[:keep]
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in AxisNames.ORDER)
    arr = np.asarray(
        order_devices_for_dcn(devices, sizes, slice_of=slice_of)
    ).reshape(shape)
    return Mesh(arr, AxisNames.ORDER)


def single_device_mesh(device: jax.Device | None = None) -> Mesh:
    devices = [device] if device is not None else platform.devices()[:1]
    return build_mesh(MeshSpec(fsdp=1), devices)
