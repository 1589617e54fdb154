"""TPU device catalog — flavors, topologies, quotas, and the submission-form enum.

Capability parity with the reference's worker/device configuration
(``app/core/device_config.py:16-109`` + ``example.config.json`` — SURVEY.md §2
component 12), redesigned for TPU pod-slice granularity:

- the reference's flat GPU count (``accelerators: {"nvidia.com/gpu": n}``,
  ``example.config.json:20-23``) becomes a **slice flavor**: chip generation,
  topology (e.g. ``4x4``), hosts × chips/host — because TPUs are provisioned as
  whole slices, not per-chip (SURVEY.md §7 "hard parts": slice topology ↔
  scheduler quota);
- each flavor carries its scheduler queue name + nominal chip quota (the
  Kueue ClusterQueue / ResourceFlavor data, ``crds/kueue/cluster-queue.yaml:13-22``)
  so the in-repo gang scheduler can enforce admission the way Kueue does;
- JSON config files may contain ``//`` comments, as the reference allows
  (``device_config.py:81-85``);
- a missing config file degrades to the built-in default catalog with a log
  line, mirroring the reference's empty-catalog fallback (``device_config.py:96-101``).
"""

from __future__ import annotations

import enum
import json
import logging
import re
from pathlib import Path

from pydantic import BaseModel, Field

logger = logging.getLogger(__name__)


class DeviceFlavor(BaseModel):
    """One schedulable slice shape (reference: ``Worker``, ``device_config.py:16-44``)."""

    name: str  # e.g. "v5e-16"
    description: str = ""
    generation: str = "v5e"  # v4 | v5e | v5p | v6e | cpu
    topology: str = ""  # e.g. "4x4" (empty for cpu flavors)
    hosts: int = 1
    chips_per_host: int = 4
    #: scheduler LocalQueue this flavor feeds (reference: ``LocalQueue``,
    #: ``example.config.json:18``)
    queue: str = "default-queue"
    #: host-side pod resources (reference: default resources, ``example.config.json:8-14``)
    cpu: str = "8"
    memory: str = "32Gi"
    #: node-selector labels for K8s backends (replaces GPU tolerations,
    #: reference ``example.config.json:24-31``)
    node_selectors: dict[str, str] = Field(default_factory=dict)
    #: "tpu" runs on real chips; "cpu" runs on a virtual CPU mesh (the
    #: CI/smoke runtime the reference never had — SURVEY.md §4)
    runtime: str = "tpu"

    @property
    def total_chips(self) -> int:
        return self.hosts * self.chips_per_host

    def k8s_resource_name(self) -> str:
        """The extended-resource key requested on pods (replaces
        ``nvidia.com/gpu``, reference ``PyTorchJobDeployer.py:45-55``)."""
        return "cpu" if self.runtime == "cpu" else "google.com/tpu"

    def accelerator_selectors(self) -> dict[str, str]:
        """TPU slice node selectors (SURVEY.md §2.2: topology selectors
        replace the reference's free GPU count)."""
        if self.runtime == "cpu":
            return {}
        sel = {
            "cloud.google.com/gke-tpu-accelerator": f"tpu-{self.generation}-slice",
            "cloud.google.com/gke-tpu-topology": self.topology,
        }
        sel.update(self.node_selectors)
        return sel


class FlavorQuota(BaseModel):
    """Nominal chip quota for one flavor in the cluster queue (reference:
    ``nominalQuota``, ``crds/kueue/cluster-queue.yaml:18-22``)."""

    flavor: str
    nominal_chips: int


class DeviceCatalog(BaseModel):
    """The full worker catalog (reference: ``APIConfiguration``,
    ``device_config.py:46-75``)."""

    flavors: list[DeviceFlavor] = Field(default_factory=list)
    quotas: list[FlavorQuota] = Field(default_factory=list)
    default_flavor: str = ""

    def get(self, name: str) -> DeviceFlavor | None:
        for f in self.flavors:
            if f.name == name:
                return f
        return None

    def get_worker(self, name: str) -> DeviceFlavor:
        """Resolve a flavor, falling back to the default (reference:
        ``device_configuration.get_worker`` + default-queue fallback,
        ``device_config.py:59-75``)."""
        f = self.get(name)
        if f is not None:
            return f
        if self.default_flavor:
            fallback = self.get(self.default_flavor)
            if fallback is not None:
                logger.warning("unknown device %r; using default %r", name, fallback.name)
                return fallback
        raise KeyError(f"unknown device flavor {name!r} and no default configured")

    def quota_for(self, flavor: str) -> int:
        for q in self.quotas:
            if q.flavor == flavor:
                return q.nominal_chips
        f = self.get(flavor)
        return f.total_chips if f else 0

    def names(self) -> list[str]:
        return [f.name for f in self.flavors]

    def device_enum(self) -> type[enum.Enum]:
        """Dynamic enum for the submission form (reference: ``DeviceTypes``,
        ``device_config.py:107-109``)."""
        return enum.Enum("DeviceTypes", {f.name: f.name for f in self.flavors})


def default_catalog() -> DeviceCatalog:
    """Built-in catalog covering the BASELINE.md configs plus the CPU smoke flavor."""
    return DeviceCatalog(
        flavors=[
            DeviceFlavor(
                name="cpu-test", description="virtual CPU mesh for CI/smoke",
                generation="cpu", topology="", hosts=1, chips_per_host=1,
                queue="cpu-queue", cpu="2", memory="4Gi", runtime="cpu",
            ),
            DeviceFlavor(
                name="cpu-test-2", description="2-device virtual CPU mesh (ep/tp smoke)",
                generation="cpu", topology="", hosts=1, chips_per_host=2,
                queue="cpu-queue", cpu="4", memory="8Gi", runtime="cpu",
            ),
            DeviceFlavor(
                name="v5e-1", description="one v5e chip on one host",
                generation="v5e", topology="1x1", hosts=1, chips_per_host=1,
                queue="tpu-small-queue",
            ),
            DeviceFlavor(
                name="v5e-4", description="single-host v5e slice",
                generation="v5e", topology="2x2", hosts=1, chips_per_host=4,
                queue="tpu-small-queue",
            ),
            DeviceFlavor(
                name="v5e-8", description="two-host v5e slice",
                generation="v5e", topology="2x4", hosts=2, chips_per_host=4,
                queue="tpu-small-queue",
            ),
            DeviceFlavor(
                name="v5e-16", description="four-host v5e slice (8B FSDP north star)",
                generation="v5e", topology="4x4", hosts=4, chips_per_host=4,
                queue="tpu-medium-queue", cpu="96", memory="384Gi",
            ),
            DeviceFlavor(
                name="v5p-64", description="v5p-64 slice (MoE expert-parallel config)",
                generation="v5p", topology="4x4x4", hosts=16, chips_per_host=4,
                queue="tpu-large-queue", cpu="96", memory="448Gi",
            ),
        ],
        quotas=[
            FlavorQuota(flavor="cpu-test", nominal_chips=2),
            FlavorQuota(flavor="cpu-test-2", nominal_chips=4),
            # one chip, one process: a second trainer (or a serve worker) on
            # the same chip fails or hangs, so the quota is the machine
            FlavorQuota(flavor="v5e-1", nominal_chips=1),
            FlavorQuota(flavor="v5e-4", nominal_chips=8),
            FlavorQuota(flavor="v5e-8", nominal_chips=16),
            FlavorQuota(flavor="v5e-16", nominal_chips=32),
            FlavorQuota(flavor="v5p-64", nominal_chips=64),
        ],
        default_flavor="cpu-test",
    )


_COMMENT_RE = re.compile(r"^\s*//.*$", re.MULTILINE)


def load_catalog(path: Path | str | None) -> DeviceCatalog:
    """Load the catalog from a JSON file with ``//`` comment support
    (reference: ``load_config``, ``device_config.py:81-104``); fall back to
    the built-in default catalog when absent."""
    if not path:
        return default_catalog()
    path = Path(path).expanduser()
    if not path.is_file():
        logger.warning("device config %s not found; using built-in catalog", path)
        return default_catalog()
    text = _COMMENT_RE.sub("", path.read_text())
    return DeviceCatalog.model_validate(json.loads(text))


#: axes a mesh policy may declare (trainer MeshSpec axis names)
_POLICY_AXES = ("fsdp", "ep", "pp", "sp", "tp")


def default_mesh_for(
    flavor: DeviceFlavor,
    num_slices: int = 1,
    policy: dict[str, int] | None = None,
) -> dict[str, int]:
    """Map a slice request to trainer MeshSpec axis sizes.

    ``policy`` is the job spec's intra-slice axis declaration (reference
    pattern: per-model resource declaration, ``finetuning.py:51-104`` — here
    it declares *parallelism*, which the reference never could):

    * keys are intra-slice axes (fsdp/ep/pp/sp/tp); at most one value may be
      ``-1``, meaning "all remaining chips";
    * the default policy ``{"fsdp": -1}`` is FSDP over the whole slice (the
      north-star strategy, SURVEY.md §2.3);
    * DP always runs over slices (the DCN axis): ``dp = num_slices``.

    Raises ``ValueError`` when the flavor's chip count cannot satisfy the
    policy — surfaced at submit time as a 400, not at train time on-device.
    """
    from ..parallel.mesh import MeshSpec

    policy = dict(policy) if policy else {"fsdp": -1}
    unknown = set(policy) - set(_POLICY_AXES)
    if unknown:
        raise ValueError(f"mesh policy axes {sorted(unknown)} not in {_POLICY_AXES}")
    for a, v in policy.items():
        if v != -1 and v < 1:
            raise ValueError(f"mesh policy axis {a}={v} must be >= 1 or -1")
    # One source of truth for -1-fill/divisibility/exact-coverage: the
    # trainer's own MeshSpec.resolve. fsdp is pinned to 1 unless the policy
    # says otherwise — MeshSpec's fsdp=-1 default ("absorb everything") must
    # not kick in when a policy chose other axes.
    try:
        sizes = MeshSpec(dp=1, **{"fsdp": 1, **policy}).resolve(flavor.total_chips)
    except ValueError as exc:
        raise ValueError(
            f"device {flavor.name!r} ({flavor.total_chips} chips) cannot "
            f"satisfy the model's mesh policy {policy}: {exc}"
        ) from None
    return {"dp": num_slices, **{a: sizes[a] for a in _POLICY_AXES}}
