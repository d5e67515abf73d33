"""Site platform descriptors — the "systems evaluated" of the paper (§V-A).

A `Platform` is the analogue of a host system entry (Laptop / Linux Cluster /
Piz Daint): it describes the hardware the runtime may bind a bundle to —
device kind, counts, interconnect tiers — plus the constants the roofline
analysis needs.  Detection mirrors Shifter's behaviour: the runtime inspects
the environment (device kind, REPRO_* variables) and selects the matching
platform; nothing about the bundle changes.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax

__all__ = [
    "HardwareSpec",
    "Platform",
    "LAPTOP",
    "CLUSTER",
    "PLATFORMS",
    "detect_platform",
    "chip_platform",
    "tpu_hardware",
    "TPU_V5E",
    "TPU_KINDS",
]


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """Per-chip capability constants (used by roofline + schedulers)."""

    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    hbm_bytes: float            # bytes of device memory per chip
    hbm_bandwidth: float        # bytes/s per chip
    ici_bandwidth: float        # bytes/s per link (intra-pod interconnect)
    dcn_bandwidth: float        # bytes/s per host (inter-pod network)
    ici_links: int = 4          # links per chip (2D torus -> 4)


# Published per-chip peaks of a TPU v5e (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI).
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bytes=16e9,
    hbm_bandwidth=819e9,
    ici_bandwidth=50e9,
    dcn_bandwidth=25e9 / 8,     # ~25 Gbit/s effective per host, in bytes/s
)

# TPU chips by the `device_kind` JAX reports.  A kind missing here is an
# error, never a default: another chip's peaks would silently skew every
# roofline and tuning decision made against them.
TPU_KINDS: dict[str, HardwareSpec] = {
    "TPU v5 lite": TPU_V5E,
}

# Commodity CPU "laptop" — the build-and-test environment of the paper's
# workflow (Fig. 2 step 1-2).  Constants are nominal; they only matter for
# relative reporting in benchmarks.
CPU_HOST = HardwareSpec(
    name="cpu-host",
    peak_flops_bf16=2e11,
    hbm_bytes=8e9,
    hbm_bandwidth=4e10,
    ici_bandwidth=1e9,
    dcn_bandwidth=1e9,
)


@dataclasses.dataclass(frozen=True)
class Platform:
    """A deployable site: hardware + topology + which native features exist.

    `native_features` lists the host resources the runtime may inject — the
    analogue of the host's CUDA driver stack and vendor MPI.  A bundle
    deployed on a platform lacking a feature silently keeps its reference
    implementation, exactly like Shifter with `--mpi` unavailable.
    """

    name: str
    hardware: HardwareSpec
    mesh_shape: tuple[int, ...]
    mesh_axes: tuple[str, ...]
    native_features: frozenset[str] = frozenset()
    description: str = ""

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.mesh_shape:
            n *= s
        return n

    @property
    def num_pods(self) -> int:
        return self.mesh_shape[self.mesh_axes.index("pod")] if "pod" in self.mesh_axes else 1

    def has(self, feature: str) -> bool:
        return feature in self.native_features


LAPTOP = Platform(
    name="laptop",
    hardware=CPU_HOST,
    mesh_shape=(1,),
    mesh_axes=("data",),
    native_features=frozenset(),
    description="single-device commodity host; reference ops only (build/test)",
)

CLUSTER = Platform(
    name="cluster",
    hardware=CPU_HOST,
    mesh_shape=(8,),
    mesh_axes=("data",),
    native_features=frozenset({"native_collectives"}),
    description="small multi-device host (8 local devices); flat collectives",
)

# CPU host that runs the Pallas kernels through the interpreter — used to
# validate the full swap path (binding reports + numerics) without a TPU.
POD_SIM = Platform(
    name="pod-sim",
    hardware=CPU_HOST,
    mesh_shape=(1,),
    mesh_axes=("data",),
    native_features=frozenset({"pallas_interpret", "native_collectives"}),
    description="CPU simulation host: Pallas kernels in interpret mode",
)


def chip_platform(hardware: HardwareSpec, n: int) -> Platform:
    """The site that is exactly the `n` chips present: a flat data mesh."""
    features = {"pallas_kernels"}
    if n > 1:
        features.add("native_collectives")
    return Platform(
        name=f"{hardware.name}-{n}chip",
        hardware=hardware,
        mesh_shape=(n,),
        mesh_axes=("data",),
        native_features=frozenset(features),
        description=f"{n} attached {hardware.name} chip{'s' if n > 1 else ''}",
    )


# Sites selectable by name (REPRO_PLATFORM): the CPU hosts, and one or
# four attached v5e chips, which is what detect_platform finds there.
PLATFORMS: dict[str, Platform] = {
    p.name: p for p in (LAPTOP, CLUSTER, POD_SIM,
                        chip_platform(TPU_V5E, 1), chip_platform(TPU_V5E, 4))
}


def tpu_hardware(device_kind: str) -> HardwareSpec:
    """Per-chip constants for a TPU `device_kind`; unknown kinds raise."""
    try:
        return TPU_KINDS[device_kind]
    except KeyError:
        raise KeyError(
            f"TPU device_kind {device_kind!r} has no hardware entry; "
            f"known: {sorted(TPU_KINDS)}"
        ) from None


def detect_platform(devices: Sequence[jax.Device] | None = None) -> Platform:
    """Auto-detect the site, CUDA_VISIBLE_DEVICES-style.

    Order of precedence mirrors Shifter: explicit environment request
    (handled by env.resolve_platform, which calls this as fallback), then
    device inspection.  TPU devices give the platform of the chips
    actually present, keyed by their `device_kind`.
    """
    devices = list(devices if devices is not None else jax.devices())
    kind = devices[0].platform if devices else "cpu"
    n = len(devices)
    if kind == "tpu":
        return chip_platform(tpu_hardware(devices[0].device_kind), n)
    if n >= 8:
        return CLUSTER
    return LAPTOP
