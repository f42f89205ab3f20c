"""Device specification registry.

Cephalo's planner reasons about devices through two numbers per device —
peak compute throughput and memory capacity — plus link bandwidth for the
cluster. The paper's Table 3 GPUs are registered verbatim so the cluster
experiments (Tables 4/5, Figs 6-9) run against the exact hardware the paper
used. TPU generations are registered for the dry-run / roofline target.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Static description of one accelerator model."""

    name: str
    #: peak dense throughput used by the analytic cost model, in TFLOP/s.
    #: For the paper's GPUs this is FP32 (the paper trains full precision);
    #: for TPUs it is bf16 (the dry-run target precision).
    peak_tflops: float
    #: usable memory capacity in GiB.
    memory_gib: float
    #: HBM bandwidth in GB/s (used by the roofline memory term).
    hbm_gbps: float
    #: generation tag, informational.
    generation: str = ""

    @property
    def memory_bytes(self) -> int:
        return int(self.memory_gib * (1 << 30))

    @property
    def peak_flops(self) -> float:
        return self.peak_tflops * 1e12


#: Paper Table 3 (FP32 TFLOPs, memory). HBM bandwidths from vendor datasheets.
_REGISTRY: Dict[str, DeviceSpec] = {}


def register(spec: DeviceSpec) -> DeviceSpec:
    _REGISTRY[spec.name] = spec
    return spec


# --- Paper's GPUs (Table 3) -------------------------------------------------
P40 = register(DeviceSpec("P40", 11.8, 24.0, 346.0, "Pascal"))
P100 = register(DeviceSpec("P100", 9.3, 12.0, 549.0, "Pascal"))
A6000 = register(DeviceSpec("A6000", 38.7, 48.0, 768.0, "Ampere"))
L4 = register(DeviceSpec("L4", 30.3, 24.0, 300.0, "Ada"))
V100 = register(DeviceSpec("V100", 14.1, 16.0, 900.0, "Volta"))
T4 = register(DeviceSpec("T4", 8.1, 15.0, 320.0, "Turing"))
A10G = register(DeviceSpec("A10G", 31.2, 24.0, 600.0, "Ampere"))

# --- TPUs (bf16 peak) — dry-run / roofline targets --------------------------
TPU_V4 = register(DeviceSpec("tpu-v4", 275.0, 32.0, 1228.0, "v4"))
TPU_V5E = register(DeviceSpec("tpu-v5e", 197.0, 16.0, 819.0, "v5e"))
TPU_V5P = register(DeviceSpec("tpu-v5p", 459.0, 95.0, 2765.0, "v5p"))

#: Roofline constants for the production target (per chip).
ROOFLINE_PEAK_FLOPS = 197e12     # bf16 TFLOP/s, TPU v5e
ROOFLINE_HBM_BPS = 819e9         # bytes/s
ROOFLINE_ICI_BPS = 50e9          # bytes/s per link


def get(name: str) -> DeviceSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown device {name!r}; known: {sorted(_REGISTRY)}") from None


def known_devices() -> List[str]:
    return sorted(_REGISTRY)


#: ``jax.Device.device_kind`` → spec, for peaks of the chip a run is on.
#: A v5e reports ``TPU v5 lite``; a kind not listed here is an error,
#: never a default.
_BY_DEVICE_KIND: Dict[str, DeviceSpec] = {
    "TPU v4": TPU_V4,
    "TPU v5 lite": TPU_V5E,
    "TPU v5": TPU_V5P,
}


def for_device_kind(kind: str) -> DeviceSpec:
    """Spec of the chip whose ``jax.Device.device_kind`` is ``kind``."""
    try:
        return _BY_DEVICE_KIND[kind]
    except KeyError:
        raise KeyError(f"no peaks known for device_kind {kind!r}; known: "
                       f"{sorted(_BY_DEVICE_KIND)}") from None


@dataclasses.dataclass(frozen=True)
class Cluster:
    """A (possibly heterogeneous) collection of devices.

    ``devices[i]`` is the spec of rank *i*.  ``link_gbps`` is the slowest
    inter-node link bandwidth, which bounds collective throughput for the
    ring-style AllGather/ReduceScatter the cost model assumes.
    """

    devices: Sequence[DeviceSpec]
    link_gbps: float = 50.0
    name: str = "cluster"
    #: achieved fraction of NIC line rate for cross-node NCCL.  Lab links
    #: (Cluster A) run near line rate; AWS TCP without EFA achieves a
    #: fraction of it (calibrated against the paper's Fig. 8 ratios).
    link_efficiency: float = 1.0
    gpus_per_node: int = 4

    def __post_init__(self):
        if not self.devices:
            raise ValueError("cluster must have at least one device")

    @property
    def n(self) -> int:
        return len(self.devices)

    @property
    def total_memory_bytes(self) -> int:
        return sum(d.memory_bytes for d in self.devices)

    @property
    def total_peak_flops(self) -> float:
        return sum(d.peak_flops for d in self.devices)

    @property
    def homogeneous(self) -> bool:
        return len({d.name for d in self.devices}) == 1

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for d in self.devices:
            out[d.name] = out.get(d.name, 0) + 1
        return out

    def describe(self) -> str:
        parts = [f"{v}x{k}" for k, v in sorted(self.counts().items())]
        return f"{self.name}[{', '.join(parts)}] @ {self.link_gbps} Gbps"


def cluster_a() -> Cluster:
    """Paper Cluster A: 2 machines / 8 GPUs, 50 Gbps inter-node link."""
    return Cluster(
        devices=[L4, L4, A6000, P40, P40, P40, P100, P100],
        link_gbps=50.0,
        name="cluster-a",
        gpus_per_node=4,
    )


def cluster_b() -> Cluster:
    """Paper Cluster B: 8 VMs / 64 GPUs, 100 Gbps network."""
    devices = [A10G] * 16 + [V100] * 16 + [T4] * 32
    return Cluster(devices=devices, link_gbps=100.0, name="cluster-b",
                   link_efficiency=0.25, gpus_per_node=8)


def cluster_b_subset(a10g: int = 16, v100: int = 0, t4: int = 0) -> Cluster:
    """Subsets of Cluster B used by the Fig. 6 scaling experiment."""
    devices = [A10G] * a10g + [V100] * v100 + [T4] * t4
    return Cluster(devices=devices, link_gbps=100.0,
                   name=f"cluster-b-{a10g}a10g-{v100}v100-{t4}t4",
                   link_efficiency=0.25, gpus_per_node=8)


def homogeneous_a10g(n: int = 32) -> Cluster:
    """Fig. 6 right: homogeneous 32xA10G comparison cluster."""
    return Cluster(devices=[A10G] * n, link_gbps=100.0,
                   name=f"homog-{n}xa10g", link_efficiency=0.25,
                   gpus_per_node=8)


def v100_cluster(n: int = 16) -> Cluster:
    """Paper Fig. 8 cluster: homogeneous AWS V100s (2x p3.16xlarge)."""
    return Cluster(devices=[V100] * n, link_gbps=100.0,
                   name=f"{n}xv100", link_efficiency=0.25,
                   gpus_per_node=8)


def tpu_pod(n: int = 256, spec: DeviceSpec = TPU_V5E) -> Cluster:
    return Cluster(devices=[spec] * n, link_gbps=ROOFLINE_ICI_BPS / 1e9 * 8,
                   name=f"tpu-{spec.name}-{n}")


def mixed_tpu_fleet(v5e: int = 256, v4: int = 128) -> Cluster:
    """TPU analogue of the paper's heterogeneous cluster: a multi-slice fleet
    mixing generations (see DESIGN.md §2)."""
    return Cluster(devices=[TPU_V5E] * v5e + [TPU_V4] * v4,
                   link_gbps=100.0, name=f"tpu-fleet-{v5e}v5e-{v4}v4")
