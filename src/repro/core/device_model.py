"""Hardware device models.

The paper (Jarmusch et al., 2025) characterizes two NVIDIA chips — GH100
(Hopper, H100 PCIe) and GB203 (Blackwell, RTX 5080) — via microbenchmarks and
tabulates execution-unit counts (Tab I), cache hierarchy (Tab II), measured
latencies (Tab III), datatype support (Tab IV/V) and power (Tab VI/VIII).

This module is the framework's equivalent artifact: a small database of
device models.  Probes (``repro.core.probes``) *measure* a model for the
backend they run on; published constants provide the *target* models (TPU
v5e for the production mesh, plus the paper's two GPUs so benchmark output
can be compared side-by-side with the paper's tables).

Everything downstream — roofline (``repro.core.roofline``), energy
(``repro.core.energy``), autotuning (``repro.core.autotune``) — consumes a
``DeviceModel``, never raw constants.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MemoryLevel:
    """One level of the memory hierarchy.

    The paper's Tab II rows (L1/shared, L2, global) map onto TPU levels
    (VMEM, HBM); ``bandwidth_Bps`` is aggregate per chip, ``latency_cycles``
    is a load-to-use latency in core cycles (the unit the paper reports).
    """

    name: str
    capacity_bytes: int
    bandwidth_Bps: float
    latency_cycles: float
    software_managed: bool = False


@dataclasses.dataclass(frozen=True)
class DeviceModel:
    """A characterized (or published) device.

    The fields mirror what the paper's microbenchmarks extract: peak compute
    per precision, the memory hierarchy, and interconnect.  ``peak_flops``
    maps dtype name -> FLOP/s for the *matrix* pipeline (tensor core / MXU);
    ``vector_flops`` is the scalar/vector (VPU / CUDA-core) pipeline.
    """

    name: str
    vendor: str
    kind: str                      # "tpu" | "gpu" | "cpu"
    clock_hz: float
    peak_flops: Dict[str, float]   # matrix pipeline, by dtype name
    vector_flops: Dict[str, float]
    memory: Tuple[MemoryLevel, ...]
    # Interconnect (per chip): aggregate off-chip link bandwidth and per-link.
    interconnect_Bps: float = 0.0
    link_Bps: float = 0.0
    num_links: int = 0
    # Matrix-unit native tile (the MXU/mma shape the paper sweeps in §V.B).
    matrix_tile: Tuple[int, int] = (0, 0)
    # Static + peak power for the energy model (§V.C / §VII).
    idle_watts: float = 0.0
    peak_watts: float = 0.0

    def level(self, name: str) -> MemoryLevel:
        for lvl in self.memory:
            if lvl.name == name:
                return lvl
        raise KeyError(f"{self.name} has no memory level {name!r}")

    @property
    def hbm(self) -> MemoryLevel:
        """The last (largest, off-core) memory level."""
        return self.memory[-1]

    def peak_flops_for(self, dtype: str) -> float:
        """Matrix-pipeline peak for ``dtype``; falls back to the widest
        supported precision the dtype would be emulated in (the paper's
        QMMA-fallback observation: FP4 rides the FP8 pipeline on GB203;
        on TPU every sub-bf16 format rides the bf16 MXU pipeline)."""
        if dtype in self.peak_flops:
            return self.peak_flops[dtype]
        if "bfloat16" in self.peak_flops:
            return self.peak_flops["bfloat16"]
        return max(self.peak_flops.values())


# ---------------------------------------------------------------------------
# Published target models
# ---------------------------------------------------------------------------

# TPU v5e — the production target for this framework.  Peaks from Google
# Cloud's "TPU v5e" documentation: 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
TPU_V5E = DeviceModel(
    name="tpu-v5e",
    vendor="google",
    kind="tpu",
    clock_hz=940e6,
    peak_flops={
        "bfloat16": 197e12,
        "float32": 98.5e12,        # fp32 via MXU passthrough at half rate
        "int8": 393e12,
        # fp8/fp6/fp4 are NOT native on v5e: emulated via bf16 MXU after
        # dequant (see DESIGN.md §3) — peak_flops_for() falls back to bf16.
    },
    vector_flops={"float32": 3.9e12, "int32": 3.9e12, "float64": 0.0},
    memory=(
        MemoryLevel("vreg", 32 * 1024, 0.0, 1.0, software_managed=True),
        MemoryLevel("vmem", 128 * 1024 * 1024, 22.0e12, 20.0,
                    software_managed=True),
        MemoryLevel("hbm", 16 * 1024**3, 819e9, 450.0),
    ),
    interconnect_Bps=200e9,        # 4 links
    link_Bps=50e9,
    num_links=4,
    matrix_tile=(128, 128),
    idle_watts=60.0,
    peak_watts=220.0,
)

# GH100 and GB203 are the paper's two GPUs, kept as named models for
# side-by-side tables; detect_backend_model never picks them.
# GH100 (H100 PCIe) — the paper's Hopper column (Tab I/II + §VI measurements).
GH100 = DeviceModel(
    name="gh100-h100-pcie",
    vendor="nvidia",
    kind="gpu",
    clock_hz=1.755e9,
    peak_flops={
        "float8_e4m3fn": 1513e12, "float8_e5m2": 1513e12,
        "float16": 756e12, "bfloat16": 756e12,
        "float32": 378e12,          # tf32 tensor core
        "float64": 51e12,           # FP64 tensor core
        "int8": 1513e12,
    },
    vector_flops={"float32": 51.2e12, "int32": 25.6e12, "float64": 25.6e12},
    memory=(
        # Paper Tab II: 256 KB unified L1/shared per SM (227 KB configurable),
        # 50 MB L2 in 2 partitions, 80 GB HBM2e.  Latencies from the paper's
        # pointer-chase: L1 30-40 cyc, L2 ~273 cyc, global ~658.7 cyc.
        MemoryLevel("l1", 256 * 1024, 128e12, 35.0, software_managed=True),
        MemoryLevel("l2", 50 * 1024**2, 12e12, 273.0),
        MemoryLevel("hbm", 80 * 1024**3, 2000e9, 658.7),
    ),
    interconnect_Bps=64e9,          # PCIe gen5 x16
    link_Bps=64e9,
    num_links=1,
    matrix_tile=(16, 8),            # mma.m16n8k* fragment (per warp)
    idle_watts=45.0,
    peak_watts=350.0,
)

# GB203 (GeForce RTX 5080) — the paper's Blackwell column.
GB203 = DeviceModel(
    name="gb203-rtx5080",
    vendor="nvidia",
    kind="gpu",
    clock_hz=2.617e9,
    peak_flops={
        "float4_e2m1fn": 900e12,     # 5th-gen TC native FP4 (paper Tab IV)
        "float6_e2m3fn": 450e12, "float6_e3m2fn": 450e12,
        "float8_e4m3fn": 450e12, "float8_e5m2": 450e12,
        "float16": 225e12, "bfloat16": 225e12,
        "float32": 112e12,
        "float64": 0.88e12,          # 2 FP64 units/SM (paper Tab I) — scarce
        "int8": 450e12,
    },
    vector_flops={"float32": 56e12, "int32": 56e12, "float64": 0.44e12},
    memory=(
        # Tab II: 128 KB unified L1 per SM (~99 KB configurable shared),
        # 65 MB monolithic L2, 16 GB GDDR7.  Latencies from the paper:
        # L1 30-40 cyc, L2 ~358 cyc, global ~876.7 cyc.
        MemoryLevel("l1", 128 * 1024, 96e12, 35.0, software_managed=True),
        MemoryLevel("l2", 65 * 1024**2, 10e12, 358.0),
        MemoryLevel("hbm", 16 * 1024**3, 960e9, 876.7),
    ),
    interconnect_Bps=64e9,
    link_Bps=64e9,
    num_links=1,
    matrix_tile=(16, 8),
    idle_watts=30.0,
    peak_watts=360.0,
)

# Host CPU — what probes actually run on in this container; filled in by
# measurement (``repro.core.probes``) but given nominal constants so the
# roofline/energy paths are total functions.
HOST_CPU = DeviceModel(
    name="host-cpu",
    vendor="generic",
    kind="cpu",
    clock_hz=3.0e9,
    peak_flops={"float32": 200e9, "bfloat16": 200e9, "float64": 100e9},
    vector_flops={"float32": 200e9, "int32": 100e9, "float64": 100e9},
    memory=(
        MemoryLevel("l1", 32 * 1024, 400e9, 4.0),
        MemoryLevel("l2", 1 * 1024**2, 200e9, 14.0),
        MemoryLevel("l3", 32 * 1024**2, 100e9, 50.0),
        MemoryLevel("hbm", 32 * 1024**3, 25e9, 250.0),
    ),
    interconnect_Bps=10e9,
    link_Bps=10e9,
    num_links=1,
    matrix_tile=(8, 8),
    idle_watts=20.0,
    peak_watts=120.0,
)

REGISTRY: Dict[str, DeviceModel] = {
    m.name: m for m in (TPU_V5E, GH100, GB203, HOST_CPU)
}


def get_device_model(name: str) -> DeviceModel:
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown device model {name!r}; known: {sorted(REGISTRY)}"
        ) from None


# ``device_kind`` as JAX reports it -> published model.  A device that is
# not listed is an error, never a default.
BY_DEVICE_KIND: Dict[str, DeviceModel] = {
    "TPU v5 lite": TPU_V5E,        # what a v5e chip reports
}


def detect_backend_model() -> DeviceModel:
    """The model of the device JAX is running on, looked up by
    ``device_kind``; the CPU platform maps to :data:`HOST_CPU`.  Raises
    for any other device."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return HOST_CPU
    try:
        return BY_DEVICE_KIND[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no device model for {dev.platform} device_kind "
            f"{dev.device_kind!r}; known: {sorted(BY_DEVICE_KIND)}"
        ) from None
