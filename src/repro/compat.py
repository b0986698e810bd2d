"""Capability detection + compatibility layer.

The paper's methodology is *portable* characterization: drop the probe
suite on a device and report what that device actually supports — which
mma formats are native vs. emulated, which pipeline a dot really lowers
to, and so on.  This module applies the same philosophy to the software
stack the reproduction runs on (Python 3.12, JAX 0.9.0, pinned in
``pyproject.toml``):

* **Backend probing** — the platform of ``jax.devices()[0]``.  A backend
  that fails to initialise raises; nothing here turns a missing chip
  into a CPU run.
* **Low-precision dtype registry** — every format resolves to a
  *container* dtype JAX can hold plus an optional ``ml_dtypes``
  host-rounding dtype: fp8 and fp4 are native jnp dtypes, fp6 rides a
  host-rounded e4m3 container (numerically exact fp6 in a byte-aligned
  box).  Sub-byte formats additionally carry a
  :class:`repro.lowbits.PackedSpec` — true bit-packed storage (fp4 2
  values/byte, fp6 4 values in 3 bytes, the paper's Tab V tile packing)
  that ``serve.quant``/``kernels.qmatmul`` use for HBM-resident weights
  and that storage accounting reports as measured bytes/element.
* **pallas_call wrapper** — native Mosaic compilation on TPU,
  ``interpret=True`` only on the CPU platform.
* **Compile cache** — :func:`enable_compile_cache`, called by the entry
  points (never on import).
* **``report()``** — a machine-readable capability report printed at the
  top of every benchmark artifact so each measurement records which paths
  ran native vs. emulated.

Everything here probes *lazily* and caches: importing this module never
touches a device.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import os
import pathlib
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import ml_dtypes
import numpy as np

from repro.lowbits import PackedSpec, is_packable
from repro.lowbits import packed_spec as _lowbits_packed_spec

__all__ = [
    "jax_version",
    "backend_platform",
    "is_tpu",
    "DTypeSpec",
    "dtype_spec",
    "dtype_registry",
    "available_formats",
    "format_bits",
    "PackedSpec",
    "packed_spec",
    "storage_bytes_per_element",
    "shard_map",
    "context_mesh",
    "pallas_interpret_default",
    "pallas_call",
    "enable_compile_cache",
    "vmem_budget_bytes",
    "has_hypothesis",
    "CompatReport",
    "report",
]


# --------------------------------------------------------------------- #
# Version / backend probing
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def jax_version() -> Tuple[int, ...]:
    """Installed JAX version as a comparable int tuple, e.g. (0, 4, 37)."""
    parts: List[int] = []
    for tok in jax.__version__.split("."):
        digits = "".join(c for c in tok if c.isdigit())
        if not digits:
            break
        parts.append(int(digits))
    return tuple(parts) or (0,)


@functools.lru_cache(maxsize=None)
def backend_platform() -> str:
    """Default-backend platform string: 'tpu' | 'gpu' | 'cpu'.  A
    backend that fails to initialise raises out of here."""
    return jax.devices()[0].platform


def is_tpu() -> bool:
    return backend_platform() == "tpu"


# --------------------------------------------------------------------- #
# Low-precision dtype registry
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class DTypeSpec:
    """How one paper format (Tab IV/V) is actually stored on this stack.

    ``container`` is a dtype JAX arrays can hold; ``round_dtype`` (an
    ``ml_dtypes`` dtype, host-side) is set when values must be rounded to
    the true format before entering the container — i.e. the format is
    *emulated*: numerically exact in a wider, byte-aligned box.
    ``native`` means the container IS the format (no emulation).
    """

    name: str                # canonical name, e.g. "float4_e2m1fn"
    bits: int                # true format width (storage accounting)
    max_finite: float        # format's largest finite magnitude
    container: Any           # jnp-compatible dtype holding the values
    round_dtype: Optional[Any]   # ml_dtypes dtype for host rounding
    native: bool             # container == format in this JAX
    packed: Optional[PackedSpec] = None   # sub-byte bit-packed layout

    @property
    def emulated(self) -> bool:
        return not self.native

    @property
    def packable(self) -> bool:
        return self.packed is not None

    def describe(self) -> str:
        suffix = (f"; packed {self.packed.bytes_per_element:g} B/elem"
                  if self.packed is not None else "")
        if self.native:
            return f"native{suffix}"
        return (f"emulated ({np.dtype(self.container).name} container, "
                f"{'host-rounded' if self.round_dtype is not None else 'exact'}"
                f"{suffix})")


def _jnp_dtype(name: str):
    """jnp.<name> if this JAX registers it as a real array dtype."""
    import jax.numpy as jnp

    dt = getattr(jnp, name, None)
    if dt is None:
        return None
    try:                      # probe: can JAX actually hold an array of it?
        np.zeros(1, dtype=np.dtype(dt))
        jnp.zeros((1,), dtype=dt)
    except Exception:
        return None
    return dt


@functools.lru_cache(maxsize=None)
def dtype_registry() -> Dict[str, DTypeSpec]:
    """name -> DTypeSpec for every paper format, probed once per process.

    Fallback ladder per format: native jnp dtype -> fp8 e4m3 container
    with ml_dtypes host rounding (every fp6/fp4 value is exactly
    representable in e4m3: narrower mantissa AND exponent range).
    """
    import jax.numpy as jnp

    e4m3 = _jnp_dtype("float8_e4m3fn") or jnp.bfloat16

    # name, bits, max_finite, ml_dtypes rounding dtype used when the
    # format has no native jnp dtype and must round on the host
    table = [
        ("float8_e4m3fn", 8, 448.0, ml_dtypes.float8_e4m3fn),
        ("float8_e5m2", 8, 57344.0, ml_dtypes.float8_e5m2),
        ("float6_e2m3fn", 6, 7.5, ml_dtypes.float6_e2m3fn),
        ("float6_e3m2fn", 6, 28.0, ml_dtypes.float6_e3m2fn),
        ("float4_e2m1fn", 4, 6.0, ml_dtypes.float4_e2m1fn),
    ]
    reg: Dict[str, DTypeSpec] = {}
    for name, bits, fmax, round_dt in table:
        packed = _lowbits_packed_spec(name) if is_packable(name) else None
        native = _jnp_dtype(name)
        if native is not None:
            reg[name] = DTypeSpec(name=name, bits=bits, max_finite=fmax,
                                  container=native, round_dtype=None,
                                  native=True, packed=packed)
        else:
            reg[name] = DTypeSpec(name=name, bits=bits, max_finite=fmax,
                                  container=e4m3, round_dtype=round_dt,
                                  native=False, packed=packed)
    return reg


def dtype_spec(name: str) -> DTypeSpec:
    try:
        return dtype_registry()[name]
    except KeyError:
        raise KeyError(
            f"unknown low-precision format {name!r}; known: "
            f"{sorted(dtype_registry())}") from None


def available_formats() -> Tuple[str, ...]:
    return tuple(dtype_registry())


def format_bits(name: str) -> int:
    return dtype_spec(name).bits


def packed_spec(name: str) -> Optional[PackedSpec]:
    """The sub-byte packed layout for ``name``, or None (byte formats)."""
    return dtype_spec(name).packed


def storage_bytes_per_element(name: str, packed: bool = True) -> float:
    """True storage B/elem: packed layout when available, else container."""
    spec = dtype_spec(name)
    if packed and spec.packed is not None:
        return spec.packed.bytes_per_element
    return float(np.dtype(spec.container).itemsize)


# ``jax.shard_map`` (its replication check is spelled ``check_vma``)
shard_map = jax.shard_map


def context_mesh():
    """The mesh of the enclosing ``with mesh:`` block (what bare
    ``PartitionSpec`` sharding constraints resolve against), else the
    one ``jax.set_mesh`` installed, else None.  Usable while tracing."""
    from jax._src import mesh as mesh_lib
    mesh = mesh_lib.thread_resources.env.physical_mesh
    if not mesh.empty:
        return mesh
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


# --------------------------------------------------------------------- #
# Pallas: interpret mode on the CPU only
# --------------------------------------------------------------------- #

def pallas_interpret_default() -> bool:
    """True on the CPU platform: run kernels through the Pallas
    interpreter so the suite executes there; Mosaic-compile natively
    on every other platform (a kernel that cannot compile then fails
    instead of silently running interpreted)."""
    return backend_platform() == "cpu"


def vmem_budget_bytes() -> int:
    """Per-core VMEM available to a single Pallas grid step, in bytes.

    TPU cores carry ~16 MiB of VMEM (see the Pallas TPU docs); Mosaic
    needs headroom for double-buffered pipelining, so the usable budget
    for one grid step's blocks + scratch is roughly half.  Off-TPU the
    interpreter has no such limit, but the static checker
    (:mod:`repro.analysis.pallas_check`) still enforces the TPU budget so
    kernels developed under interpret mode don't blow up on hardware.
    Override with ``REPRO_VMEM_BUDGET_BYTES`` when targeting parts with
    different VMEM (e.g. v4's 32 MiB variants).
    """
    env = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
    if env:
        return int(env)
    return 8 * 1024 * 1024


def pallas_call(kernel: Callable, *, interpret: Optional[bool] = None,
                dimension_semantics: Optional[Tuple[str, ...]] = None,
                compiler_params: Any = None, **kwargs):
    """``pl.pallas_call`` with capability-aware defaults.

    * ``interpret=None`` resolves via :func:`pallas_interpret_default` —
      the interpreter on the CPU, native Mosaic elsewhere.
    * ``dimension_semantics`` builds ``pltpu.CompilerParams``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if interpret is None:
        interpret = pallas_interpret_default()
    if compiler_params is None and dimension_semantics is not None:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=tuple(dimension_semantics))
    if compiler_params is not None:
        kwargs["compiler_params"] = compiler_params
    return pl.pallas_call(kernel, interpret=interpret, **kwargs)


# --------------------------------------------------------------------- #
# Persistent compile cache
# --------------------------------------------------------------------- #

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    Entry points call this first thing (importing ``repro`` never
    does).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, since the path is part of
    what a later process must find again."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# --------------------------------------------------------------------- #
# Optional test/tooling deps
# --------------------------------------------------------------------- #

@functools.lru_cache(maxsize=None)
def has_hypothesis() -> bool:
    return importlib.util.find_spec("hypothesis") is not None


# --------------------------------------------------------------------- #
# Capability report
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class CompatReport:
    jax_version: str
    platform: str
    device_count: int
    pallas_mode: str             # "native-mosaic" | "interpret"
    formats: Dict[str, str]      # name -> "native" | "emulated (...)"
    hypothesis: bool

    def lines(self) -> List[str]:
        out = [
            f"compat,jax={self.jax_version},platform={self.platform},"
            f"devices={self.device_count}",
            f"compat,pallas={self.pallas_mode},"
            f"hypothesis={'yes' if self.hypothesis else 'no'}",
        ]
        out += [f"compat,format={name},{how}"
                for name, how in self.formats.items()]
        return out

    def __str__(self) -> str:
        return "\n".join(self.lines())


def report() -> CompatReport:
    """Probe everything once and return the capability report that the
    benchmark runner and examples print at startup, so every artifact
    records which paths ran native vs. emulated."""
    return CompatReport(
        jax_version=jax.__version__,
        platform=backend_platform(),
        device_count=jax.device_count(),
        pallas_mode="interpret" if pallas_interpret_default()
        else "native-mosaic",
        formats={name: spec.describe()
                 for name, spec in dtype_registry().items()},
        hypothesis=has_hypothesis(),
    )
