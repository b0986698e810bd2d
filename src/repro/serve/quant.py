"""Blockwise low-precision quantization — the paper's §V.B/§V.C subject.

The paper enumerates mma datatypes e2m1 (FP4), e2m3/e3m2 (FP6), e4m3/e5m2
(FP8) with e8m0 reserved for block-scale exponents (Tab V), and finds FP4
falls back to the FP8 pipeline (QMMA) in current software.  The TPU
adaptation (DESIGN.md §3): v5e's MXU has no sub-bf16 pipeline at all, so
every format here is *storage* precision — weights are kept quantized with
e8m0 (power-of-two) block scales and dequantized to bf16 on the way into
the MXU.  ``repro.kernels.qmatmul`` fuses that dequant into the matmul's
VMEM staging; this module is the numpy-level quantizer + the serving-stack
integration (weight-only PTQ for the Tab VIII inference sweep).

Storage comes in two layers:

* :func:`quantize_blockwise` — values in the registry *container* dtype
  (byte-aligned; the numerical oracle),
* :func:`quantize_tree` — true bit-packed weight storage
  (``packed=True``, via ``repro.lowbits``): fp4 at 0.5 B/elem, fp6 at
  0.75 B/elem, matching Tab V's tile packing, with measured byte counts
  in the returned stats (what the Tab VII/VIII artifacts report as HBM
  traffic).  Block scales are held as the 1-byte e8m0 store (uint8
  biased exponents, ``lowbits.e8m0_encode``) — the paper reserves e8m0
  for exactly this, and fp32-held scales were eating most of fp4's
  margin (3.2x -> ~3.8x measured traffic drop at BLOCK=32).

The KV-cache twin of this quantizer lives in
``repro.models.attention`` (``init_kv_cache(kv_format=...)``), built on
the same ``lowbits`` codec so it can run *inside* the jitted decode
step.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Any, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, lowbits

# (registry object, derived table) — keyed on the registry's *identity*
# rather than lru_cache'd, so a runtime whose registry changes (tests
# clearing compat's cache, a JAX gaining native fp4) never sees a stale
# table.  Holding the registry object itself (not its id()) makes the
# check immune to id reuse after GC.
_FORMAT_CACHE: Tuple[Optional[dict], dict] = (None, {})


def _format_table() -> dict:
    global _FORMAT_CACHE
    reg = compat.dtype_registry()
    if _FORMAT_CACHE[0] is not reg:
        _FORMAT_CACHE = (reg, {
            name: (spec.container, spec.max_finite, spec.round_dtype)
            for name, spec in reg.items()})
    return _FORMAT_CACHE[1]


def invalidate_format_table() -> None:
    """Drop the derived format table (next access rebuilds it).  Usually
    unnecessary — the table already tracks ``compat.dtype_registry()``
    identity — but explicit for callers that mutate a registry in
    place."""
    global _FORMAT_CACHE
    _FORMAT_CACHE = (None, {})


class _LazyFormats(Mapping):
    """name -> (container dtype, max finite magnitude, host rounding dtype).

    Built on first access from the ``repro.compat`` dtype registry so
    importing this module never dereferences a dtype the installed JAX
    lacks.  Formats without a native jnp dtype (fp6 always; fp4 on older
    JAX) round via ml_dtypes on the host and ride an e4m3 container —
    every e2m3/e3m2/e2m1 value is exactly representable in e4m3 (narrower
    mantissa AND exponent range), so the emulation is numerically exact.
    The container is the *compute-side* representation only: HBM-resident
    weight storage bit-packs sub-byte formats (``quantize_tree(packed=
    True)`` / ``repro.lowbits``) per the paper's Tab V tile packing.
    """

    def __getitem__(self, name: str) -> Tuple[Any, float, Any]:
        return _format_table()[name]

    def __iter__(self) -> Iterator[str]:
        return iter(_format_table())

    def __len__(self) -> int:
        return len(_format_table())


LOW_PRECISION_FORMATS: Mapping = _LazyFormats()

BLOCK = 32   # elements per scale block (matches mxfp4/mxfp6/mxfp8 spec)


def _e8m0_scale(absmax: jax.Array, fmt_max: float) -> jax.Array:
    """Power-of-two scale (e8m0 semantics): 2^ceil(log2(absmax/fmt_max)),
    clamped to e8m0's representable exponent range [-127, 127] so every
    scale this quantizer emits survives the 1-byte store losslessly
    (previously a tiny absmax produced exponents below -127 that no
    e8m0 byte can hold).  Routed through the ``repro.lowbits`` codec so
    scale rule and storage rule cannot drift apart."""
    return lowbits.e8m0_decode(lowbits.e8m0_scale_code(absmax, fmt_max))


def quantize_blockwise(w: jax.Array, fmt: str
                       ) -> Tuple[jax.Array, jax.Array]:
    """Quantize along the last axis in blocks of ``BLOCK``.

    Returns (q (..., n) in ``fmt``, scales (..., n/BLOCK) fp32 = powers of
    two, i.e. e8m0 content — 1-byte-storable by construction).

    Trace-safe end to end: fp6, which has no native jnp dtype, rounds
    via ``lowbits.quantize_values`` (pure shift/mask/exp2 — the
    RTNE arithmetic twin of ml_dtypes), not host numpy, so the whole
    function jits/vmaps.  The KV-cache twin
    (``models.attention.quantize_kv`` — can't import this module without
    a serve<->models cycle) orchestrates the same ``lowbits`` scale and
    rounding primitives, so the two quantizers share their numerics by
    construction.
    """
    dtype, fmt_max, round_dtype = LOW_PRECISION_FORMATS[fmt]
    *lead, n = w.shape
    assert n % BLOCK == 0, f"last dim {n} % {BLOCK} != 0"
    wb = w.astype(jnp.float32).reshape(*lead, n // BLOCK, BLOCK)
    scales = _e8m0_scale(jnp.max(jnp.abs(wb), axis=-1), fmt_max)
    vals = wb / scales[..., None]
    if round_dtype is not None:        # fp6: emulated in an e4m3 container
        vals = lowbits.quantize_values(vals, fmt)   # trace-safe RTNE
    q = vals.astype(dtype)
    return q.reshape(*lead, n), scales


def dequantize_blockwise(q: jax.Array, scales: jax.Array,
                         out_dtype=jnp.bfloat16) -> jax.Array:
    *lead, n = q.shape
    block = n // scales.shape[-1]
    qb = q.astype(jnp.float32).reshape(*lead, n // block, block)
    return (qb * scales[..., None]).reshape(*lead, n).astype(out_dtype)


# --------------------------------------------------------------------- #
# Weight-only PTQ over a parameter tree (Tab VIII serving sweep)
# --------------------------------------------------------------------- #

class _TreeStats:
    """Shared MSE/byte accounting for the tree quantizers.

    The squared-error sums accumulate as 0-d *device* scalars; nothing
    forces a host sync until :meth:`mse` reduces them in one
    ``jax.device_get`` per tree.  (The previous copy-pasted accounting
    called ``float(jnp.sum(...))`` twice per leaf — two blocking
    round trips per parameter, dominating engine build time on real
    devices; ``repro.analysis.sanitize`` counts exactly this.)
    """

    def __init__(self):
        self.n_q = 0
        self.q_bytes = 0
        self.w_bytes = 0
        self.w_elems = 0
        self._err = []       # per-leaf device scalars: sum(err^2)
        self._ref = []       # per-leaf device scalars: sum(ref^2)

    def passthrough(self, leaf) -> None:
        self.q_bytes += leaf.nbytes

    def quantized(self, deq, leaf, stored_bytes: int) -> None:
        self.n_q += 1
        self.q_bytes += stored_bytes
        self.w_elems += leaf.size
        ref = leaf.astype(jnp.float32)
        err = deq.astype(jnp.float32) - ref
        self._err.append(jnp.sum(jnp.square(err)))
        self._ref.append(jnp.sum(jnp.square(ref)))

    def mse(self) -> float:
        if not self._err:
            return 0.0
        num, den = jax.device_get((jnp.sum(jnp.stack(self._err)),
                                   jnp.sum(jnp.stack(self._ref))))
        return float(num) / max(float(den), 1e-30)


def _quantizable(path_names, leaf) -> bool:
    if leaf.ndim < 2:
        return False
    if leaf.shape[-1] % BLOCK != 0:
        return False
    name = path_names[-1]
    return name in ("w1", "w2", "w3", "wq", "wk", "wv", "wo", "embed",
                    "unembed", "wz", "wx", "out_proj")


def quantize_params(params: Any, fmt: str, compute_dtype=jnp.bfloat16
                    ) -> Tuple[Any, dict]:
    """Quantize-dequantize (weight-only, fake-quant) a parameter tree.

    Returns (params', stats).  Mirrors what a deployed engine does with
    ``repro.kernels.qmatmul`` keeping weights resident in ``fmt`` — here we
    materialize the dequantized bf16 copy because the XLA path consumes
    dense arrays; storage-byte accounting for the energy model uses
    ``stats['quantized_bytes']`` at the *true packed* width
    (``compat.storage_bytes_per_element``: fp4 0.5 B, fp6 0.75 B, fp8
    1 B — what :func:`quantize_tree` actually materializes), with scales
    counted at the 1-byte e8m0 store (one uint8 code per block, what
    :func:`quantize_tree` keeps), not fp32.
    """
    if fmt in ("float32", "bfloat16", "float16"):
        cast = jax.tree.map(lambda w: w.astype(jnp.dtype(fmt))
                            if w.ndim >= 2 else w, params)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(cast))
        return cast, {"format": fmt, "quantized_bytes": nbytes,
                      "n_quantized": 0, "mse": 0.0,
                      "bytes_per_element": jnp.dtype(fmt).itemsize}

    bpe = compat.storage_bytes_per_element(fmt, packed=True)
    stats = _TreeStats()

    def visit(path, leaf):
        names = tuple(str(getattr(k, "key", k)) for k in path)
        if not _quantizable(names, leaf):
            stats.passthrough(leaf)
            return leaf
        q, s = quantize_blockwise(leaf, fmt)
        deq = dequantize_blockwise(q, s, compute_dtype)
        # scales: 1 B e8m0 each
        stats.quantized(deq, leaf, int(leaf.size * bpe) + s.size)
        return deq

    out = jax.tree_util.tree_map_with_path(visit, params)
    return out, {"format": fmt, "quantized_bytes": int(stats.q_bytes),
                 "n_quantized": stats.n_q, "bytes_per_element": bpe,
                 "mse": stats.mse()}


# --------------------------------------------------------------------- #
# True quantized weight storage (packed sub-byte via repro.lowbits)
# --------------------------------------------------------------------- #

def quantize_tree(params: Any, fmt: str, packed: bool = True
                  ) -> Tuple[Any, dict]:
    """Quantize a parameter tree into *stored* low-precision form.

    Unlike :func:`quantize_params` (fake-quant: returns dense
    ``compute_dtype`` arrays), this keeps the quantized representation:
    each quantizable leaf becomes ``{"q": codes, "scales": s, "fmt":
    fmt}`` where ``q`` is the bit-packed uint8 array (``packed=True``
    and the format is sub-byte: fp4 2 values/byte, fp6 4 values in 3
    bytes) or the registry container array (``packed=False`` — the
    byte-aligned oracle layout), and ``scales`` is the **packed e8m0
    store**: one uint8 biased-exponent code per block
    (``lowbits.e8m0_encode``, lossless for the power-of-two scales the
    quantizer emits) instead of 4-byte fp32.  Non-quantizable leaves
    pass through.

    Stats report *measured* bytes (``sum(arr.nbytes)`` over what is
    actually stored), not nominal widths — the number the Tab VII/VIII
    benchmarks quote as HBM weight traffic.  :func:`dequantize_tree`
    reverses.
    """
    do_pack = packed and lowbits.is_packable(fmt)
    stats = _TreeStats()

    def visit(path, leaf):
        names = tuple(str(getattr(k, "key", k)) for k in path)
        if not _quantizable(names, leaf):
            stats.passthrough(leaf)
            return leaf
        q, s = quantize_blockwise(leaf, fmt)
        deq = dequantize_blockwise(q, s, jnp.float32)
        if do_pack:
            q = jnp.asarray(lowbits.pack(
                np.asarray(q.astype(jnp.float32)), fmt))
        s_codes = jnp.asarray(lowbits.e8m0_encode(np.asarray(s)))
        stats.quantized(deq, leaf, q.nbytes + s_codes.nbytes)
        stats.w_bytes += q.nbytes
        return {"q": q, "scales": s_codes, "scale_fmt": "e8m0",
                "fmt": fmt, "shape": leaf.shape, "packed": do_pack}

    store = jax.tree_util.tree_map_with_path(visit, params)
    return store, {"format": fmt, "packed": do_pack,
                   "quantized_bytes": int(stats.q_bytes),
                   "n_quantized": stats.n_q,
                   "weight_bytes": int(stats.w_bytes),
                   "mse": stats.mse(),
                   "bytes_per_element": (
                       stats.w_bytes / stats.w_elems if stats.w_elems
                       else compat.storage_bytes_per_element(
                           fmt, packed=do_pack))}


def _is_qleaf(x: Any) -> bool:
    return isinstance(x, dict) and set(x) >= {"q", "scales", "fmt"}


def dequantize_tree(store: Any, compute_dtype=jnp.bfloat16) -> Any:
    """Materialize dense ``compute_dtype`` params from a quantize_tree
    store (unpacking bit-packed leaves and decoding 1-byte e8m0 scales
    through ``repro.lowbits``)."""

    def leaf(x):
        if not _is_qleaf(x):
            return x
        q = x["q"]
        if x.get("packed"):
            n = x["shape"][-1]
            vals = lowbits.unpack(np.asarray(q), x["fmt"], n)
            q = jnp.asarray(vals.reshape(x["shape"]))
        s = x["scales"]
        if x.get("scale_fmt") == "e8m0":
            s = lowbits.e8m0_decode(s)
        return dequantize_blockwise(q, s, compute_dtype)

    return jax.tree.map(leaf, store, is_leaf=_is_qleaf)
