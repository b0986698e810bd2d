"""Bit-packed sub-byte storage for the emulated mma formats (Tab V).

The paper's sub-byte datatypes (e2m1 FP4, e2m3/e3m2 FP6) exist *for*
storage density: Tab V's packing discussion is explicit that fp4 tiles
pack 2 values/byte and fp6 tiles 4 values in 3 bytes.  The PR-1 compat
registry emulated these formats numerically (exact values in a 1-byte
e4m3 container) but stored them at container width — so the "~4x HBM
traffic drop" the qmatmul docstring promised was nominal, not measured.

This module is the packing layer behind ``repro.compat``'s dtype
registry:

* :class:`PackedSpec` — per-format bit layout (field widths, exponent
  bias, group geometry: how many values share how many bytes),
* :func:`encode` / :func:`decode` — value <-> bit-code conversion.
  Encoding rides ``ml_dtypes`` (its byte encoding IS the format's bit
  pattern, zero-extended into a uint8 — verified by the all-codes test);
  decoding is plain shift/mask/exp2 arithmetic so the *same* function
  body runs on numpy arrays on the host and on jnp tiles inside a
  Pallas kernel (``repro.kernels.qmatmul.qmatmul_packed_mkn`` expands
  nibble-packed k-blocks in VMEM with it),
* :func:`quantize_values` / :func:`encode_codes` / :func:`pack_codes` —
  the *trace-safe* twins of encode/pack: round-to-nearest-even into the
  format's value set, field assembly, and bit packing via pure
  shift/mask/exp2 arithmetic, so quantization itself can run under
  ``jit``/``vmap`` and inside Pallas kernels (the KV-cache write path
  quantizes on the fly every decode step),
* :func:`pack` / :func:`unpack` — vectorized (de)packing along the last
  axis, tail-padded with zero codes so odd lengths round-trip,
* :func:`packed_nbytes` — true storage accounting (0.5 B/elem fp4,
  0.75 B/elem fp6) used by the quantizer stats and benchmark artifacts,
* the **e8m0 scale codec** (:func:`e8m0_encode` / :func:`e8m0_decode` /
  :func:`e8m0_scale_code`) — block scales stored as 1-byte biased
  exponents (the paper's Tab V reserves e8m0 for exactly this), clamped
  to the representable range [2^-127, 2^127].  Holding power-of-two
  scales in fp32 wastes 4 bytes per block; at BLOCK=32 the 1-byte store
  takes fp4 from ~3.2x to ~3.8x measured HBM traffic drop.

Bit order is little-endian within a group: value ``i`` of an fp4 pair
occupies bits ``[4i, 4i+4)`` of the byte; an fp6 quad occupies the 24
bits of its 3 bytes in the same ascending order.

No ``repro`` imports here — this is a leaf module ``repro.compat``
builds its registry on top of.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import ml_dtypes
import numpy as np

__all__ = [
    "PackedSpec",
    "PACKED_FORMATS",
    "packed_spec",
    "is_packable",
    "encode",
    "decode",
    "quantize_values",
    "encode_codes",
    "pack",
    "pack_codes",
    "unpack",
    "unpack_codes",
    "packed_nbytes",
    "E8M0_BIAS",
    "E8M0_MIN_EXP",
    "E8M0_MAX_EXP",
    "e8m0_encode",
    "e8m0_decode",
    "e8m0_scale_code",
]


@dataclasses.dataclass(frozen=True)
class PackedSpec:
    """Bit layout + group geometry of one sub-byte format.

    ``values_per_group`` values are stored in ``bytes_per_group`` bytes:
    fp4 packs 2/1 (nibbles), fp6 packs 4/3 (24 bits) — the Tab V tile
    packing.  ``code_dtype`` is the ``ml_dtypes`` scalar whose uint8
    encoding equals the format's bit code (used for host-side encode,
    i.e. rounding float -> code).
    """

    name: str                # canonical registry name, e.g. "float4_e2m1fn"
    bits: int                # code width
    ebits: int               # exponent field width
    mbits: int               # mantissa field width
    bias: int                # exponent bias
    values_per_group: int    # values per packed group
    bytes_per_group: int     # bytes per packed group
    code_dtype: Any          # ml_dtypes dtype for host-side encoding
    max_finite: float = 0.0  # largest finite magnitude (saturation point)

    @property
    def bytes_per_element(self) -> float:
        return self.bytes_per_group / self.values_per_group

    def packed_len(self, n: int) -> int:
        """Packed byte count for ``n`` values (tail group zero-padded)."""
        g = self.values_per_group
        return (n + g - 1) // g * self.bytes_per_group


PACKED_FORMATS: Dict[str, PackedSpec] = {
    "float4_e2m1fn": PackedSpec("float4_e2m1fn", 4, ebits=2, mbits=1,
                                bias=1, values_per_group=2,
                                bytes_per_group=1,
                                code_dtype=ml_dtypes.float4_e2m1fn,
                                max_finite=6.0),
    "float6_e2m3fn": PackedSpec("float6_e2m3fn", 6, ebits=2, mbits=3,
                                bias=1, values_per_group=4,
                                bytes_per_group=3,
                                code_dtype=ml_dtypes.float6_e2m3fn,
                                max_finite=7.5),
    "float6_e3m2fn": PackedSpec("float6_e3m2fn", 6, ebits=3, mbits=2,
                                bias=3, values_per_group=4,
                                bytes_per_group=3,
                                code_dtype=ml_dtypes.float6_e3m2fn,
                                max_finite=28.0),
}


def packed_spec(name: str) -> PackedSpec:
    try:
        return PACKED_FORMATS[name]
    except KeyError:
        raise KeyError(f"format {name!r} has no packed storage layout; "
                       f"packable: {sorted(PACKED_FORMATS)}") from None


def is_packable(name: str) -> bool:
    return name in PACKED_FORMATS


def packed_nbytes(n: int, fmt: str) -> int:
    """True storage bytes for ``n`` values of ``fmt`` (no scales)."""
    return packed_spec(fmt).packed_len(n)


# --------------------------------------------------------------------- #
# value <-> code
# --------------------------------------------------------------------- #

def encode(values, fmt: str) -> np.ndarray:
    """Round float values to ``fmt`` and return uint8 bit codes (host).

    ``ml_dtypes`` encodes each sub-byte format's bit pattern in the low
    bits of one byte, so ``astype(code_dtype).view(uint8)`` is exactly
    "round, then read the code".
    """
    spec = packed_spec(fmt)
    a = np.asarray(values, dtype=np.float32)
    return a.astype(spec.code_dtype).view(np.uint8)


def decode(codes, fmt: str):
    """Bit codes -> float32 values, via shift/mask/exp2 arithmetic only.

    Works on numpy *and* jnp/traced arrays (no ml_dtypes, no table
    lookup), so Pallas kernels call this directly on VMEM tiles.
    """
    spec = packed_spec(fmt)
    c = codes.astype(np.int32) if isinstance(codes, np.ndarray) \
        else codes.astype("int32")
    m = c & ((1 << spec.mbits) - 1)
    e = (c >> spec.mbits) & ((1 << spec.ebits) - 1)
    s = c >> (spec.mbits + spec.ebits)
    frac = m.astype(np.float32) * np.float32(2.0 ** -spec.mbits)
    is_sub = (e == 0)
    # subnormal: frac * 2^(1-bias); normal: (1+frac) * 2^(e-bias)
    mag = _where(is_sub,
                 frac * np.float32(2.0 ** (1 - spec.bias)),
                 (np.float32(1.0) + frac)
                 * _exp2(e.astype(np.float32) - np.float32(spec.bias)))
    return _where(s != 0, -mag, mag)


def _xp(x):
    """numpy for numpy inputs, jax.numpy otherwise (traced arrays)."""
    if isinstance(x, np.ndarray) or np.isscalar(x):
        return np
    import jax.numpy as jnp
    return jnp


def _where(cond, a, b):
    return _xp(cond).where(cond, a, b)


def _exp2(x):
    return _xp(x).exp2(x)


def quantize_values(values, fmt: str):
    """Round values into ``fmt``'s value set: RTNE, saturating at
    ``max_finite`` — pure arithmetic, so it runs under ``jit``/``vmap``
    and inside Pallas kernels (the host-free twin of ``encode`` +
    ``decode``; bit-identical to ``ml_dtypes`` rounding, property-
    tested).  Returns float32 of the same shape.
    """
    spec = packed_spec(fmt)
    xp = _xp(values)
    x = values.astype(np.float32)
    a = xp.abs(x)
    # floor(log2(a)) via frexp (exact, unlike log2 rounding); a == 0 is
    # routed through 1.0 and comes out as 0 anyway.
    _, e2 = xp.frexp(xp.where(a > 0, a, np.float32(1.0)))
    e = xp.maximum(e2 - 1, 1 - spec.bias)        # subnormal exponent floor
    quant = xp.exp2((e - spec.mbits).astype(np.float32))
    r = xp.round(a / quant) * quant              # RTNE on the mantissa grid
    r = xp.minimum(r, np.float32(spec.max_finite))
    return xp.where(xp.signbit(x), -r, r).astype(np.float32)


def encode_codes(values, fmt: str):
    """Float values -> int32 bit codes via pure arithmetic (trace-safe).

    The jit-capable twin of :func:`encode` (which rides ml_dtypes on the
    host): rounds with :func:`quantize_values`, then assembles the
    sign/exponent/mantissa fields.  Used by the quantized KV-cache write
    path, which must encode inside a jitted decode step.
    """
    spec = packed_spec(fmt)
    xp = _xp(values)
    v = quantize_values(values, fmt)
    a = xp.abs(v)
    thr = np.float32(2.0 ** (1 - spec.bias))     # smallest normal
    _, e2 = xp.frexp(xp.where(a > 0, a, np.float32(1.0)))
    normal = a >= thr
    e = xp.where(normal, e2 - 1, 1 - spec.bias)
    # integer mantissa incl. the implicit bit: a * 2^(mbits - e)
    m = xp.round(a * xp.exp2((spec.mbits - e).astype(np.float32)))
    m = m.astype(np.int32)
    e_field = xp.where(normal, e + spec.bias, 0).astype(np.int32)
    m_field = m - xp.where(normal, 1 << spec.mbits, 0).astype(np.int32)
    sign = xp.signbit(v).astype(np.int32)   # signbit, not <0: -0.0 packs
    return ((sign << (spec.ebits + spec.mbits))
            | (e_field << spec.mbits) | m_field)


# --------------------------------------------------------------------- #
# pack / unpack along the last axis
# --------------------------------------------------------------------- #

def pack(values, fmt: str) -> np.ndarray:
    """(..., n) float values -> (..., packed_len(n)) uint8, host-side.

    Values are rounded to ``fmt`` first (exact when they already are
    ``fmt`` values, e.g. out of ``quantize_blockwise``); a tail shorter
    than the group is zero-code padded.
    """
    spec = packed_spec(fmt)
    codes = encode(values, fmt)
    *lead, n = codes.shape
    pad = (-n) % spec.values_per_group
    if pad:
        codes = np.concatenate(
            [codes, np.zeros((*lead, pad), np.uint8)], axis=-1)
    return pack_codes(codes, fmt)


def pack_codes(codes, fmt: str):
    """(..., n) int bit codes -> (..., n*bits/8) uint8; trace-safe.

    Pure shift/or/reshape (the inverse of :func:`unpack_codes`), so it
    runs on numpy or jnp arrays — including under jit in the KV-cache
    write path.  ``n`` must be a multiple of the group size (callers
    with odd tails pad first; :func:`pack` does).
    """
    spec = packed_spec(fmt)
    xp = _xp(codes)
    *lead, n = codes.shape
    g = spec.values_per_group
    if n % g:
        raise ValueError(f"pack_codes: n={n} not a multiple of the "
                         f"{fmt} group size {g}")
    grp = codes.astype(np.int32).reshape(*lead, n // g, g)
    if fmt == "float4_e2m1fn":
        by = (grp[..., 0] | (grp[..., 1] << 4))[..., None]
    else:                         # fp6: 4 codes -> 24 bits -> 3 bytes
        word = (grp[..., 0] | (grp[..., 1] << 6)
                | (grp[..., 2] << 12) | (grp[..., 3] << 18))
        by = xp.stack([word & 0xFF, (word >> 8) & 0xFF, word >> 16],
                      axis=-1)
    return by.reshape(*lead, -1).astype(np.uint8)


def unpack_codes(packed, fmt: str):
    """(..., nbytes) uint8 -> (..., values) int32 codes (padding incl.).

    Pure shift/mask/reshape — runs on numpy or jnp arrays, including
    inside Pallas kernels (the VMEM expand step of ``qmatmul_packed``).
    """
    spec = packed_spec(fmt)
    is_np = isinstance(packed, np.ndarray)
    b = packed.astype(np.int32) if is_np else packed.astype("int32")
    *lead, nb = b.shape
    if is_np:
        import numpy as xp
    else:
        import jax.numpy as xp
    if fmt == "float4_e2m1fn":
        grp = xp.stack([b & 0xF, b >> 4], axis=-1)
    else:
        tri = b.reshape(*lead, nb // spec.bytes_per_group, 3)
        word = tri[..., 0] | (tri[..., 1] << 8) | (tri[..., 2] << 16)
        grp = xp.stack([word & 0x3F, (word >> 6) & 0x3F,
                        (word >> 12) & 0x3F, (word >> 18) & 0x3F],
                       axis=-1)
    return grp.reshape(*lead, -1)


def unpack(packed, fmt: str, n: int):
    """(..., nbytes) uint8 -> (..., n) float32 (tail padding sliced off)."""
    vals = decode(unpack_codes(packed, fmt), fmt)
    return vals[..., :n]


# --------------------------------------------------------------------- #
# 2-D tile helpers for Pallas kernel bodies
# --------------------------------------------------------------------- #
# Mosaic cannot lower a reshape that splits or interleaves the lane
# (last) axis, which is what unpack_codes' stack + reshape and a
# per-block scale broadcast through (rows, n, blk) do.  These helpers
# move values across lanes with 0/1 matmuls instead.  Each output
# element is one input value times 1 plus zeros, so the result is
# exact, on the interpreter and on the MXU alike (fp4 values and
# power-of-two scales are exact in bf16).

def _onehot(n: int, width: int, hit):
    """(n, width) float32, 1 where ``hit(row, col)`` holds, else 0."""
    import jax
    import jax.numpy as jnp

    row = jax.lax.broadcasted_iota(jnp.int32, (n, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1)
    return hit(row, col).astype(jnp.float32)


def unpack_tile(packed, fmt: str):
    """(rows, nbytes) uint8 tile -> (rows, values) float32, in-kernel.

    fp4 decodes the low and high nibble planes separately and
    interleaves them with one-hot matmuls, so it lowers in Mosaic.
    fp6 goes through :func:`unpack_codes` and runs only under the
    Pallas interpreter."""
    if fmt != "float4_e2m1fn":
        return decode(unpack_codes(packed, fmt), fmt)
    import jax
    import jax.numpy as jnp

    b = packed.astype(jnp.int32)
    nb = b.shape[-1]
    out = None
    for half, plane in enumerate((b & 0xF, b >> 4)):
        place = _onehot(nb, 2 * nb, lambda r, c, h=half: c == 2 * r + h)
        part = jax.lax.dot(decode(plane, fmt), place,
                           preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def spread_scales(scales, blk: int, width: int, first=0):
    """(rows, n) block scales -> (rows, width) per-element scales, in-
    kernel: column ``l`` takes ``scales[:, first + l // blk]``.
    ``first`` may be traced (a grid-step offset)."""
    import jax
    import jax.numpy as jnp

    sel = _onehot(scales.shape[-1], width,
                  lambda r, c: r == first + c // blk)
    return jax.lax.dot(scales.astype(jnp.float32), sel,
                       preferred_element_type=jnp.float32)


# --------------------------------------------------------------------- #
# e8m0 scale codec (1-byte block-scale exponents, OCP MX / paper Tab V)
# --------------------------------------------------------------------- #
# e8m0 is an 8-bit *unsigned biased exponent* with no sign or mantissa:
# code c represents 2^(c - 127), c in [0, 254] (255 is NaN, never
# produced here).  Representable scales therefore span [2^-127, 2^127];
# everything below/above clamps.  All functions are pure arithmetic —
# they run on numpy or jnp arrays, under jit, and inside Pallas kernels
# (the flash_decode quantized-KV leg decodes scale bytes in VMEM).

E8M0_BIAS = 127
E8M0_MIN_EXP = -127        # code 0
E8M0_MAX_EXP = 127         # code 254

def e8m0_encode(scales):
    """Power-of-two fp32 scales -> uint8 e8m0 codes (clamped, exact for
    in-range powers of two — the round trip is bit-lossless)."""
    xp = _xp(scales)
    s = xp.maximum(scales.astype(np.float32), np.float32(1e-45))
    _, e2 = xp.frexp(s)                     # s = m * 2^e2, m in [0.5, 1)
    exp = xp.clip(e2 - 1, E8M0_MIN_EXP, E8M0_MAX_EXP)
    return (exp + E8M0_BIAS).astype(np.uint8)


def e8m0_decode(codes):
    """uint8 e8m0 codes -> fp32 power-of-two scales (2^(code - 127)).
    Widens through int32: Mosaic has no uint8 -> float32 cast."""
    xp = _xp(codes)
    return xp.exp2(codes.astype(np.int32).astype(np.float32)
                   - np.float32(E8M0_BIAS))


def e8m0_scale_code(absmax, fmt_max: float):
    """Block absmax -> the e8m0 code of the smallest power-of-two scale
    with absmax/scale <= fmt_max: ceil(log2(absmax/fmt_max)), clamped to
    e8m0's representable exponent range.  This IS the quantizer's scale
    rule (``serve.quant._e8m0_scale`` decodes this code), so scales are
    1-byte-storable by construction."""
    xp = _xp(absmax)
    a = xp.maximum(absmax.astype(np.float32), np.float32(1e-38))
    exp = xp.ceil(xp.log2(a / np.float32(fmt_max)))
    exp = xp.clip(exp, E8M0_MIN_EXP, E8M0_MAX_EXP)
    return (exp + E8M0_BIAS).astype(np.uint8)
