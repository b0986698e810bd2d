"""Public jit'd wrappers for the Pallas kernels.

Each op auto-selects ``interpret=True`` off-TPU via ``repro.compat``
(this container's CPU validates the kernel bodies; a real v5e compiles
them via Mosaic) and handles layout/padding so callers use model-native
shapes.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat, lowbits
from repro.kernels import flash_attention as _fa
from repro.kernels import qmatmul as _qm
from repro.kernels import ssd_scan as _ssd
# imported up-front: the submodule name is shadowed by this module's
# flash_decode wrapper once repro.kernels.__init__ finishes
from repro.kernels.flash_decode import flash_decode_bhd as _flash_decode_bhd
from repro.kernels.flash_decode import (
    flash_decode_quant_bhd as _flash_decode_quant_bhd)
from repro.kernels.probe_chase import chase, make_chase_buffer  # noqa: F401
from repro.kernels.probe_dep_chain import dep_chain  # noqa: F401
from repro.kernels.probe_mma import mma_probe  # noqa: F401
from repro.serve.quant import BLOCK, quantize_blockwise


def _interpret() -> bool:
    return compat.pallas_interpret_default()


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bk"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    bq: int = 128, bk: int = 128) -> jax.Array:
    """Model-layout flash attention: q (b, sq, hq, d), k/v (b, skv, hkv, d).

    sq is padded to bq internally (extra queries attend causally and are
    sliced off)."""
    b, sq, hq, d = q.shape
    pad = (-sq) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    out = _fa.flash_attention_bhsd(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal, window=window, softcap=softcap, scale=scale,
        bq=bq, bk=bk, interpret=_interpret())
    out = out.transpose(0, 2, 1, 3)
    return out[:, :sq] if pad else out


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "scale", "bk"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 slot_pos: jax.Array, pos: jax.Array, *,
                 window: Optional[int] = None,
                 softcap: Optional[float] = None,
                 scale: Optional[float] = None,
                 bk: int = 512) -> jax.Array:
    """Model-layout flash-decoding: q (b, 1, hq, d), cache (b, S, hkv, d),
    slot_pos (b, S), pos (b,) -> (b, 1, hq, d)."""
    out = _flash_decode_bhd(
        q[:, 0], k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        slot_pos, pos, window=window, softcap=softcap, scale=scale,
        bk=bk, interpret=_interpret())
    return out[:, None]


@functools.partial(jax.jit, static_argnames=(
    "fmt", "window", "softcap", "scale", "bk"))
def flash_decode_quant(q: jax.Array, kv_cache: dict, pos: jax.Array, *,
                       fmt: str,
                       window: Optional[int] = None,
                       softcap: Optional[float] = None,
                       scale: Optional[float] = None,
                       bk: int = 512) -> jax.Array:
    """Model-layout flash decode over a *quantized* KV cache.

    q (b, 1, hq, d); ``kv_cache`` is the quantized ring-cache dict from
    ``repro.models.attention.init_kv_cache(kv_format=fmt)`` (``k_q``/
    ``v_q`` packed codes (b, S, hkv, stored_d), ``k_s``/``v_s`` 1-byte
    e8m0 scales, ``slot_pos``); pos (b,) -> (b, 1, hq, d).  The kernel
    streams the packed bytes and expands them in VMEM — HBM KV traffic
    is the true stored byte count (fp4 ≈ 0.53 B/elem), not the dense
    width."""
    t = lambda a: a.transpose(0, 2, 1, 3)
    out = _flash_decode_quant_bhd(
        q[:, 0], t(kv_cache["k_q"]), t(kv_cache["k_s"]),
        t(kv_cache["v_q"]), t(kv_cache["v_s"]),
        kv_cache["slot_pos"], pos, fmt=fmt,
        window=window, softcap=softcap, scale=scale,
        bk=bk, interpret=_interpret())
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, dt_a: jax.Array, b: jax.Array, c: jax.Array,
             chunk: int = 256,
             initial_state: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """Model-layout SSD: x (bt, s, h, p) pre-discretized (x*dt),
    dt_a (bt, s, h), b/c (bt, s, n).  Pads s to the chunk (identity tail).
    ``initial_state`` (bt, h, p, n) seeds the scan (zeros when omitted) —
    the chunked-prefill carry between a slot's successive chunks.
    Returns (y (bt, s, h, p), final_state (bt, h, p, n))."""
    bt, s, h, p = x.shape
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt_a = jnp.pad(dt_a, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    y, state = _ssd.ssd_scan_bhsp(
        x.transpose(0, 2, 1, 3), dt_a.transpose(0, 2, 1),
        b, c, chunk=chunk, initial_state=initial_state,
        interpret=_interpret())
    y = y.transpose(0, 2, 1, 3)
    return (y[:, :s] if pad else y), state


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk"))
def qmatmul(x: jax.Array, qw: jax.Array, scales: jax.Array, *,
            bm: int = 128, bn: int = 128, bk: int = 256) -> jax.Array:
    """x (m, k) @ dequant(qw (n, k)).T with e8m0 block scales (n, k/32)."""
    m, k = x.shape
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    out = _qm.qmatmul_mkn(x, qw, scales, bm=bm, bn=bn, bk=bk,
                          interpret=_interpret())
    return out[:m] if pad_m else out


@functools.partial(jax.jit, static_argnames=("fmt", "bm", "bn", "bk"))
def qmatmul_packed(x: jax.Array, pw: jax.Array, scales: jax.Array,
                   fmt: str, *,
                   bm: int = 128, bn: int = 128, bk: int = 256
                   ) -> jax.Array:
    """x (m, k) @ dequant(unpack(pw), scales).T with bit-packed weights.

    ``pw`` is (n, k*bits/8) uint8 from :func:`pack_for_qmatmul` — true
    0.5 B/elem (fp4) / 0.75 B/elem (fp6) HBM-resident storage, expanded
    in VMEM; bit-exact with :func:`qmatmul` on the same quantized
    values."""
    m, _ = x.shape
    pad_m = (-m) % bm
    if pad_m:
        x = jnp.pad(x, ((0, pad_m), (0, 0)))
    out = _qm.qmatmul_packed_mkn(x, pw, scales, fmt, bm=bm, bn=bn, bk=bk,
                                 interpret=_interpret())
    return out[:m] if pad_m else out


def quantize_for_qmatmul(w: jax.Array, fmt: str
                         ) -> Tuple[jax.Array, jax.Array]:
    """w (k, n) -> (qw (n, k) quantized along k, scales (n, k/32))."""
    return quantize_blockwise(w.T, fmt)


def pack_for_qmatmul(w: jax.Array, fmt: str
                     ) -> Tuple[jax.Array, jax.Array]:
    """w (k, n) -> (pw (n, k*bits/8) uint8 bit-packed, scales (n, k/32)).

    Same quantization as :func:`quantize_for_qmatmul` (so the packed and
    container kernels see identical values), then ``repro.lowbits.pack``
    along k.  ``fmt`` must be packable (fp4/fp6)."""
    qw, scales = quantize_blockwise(w.T, fmt)
    pw = lowbits.pack(np.asarray(qw.astype(jnp.float32)), fmt)
    return jnp.asarray(pw), scales
