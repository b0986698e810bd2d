"""Flash-decoding, TPU Pallas — single-token attention against a (ring)
KV cache.

The serving hot path (decode_32k / long_500k cells): one query row
attends to S cached positions.  The XLA path materializes the (1, S)
score row per head in HBM; this kernel streams KV blocks through VMEM
with the m/l/acc partial-softmax state in scratch — HBM traffic is the
KV read itself (the roofline floor), which is why the quantized-KV
lever composes: :func:`flash_decode_quant_bhd` streams fp8-container or
nibble-packed fp4 KV blocks plus their 1-byte e8m0 scales and expands
them in VMEM on the way in (``repro.lowbits`` shift/mask/exp2 — the
same codec the cache write path encodes with), so the HBM read per
cached token is the true packed byte count (fp4 ≈ 0.53 B/elem vs 2
B/elem bf16 — the §VI.D read-bandwidth story).

Grid (batch*q_heads, S/bk), KV-block dim innermost/arbitrary.  Ring-cache
semantics match ``repro.models.attention.decode_attention`` (the oracle):
slot visibility = 0 <= slot_pos <= pos (and > pos - window for local
layers).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat, lowbits

NEG_INF = -1.0e30


def _attend_block(q, k, v, slot_pos, pos, o_ref, m_scr, l_scr, acc_scr, *,
                  window: Optional[int], softcap: Optional[float],
                  scale: float):
    """Shared online-softmax body: one (1, d) query against one (bk, d)
    KV block, scratch-carried m/l/acc, finalize on the last block.
    ``slot_pos`` is the block's (1, bk) row and ``pos`` a scalar; every
    value stays 2-D so Mosaic lowers it."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    ok = (slot_pos >= 0) & (slot_pos <= pos)
    if window is not None:
        ok &= slot_pos > pos - window
    s = jnp.where(ok, s, NEG_INF)                      # (1, bk)

    m_prev = m_scr[...]                                # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = (acc_scr[...] * corr
                    + jax.lax.dot(p, v,
                                  preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _kernel(pos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref,
            m_scr, l_scr, acc_scr, *,
            bk: int, hq: int, window: Optional[int],
            softcap: Optional[float], scale: float):
    q = q_ref[0].astype(jnp.float32)                  # (1, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)                  # (bk, d)
    pos = pos_ref[pl.program_id(0) // hq]             # SMEM scalar
    _attend_block(q, k, v, sp_ref[0], pos, o_ref, m_scr, l_scr, acc_scr,
                  window=window, softcap=softcap, scale=scale)


def _expand_kv_tile(stored, s_codes, *, fmt: str, packed: bool, d: int,
                    blk: int):
    """(bk, stored_d) codes/container + (bk, d/blk) e8m0 bytes ->
    (bk, d) fp32, in VMEM — dequant-on-the-way-in (shift/mask/exp2 only,
    no ml_dtypes: the ``repro.lowbits`` in-kernel codec)."""
    if packed:
        vals = lowbits.unpack_tile(stored, fmt)
    else:
        vals = stored.astype(jnp.float32)
    scales = lowbits.e8m0_decode(s_codes)             # (bk, d/blk)
    return vals * lowbits.spread_scales(scales, blk, d)


def _quant_kernel(pos_ref, q_ref, kq_ref, ks_ref, vq_ref, vs_ref, sp_ref,
                  o_ref, m_scr, l_scr, acc_scr, *,
                  bk: int, hq: int, window: Optional[int],
                  softcap: Optional[float], scale: float, fmt: str,
                  packed: bool, d: int, blk: int):
    q = q_ref[0].astype(jnp.float32)                  # (1, d)
    k = _expand_kv_tile(kq_ref[0], ks_ref[0], fmt=fmt, packed=packed,
                        d=d, blk=blk)                 # (bk, d)
    v = _expand_kv_tile(vq_ref[0], vs_ref[0], fmt=fmt, packed=packed,
                        d=d, blk=blk)                 # (bk, d)
    pos = pos_ref[pl.program_id(0) // hq]             # SMEM scalar
    _attend_block(q, k, v, sp_ref[0], pos, o_ref, m_scr, l_scr, acc_scr,
                  window=window, softcap=softcap, scale=scale)


def flash_decode_bhd(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     slot_pos: jax.Array, pos: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None,
                     bk: int = 512,
                     interpret: Optional[bool] = None) -> jax.Array:
    """q (b, hq, d); k/v cache (b, hkv, S, d); slot_pos (b, S) int32;
    pos (b,) int32 -> (b, hq, d).  S padded to bk (empty slots carry
    slot_pos = -1 and mask out)."""
    b, hq, d = q.shape
    hkv, S = k_cache.shape[1], k_cache.shape[2]
    ratio = hq // hkv
    pad = (-S) % bk
    if pad:
        k_cache, v_cache = _pad_s(k_cache, pad), _pad_s(v_cache, pad)
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)),
                           constant_values=-1)
    S_pad = S + pad
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qf = q.reshape(b * hq, 1, d)
    kf = k_cache.reshape(b * hkv, S_pad, d)
    vf = v_cache.reshape(b * hkv, S_pad, d)

    def kv_index(g, j):
        return (g // hq) * hkv + (g % hq) // ratio, j, 0

    kernel = functools.partial(_kernel, bk=bk, hq=hq, window=window,
                               softcap=softcap, scale=scale)
    out = compat.pallas_call(
        kernel,
        grid=(b * hq, S_pad // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # pos
            pl.BlockSpec((1, 1, d), lambda g, j: (g, 0, 0)),    # q
            pl.BlockSpec((1, bk, d), kv_index),                 # k
            pl.BlockSpec((1, bk, d), kv_index),                 # v
            # slot_pos viewed (b, 1, S): (1, bk) tiles meet Mosaic's rule
            pl.BlockSpec((1, 1, bk), lambda g, j: (g // hq, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda g, j: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "arbitrary"),
        interpret=interpret,
    )(pos.astype(jnp.int32), qf, kf, vf, slot_pos[:, None, :])
    return out.reshape(b, hq, d)


def _pad_s(x: jax.Array, pad: int, fill=0) -> jax.Array:
    """Pad axis 2 (the S axis of (b, h, S, ...) arrays) by ``pad``."""
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[2] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def flash_decode_quant_bhd(q: jax.Array,
                           k_q: jax.Array, k_s: jax.Array,
                           v_q: jax.Array, v_s: jax.Array,
                           slot_pos: jax.Array, pos: jax.Array, *,
                           fmt: str,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None,
                           scale: Optional[float] = None,
                           bk: int = 512,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Quantized-KV flash decode: the dequant-in-VMEM leg.

    q (b, hq, d); k_q/v_q (b, hkv, S, stored_d) — nibble/3-byte-group
    packed uint8 codes for sub-byte ``fmt``, container bytes for fp8;
    k_s/v_s (b, hkv, S, d/blk) uint8 e8m0 block-scale codes (the layout
    ``repro.models.attention.init_kv_cache(kv_format=...)`` holds, head/
    seq axes swapped); slot_pos (b, S) int32; pos (b,) int32 ->
    (b, hq, d).  HBM reads per cached token are the true packed bytes +
    1-byte scales; expansion happens on the VMEM tile on the way into
    the dot (``lowbits.decode``/``e8m0_decode``).
    """
    spec = compat.dtype_spec(fmt)
    b, hq, d = q.shape
    hkv, S, stored_d = k_q.shape[1], k_q.shape[2], k_q.shape[3]
    n_blk = k_s.shape[3]
    packed = spec.packed is not None
    if packed:
        ps = spec.packed
        assert stored_d == d // ps.values_per_group * ps.bytes_per_group, \
            (stored_d, d, fmt)
    else:
        assert stored_d == d, (stored_d, d, fmt)
    assert d % n_blk == 0, (d, n_blk)
    blk = d // n_blk
    ratio = hq // hkv
    pad = (-S) % bk
    if pad:
        k_q, v_q = _pad_s(k_q, pad), _pad_s(v_q, pad)
        k_s, v_s = _pad_s(k_s, pad), _pad_s(v_s, pad)
        slot_pos = jnp.pad(slot_pos, ((0, 0), (0, pad)),
                           constant_values=-1)
    S_pad = S + pad
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    qf = q.reshape(b * hq, 1, d)
    kqf = k_q.reshape(b * hkv, S_pad, stored_d)
    ksf = k_s.reshape(b * hkv, S_pad, n_blk)
    vqf = v_q.reshape(b * hkv, S_pad, stored_d)
    vsf = v_s.reshape(b * hkv, S_pad, n_blk)

    def kv_index(g, j):
        return (g // hq) * hkv + (g % hq) // ratio, j, 0

    kernel = functools.partial(
        _quant_kernel, bk=bk, hq=hq, window=window, softcap=softcap,
        scale=scale, fmt=fmt, packed=packed, d=d, blk=blk)
    out = compat.pallas_call(
        kernel,
        grid=(b * hq, S_pad // bk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),                # pos
            pl.BlockSpec((1, 1, d), lambda g, j: (g, 0, 0)),      # q
            pl.BlockSpec((1, bk, stored_d), kv_index),            # k codes
            pl.BlockSpec((1, bk, n_blk), kv_index),               # k scales
            pl.BlockSpec((1, bk, stored_d), kv_index),            # v codes
            pl.BlockSpec((1, bk, n_blk), kv_index),               # v scales
            # slot_pos viewed (b, 1, S): (1, bk) tiles meet Mosaic's rule
            pl.BlockSpec((1, 1, bk), lambda g, j: (g // hq, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda g, j: (g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        dimension_semantics=("parallel", "arbitrary"),
        interpret=interpret,
    )(pos.astype(jnp.int32), qf, kqf, ksf, vqf, vsf, slot_pos[:, None, :])
    return out.reshape(b, hq, d)
