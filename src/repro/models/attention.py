"""Attention: GQA/MQA projections, flash-equivalent chunked softmax
(online-softmax ``lax.scan`` over KV blocks — the XLA-path twin of
``repro.kernels.flash_attention``), sliding windows, logit softcaps, and
ring-buffer KV caches for decode.

Memory behavior is the point: naive attention materializes the (sq, skv)
score matrix — 2 GiB/head at 32k — so every path here is O(sq * chunk).
Softmax statistics are always fp32 (paper §V precision discipline).

Decode at long context is bound by the KV-cache *read* (§VI.D: the KV
bytes, not the weights, dominate HBM traffic past a few k positions), so
the cache supports **quantized storage**: ``init_kv_cache(kv_format=...)``
holds K/V as fp8-container bytes or nibble-packed fp4/fp6 codes plus
1-byte e8m0 block scales along ``head_dim``, and the write paths
(:func:`cache_write_decode` / :func:`cache_write_prefill`) quantize on
the way in — trace-safe ``repro.lowbits`` arithmetic, since decode
writes happen inside a jitted step.  :func:`cache_kv` materializes the
dense view for the XLA oracle; ``repro.kernels.flash_decode`` streams
the packed bytes directly and expands them in VMEM.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat, lowbits
from repro.configs.base import ArchConfig
from repro.models.layers import apply_rope, dense_init
from repro.models.slotstate import mask_rows  # noqa: F401 — re-export;
# the per-slot write discipline lives in repro.models.slotstate now

_NEG_INF = -1.0e30

# Leaf names of a *quantized* ring cache (packed codes + 1-byte e8m0
# scales — see :func:`init_kv_cache`).  Single source of truth shared
# with ``repro.distributed.sharding.cache_rule`` so the mesh placement
# rules cannot drift from the cache layout: payload leaves carry
# (batch, capacity, heads, stored) like dense k/v, and the last dim is
# packed storage (never shardable — sub-byte groups are device-local).
QUANT_KV_LEAVES = ("k_q", "k_s", "v_q", "v_s")


# --------------------------------------------------------------------- #
# Projections
# --------------------------------------------------------------------- #

def init_attention(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads, cfg.head_dim), dtype,
                         fan_in=d),
        "wk": dense_init(ks[1], (d, cfg.n_kv_heads, cfg.head_dim), dtype,
                         fan_in=d),
        "wv": dense_init(ks[2], (d, cfg.n_kv_heads, cfg.head_dim), dtype,
                         fan_in=d),
        "wo": dense_init(ks[3], (cfg.n_heads, cfg.head_dim, d), dtype,
                         fan_in=cfg.n_heads * cfg.head_dim),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads, cfg.head_dim), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads, cfg.head_dim), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads, cfg.head_dim), dtype)
    return p


def project_q(p: dict, x: jax.Array) -> jax.Array:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    return q


def project_kv(p: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bk" in p:
        k, v = k + p["bk"], v + p["bv"]
    return k, v


def project_out(p: dict, o: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"])


# --------------------------------------------------------------------- #
# Core softmax-attention maths (grouped-query layout)
# --------------------------------------------------------------------- #

def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """(b, s, hq, d) -> (b, s, n_kv, group, d)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _scores(q: jax.Array, k: jax.Array, scale: float,
            softcap: Optional[float]) -> jax.Array:
    """q (b,sq,h,g,d) x k (b,sk,h,d) -> fp32 logits (b,h,g,sq,sk).

    Operands stay at their native dtype (bf16 activations feed the MXU
    directly); only the ACCUMULATION is forced fp32.  Explicitly casting
    inputs to fp32 adds no information for bf16-valued activations but
    doubles HBM operand traffic and halves MXU rate (§Perf iteration)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    return s


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: Optional[int]) -> jax.Array:
    """Additive fp32 bias (sq, sk): 0 where visible, -inf-ish elsewhere."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        ok &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: Optional[int] = None,
                   softcap: Optional[float] = None,
                   scale: Optional[float] = None,
                   q_positions: Optional[jax.Array] = None,
                   k_positions: Optional[jax.Array] = None,
                   k_valid: Optional[jax.Array] = None) -> jax.Array:
    """Reference O(sq*sk)-memory attention (oracle + short-seq path).

    q: (b, sq, hq, d); k, v: (b, sk, hkv, d).  Returns (b, sq, hq, d).
    ``k_valid`` (b, sk) bool masks per-row key padding (pooled encoder
    batches pad frames to a fixed enc_len).
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = _group(q, hkv)
    s = _scores(qg, k, scale, softcap)
    q_pos = jnp.arange(sq) if q_positions is None else q_positions
    k_pos = jnp.arange(sk) if k_positions is None else k_positions
    s = s + _mask_bias(q_pos, k_pos, causal, window)
    if k_valid is not None:
        s = jnp.where(k_valid[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      scale: Optional[float] = None,
                      chunk: int = 1024,
                      k_valid: Optional[jax.Array] = None) -> jax.Array:
    """Flash-equivalent attention: ``lax.scan`` over KV chunks with online
    softmax.  O(sq * chunk) live memory instead of O(sq * sk).

    Matches :func:`full_attention` to fp32-accumulation tolerance for any
    chunk size (property-tested).  This is the production XLA path; the
    Pallas twin (``repro.kernels.flash_attention``) additionally tiles sq
    and pins operands in VMEM on real TPUs.
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    if sk % chunk != 0:
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if k_valid is not None:
            k_valid = jnp.pad(k_valid, ((0, 0), (0, pad)))
        sk_pad = sk + pad
    else:
        sk_pad = sk
    if k_valid is None:
        k_valid = jnp.ones((b, sk_pad), bool)
    n_chunks = sk_pad // chunk
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    g = hq // hkv
    qg = _group(q, hkv)                               # (b,sq,h,g,d)
    q_pos = jnp.arange(sq)

    kc = k.reshape(b, n_chunks, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    kvc = k_valid.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def step(carry, inputs):
        m, l, acc = carry
        ci, k_i, v_i, kv_i = inputs
        k_pos = ci * chunk + jnp.arange(chunk)
        s = _scores(qg, k_i, scale, softcap)          # (b,h,g,sq,chunk)
        valid = k_pos < sk                            # mask padding
        bias = _mask_bias(q_pos, k_pos, causal, window)
        bias = jnp.where(valid[None, :], bias, _NEG_INF)
        s = s + bias
        s = jnp.where(kv_i[:, None, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = (acc * corr[..., None]
                   + jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v_i.dtype),
                                v_i, preferred_element_type=jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0), (jnp.arange(n_chunks), kc, vc, kvc))
    l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l[..., None]).transpose(0, 3, 1, 2, 4)  # (b,sq,h,g,d)
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def attention(q, k, v, *, causal=True, window=None, softcap=None,
              scale=None, chunk: int = 1024, k_valid=None):
    """Dispatch: chunked when the KV axis is long enough to matter."""
    if k.shape[1] <= chunk:
        return full_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, scale=scale, k_valid=k_valid)
    return chunked_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, chunk=chunk,
                             k_valid=k_valid)


# --------------------------------------------------------------------- #
# Decode (single new token against a — possibly ring — KV cache)
# --------------------------------------------------------------------- #

def cache_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    slot_pos: jax.Array, q_positions: jax.Array, *,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Attention of ``sq`` query tokens against a (ring) cache.

    q: (b, sq, hq, d); k_cache/v_cache: (b, S, hkv, d);
    slot_pos: (b, S) int32 — absolute position held by each slot, -1 empty;
    q_positions: (b, sq) int32 absolute position of each query token.

    Masking is entirely position-computed (``slot_pos <= q_pos``), so it
    covers both decode (sq=1 attending over history) and chunked prefill
    (sq=chunk attending over history *and* itself causally — a chunk
    token sees earlier chunk tokens because their slots were written
    before this call with smaller absolute positions).
    """
    b, sq, hq, d = q.shape
    hkv = k_cache.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = _group(q, hkv)
    s = _scores(qg, k_cache, scale, softcap)          # (b,h,g,sq,S)
    sp = slot_pos[:, None, :]                         # (b, 1, S)
    qp = q_positions[:, :, None]                      # (b, sq, 1)
    ok = (sp >= 0) & (sp <= qp)                       # (b, sq, S)
    if window is not None:
        ok &= sp > qp - window
    s = jnp.where(ok[:, None, None, :, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, sq, hq, d).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     slot_pos: jax.Array, pos: jax.Array, *,
                     window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     scale: Optional[float] = None) -> jax.Array:
    """One-token attention against a cache (sq=1 :func:`cache_attention`).

    q: (b, 1, hq, d); pos: (b,) per-row current position (continuous
    batching: rows advance independently).  Ring buffers wrap slot_pos.
    """
    return cache_attention(q, k_cache, v_cache, slot_pos, pos[:, None],
                           window=window, softcap=softcap, scale=scale)


# --------------------------------------------------------------------- #
# KV-cache plumbing (capacity = window for local layers — the ring buffer
# is what makes gemma2 long_500k viable: 13 local layers hold 4k slots
# instead of 500k)
# --------------------------------------------------------------------- #

def cache_capacity(max_seq: int, window: Optional[int]) -> int:
    return min(max_seq, window) if window else max_seq


def kv_scale_block(head_dim: int) -> int:
    """Scale-block size along head_dim: the mxfp BLOCK (32) when it
    divides, else the largest power-of-two divisor (reduced smoke
    configs run head_dim 16)."""
    for blk in (32, 16, 8, 4, 2, 1):
        if head_dim % blk == 0:
            return blk
    return 1


def quantize_kv(x: jax.Array, kv_format: str) -> Tuple[jax.Array, jax.Array]:
    """Quantize (..., d) activations into KV-cache storage form.

    Returns (stored, scale_codes):
      * fp8: ``stored`` (..., d) in the registry container dtype,
      * fp4/fp6: ``stored`` (..., d*bits/8) uint8 nibble/3-byte-group
        packed codes (``lowbits.encode_codes`` + ``pack_codes``),
      * ``scale_codes`` (..., d/kv_scale_block(d)) uint8 e8m0 exponents.

    Pure trace-safe arithmetic throughout — this runs inside the jitted
    decode step on every token.
    """
    spec = compat.dtype_spec(kv_format)
    *lead, d = x.shape
    blk = kv_scale_block(d)
    xb = x.astype(jnp.float32).reshape(*lead, d // blk, blk)
    s_codes = lowbits.e8m0_scale_code(jnp.max(jnp.abs(xb), axis=-1),
                                      spec.max_finite)
    vals = xb / lowbits.e8m0_decode(s_codes)[..., None]
    vals = vals.reshape(*lead, d)
    if spec.packed is not None:
        if d % spec.packed.values_per_group:
            raise ValueError(
                f"head_dim {d} not a multiple of {kv_format}'s pack "
                f"group ({spec.packed.values_per_group})")
        stored = lowbits.pack_codes(
            lowbits.encode_codes(vals, kv_format), kv_format)
    else:
        stored = vals.astype(spec.container)
    return stored, s_codes


def dequantize_kv(stored: jax.Array, scale_codes: jax.Array,
                  kv_format: str, head_dim: int,
                  out_dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_kv`: (..., stored) + scale codes ->
    (..., head_dim) dense values.  Same arithmetic the Pallas
    flash-decode leg applies per VMEM tile."""
    spec = compat.dtype_spec(kv_format)
    if spec.packed is not None:
        vals = lowbits.decode(
            lowbits.unpack_codes(stored, kv_format), kv_format)
    else:
        vals = stored.astype(jnp.float32)
    *lead, d = vals.shape
    blk = kv_scale_block(head_dim)
    scales = lowbits.e8m0_decode(scale_codes)
    out = (vals.reshape(*lead, d // blk, blk) * scales[..., None])
    return out.reshape(*lead, d).astype(out_dtype)


def init_kv_cache(batch: int, capacity: int, n_kv: int, head_dim: int,
                  dtype, kv_format: Optional[str] = None) -> dict:
    """Ring-cache pytree.  Dense layout (kv_format None): full-width
    ``k``/``v`` at ``dtype``.  Quantized layout: ``k_q``/``v_q`` stored
    codes + ``k_s``/``v_s`` 1-byte e8m0 scales (see :func:`quantize_kv`);
    fp4 lands at 0.5 + 1/32 ≈ 0.53 B/elem vs 2 B/elem bf16."""
    if kv_format is None:
        return {
            "k": jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
            "v": jnp.zeros((batch, capacity, n_kv, head_dim), dtype),
            "slot_pos": jnp.full((batch, capacity), -1, jnp.int32),
        }
    spec = compat.dtype_spec(kv_format)
    if spec.packed is not None:
        ps = spec.packed
        stored_d = head_dim // ps.values_per_group * ps.bytes_per_group
        stored_dtype = jnp.uint8
    else:
        stored_d = head_dim
        stored_dtype = spec.container
    n_blk = head_dim // kv_scale_block(head_dim)
    z = jnp.zeros((batch, capacity, n_kv, stored_d), stored_dtype)
    s = jnp.zeros((batch, capacity, n_kv, n_blk), jnp.uint8)
    return {"k_q": z, "k_s": s, "v_q": z, "v_s": s,
            "slot_pos": jnp.full((batch, capacity), -1, jnp.int32)}


def is_quantized_cache(cache: dict) -> bool:
    return "k_q" in cache


def cache_kv(cache: dict, kv_format: Optional[str], head_dim: int,
             out_dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """Dense (k, v) view of a cache, dequantizing if stored quantized.

    The XLA decode path materializes this per step (the oracle); the
    Pallas kernel leg (``repro.kernels.flash_decode_quant``) reads the
    packed arrays directly instead."""
    if not is_quantized_cache(cache):
        return cache["k"], cache["v"]
    assert kv_format is not None, "quantized cache needs its kv_format"
    k = dequantize_kv(cache["k_q"], cache["k_s"], kv_format, head_dim,
                      out_dtype)
    v = dequantize_kv(cache["v_q"], cache["v_s"], kv_format, head_dim,
                      out_dtype)
    return k, v


def cache_write_decode(cache: dict, k: jax.Array, v: jax.Array,
                       pos: jax.Array,
                       kv_format: Optional[str] = None,
                       active: Optional[jax.Array] = None,
                       layer: Optional[jax.Array] = None) -> dict:
    """Write one (b, 1, hkv, d) k/v at per-row slot ``pos % capacity``.

    pos: (b,) — rows may sit at different positions (continuous batching),
    so the write is a per-row scatter (one distinct slot per row).
    Quantized caches encode on the way in (trace-safe).

    active: optional (b,) bool — rows where False keep their previous
    slot contents and ``slot_pos`` untouched (inactive pool slots inside
    the fused decode loop must not write; their incoming k/v is garbage
    from a held-constant last_token).

    layer: optional traced scalar — ``cache`` is then the period-stacked
    pool (leaves (n_periods, b, cap, ...)) and the rows land at
    ``[layer, row, pos % cap]``: b rows written in place, the rest of
    the pool untouched."""
    sp_arr = cache["slot_pos"]
    b, cap = sp_arr.shape[-2:]
    slot = (pos % cap).astype(jnp.int32)
    rows = jnp.arange(b)
    idx = (rows, slot) if layer is None else (layer, rows, slot)
    sp = sp_arr.at[idx].set(
        mask_rows(active, pos.astype(jnp.int32), sp_arr[idx]))

    def put(pool, new):
        return pool.at[idx].set(mask_rows(active, new, pool[idx]))

    if is_quantized_cache(cache):
        assert kv_format is not None, "quantized cache needs its kv_format"
        k_q, k_s = quantize_kv(k[:, 0], kv_format)
        v_q, v_s = quantize_kv(v[:, 0], kv_format)
        return {"k_q": put(cache["k_q"], k_q), "k_s": put(cache["k_s"], k_s),
                "v_q": put(cache["v_q"], v_q), "v_s": put(cache["v_s"], v_s),
                "slot_pos": sp}
    return {"k": put(cache["k"], k[:, 0].astype(cache["k"].dtype)),
            "v": put(cache["v"], v[:, 0].astype(cache["v"].dtype)),
            "slot_pos": sp}


def cache_write_chunk(cache: dict, k: jax.Array, v: jax.Array,
                      positions: jax.Array,
                      valid: Optional[jax.Array] = None,
                      kv_format: Optional[str] = None,
                      layer: Optional[jax.Array] = None,
                      slot: Optional[jax.Array] = None) -> dict:
    """Bulk-write a prompt *chunk* (b, s, hkv, d) at absolute
    ``positions`` (s,) into the (ring) cache — the chunked-prefill write.

    Unlike :func:`cache_write_prefill` this does not assume the cache
    starts empty or that positions begin at 0: ``positions`` may start at
    any offset (traced — one compiled executable serves every chunk of
    every prompt) and earlier cache contents outside the chunk survive.
    ``valid`` masks the padded tail of the last chunk (masked positions
    keep their previous contents and slot_pos).  Positions must map to
    distinct ring slots, i.e. s <= capacity (the engine clamps its chunk
    size to the smallest layer capacity).  Quantized caches encode on
    the way in — quantize-on-write, inside the jitted chunk step.

    layer, slot: optional traced scalars — ``cache`` is then the
    period-stacked pool (leaves (n_periods, batch, cap, ...)), the chunk
    is one row's (b == 1), and it lands at ``[layer, slot, positions %
    cap]``: s entries written in place, the rest of the pool untouched.
    """
    cap = cache["slot_pos"].shape[-1]
    slots = (positions % cap).astype(jnp.int32)
    if layer is None:
        idx = (slice(None), slots)
    else:
        idx = (layer, slot, slots)
        k, v = k[0], v[0]
    lead = k.shape[:-2]
    sp_new = jnp.broadcast_to(positions.astype(jnp.int32), lead)
    vmask = None if valid is None else jnp.broadcast_to(valid, lead)
    sp = cache["slot_pos"].at[idx].set(
        mask_rows(vmask, sp_new, cache["slot_pos"][idx]))

    def put(pool, new):
        return pool.at[idx].set(mask_rows(vmask, new, pool[idx]))

    if is_quantized_cache(cache):
        assert kv_format is not None, "quantized cache needs its kv_format"
        k_q, k_s = quantize_kv(k, kv_format)
        v_q, v_s = quantize_kv(v, kv_format)
        return {"k_q": put(cache["k_q"], k_q), "k_s": put(cache["k_s"], k_s),
                "v_q": put(cache["v_q"], v_q), "v_s": put(cache["v_s"], v_s),
                "slot_pos": sp}
    return {"k": put(cache["k"], k.astype(cache["k"].dtype)),
            "v": put(cache["v"], v.astype(cache["v"].dtype)),
            "slot_pos": sp}


def cache_write_rows(cache: dict, k: jax.Array, v: jax.Array,
                     positions: jax.Array,
                     valid: Optional[jax.Array] = None,
                     kv_format: Optional[str] = None) -> dict:
    """Bulk-write (b, s, hkv, d) k/v at PER-ROW absolute ``positions``
    (b, s) into the (ring) cache — the speculative-commit write.

    This is :func:`cache_write_chunk` generalized to per-row positions:
    under continuous batching each slot sits at a different absolute
    position, so committing an accepted speculative prefix is a per-row
    scatter at ``positions % capacity``.  ``valid`` (b, s) masks rejected
    draft tails and inactive rows (masked entries keep their previous
    contents and slot_pos).  Per row, positions must map to distinct
    ring slots (s <= capacity).  Quantized caches encode on the way in.
    """
    sp_arr = cache["slot_pos"]
    b, cap = sp_arr.shape
    s = k.shape[1]
    rows = jnp.arange(b)[:, None]                     # (b, 1)
    slots = (positions % cap).astype(jnp.int32)       # (b, s)
    sp = sp_arr.at[rows, slots].set(
        mask_rows(valid, positions.astype(jnp.int32), sp_arr[rows, slots]))

    def put(pool, new):
        return pool.at[rows, slots].set(
            mask_rows(valid, new, pool[rows, slots]))

    if is_quantized_cache(cache):
        assert kv_format is not None, "quantized cache needs its kv_format"
        k_q, k_s = quantize_kv(k, kv_format)
        v_q, v_s = quantize_kv(v, kv_format)
        return {"k_q": put(cache["k_q"], k_q), "k_s": put(cache["k_s"], k_s),
                "v_q": put(cache["v_q"], v_q), "v_s": put(cache["v_s"], v_s),
                "slot_pos": sp}
    return {"k": put(cache["k"], k.astype(cache["k"].dtype)),
            "v": put(cache["v"], v.astype(cache["v"].dtype)),
            "slot_pos": sp}


def cache_rollback(cache: dict, positions: jax.Array,
                   reject: jax.Array) -> dict:
    """Invalidate rejected speculative writes: a pointer move, no payload
    traffic.

    positions: (b, s) absolute positions that were speculatively written;
    reject: (b, s) bool — True where the write must be undone.  A slot is
    cleared (slot_pos -> -1) only when it STILL holds the rejected
    position (``slot_pos[row, p % cap] == p``) — a slot already
    overwritten by a later accepted position, or never written (inactive
    row), is left alone.  Payload leaves are untouched: a -1 slot_pos
    makes the entry invisible to the position-computed mask in
    :func:`cache_attention`, and the next write at that slot replaces the
    bytes.  Accepts period-stacked caches too (slot_pos (n_p, b, cap))."""
    sp = cache["slot_pos"]
    slots = (positions % sp.shape[-1]).astype(jnp.int32)   # (b, s)
    rows = jnp.arange(positions.shape[0])[:, None]         # (b, 1)
    if sp.ndim == 2:
        cur = sp[rows, slots]                              # (b, s)
        hit = reject & (cur == positions)
        sp = sp.at[rows, slots].set(jnp.where(hit, -1, cur))
    else:
        cur = sp[:, rows, slots]                           # (n_p, b, s)
        hit = reject[None] & (cur == positions[None])
        sp = sp.at[:, rows, slots].set(jnp.where(hit, -1, cur))
    return dict(cache, slot_pos=sp)


def cache_write_prefill(cache: dict, k: jax.Array, v: jax.Array,
                        kv_format: Optional[str] = None) -> dict:
    """Bulk-write a prefill's K/V (b, s, hkv, d) into the (ring) cache.

    Keeps the last ``capacity`` positions; their slots ``p % capacity`` are
    distinct, so the scatter is a permutation (well-defined).  Quantized
    caches encode the kept span on the way in.
    """
    cap = cache["slot_pos"].shape[1]
    s = k.shape[1]
    take = min(s, cap)
    positions = jnp.arange(s - take, s, dtype=jnp.int32)
    slots = positions % cap
    sp = cache["slot_pos"].at[:, slots].set(
        jnp.broadcast_to(positions, (k.shape[0], take)))
    k_t, v_t = k[:, s - take:], v[:, s - take:]
    if is_quantized_cache(cache):
        assert kv_format is not None, "quantized cache needs its kv_format"
        k_q, k_s = quantize_kv(k_t, kv_format)
        v_q, v_s = quantize_kv(v_t, kv_format)
        return {"k_q": cache["k_q"].at[:, slots].set(k_q),
                "k_s": cache["k_s"].at[:, slots].set(k_s),
                "v_q": cache["v_q"].at[:, slots].set(v_q),
                "v_s": cache["v_s"].at[:, slots].set(v_s),
                "slot_pos": sp}
    k_new = cache["k"].at[:, slots].set(k_t.astype(cache["k"].dtype))
    v_new = cache["v"].at[:, slots].set(v_t.astype(cache["v"].dtype))
    return {"k": k_new, "v": v_new, "slot_pos": sp}
