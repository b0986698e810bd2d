"""Block-scaled low-precision matmul (qmatmul), TPU Pallas.

The paper's §V.B subject adapted to TPU (DESIGN.md §3): v5e's MXU has no
FP8/FP6/FP4 pipeline (the paper's own observation that FP4 mma falls back
to the QMMA/FP8 pipeline is the same story one step earlier), so low
precision on TPU is a *storage* format: weights stay quantized in HBM
with e8m0 (power-of-two) block scales — mxfp-style, 32 elements/scale —
and are dequantized to bf16 *inside the kernel*, in VMEM, on the way into
the MXU.  HBM weight traffic drops ~2x (fp8) to ~4x (fp4: the packed
variant below stores true 0.5 B/elem nibbles, fp6 0.75 B/elem — Tab V's
tile packing, accounted as measured bytes by the benchmarks).

Two entry points:

* :func:`qmatmul_mkn` — weights in the registry *container* dtype
  (1 B/elem; the numerical oracle for the packed path),
* :func:`qmatmul_packed_mkn` — weights bit-packed (``repro.lowbits``):
  each k-block loads a nibble/fp6-packed uint8 tile and expands it in
  VMEM (shift/mask/exp2 — no ml_dtypes in-kernel) before the same
  scale-multiply + fp32-accumulator dot, so the two paths are bit-exact.

Layout: x (m, k) bf16; qw (n, k) quantized along k (packed: (n, k*b/8)
uint8); scales (n, k/32) fp32 (power-of-two values = e8m0 content).
Grid (m/bm, n/bn, k/bk), k innermost/arbitrary with an fp32 VMEM
accumulator.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat, lowbits
from repro.serve.quant import BLOCK


def _accumulate(x_ref, s_ref, o_ref, acc, w, *, bk: int):
    """Shared tail of both kernels: scale w, dot, accumulate, emit."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    x = x_ref[...].astype(jnp.float32)                 # (bm, bk)
    # s_ref holds the whole (bn, k/32) scale row (a (bn, bk/32) block
    # breaks Mosaic's (8, 128) tiling rule); pick this k step's columns
    w = w * lowbits.spread_scales(s_ref[...], BLOCK, bk,
                                  first=ki * (bk // BLOCK))
    acc[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _emit():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _kernel(x_ref, qw_ref, s_ref, o_ref, acc, *, bk: int):
    w = qw_ref[...].astype(jnp.float32)                # (bn, bk)
    _accumulate(x_ref, s_ref, o_ref, acc, w, bk=bk)


def _packed_kernel(x_ref, pw_ref, s_ref, o_ref, acc, *, bk: int, fmt: str):
    # (bn, bk*b/8) uint8 -> expand to (bn, bk) fp32 in VMEM
    w = lowbits.unpack_tile(pw_ref[...], fmt)
    _accumulate(x_ref, s_ref, o_ref, acc, w, bk=bk)


def qmatmul_mkn(x: jax.Array, qw: jax.Array, scales: jax.Array, *,
                bm: int = 128, bn: int = 128, bk: int = 256,
                out_dtype=jnp.bfloat16,
                interpret: Optional[bool] = None) -> jax.Array:
    """x (m, k) @ dequant(qw (n, k), scales (n, k/32)).T -> (m, n).

    ``interpret=None`` auto-selects native Mosaic on TPU vs. the Pallas
    interpreter elsewhere (``repro.compat``)."""
    m, k = x.shape
    n = qw.shape[0]
    bk = min(bk, k)
    assert qw.shape == (n, k) and scales.shape == (n, k // BLOCK)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    assert bk % BLOCK == 0
    kernel = functools.partial(_kernel, bk=bk)
    return compat.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, k // BLOCK), lambda i, j, kk: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, qw, scales)


def qmatmul_packed_mkn(x: jax.Array, pw: jax.Array, scales: jax.Array,
                       fmt: str, *,
                       bm: int = 128, bn: int = 128, bk: int = 256,
                       out_dtype=jnp.bfloat16,
                       interpret: Optional[bool] = None) -> jax.Array:
    """Like :func:`qmatmul_mkn` but with bit-packed weight storage.

    ``pw`` is (n, k * bits/8) uint8 out of ``repro.lowbits.pack`` (fp4:
    (n, k/2), fp6: (n, 3k/4)); each k-block tile is expanded to fp32 in
    VMEM before the identical scale/dot/accumulate, so the result is
    bit-exact with the container-storage kernel while the HBM weight
    read is the true packed byte count.
    """
    spec = lowbits.packed_spec(fmt)
    m, k = x.shape
    n = pw.shape[0]
    bk = min(bk, k)
    g, bpg = spec.values_per_group, spec.bytes_per_group
    assert k % g == 0 and bk % g == 0, (k, bk, fmt)
    kb, bkb = k * bpg // g, bk * bpg // g      # packed bytes: total, block
    assert pw.shape == (n, kb) and scales.shape == (n, k // BLOCK), \
        (pw.shape, scales.shape, n, kb)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k)
    assert bk % BLOCK == 0
    kernel = functools.partial(_packed_kernel, bk=bk, fmt=fmt)
    return compat.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bkb), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bn, k // BLOCK), lambda i, j, kk: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        interpret=interpret,
    )(x, pw, scales)
