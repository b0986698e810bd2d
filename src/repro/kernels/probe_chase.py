"""Pointer-chase memory probe, TPU Pallas — the paper's §VI.A (Fig 6).

The paper walks a random permutation with serialized dependent loads to
expose each cache level's load-to-use latency.  TPU adaptation: the
permutation lives in a VMEM-resident (rows, 128) int32 buffer; each step
loads row ``idx`` and takes lane 0 as the next index — a serialized
VMEM-load chain.  Sweeping ``rows`` across the VMEM capacity boundary (and
running the jnp twin over HBM-sized buffers) reproduces the hierarchy-walk
methodology; on CPU the same sweep walks the host L1/L2/L3 (the
methodology-validation plot in benchmarks/fig6_memory.py).

Validated against a numpy chase in interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat


def _kernel(buf_ref, o_ref, *, steps: int):
    def body(_, idx):
        row = buf_ref[idx]                 # dependent VMEM load
        return row[0]

    idx = jax.lax.fori_loop(0, steps, body, jnp.int32(0))
    o_ref[0, 0] = idx


def chase(buf: jax.Array, steps: int,
          interpret: Optional[bool] = None) -> jax.Array:
    """buf (rows, 128) int32 — buf[i, 0] = next row.  Returns final index."""
    kernel = functools.partial(_kernel, steps=steps)
    return compat.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(buf.shape, lambda: (0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),  # scalar result
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        interpret=interpret,
    )(buf)[0, 0]


def make_chase_buffer(rows: int, seed: int = 0) -> jax.Array:
    """Random single-cycle permutation broadcast across 128 lanes."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(rows - 1) + 1     # cycle visiting every row
    nxt = np.zeros(rows, np.int32)
    cur = 0
    for p in perm:
        nxt[cur] = p
        cur = p
    nxt[cur] = 0
    return jnp.asarray(np.broadcast_to(nxt[:, None], (rows, 128)).copy())


def chase_reference(buf: np.ndarray, steps: int) -> int:
    idx = 0
    col = np.asarray(buf)[:, 0]
    for _ in range(steps):
        idx = int(col[idx])
    return idx
