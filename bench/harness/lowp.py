"""Precision of the references and of their control.

The reference reads every weight as float32 (exact for bfloat16
weights) and keeps activations in float32.  The control is the same
reference one precision step below the configurations' bfloat16: each
weight stored as fp8 e4m3 with one scale per tensor (max |w| / 448), and
activations in bfloat16 -- what an fp8 weight store would serve.
"""

import jax.numpy as jnp

FP8_MAX = 448.0


def activation_dtype(control: bool):
    return jnp.bfloat16 if control else jnp.float32


def weight(w, control: bool):
    if not control:
        return w.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w32)) / FP8_MAX
    q = (w32 / scale).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
