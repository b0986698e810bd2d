"""Explicit data-parallel trainer via shard_map — deferred gradient
reduction + int8-compressed all-reduce.

The auto-SPMD (pjit) trainer re-reduces weight gradients on EVERY
microbatch of the accumulation scan (§Perf K3: ~2 TB/device/step of dw
all-reduce on the 1T MoE cell; 8x the necessary wire bytes at accum=8).
XLA cannot express "accumulate unreduced partial gradients" under jit —
shard_map can: each data shard accumulates LOCAL gradients across all its
microbatches and the reduction happens ONCE, optionally int8-quantized
with stochastic rounding (2x wire vs fp32; unbiased — see
repro.distributed.compression).

Scope: replicated-parameter DP (no TP/FSDP inside the shard_map), i.e.
models whose params fit one device — the right tool for the <=3B archs on
data-only meshes, and the measurement vehicle for the deferred-reduction
collective win (benchmarks/collectives_bench.py).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.distributed.compression import compressed_psum_tree
from repro.models.model import Model
from repro.optim import AdamWConfig, adamw_update
from repro.train.step import make_loss_fn


def make_local_dp_train_step(
    model: Model,
    opt_cfg: AdamWConfig,
    mesh: Mesh,
    axis: str = "data",
    accum_steps: int = 1,
    compress: bool = False,
    seed: int = 0,
) -> Callable:
    """train_step(state, batch) -> (state, metrics), shard_map-DP.

    state is replicated; batch dim 0 is sharded over ``axis``.  Gradients
    are accumulated locally (fp32) over ``accum_steps`` microbatches and
    reduced exactly once.
    """
    loss_fn = make_loss_fn(model)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    world = int(mesh.shape[axis])

    def local_step(state, batch, key):
        params = state["params"]

        def micro(batch_i):
            (_, m), g = grad_fn(params, batch_i)
            return g, m

        if accum_steps == 1:
            grads, metrics = micro(batch)
            grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps,
                                    *x.shape[1:]), batch)

            def acc(carry, b_i):
                g_sum, m_sum = carry
                g, m = micro(b_i)
                return (jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_sum, g),
                    jax.tree.map(jnp.add, m_sum, m)), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            m0 = {k: jnp.zeros((), jnp.float32)
                  for k in ("loss", "ce", "acc", "moe_lb_loss",
                            "moe_z_loss")}
            (g_sum, m_sum), _ = jax.lax.scan(acc, (g0, m0), mb)
            grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
            metrics = jax.tree.map(lambda m: m / accum_steps, m_sum)

        # THE deferred reduction: exactly one collective per step
        if compress:
            grads = compressed_psum_tree(grads, key, axis, world)
        else:
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g, axis), grads)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axis), metrics)

        new_params, new_opt = adamw_update(opt_cfg, params, grads,
                                           state["opt"])
        metrics = dict(metrics)
        metrics["grad_norm"] = jax.tree.reduce(
            jnp.add, jax.tree.map(
                lambda g: jnp.sum(jnp.square(g.astype(jnp.float32))),
                grads)) ** 0.5
        return {"params": new_params, "opt": new_opt}, metrics

    batch_spec = P(axis)
    mapped = shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P()),
        check_vma=False,
    )

    @jax.jit
    def train_step(state, batch):
        step_no = state["opt"]["step"]
        key = jax.random.fold_in(jax.random.PRNGKey(seed), step_no)
        return mapped(state, batch, key)

    return train_step
