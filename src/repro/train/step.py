"""Train step builder.

One jitted ``train_step(state, batch) -> (state, metrics)`` per
(arch x shape), with:

* fp32 cross-entropy (+ router aux losses for MoE archs),
* gradient accumulation as a ``lax.scan`` over microbatches — the carry
  holds fp32 gradient sums, so the dry-run memory analysis reflects the
  real activation footprint of one microbatch, not the whole global batch,
* global-norm clipping + AdamW inside (see ``repro.optim``),
* state donation handled at the jit call site (launch/dryrun, launch/train).

The loss slices the trunk logits to the *text* positions (VLM trunks carry
a patch prefix) and shifts by one for next-token prediction.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.optim import AdamWConfig, adamw_init, adamw_update

MOE_LB_WEIGHT = 0.01
MOE_Z_WEIGHT = 0.001


def cross_entropy_loss(logits: jax.Array, targets: jax.Array,
                       mask: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """Mean token CE (fp32) and accuracy.  logits (b,s,v), targets (b,s)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    acc = (jnp.argmax(logits, axis=-1) == targets).astype(jnp.float32)
    if mask is None:
        return jnp.mean(nll), jnp.mean(acc)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / denom, jnp.sum(acc * mask) / denom


def chunked_cross_entropy(features: jax.Array, w_out: jax.Array,
                          targets: jax.Array,
                          mask: Optional[jax.Array] = None,
                          softcap: Optional[float] = None,
                          chunk: int = 2048
                          ) -> Tuple[jax.Array, jax.Array]:
    """CE without materializing (b, s, vocab) logits.

    Scans sequence chunks; each chunk's logits ((b, chunk, v) fp32) live
    only inside a rematted step, so peak memory is O(b*chunk*v) instead
    of O(b*s*v) — at 150k vocabs this is the difference between ~5 GiB
    and ~150 MiB per device (EXPERIMENTS.md §Perf iteration 0).

    features (b, s, d), targets (b, s); returns (mean nll, accuracy).
    """
    b, s, d = features.shape
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    if s % chunk != 0:
        pad = chunk - s % chunk
        features = jnp.pad(features, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
        s += pad
    n_c = s // chunk
    xc = features.reshape(b, n_c, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n_c, chunk).transpose(1, 0, 2)
    mc = mask.reshape(b, n_c, chunk).transpose(1, 0, 2)

    def step(carry, inp):
        nll_sum, acc_sum, tok_sum = carry
        x_i, t_i, m_i = inp
        logits = jnp.einsum("bsd,dv->bsv", x_i.astype(jnp.float32),
                            w_out.astype(jnp.float32))
        if softcap is not None:
            logits = jnp.tanh(logits / softcap) * softcap
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_i[..., None], axis=-1)[..., 0]
        hit = (jnp.argmax(logits, axis=-1) == t_i).astype(jnp.float32)
        return (nll_sum + jnp.sum((logz - gold) * m_i),
                acc_sum + jnp.sum(hit * m_i),
                tok_sum + jnp.sum(m_i)), None

    step = jax.checkpoint(
        step, policy=jax.checkpoint_policies.nothing_saveable)
    (nll, acc, toks), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32),) * 3, (xc, tc, mc))
    toks = jnp.maximum(toks, 1.0)
    return nll / toks, acc / toks


def make_loss_fn(model: Model, ce_chunk: int = 2048) -> Callable:
    cfg = model.cfg

    def loss_fn(params: Any, batch: Dict[str, jax.Array]):
        features, aux = model.features(params, batch)
        tokens = batch["tokens"]
        features = features[:, -tokens.shape[1]:]      # text positions only
        mask = batch.get("loss_mask")
        mask = mask[:, 1:] if mask is not None else None
        ce, acc = chunked_cross_entropy(
            features[:, :-1], model.unembed_weight(params),
            tokens[:, 1:], mask, softcap=cfg.final_logit_softcap,
            chunk=min(ce_chunk, max(tokens.shape[1] - 1, 1)))
        loss = (ce + MOE_LB_WEIGHT * aux["moe_lb_loss"]
                + MOE_Z_WEIGHT * aux["moe_z_loss"])
        metrics = {"loss": loss, "ce": ce, "acc": acc, **aux}
        return loss, metrics
    return loss_fn


def train_state_init(model: Model, opt_cfg: AdamWConfig, key: jax.Array
                     ) -> dict:
    params = model.init(key)
    return {"params": params, "opt": adamw_init(opt_cfg, params)}


def _microbatch(batch: Dict[str, jax.Array], accum: int,
                dp_axes: Optional[tuple] = None) -> Dict[str, jax.Array]:
    """(b, ...) -> (accum, b/accum, ...), microbatch-major.

    The reshape splits the sharded batch dim; XLA's propagation can pick
    the WRONG factor (sharding the accum dim => replicating the batch and
    silently voiding the accumulation's memory win — caught by the
    dry-run memory analysis), so when ``dp_axes`` is given we pin the
    microbatch dim's sharding explicitly."""
    from jax.sharding import PartitionSpec as P

    def r(x):
        b = x.shape[0]
        assert b % accum == 0, f"batch {b} % accum {accum} != 0"
        out = x.reshape(accum, b // accum, *x.shape[1:])
        if dp_axes:
            spec = P(None, dp_axes, *(None for _ in x.shape[1:]))
            out = jax.lax.with_sharding_constraint(out, spec)
        return out
    return jax.tree.map(r, batch)


def make_train_step(model: Model, opt_cfg: AdamWConfig,
                    accum_steps: int = 1,
                    dp_axes: Optional[tuple] = None,
                    accum_dtype: str = "float32") -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    ``accum_dtype="bfloat16"`` halves the per-microbatch weight-gradient
    psum/regather traffic that XLA SPMD emits inside the accumulation
    scan — for the 1T-param MoE cell that traffic is ~2 TB/device/step
    at fp32 (§Perf iteration; the full fix is shard_map-local DP)."""
    loss_fn = make_loss_fn(model)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    acc_dt = jnp.dtype(accum_dtype)

    def train_step(state: dict, batch: Dict[str, jax.Array]):
        params = state["params"]
        if accum_steps == 1:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = _microbatch(batch, accum_steps, dp_axes)

            def accum_fn(carry, mb):
                g_sum, m_sum = carry
                (_, m), g = grad_fn(params, mb)
                g_sum = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dt), g_sum, g)
                m_sum = jax.tree.map(lambda a, b: a + b, m_sum, m)
                return (g_sum, m_sum), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), params)
            m0 = {k: jnp.zeros((), jnp.float32)
                  for k in ("loss", "ce", "acc", "moe_lb_loss",
                            "moe_z_loss")}
            (g_sum, m_sum), _ = jax.lax.scan(accum_fn, (g0, m0), micro)
            grads = jax.tree.map(lambda g: g / accum_steps, g_sum)
            metrics = jax.tree.map(lambda m: m / accum_steps, m_sum)

        new_params, new_opt = adamw_update(opt_cfg, params, grads,
                                           state["opt"])
        metrics = dict(metrics)
        metrics["grad_norm"] = jax.tree.reduce(
            lambda a, b: a + b,
            jax.tree.map(lambda g: jnp.sum(jnp.square(
                g.astype(jnp.float32))), grads)) ** 0.5
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step
