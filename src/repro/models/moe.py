"""Mixture-of-Experts FFN — dropless, expert-parallel, one layer for every
path (training, prefill, decode, verify).

* The router scores all ``cfg.moe_num_experts`` experts in float32 and
  keeps the top-k; the gates are the softmax over those k logits (equal
  to the top-k softmax probabilities renormalized).
* The layer's weights hold experts ``[0, experts_held)`` of the routed
  ones (all of them by default): it keeps the (token, expert)
  assignments whose expert it holds, sorts them by expert, and runs the
  held experts as one grouped matmul (``jax.lax.ragged_dot``) — each
  held expert's weights are read once, whatever the token count.  The
  gated results are scatter-added per token.  No capacity: no token is
  ever dropped.  In an expert-parallel deployment each chip runs this
  layer over its own experts and the shares are summed across chips;
  the shared expert, which every chip holds, is added unweighted here
  and counted once there.
* Under a training mesh (``cfg.batch_axes`` set, experts on 'model') the
  layer is that deployment: a ``shard_map`` in which each 'model' shard
  runs its own experts on its batch shard's tokens and the shares are
  summed with one ``psum`` over 'model' (expert weights sharded on d_ff
  for FSDP are gathered at the boundary).  The sort and the grouped
  matmul stay local, so the partitioner never sees them.
* Aux losses over the whole router: load balance (Switch eq. 4, on the
  top-1 assignment) and the router z-loss.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import context_mesh, shard_map
from repro.configs.base import ArchConfig
from repro.models.layers import dense_init, init_mlp, apply_mlp

_ROUTED = ("w1", "w2", "w3")


def init_moe(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, f, e = cfg.d_model, cfg.expert_d_ff, cfg.experts_held
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, cfg.moe_num_experts), jnp.float32,
                             fan_in=d),
        "w1": dense_init(ks[1], (e, d, f), dtype, fan_in=d),
        "w2": dense_init(ks[2], (e, f, d), dtype, fan_in=f),
    }
    if cfg.mlp_variant in ("swiglu", "geglu"):
        p["w3"] = dense_init(ks[3], (e, d, f), dtype, fan_in=d)
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(ks[4], d, cfg.shared_d_ff, cfg.mlp_variant,
                               dtype)
    return p


def _grouped_ffn(p: dict, rows: jax.Array, sizes: jax.Array,
                 variant: str) -> jax.Array:
    """rows (m, d), sorted by held expert with ``sizes`` (e,) rows each,
    through each row's expert.  Rows past ``sum(sizes)`` are undefined:
    the TPU's grouped matmul does not write them."""
    h = jax.lax.ragged_dot(rows, p["w1"], sizes)
    if variant == "swiglu":
        h = jax.nn.silu(h) * jax.lax.ragged_dot(rows, p["w3"], sizes)
    elif variant == "geglu":
        h = jax.nn.gelu(h, approximate=True) \
            * jax.lax.ragged_dot(rows, p["w3"], sizes)
    else:
        h = jax.nn.gelu(h, approximate=True)
    return jax.lax.ragged_dot(h, p["w2"], sizes)


def _held(p: dict, xt: jax.Array, idx: jax.Array, gate: jax.Array,
          variant: str) -> jax.Array:
    """The routed experts' part for tokens xt (t, d) with top-k experts
    ``idx`` and gates ``gate`` (t, k): the experts ``p`` holds are
    ``[0, held)``; assignments outside that range are not this layer's.
    Returns (t, d) float32."""
    (t, d), (held, k) = xt.shape, (p["w1"].shape[0], idx.shape[-1])
    flat = idx.reshape(-1)
    mine = (flat >= 0) & (flat < held)
    # assignments of held experts first, sorted by expert; the rest get
    # the sentinel group ``held`` and sort last, outside every group
    group = jnp.where(mine, flat, held)
    order = jnp.argsort(group, stable=True)
    sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
    out = _grouped_ffn(p, xt[order // k], sizes, variant)
    # back to (token, k) order, the other experts' rows zeroed, and the
    # gated sum over k accumulated in float32
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k))
    out = jnp.where(mine[:, None], out[back], 0).reshape(t, k, d)
    return jnp.einsum("tkd,tk->td", out, gate,
                      preferred_element_type=jnp.float32)


def _expert_mesh(cfg: ArchConfig):
    """The training mesh whose 'model' axis splits the held experts, or
    None (one device, a serving engine's in/out shardings, or experts
    that do not divide over 'model')."""
    if not cfg.batch_axes:
        return None
    mesh = context_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    n = mesh.shape["model"]
    return mesh if n > 1 and cfg.experts_held % n == 0 else None


def _held_over_mesh(p: dict, xt: jax.Array, idx: jax.Array,
                    gate: jax.Array, cfg: ArchConfig, mesh) -> jax.Array:
    """``_held`` as expert parallelism over ``mesh``'s 'model' axis:
    shard j holds experts ``[j * n, (j + 1) * n)`` and computes their
    part for the tokens of its batch shard; the parts are summed over
    'model'."""
    n = cfg.experts_held // mesh.shape["model"]
    dp = tuple(cfg.batch_axes)
    tok = P(dp[0] if len(dp) == 1 else dp)
    routed = {w: p[w] for w in _ROUTED if w in p}

    def share(w, xt, idx, gate):
        first = jax.lax.axis_index("model") * n
        # the tokens and gates are the same on every 'model' shard but
        # each shard's gradient for them is its own part: marking them
        # varying makes the backward pass sum those parts over 'model'
        xt, gate = jax.lax.pcast((xt, gate), "model", to="varying")
        y = _held(w, xt, idx - first, gate, cfg.mlp_variant)
        return jax.lax.psum(y, "model")

    return shard_map(share, mesh=mesh,
                     in_specs=({w: P("model") for w in routed}, tok, tok,
                               tok),
                     out_specs=tok)(routed, xt, idx, gate)


def apply_moe(p: dict, x: jax.Array, cfg: ArchConfig
              ) -> Tuple[jax.Array, dict]:
    """MoE FFN over the held experts.  x: (b, s, d) -> (y, aux) with
    aux = {moe_lb_loss, moe_z_loss}."""
    b, s, d = x.shape
    e, k = cfg.moe_num_experts, cfg.moe_top_k
    xt = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    top, idx = jax.lax.top_k(logits, k)                  # (t, k)
    gate = jax.nn.softmax(top, axis=-1)

    mesh = _expert_mesh(cfg)
    if mesh is None:
        y = _held(p, xt, idx, gate, cfg.mlp_variant)
    else:
        y = _held_over_mesh(p, xt, idx, gate, cfg, mesh)
    y = y.astype(x.dtype).reshape(b, s, d)

    if "shared" in p:
        y = y + apply_mlp(p["shared"], x, cfg.mlp_variant)

    # --- aux losses (Switch eq.4 load balance + z-loss) ---
    probs = jax.nn.softmax(logits, axis=-1)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), 0)
    lb_loss = e * jnp.sum(density * jnp.mean(probs, axis=0))
    z_loss = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return y, {"moe_lb_loss": lb_loss, "moe_z_loss": z_loss}
