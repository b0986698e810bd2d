"""MoE: the dropless grouped dispatch vs a naive per-token oracle, gate
normalization, aux losses, and the layer over a training mesh.  The
expert-parallel shares and the all-to-one router are pinned in
``tests/test_granite.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import KIMI_K2
from repro.models import moe as M
from repro.models.layers import apply_mlp


def _cfg(**kw):
    base = KIMI_K2.reduced()   # 4 experts, top-2, swiglu, shared expert
    return dataclasses.replace(base, d_model=16, moe_d_ff=32, **kw)


def _naive_moe(p, x, cfg):
    """Per-token oracle: every expert on every token, gated."""
    b, s, d = x.shape
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    out = jnp.zeros_like(x, jnp.float32)
    for e in range(cfg.moe_num_experts):
        pe = {"w1": p["w1"][e], "w2": p["w2"][e], "w3": p["w3"][e]}
        ye = apply_mlp(pe, x, cfg.mlp_variant).astype(jnp.float32)
        w_e = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)
        out = out + ye * w_e[..., None]
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, cfg.mlp_variant)
    return out.astype(x.dtype)


def test_moe_matches_naive_oracle_when_no_drops(key):
    cfg = _cfg()
    p = M.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model)) * 0.5
    got, aux = M.apply_moe(p, x, cfg)
    want = _naive_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert set(aux) == {"moe_lb_loss", "moe_z_loss"}


def test_lb_loss_minimal_for_uniform_router(key):
    """A uniform router gives lb_loss == 1 (the Switch minimum)."""
    cfg = _cfg()
    p = M.init_moe(key, cfg, jnp.float32)
    p = dict(p, router=jnp.zeros_like(p["router"]))
    x = jax.random.normal(key, (4, 64, cfg.d_model))
    _, aux = M.apply_moe(p, x, cfg)
    assert abs(float(aux["moe_lb_loss"]) - 1.0) < 0.2


def test_gate_renormalization(key):
    """Top-k gates sum to 1 per token."""
    cfg = _cfg()
    p = M.init_moe(key, cfg, jnp.float32)
    x = jnp.zeros((1, 8, cfg.d_model))
    # zero input -> expert outputs all equal -> output equals one expert's
    got, _ = M.apply_moe(p, x, cfg)
    want = _naive_moe(p, x, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_rows_past_the_held_assignments_are_never_read(key, monkeypatch):
    """The TPU's grouped matmul leaves the rows past ``sum(group_sizes)``
    unwritten; the layer must not let them reach its output.  With those
    rows NaN, and with only some experts held (the other assignments sort
    there), the output is the one an exact grouped matmul gives."""
    cfg = _cfg(moe_num_experts=8, moe_top_k=3, moe_experts_held=4)
    p = M.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    want, _ = M.apply_moe(p, x, cfg)
    exact = jax.lax.ragged_dot

    def unwritten(lhs, rhs, sizes, **kw):
        out = exact(lhs, rhs, sizes, **kw)
        past = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
        return jnp.where(past[:, None], jnp.nan, out)

    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    got, _ = M.apply_moe(p, x, cfg)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_expert_parallel_layer_matches_one_device():
    """Under a (data 2, model 2) training mesh the layer runs as expert
    parallelism in a shard_map and gives the one-device output, loss and
    gradients (``tests/sharded_cases.py::moe_expert_parallel``, on 4
    host devices in a subprocess)."""
    from test_serve_sharded import _run_case
    _run_case("moe_expert_parallel")
