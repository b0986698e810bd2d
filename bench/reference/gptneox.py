"""Plain reference for the GPT-NeoX-shaped decoder the program serves.

Equations (the block as the program implements it; the departures from
GPT-NeoX are listed in the configuration file under ``reduced``):

    x   = E[tokens]
    per layer:  h = rms(x) * g1;  q, k, v = h Wq, h Wk, h Wv
                q, k = rope(q), rope(k)   (split halves, theta, all dims)
                x = x + softmax(q k^T / sqrt(dh) + causal) v Wo
                h = rms(x) * g2;  x = x + gelu_tanh(h W1) W2
    logits = (rms(x) * gf) U

Written from these equations alone: it imports nothing of the program
and computes in float32 at ``highest`` matmul precision, one layer at a
time.  ``init_weights`` makes the weights from the seed, in the tree
layout and dtypes the program takes them in.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness import lowp


def arch_fields(cfg: dict) -> dict:
    """The program's ``ArchConfig`` fields this configuration fixes."""
    return {"n_layers": cfg["num_hidden_layers"],
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_attention_heads"],
            "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "mlp_variant": "gelu",
            "rope_theta": float(cfg["rotary_emb_base"]),
            "norm_eps": cfg["layer_norm_eps"],
            "tie_embeddings": False,
            "param_dtype": cfg["torch_dtype"],
            "compute_dtype": cfg["torch_dtype"]}


def init_weights(cfg: dict, key: jax.Array) -> dict:
    L, d = cfg["num_hidden_layers"], cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    dh, f, V = d // h, cfg["intermediate_size"], cfg["vocab_size"]
    dt = jnp.dtype(cfg["torch_dtype"])
    ks = iter(jax.random.split(key, 8))

    def w(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    ones = lambda *s: jnp.ones(s, dt)
    return {
        "embed": w((V, d), d),
        "unembed": w((d, V), d),
        "final_norm": ones(d),
        "layers": {"pos0": {
            "ln_mix": ones(L, d),
            "attn": {"wq": w((L, d, h, dh), d), "wk": w((L, d, h, dh), d),
                     "wv": w((L, d, h, dh), d),
                     "wo": w((L, h, dh, d), h * dh)},
            "ln_ffn": ones(L, d),
            "mlp": {"w1": w((L, d, f), d), "w2": w((L, f, d), f)},
        }},
    }


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (s, h, dh): rotate the pairs (x[:dh/2], x[dh/2:]) by position."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def logits(cfg: dict, params: dict, tokens: jax.Array,
           control: bool = False) -> jax.Array:
    """Logits (s, vocab) float32 at every position of ``tokens`` (s,).

    ``control``: the same equations with every matrix stored in fp8
    (e4m3, one scale per tensor) and activations in bfloat16 -- the
    precision step below the configuration's bfloat16."""
    eps, theta = cfg["layer_norm_eps"], float(cfg["rotary_emb_base"])
    act = lowp.activation_dtype(control)
    mat = lambda a: lowp.weight(a, control)
    p = params["layers"]["pos0"]
    s = tokens.shape[0]
    x = mat(params["embed"])[tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, lp):
        h = _rms(x, lp["ln_mix"].astype(jnp.float32), eps).astype(act)
        a = lp["attn"]
        q = jnp.einsum("sd,dhk->shk", h, mat(a["wq"]),
                       preferred_element_type=jnp.float32)
        k = jnp.einsum("sd,dhk->shk", h, mat(a["wk"]),
                       preferred_element_type=jnp.float32)
        v = jnp.einsum("sd,dhk->shk", h, mat(a["wv"]),
                       preferred_element_type=jnp.float32)
        q, k = _rope(q, theta), _rope(k, theta)
        sc = jnp.einsum("qhk,shk->hqs", q.astype(act), k.astype(act),
                        preferred_element_type=jnp.float32)
        sc = jnp.where(causal, sc / math.sqrt(q.shape[-1]), -jnp.inf)
        o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(sc, -1).astype(act),
                       v.astype(act), preferred_element_type=jnp.float32)
        x = x + jnp.einsum("qhk,hkd->qd", o.astype(act), mat(a["wo"]),
                           preferred_element_type=jnp.float32)
        h = _rms(x, lp["ln_ffn"].astype(jnp.float32), eps).astype(act)
        u = jnp.einsum("sd,df->sf", h, mat(lp["mlp"]["w1"]),
                       preferred_element_type=jnp.float32)
        u = jax.nn.gelu(u, approximate=True).astype(act)
        x = x + jnp.einsum("sf,fd->sd", u, mat(lp["mlp"]["w2"]),
                           preferred_element_type=jnp.float32)
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, p)
        x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
        return jnp.einsum("sd,dv->sv", x.astype(act),
                          mat(params["unembed"]),
                          preferred_element_type=jnp.float32)
