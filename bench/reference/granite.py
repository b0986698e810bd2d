"""Plain reference for IBM Granite 4.0-H (HF ``GraniteMoeHybridForCausalLM``)
as one chip's share of an expert-parallel deployment.

Equations (``layer_types`` picks each layer's mixer; ``rm`` is
``residual_multiplier``):

    x = embedding_multiplier * E[tokens]
    per layer:  x = x + rm * mixer(rms(x) * g1)
                h = rms(x) * g2
                x = x + rm * (moe(h) + shared(h))
    logits = ((rms(x) * gf) E^T) / logits_scaling     (tied embeddings)

    attention: GQA, no positional encoding (NoPE), causal,
               softmax(q k^T * attention_multiplier) v Wo
    mamba:     the Mamba-2 layer of ``reference.mamba2`` (one B/C group)
    moe:       r = h R over all num_experts_routed experts;
               top-k of r, gates = softmax over those k logits;
               sum over the top-k experts that this chip holds
               (the first num_local_experts) of
               gate_e * W2_e(silu(W1_e h) * W3_e h)
    shared:    W2(silu(W1 h) * W3 h), added unweighted

What the experts this chip does not hold would add is left out, as the
program leaves it out.  Float32 throughout at ``highest`` matmul
precision; of the program it imports nothing, and of the references only
``reference.mamba2``'s norm, convolution and recurrence.  ``init_weights``
makes the weights from the seed in the program's tree layout and dtypes
(the router, A_log, dt_bias and D are float32 there).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness import lowp
from reference import mamba2

BLOCK = mamba2.BLOCK
_rms = mamba2._rms


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    L = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:L]
    attn = [i for i, t in enumerate(types) if t == "attention"]
    period = attn[1] - attn[0] if len(attn) > 1 else L
    pattern = types[:period]
    if types != pattern * (L // period):
        raise ValueError(f"layer_types[:{L}] is not a repeated period")
    d_in = cfg["mamba_expand"] * d
    return {"L": L, "d": d, "h": h, "kv": cfg["num_key_value_heads"],
            "dh": d // h, "V": cfg["vocab_size"], "d_in": d_in,
            "n": cfg["mamba_d_state"], "p": cfg["mamba_d_head"],
            "mh": cfg["mamba_n_heads"], "k": cfg["mamba_d_conv"],
            "f": cfg["intermediate_size"],
            "fs": cfg["shared_intermediate_size"],
            "E": cfg["num_experts_routed"], "held": cfg["num_local_experts"],
            "top": cfg["num_experts_per_tok"],
            "pattern": pattern, "periods": L // period}


def arch_fields(cfg: dict) -> dict:
    """The program's ``ArchConfig`` fields this configuration fixes."""
    m = dims(cfg)
    attn = m["pattern"].index("attention")
    return {"n_layers": m["L"], "d_model": m["d"], "n_heads": m["h"],
            "n_kv_heads": m["kv"], "head_dim": m["dh"],
            "vocab_size": m["V"], "mlp_variant": "swiglu",
            "norm_eps": cfg["rms_norm_eps"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "embedding_multiplier": cfg["embedding_multiplier"],
            "residual_multiplier": cfg["residual_multiplier"],
            "logits_scaling": cfg["logits_scaling"],
            "attn_scale": cfg["attention_multiplier"],
            "use_rope": cfg["position_embedding_type"] != "nope",
            "attn_every": len(m["pattern"]), "attn_offset": attn,
            "moe_num_experts": m["E"], "moe_top_k": m["top"],
            "moe_experts_held": m["held"], "moe_d_ff": m["f"],
            "moe_shared_expert": True, "moe_shared_d_ff": m["fs"],
            "ssm_state": m["n"], "ssm_expand": cfg["mamba_expand"],
            "ssm_head_dim": m["p"], "ssm_conv": m["k"],
            "ssm_chunk": cfg["mamba_chunk_size"],
            "param_dtype": cfg["torch_dtype"],
            "compute_dtype": cfg["torch_dtype"]}


def init_weights(cfg: dict, key: jax.Array) -> dict:
    m = dims(cfg)
    n_p, d, d_in, n, mh, k = (m[x] for x in "periods d d_in n mh k".split())
    dt_ = jnp.dtype(cfg["torch_dtype"])
    ks = iter(jax.random.split(key, 24 * len(m["pattern"]) + 2))

    def w(shape, fan_in, dtype=dt_):
        return (jax.random.normal(next(ks), (n_p,) + shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dtype)

    ones = lambda *s: jnp.ones((n_p,) + s, dt_)
    zeros = lambda *s: jnp.zeros((n_p,) + s, dt_)

    def mamba():
        # A in [1, 16] and dt in [1e-3, 1e-1] (log-uniform), as Mamba-2 draws
        a = jax.random.uniform(next(ks), (n_p, mh), jnp.float32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(next(ks), (n_p, mh), jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return {"wz": w((d, d_in), d), "wx": w((d, d_in), d),
                "wb": w((d, n), d), "wc": w((d, n), d), "wdt": w((d, mh), d),
                "conv_x_w": w((d_in, k), k), "conv_x_b": zeros(d_in),
                "conv_b_w": w((n, k), k), "conv_b_b": zeros(n),
                "conv_c_w": w((n, k), k), "conv_c_b": zeros(n),
                "A_log": jnp.log(a), "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((n_p, mh), jnp.float32),
                "gate_norm": ones(d_in), "out_proj": w((d_in, d), d_in)}

    def attention():
        h, kv, dh = m["h"], m["kv"], m["dh"]
        return {"wq": w((d, h, dh), d), "wk": w((d, kv, dh), d),
                "wv": w((d, kv, dh), d), "wo": w((h, dh, d), h * dh)}

    def swiglu(f, lead=()):
        return {"w1": w(lead + (d, f), d), "w2": w(lead + (f, d), f),
                "w3": w(lead + (d, f), d)}

    layers = {}
    for i, kind in enumerate(m["pattern"]):
        mixer = ("attn", attention()) if kind == "attention" \
            else ("ssm", mamba())
        layers[f"pos{i}"] = {
            "ln_mix": ones(d), mixer[0]: mixer[1], "ln_ffn": ones(d),
            "moe": {"router": w((d, m["E"]), d, jnp.float32),
                    **swiglu(m["f"], (m["held"],)),
                    "shared": swiglu(m["fs"])}}
    # rows at 1 / (embedding_multiplier sqrt(d)): the scaled embedding then
    # has the unit size the other references' inputs have.  At 1/sqrt(d)
    # the 12x input would outweigh what 20 layers add at 0.22 each, and
    # the tied unembedding would put each position's own token first.
    emb_std = 1.0 / (cfg["embedding_multiplier"] * math.sqrt(d))
    return {"embed": (jax.random.normal(next(ks), (m["V"], d), jnp.float32)
                      * emb_std).astype(dt_),
            "final_norm": jnp.ones((d,), dt_),
            "layers": layers}


def _mamba(m, q, h, eps, act, mat):
    """The Mamba-2 layer on h (s, d), as ``reference.mamba2`` writes it."""
    s = h.shape[0]
    f32 = lambda t: t.astype(jnp.float32)
    proj = lambda wt: jnp.einsum("sd,de->se", h, mat(q[wt]),
                                 preferred_element_type=jnp.float32)
    z, u, b, c, r = (proj(wt) for wt in ("wz", "wx", "wb", "wc", "wdt"))
    conv = lambda t, wt: jax.nn.silu(
        mamba2._conv(t.astype(act).astype(jnp.float32),
                     f32(mat(q[wt + "_w"])), f32(q[wt + "_b"]))
    ).astype(act).astype(jnp.float32)
    u, b, c = conv(u, "conv_x"), conv(b, "conv_b"), conv(c, "conv_c")
    dt = jax.nn.softplus(r + q["dt_bias"])
    uh = u.reshape(s, m["mh"], m["p"])
    y = mamba2._ssd(uh, dt, -jnp.exp(q["A_log"]), b, c)
    y = (y + q["D"][:, None] * uh).reshape(s, m["d_in"])
    y = y.astype(act).astype(jnp.float32) * jax.nn.silu(z)
    y = _rms(y, q["gate_norm"].astype(jnp.float32), eps).astype(act)
    return jnp.einsum("se,ed->sd", y, mat(q["out_proj"]),
                      preferred_element_type=jnp.float32)


def _attention(m, a, h, scale, act, mat):
    """Causal GQA without positional encoding on h (s, d)."""
    s = h.shape[0]
    qkv = lambda wt: jnp.einsum("sd,dhk->shk", h, mat(a[wt]),
                                preferred_element_type=jnp.float32)
    q, k, v = qkv("wq"), qkv("wk"), qkv("wv")
    rep = m["h"] // m["kv"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhk,shk->hqs", q.astype(act), k.astype(act),
                    preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc * scale, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(sc, -1).astype(act),
                   v.astype(act), preferred_element_type=jnp.float32)
    return jnp.einsum("qhk,hkd->qd", o.astype(act), mat(a["wo"]),
                      preferred_element_type=jnp.float32)


def _swiglu(w, h, act, mat, lead=""):
    """W2(silu(W1 h) * W3 h) on h (s, d); ``lead`` names a leading
    expert axis of the weights."""
    mm = lambda x, wt, spec: jnp.einsum(spec, x, mat(w[wt]),
                                        preferred_element_type=jnp.float32)
    e = lead
    g = mm(h, "w1", f"sd,{e}df->{e}sf")
    u = (jax.nn.silu(g) * mm(h, "w3", f"sd,{e}df->{e}sf")).astype(act)
    return mm(u, "w2", f"{e}sf,{e}fd->{e}sd")


def moe(m, p, h, act, mat):
    """The held experts' share of the routed experts on h (s, d)."""
    r = jnp.einsum("sd,de->se", h.astype(jnp.float32), mat(p["router"]),
                   preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(r, m["top"])
    gate = jax.nn.softmax(top, -1)
    held = jnp.arange(m["held"])
    g = jnp.sum(jnp.where(idx[None] == held[:, None, None], gate[None],
                          0.0), -1)                       # (held, s)
    y = _swiglu(p, h, act, mat, lead="e")                 # (held, s, d)
    return jnp.einsum("es,esd->sd", g, y)


def logits(cfg: dict, params: dict, tokens: jax.Array,
           control: bool = False) -> jax.Array:
    """Logits (s, vocab) float32 at every position of ``tokens`` (s,);
    s must be a multiple of ``BLOCK``.  ``control`` as in
    ``reference.gptneox.logits``: fp8 weights, bfloat16 activations."""
    m = dims(cfg)
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    act = lowp.activation_dtype(control)
    mat = lambda t: lowp.weight(t, control)
    emb = mat(params["embed"])
    x = emb[tokens].astype(jnp.float32) * cfg["embedding_multiplier"]
    norm = lambda x, g: _rms(x, g.astype(jnp.float32), eps).astype(act)

    def period(x, pp):
        for i, kind in enumerate(m["pattern"]):
            lp = pp[f"pos{i}"]
            h = norm(x, lp["ln_mix"])
            if kind == "attention":
                y = _attention(m, lp["attn"], h,
                               cfg["attention_multiplier"], act, mat)
            else:
                y = _mamba(m, lp["ssm"], h, eps, act, mat)
            x = x + rm * y
            h = norm(x, lp["ln_ffn"])
            y = (moe(m, lp["moe"], h, act, mat)
                 + _swiglu(lp["moe"]["shared"], h, act, mat))
            x = x + rm * y
        return x, None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(period, x, params["layers"])
        x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
        return jnp.einsum("sd,vd->sv", x.astype(act), emb,
                          preferred_element_type=jnp.float32
                          ) / cfg["logits_scaling"]
