"""Plain reference for the Mamba-2 (SSD) language model the program serves.

Equations (Dao & Gu, arXiv:2405.21060; one B/C group shared by all
heads, the x/B/C depthwise convolution written as three convolutions):

    x = E[tokens]
    per layer:  h = rms(x) * g
                z, u, b, c, r = h Wz, h Wx, h Wb, h Wc, h Wdt
                u, b, c = silu(conv(u)), silu(conv(b)), silu(conv(c))
                dt = softplus(r + dt_bias);  a = -exp(A_log)
                S_t = exp(dt_t a) S_{t-1} + (dt_t u_t) (x) b_t     per head
                y_t = S_t c_t + D u_t
                x = x + (rms(y * silu(z)) * gn) Wout
    logits = (rms(x) * gf) E^T            (tied embeddings)

The recurrence is evaluated exactly, in blocks of ``BLOCK`` positions:
inside a block as the masked product of decays, across blocks through the
carried state.  Float32 throughout at ``highest`` matmul precision; it
imports nothing of the program.  ``init_weights`` makes the weights from
the seed in the program's tree layout and dtypes (A_log, dt_bias and D
are float32 there).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from harness import lowp

BLOCK = 64


def dims(cfg: dict) -> dict:
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    return {"L": cfg["n_layer"], "d": d, "d_in": d_in,
            "n": cfg["d_state"], "p": cfg["headdim"],
            "h": d_in // cfg["headdim"], "k": cfg["d_conv"],
            "V": cfg["vocab_size"]}


def arch_fields(cfg: dict) -> dict:
    m = dims(cfg)
    return {"n_layers": m["L"], "d_model": m["d"], "d_ff": 0,
            "vocab_size": m["V"], "ssm_state": m["n"],
            "ssm_expand": cfg["expand"], "ssm_head_dim": m["p"],
            "ssm_conv": m["k"], "ssm_chunk": cfg["chunk_size"],
            "tie_embeddings": True, "norm_eps": cfg["norm_epsilon"],
            "param_dtype": cfg["torch_dtype"],
            "compute_dtype": cfg["torch_dtype"]}


def init_weights(cfg: dict, key: jax.Array) -> dict:
    m = dims(cfg)
    L, d, d_in, n, h, k = (m[x] for x in "L d d_in n h k".split())
    dt_ = jnp.dtype(cfg["torch_dtype"])
    ks = iter(jax.random.split(key, 12))

    def w(shape, fan_in):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt_)

    # A in [1, 16] and dt in [1e-3, 1e-1] (log-uniform), as Mamba-2 draws
    a = jax.random.uniform(next(ks), (L, h), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(next(ks), (L, h), jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    zeros = lambda *s: jnp.zeros(s, dt_)
    return {
        "embed": w((m["V"], d), d),
        "final_norm": jnp.ones((d,), dt_),
        "layers": {"pos0": {
            "ln_mix": jnp.ones((L, d), dt_),
            "ssm": {
                "wz": w((L, d, d_in), d), "wx": w((L, d, d_in), d),
                "wb": w((L, d, n), d), "wc": w((L, d, n), d),
                "wdt": w((L, d, h), d),
                "conv_x_w": w((L, d_in, k), k), "conv_x_b": zeros(L, d_in),
                "conv_b_w": w((L, n, k), k), "conv_b_b": zeros(L, n),
                "conv_c_w": w((L, n, k), k), "conv_c_b": zeros(L, n),
                "A_log": jnp.log(a),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": jnp.ones((L, h), jnp.float32),
                "gate_norm": jnp.ones((L, d_in), dt_),
                "out_proj": w((L, d_in, d), d_in),
            }}},
    }


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _conv(u, w, b):
    """Causal depthwise convolution: out_t = sum_j w[:, j] u_{t-k+1+j}."""
    k = w.shape[-1]
    up = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(up[j:j + u.shape[0]] * w[:, j] for j in range(k)) + b


def _ssd(u, dt, a, b, c):
    """The recurrence over s positions (s a multiple of BLOCK).
    u (s, h, p), dt (s, h), a (h,), b and c (s, n) -> y (s, h, p)."""
    s, h, p = u.shape
    n = b.shape[-1]
    nb = s // BLOCK
    blk = lambda t: t.reshape((nb, BLOCK) + t.shape[1:])
    la = dt * a                                        # log decay (s, h)
    tri = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))

    def step(state, xs):
        ub, dtb, lab, bb, cb = xs
        cum = jnp.cumsum(lab, 0)                       # (Q, h)
        diff = cum[:, None, :] - cum[None, :, :]       # (Q, Q, h) i, j
        decay = jnp.where(tri[..., None],
                          jnp.exp(jnp.where(tri[..., None], diff, 0.0)),
                          0.0)
        xin = ub * dtb[..., None]                      # (Q, h, p)
        cb_ = cb @ bb.T                                # (Q, Q)
        y = jnp.einsum("ij,ijh,jhp->ihp", cb_, decay, xin)
        y = y + jnp.einsum("in,hpn,ih->ihp", cb, state, jnp.exp(cum))
        last = jnp.exp(cum[-1][None, :] - cum)         # (Q, h)
        state = (state * jnp.exp(cum[-1])[:, None, None]
                 + jnp.einsum("jh,jhp,jn->hpn", last, xin, bb))
        return state, y

    s0 = jnp.zeros((h, p, n), jnp.float32)
    _, y = jax.lax.scan(step, s0, (blk(u), blk(dt), blk(la), blk(b),
                                   blk(c)))
    return y.reshape(s, h, p)


def logits(cfg: dict, params: dict, tokens: jax.Array,
           control: bool = False) -> jax.Array:
    """Logits (s, vocab) float32 at every position of ``tokens`` (s,);
    s must be a multiple of ``BLOCK``.  ``control`` as in
    ``reference.gptneox.logits``: fp8 weights, bfloat16 activations."""
    m = dims(cfg)
    eps = cfg["norm_epsilon"]
    act = lowp.activation_dtype(control)
    mat = lambda t: lowp.weight(t, control)
    s = tokens.shape[0]
    emb = mat(params["embed"])
    x = emb[tokens].astype(jnp.float32)

    def proj(h, wt):
        return jnp.einsum("sd,de->se", h, mat(wt),
                          preferred_element_type=jnp.float32)

    def layer(x, lp):
        q = lp["ssm"]
        h = _rms(x, lp["ln_mix"].astype(jnp.float32), eps).astype(act)
        z, u, b, c, r = (proj(h, q[w]) for w in
                         ("wz", "wx", "wb", "wc", "wdt"))
        f32 = lambda t: t.astype(jnp.float32)
        conv = lambda t, w: jax.nn.silu(
            _conv(t.astype(act).astype(jnp.float32), f32(mat(q[w + "_w"])),
                  f32(q[w + "_b"]))).astype(act).astype(jnp.float32)
        u, b, c = conv(u, "conv_x"), conv(b, "conv_b"), conv(c, "conv_c")
        dt = jax.nn.softplus(r + q["dt_bias"])
        uh = u.reshape(s, m["h"], m["p"])
        y = _ssd(uh, dt, -jnp.exp(q["A_log"]), b, c)
        y = (y + q["D"][:, None] * uh).reshape(s, m["d_in"])
        y = y.astype(act).astype(jnp.float32) * jax.nn.silu(z)
        y = _rms(y, q["gate_norm"].astype(jnp.float32), eps).astype(act)
        return x + proj(y, q["out_proj"]), None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(layer, x, params["layers"]["pos0"])
        x = _rms(x, params["final_norm"].astype(jnp.float32), eps)
        return jnp.einsum("sd,vd->sv", x.astype(act), emb,
                          preferred_element_type=jnp.float32)
