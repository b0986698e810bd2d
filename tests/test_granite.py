"""Granite 4.0-H (NoPE GQA + Mamba-2 + dropless MoE with a shared expert)
against the benchmark's plain float32 reference, at a tiny size on the
CPU, and the expert-parallel MoE layer's shares against the uncut layer.

The tiny configuration (``bench/tests/data/bench/configs/tiny-granite
.json``) keeps every mechanism of the published one: a period of ten
with attention at position 5, two periods, the three multipliers, NoPE
at the configured attention scale, and 8 routed experts (top-3) of
which the program holds the first 4.
"""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models import moe as M
from repro.models.layers import apply_mlp
from repro.serve import ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
sys.path.insert(0, BENCH)

from reference import granite as ref  # noqa: E402

with open(os.path.join(BENCH, "tests", "data", "bench", "configs",
                       "tiny-granite.json")) as f:
    TINY = json.load(f)["model"]
TINY32 = dict(TINY, torch_dtype="float32")


def F32(t):
    """The reference's weight reader at float32 (its ``mat``)."""
    return t.astype(jnp.float32)


def _arch(model_cfg, **kw):
    return dataclasses.replace(get_config("granite-4.0-h-small"),
                               **{**ref.arch_fields(model_cfg), **kw})


@pytest.fixture(scope="module")
def tiny():
    """(model, reference weights) in float32."""
    model = build_model(_arch(TINY32))
    return model, ref.init_weights(TINY32, jax.random.PRNGKey(7))


def _ref_logits(params, seq):
    s = len(seq)
    padded = np.zeros(-(-s // ref.BLOCK) * ref.BLOCK, np.int32)
    padded[:s] = seq
    return np.asarray(ref.logits(TINY32, params, jnp.asarray(padded)))[:s]


def test_prefill_then_decode_matches_reference(tiny):
    """Chunked prefill of a 13-token prompt (chunk 8: one boundary) into
    the engine's pool, then 6 decode steps through the cache, against
    the reference's full forward, logit for logit.  Both sides compute
    in float32 (the reference at ``highest`` matmul precision).  The
    logits spread about 0.005 and the two sides differ by about 3e-8
    (float32 reassociation over 20 layers and the chunked recurrence's
    order): the tolerance, 1e-6, leaves 30 times that, while the same
    program in bfloat16 misses by 1e-3."""
    model, params = tiny
    eng = ServeEngine(model, params, batch=2, max_seq=64, decode_block=4,
                      prefill_chunk=8)
    rng = np.random.default_rng(0)
    seq = rng.integers(0, TINY["vocab_size"], 19).tolist()
    prompt, forced = seq[:13], seq[13:]
    slot = jnp.int32(1)
    cache, got = eng.cache, []
    for off in range(0, len(prompt), 8):
        chunk = np.zeros(8, np.int32)
        part = prompt[off:off + 8]
        chunk[:len(part)] = part
        logits, cache = eng._prefill_chunk_fn(
            eng.params, cache, jnp.asarray(chunk), slot, jnp.int32(off),
            jnp.int32(len(part)))
    got.append(np.asarray(logits[0]))
    step = jax.jit(model.decode_step)
    for j, tok in enumerate(forced[:-1]):
        token = jnp.asarray([0, tok], jnp.int32)
        pos = jnp.asarray([0, len(prompt) + j], jnp.int32)
        logits, cache = step(eng.params, cache, token, pos,
                             jnp.asarray([False, True]))
        got.append(np.asarray(logits[1]))
    want = _ref_logits(params, seq)[len(prompt) - 1:len(seq) - 1]
    np.testing.assert_allclose(np.stack(got), want, atol=1e-6, rtol=0)


def test_engine_greedy_stream_is_the_references_argmax(tiny):
    """The engine's normal path (submit, chunked admission, the fused
    loop) serves greedy tokens the reference also puts first, up to
    float32 rounding: the reference's best logit exceeds its logit for
    each served token by at most 1e-6 (the comparison the benchmark's
    ``correct`` makes, at float32 here; see the tolerance above)."""
    model, params = tiny
    eng = ServeEngine(model, params, batch=2, max_seq=64, decode_block=4,
                      prefill_chunk=8)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, TINY["vocab_size"], n).tolist()
               for n in (13, 5, 21)]
    for p in prompts:
        eng.submit(p, max_new_tokens=9)
    for r in eng.run():
        prompt = prompts[r.request_id]
        lg = _ref_logits(params, prompt + r.tokens)[len(prompt) - 1:-1]
        gap = lg.max(-1) - lg[np.arange(len(r.tokens)), r.tokens]
        assert len(r.tokens) == 9 and gap.max() <= 1e-6, gap


def _layer(cfg, key):
    p = M.init_moe(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, cfg.d_model))
    return p, x


@pytest.mark.parametrize("shares", [2, 4])
def test_expert_shares_sum_to_the_uncut_layer(shares, key):
    """Each share holds 8 / shares of the 8 routed experts and routes
    over all 8; the shares' outputs summed, with the shared expert (which
    every share computes) counted once, are the uncut layer's output,
    and the uncut layer is the reference's.  A layer holds its first
    experts, so share s gets experts [s n, (s + 1) n) and a router whose
    columns are rolled to put those experts first."""
    full = _arch(TINY32, moe_experts_held=0)
    p, x = _layer(full, key)
    want, _ = M.apply_moe(p, x, full)
    shared = apply_mlp(p["shared"], x, full.mlp_variant)
    n = full.moe_num_experts // shares
    total = -(shares - 1) * shared
    for s in range(shares):
        cut = dataclasses.replace(full, moe_experts_held=n)
        ps = dict(p, router=jnp.roll(p["router"], -s * n, axis=1),
                  **{w: p[w][s * n:(s + 1) * n]
                     for w in ("w1", "w2", "w3")})
        got, _ = M.apply_moe(ps, x, cut)
        total = total + got
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=0)
    m = ref.dims(dict(TINY32, num_local_experts=8))
    for b in range(x.shape[0]):
        r = ref.moe(m, p, x[b], jnp.float32, F32) + ref._swiglu(
            p["shared"], x[b], jnp.float32, F32)
        np.testing.assert_allclose(np.asarray(want[b]), np.asarray(r),
                                   atol=2e-5, rtol=0)


@pytest.mark.parametrize("expert", [1, 3])
def test_router_sending_every_token_to_one_expert_drops_none(expert, key):
    """All 48 tokens routed first to one held expert (a router that
    scores it far above the rest on these all-positive inputs): the
    layer computes every token through it -- a capacity of 48 x 3 / 8
    slots per expert would have dropped most -- and matches the
    reference."""
    cfg = _arch(TINY32)
    p, x = _layer(cfg, key)
    x = jnp.abs(x)
    p = dict(p, router=jnp.zeros_like(p["router"]).at[:, expert].set(1.0))
    got, _ = M.apply_moe(p, x, cfg)
    m = ref.dims(TINY32)
    for b in range(x.shape[0]):
        routed = ref.moe(m, p, x[b], jnp.float32, F32)
        r = routed + ref._swiglu(p["shared"], x[b], jnp.float32, F32)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(r),
                                   atol=2e-5, rtol=0)
        assert float(jnp.linalg.norm(routed, axis=-1).min()) > 0.0
