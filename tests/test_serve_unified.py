"""Unified per-slot decode state: EVERY arch family (SSM, hybrid,
enc-dec, VLM) through the fused K-step scan and chunked pooled prefill.

The slot-state protocol (``repro.models.slotstate``) makes the engine
arch-agnostic: pooled ring KV, SSM conv/state, slot-resident encoder
output + quantized cross-KV are all addressed by slot index and advanced
by one ``active`` predicate.  These tests pin the acceptance contract:
fused == per-step greedy bit-identity per family x kv_format, sampled
equivalence, and chunked prefill == full-prompt oracle for the stateful
legs (SSM carry, hybrid ring wrap, enc-dec encode-once, VLM patches).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import ServeEngine

# The MoE layer is dropless: routing is per token, so the full-prompt
# oracle and the chunk-local prefill groups route alike.
ARCHS = {
    "ssm": ("mamba2-2.7b", {}),
    "hybrid": ("jamba-v0.1-52b", {}),
    "enc-dec": ("seamless-m4t-medium", {}),
    "vlm": ("internvl2-2b", {}),
}


def _build(family):
    name, over = ARCHS[family]
    cfg = get_config(name).reduced()
    if over:
        cfg = dataclasses.replace(cfg, **over)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def models():
    return {f: _build(f) for f in ARCHS}


def _modal_inputs(cfg, seed=7):
    """(frames, patches) for the family, deterministic."""
    rng = np.random.RandomState(seed)
    frames = patches = None
    if cfg.is_encoder_decoder:
        frames = rng.randn(9, cfg.d_model).astype(np.float32) * 0.02
    if cfg.frontend == "vision":
        patches = rng.randn(5, cfg.d_model).astype(np.float32) * 0.02
    return frames, patches


def _tokens(results):
    return [r.tokens for r in sorted(results, key=lambda r: r.request_id)]


def _oracle(model, params, prompt, steps, frames=None, patches=None):
    """Full-prompt lm_prefill + per-step greedy decode — the reference
    the pooled chunked path must reproduce bit-exactly."""
    batch = {"tokens": jnp.asarray([prompt], jnp.int32)}
    n_pat = 0
    if frames is not None:
        batch["frames"] = jnp.asarray(frames[None], jnp.float32)
    if patches is not None:
        batch["patches"] = jnp.asarray(patches[None], jnp.float32)
        n_pat = patches.shape[0]
    logits, cache = model.prefill(params, batch, 64)
    out = [int(jnp.argmax(logits[0]))]
    pos = n_pat + len(prompt)
    for _ in range(steps - 1):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([out[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32), active=jnp.asarray([True]))
        out.append(int(jnp.argmax(lg[0])))
        pos += 1
    return out


# --------------------------------------------------------------------- #
# fused K-step scan == per-step dispatch, per family x kv_format
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kv_format", [None, "float8_e4m3fn",
                                       "float4_e2m1fn"])
@pytest.mark.parametrize("family", list(ARCHS))
def test_fused_matches_per_step(models, family, kv_format):
    cfg, model, params = models[family]
    frames, patches = _modal_inputs(cfg)
    outs = []
    for block in (7, 1):                 # fused K=7 vs per-step
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          kv_format=kv_format, decode_block=block,
                          prefill_chunk=8)
        eng.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=12,
                   frames=frames, patches=patches)
        eng.submit([9, 8, 7], max_new_tokens=4,       # finishes mid-K
                   frames=frames, patches=patches)
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]
    assert [len(t) for t in outs[0]] == [12, 4]


@pytest.mark.parametrize("family", list(ARCHS))
def test_fused_sampled_matches_per_step(models, family):
    """Per-slot (request id, position) key folding: SAMPLED streams are
    identical between the fused scan and per-step dispatch for every
    family, independent of batch composition."""
    cfg, model, params = models[family]
    frames, patches = _modal_inputs(cfg)
    a = ServeEngine(model, params, batch=2, max_seq=64, temperature=0.8,
                    top_k=8, seed=3, decode_block=5)
    b = ServeEngine(model, params, batch=1, max_seq=64, temperature=0.8,
                    top_k=8, seed=3, decode_block=1)
    a.submit([4, 5, 6], max_new_tokens=7, frames=frames, patches=patches)
    a.submit([9, 9], max_new_tokens=3, frames=frames, patches=patches)
    b.submit([4, 5, 6], max_new_tokens=7, frames=frames, patches=patches)
    assert _tokens(a.run())[0] == _tokens(b.run())[0]


# --------------------------------------------------------------------- #
# chunked pooled prefill == full-prompt oracle (the stateful legs)
# --------------------------------------------------------------------- #

def test_chunked_prefill_ssm_state_carry(models):
    """SSM chunked prefill: conv tail + ssd state carried across chunk
    boundaries (20-token prompt, chunk 8 -> two full chunks + a
    partially-valid tail whose invalid positions must be identity
    steps)."""
    cfg, model, params = models["ssm"]
    prompt = [int(2 + (i * 11) % 300) for i in range(20)]
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    eng.submit(prompt, max_new_tokens=6)
    got = eng.run()[0].tokens
    assert got == _oracle(model, params, prompt, 6)


def test_chunked_prefill_hybrid_ring_wrap():
    """Hybrid (jamba) with a sliding window SMALLER than the prompt: the
    attention layer's ring wraps during chunked prefill while the SSM
    layers carry state — both must match the full-prompt oracle."""
    cfg = dataclasses.replace(
        get_config("jamba-v0.1-52b").reduced(),
        sliding_window=16)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    prompt = [int(1 + (i * 7) % 200) for i in range(24)]   # 24 > window
    eng = ServeEngine(model, params, batch=1, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    eng.submit(prompt, max_new_tokens=6)
    got = eng.run()[0].tokens
    assert got == _oracle(model, params, prompt, 6)


def test_chunked_prefill_encdec_matches_oracle(models):
    """enc-dec: encode ONCE into slot-resident enc_out + cross-KV, then
    chunk the decoder prompt; engine pads frames to the pool's fixed
    enc_len, so matching the unpadded oracle also proves the key-valid
    masking throughout encoder self-attention and cross-attention."""
    cfg, model, params = models["enc-dec"]
    frames, _ = _modal_inputs(cfg)
    prompt = [int(3 + (i * 5) % 250) for i in range(13)]
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=3, prefill_chunk=8)
    eng.submit(prompt, max_new_tokens=6, frames=frames)
    got = eng.run()[0].tokens
    assert got == _oracle(model, params, prompt, 6, frames=frames)


def test_chunked_prefill_vlm_patches_matches_oracle(models):
    """VLM: patch-prefix embeddings streamed through the chunked prefill
    (embeds executable), then the text prompt — one trunk, one oracle."""
    cfg, model, params = models["vlm"]
    _, patches = _modal_inputs(cfg)
    prompt = [int(3 + (i * 5) % 250) for i in range(13)]
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=3, prefill_chunk=8)
    eng.submit(prompt, max_new_tokens=6, patches=patches)
    got = eng.run()[0].tokens
    assert got == _oracle(model, params, prompt, 6, patches=patches)


# --------------------------------------------------------------------- #
# quantized cross-KV + per-layer mixed formats
# --------------------------------------------------------------------- #

def test_cross_kv_quantized_stats(models):
    """Cross-attention KV is a quantized ring cache like self-attention
    KV: kv_cache_stats counts its bytes, and fp4 storage is sub-byte."""
    cfg, model, params = models["enc-dec"]
    dense = ServeEngine(model, params, batch=2, max_seq=64)
    quant = ServeEngine(model, params, batch=2, max_seq=64,
                        kv_format="float4_e2m1fn")
    assert dense.kv_stats["cross_kv_bytes"] > 0
    assert quant.kv_stats["cross_kv_bytes"] > 0
    assert (quant.kv_stats["cross_kv_bytes"]
            < dense.kv_stats["cross_kv_bytes"] / 2)
    assert quant.kv_stats["bytes_per_elem"] < 1.0
    # cross layers are reported per-position alongside self-attn KV
    assert any(name.endswith(".cross")
               for name in quant.kv_stats["per_layer"])


def test_mixed_per_layer_kv_formats():
    """cfg.kv_formats: fp4 on gemma2's sliding-window locals, fp8 on
    globals — measured per-layer B/elem differs, and the engine serves
    greedily identical tokens to the unquantized engine's format run."""
    cfg = get_config("gemma2-2b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    fmts = tuple("float4_e2m1fn" if blk.window else "float8_e4m3fn"
                 for blk in cfg.block_pattern())
    eng = ServeEngine(model, params, batch=1, max_seq=64,
                      kv_format=fmts, decode_block=4, prefill_chunk=8)
    per_layer = eng.kv_stats["per_layer"]
    bpe = {name: d["bytes_per_elem"] for name, d in per_layer.items()}
    assert bpe["pos0"] < 0.7 < 1.0 < bpe["pos1"] <= 1.25
    # fused == per-step still holds under mixed formats
    outs = []
    for block in (4, 1):
        e = ServeEngine(model, params, batch=1, max_seq=64,
                        kv_format=fmts, decode_block=block,
                        prefill_chunk=8)
        e.submit([5, 4, 3, 2, 1], max_new_tokens=8)
        outs.append(_tokens(e.run()))
    assert outs[0] == outs[1]


def test_supports_chunked_prefill_everywhere():
    """There is no fallback path left: every config reports chunked
    prefill support (the engine has no width-1 prefill to fall back
    to)."""
    from repro.configs import REGISTRY

    for name in REGISTRY:
        assert build_model(get_config(name).reduced()) \
            .supports_chunked_prefill, name
