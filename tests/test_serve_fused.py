"""Fused device-resident serving hot loop: decode_loop(k=N) equivalence
vs N per-step dispatches (greedy AND sampled), chunked pooled prefill vs
the width-1 prefill oracle, mid-loop slot finishes, ring-wrap
boundaries, and run() truncation flushing."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models import build_model
from repro.models import slotstate
from repro.models import ssm as ssm_lib
from repro.models import transformer as T
from repro.models.layers import rms_norm
from repro.serve import ServeEngine


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("gptneox-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _tokens(results):
    return [r.tokens for r in sorted(results, key=lambda r: r.request_id)]


@pytest.mark.parametrize("kv_format", [None, "float8_e4m3fn",
                                       "float4_e2m1fn"])
def test_fused_loop_matches_per_step(small_model, kv_format):
    """Greedy decode_loop(k=N) must be token-identical to N step() calls,
    including a slot that finishes mid-loop (shorter second request)."""
    cfg, model, params = small_model
    outs = []
    for block in (7, 1):          # fused K=7 vs per-step
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          kv_format=kv_format, decode_block=block,
                          prefill_chunk=4)
        eng.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=12)
        eng.submit([9, 8, 7], max_new_tokens=4)   # finishes mid-K
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]
    assert [len(t) for t in outs[0]] == [12, 4]


def test_fused_loop_ring_wrap(small_model):
    """Decode far past a sliding window so local-layer ring buffers wrap
    inside a fused block; fused and per-step must stay identical."""
    cfg = get_config("gemma2-2b").reduced()      # window 32 local layers
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    outs = []
    for block in (8, 1):
        eng = ServeEngine(model, params, batch=1, max_seq=64,
                          decode_block=block, prefill_chunk=8)
        eng.submit(list(range(1, 11)), max_new_tokens=45)  # 10+45 > 32
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]
    assert len(outs[0][0]) == 45


def test_fused_loop_sampled_matches_per_step(small_model):
    """Per-slot key folding (request id, position) makes even SAMPLED
    streams identical between the fused loop and per-step dispatches —
    and independent of batch composition."""
    cfg, model, params = small_model
    a = ServeEngine(model, params, batch=2, max_seq=64, temperature=0.8,
                    top_k=8, seed=3, decode_block=5)
    b = ServeEngine(model, params, batch=1, max_seq=64, temperature=0.8,
                    top_k=8, seed=3, decode_block=1)
    a.submit([4, 5, 6], max_new_tokens=7)
    a.submit([9, 9], max_new_tokens=3)           # batch companion
    b.submit([4, 5, 6], max_new_tokens=7)
    assert _tokens(a.run())[0] == _tokens(b.run())[0]


def test_chunked_prefill_matches_manual_decode(small_model):
    """Chunked pooled prefill (prompt split over several jitted chunk
    dispatches, padded tail included) must reproduce the full-prompt
    prefill + decode oracle."""
    cfg, model, params = small_model
    prompt = list(range(2, 22))                  # 20 tokens, chunk 8 -> 3
    eng = ServeEngine(model, params, batch=2, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    assert eng._chunked
    eng.submit(prompt, max_new_tokens=5)
    got = eng.run()[0].tokens

    logits, cache = model.prefill(params, {"tokens": jnp.asarray([prompt])},
                                  64)
    want = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(4):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([want[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        want.append(int(jnp.argmax(lg[0])))
        pos += 1
    assert got == want


def test_chunked_prefill_window_wrap_matches_oracle():
    """A prompt LONGER than a sliding window (gemma2 reduced: window 32,
    ring capacity 32) must still match the full-prefill oracle: chunk
    writes wrapping the ring must not evict positions that earlier
    queries of the same chunk still see (regression — the chunk used to
    write before attending)."""
    cfg = get_config("gemma2-2b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    prompt = [int(1 + (i * 7) % 200) for i in range(40)]   # 40 > window
    eng = ServeEngine(model, params, batch=1, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    assert eng._chunked
    eng.submit(prompt, max_new_tokens=6)
    got = eng.run()[0].tokens

    logits, cache = model.prefill(params, {"tokens": jnp.asarray([prompt])},
                                  64)
    want = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(5):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([want[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        want.append(int(jnp.argmax(lg[0])))
        pos += 1
    assert got == want


def test_chunked_prefill_slot_reuse_isolation(small_model):
    """A slot's previous (longer) tenant must be invisible after
    readmission: clear_slot resets the ring bookkeeping, so a short
    prompt admitted into a dirty slot matches a fresh engine."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, batch=1, max_seq=64,
                      decode_block=4, prefill_chunk=8)
    eng.submit(list(range(1, 30)), max_new_tokens=6)  # long first tenant
    eng.submit([3, 1, 4, 1, 5], max_new_tokens=6)     # short, reuses slot
    got = _tokens(eng.run())[1]

    fresh = ServeEngine(model, params, batch=1, max_seq=64,
                        decode_block=4, prefill_chunk=8)
    fresh.submit([3, 1, 4, 1, 5], max_new_tokens=6)
    assert got == _tokens(fresh.run())[0]


def test_run_flushes_truncated_results(small_model):
    """Hitting the run() step budget must flush in-flight requests as
    truncated partials instead of silently dropping them."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, batch=2, max_seq=64, decode_block=4)
    done = eng.submit([1, 2, 3], max_new_tokens=4)
    cut = eng.submit([4, 5, 6], max_new_tokens=50)
    results = {r.request_id: r for r in eng.run(max_steps=8)}
    assert not results[done].truncated
    assert len(results[done].tokens) == 4
    assert results[cut].truncated
    assert 0 < len(results[cut].tokens) < 50
    # a later run() must not advance the flushed slot
    n = len(results[cut].tokens)
    eng.run(max_steps=4)
    assert len(results[cut].tokens) == n


def test_engine_reset_reuses_compilation(small_model):
    """reset() clears serving state but keeps compiled loops; results
    repeat exactly."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, batch=2, max_seq=64, decode_block=4,
                      prefill_chunk=4)
    eng.submit([5, 6, 7], max_new_tokens=6)
    first = _tokens(eng.run())
    loops_before = set(eng._loops)
    eng.reset()
    eng.submit([5, 6, 7], max_new_tokens=6)
    assert _tokens(eng.run()) == first
    assert set(eng._loops) == loops_before


def test_max_new_tokens_one(small_model):
    """max_new_tokens=1 yields exactly the admission token (the old
    per-step engine over-generated a second token)."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, batch=1, max_seq=64)
    eng.submit([1, 2, 3], max_new_tokens=1)
    (res,) = eng.run()
    assert len(res.tokens) == 1 and not res.truncated


def test_state_lives_on_device(small_model):
    """Slot state is device arrays (the tentpole's point): one dispatch
    advances K tokens with no per-token host bookkeeping."""
    cfg, model, params = small_model
    eng = ServeEngine(model, params, batch=2, max_seq=64, decode_block=8)
    for name in ("pos", "remaining", "last_token", "active", "seed"):
        assert isinstance(eng.state[name], jax.Array)
    eng.submit([1, 2, 3, 4], max_new_tokens=8)
    eng.decode_loop()                            # one fused dispatch
    assert len(eng.results) == 1                 # 1 admit + 8 fused >= 8
    assert len(eng.results[0].tokens) == 8


@pytest.mark.parametrize("arch", ["gptneox-1b", "mamba2-2.7b"])
def test_decode_loop_donates_pool(arch):
    """The fused loop consumes the pool it is given (KV rows or SSM
    state): after a dispatch the previous pool's buffers are deleted,
    and the next admission and decode, on the pool the loop returned,
    serve the same tokens as per-step dispatch."""
    model = build_model(get_config(arch).reduced())
    params = model.init(jax.random.PRNGKey(2))
    outs = []
    for block in (4, 1):
        eng = ServeEngine(model, params, batch=2, max_seq=64,
                          decode_block=block, prefill_chunk=4)
        eng.submit([1, 2, 3, 4, 5, 6], max_new_tokens=12)
        eng.decode_loop()                        # admit, first block
        before = jax.tree.leaves(eng.cache)
        eng.decode_loop()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in jax.tree.leaves(eng.cache))
        eng.submit([9, 8, 7], max_new_tokens=5)
        outs.append(_tokens(eng.run()))
    assert outs[0] == outs[1]
    assert [len(t) for t in outs[0]] == [12, 5]


def _scanned_prefill_chunk(cfg, params, cache, tokens, slot, pos_offset,
                           valid_len):
    """The prefill chunk with the pool as the layer scan's input: each
    layer's row is sliced out with ``take_row`` and the layer restacked
    whole with ``put_row``.  The oracle for the in-place chunk, for
    decoder-only attention and SSM layers."""
    s = tokens.shape[0]
    x = T._embed(params, tokens[None, :], cfg)
    positions = pos_offset + jnp.arange(s, dtype=jnp.int32)
    valid = jnp.arange(s) < valid_len

    def period_fn(x, scanned):
        period_params, period_cache = scanned
        new_cache = {}
        for i, blk in enumerate(cfg.block_pattern()):
            p, c = period_params[f"pos{i}"], period_cache[f"pos{i}"]
            h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
            if blk.mixer == "attn":
                q = T._rope(attn.project_q(p["attn"], h), positions[None],
                            cfg)
                k, v = attn.project_kv(p["attn"], h)
                k = T._rope(k, positions[None], cfg)
                row = slotstate.take_row(c["kv"], slot)
                o = attn.cache_attention(
                    q, jnp.concatenate([row["k"], k], axis=1),
                    jnp.concatenate([row["v"], v], axis=1),
                    jnp.concatenate([row["slot_pos"], jnp.where(
                        valid, positions, -1)[None]], axis=1),
                    positions[None], window=blk.window,
                    softcap=cfg.attn_logit_softcap, scale=cfg.attn_scale)
                x = T._residual(x, attn.project_out(p["attn"], o), cfg)
                row = attn.cache_write_chunk(row, k, v, positions, valid)
                new_cache[f"pos{i}"] = {
                    "kv": slotstate.put_row(c["kv"], row, slot)}
            else:
                out, row = ssm_lib.ssm_prefill_chunk(
                    p["ssm"], h, slotstate.take_row(c["ssm"], slot), cfg,
                    valid, valid_len)
                x = T._residual(x, out, cfg)
                new_cache[f"pos{i}"] = {
                    "ssm": slotstate.put_row(c["ssm"], row, slot)}
            x, _ = T._ffn(p, blk, x, cfg)
        return x, new_cache

    x, cache = jax.lax.scan(period_fn, x, (params["layers"], cache))
    x_last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
    return T._head(params, x_last, cfg)[:, 0], cache


def _filled(cache, key):
    """Every leaf of ``cache`` drawn at random: payload normal, ring
    positions in [-1, 64)."""
    leaves, tree = jax.tree.flatten(cache)
    keys = jax.random.split(key, len(leaves))
    return tree.unflatten([
        jax.random.randint(k, a.shape, -1, 64, a.dtype)
        if jnp.issubdtype(a.dtype, jnp.integer)
        else jax.random.normal(k, a.shape, a.dtype)
        for a, k in zip(leaves, keys)])


def _snapshot(tree):
    """A host copy of ``tree`` that leaves its arrays donatable: on the
    CPU a host view of an array pins its buffer, and a pinned buffer is
    copied rather than donated."""
    return jax.device_get(jax.tree.map(jnp.copy, tree))


def _assert_rows(got, want, slot, row=None):
    """Every pool row but ``slot`` (axis 1) of ``got`` equals ``want``
    bit for bit; row ``slot`` equals ``row`` when it is given."""
    for g, w, r in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(row if row is not None else want)):
        np.testing.assert_array_equal(np.delete(g, slot, axis=1),
                                      np.delete(w, slot, axis=1))
        if row is not None:
            np.testing.assert_array_equal(g[:, slot], r[:, slot])


@pytest.mark.parametrize("arch", ["gptneox-1b", "mamba2-2.7b",
                                  "granite-4.0-h-small"])
def test_admission_updates_pool_in_place(arch):
    """``clear_slot`` and a three-chunk prefill (the last chunk part
    padding) into slot 1 of a pool whose every row holds data, through
    the engine's executables, which consume the pool they are given:
    every other row stays bit-identical, and the logits and row 1 are
    those the scanned form gives from a snapshot of the pool taken
    before the donated calls."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    eng = ServeEngine(model, params, batch=3, max_seq=64, prefill_chunk=8)
    slot = jnp.int32(1)
    cache = _filled(eng.cache, jax.random.PRNGKey(4))
    full = _snapshot(cache)
    donated = jax.tree.leaves(cache)
    cache = eng._clear_slot_fn(cache, slot)
    assert all(a.is_deleted() for a in donated)
    want_cache = _snapshot(cache)
    _assert_rows(want_cache, full, 1)
    scanned = jax.jit(functools.partial(_scanned_prefill_chunk, cfg))
    prompt = np.random.default_rng(5).integers(0, cfg.vocab_size, 21)
    for off in range(0, len(prompt), 8):
        part = prompt[off:off + 8]
        chunk = jnp.asarray(np.pad(part, (0, 8 - len(part))), jnp.int32)
        args = (chunk, slot, jnp.int32(off), jnp.int32(len(part)))
        donated = jax.tree.leaves(cache)
        logits, cache = eng._prefill_chunk_fn(params, cache, *args)
        assert all(a.is_deleted() for a in donated)
        want, want_cache = scanned(params, want_cache, *args)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))
    _assert_rows(_snapshot(cache), full, 1, row=want_cache)
