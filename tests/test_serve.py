"""Serving engine: batched continuous batching, greedy determinism,
quantized-weights serving."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import ServeEngine, quantize_params, sample_token


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("gptneox-1b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_engine_completes_requests(small_model):
    cfg, model, params = small_model
    eng = ServeEngine(model, params, batch=2, max_seq=64)
    ids = [eng.submit([1, 2, 3, 4], max_new_tokens=5) for _ in range(5)]
    results = eng.run()
    assert sorted(r.request_id for r in results) == ids
    for r in results:
        assert len(r.tokens) == 5


def test_greedy_engine_matches_manual_decode(small_model):
    """Engine output == hand-rolled prefill + decode loop (greedy)."""
    cfg, model, params = small_model
    prompt = [5, 7, 9, 11, 13, 2, 4, 6]
    eng = ServeEngine(model, params, batch=1, max_seq=64)
    eng.submit(prompt, max_new_tokens=4)
    got = eng.run()[0].tokens

    logits, cache = model.prefill(params, {"tokens": jnp.asarray([prompt])},
                                  64)
    want = [int(jnp.argmax(logits[0]))]
    pos = len(prompt)
    for _ in range(3):
        lg, cache = model.decode_step(
            params, cache, jnp.asarray([want[-1]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        want.append(int(jnp.argmax(lg[0])))
        pos += 1
    assert got == want


def test_continuous_batching_isolation(small_model):
    """A request's output must not depend on its batch companions."""
    cfg, model, params = small_model
    solo = ServeEngine(model, params, batch=1, max_seq=64)
    solo.submit([1, 2, 3, 4], max_new_tokens=4)
    want = solo.run()[0].tokens

    crowded = ServeEngine(model, params, batch=3, max_seq=64)
    rid = crowded.submit([1, 2, 3, 4], max_new_tokens=4)
    crowded.submit([9, 9, 9, 9, 9, 9], max_new_tokens=6)
    crowded.submit([4, 4], max_new_tokens=3)
    got = [r for r in crowded.run() if r.request_id == rid][0].tokens
    assert got == want


def test_sampler_modes(key):
    logits = jnp.asarray([[0.0, 5.0, 1.0]])
    assert int(sample_token(logits)[0]) == 1           # greedy
    t = sample_token(logits, key, temperature=1.0, top_k=2)
    assert int(t[0]) in (1, 2)                         # top-2 excludes 0


@pytest.mark.parametrize("fmt", ["bfloat16", "float8_e4m3fn",
                                 "float4_e2m1fn"])
def test_quantized_serving_runs(small_model, fmt):
    cfg, model, params = small_model
    qparams, stats = quantize_params(params, fmt)
    if fmt != "bfloat16":
        assert stats["n_quantized"] > 0
        assert stats["mse"] < 0.05
    eng = ServeEngine(model, qparams, batch=1, max_seq=32)
    eng.submit([1, 2, 3], max_new_tokens=3)
    results = eng.run()
    assert len(results[0].tokens) == 3


def test_quantized_bytes_shrink(small_model):
    cfg, model, params = small_model
    _, s8 = quantize_params(params, "float8_e4m3fn")
    _, s4 = quantize_params(params, "float4_e2m1fn")
    _, s16 = quantize_params(params, "bfloat16")
    assert s8["quantized_bytes"] < s16["quantized_bytes"]
    assert s4["quantized_bytes"] < s8["quantized_bytes"]


@pytest.mark.parametrize("kv_format", [None, "float4_e2m1fn"])
def test_launcher_builds_the_engine(kv_format):
    """``launch.serve.build_engine`` (the CLI's and chip_smoke.py's
    builder) turns parsed arguments into a working engine: the seed
    picks the weights, --kv-format the KV store."""
    from repro.launch import serve

    argv = ["--arch", "gptneox-1b", "--reduced", "--batch", "2",
            "--max-seq", "64", "--decode-block", "4", "--seed", "3"]
    if kv_format:
        argv += ["--kv-format", kv_format]
    eng = serve.build_engine(serve.make_parser().parse_args(argv))
    assert eng.kv_format == kv_format
    assert (eng.model.cfg.kv_format or None) == kv_format
    want, _ = quantize_params(eng.model.init(jax.random.PRNGKey(3)),
                              "bfloat16")          # the default --precision
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
    (res,) = eng.run()
    assert res.status == "ok" and len(res.tokens) == 6
