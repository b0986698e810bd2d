"""Serving-vs-training consistency: prefill + token-by-token decode must
reproduce the teacher-forced forward logits for every architecture family
(the strongest end-to-end correctness check in the suite)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED, get_config
from repro.models import build_model, make_batch
from repro.configs.base import ShapeConfig

# One representative per family (the MoE layer is dropless, so routing
# does not depend on how tokens are grouped).
CASES = [
    ("mamba2-2.7b", {}),                       # ssm
    ("qwen2.5-3b", {}),                        # dense GQA + bias
    ("gemma2-2b", {}),                         # local/global + softcaps
    ("gemma-2b", {}),                          # MQA
    ("jamba-v0.1-52b", {}),                    # hybrid + MoE
    ("kimi-k2-1t-a32b", {}),                   # MoE top-8
    ("internvl2-2b", {}),                      # VLM early fusion
    ("granite-4.0-h-small", {}),               # NoPE hybrid, scaled
]


@pytest.mark.parametrize("arch,overrides", CASES)
def test_decode_matches_forward(arch, overrides, key):
    cfg = dataclasses.replace(get_config(arch).reduced(), **overrides)
    model = build_model(cfg)
    params = model.init(key)
    S, P = 48, 32
    shape = ShapeConfig("t", "train", S, 2)
    batch = make_batch(cfg, shape, key)
    full_logits, _ = jax.jit(model.forward)(params, batch)

    tokens = batch["tokens"]
    prefill_batch = dict(batch, tokens=tokens[:, :P])
    pre_logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, S + 8))(params, prefill_batch)
    # trunk position of text token P-1 == -(len(text) - (P-1)) from end
    text_len = tokens.shape[1]
    trunk_idx = full_logits.shape[1] - text_len + (P - 1)
    errs = [float(jnp.abs(pre_logits - full_logits[:, trunk_idx]).max())]

    step = jax.jit(model.decode_step)
    offset = full_logits.shape[1] - text_len    # patch prefix for VLM
    for t in range(P, text_len):
        pos = jnp.full((2,), offset + t, jnp.int32)
        lg, cache = step(params, cache, tokens[:, t], pos)
        errs.append(float(jnp.abs(lg - full_logits[:, offset + t]).max()))
    assert max(errs) < 5e-4, f"{arch}: decode diverges {max(errs):.2e}"


def test_encdec_decode_matches_forward(key):
    cfg = get_config("seamless-m4t-medium").reduced()
    model = build_model(cfg)
    params = model.init(key)
    S, P = 32, 16
    shape = ShapeConfig("t", "train", S, 2)
    batch = make_batch(cfg, shape, key)
    full_logits, _ = jax.jit(model.forward)(params, batch)
    tokens = batch["tokens"]
    pre_logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, S + 8))(
        params, dict(batch, tokens=tokens[:, :P]))
    errs = [float(jnp.abs(pre_logits - full_logits[:, P - 1]).max())]
    step = jax.jit(model.decode_step)
    for t in range(P, S):
        lg, cache = step(params, cache, tokens[:, t],
                         jnp.full((2,), t, jnp.int32))
        errs.append(float(jnp.abs(lg - full_logits[:, t]).max()))
    assert max(errs) < 5e-4, f"enc-dec decode diverges {max(errs):.2e}"


def test_ring_buffer_long_decode(key):
    """gemma2 local layers use a ring cache: decoding far past the window
    must still match the teacher-forced forward."""
    cfg = get_config("gemma2-2b").reduced()   # window = 32
    model = build_model(cfg)
    params = model.init(key)
    S = 80                                     # > 2x window
    tokens = jax.random.randint(key, (1, S), 0, cfg.vocab_size, jnp.int32)
    full_logits, _ = jax.jit(model.forward)(params, {"tokens": tokens})
    P = 8
    _, cache = jax.jit(lambda p, b: model.prefill(p, b, S))(
        params, {"tokens": tokens[:, :P]})
    step = jax.jit(model.decode_step)
    errs = []
    for t in range(P, S):
        lg, cache = step(params, cache, tokens[:, t],
                         jnp.full((1,), t, jnp.int32))
        errs.append(float(jnp.abs(lg - full_logits[:, t]).max()))
    assert max(errs) < 5e-4, f"ring cache diverges: {max(errs):.2e}"
