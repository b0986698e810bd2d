"""The plain references agree with the program's own full forward pass
at a tiny size in float32, on weights the references make."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("name", ["tiny-gptneox", "tiny-mamba2"])
def test_reference_matches_program_forward(name):
    from repro.configs import get_config
    from repro.models import build_model

    with open(os.path.join(DATA, "bench", "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    cfg["model"]["torch_dtype"] = "float32"
    ref, _ = cell.family(cfg)
    arch = dataclasses.replace(get_config(cfg["program"]["arch"]),
                               **cfg["program"]["overrides"],
                               param_dtype="float32",
                               compute_dtype="float32")
    model = build_model(arch)
    params = ref.init_weights(cfg["model"], jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, arch.vocab_size, 64), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.forward(params, {"tokens": tokens[None]})[0][0]
    got = ref.logits(cfg["model"], params, tokens)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=0)
    low = ref.logits(cfg["model"], params, tokens, control=True)
    assert float(jnp.abs(low - want).max()) > 10 * float(
        jnp.abs(got - want).max())
