"""The Granite 4.0-H configuration: its reference against the program's
full forward, its costs against hand figures, and a tiny cell of it
through the whole harness on the CPU (weights from the seed, engine,
warm-up, the backlog window, and the comparison with the reference).

The tiny cell's limit (``data/bench/configs/tiny-granite.json``, 0.004)
sits between the sound runs' readings on the CPU (at most 0.0028 over
seeds 3, 4 and 5) and the control's (at least 0.0095 on the same seeds).
"""

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import cell
from costs import granite as costs

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")


def _load(path):
    with open(path) as f:
        return json.load(f)


def test_uncut_published_config_has_32b_parameters():
    """32.2B as published; the served cut, 4.418B (8.84 GB in bf16)."""
    cut = _load(os.path.join(BENCH, "configs", "granite-4h-small.json"))
    uncut = dict(cut["model"], num_hidden_layers=40, num_local_experts=72)
    assert abs(costs.param_count(uncut) / 32.2e9 - 1) < 0.01
    assert abs(costs.param_count(cut["model"]) / 4.418e9 - 1) < 0.001
    assert abs(costs.weight_bytes(cut["model"]) / 8.84e9 - 1) < 0.005


def test_hand_figures():
    m = _load(os.path.join(BENCH, "configs", "granite-4h-small.json"))
    model = m["model"]
    # 18 Mamba-2 layers of 128 x 64 x 128 float32 state plus 3 conv inputs
    # of 8192 + 2 x 128 bf16 channels; 2 GQA layers of 2 x 8 x 128 bf16
    assert costs.state_bytes_per_slot(model) == 18 * (
        4 * 128 * 64 * 128 + 2 * 3 * (8192 + 256))
    assert costs.kv_bytes_per_token(model) == 2 * 2 * 8 * 128 * 2
    f1, b1 = costs.decode_step(model, [100])
    f2, b2 = costs.decode_step(model, [100, 100])
    assert b2 - b1 == 2 * costs.state_bytes_per_slot(model) \
        + 100 * costs.kv_bytes_per_token(model)
    assert b1 > costs.weight_bytes(model)
    assert f2 > f1 > 0
    assert m["memory"]["params"] == costs.param_count(model)
    assert m["memory"]["weight_bytes"] == costs.weight_bytes(model)


def test_config_keeps_the_published_widths():
    """Only the depth and the experts held differ from the source."""
    cfg = _load(os.path.join(BENCH, "configs", "granite-4h-small.json"))
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts"]
    for key in ("hidden_size", "mamba_n_heads", "mamba_d_head",
                "mamba_d_state", "num_attention_heads",
                "num_key_value_heads", "intermediate_size",
                "shared_intermediate_size", "num_experts_per_tok",
                "vocab_size", "layer_types"):
        assert cfg["model"][key] == cfg[key], key
    assert cfg["model"]["num_experts_routed"] == 72
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"]) == (20, 9)


def test_reference_matches_program_forward():
    from repro.configs import get_config
    from repro.models import build_model

    cfg = _load(os.path.join(DATA, "bench", "configs", "tiny-granite.json"))
    cfg["model"]["torch_dtype"] = "float32"
    ref, _ = cell.family(cfg)
    arch = dataclasses.replace(get_config(cfg["program"]["arch"]),
                               **cfg["program"]["overrides"],
                               param_dtype="float32",
                               compute_dtype="float32")
    assert {k: getattr(arch, k) for k in ref.arch_fields(cfg["model"])} \
        == ref.arch_fields(cfg["model"])
    model = build_model(arch)
    params = ref.init_weights(cfg["model"], jax.random.PRNGKey(3))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, arch.vocab_size, 64), jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = model.forward(params, {"tokens": tokens[None]})[0][0]
    got = ref.logits(cfg["model"], params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4, rtol=0)
    low = ref.logits(cfg["model"], params, tokens, control=True)
    assert float(jnp.abs(low - want).max()) > 10 * float(
        jnp.abs(got - want).max())


@pytest.fixture
def granite_root(tiny_root):
    """The tiny checkout with a backlog cell of tiny-granite."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    bm = _load(path)
    bm["configs"].append({"name": "tiny-granite", "source": "test",
                          "file": "bench/configs/tiny-granite.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "tiny-granite.backlog",
                            "config": "tiny-granite",
                            "traffic": "tiny-backlog", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"]:
        if m["name"] == "output_tok_s":
            m["workloads"].append("tiny-granite.backlog")
    with open(path, "w") as f:
        json.dump(bm, f)
    return tiny_root


def test_tiny_cell_is_correct_and_its_control_is_not(granite_root):
    r = cell.run("tiny-granite.backlog", 3, 2.0, False, time.monotonic(),
                 root=granite_root, require_tpu=False, control=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"output_tok_s", "setup_s"}
    c = r["checks"]
    assert c["control_gap"]["value"] > c["max_logit_gap"]["limit"]
