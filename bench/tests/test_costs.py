"""The shape-based counts against hand figures of the two models."""

import json
import os

import pytest

from costs import gptneox, mamba2

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


NEOX, MAMBA = _cfg("gptneox-1b"), _cfg("mamba2-2.7b")


def test_gptneox_hand_figures():
    # 2 (K, V) x 16 layers x 16 heads x 128 x 2 B
    assert gptneox.kv_bytes_per_token(NEOX) == 131_072
    assert gptneox.weight_bytes(NEOX) == pytest.approx(2.02e9, rel=0.01)
    # a full 16 x 2048 pool
    assert gptneox.kv_bytes_per_token(NEOX) * 16 * 2048 == 4_294_967_296


def test_mamba2_hand_figures():
    assert mamba2.weight_bytes(MAMBA) == pytest.approx(5.41e9, rel=0.01)
    # float32 SSD state: 64 layers x 80 heads x 64 x 128 x 4 B, plus the
    # conv carry (3 x 5376 bf16 per layer)
    ssd = 64 * 80 * 64 * 128 * 4
    assert ssd == 167_772_160
    assert mamba2.state_bytes_per_slot(MAMBA) == ssd + 64 * 3 * 5376 * 2
    assert mamba2.state_bytes_per_slot(MAMBA) == pytest.approx(170e6,
                                                               rel=0.01)


def test_decode_counts_live_kv_not_the_pool():
    w = gptneox.decode_step(NEOX, [])[1]
    one = gptneox.decode_step(NEOX, [100])[1]
    two = gptneox.decode_step(NEOX, [100, 1000])[1]
    kv, row = gptneox.kv_bytes_per_token(NEOX), 2048 * 2
    assert one - w == 100 * kv + row
    assert two - one == 1000 * kv + row
    # weights once, whatever the batch: 16 rows read far less than 16 x
    assert gptneox.decode_step(NEOX, [1] * 16)[1] < 1.1 * w


def test_prefill_gathers_rows_not_the_table():
    f0, b0 = gptneox.prefill_chunk(NEOX, 0, 1)
    f1, b1 = gptneox.prefill_chunk(NEOX, 0, 2)
    assert b1 - b0 == 2048 * 2 + gptneox.kv_bytes_per_token(NEOX)
    # history is read, not recomputed
    assert gptneox.prefill_chunk(NEOX, 64, 32)[1] - \
        gptneox.prefill_chunk(NEOX, 0, 32)[1] == \
        64 * gptneox.kv_bytes_per_token(NEOX)
    # one unembedding row per chunk: 2 d V, not 2 d V x valid
    assert f1 - f0 == 2 * gptneox.layer_matrix_params(NEOX) * 16 + \
        4 * 16 * 2048 * 2


def test_ssm_bytes_follow_rows_not_context():
    assert mamba2.decode_step(MAMBA, [10, 10])[1] == \
        mamba2.decode_step(MAMBA, [2000, 5])[1]
    w = mamba2.decode_step(MAMBA, [])[1]
    assert mamba2.decode_step(MAMBA, [1])[1] - w == \
        2 * mamba2.state_bytes_per_slot(MAMBA)
    assert mamba2.prefill_chunk(MAMBA, 0, 32)[1] == \
        mamba2.prefill_chunk(MAMBA, 512, 32)[1]
