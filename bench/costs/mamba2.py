"""Operations and HBM bytes the Mamba-2 algorithm needs.

Bytes: every weight once per dispatch (the tied embedding table once, as
the unembedding), and the recurrent state of the rows served, read and
written (float32 SSD state plus the last d_conv - 1 conv inputs).
Operations: two per multiply-add in the projections and the tied
unembedding, 2 k per channel in the convolutions, and the recurrence as
its sequential form needs it: 4 h p n per token and layer (state update
and read-out).  A measured time cannot beat the roofline these give.
"""

from __future__ import annotations

from typing import Sequence, Tuple

ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    return dict(L=cfg["n_layer"], d=d, d_in=d_in, n=cfg["d_state"],
                p=cfg["headdim"], h=d_in // cfg["headdim"], k=cfg["d_conv"],
                V=cfg["vocab_size"], it=ITEM[cfg["torch_dtype"]])


def _layer(cfg: dict) -> Tuple[int, int, int]:
    """(matrix params, other params at the weight dtype, float32
    params) of one layer."""
    m = _dims(cfg)
    d, d_in, n, h, k = m["d"], m["d_in"], m["n"], m["h"], m["k"]
    mats = d * (2 * d_in + 2 * n + h) + d_in * d
    other = (d_in + 2 * n) * (k + 1) + d_in + d
    return mats, other, 3 * h


def param_count(cfg: dict) -> int:
    m = _dims(cfg)
    mats, other, f32 = _layer(cfg)
    return m["L"] * (mats + other + f32) + m["V"] * m["d"] + m["d"]


def weight_bytes(cfg: dict) -> int:
    m = _dims(cfg)
    mats, other, f32 = _layer(cfg)
    return (m["it"] * (m["L"] * (mats + other) + m["V"] * m["d"] + m["d"])
            + 4 * m["L"] * f32)


def state_bytes_per_slot(cfg: dict) -> int:
    """SSD state (float32) plus the conv carry, over all layers."""
    m = _dims(cfg)
    return m["L"] * (4 * m["h"] * m["p"] * m["n"]
                     + m["it"] * (m["k"] - 1) * (m["d_in"] + 2 * m["n"]))


def _token_flops(cfg: dict) -> int:
    """Per token, without the unembedding."""
    m = _dims(cfg)
    mats, _, _ = _layer(cfg)
    conv = 2 * m["k"] * (m["d_in"] + 2 * m["n"])
    return m["L"] * (2 * mats + conv + 4 * m["h"] * m["p"] * m["n"])


def prefill_chunk(cfg: dict, offset: int, valid: int) -> Tuple[int, int]:
    """(flops, bytes) of one prompt chunk of ``valid`` tokens into one
    slot; the recurrent state makes ``offset`` irrelevant."""
    m = _dims(cfg)
    flops = valid * _token_flops(cfg) + 2 * m["d"] * m["V"]
    return flops, weight_bytes(cfg) + 2 * state_bytes_per_slot(cfg)


def decode_step(cfg: dict, lives: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of one decode step over ``len(lives)`` active rows
    (their context lengths do not matter to a recurrence)."""
    m = _dims(cfg)
    r = len(lives)
    flops = r * (_token_flops(cfg) + 2 * m["d"] * m["V"])
    return flops, weight_bytes(cfg) + 2 * r * state_bytes_per_slot(cfg)
