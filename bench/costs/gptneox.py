"""Operations and HBM bytes the GPT-NeoX block's algorithm needs.

Counted from the configuration's shapes alone.  Bytes are what the
step cannot avoid moving: every weight once per dispatch, the embedding
rows it gathers (not the table), the live keys and values of the rows
it serves (not the whole pool), and the new keys and values it writes.
Operations are two per multiply-add; attention counts each query
against the keys it may see.  So a measured time can never beat the
roofline these give, and a share of it cannot pass 100%.
"""

from __future__ import annotations

from typing import Sequence, Tuple

ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict):
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["intermediate_size"], cfg["vocab_size"],
            ITEM[cfg["torch_dtype"]])


def layer_matrix_params(cfg: dict) -> int:
    L, d, f, V, _ = _dims(cfg)
    return 4 * d * d + 2 * d * f


def param_count(cfg: dict) -> int:
    L, d, f, V, _ = _dims(cfg)
    return L * (layer_matrix_params(cfg) + 2 * d) + 2 * V * d + d


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * _dims(cfg)[4]


def kv_bytes_per_token(cfg: dict) -> int:
    L, d, f, V, it = _dims(cfg)
    return 2 * L * d * it


def _pass_bytes(cfg: dict, tokens: int) -> int:
    """Weights read once by one dispatch over ``tokens`` positions: the
    layers, the final norm, the unembedding, and the gathered rows."""
    L, d, f, V, it = _dims(cfg)
    return it * (L * (layer_matrix_params(cfg) + 2 * d) + d + d * V
                 + tokens * d)


def prefill_chunk(cfg: dict, offset: int, valid: int) -> Tuple[int, int]:
    """(flops, bytes) of one prompt chunk: ``valid`` tokens after
    ``offset`` cached positions; logits for the last position only."""
    L, d, f, V, it = _dims(cfg)
    seen = valid * offset + valid * (valid + 1) // 2
    flops = (2 * valid * L * layer_matrix_params(cfg) + 4 * L * d * seen
             + 2 * d * V)
    kv = kv_bytes_per_token(cfg)
    return flops, _pass_bytes(cfg, valid) + kv * (offset + valid)


def decode_step(cfg: dict, lives: Sequence[int]) -> Tuple[int, int]:
    """(flops, bytes) of one decode step for the active rows; ``lives``
    holds, per row, the positions its new token attends to (itself
    included)."""
    L, d, f, V, it = _dims(cfg)
    r = len(lives)
    flops = (r * (2 * L * layer_matrix_params(cfg) + 2 * d * V)
             + 4 * L * d * sum(lives))
    return flops, _pass_bytes(cfg, r) + kv_bytes_per_token(cfg) * sum(lives)
