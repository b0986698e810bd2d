"""Operations and HBM bytes the Granite 4.0-H algorithm needs, for one
chip's share of an expert-parallel deployment.

Bytes: every weight this chip holds once per dispatch -- every held
expert included, whether or not a token of the dispatch routes to it --
with the tied embedding table once (as the unembedding).  The grouped
matmul reads only the held experts some row chose, so where the routing
leaves held experts idle the roofline counts expert bytes that were not
read: what deployment traffic, which reaches every held expert, would
need, not what the step moved (the share read at the cell's routing is
measured in PERF.md, section 5).  Then the recurrent
state of the rows served, read and written (float32 SSD state plus the
last d_conv - 1 conv inputs); and the live keys and values the rows'
queries attend to.  Operations: two per multiply-add in the projections,
the shared expert, the router and the unembedding; the routed experts at
the evaluations a token makes on this chip under uniform routing, top-k
times held / routed (10 x 9 / 72 = 1.25 for the served cut); the
convolutions and the recurrence as ``costs.mamba2`` counts them; and
attention per query over the keys it may see.
"""

from __future__ import annotations

from typing import Sequence, Tuple

ITEM = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(cfg: dict) -> dict:
    L = cfg["num_hidden_layers"]
    types = cfg["layer_types"][:L]
    d = cfg["hidden_size"]
    d_in = cfg["mamba_expand"] * d
    return dict(
        L=L, n_attn=types.count("attention"), n_ssm=types.count("mamba"),
        d=d, q=d, kv=2 * cfg["num_key_value_heads"] * (
            d // cfg["num_attention_heads"]),
        d_in=d_in, n=cfg["mamba_d_state"], h=cfg["mamba_n_heads"],
        p=cfg["mamba_d_head"], k=cfg["mamba_d_conv"],
        f=cfg["intermediate_size"], fs=cfg["shared_intermediate_size"],
        E=cfg["num_experts_routed"], held=cfg["num_local_experts"],
        top=cfg["num_experts_per_tok"], V=cfg["vocab_size"],
        it=ITEM[cfg["torch_dtype"]])


def _params(cfg: dict) -> Tuple[int, int]:
    """(parameters at the weight dtype, float32 parameters) held."""
    m = _dims(cfg)
    d, d_in, n, h = m["d"], m["d_in"], m["n"], m["h"]
    ssm = (d * (2 * d_in + 2 * n + h) + d_in * d
           + (d_in + 2 * n) * (m["k"] + 1) + d_in)
    attn = d * (m["q"] + m["kv"]) + m["q"] * d
    ffn = 3 * d * (m["held"] * m["f"] + m["fs"])
    norms = 2 * d
    wt = (m["n_ssm"] * ssm + m["n_attn"] * attn + m["L"] * (ffn + norms)
          + m["V"] * d + d)
    f32 = m["n_ssm"] * 3 * h + m["L"] * d * m["E"]      # A_log, dt_bias, D
    return wt, f32                                       # and the router


def param_count(cfg: dict) -> int:
    return sum(_params(cfg))


def weight_bytes(cfg: dict) -> int:
    wt, f32 = _params(cfg)
    return _dims(cfg)["it"] * wt + 4 * f32


def state_bytes_per_slot(cfg: dict) -> int:
    """SSD state (float32) plus the conv carry, over the Mamba-2 layers."""
    m = _dims(cfg)
    return m["n_ssm"] * (4 * m["h"] * m["p"] * m["n"]
                         + m["it"] * (m["k"] - 1) * (m["d_in"] + 2 * m["n"]))


def kv_bytes_per_token(cfg: dict) -> int:
    m = _dims(cfg)
    return m["n_attn"] * m["kv"] * m["it"]


def _token_flops(cfg: dict) -> float:
    """Per token, without attention's scores and the unembedding."""
    m = _dims(cfg)
    d, d_in, n, h = m["d"], m["d_in"], m["n"], m["h"]
    ssm = (2 * (d * (2 * d_in + 2 * n + h) + d_in * d)
           + 2 * m["k"] * (d_in + 2 * n) + 4 * h * m["p"] * n)
    attn = 2 * (d * (m["q"] + m["kv"]) + m["q"] * d)
    evals = m["top"] * m["held"] / m["E"]
    ffn = 6 * d * (evals * m["f"] + m["fs"]) + 2 * d * m["E"]
    return m["n_ssm"] * ssm + m["n_attn"] * attn + m["L"] * ffn


def prefill_chunk(cfg: dict, offset: int, valid: int) -> Tuple[float, int]:
    """(flops, bytes) of one prompt chunk: ``valid`` tokens after
    ``offset`` cached positions into one slot; logits for the last
    position only."""
    m = _dims(cfg)
    seen = valid * offset + valid * (valid + 1) // 2
    flops = (valid * _token_flops(cfg) + 4 * m["n_attn"] * m["q"] * seen
             + 2 * m["d"] * m["V"])
    nbytes = (weight_bytes(cfg) + 2 * state_bytes_per_slot(cfg)
              + kv_bytes_per_token(cfg) * (offset + valid))
    return flops, nbytes


def decode_step(cfg: dict, lives: Sequence[int]) -> Tuple[float, int]:
    """(flops, bytes) of one decode step for the active rows; ``lives``
    holds, per row, the positions its new token attends to (itself
    included)."""
    m = _dims(cfg)
    r = len(lives)
    flops = (r * (_token_flops(cfg) + 2 * m["d"] * m["V"])
             + 4 * m["n_attn"] * m["q"] * sum(lives))
    nbytes = (weight_bytes(cfg) + 2 * r * state_bytes_per_slot(cfg)
              + kv_bytes_per_token(cfg) * sum(lives))
    return flops, nbytes
