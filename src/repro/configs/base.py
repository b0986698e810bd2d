"""Architecture + shape configuration system.

Every assigned architecture is an :class:`ArchConfig` (one module per arch
under ``repro.configs``); every benchmark shape is a :class:`ShapeConfig`.
``reduced()`` yields the same-family small config used by the CPU smoke
tests — the FULL configs are exercised only via the dry-run
(ShapeDtypeStruct, no allocation).

The block-pattern abstraction: a model is ``n_layers`` blocks arranged as a
repeating *period* of heterogeneous blocks (attention / SSM mixers, dense /
MoE FFNs).  ``block_pattern()`` returns one period; the model stacks layer
parameters per position-in-period and scans over periods, which keeps HLO
size O(period) instead of O(n_layers).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One block within a period (mixer + ffn)."""

    mixer: str                    # "attn" | "ssm" | "none"
    ffn: str                      # "dense" | "moe" | "none"
    window: Optional[int] = None  # sliding-window size for local attention
    cross_attn: bool = False      # decoder block with cross-attention


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    # identity
    name: str
    family: str                   # dense | ssm | hybrid | moe | audio | vlm
    # trunk dimensions
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    # MLP / norm
    mlp_variant: str = "swiglu"   # swiglu | geglu | gelu
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    qkv_bias: bool = False
    # granite scaling: x = embedding_multiplier * E[t]; every sublayer
    # adds residual_multiplier * its output; logits / logits_scaling.
    # Trace-time constants: at 1.0 they emit no operation.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # attention flavor
    rope_theta: float = 10000.0
    attn_scale: Optional[float] = None     # None = 1/sqrt(head_dim)
    use_rope: bool = True         # False = no positional encoding (NoPE)
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    sliding_window: Optional[int] = None   # used by blocks with window
    local_global_period: int = 0  # gemma2: alternate local/global every layer
    # MoE
    moe_num_experts: int = 0
    moe_top_k: int = 0
    moe_every: int = 1            # MoE FFN every k-th block (1 = all blocks)
    moe_d_ff: int = 0             # per-expert hidden dim (0 = use d_ff)
    moe_shared_expert: bool = False
    moe_shared_d_ff: int = 0      # shared expert's hidden dim (0 = expert's)
    # expert parallelism: the router scores all moe_num_experts experts;
    # this chip holds the first moe_experts_held of them (0 = all) and
    # computes their part alone
    moe_experts_held: int = 0
    # hybrid: attention block every k-th block, SSM otherwise
    attn_every: int = 1           # 1 = all attention; 8 = jamba 1:7
    attn_offset: int = 0          # attention's position in the period
    # SSM (mamba2 SSD)
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    # encoder-decoder
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    # modality frontend stub: input is precomputed frame/patch embeddings
    frontend: Optional[str] = None   # None | "audio" | "vision"
    # numerics
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    cache_dtype: str = ""         # KV-cache storage ("" = compute_dtype);
                                  # fp8 halves decode weight/KV traffic
    kv_format: str = ""           # blockwise-QUANTIZED KV storage: a
                                  # repro.compat registry format (e.g.
                                  # "float8_e4m3fn", "float4_e2m1fn");
                                  # K/V held as packed codes + 1-byte
                                  # e8m0 block scales, (de)quantized in
                                  # the cache write/read paths.  "" =
                                  # plain cast storage per cache_dtype.
    kv_formats: Tuple[str, ...] = ()   # per-POSITION-IN-PERIOD override of
                                  # kv_format (mixed-precision KV: e.g.
                                  # fp8 on global-attention layers, fp4
                                  # on sliding-window locals).  Length
                                  # must equal the block period; "" at a
                                  # position falls back to kv_format.
                                  # Applies to self- AND cross-attention
                                  # KV of that position.
    attn_chunk: int = 1024        # online-softmax KV block (XLA path)
    attn_repeat_kv: bool = False  # materialize KV at full q-head count:
                                  # the (hq)->(hkv, g) grouping reshape is
                                  # unshardable when hkv < mesh 'model'
                                  # (kimi: 8 kv heads on 16-way TP) —
                                  # repeating KV keeps q-heads sharded
    attn_seq_shard: bool = False  # context-parallel attention: shard the
                                  # q sequence dim over 'model' inside the
                                  # mixer (for archs whose head count the
                                  # model axis cannot divide, e.g.
                                  # llama3.2's 24 heads on 16-way TP,
                                  # where attention otherwise computes
                                  # fully replicated on that axis)
    # distribution hints
    fsdp: bool = False            # shard params over the data axis too
    remat: str = "block"          # "none" | "block" | "full"
    # batch-dim mesh axes for activation sharding constraints; set by the
    # launcher (dataclasses.replace) — () = no constraints (CPU tests).
    # Without these, XLA resolves the FSDP-weight x DP-batch einsum
    # ambiguity by REPLICATING the batch (measured 650 GiB/dev on the
    # llama4 train cell; EXPERIMENTS.md §Perf).
    batch_axes: Tuple[str, ...] = ()

    # ------------------------------------------------------------------ #
    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def shared_d_ff(self) -> int:
        return self.moe_shared_d_ff or self.expert_d_ff

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.moe_num_experts

    def kv_format_for(self, pos_in_period: int) -> Optional[str]:
        """Effective KV format for one position-in-period (None = plain).

        ``kv_formats`` (per-layer mixed precision) wins over the uniform
        ``kv_format``; empty strings in either mean unquantized storage.
        """
        if self.kv_formats:
            assert len(self.kv_formats) == len(self.block_pattern()), (
                f"{self.name}: kv_formats has {len(self.kv_formats)} "
                f"entries but the block period is "
                f"{len(self.block_pattern())}")
            fmt = self.kv_formats[pos_in_period] or self.kv_format
        else:
            fmt = self.kv_format
        return fmt or None

    def block_pattern(self) -> List[BlockSpec]:
        """One period of the layer stack (see module docstring)."""
        period = 1
        if self.attn_every > 1:
            period = max(period, self.attn_every)
        if self.moe_num_experts and self.moe_every > 1:
            period = max(period, self.moe_every)
        if self.local_global_period:
            period = max(period, self.local_global_period)
        blocks = []
        for i in range(period):
            if self.family == "ssm":
                mixer: str = "ssm"
            elif self.attn_every > 1:
                # hybrid: attention at attn_offset of each period, SSM else
                mixer = ("attn" if i % self.attn_every == self.attn_offset
                         else "ssm")
            else:
                mixer = "attn"
            window = None
            if self.local_global_period and i % self.local_global_period == 0:
                window = self.sliding_window   # even positions local
            elif self.sliding_window and not self.local_global_period:
                window = self.sliding_window
            if self.family == "ssm":
                ffn = "none" if self.d_ff == 0 else "dense"
            elif self.moe_num_experts:
                ffn = "moe" if (i + 1) % self.moe_every == 0 else "dense"
            else:
                ffn = "dense"
            blocks.append(BlockSpec(mixer=mixer, ffn=ffn, window=window,
                                    cross_attn=self.is_encoder_decoder))
        return blocks

    @property
    def n_periods(self) -> int:
        period = len(self.block_pattern())
        assert self.n_layers % period == 0, (
            f"{self.name}: n_layers={self.n_layers} not divisible by "
            f"period={period}")
        return self.n_layers // period

    # ------------------------------------------------------------------ #
    def param_count(self) -> int:
        """Total parameters (exact for our implementation)."""
        n = 0
        embed = self.vocab_size * self.d_model
        n += embed
        if not self.tie_embeddings:
            n += embed
        for blk in self.block_pattern():
            b = 0
            if blk.mixer == "attn":
                b += self.d_model * (self.q_dim + 2 * self.kv_dim)
                b += self.q_dim * self.d_model
                if self.qkv_bias:
                    b += self.q_dim + 2 * self.kv_dim
                b += 2 * self.d_model          # pre norms (attn)
                if blk.cross_attn:
                    b += self.d_model * (self.q_dim + 2 * self.kv_dim)
                    b += self.q_dim * self.d_model
                    b += self.d_model
            elif blk.mixer == "ssm":
                d_in = self.d_inner
                conv_dim = d_in + 2 * self.ssm_state
                b += self.d_model * (2 * d_in + 2 * self.ssm_state
                                     + self.ssm_heads)
                b += conv_dim * (self.ssm_conv + 1)   # conv weights + biases
                b += 3 * self.ssm_heads        # A_log, dt_bias, D
                b += d_in                      # gated norm
                b += d_in * self.d_model       # out proj
                b += self.d_model              # pre norm
            if blk.ffn == "dense":
                mult = 3 if self.mlp_variant in ("swiglu", "geglu") else 2
                b += mult * self.d_model * self.d_ff + self.d_model
            elif blk.ffn == "moe":
                mult = 3 if self.mlp_variant in ("swiglu", "geglu") else 2
                b += (self.experts_held * mult * self.d_model
                      * self.expert_d_ff)
                b += self.d_model * self.moe_num_experts   # router
                if self.moe_shared_expert:
                    b += mult * self.d_model * self.shared_d_ff
                b += self.d_model
            n += b * self.n_periods
        if self.is_encoder_decoder:
            # encoder blocks: self-attn + dense ffn
            mult = 3 if self.mlp_variant in ("swiglu", "geglu") else 2
            b = (self.d_model * (self.q_dim + 2 * self.kv_dim)
                 + self.q_dim * self.d_model
                 + mult * self.d_model * self.d_ff + 2 * self.d_model)
            n += b * self.n_encoder_layers
        n += self.d_model                      # final norm
        return n

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k + shared experts)."""
        if not self.moe_num_experts:
            return self.param_count()
        mult = 3 if self.mlp_variant in ("swiglu", "geglu") else 2
        expert = mult * self.d_model * self.expert_d_ff
        inactive_per_moe_block = (
            max(self.experts_held - self.moe_top_k, 0) * expert)
        n_moe_blocks = sum(1 for b in self.block_pattern()
                           if b.ffn == "moe") * self.n_periods
        return self.param_count() - inactive_per_moe_block * n_moe_blocks

    # ------------------------------------------------------------------ #
    def reduced(self) -> "ArchConfig":
        """Same-family tiny config for CPU smoke tests."""
        period = len(self.block_pattern())
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=period * (2 if period <= 2 else 1),
            n_encoder_layers=2 if self.is_encoder_decoder else 0,
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads > 1 else 1,
            head_dim=16,
            d_ff=0 if self.d_ff == 0 else 128,
            vocab_size=512,
            moe_num_experts=min(self.moe_num_experts, 4),
            moe_top_k=min(self.moe_top_k, 2),
            moe_d_ff=64 if self.moe_d_ff else 0,
            moe_shared_d_ff=96 if self.moe_shared_d_ff else 0,
            moe_experts_held=0,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_head_dim=16 if self.ssm_state else 64,
            ssm_chunk=32,
            sliding_window=32 if self.sliding_window else None,
            fsdp=False,
            param_dtype="float32",
            compute_dtype="float32",
        )


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One benchmark input shape (assigned per-arch in the task spec)."""

    name: str
    kind: str          # "train" | "prefill" | "decode"
    seq_len: int
    global_batch: int

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


TRAIN_4K = ShapeConfig("train_4k", "train", 4096, 256)
PREFILL_32K = ShapeConfig("prefill_32k", "prefill", 32768, 32)
DECODE_32K = ShapeConfig("decode_32k", "decode", 32768, 128)
LONG_500K = ShapeConfig("long_500k", "decode", 524288, 1)

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


def get_shape(name: str) -> ShapeConfig:
    try:
        return SHAPES[name]
    except KeyError:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}"
                       ) from None


def smoke_shape(kind: str = "train") -> ShapeConfig:
    """Tiny shape for CPU smoke tests."""
    if kind == "train":
        return ShapeConfig("smoke_train", "train", 64, 2)
    if kind == "prefill":
        return ShapeConfig("smoke_prefill", "prefill", 64, 2)
    return ShapeConfig("smoke_decode", "decode", 64, 2)
