"""Model assembly: heterogeneous blocks arranged in repeating periods,
scanned with ``lax.scan`` so HLO size is O(period) not O(n_layers).

Three execution modes share one parameter tree:
  * ``lm_forward``     — teacher-forced full sequence (training / scoring)
  * ``lm_prefill``     — forward + KV/SSM cache construction (serving)
  * ``lm_decode_step`` — one token against the cache (serving)

Supports: decoder-only LMs (dense/GQA/MQA, local+global windows, logit
softcaps, MoE FFNs, SSD mixers, hybrid interleaves), encoder-decoder
(seamless: audio-frontend stub -> encoder; decoder w/ cross-attention),
and VLM early fusion (patch-embedding stub prepended to the trunk).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockSpec
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import slotstate
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    apply_mlp, apply_rope, dense_init, embed, init_mlp, init_rms_norm,
    rms_norm, unembed)

# Number of vision patches the VLM frontend stub contributes to the trunk.
VLM_PATCHES = 256

AUX_KEYS = ("moe_lb_loss", "moe_z_loss")


def _shard_batch(x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Pin the batch dim to the DP mesh axes (activation sharding
    constraint at block boundaries — megatron-style batch-sharded,
    d-replicated activations).  No-op when cfg.batch_axes is unset."""
    if not cfg.batch_axes:
        return x
    from jax.sharding import PartitionSpec as P
    axes = cfg.batch_axes[0] if len(cfg.batch_axes) == 1 \
        else tuple(cfg.batch_axes)
    return jax.lax.with_sharding_constraint(
        x, P(axes, *(None for _ in x.shape[1:])))


def _zero_aux() -> Dict[str, jax.Array]:
    return {k: jnp.zeros((), jnp.float32) for k in AUX_KEYS}


def _acc_aux(acc, new):
    out = dict(acc)
    for k, v in new.items():
        out[k] = out[k] + v
    return out


# The configuration's scaling, positions and attention scale are
# trace-time constants: at their defaults (multipliers 1, rotary on,
# scale 1/sqrt(head_dim)) these helpers emit exactly the operations the
# unscaled block always did.

def _embed(params: dict, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    x = embed(params["embed"], tokens)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x.astype(jnp.dtype(cfg.compute_dtype))


def _rope(x: jax.Array, positions: jax.Array, cfg: ArchConfig
          ) -> jax.Array:
    return apply_rope(x, positions, cfg.rope_theta) if cfg.use_rope else x


def _residual(x: jax.Array, y: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x + residual_multiplier * y: one sublayer's residual add."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _final(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """The trunk's output features: final norm, over logits_scaling
    (dividing the features divides the logits the unembedding makes)."""
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    if cfg.logits_scaling != 1.0:
        x = x / jnp.asarray(cfg.logits_scaling, x.dtype)
    return x


def _head(params: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Logits (fp32) from the last block's hidden state."""
    return unembed(unembed_weight(params, cfg), _final(params, x, cfg),
                   softcap=cfg.final_logit_softcap)


def _ffn(p: dict, blk: BlockSpec, x: jax.Array, cfg: ArchConfig
         ) -> Tuple[jax.Array, dict]:
    """The block's FFN sublayer (dense MLP or MoE on the normed input),
    residual included.  Returns (x, aux)."""
    if blk.ffn == "none":
        return x, {}
    with jax.named_scope("mlp" if blk.ffn == "dense" else "moe"):
        h = rms_norm(p["ln_ffn"], x, cfg.norm_eps)
        if blk.ffn == "dense":
            y, aux = apply_mlp(p["mlp"], h, cfg.mlp_variant), {}
        else:
            y, aux = moe_lib.apply_moe(p["moe"], h, cfg)
        return _residual(x, y, cfg), aux


# --------------------------------------------------------------------- #
# Block init / apply
# --------------------------------------------------------------------- #

def init_block(key: jax.Array, cfg: ArchConfig, blk: BlockSpec, dtype
               ) -> dict:
    ks = iter(jax.random.split(key, 8))
    p: dict = {}
    if blk.mixer == "attn":
        p["ln_mix"] = init_rms_norm(cfg.d_model, dtype)
        p["attn"] = attn.init_attention(next(ks), cfg, dtype)
        if blk.cross_attn:
            p["ln_cross"] = init_rms_norm(cfg.d_model, dtype)
            p["cross"] = attn.init_attention(next(ks), cfg, dtype)
    elif blk.mixer == "ssm":
        p["ln_mix"] = init_rms_norm(cfg.d_model, dtype)
        p["ssm"] = ssm_lib.init_ssm(next(ks), cfg, dtype)
    if blk.ffn == "dense":
        p["ln_ffn"] = init_rms_norm(cfg.d_model, dtype)
        p["mlp"] = init_mlp(next(ks), cfg.d_model, cfg.d_ff,
                            cfg.mlp_variant, dtype)
    elif blk.ffn == "moe":
        p["ln_ffn"] = init_rms_norm(cfg.d_model, dtype)
        p["moe"] = moe_lib.init_moe(next(ks), cfg, dtype)
    return p


def _self_attention_train(p, x, cfg: ArchConfig, blk: BlockSpec,
                          causal: bool = True,
                          return_kv: bool = False,
                          k_valid: Optional[jax.Array] = None):
    positions = jnp.arange(x.shape[1])
    q = attn.project_q(p, x)
    k, v = attn.project_kv(p, x)
    q, k = _rope(q, positions, cfg), _rope(k, positions, cfg)
    ka, va = k, v
    if cfg.attn_repeat_kv and cfg.n_kv_heads < cfg.n_heads:
        g = cfg.n_heads // cfg.n_kv_heads
        ka, va = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    if cfg.attn_seq_shard and cfg.batch_axes:
        # context parallelism: queries sharded over 'model' (KV stays
        # full — each shard attends its query slice to all keys); the
        # causal mask is position-computed so SPMD partitions it exactly
        from jax.sharding import PartitionSpec as P
        b_ax = cfg.batch_axes[0] if len(cfg.batch_axes) == 1 \
            else tuple(cfg.batch_axes)
        q = jax.lax.with_sharding_constraint(
            q, P(b_ax, "model", None, None))
    o = attn.attention(q, ka, va, causal=causal, window=blk.window,
                       softcap=cfg.attn_logit_softcap, scale=cfg.attn_scale,
                       chunk=cfg.attn_chunk, k_valid=k_valid)
    if cfg.attn_seq_shard and cfg.batch_axes:
        from jax.sharding import PartitionSpec as P
        b_ax = cfg.batch_axes[0] if len(cfg.batch_axes) == 1 \
            else tuple(cfg.batch_axes)
        o = jax.lax.with_sharding_constraint(
            o, P(b_ax, "model", None, None))
    out = attn.project_out(p, o)
    if return_kv:
        return out, (k, v)
    return out


def apply_block(p: dict, blk: BlockSpec, cfg: ArchConfig, x: jax.Array,
                enc_out: Optional[jax.Array] = None,
                causal: bool = True,
                k_valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, dict]:
    """Full-sequence block (training / scoring).  Returns (x, aux).

    ``k_valid`` (b, s) masks padded key positions in self-attention
    (pooled encoder batches pad frames to a fixed enc_len)."""
    x = _shard_batch(x, cfg)
    with jax.named_scope(blk.mixer):
        if blk.mixer == "attn":
            h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
            x = _residual(x, _self_attention_train(
                p["attn"], h, cfg, blk, causal=causal, k_valid=k_valid), cfg)
            if blk.cross_attn and enc_out is not None:
                h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
                q = attn.project_q(p["cross"], h)
                k, v = attn.project_kv(p["cross"], enc_out)
                o = attn.attention(q, k, v, causal=False, scale=cfg.attn_scale)
                x = _residual(x, attn.project_out(p["cross"], o), cfg)
        elif blk.mixer == "ssm":
            h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
            x = _residual(x, ssm_lib.ssm_forward(p["ssm"], h, cfg), cfg)
    return _ffn(p, blk, x, cfg)


# --------------------------------------------------------------------- #
# Parameter tree
# --------------------------------------------------------------------- #

def init_lm(key: jax.Array, cfg: ArchConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    pattern = cfg.block_pattern()
    n_p = cfg.n_periods
    k_embed, k_unembed, k_layers, k_enc = jax.random.split(key, 4)

    params: dict = {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype,
                            fan_in=cfg.d_model),
        "final_norm": init_rms_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(
            k_unembed, (cfg.d_model, cfg.vocab_size), dtype,
            fan_in=cfg.d_model)

    layer_keys = jax.random.split(k_layers, len(pattern))
    stacked = {}
    for i, blk in enumerate(pattern):
        per_keys = jax.random.split(layer_keys[i], n_p)
        stacked[f"pos{i}"] = jax.vmap(
            lambda k, blk=blk: init_block(k, cfg, blk, dtype))(per_keys)
    params["layers"] = stacked

    if cfg.is_encoder_decoder:
        enc_blk = BlockSpec(mixer="attn", ffn="dense")
        enc_keys = jax.random.split(k_enc, cfg.n_encoder_layers)
        params["encoder"] = {
            "layers": jax.vmap(
                lambda k: init_block(k, cfg, enc_blk, dtype))(enc_keys),
            "final_norm": init_rms_norm(cfg.d_model, dtype),
        }
    return params


def _remat_wrap(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


# --------------------------------------------------------------------- #
# Encoder (enc-dec archs)
# --------------------------------------------------------------------- #

def encode(params: dict, frames: jax.Array, cfg: ArchConfig,
           valid: Optional[jax.Array] = None) -> jax.Array:
    """Bidirectional encoder over frontend embeddings (b, s_src, d).

    ``valid`` (b, s_src) bool masks padded frames out of every
    self-attention (outputs at padded positions are garbage and must be
    masked by the caller)."""
    enc_blk = BlockSpec(mixer="attn", ffn="dense")
    x = frames.astype(jnp.dtype(cfg.compute_dtype))

    def layer_fn(x, layer_params):
        x, _ = apply_block(layer_params, enc_blk, cfg, x, causal=False,
                           k_valid=valid)
        return x, None

    x, _ = jax.lax.scan(_remat_wrap(layer_fn, cfg), x,
                        params["encoder"]["layers"])
    return rms_norm(params["encoder"]["final_norm"], x, cfg.norm_eps)


# --------------------------------------------------------------------- #
# Full-sequence forward (training / scoring)
# --------------------------------------------------------------------- #

def trunk_inputs(params: dict, cfg: ArchConfig, batch: Dict[str, jax.Array]
                 ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Token embeddings (+ modality fusion) and optional encoder output."""
    x = _embed(params, batch["tokens"], cfg)
    enc_out = None
    if cfg.frontend == "vision" and "patches" in batch:
        x = jnp.concatenate(
            [batch["patches"].astype(x.dtype), x], axis=1)
    if cfg.is_encoder_decoder:
        enc_out = encode(params, batch["frames"], cfg)
    return _shard_batch(x.astype(jnp.dtype(cfg.compute_dtype)), cfg), enc_out


def lm_features(params: dict, batch: Dict[str, jax.Array], cfg: ArchConfig
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Trunk output after the final norm (over ``logits_scaling``),
    BEFORE unembedding: (features (b, s_trunk, d) at compute dtype, aux
    losses).

    The training loss consumes features + :func:`unembed_weight` and
    projects to vocab in sequence chunks — materializing the full fp32
    (b, s, vocab) logits costs ~5 GiB/device at 150k vocabs (measured in
    the dry-run before this refactor; see EXPERIMENTS.md §Perf)."""
    pattern = cfg.block_pattern()
    x, enc_out = trunk_inputs(params, cfg, batch)

    def period_fn(carry, period_params):
        x, aux = carry
        for i, blk in enumerate(pattern):
            x, a = apply_block(period_params[f"pos{i}"], blk, cfg, x,
                               enc_out=enc_out)
            aux = _acc_aux(aux, a)
        return (x, aux), None

    (x, aux), _ = jax.lax.scan(_remat_wrap(period_fn, cfg),
                               (x, _zero_aux()), params["layers"])
    return _final(params, x, cfg), aux


def unembed_weight(params: dict, cfg: ArchConfig) -> jax.Array:
    return params["embed"].T if cfg.tie_embeddings else params["unembed"]


def lm_forward(params: dict, batch: Dict[str, jax.Array], cfg: ArchConfig
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Returns (logits (b, s_trunk, vocab) fp32, aux losses)."""
    x, aux = lm_features(params, batch, cfg)
    return unembed(unembed_weight(params, cfg), x,
                   softcap=cfg.final_logit_softcap), aux


# --------------------------------------------------------------------- #
# Serving: cache init / prefill / decode
# --------------------------------------------------------------------- #

def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               enc_len: int = 0) -> dict:
    """Cache pytree; attention capacities honor sliding windows (ring).

    ``cfg.cache_dtype`` (e.g. float8_e4m3fn) stores attention KV at
    reduced precision — decode is weight/KV-read bound, so this is the
    §VII.B serving-precision lever applied to the cache.
    ``cfg.kv_format`` goes further: truly *quantized* KV storage
    (packed fp8/fp4 codes + 1-byte e8m0 block scales; fp4 ≈ 0.53 B/elem
    measured vs 2 B/elem bf16 — the §VI.D read-bandwidth lever), and
    ``cfg.kv_formats`` mixes formats per position-in-period (fp8 global /
    fp4 local layers).  Cross-attention KV is a ring cache of the same
    layout (capacity = enc_len, slot_pos marks valid source positions),
    so it quantizes — and is evicted/cleared — exactly like self-attn KV.
    SSM conv/state stay at compute/fp32 precision (tiny, and the
    recurrence compounds rounding)."""
    dtype = jnp.dtype(cfg.compute_dtype)
    kv_dtype = jnp.dtype(cfg.cache_dtype or cfg.compute_dtype)
    pattern = cfg.block_pattern()
    n_p = cfg.n_periods
    cache: dict = {}
    for i, blk in enumerate(pattern):
        entry: dict = {}
        kv_fmt = cfg.kv_format_for(i)
        if blk.mixer == "attn":
            cap = attn.cache_capacity(max_seq, blk.window)
            kv = attn.init_kv_cache(batch, cap, cfg.n_kv_heads,
                                    cfg.head_dim, kv_dtype,
                                    kv_format=kv_fmt)
            entry["kv"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_p,) + a.shape), kv)
            if blk.cross_attn:
                ckv = attn.init_kv_cache(batch, enc_len, cfg.n_kv_heads,
                                         cfg.head_dim, kv_dtype,
                                         kv_format=kv_fmt)
                entry["cross_kv"] = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (n_p,) + a.shape), ckv)
        elif blk.mixer == "ssm":
            sc = ssm_lib.init_ssm_cache(cfg, batch, dtype)
            entry["ssm"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_p,) + a.shape), sc)
        cache[f"pos{i}"] = entry
    if cfg.is_encoder_decoder:
        cache["enc_out"] = jnp.zeros((batch, enc_len, cfg.d_model), dtype)
    return cache


def kv_cache_stats(cache: dict, cfg: ArchConfig) -> dict:
    """*Measured* attention-KV storage accounting over a cache pytree.

    Walks the ``pos*`` entries' ``kv`` AND ``cross_kv`` ring caches (SSM
    state and the int32 ``slot_pos`` bookkeeping are excluded — they are
    format-independent) and reports ``sum(arr.nbytes)`` over what is
    actually stored, the number the Tab VIII / long-context artifacts
    quote:

      * ``kv_bytes``        — total stored K/V payload (codes + scales),
        self- and cross-attention combined,
      * ``cross_kv_bytes``  — the cross-attention share of ``kv_bytes``
        (0 for decoder-only archs),
      * ``bytes_per_elem``  — payload / logical K,V element count (fp4 +
        e8m0 byte scales ≈ 0.53 at head_dim 128; 2.0 for bf16),
      * ``bytes_per_token`` — HBM bytes one cached *decoder* token
        position costs across the layer stack (what each decoded token
        reads per position of context, and writes once; cross-KV is
        per-source-position, not per-decoded-token, so it is reported
        in ``cross_kv_bytes`` instead),
      * ``per_layer``       — {pos name: {format, bytes_per_elem}}
        measured per position-in-period (mixed ``kv_formats`` show
        their different widths here).
    """
    kv_bytes, cross_bytes, elems, per_token = 0, 0, 0, 0.0
    per_layer: dict = {}
    for name, entry in cache.items():
        if not name.startswith("pos"):
            continue
        i = int(name[3:])
        for part in ("kv", "cross_kv"):
            if part not in entry:
                continue
            kv = entry[part]
            n_p, b, cap = kv["slot_pos"].shape
            payload = sum(v.nbytes for k2, v in kv.items()
                          if k2 != "slot_pos")
            part_elems = 2 * n_p * b * cap * cfg.n_kv_heads * cfg.head_dim
            kv_bytes += payload
            elems += part_elems
            if part == "kv":
                per_token += payload / (b * cap)
            else:
                cross_bytes += payload
            key = name if part == "kv" else f"{name}.cross"
            per_layer[key] = {
                "format": cfg.kv_format_for(i)
                or (cfg.cache_dtype or cfg.compute_dtype),
                "bytes_per_elem": payload / part_elems,
            }
    return {"kv_format": cfg.kv_format or (cfg.cache_dtype
                                           or cfg.compute_dtype),
            "kv_bytes": int(kv_bytes),
            "cross_kv_bytes": int(cross_bytes),
            "bytes_per_elem": kv_bytes / elems if elems else 0.0,
            "bytes_per_token": per_token,
            "per_layer": per_layer}


def lm_prefill(params: dict, batch: Dict[str, jax.Array], cfg: ArchConfig,
               max_seq: int) -> Tuple[jax.Array, dict]:
    """Forward over the prompt, building the cache.  Returns
    (last-position logits (b, vocab), cache)."""
    pattern = cfg.block_pattern()
    x, enc_out = trunk_inputs(params, cfg, batch)
    s = x.shape[1]
    cache = init_cache(cfg, x.shape[0], max_seq,
                       enc_len=enc_out.shape[1] if enc_out is not None else 0)

    def period_fn(carry, period_params):
        x, aux = carry
        new_entries = {}
        for i, blk in enumerate(pattern):
            x = _shard_batch(x, cfg)
            p = period_params[f"pos{i}"]
            entry = {}
            kv_fmt = cfg.kv_format_for(i)
            with jax.named_scope(blk.mixer):
                if blk.mixer == "attn":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    out, (k, v) = _self_attention_train(
                        p["attn"], h, cfg, blk, return_kv=True)
                    x = _residual(x, out, cfg)
                    cap = attn.cache_capacity(max_seq, blk.window)
                    kv0 = attn.init_kv_cache(x.shape[0], cap, cfg.n_kv_heads,
                                             cfg.head_dim, k.dtype,
                                             kv_format=kv_fmt)
                    entry["kv"] = attn.cache_write_prefill(kv0, k, v,
                                                           kv_format=kv_fmt)
                    if blk.cross_attn and enc_out is not None:
                        h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
                        q = attn.project_q(p["cross"], h)
                        ck, cv = attn.project_kv(p["cross"], enc_out)
                        # cross-KV is a ring cache like self-attn KV:
                        # quantize-on-write (kv_fmt), slot_pos = source
                        # positions; the prompt attends the CACHED view so
                        # prefill, chunked prefill, and decode all read the
                        # same (possibly dequantized) cross keys
                        ckv0 = attn.init_kv_cache(
                            x.shape[0], enc_out.shape[1], cfg.n_kv_heads,
                            cfg.head_dim, k.dtype, kv_format=kv_fmt)
                        ckv = attn.cache_write_prefill(ckv0, ck, cv,
                                                       kv_format=kv_fmt)
                        kc, vc = attn.cache_kv(ckv, kv_fmt, cfg.head_dim,
                                               out_dtype=x.dtype)
                        o = attn.attention(q, kc, vc, causal=False,
                                           scale=cfg.attn_scale)
                        x = _residual(x, attn.project_out(p["cross"], o), cfg)
                        entry["cross_kv"] = ckv
                elif blk.mixer == "ssm":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    out, ssm_cache = ssm_lib.ssm_forward(
                        p["ssm"], h, cfg, return_state=True)
                    x = _residual(x, out, cfg)
                    entry["ssm"] = ssm_cache
            x, a = _ffn(p, blk, x, cfg)
            aux = _acc_aux(aux, a)
            new_entries[f"pos{i}"] = entry
        return (x, aux), new_entries

    (x, _), per_period = jax.lax.scan(period_fn, (x, _zero_aux()),
                                      params["layers"])
    for key in per_period:
        cache[key] = per_period[key]
    if enc_out is not None:
        cache["enc_out"] = enc_out
    return _head(params, x[:, -1:], cfg)[:, 0], cache


def lm_decode_step(params: dict, cache: dict, token: jax.Array,
                   pos: jax.Array, cfg: ArchConfig,
                   active: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, dict]:
    """One decode step.  token: (b,) int32; pos: (b,) int32 per-row
    position of the *incoming* token (rows advance independently under
    continuous batching; pass a broadcast scalar for lockstep decode).
    Returns (logits (b, vocab), updated cache).

    The period-stacked pool is carried through the layer scan, not
    scanned over: period ``l`` reads its parts at ``pool[l]`` (the
    compiler fuses the read into attention and the recurrence) and
    writes only what changed — one ring-KV row per slot at ``[l, row,
    pos % cap]``, the recurrent state's new value at ``[l]``.  Inside
    the fused decode loop, whose jit donates the pool, the whole step
    therefore updates the pool in place: no layer or pool is copied.

    ``active`` (optional (b,) bool) masks *all* cache mutation through
    the slot-state protocol (``repro.models.slotstate.decode_advance``):
    ring KV is masked at the write site, cross-KV/enc_out are read-only,
    and every recurrent part (SSM conv/state) row-selects new-vs-old —
    one predicate, no per-mixer special cases.  That is what makes this
    step scan-compatible inside the fused multi-token decode loop for
    EVERY arch family: finished pool slots ride along at zero state cost
    (their logits are computed but garbage, and the caller masks their
    samples)."""
    pattern = cfg.block_pattern()
    x = _embed(params, token[:, None], cfg)           # (b, 1, d)
    enc_out = cache.get("enc_out")
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, token.shape)
    positions = pos[:, None]                          # (b, 1)

    def period_fn(carry, scanned):
        x, pool = carry
        period_params, l = scanned
        new_pool = {}
        for i, blk in enumerate(pattern):
            p = period_params[f"pos{i}"]
            c = pool[f"pos{i}"]
            kv_fmt = cfg.kv_format_for(i)
            new_parts = {}
            with jax.named_scope(blk.mixer):
                if blk.mixer == "attn":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    q = attn.project_q(p["attn"], h)
                    k, v = attn.project_kv(p["attn"], h)
                    q, k = _rope(q, positions, cfg), _rope(k, positions, cfg)
                    new_parts["kv"] = attn.cache_write_decode(
                        c["kv"], k, v, pos, kv_format=kv_fmt, active=active,
                        layer=l)
                    kv = slotstate.take_layer(new_parts["kv"], l)
                    kc, vc = attn.cache_kv(kv, kv_fmt, cfg.head_dim,
                                           out_dtype=x.dtype)
                    o = attn.decode_attention(
                        q, kc, vc, kv["slot_pos"], pos, window=blk.window,
                        softcap=cfg.attn_logit_softcap, scale=cfg.attn_scale)
                    x = _residual(x, attn.project_out(p["attn"], o), cfg)
                    if blk.cross_attn and "cross_kv" in c:
                        h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
                        q = attn.project_q(p["cross"], h)
                        ckv = slotstate.take_layer(c["cross_kv"], l)
                        ck, cv = attn.cache_kv(ckv, kv_fmt, cfg.head_dim,
                                               out_dtype=x.dtype)
                        # every valid source slot is visible (slot_pos >= 0
                        # masks padding); a huge query position makes the
                        # causal comparison vacuous
                        o = attn.cache_attention(
                            q, ck, cv, ckv["slot_pos"],
                            jnp.full_like(positions, jnp.int32(2 ** 30)),
                            scale=cfg.attn_scale)
                        x = _residual(x, attn.project_out(p["cross"], o), cfg)
                        new_parts["cross_kv"] = c["cross_kv"]
                elif blk.mixer == "ssm":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    out, new_parts["ssm"] = ssm_lib.ssm_decode(
                        p["ssm"], h, slotstate.take_layer(c["ssm"], l), cfg)
                    x = _residual(x, out, cfg)
            entry = {part: slotstate.decode_advance(active, part, new,
                                                    c[part], l)
                     for part, new in new_parts.items()}
            x, _ = _ffn(p, blk, x, cfg)
            new_pool[f"pos{i}"] = entry
        return (x, new_pool), None

    layer_cache = {k: v for k, v in cache.items() if k.startswith("pos")}
    (x, new_layer_cache), _ = jax.lax.scan(
        period_fn, (x, layer_cache),
        (params["layers"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    out_cache = dict(new_layer_cache)
    if enc_out is not None:
        out_cache["enc_out"] = enc_out
    return _head(params, x, cfg)[:, 0], out_cache


# --------------------------------------------------------------------- #
# Chunked pooled prefill (serving admission without host scatter)
# --------------------------------------------------------------------- #

def supports_chunked_prefill(cfg: ArchConfig) -> bool:
    """Always true: the slot-state protocol gives every arch family a
    chunked-prefill leg — attention writes the chunk's ring region, SSM
    carries conv/state across chunk boundaries
    (:func:`repro.models.ssm.ssm_prefill_chunk`), enc-dec encodes once
    into slot-resident enc_out/cross-KV (:func:`lm_encode_slot`) and
    chunks the decoder prompt, and VLM chunks the patch-embedding prefix
    through the same executable (``embeds=``).  Kept as a function for
    API compatibility with the pre-protocol engine."""
    return True


def min_cache_capacity(cfg: ArchConfig, max_seq: int) -> int:
    """Smallest per-layer ring capacity (local windows shrink it) — the
    upper bound on a prefill chunk (chunk slots must be distinct)."""
    caps = [attn.cache_capacity(max_seq, b.window)
            for b in cfg.block_pattern() if b.mixer == "attn"]
    return min(caps) if caps else max_seq


def clear_slot(cache: dict, slot: jax.Array) -> dict:
    """Evict pool row ``slot`` under the slot-state protocol: ring parts
    (self- AND cross-attn KV) mark their entries empty (slot_pos = -1;
    payload bytes stay — position masking makes them unreachable), every
    other part zeroes the slot row.  Runs jitted with ``slot`` traced
    (one executable serves every slot).  See ``repro.models.slotstate``."""
    return slotstate.clear_slot(cache, slot)


def lm_prefill_chunk(params: dict, cache: dict, tokens: jax.Array,
                     slot: jax.Array, pos_offset: jax.Array,
                     valid_len: jax.Array, cfg: ArchConfig,
                     embeds: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, dict]:
    """Prefill one prompt *chunk* for pool row ``slot`` directly into the
    shared serving cache — the chunked pooled-prefill step, for every
    arch family via the slot-state protocol.

    tokens: (chunk,) int32, zero-padded past ``valid_len``;
    pos_offset: scalar int32 absolute trunk position of tokens[0];
    valid_len: scalar int32 number of real tokens in this chunk;
    embeds: optional (1, chunk, d_model) — when given, the chunk's trunk
    inputs are these precomputed embeddings instead of token lookups
    (the VLM patch prefix streams through the SAME chunk machinery; the
    engine keeps it a separate jitted executable so each stays
    compiled-exactly-once).
    slot/pos_offset/valid_len are traced, so ceil(prompt/chunk)
    dispatches of ONE compiled executable admit any prompt — no
    host-side cache pytree rematerialization, no recompilation per
    prompt length.

    Per mixer (one ``valid`` predicate drives every write):
      * attention writes the chunk's K/V (quantize-on-write under the
        position's kv format) into the slot's ring region and attends
        the chunk queries against history + itself via position masking;
      * SSM carries conv/ssm state across chunk boundaries
        (:func:`repro.models.ssm.ssm_prefill_chunk`);
      * cross-attention reads the slot's cross-KV written once by
        :func:`lm_encode_slot` (read-only here, like decode).

    The period-stacked pool is carried through the layer scan, as in
    :func:`lm_decode_step`: period ``l`` reads the slot's row at
    ``pool[l, slot]`` and writes back only what the chunk changed — its
    ``s`` ring entries at ``[l, slot, positions % cap]``, the recurrent
    state's row at ``[l, slot]``.  Under the engine's jit, which donates
    the pool, a chunk therefore updates the pool in place: no layer or
    pool is copied.

    Returns (logits (1, vocab) at the last valid position, updated
    cache).
    """
    pattern = cfg.block_pattern()
    s = tokens.shape[0]
    if embeds is not None:
        x = embeds.astype(jnp.dtype(cfg.compute_dtype))
    else:
        x = _embed(params, tokens[None, :], cfg)          # (1, s, d)
    positions = pos_offset + jnp.arange(s, dtype=jnp.int32)   # (s,)
    valid = jnp.arange(s) < valid_len

    def period_fn(carry, scanned):
        x, pool = carry
        period_params, l = scanned
        new_pool = {}
        for i, blk in enumerate(pattern):
            p = period_params[f"pos{i}"]
            c = pool[f"pos{i}"]
            kv_fmt = cfg.kv_format_for(i)
            entry = dict(c)
            with jax.named_scope(blk.mixer):
                if blk.mixer == "attn":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    q = attn.project_q(p["attn"], h)
                    k, v = attn.project_kv(p["attn"], h)
                    q = _rope(q, positions[None, :], cfg)
                    k = _rope(k, positions[None, :], cfg)
                    kv_row = slotstate.take_slot(c["kv"], l, slot)
                    if attn.is_quantized_cache(kv_row):
                        # packed codes unpack in a layout of their own:
                        # sliced out first, the row is relaid, where a
                        # slice fused into the unpack relays the pool
                        kv_row = jax.lax.optimization_barrier(kv_row)
                    # Attend against the PRE-write history concatenated with
                    # the chunk's own raw K/V.  Writing first and attending
                    # over the ring would be wrong once a chunk wraps a
                    # sliding-window ring (capacity == window): the chunk's
                    # later writes evict positions still inside its earlier
                    # queries' windows.  The concat view keeps every position
                    # the full-prefill oracle sees — history from the cache,
                    # intra-chunk causality via the position mask — and
                    # matches lm_prefill in using the chunk's unquantized K/V
                    # for its own queries.
                    kc, vc = attn.cache_kv(kv_row, kv_fmt, cfg.head_dim,
                                           out_dtype=x.dtype)
                    chunk_sp = jnp.where(valid, positions, -1)[None, :]
                    o = attn.cache_attention(
                        q,
                        jnp.concatenate([kc, k.astype(kc.dtype)], axis=1),
                        jnp.concatenate([vc, v.astype(vc.dtype)], axis=1),
                        jnp.concatenate([kv_row["slot_pos"], chunk_sp],
                                        axis=1),
                        positions[None, :], window=blk.window,
                        softcap=cfg.attn_logit_softcap, scale=cfg.attn_scale)
                    x = _residual(x, attn.project_out(p["attn"], o), cfg)
                    entry["kv"] = attn.cache_write_chunk(
                        c["kv"], k, v, positions, valid, kv_format=kv_fmt,
                        layer=l, slot=slot)
                    if blk.cross_attn and "cross_kv" in c:
                        h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
                        q = attn.project_q(p["cross"], h)
                        ckv_row = slotstate.take_slot(c["cross_kv"], l, slot)
                        ck, cv = attn.cache_kv(ckv_row, kv_fmt, cfg.head_dim,
                                               out_dtype=x.dtype)
                        o = attn.cache_attention(
                            q, ck, cv, ckv_row["slot_pos"],
                            jnp.full_like(positions, jnp.int32(2 ** 30))[
                                None, :], scale=cfg.attn_scale)
                        x = _residual(x, attn.project_out(p["cross"], o), cfg)
                elif blk.mixer == "ssm":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    # the row is sliced out once, whole: with the slice
                    # fused into its two readers, the compiler staged a
                    # small stack (granite's) through VMEM and back
                    ssm_row = jax.lax.optimization_barrier(
                        slotstate.take_slot(c["ssm"], l, slot))
                    out, ssm_row = ssm_lib.ssm_prefill_chunk(
                        p["ssm"], h, ssm_row, cfg, valid, valid_len)
                    x = _residual(x, out, cfg)
                    entry["ssm"] = slotstate.put_slot(c["ssm"], ssm_row, l,
                                                      slot)
            x, _ = _ffn(p, blk, x, cfg)
            new_pool[f"pos{i}"] = entry
        return (x, new_pool), None

    layer_cache = {k: v for k, v in cache.items() if k.startswith("pos")}
    (x, new_layer_cache), _ = jax.lax.scan(
        period_fn, (x, layer_cache),
        (params["layers"], jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    x_last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
    logits = _head(params, x_last, cfg)[:, 0]
    out_cache = dict(new_layer_cache)
    if "enc_out" in cache:
        out_cache["enc_out"] = cache["enc_out"]          # read-only
    return logits, out_cache


def lm_verify_chunk(params: dict, cache: dict, tokens: jax.Array,
                    positions: jax.Array, cfg: ArchConfig
                    ) -> Tuple[jax.Array, dict]:
    """Speculative verify: forward ``s`` tentative tokens per pool row in
    ONE batched pass, producing logits BIT-IDENTICAL to ``s`` successive
    :func:`lm_decode_step` calls — without writing the cache.

    tokens: (b, s) int32 — row r is [last committed token, draft_1, ...,
    draft_{s-1}]; positions: (b, s) int32 — the absolute position of each
    incoming token (``pos[r] + j``; rows advance independently).  Returns
    (logits (b, s, vocab) fp32, ``info``): logits row j is the
    next-token distribution after consuming tokens[:, :j+1], and ``info``
    is the period-stacked commit payload :func:`lm_commit_chunk` consumes
    (attention: the chunk's post-rope raw K/V; SSM: discretized inputs +
    conv streams).

    Exactness per mixer (the differential conformance suite pins this):

      * attention queries attend the CONCAT of the pre-block cache view
        and the chunk's own roundtripped K/V (quantize->dequantize under
        the position's kv format — exactly the values decode reads back
        after its quantize-on-write; dense caches cast to the storage
        dtype).  The visible set matches decode at every step: a ring
        overwrite during the block evicts an entry exactly when it
        leaves the window (capacity == window), and the window mask
        hides that entry from precisely the queries whose step would
        have run post-overwrite.
      * SSM runs the decode recurrence sequentially
        (:func:`repro.models.ssm.ssm_verify_chunk`), read-only.
      * cross-attention / enc_out are read-only in decode already.

    Inactive rows produce garbage logits (their tokens are held
    constant); the engine masks them at acceptance time, exactly like
    the non-speculative loop masks its samples.
    """
    pattern = cfg.block_pattern()
    b, s = tokens.shape
    x = _embed(params, tokens, cfg)                   # (b, s, d)
    enc_out = cache.get("enc_out")

    def period_fn(x, scanned):
        period_params, period_cache = scanned
        info = {}
        for i, blk in enumerate(pattern):
            p = period_params[f"pos{i}"]
            c = period_cache[f"pos{i}"]
            kv_fmt = cfg.kv_format_for(i)
            leg: dict = {}
            with jax.named_scope(blk.mixer):
                if blk.mixer == "attn":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    q = attn.project_q(p["attn"], h)
                    k, v = attn.project_kv(p["attn"], h)
                    q, k = _rope(q, positions, cfg), _rope(k, positions, cfg)
                    kc, vc = attn.cache_kv(c["kv"], kv_fmt, cfg.head_dim,
                                           out_dtype=x.dtype)
                    if attn.is_quantized_cache(c["kv"]):
                        # the chunk's own entries must be what decode READS
                        # after its quantize-on-write, not the raw values
                        kd = attn.dequantize_kv(*attn.quantize_kv(k, kv_fmt),
                                                kv_fmt, cfg.head_dim,
                                                out_dtype=x.dtype)
                        vd = attn.dequantize_kv(*attn.quantize_kv(v, kv_fmt),
                                                kv_fmt, cfg.head_dim,
                                                out_dtype=x.dtype)
                    else:
                        kd, vd = k.astype(kc.dtype), v.astype(vc.dtype)
                    o = attn.cache_attention(
                        q,
                        jnp.concatenate([kc, kd], axis=1),
                        jnp.concatenate([vc, vd], axis=1),
                        jnp.concatenate([c["kv"]["slot_pos"],
                                         positions.astype(jnp.int32)], axis=1),
                        positions, window=blk.window,
                        softcap=cfg.attn_logit_softcap, scale=cfg.attn_scale)
                    x = _residual(x, attn.project_out(p["attn"], o), cfg)
                    leg["kv"] = {"k": k, "v": v}
                    if blk.cross_attn and "cross_kv" in c:
                        h = rms_norm(p["ln_cross"], x, cfg.norm_eps)
                        q = attn.project_q(p["cross"], h)
                        ck, cv = attn.cache_kv(c["cross_kv"], kv_fmt,
                                               cfg.head_dim, out_dtype=x.dtype)
                        o = attn.cache_attention(
                            q, ck, cv, c["cross_kv"]["slot_pos"],
                            jnp.full_like(positions, jnp.int32(2 ** 30)),
                            scale=cfg.attn_scale)
                        x = _residual(x, attn.project_out(p["cross"], o), cfg)
                elif blk.mixer == "ssm":
                    h = rms_norm(p["ln_mix"], x, cfg.norm_eps)
                    out, leg["ssm"] = ssm_lib.ssm_verify_chunk(p["ssm"], h,
                                                               c["ssm"], cfg)
                    x = _residual(x, out, cfg)
            x, _ = _ffn(p, blk, x, cfg)
            info[f"pos{i}"] = leg
        return x, info

    layer_cache = {k: v for k, v in cache.items() if k.startswith("pos")}
    x, info = jax.lax.scan(period_fn, x, (params["layers"], layer_cache))
    return _head(params, x, cfg), info


def lm_commit_chunk(cache: dict, info: dict, positions: jax.Array,
                    e: jax.Array, cfg: ArchConfig) -> dict:
    """Commit the first ``e`` verified positions per row into the serving
    cache — the write half :func:`lm_verify_chunk` deferred.

    positions: (b, s) as passed to verify; e: (b,) int32 accepted counts
    in [0, s] (0 for inactive/rejected-at-once rows — every write is a
    no-op there, which is what lets one executable serve all rows
    uniformly).  Attention commits through the SAME quantize-on-write
    path as decode (:func:`repro.models.attention.cache_write_rows`);
    SSM re-materializes state from the pre-block checkpoint with the
    rejected tail identity-masked
    (:func:`repro.models.ssm.ssm_commit_chunk`); cross-KV / enc_out are
    read-only.  Needs no parameters: verify's ``info`` already carries
    the post-rope K/V and discretized SSM inputs.
    """
    pattern = cfg.block_pattern()
    b, s = positions.shape
    valid = jnp.arange(s)[None, :] < e[:, None]          # (b, s)

    def period_fn(carry, scanned):
        period_cache, period_info = scanned
        new_cache = {}
        for i, blk in enumerate(pattern):
            c = period_cache[f"pos{i}"]
            leg = period_info[f"pos{i}"]
            entry = dict(c)
            if blk.mixer == "attn":
                entry["kv"] = attn.cache_write_rows(
                    c["kv"], leg["kv"]["k"], leg["kv"]["v"], positions,
                    valid, kv_format=cfg.kv_format_for(i))
            elif blk.mixer == "ssm":
                new_ssm = ssm_lib.ssm_commit_chunk(c["ssm"], leg["ssm"],
                                                   e, cfg)
                entry["ssm"] = slotstate.masked_tree(e > 0, new_ssm,
                                                     c["ssm"])
            new_cache[f"pos{i}"] = entry
        return carry, new_cache

    layer_cache = {k: v for k, v in cache.items() if k.startswith("pos")}
    _, new_layer_cache = jax.lax.scan(period_fn, 0.0, (layer_cache, info))
    out_cache = dict(new_layer_cache)
    if "enc_out" in cache:
        out_cache["enc_out"] = cache["enc_out"]
    return out_cache


def lm_rollback_chunk(cache: dict, positions: jax.Array,
                      reject: jax.Array) -> dict:
    """Invalidate speculative ring-cache writes at ``positions`` (b, s)
    where ``reject`` (b, s) — a slot_pos pointer move per self-attention
    layer (:func:`repro.models.attention.cache_rollback`), applied
    directly on the period-stacked leaves.  Cross-KV and recurrent parts
    are untouched: cross-KV is never speculatively written, and SSM
    state is committed-not-written (see :func:`lm_commit_chunk`).  Used
    on the DRAFT model's cache, whose drafting decode steps write
    eagerly and must un-write the rejected tail."""
    out: dict = {}
    for name, entry in cache.items():
        if not (name.startswith("pos") and isinstance(entry, dict)):
            out[name] = entry
            continue
        e = dict(entry)
        if "kv" in e:
            e["kv"] = attn.cache_rollback(e["kv"], positions, reject)
        out[name] = e
    return out


def lm_encode_slot(params: dict, cache: dict, frames: jax.Array,
                   slot: jax.Array, src_len: jax.Array, cfg: ArchConfig
                   ) -> dict:
    """Run the encoder ONCE for pool row ``slot`` and write the results
    slot-resident: ``enc_out`` row + every decoder layer's cross-KV ring
    row (quantize-on-write under the position's kv format, slot_pos =
    source positions, padding stays -1).  The decoder prompt then streams
    through :func:`lm_prefill_chunk` and decode reads the same cached
    cross view — encode-once, chunk-the-rest.

    frames: (1, enc_len, d_model) frontend embeddings padded to the
    pool's fixed enc_len; src_len: traced scalar int32 count of real
    frames.  ``slot``/``src_len`` traced — one compiled executable
    admits every request.
    """
    enc_len = frames.shape[1]
    valid = (jnp.arange(enc_len) < src_len)[None, :]      # (1, enc_len)
    enc = encode(params, frames, cfg, valid=valid)
    # padded encoder positions are garbage — zero them so the stored
    # enc_out row is clean (cross-attention masks them via slot_pos
    # anyway; this keeps the top-level leaf inspectable)
    enc = jnp.where(valid[..., None], enc, 0.0).astype(enc.dtype)
    positions = jnp.arange(enc_len, dtype=jnp.int32)
    pattern = cfg.block_pattern()

    def period_fn(carry, scanned):
        period_params, period_cache = scanned
        new_cross = {}
        for i, blk in enumerate(pattern):
            entry = {}
            if blk.cross_attn and "cross_kv" in period_cache[f"pos{i}"]:
                p = period_params[f"pos{i}"]
                c = period_cache[f"pos{i}"]
                ck, cv = attn.project_kv(p["cross"], enc)
                ckv_row = slotstate.take_row(c["cross_kv"], slot)
                ckv_row = attn.cache_write_chunk(
                    ckv_row, ck, cv, positions, valid[0],
                    kv_format=cfg.kv_format_for(i))
                entry["cross_kv"] = slotstate.put_row(
                    c["cross_kv"], ckv_row, slot)
            new_cross[f"pos{i}"] = entry
        return carry, new_cross

    layer_cache = {k: v for k, v in cache.items() if k.startswith("pos")}
    _, new_cross = jax.lax.scan(
        period_fn, 0.0, (params["layers"], layer_cache))
    out = dict(cache)
    for name, entry in new_cross.items():
        out[name] = {**cache[name], **entry}
    out["enc_out"] = jax.lax.dynamic_update_slice_in_dim(
        cache["enc_out"], enc.astype(cache["enc_out"].dtype), slot, 0)
    return out
